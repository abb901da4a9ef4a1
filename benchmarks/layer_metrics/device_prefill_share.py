"""Scheduler and admission: the share of the window the device spent on
prefill programs, by the engine's device ledger: the rise of
``device_prefill_seconds_total`` over the rise of its four accounts
together (decode, prefill, other, starved: the scheduler loop's wall clock
while it had work), in %. What a decode row's token waits for beside its
own step."""
from layer_metrics.engine_decode_step_ms import device_seconds
from layer_metrics.prefill_decode_wait_share import delta

PREFILL = "quorum_tpu_engine_device_prefill_seconds_total"


def read(art):
    total = device_seconds(art)
    if not total or total <= 0:
        return None
    return 100.0 * delta(art, PREFILL) / total
