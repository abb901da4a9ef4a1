"""Scheduler and admission: of the time the scheduler loop had work, the
share the host held the device up, nothing dispatched to it and not yet
landed, by the engine's device ledger: the rise of
``device_starved_seconds_total`` (summed over the phases of the scheduler's
turn that it is booked to) over the rise of the ledger's four accounts
together (decode, prefill, other, starved; the loop waiting with no request
is ``device_idle_seconds_total``, in neither), in %. A lower bound of the
device's idle time, on the host's clock: between a dispatch call's return
and the program's start, and between its end and the waiting thread's
wake-up, the device is dry and the ledger books busy. ``device_idle_share``
reads the device's own number off the last 2 s's profile; the phases are on
``/metrics``."""
from layer_metrics.engine_decode_step_ms import device_seconds
from layer_metrics.prefill_decode_wait_share import delta

STARVED = "quorum_tpu_engine_device_starved_seconds_total"


def read(art):
    total = device_seconds(art)
    if not total or total <= 0:
        return None
    return 100.0 * delta(art, STARVED) / total
