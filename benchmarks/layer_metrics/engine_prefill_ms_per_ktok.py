"""Scheduler and admission: device time of the prefill programs (admit,
member admit, segments) over the whole window per thousand tokens they
computed, as padded: the rise of ``device_prefill_seconds_total`` (the
engine's device ledger) over the rise of ``prefill_padded_tokens_total``
between the window's scrapes. ``prefill_ms_per_ktok`` reads the last 2 s's
profile and takes its tokens from the programs' text."""
from layer_metrics.prefill_decode_wait_share import delta

SECONDS = "quorum_tpu_engine_device_prefill_seconds_total"
TOKENS = "quorum_tpu_engine_prefill_padded_tokens_total"


def read(art):
    seconds, tokens = delta(art, SECONDS), delta(art, TOKENS)
    if seconds is None or not tokens or tokens <= 0:
        return None
    return 1e6 * seconds / tokens
