"""Scheduler and admission: the share of prefill work that is padding: one
minus prompt tokens asked for (``prefill_tokens_total``) over tokens as the
admit, member-admit and segment programs compute them, rows x bucket
(``prefill_padded_tokens_total``), between the window's scrapes, in percent."""
from layer_metrics.prefill_decode_wait_share import delta


def read(art):
    asked = delta(art, "quorum_tpu_engine_prefill_tokens_total")
    padded = delta(art, "quorum_tpu_engine_prefill_padded_tokens_total")
    if asked is None or not padded or padded <= 0:
        return None
    return 100.0 * (1.0 - asked / padded)
