"""Model step: picks on a held expert that no product computed, between the
window's scrapes: the rise of ``moe_dropped_picks_total``, which the engine
counts on the device as held picks less the rows its grouped products say
they took. Expected 0, as ``window_compiles`` is: a served answer that drops
a pick is a different answer. A program without the counter reads nothing."""
from layer_metrics.prefill_decode_wait_share import delta
from layer_metrics.expert_picks_held_share import PICKS


def read(art):
    dropped, picks = delta(art, "quorum_tpu_engine_moe_dropped_picks_total"), \
        delta(art, PICKS)
    if dropped is None or not picks or picks <= 0:
        return None  # no router ran: nothing could have been dropped or kept
    return float(dropped)
