"""Model step: of the picks the routers made, the share that fell on experts
held here: the rise of ``moe_picks_held_total`` over the rise of
``moe_picks_total`` between the window's scrapes, in percent. 12.5 with 16 of
128 held under even routing: above it this chip's experts do more than an
eighth of the layer's work. An engine that counts no picks (a spec without a
pattern, a program without the counters) reads nothing."""
from layer_metrics.prefill_decode_wait_share import delta

PICKS = "quorum_tpu_engine_moe_picks_total"
HELD = "quorum_tpu_engine_moe_picks_held_total"


def read(art):
    picks, held = delta(art, PICKS), delta(art, HELD)
    if held is None or not picks or picks <= 0:
        return None
    return 100.0 * held / picks
