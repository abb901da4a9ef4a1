"""Model step: of the earlier positions the full layers' queries could have
attended, the share they did attend: the rise of ``dsa_keys_attended_total``
over the rise of ``dsa_keys_in_history_total`` between the window's scrapes,
in percent. The engine counts both on the device beside the expert counters,
over prefill and decode alike: a query at position p of a layer that selects
adds ``min(p + 1, index_topk)`` and ``p + 1``. 100 means the selection is
bypassed (no history passed ``index_topk``); about 40 with prompts of 7,500
and 2,048 kept. An engine whose model selects nothing (the counters absent,
or at rest) reads nothing."""
from layer_metrics.prefill_decode_wait_share import delta

ATTENDED = "quorum_tpu_engine_dsa_keys_attended_total"
HISTORY = "quorum_tpu_engine_dsa_keys_in_history_total"


def read(art):
    attended, history = delta(art, ATTENDED), delta(art, HISTORY)
    if attended is None or not history or history <= 0:
        return None
    return 100.0 * attended / history
