"""Model step: of the rows' recurrent states a decode step read and wrote
back (every row of the cache, each step and layer), the share that belonged
to a row decoding in that step: the rise of ``ssm_state_rows_live_total``
over the rise of ``ssm_state_rows_stepped_total`` between the window's
scrapes, in percent. The engine counts both on the host where it dispatches a
decode chunk, from the rows it dispatched and their remaining budgets. Under
100 the step moves 8.4 MB a layer for each dead row to no end: what a step
that skips dead rows' states would save. An engine without the counters, or
one that dispatched no chunk, reads nothing."""
from layer_metrics.prefill_decode_wait_share import delta

LIVE = "quorum_tpu_engine_ssm_state_rows_live_total"
STEPPED = "quorum_tpu_engine_ssm_state_rows_stepped_total"


def read(art):
    live, stepped = delta(art, LIVE), delta(art, STEPPED)
    if live is None or not stepped or stepped <= 0:
        return None
    return 100.0 * live / stepped
