"""Scheduler and admission: stalls the engine witnessed inside the window:
the rise of ``stalls_total`` between the window's scrapes: a blocking wait
on a landing, or a stretch the device stood dry with the loop neither idle
nor compiling, of over 2 s and ten times the family's booked median.
Expected 0; the engine logs each and dumps its flight recorder."""
from layer_metrics.prefill_decode_wait_share import delta

STALLS = "quorum_tpu_engine_stalls_total"


def read(art):
    return delta(art, STALLS)
