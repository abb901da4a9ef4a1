"""Scheduler and admission: host time the scheduler loop spends per decode
chunk outside its waits: the ``turn_<phase>_seconds_total`` of every phase
but ``idle`` (nothing to do) and ``reap_block`` (waiting for the device),
over ``decode_chunks_total``, between the window's scrapes."""
from layer_metrics.prefill_decode_wait_share import delta

PHASES = ("sweep", "admit", "fill", "emit", "compile")


def read(art):
    parts = [delta(art, f"quorum_tpu_engine_turn_{p}_seconds_total")
             for p in PHASES]
    chunks = delta(art, "quorum_tpu_engine_decode_chunks_total")
    if None in parts or not chunks or chunks <= 0:
        return None
    return 1000.0 * sum(parts) / chunks
