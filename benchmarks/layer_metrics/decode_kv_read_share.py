"""Kernels: of the tiles (512 positions of one row) that reading every row of
the decode batch to the shared history bucket would fetch, the share the
dense decode step's read of the cache fetches: the rise of
``decode_kv_tiles_read_total`` over the rise of
``decode_kv_tiles_bucket_total`` between the window's scrapes, in percent.
The engine counts both on the host where it dispatches a decode chunk, from
the rows' lengths and the bucket: 100 where XLA's einsums read the window
(a CPU, stacked members, an int8 cache side), under it where the Pallas call
reads each live row to its own length and skips the dead ones. An engine
without the counters, or one that dispatched no chunk, reads nothing."""
from layer_metrics.prefill_decode_wait_share import delta

READ = "quorum_tpu_engine_decode_kv_tiles_read_total"
BUCKET = "quorum_tpu_engine_decode_kv_tiles_bucket_total"


def read(art):
    fetched, bucket = delta(art, READ), delta(art, BUCKET)
    if fetched is None or not bucket or bucket <= 0:
        return None
    return 100.0 * fetched / bucket
