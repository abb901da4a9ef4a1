"""KV manager: of the slot cache's bytes, the share the indexer's keys hold:
``kv_cache_index_bytes`` over all three kinds (full layers' rows, window
layers' rings, index keys), from the sizes of the arrays the engine holds, at
the window's last scrape, in percent. The arrays are counted as they lie,
lane padding included: 15.24 with three full layers of 16,384 positions (rows
of 576 padded to 640, + 128 a position) beside three rings of 1,024 x 1,152
(1,088 padded); 16.6 if only the lanes in use counted. It falls to 8.2 if the
index keys are ever kept in 8 bits. An engine whose cache has no index keys
reads nothing."""

FULL = "quorum_tpu_engine_kv_cache_full_bytes"
WINDOW = "quorum_tpu_engine_kv_cache_window_bytes"
INDEX = "quorum_tpu_engine_kv_cache_index_bytes"


def read(art):
    m = art["m1"]
    if FULL not in m or not m.get(INDEX):
        return None
    return 100.0 * m[INDEX] / (m[INDEX] + m[FULL] + m.get(WINDOW, 0))
