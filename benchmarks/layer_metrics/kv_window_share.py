"""KV manager: of the slot cache's bytes, the share the window layers' rings
hold: ``kv_cache_window_bytes`` over both kinds, from the sizes of the arrays
the engine holds, at the window's last scrape, in percent. About 8.6 with six
rings of 128 positions beside two full layers of 4096; it rises towards 75 if
a window layer ever keeps every position again. An engine whose cache has no
kinds reads nothing."""

FULL = "quorum_tpu_engine_kv_cache_full_bytes"
WINDOW = "quorum_tpu_engine_kv_cache_window_bytes"


def read(art):
    m = art["m1"]
    if FULL not in m or not m.get(WINDOW):
        return None
    return 100.0 * m[WINDOW] / (m[WINDOW] + m[FULL])
