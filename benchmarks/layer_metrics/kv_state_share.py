"""KV manager: of the slot cache's bytes, the share the mixer's recurrent
state and convolution tails hold: ``kv_cache_state_bytes`` over state + K and
V (and rings and index keys, where a cache has them), from the sizes of the
arrays the engine holds, at the window's last scrape, in percent. 50.2 with
six layers of 64 rows of 2,048 positions (2 KB a position) beside six
layers of 64 float32 states of [32, 128, 256] and tails of [3, 5120]: a
row's state is as much as 2,048 positions of its K and V, whatever its
length. An engine whose cache holds no state reads nothing."""

STATE = "quorum_tpu_engine_kv_cache_state_bytes"
REST = tuple(f"quorum_tpu_engine_kv_cache_{kind}_bytes"
             for kind in ("full", "window", "index"))


def read(art):
    m = art["m1"]
    if not m.get(STATE):
        return None
    return 100.0 * m[STATE] / (m[STATE] + sum(m.get(k, 0) for k in REST))
