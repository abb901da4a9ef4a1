"""The two counters behind ``decode_kv_read_share``: what a dispatched decode
chunk reads of the dense cache's history window, in tiles, counted on the
host from the rows' lengths and the bucket (engine._count_kv_tiles)."""

import pytest

from quorum_tpu.engine import engine as engine_module
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

GREEDY = SamplerConfig(temperature=0.0)


@pytest.fixture()
def eng():
    spec = resolve_spec("llama-tiny", {"max_seq": "256"})
    e = InferenceEngine(spec, decode_chunk=4, decode_pipeline=1, n_slots=2)
    yield e
    e.shutdown()


def _chunks(eng, prompt_len: int, new_tokens: int):
    """One request on two rows (one live, one dead); the chunks' rises."""
    before = eng.metrics()
    eng.generate(list(range(3, 3 + prompt_len)), max_new_tokens=new_tokens,
                 sampler=GREEDY)
    after = eng.metrics()
    return {k: after[k] - before[k] for k in (
        "decode_chunks_total", "decode_kv_tiles_read_total",
        "decode_kv_tiles_bucket_total")}


def test_where_the_einsums_read_the_window_read_counts_the_bucket(eng):
    # a CPU engine: every row is read to the bucket, so the share is 100
    rise = _chunks(eng, prompt_len=20, new_tokens=9)
    chunks = rise["decode_chunks_total"]
    assert chunks >= 2
    # 2 rows x one tile (a bucket under 512 positions) x 4 steps a chunk
    assert rise["decode_kv_tiles_bucket_total"] == 2 * 1 * 4 * chunks
    assert rise["decode_kv_tiles_read_total"] == (
        rise["decode_kv_tiles_bucket_total"])


def test_where_the_kernel_reads_a_live_row_counts_to_its_own_length(
        eng, monkeypatch):
    """Tiles of 16 positions and the kernel's rule answered for a TPU: the
    dead row counts nothing, the live row its tiles at every step."""
    monkeypatch.setattr(engine_module, "decode_tile", lambda h: min(16, h))
    monkeypatch.setattr(eng, "_kernel_reads", lambda history: True)
    # admission samples token 1 at position 20; the first chunk's four steps
    # sit at positions 20-23 (21-24 entries: two tiles of 16 each), the
    # second's at 24-27, in a history bucket of 32 (two tiles a row)
    rise = _chunks(eng, prompt_len=20, new_tokens=9)
    chunks = rise["decode_chunks_total"]
    assert chunks == 2
    assert rise["decode_kv_tiles_bucket_total"] == 2 * 2 * 4 * chunks
    assert rise["decode_kv_tiles_read_total"] == 2 * 4 * chunks
    # past the tile's edge a third tile shows: positions 28-35 of bucket 64
    rise = _chunks(eng, prompt_len=28, new_tokens=9)
    assert rise["decode_kv_tiles_bucket_total"] == 2 * 4 * 4 * 2
    # entries 29-32 read two tiles, 33-36 three
    assert rise["decode_kv_tiles_read_total"] == 4 * 2 + 4 * 3


def test_the_rule_is_the_kernels_own(eng):
    # on the CPU the engine's rule says no, whatever the shapes
    assert eng._kernel_reads(128) is False
