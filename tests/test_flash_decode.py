"""The Pallas decode kernel that reads the carried cache in place, against
the masked-dense reference.

``ops.flash_decode.cache_decode_attention`` must match
``ops.attention.decode_attention`` on the same values for every layout the
engine produces: the leaves positions-major with the heads flattened
(``[L, B, max_seq, K·hd]``), the layer picked by index, GQA and MHA head
counts, per-row lengths at a tile's edges, dead rows at length 0, histories
of one tile and of four. Interpret mode on the CPU, as test_flash_attention
does it; ``tests/test_decode_in_place.py`` compiles the same call with Mosaic
at the served shapes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.ops.attention import decode_attention
from quorum_tpu.ops.flash_decode import (
    DECODE_TILE,
    cache_decode_attention,
    fetch_plan,
    kernel_refusal,
    live_tiles,
)


def _mk(b, h, n_kv, max_seq, hd, dtype, *, layers=2, seed=0):
    """q [B, H, 1, hd] and two carried leaves [L, B, max_seq, K·hd]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, 1, hd), dtype)
    k = jax.random.normal(ks[1], (layers, b, max_seq, n_kv * hd), dtype)
    v = jax.random.normal(ks[2], (layers, b, max_seq, n_kv * hd), dtype)
    return q, k, v


def _reference(q, k, v, layer, lengths, history, window=0):
    """decode_attention over the K-major window the parent's store held."""
    b, hd = q.shape[0], q.shape[-1]

    def k_major(leaf):
        return leaf[layer, :, :history].reshape(b, history, -1, hd).transpose(
            0, 2, 1, 3)

    return decode_attention(q, k_major(k), k_major(v), lengths, window=window)


def _assert_live_rows_match(got, ref, live, **tol):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(got).all()  # a dead row's output is discarded, not NaN
    np.testing.assert_allclose(got[np.asarray(live)], ref[np.asarray(live)],
                               **tol)


@pytest.mark.parametrize("h,n_kv", [(32, 8), (8, 8), (12, 3)])
@pytest.mark.parametrize("history", [DECODE_TILE, 4 * DECODE_TILE, 128])
def test_matches_reference_at_a_tile_s_edges(h, n_kv, history):
    """Lengths 1, a tile's edge - 1, the edge, the edge + 1 (where the
    bucket has a second tile), the full bucket, and a dead row."""
    tile = min(DECODE_TILE, history)
    lengths = jnp.array(
        [1, tile - 1, tile, min(tile + 1, history), history, 5], jnp.int32)
    live = jnp.array([True] * 5 + [False])
    q, k, v = _mk(6, h, n_kv, history + 64, 16, jnp.float32)
    got = cache_decode_attention(q, k, v, jnp.int32(1), lengths, live,
                                 history=history, interpret=True)
    ref = _reference(q, k, v, 1, lengths, history)
    _assert_live_rows_match(got, ref, live, rtol=2e-5, atol=2e-5)


def test_matches_reference_bf16_at_the_served_width():
    """bfloat16 leaves at 8 KV heads of 128: keys and values are read as
    stored and both products accumulate in float32, so the kernel is at
    least as close to the float32 result as the reference."""
    q, k, v = _mk(3, 32, 8, 1024, 128, jnp.bfloat16, seed=3)
    lengths = jnp.array([1023, 64, 513], jnp.int32)
    live = jnp.array([True, True, True])
    got = cache_decode_attention(q, k, v, jnp.int32(0), lengths, live,
                                 history=1024, interpret=True)
    ref = _reference(q, k, v, 0, lengths, 1024)
    assert got.dtype == ref.dtype == jnp.bfloat16
    _assert_live_rows_match(got, ref, live, rtol=2e-2, atol=2e-2)
    exact = _reference(*(x.astype(jnp.float32) for x in (q, k, v)), 0,
                       lengths, 1024)
    err = lambda x: np.abs(np.asarray(x, np.float32) - np.asarray(exact)).max()
    assert err(got) <= err(ref) + 1e-3


def test_the_layer_index_picks_the_layer_of_the_carried_leaf():
    q, k, v = _mk(2, 4, 2, 256, 32, jnp.float32, layers=3, seed=5)
    lengths = jnp.array([97, 200], jnp.int32)
    live = jnp.array([True, True])
    outs = [cache_decode_attention(q, k, v, jnp.int32(layer), lengths, live,
                                   history=256, interpret=True)
            for layer in range(3)]
    for layer, got in enumerate(outs):
        _assert_live_rows_match(got, _reference(q, k, v, layer, lengths, 256),
                                live, rtol=2e-5, atol=2e-5)
    assert not np.allclose(np.asarray(outs[0]), np.asarray(outs[2]))


def test_what_the_kernel_cannot_read_goes_through_the_einsums():
    """An int8 side, a partitioned program and (outside interpret mode) a
    head that is no 128 lanes are refused with a reason, and the call still
    answers, bit for bit what the K-major reference answers on the CPU."""
    q, k, v = _mk(2, 4, 2, 128, 64, jnp.bfloat16, seed=7)
    lengths = jnp.array([5, 96], jnp.int32)
    live = jnp.array([True, True])
    assert "128" in kernel_refusal(q.shape, k, 128, sharded=False)
    assert kernel_refusal(q.shape, k, 128, sharded=False, interpret=True) == ""
    assert "partitioned" in kernel_refusal(q.shape, k, 128, sharded=True,
                                           interpret=True)
    assert "int8" in kernel_refusal(q.shape, (k, k), 128, sharded=False)
    ref = _reference(q, k, v, 1, lengths, 128)
    for sharded in (False, True):
        got = cache_decode_attention(q, k, v, jnp.int32(1), lengths, live,
                                     history=128, sharded=sharded)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))


def test_under_a_member_vmap_the_einsums_read_the_stacked_store():
    """The stacked-members engine vmaps the decode step over a leading
    weight-set axis: the call's own batching rule answers with the einsums
    (the Pallas rule would slice each member's whole cache side out), at
    the served head width where the kernel would otherwise be lowered."""
    m, b, h, n_kv, hd = 3, 2, 8, 2, 128
    q, k, v = (jnp.stack(xs) for xs in zip(*(
        _mk(b, h, n_kv, 256, hd, jnp.bfloat16, seed=11 + i)
        for i in range(m))))
    lengths = jnp.array([19, 250], jnp.int32)
    live = jnp.array([True, True])
    assert kernel_refusal(q.shape[1:], k[0], 256, sharded=False) == ""

    def one(qq, kk, vv):
        return cache_decode_attention(qq, kk, vv, jnp.int32(1), lengths,
                                      live, history=256)

    got = jax.jit(jax.vmap(one))(q, k, v)
    assert "pallas" not in jax.jit(jax.vmap(one)).lower(q, k, v).as_text()
    ref = jnp.stack([_reference(q[i], k[i], v[i], 1, lengths, 256)
                     for i in range(m)])
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))
    # the same call outside a vmap, lowered for the CPU: the einsums too
    np.testing.assert_array_equal(
        np.asarray(jax.jit(one)(q[0], k[0], v[0]), np.float32),
        np.asarray(ref[0], np.float32))


def test_sliding_window_rows_skip_the_tiles_before_their_window():
    q, k, v = _mk(3, 8, 4, 1024, 16, jnp.float32, seed=13)
    lengths = jnp.array([1000, 7, 600], jnp.int32)
    live = jnp.array([True, True, True])
    got = cache_decode_attention(q, k, v, jnp.int32(0), lengths, live,
                                 history=1024, window=32, interpret=True)
    ref = _reference(q, k, v, 0, lengths, 1024, window=32)
    _assert_live_rows_match(got, ref, live, rtol=2e-5, atol=2e-5)
    # rows 0 and 2 see positions 968-999 and 568-599: their second tile only
    rows, tiles = fetch_plan(lengths, 1024, 512, window=32)
    assert (np.asarray(rows).tolist(), np.asarray(tiles).tolist()) == (
        [0, 0, 1, 1, 1, 2], [1, 1, 0, 0, 0, 1])


def test_fetch_plan_repeats_the_last_live_block_for_dead_steps():
    """A step past its row's length, or in a dead row, names the block the
    last live step fetched (the first live step's, before any): the
    pipeline copies nothing for a repeated block."""
    lens = jnp.array([0, 700, 0, 3, 0], jnp.int32)
    rows, tiles = fetch_plan(lens, 1024, 512)
    assert np.asarray(rows).tolist() == [1, 1, 1, 1, 1, 1, 3, 3, 3, 3]
    assert np.asarray(tiles).tolist() == [0, 0, 0, 1, 1, 1, 0, 0, 0, 0]
    rows, tiles = fetch_plan(jnp.zeros((3,), jnp.int32), 512, 512)
    assert np.asarray(rows).tolist() == np.asarray(tiles).tolist() == [0] * 3


@pytest.mark.parametrize("window", [0, 32, 600])
def test_the_hosts_count_of_a_rows_tiles_is_the_plans(window):
    """``live_tiles`` (what the engine's counters add up) counts the blocks
    :func:`fetch_plan` gives a row of their own."""
    lens = np.array([1, 511, 512, 513, 1000, 1024, 1536, 2048])
    for length, counted in zip(lens, live_tiles(lens, 512, window)):
        rows, tiles = fetch_plan(jnp.array([0, length, 0], jnp.int32), 2048,
                                 512, window)
        assert len(set(np.asarray(tiles).tolist())) == counted, length


@pytest.mark.slow
def test_engine_serves_identically_through_the_kernel(monkeypatch):
    """End to end through the continuous-batching engine: the kernel
    (interpret mode, steered here and by no option of the program) must
    reproduce the einsum path token for token, co-batching skewed lengths
    with a dead row between."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models import transformer
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = resolve_spec("llama-tiny", {"n_kv_heads": "4", "max_seq": "256"})
    sampler = SamplerConfig(temperature=0.8, top_p=0.9)
    long_prompt = list(range(3, 120))

    def serve():
        eng = InferenceEngine(spec, decode_chunk=4, n_slots=3)
        out = [
            eng.generate(p, max_new_tokens=8, sampler=sampler, seed=5).token_ids
            for p in ([3, 4, 5], long_prompt)
        ]
        eng.shutdown()
        return out

    ref = serve()
    monkeypatch.setattr(
        transformer, "cache_decode_attention",
        functools.partial(cache_decode_attention, interpret=True))
    assert serve() == ref
