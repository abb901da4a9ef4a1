"""Flash-attention kernel vs the XLA-native reference, in interpreter mode.

The reference path (quorum_tpu.ops.attention.prefill_attention) is itself
validated end-to-end against transformers' forward in tests/test_hf_loader.py,
so matching it here transitively validates the kernel.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quorum_tpu.ops.attention import prefill_attention
from quorum_tpu.ops.flash_attention import (
    flash_disabled,
    flash_prefill_attention,
    flash_refusal,
)

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow


def rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def run_both(b, h, n_kv, s, hd, lengths, block_q=128, block_k=128):
    q = rand(0, (b, h, s, hd))
    k = rand(1, (b, n_kv, s, hd))
    v = rand(2, (b, n_kv, s, hd))
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = prefill_attention(q, k, v, lengths)
    out = flash_prefill_attention(
        q, k, v, lengths, block_q=block_q, block_k=block_k, interpret=True
    )
    return np.asarray(out), np.asarray(ref), lengths


def assert_valid_rows_close(out, ref, lengths, atol=2e-5):
    """Compare only rows inside each batch row's valid length — padded query
    rows are unspecified (never read downstream)."""
    for bi, n in enumerate(np.asarray(lengths)):
        np.testing.assert_allclose(
            out[bi, :, :n, :], ref[bi, :, :n, :], atol=atol, rtol=1e-4
        )


def test_flash_matches_reference_single_block():
    out, ref, lengths = run_both(1, 2, 2, 128, 64, [128])
    assert_valid_rows_close(out, ref, lengths)


def test_flash_matches_reference_multi_block_causal():
    out, ref, lengths = run_both(1, 2, 2, 256, 64, [256])
    assert_valid_rows_close(out, ref, lengths)


def test_flash_gqa_head_mapping():
    out, ref, lengths = run_both(1, 4, 2, 128, 64, [128])
    assert_valid_rows_close(out, ref, lengths)


def test_flash_length_masking_batched():
    out, ref, lengths = run_both(2, 2, 2, 128, 64, [37, 101])
    assert_valid_rows_close(out, ref, lengths)
    assert not np.isnan(out).any()  # padded rows defined (no NaN)


def test_flash_small_bucket_uses_clamped_blocks():
    # bucket 64 < default 128: tiles clamp to the sequence
    out, ref, lengths = run_both(1, 2, 2, 64, 64, [50])
    assert_valid_rows_close(out, ref, lengths)


def test_flash_refusal_gates():
    assert not flash_refusal((1, 4, 256, 64), (1, 2, 256, 64), 128, 128)
    assert flash_refusal((1, 4, 100, 64), (1, 2, 100, 64), 128, 128)
    assert flash_refusal((1, 3, 256, 64), (1, 2, 256, 64), 128, 128)
    # tensor-parallel callers: both head counts must split over tp
    assert not flash_refusal((1, 8, 256, 64), (1, 4, 256, 64), 128, 128, tp=4)
    assert flash_refusal((1, 8, 256, 64), (1, 2, 256, 64), 128, 128, tp=4)


def test_prefill_uses_fallback_off_tpu():
    """On CPU (tests force JAX_PLATFORMS=cpu) the dispatcher must take the
    XLA reference path, not the kernel."""
    assert jax.default_backend() == "cpu"
    assert "cpu" in flash_disabled()


def test_flash_under_tensor_parallel_mesh_matches_reference():
    """tp>1 callers: the kernel has no partitioning rule (XLA refuses a
    Mosaic call inside a GSPMD-partitioned program), so it runs under
    shard_map over tp, each shard on its own q/kv head slice — same numbers
    as the unsharded reference, GQA grouping intact per shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quorum_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=4))
    heads = NamedSharding(mesh, P(None, "tp", None, None))
    q = jax.device_put(rand(0, (1, 8, 128, 64)), heads)
    k = jax.device_put(rand(1, (1, 4, 128, 64)), heads)
    v = jax.device_put(rand(2, (1, 4, 128, 64)), heads)
    lengths = jnp.asarray([101], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, window=48)
    out = jax.jit(lambda q, k, v: flash_prefill_attention(
        q, k, v, lengths, block_q=64, block_k=64, interpret=True,
        window=48, tp_mesh=mesh))(q, k, v)
    assert out.sharding.spec == heads.spec
    assert_valid_rows_close(np.asarray(out), np.asarray(ref), lengths)
