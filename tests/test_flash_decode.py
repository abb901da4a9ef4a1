"""Pallas decode-attention kernel vs the masked-dense reference.

The kernel (ops/flash_decode.py) must match ops.attention.decode_attention —
the engine's numerical ground truth — for every layout the engine produces:
GQA and MHA head counts, skewed per-row lengths (the kernel's reason to
exist: per-row-exact cache reads), single-tile and multi-tile histories,
bf16 and f32. Interpret mode on CPU, same strategy as test_flash_attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.ops.attention import decode_attention
from quorum_tpu.ops.flash_decode import (
    DEFAULT_BLOCK_K,
    flash_decode_attention,
    flash_decode_refusal,
)

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow


def _mk(b, h, n_kv, t, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, 1, hd), dtype)
    k = jax.random.normal(ks[1], (b, n_kv, t, hd), dtype)
    v = jax.random.normal(ks[2], (b, n_kv, t, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("h,n_kv", [(8, 2), (4, 4), (12, 3)])
@pytest.mark.parametrize("t,block_k", [(256, 128), (512, 128), (128, 128)])
def test_matches_reference_skewed_lengths(h, n_kv, t, block_k):
    q, k, v = _mk(4, h, n_kv, t, 64, jnp.float32)
    # Heavily skewed: one row near-empty, one full — the kernel's win case.
    lengths = jnp.array([1, t // 2 - 3, t, 7], jnp.int32)
    ref = decode_attention(q, k, v, lengths)
    got = flash_decode_attention(q, k, v, lengths,
                                 block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_matches_reference_bf16():
    q, k, v = _mk(2, 8, 4, 256, 128, jnp.bfloat16, seed=3)
    lengths = jnp.array([255, 64], jnp.int32)
    ref = decode_attention(q, k, v, lengths)
    got = flash_decode_attention(q, k, v, lengths,
                                 block_k=128, interpret=True)
    assert got.dtype == ref.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_scalar_length_broadcasts():
    q, k, v = _mk(3, 4, 2, 128, 64, jnp.float32, seed=5)
    ref = decode_attention(q, k, v, 97)
    got = flash_decode_attention(q, k, v, 97, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_unsupported_shapes_fall_back():
    # t not divisible by the tile → reference path (still correct).
    q, k, v = _mk(2, 4, 2, 96, 64, jnp.float32, seed=7)
    lengths = jnp.array([5, 96], jnp.int32)
    got = flash_decode_attention(q, k, v, lengths,
                                 block_k=DEFAULT_BLOCK_K, interpret=True)
    ref = decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert flash_decode_refusal(q.shape, k.shape, 64)  # 96 % 64 != 0


def test_under_vmap_members_axis():
    # The stacked-members engine vmaps decode over the leading weight-set
    # axis; the kernel must compose with vmap (Pallas lifts it to a grid
    # dimension).
    m, b, h, n_kv, t, hd = 3, 2, 8, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (m, b, h, 1, hd), jnp.float32)
    k = jax.random.normal(ks[1], (m, b, n_kv, t, hd), jnp.float32)
    v = jax.random.normal(ks[2], (m, b, n_kv, t, hd), jnp.float32)
    lengths = jnp.array([19, 250], jnp.int32)
    ref = jax.vmap(lambda qq, kk, vv: decode_attention(qq, kk, vv, lengths))(
        q, k, v)
    got = jax.vmap(lambda qq, kk, vv: flash_decode_attention(
        qq, kk, vv, lengths, block_k=128, interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_engine_serves_identically_with_kernel(monkeypatch):
    # End-to-end through the continuous-batching engine: the kernel path
    # (interpret mode) must reproduce the default masked-dense path
    # token-for-token, co-batching skewed-length requests.
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = resolve_spec("llama-tiny", {"n_kv_heads": "4", "max_seq": "256"})
    sampler = SamplerConfig(temperature=0.8, top_p=0.9)
    long_prompt = list(range(3, 120))

    def serve():
        eng = InferenceEngine(spec, decode_chunk=4, n_slots=2)
        out = [
            eng.generate(p, max_new_tokens=8, sampler=sampler, seed=5).token_ids
            for p in ([3, 4, 5], long_prompt)
        ]
        eng.shutdown()
        return out

    monkeypatch.delenv("QUORUM_TPU_FLASH_DECODE", raising=False)
    ref = serve()
    monkeypatch.setenv("QUORUM_TPU_FLASH_DECODE", "interpret")
    got = serve()
    assert got == ref


def test_flash_decode_url_knob(monkeypatch):
    """The per-backend flash_decode= knob (first-class since ISSUE 6):
    resolves per engine without the env var, is validated at config time,
    and serves token-identically to the masked-dense path; the env var
    stays a process override that beats the knob."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.flash_decode import (
        parse_flash_decode,
        resolve_flash_decode,
    )
    from quorum_tpu.ops.sampling import SamplerConfig

    monkeypatch.delenv("QUORUM_TPU_FLASH_DECODE", raising=False)
    assert parse_flash_decode("1") == "1"
    assert parse_flash_decode("off") == "0"
    assert parse_flash_decode("interpret") == "interpret"
    with pytest.raises(ValueError):
        parse_flash_decode("maybe")
    # knob drives resolution when the env var is unset...
    assert resolve_flash_decode("interpret") == "interpret"
    assert resolve_flash_decode(None) == ""
    # ...and the env override wins over the knob (A/B scripts flip it)
    monkeypatch.setenv("QUORUM_TPU_FLASH_DECODE", "0")
    assert resolve_flash_decode("interpret") == ""
    monkeypatch.setenv("QUORUM_TPU_FLASH_DECODE", "interpret")
    assert resolve_flash_decode("0") == "interpret"
    # env takes the URL knob's spellings ("off" parses, wins over the knob)
    monkeypatch.setenv("QUORUM_TPU_FLASH_DECODE", "off")
    assert resolve_flash_decode("interpret") == ""
    # unparseable env is a LOUD off (logged), never a crash — one typo'd
    # var must not brick every engine construction in the process
    monkeypatch.setenv("QUORUM_TPU_FLASH_DECODE", "garbage")
    assert resolve_flash_decode("interpret") == ""
    monkeypatch.delenv("QUORUM_TPU_FLASH_DECODE", raising=False)

    spec = resolve_spec("llama-tiny", {"n_kv_heads": "4", "max_seq": "256"})
    sampler = SamplerConfig(temperature=0.8, top_p=0.9)

    def serve(flash):
        eng = InferenceEngine(spec, decode_chunk=4, n_slots=2,
                              flash_decode=flash)
        assert eng._flash == ("interpret" if flash == "interpret" else "")
        out = eng.generate([3, 4, 5], max_new_tokens=8, sampler=sampler,
                           seed=5).token_ids
        eng.shutdown()
        return out

    assert serve(None) == serve("interpret")
