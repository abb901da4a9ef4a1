"""int8 KV cache (``kv_quant=int8``): accuracy, capacity, and engine paths.

Representation contract (models/transformer.py): each cache side becomes
``(int8 values, f32 per-token scales)`` with ``value ≈ q8 * scale``; decode
attention contracts natively in int8 (ops.attention.decode_attention_q8 —
never dequantize-into-dot, the measured lesson from weight quant, PERF.md
§2), while the cold prefill-segment path dequantizes its bounded
history window.
"""

import jax
import jax.numpy as jnp
import numpy as np

from quorum_tpu.backends.tpu_backend import TpuBackend
from quorum_tpu.config import BackendSpec
from quorum_tpu.engine.engine import InferenceEngine, get_engine
from quorum_tpu.models.model_config import MODEL_PRESETS, resolve_spec
from quorum_tpu.models.transformer import init_cache
from quorum_tpu.ops.attention import (
    decode_attention,
    decode_attention_q8,
    quantize_rows,
)
from quorum_tpu.ops.sampling import SamplerConfig

import pytest
# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow

TINY = MODEL_PRESETS["llama-tiny"]


def test_q8_decode_attention_close_to_dense():
    """Native-int8 decode attention must track the bf16 path within the
    int8 quantization noise floor on random caches."""
    rng = np.random.default_rng(0)
    b, h, kh, t, hd = 2, 4, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(b, h, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kh, t, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kh, t, hd)), jnp.float32)
    length = jnp.asarray([t, t // 2], jnp.int32)

    ref = decode_attention(q, k, v, length)
    k8, ks = quantize_rows(k, axis=-1)
    v8, vs = quantize_rows(v, axis=-1)
    got = decode_attention_q8(q, k8, ks[..., 0], v8, vs[..., 0], length)

    err = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert err < 0.05, f"int8 KV attention error {err:.4f} exceeds 5%"


def test_kv_cache_int8_half_bytes():
    ck_bf, cv_bf = init_cache(TINY, batch=2)
    ck_q8, cv_q8 = init_cache(TINY, batch=2, kv_quant="int8")
    bf_bytes = ck_bf.nbytes + cv_bf.nbytes
    q8_bytes = sum(x.nbytes for x in jax.tree.leaves((ck_q8, cv_q8)))
    # int8 values are half of bf16; the f32 per-token scale adds 4 bytes per
    # 2·head_dim bf16 bytes → ratio 0.5 + 2/head_dim (1.6% at hd=128; the
    # tiny spec's hd=16 pays 12.5%)
    assert q8_bytes <= (0.5 + 2 / TINY.head_dim + 0.001) * bf_bytes
    assert ck_q8[0].dtype == jnp.int8 and ck_q8[1].dtype == jnp.float32


def test_engine_kv_quant_generates_and_first_token_matches():
    """The admission prefill attends over the ORIGINAL bf16 k/v (the cache
    write is separate), so the first sampled token must match the bf16-cache
    engine exactly; later tokens may drift within quantization noise but the
    generation must complete its budget."""
    eng_bf = InferenceEngine(TINY, seed=0, decode_chunk=4, n_slots=2)
    eng_q8 = InferenceEngine(TINY, seed=0, decode_chunk=4, n_slots=2,
                             kv_quant="int8")
    prompt = [3, 4, 5, 6]
    out_bf = eng_bf.generate(prompt, max_new_tokens=8,
                             sampler=SamplerConfig(temperature=0.0)).token_ids
    out_q8 = eng_q8.generate(prompt, max_new_tokens=8,
                             sampler=SamplerConfig(temperature=0.0)).token_ids
    assert len(out_q8) == 8
    assert out_q8[0] == out_bf[0]
    assert all(0 <= t < TINY.vocab_size for t in out_q8)


def test_kv_quant_chunked_prefill_and_prefix_reuse_exact():
    """Long prompts ride chunked prefill with a quantized cache, and prefix
    reuse stays EXACT within the representation: a warm request reusing
    resident int8 rows matches the cold kv_quant engine token-for-token
    (identical stored bytes → identical reads)."""
    spec = resolve_spec("llama-tiny", {"max_seq": "128"})
    cold = InferenceEngine(spec, seed=2, decode_chunk=4, n_slots=1,
                           prefill_chunk=16, kv_quant="int8",
                           prefix_cache=False)
    warm = InferenceEngine(spec, seed=2, decode_chunk=4, n_slots=1,
                           prefill_chunk=16, kv_quant="int8")
    prompt = [(7 + 3 * i) % 500 for i in range(50)]
    follow = prompt + [9, 8, 7]

    kw = dict(max_new_tokens=6, sampler=SamplerConfig(temperature=0.7),
              seed=4)
    want_first = cold.generate(prompt, **kw).token_ids
    want_follow = cold.generate(follow, **kw).token_ids
    got_first = warm.generate(prompt, **kw).token_ids   # cold in warm engine
    got_follow = warm.generate(follow, **kw).token_ids  # reuses prefix rows
    assert got_first == want_first
    assert got_follow == want_follow
    assert warm.prefix_hits >= 1


def test_kv_quant_engine_on_mesh():
    """The (int8, scale) cache under GSPMD: values shard like the bf16 cache
    and the scale array drops the head_dim axis — the full engine path on a
    dp×tp mesh must still generate, and its first token (sampled from the
    bf16 prefill logits) must match the single-device kv_quant engine."""
    from quorum_tpu.parallel import MeshConfig, make_mesh

    spec = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
    eng_1 = InferenceEngine(spec, seed=3, decode_chunk=4, n_slots=2,
                            kv_quant="int8")
    eng_m = InferenceEngine(spec, make_mesh(MeshConfig(dp=2, tp=4)), seed=3,
                            decode_chunk=4, n_slots=2, kv_quant="int8")
    kw = dict(max_new_tokens=8, sampler=SamplerConfig(temperature=0.0))
    one = eng_1.generate([7, 8, 9], **kw).token_ids
    sharded = eng_m.generate([7, 8, 9], **kw).token_ids
    assert len(sharded) == 8
    # full token-for-token equality (same bar as the bf16 sibling test,
    # test_engine_mesh.py): int8 rounding happens before the cache write,
    # so sharded and single-device decode read identical stored bytes
    assert sharded == one


def test_kv_quant_url_and_engine_identity():
    def mk(url):
        return TpuBackend.from_spec(BackendSpec(name="b", url=url, model="t"))

    b1 = mk("tpu://llama-tiny?kv_quant=int8&seed=700")
    b2 = mk("tpu://llama-tiny?kv_quant=int8&seed=700")
    b3 = mk("tpu://llama-tiny?seed=700")
    assert b1.engine is b2.engine
    assert b1.engine is not b3.engine
    assert b1.engine.kv_quant == "int8" and b3.engine.kv_quant is None


def test_kv_quant_composes_with_members():
    """The (int8, scale) cache under the member axis: stacked fan-out
    (members=M, separate streams) vmaps over tuple-leaf caches. Member
    streams must still match the members=1 kv_quant engine with that
    member's seed."""
    stacked = InferenceEngine(TINY, seed=0, members=2, decode_chunk=4,
                              n_slots=2, kv_quant="int8")
    singles = [InferenceEngine(TINY, seed=i, decode_chunk=4, n_slots=2,
                               kv_quant="int8") for i in range(2)]
    kw = dict(max_new_tokens=6,
              sampler=SamplerConfig(temperature=0.8, top_p=0.9), seed=4)
    got = [stacked.generate([3, 4, 5], member=m, **kw).token_ids
           for m in range(2)]
    want = [singles[i].generate([3, 4, 5], **kw).token_ids for i in range(2)]
    assert got == want


def test_kv_quant_composes_with_weight_quant():
    """quant=int8 (weights) + kv_quant=int8 (cache) together: the smallest
    serving footprint — generation still completes and emits valid ids."""
    eng = InferenceEngine(TINY, seed=1, decode_chunk=4, n_slots=2,
                          quant="int8", kv_quant="int8")
    out = eng.generate([5, 6, 7], max_new_tokens=8,
                       sampler=SamplerConfig(temperature=0.8, top_p=0.9),
                       seed=3).token_ids
    assert len(out) == 8
    assert all(0 <= t < TINY.vocab_size for t in out)
