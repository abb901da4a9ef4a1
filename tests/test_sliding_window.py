"""Sliding-window attention (mistral): every attention path honors
``ModelSpec.sliding_window``.

The strongest pin is HF parity: a tiny MistralForCausalLM with a window
SMALLER than the sequence, logits matched against transformers' own SWA
masking — if any path silently computed full causal attention, the tail
tokens (which must NOT see the early ones) would diverge. Internal
consistency then pins that the cache-free forward, the admission prefill +
decode engine path, chunked prefill, the Pallas kernels, and the int8 KV
path all agree with each other under a window.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.attention import decode_attention, prefill_attention
from quorum_tpu.ops.sampling import SamplerConfig

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow

GREEDY = SamplerConfig(temperature=0.0, top_p=1.0)
WSPEC = {"n_kv_heads": "4", "max_seq": "128", "sliding_window": "16"}


def test_hf_mistral_sliding_window_parity(tmp_path):
    import torch
    from transformers import MistralConfig, MistralForCausalLM

    torch.manual_seed(0)
    cfg = MistralConfig(
        vocab_size=512, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        sliding_window=8, attn_implementation="eager",
        tie_word_embeddings=False,
    )
    model = MistralForCausalLM(cfg).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    from quorum_tpu.models.hf_loader import load_hf_checkpoint
    from quorum_tpu.models.transformer import forward_logits

    spec, params = load_hf_checkpoint(tmp_path)
    assert spec.sliding_window == 8, "loader dropped the config's window"

    tokens = np.arange(3, 27, dtype=np.int64)[None, :]  # 24 > window 8
    with torch.no_grad():
        theirs = model(torch.tensor(tokens)).logits.float().numpy()
    ours = np.asarray(
        forward_logits(params, spec, jnp.asarray(tokens, jnp.int32)),
        np.float32)
    np.testing.assert_allclose(ours, theirs, rtol=2e-2, atol=5e-3)  # bf16 load
    # and the window genuinely matters at this length: a windowless load
    # must NOT match the tail of the sequence.
    import dataclasses

    full = np.asarray(forward_logits(
        params, dataclasses.replace(spec, sliding_window=0),
        jnp.asarray(tokens, jnp.int32)), np.float32)
    assert np.abs(full[:, -1] - theirs[:, -1]).max() > 1e-3, (
        "window had no effect — test sequence too short?")


def test_engine_decode_matches_cache_free_forward():
    """Greedy generation through the engine (prefill + windowed decode over
    the cache) must equal argmax continuation of the cache-free windowed
    forward — pinning that BOTH paths apply the same window."""
    from quorum_tpu.models.init import init_params
    from quorum_tpu.models.transformer import forward_logits

    spec = resolve_spec("llama-tiny", WSPEC)
    params = init_params(spec, seed=3)
    prompt = [(i % 97) + 3 for i in range(40)]  # 40 > window 16

    eng = InferenceEngine(spec, params=jax.tree.map(np.asarray, params),
                         decode_chunk=4, n_slots=2)
    got = eng.generate(prompt, max_new_tokens=8, sampler=GREEDY).token_ids
    eng.shutdown()

    toks = list(prompt)
    for _ in range(8):
        logits = forward_logits(params, spec, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert got == toks[len(prompt):], (
        "engine decode disagrees with the cache-free windowed forward")


def test_chunked_prefill_and_prefix_cache_respect_window():
    """Long prompts admitted in segments (and re-admitted over a cached
    prefix) must produce the same windowed continuation."""
    spec = resolve_spec("llama-tiny", WSPEC)
    prompt = [(i % 89) + 3 for i in range(50)]

    whole = InferenceEngine(spec, decode_chunk=4, n_slots=2, seed=3)
    ref = whole.generate(prompt, max_new_tokens=6, sampler=GREEDY).token_ids
    whole.shutdown()

    chunked = InferenceEngine(spec, decode_chunk=4, n_slots=2, seed=3,
                              prefill_chunk=16)
    got = chunked.generate(prompt, max_new_tokens=6, sampler=GREEDY).token_ids
    warm = chunked.generate(prompt, max_new_tokens=6, sampler=GREEDY).token_ids
    chunked.shutdown()
    assert got == ref and warm == ref


def test_flash_kernels_match_reference_with_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, n_kv, t, hd = 2, 8, 4, 256, 64
    from quorum_tpu.ops.flash_attention import flash_prefill_attention
    from quorum_tpu.ops.flash_decode import cache_decode_attention

    # prefill kernel
    q = jax.random.normal(ks[0], (b, h, t, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, n_kv, t, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, n_kv, t, hd), jnp.float32)
    lengths = jnp.array([256, 100], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, window=32)
    got = flash_prefill_attention(q, k, v, lengths, block_q=128, block_k=128,
                                  interpret=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # decode kernel
    qd = jax.random.normal(ks[0], (b, h, 1, hd), jnp.float32)
    dlen = jnp.array([200, 7], jnp.int32)
    refd = decode_attention(qd, k, v, dlen, window=32)
    # the kernel reads the cache's own store: one layer of [B, T, K*hd] lines
    lines = lambda x: x.transpose(0, 2, 1, 3).reshape(1, b, t, n_kv * hd)  # noqa: E731
    gotd = cache_decode_attention(
        qd, lines(k), lines(v), jnp.int32(0), dlen, jnp.ones((b,), bool),
        history=t, interpret=True, window=32)
    np.testing.assert_allclose(np.asarray(gotd), np.asarray(refd),
                               rtol=2e-5, atol=2e-5)


def test_int8_kv_respects_window():
    """kv_quant=int8 decode runs the same window: it must reproduce the
    plain windowed engine's output."""
    spec = resolve_spec("llama-tiny", WSPEC)
    prompt = [(i % 83) + 3 for i in range(30)]

    plain = InferenceEngine(spec, decode_chunk=4, n_slots=2, seed=5)
    ref = plain.generate(prompt, max_new_tokens=8, sampler=GREEDY).token_ids
    plain.shutdown()

    q8 = InferenceEngine(spec, decode_chunk=4, n_slots=2, seed=5,
                         kv_quant="int8")
    got8 = q8.generate(prompt, max_new_tokens=8, sampler=GREEDY).token_ids
    q8.shutdown()
    # int8 rounding can flip near-tie argmaxes; require high agreement and
    # identical prefixes rather than exact equality.
    agree = sum(a == b for a, b in zip(got8, ref))
    assert agree >= 6, (got8, ref)


def test_sp_mesh_rejects_windowed_spec():
    from quorum_tpu.parallel import MeshConfig, make_mesh

    spec = resolve_spec("llama-tiny", WSPEC)
    mesh = make_mesh(MeshConfig(sp=2))
    with pytest.raises(ValueError, match="sliding_window"):
        InferenceEngine(spec, mesh)


def test_stacked_members_respect_window():
    """members=M stacks windowed engines member-vmapped; each member's
    stream must equal its own per-seed single engine."""
    spec = resolve_spec("llama-tiny", WSPEC)
    prompt = [(i % 79) + 3 for i in range(40)]
    stacked = InferenceEngine(spec, members=2, decode_chunk=4, n_slots=2)
    singles = [InferenceEngine(spec, seed=i, decode_chunk=4, n_slots=2)
               for i in range(2)]
    try:
        for m in range(2):
            a = stacked.generate(prompt, max_new_tokens=8, sampler=GREEDY,
                                 seed=9, member=m).token_ids
            b = singles[m].generate(prompt, max_new_tokens=8, sampler=GREEDY,
                                    seed=9).token_ids
            assert a == b, f"member {m} diverged under the window"
    finally:
        stacked.shutdown()
        for s in singles:
            s.shutdown()
