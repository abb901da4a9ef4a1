"""Weight-only int8 quantization (``quant=int8``, models/quant.py).

Decode is HBM-bandwidth-bound, so int8 weights halve bytes/token (PERF.md).
These tests pin the accuracy contract (per-channel quantization error bound,
near-lossless logits), the pytree/sharding integration (q8/qs leaves inherit
the parent spec on a real mesh), and end-to-end serving through the engine
and the ``tpu://…&quant=int8`` URL knob.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import init_params, resolve_spec
from quorum_tpu.models.quant import (
    dq,
    is_quantized,
    quantize_leaf,
    quantize_params,
    quantized_param_bytes,
)
from quorum_tpu.models.transformer import forward_logits
from quorum_tpu.parallel import MeshConfig, make_mesh
from quorum_tpu.parallel.sharding import LOGICAL_RULES, param_shardings


def test_quantize_leaf_error_bound():
    """|w - dq(q(w))| ≤ scale/2 + bf16 rounding, per channel."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48), jnp.float32)
    q = quantize_leaf(w, axis=-2)
    assert q["q8"].dtype == jnp.int8
    assert q["qs"].shape == (1, 48)
    back = np.asarray(dq(q, jnp.float32), np.float32)
    scale = np.asarray(q["qs"], np.float32)
    err = np.abs(back - np.asarray(w))
    # round-to-nearest: ≤ scale/2 everywhere (dequant here is f32 — exact)
    assert (err <= scale / 2 + 1e-6).all()


def test_dq_passthrough_for_plain_leaves():
    w = jnp.ones((4, 4), jnp.bfloat16)
    assert dq(w) is w
    assert not is_quantized(w)


def test_quantized_logits_near_lossless():
    """Tiny llama: quantized forward tracks bf16 forward closely and agrees
    on the argmax for most positions (weight-only int8 contract)."""
    spec = resolve_spec("llama-tiny")
    params = init_params(spec, seed=0)
    qparams = quantize_params(params)
    assert is_quantized(qparams["blocks"]["wq"])
    assert is_quantized(qparams["tok_emb"])
    assert not is_quantized(qparams["blocks"]["attn_norm_w"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, spec.vocab_size)
    ref = np.asarray(forward_logits(params, spec, tokens), np.float32)
    got = np.asarray(forward_logits(qparams, spec, tokens), np.float32)
    # relative L2 error small; argmax agrees on ≥ 90% of positions
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 0.05, f"relative logits error {rel:.4f}"
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, f"argmax agreement {agree:.2f}"


def test_quantized_moe_forward_runs():
    spec = resolve_spec("mixtral-tiny")
    qparams = quantize_params(init_params(spec, seed=0))
    assert is_quantized(qparams["blocks"]["moe_w_gate"])
    assert not is_quantized(qparams["blocks"]["router"])
    tokens = jnp.ones((1, 8), jnp.int32)
    out = forward_logits(qparams, spec, tokens)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_quantized_bytes_halved():
    spec = resolve_spec("llama-tiny")
    params = init_params(spec, seed=0)
    bf16_bytes = quantized_param_bytes(params)
    q_bytes = quantized_param_bytes(quantize_params(params))
    # int8 + scales + unquantized norms: well under 60% of bf16
    assert q_bytes < 0.6 * bf16_bytes


def test_quantized_shardings_inherit_parent_spec():
    """q8 gets the parent leaf's PartitionSpec (the layer axis by its rule,
    tp on heads/ff/vocab); the size-1 scale dims replicate via _fit_spec."""
    spec = resolve_spec("llama-tiny")
    mesh = make_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
    qtree = jax.eval_shape(lambda: quantize_params(init_params(spec, 0)))
    sh = param_shardings(mesh, qtree)
    wq = sh["blocks"]["wq"]
    layers = LOGICAL_RULES["layers"]
    assert wq["q8"].spec == jax.sharding.PartitionSpec(layers, None, "tp")
    assert wq["qs"].spec == jax.sharding.PartitionSpec(layers, None, "tp")


def test_engine_int8_serves_on_mesh():
    """End-to-end: int8 engine on a dp2×tp2 mesh generates deterministically
    and matches its own single-device int8 output token-for-token."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = resolve_spec("llama-tiny", {"max_seq": "64"})
    mesh = make_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
    eng_mesh = InferenceEngine(spec, mesh, decode_chunk=4, quant="int8")
    eng_one = InferenceEngine(spec, decode_chunk=4, quant="int8")
    prompt = [3, 5, 7]
    sampler = SamplerConfig(temperature=0.0)
    a = eng_mesh.generate(prompt, max_new_tokens=8, sampler=sampler).token_ids
    b = eng_one.generate(prompt, max_new_tokens=8, sampler=sampler).token_ids
    assert len(a) == 8
    assert a == b, "int8 generation diverged between mesh and single device"


async def test_tpu_url_quant_knob():
    """tpu://…&quant=int8 serves a completion; quant=int4 is rejected."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    be = TpuBackend.from_spec(BackendSpec(
        name="Q8", url="tpu://llama-tiny?quant=int8&max_seq=64", model="m",
    ))
    assert be.engine.quant == "int8"
    out = await be.complete(
        {"model": "m", "messages": [{"role": "user", "content": "hi"}],
         "max_tokens": 4},
        {}, timeout=60,
    )
    assert out.status_code == 200
    assert out.body["choices"][0]["message"]["content"] is not None

    with pytest.raises(ValueError):
        TpuBackend.from_spec(BackendSpec(
            name="Q4", url="tpu://llama-tiny?quant=int4", model="m",
        ))


@pytest.mark.slow  # loads torch and transformers: 69 s
def test_ckpt_quant_logits_close_to_transformers(tmp_path):
    """Real-weights path: a HF checkpoint loaded with quant=int8 still tracks
    the transformers forward (weight mapping + quantization compose)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from quorum_tpu.models.hf_loader import load_hf_checkpoint

    cfg = LlamaConfig(
        vocab_size=512, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    # Deterministic weights: the int8 error/argmax bounds below are tight
    # enough that an unlucky UNSEEDED draw can cross them (observed once in
    # a full-suite run) — that flake tells us nothing about the quantizer.
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    tokens = np.array([[3, 17, 5, 9, 250, 11, 42, 7]], dtype=np.int32)
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.float().numpy()

    spec, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
    qlogits = np.asarray(
        forward_logits(quantize_params(params), spec, jnp.asarray(tokens)),
        np.float32,
    )
    rel = np.linalg.norm(qlogits - theirs) / np.linalg.norm(theirs)
    assert rel < 0.05, f"relative error vs transformers {rel:.4f}"
    agree = (qlogits.argmax(-1) == theirs.argmax(-1)).mean()
    assert agree >= 0.85, f"argmax agreement {agree:.2f}"


def test_native_int8_and_f32_gemm_branches_agree(monkeypatch):
    """The shipping TPU branch (native int8 einsum) must compute the same
    products as the CPU f32-GEMM formulation. At tiny contraction dims the
    f32 accumulation is exact (sums < 2^24), so equality is EXACT — a
    regression in the chip-only branch fails here on CPU."""
    from quorum_tpu.models.quant import qeinsum, quantize_leaf

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(5, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    leaf = quantize_leaf(w, -2)

    monkeypatch.setenv("QUORUM_TPU_QEINSUM_INT8", "1")  # force native path
    native = np.asarray(qeinsum("td,df->tf", x, leaf))
    monkeypatch.setenv("QUORUM_TPU_QEINSUM_INT8", "0")  # force f32 GEMM
    gemm = np.asarray(qeinsum("td,df->tf", x, leaf))
    np.testing.assert_array_equal(native, gemm)
