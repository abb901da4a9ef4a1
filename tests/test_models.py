"""Model-runtime unit tests: all three families, cache consistency, sampling.

Strategy per SURVEY.md §4(c): TPU-free jax-on-CPU with tiny presets — the
same code paths the TPU runs, at toy sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import (
    MODEL_PRESETS,
    decode_step,
    forward_logits,
    init_params,
    prefill,
    resolve_spec,
)
from quorum_tpu.models.init import param_count
from quorum_tpu.models.transformer import init_cache
from quorum_tpu.ops.sampling import SamplerConfig, sample_token


TINY = ["gpt2-tiny", "llama-tiny", "mixtral-tiny", "gemma-tiny"]


def _toy_batch():
    toks = jnp.array([[5, 6, 7, 8, 0, 0], [9, 10, 0, 0, 0, 0]], dtype=jnp.int32)
    lengths = jnp.array([4, 2], dtype=jnp.int32)
    return toks, lengths


@pytest.mark.parametrize("model_id", TINY)
def test_prefill_matches_cache_free_forward(model_id):
    spec = resolve_spec(model_id)
    params = init_params(spec, seed=0)
    toks, lengths = _toy_batch()
    ck, cv = init_cache(spec, 2)
    logits, ck, cv = jax.jit(prefill, static_argnums=(1,))(
        params, spec, toks, lengths, ck, cv
    )
    full = jax.jit(forward_logits, static_argnums=(1,))(params, spec, toks)
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full[0, 3]), rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(logits[1]), np.asarray(full[1, 1]), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("model_id", TINY)
def test_decode_step_matches_extended_forward(model_id):
    spec = resolve_spec(model_id)
    params = init_params(spec, seed=0)
    toks, lengths = _toy_batch()
    ck, cv = init_cache(spec, 2)
    logits, ck, cv = jax.jit(prefill, static_argnums=(1,))(
        params, spec, toks, lengths, ck, cv
    )
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    dl, ck, cv = jax.jit(decode_step, static_argnums=(1,))(
        params, spec, nxt, lengths, ck, cv
    )
    toks2 = toks.at[0, 4].set(nxt[0]).at[1, 2].set(nxt[1])
    full2 = jax.jit(forward_logits, static_argnums=(1,))(params, spec, toks2)
    np.testing.assert_allclose(
        np.asarray(dl[0]), np.asarray(full2[0, 4]), rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(dl[1]), np.asarray(full2[1, 2]), rtol=2e-2, atol=2e-2
    )


def test_multi_step_greedy_decode_is_deterministic():
    spec = resolve_spec("llama-tiny")
    params = init_params(spec, seed=0)
    toks = jnp.array([[3, 1, 4, 1, 5]], dtype=jnp.int32)
    lengths = jnp.array([5], dtype=jnp.int32)

    def run():
        ck, cv = init_cache(spec, 1)
        logits, ck, cv = jax.jit(prefill, static_argnums=(1,))(
            params, spec, toks, lengths, ck, cv
        )
        out, ls = [], lengths
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        step = jax.jit(decode_step, static_argnums=(1,))
        for _ in range(8):
            out.append(int(tok[0]))
            logits, ck, cv = step(params, spec, tok, ls, ck, cv)
            ls = ls + 1
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return out

    assert run() == run()


def test_padding_does_not_change_results():
    """Right-padding the prompt bucket must not affect logits (static shapes)."""
    spec = resolve_spec("llama-tiny")
    params = init_params(spec, seed=0)
    lengths = jnp.array([3], dtype=jnp.int32)
    short = jnp.array([[7, 8, 9]], dtype=jnp.int32)
    padded = jnp.array([[7, 8, 9, 0, 0, 0, 0, 0]], dtype=jnp.int32)
    ck, cv = init_cache(spec, 1)
    l1, *_ = jax.jit(prefill, static_argnums=(1,))(params, spec, short, lengths, ck, cv)
    ck, cv = init_cache(spec, 1)
    l2, *_ = jax.jit(prefill, static_argnums=(1,))(params, spec, padded, lengths, ck, cv)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-2, atol=2e-2)


def test_presets_resolve_and_validate():
    for name in MODEL_PRESETS:
        spec = resolve_spec(name)
        assert spec.validate() is spec


def test_resolve_spec_query_overrides():
    spec = resolve_spec("llama-tiny", {"n_layers": "3", "rope_theta": "500000.0", "tp": "4"})
    assert spec.n_layers == 3
    assert spec.rope_theta == 500000.0  # engine option "tp" ignored here


def test_resolve_spec_unknown_id_raises():
    with pytest.raises(KeyError):
        resolve_spec("no-such-model")


def test_gpt2_preset_param_count_is_124m():
    params = init_params(resolve_spec("gpt2"), seed=0)
    n = param_count(params)
    assert 120e6 < n < 130e6, n


def test_sampling_greedy_and_topk():
    logits = jnp.array([[0.0, 5.0, 1.0, 2.0]])
    key = jax.random.PRNGKey(0)
    assert int(sample_token(logits, key, SamplerConfig(temperature=0.0))[0]) == 1
    # top_k=1 at any temperature must also pick the argmax
    assert int(sample_token(logits, key, SamplerConfig(temperature=2.0, top_k=1))[0]) == 1
    # top_p tiny → only the argmax survives the nucleus
    assert int(sample_token(logits, key, SamplerConfig(temperature=1.0, top_p=0.1))[0]) == 1


def test_sampling_temperature_distribution():
    logits = jnp.zeros((1, 4)).at[0, 2].set(3.0)
    keys = jax.random.split(jax.random.PRNGKey(1), 64)
    toks = [int(sample_token(logits, k, SamplerConfig(temperature=1.0))[0]) for k in keys]
    assert max(set(toks), key=toks.count) == 2
    assert len(set(toks)) > 1  # not greedy


def test_llama31_scaled_rope_preset_serves_and_scaling_is_load_bearing():
    """The llama-3.1 preset (rope_scaling=llama3), tiny-ified via URL
    overrides, serves through the engine; and the scaled tables really
    differ from plain RoPE in the stretched band."""
    import numpy as np

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.ops.rotary import rope_cos_sin, rope_cos_sin_for

    tiny = {"n_layers": "2", "d_model": "64", "n_heads": "4",
            "n_kv_heads": "2", "head_dim": "16", "d_ff": "128",
            "vocab_size": "512", "max_seq": "128",
            "rope_original_max_seq": "32"}
    spec = resolve_spec("llama-3.1-8b", tiny)
    assert spec.rope_scaling == "llama3"
    eng = InferenceEngine(spec, decode_chunk=4, n_slots=1)
    out = eng.generate([3, 4, 5, 6], max_new_tokens=6,
                       sampler=SamplerConfig(temperature=0.0),
                       seed=0).token_ids
    eng.shutdown()
    assert len(out) == 6

    cos_s, _ = rope_cos_sin_for(spec)
    cos_p, _ = rope_cos_sin(spec.max_seq, spec.head_dim, spec.rope_theta)
    # Low-frequency (long-wavelength) components are stretched by the
    # factor; the highest-frequency component is untouched.
    assert float(np.abs(np.asarray(cos_s) - np.asarray(cos_p)).max()) > 0.1
    np.testing.assert_allclose(np.asarray(cos_s[:, 0]),
                               np.asarray(cos_p[:, 0]), atol=1e-6)
