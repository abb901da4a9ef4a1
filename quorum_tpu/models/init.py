"""Random parameter initialization for any :class:`ModelSpec`.

Produces the exact pytree layout quorum_tpu.models.transformer consumes and
quorum_tpu.parallel.sharding knows how to shard. Init is seeded and scaled
(normal, 1/sqrt(fan_in)) so generated text is stable across runs and logits
stay O(1) — what the serving tests and benchmarks need; real weights come
from quorum_tpu.models.hf_loader when a local checkpoint exists.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.transformer import Params


def init_params(spec: ModelSpec, seed: int = 0) -> Params:
    return init_params_from_key(spec, jax.random.PRNGKey(seed))


def init_params_from_key(spec: ModelSpec, key) -> Params:
    """Init from a PRNG key (traced-friendly: vmappable over stacked keys —
    how ensemble members materialize directly into their [M, …] slices)."""
    spec.validate()
    dt = jnp.dtype(spec.dtype)
    keys = iter(jax.random.split(key, 32))

    def w(k, *shape, fan_in=None):
        fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.normal(k, shape, jnp.float32) * (fan ** -0.5)).astype(dt)

    L, D, V = spec.n_layers, spec.d_model, spec.vocab_size
    H = spec.n_heads * spec.head_dim
    K = spec.n_kv_heads * spec.head_dim
    F, E = spec.d_ff, spec.n_experts
    # Stored norm weight such that the effective multiplier (norm_offset + w)
    # is identity: 1.0 for llama-style, 0.0 for gemma's (1 + w) convention.
    norm_one = 1.0 - spec.norm_offset

    blocks: dict = {
        "attn_norm_w": jnp.full((L, D), norm_one, dt),
        "attn_norm_b": jnp.zeros((L, D), dt) if spec.norm == "layernorm" else None,
        "wq": w(next(keys), L, D, H),
        "wk": w(next(keys), L, D, K),
        "wv": w(next(keys), L, D, K),
        "wo": w(next(keys), L, H, D),
        "bq": jnp.zeros((L, H), dt) if spec.use_bias else None,
        "bk": jnp.zeros((L, K), dt) if spec.use_bias else None,
        "bv": jnp.zeros((L, K), dt) if spec.use_bias else None,
        "bo": jnp.zeros((L, D), dt) if spec.use_bias else None,
        "mlp_norm_w": jnp.full((L, D), norm_one, dt),
        "mlp_norm_b": jnp.zeros((L, D), dt) if spec.norm == "layernorm" else None,
    }
    if spec.is_moe:
        blocks.update(
            router=w(next(keys), L, D, E),
            moe_w_gate=w(next(keys), L, E, D, F, fan_in=D),
            moe_w_up=w(next(keys), L, E, D, F, fan_in=D),
            moe_w_down=w(next(keys), L, E, F, D, fan_in=F),
        )
    else:
        blocks.update(
            w_gate=w(next(keys), L, D, F) if spec.gated_mlp else None,
            w_up=w(next(keys), L, D, F),
            w_down=w(next(keys), L, F, D),
            b_up=jnp.zeros((L, F), dt) if spec.use_bias else None,
            b_down=jnp.zeros((L, D), dt) if spec.use_bias else None,
        )

    params: Params = {
        "tok_emb": w(next(keys), V, D, fan_in=D),
        "pos_emb": w(next(keys), spec.max_seq, D, fan_in=D) if spec.pos == "learned" else None,
        "final_norm_w": jnp.full((D,), norm_one, dt),
        "final_norm_b": jnp.zeros((D,), dt) if spec.norm == "layernorm" else None,
        "lm_head": None if spec.tied_lm_head else w(next(keys), D, V),
        "blocks": blocks,
    }
    return params


def init_params_sharded(spec: ModelSpec, mesh, seed: int = 0) -> Params:
    """Initialize parameters directly on the mesh, sharded, in ONE compiled
    program.

    At 7B scale the eager path (``init_params`` + ``shard_pytree``) dispatches
    a dozen separate device ops and round-trips layouts; jitting the whole
    init with the target shardings as ``out_shardings`` makes XLA materialize
    every leaf in place — no host copy, no replicated intermediate, one
    compile. This is how a 14 GB bf16 model comes up on a 16 GB chip."""
    from quorum_tpu.parallel.sharding import param_shardings

    shapes = jax.eval_shape(lambda: init_params(spec, seed))
    shardings = param_shardings(mesh, shapes, n_kv_heads=spec.n_kv_heads)
    return jax.jit(
        lambda: init_params(spec, seed), out_shardings=shardings
    )()


def init_params_ensemble_sharded(
    spec: ModelSpec, mesh, seeds: list[int], quant: str | None = None
) -> Params:
    """Member-stacked parameters ``[M, …]`` for on-device logit-ensemble
    decoding (engine ``ensemble=N``): each member is an independent seeded
    init, vmapped over stacked PRNG keys so every leaf materializes directly
    into its ``[M, …]`` slice — no per-member temporaries + stack copy
    (which would transiently need ~2× the ensemble's weight HBM). The
    member axis is replicated (vmapped, never communicated).

    ``quant="int8"`` fuses per-member quantization into the same program
    (scales reduce over the contraction axis, so the stacked tree's scales
    are exactly each member's own) — two int8 7B members fit one 16 GB
    chip, a consensus ensemble a single device could never hold in bf16."""
    from quorum_tpu.parallel.sharding import param_shardings

    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])

    def build(ks) -> Params:
        params = jax.vmap(lambda k: init_params_from_key(spec, k))(ks)
        if quant == "int8":
            from quorum_tpu.models.quant import quantize_params

            params = quantize_params(params)
        return params

    shapes = jax.eval_shape(build, keys)
    shardings = param_shardings(mesh, shapes, lead_axes=1,
                                n_kv_heads=spec.n_kv_heads)
    return jax.jit(build, out_shardings=shardings)(keys)


def param_count(params: Params) -> int:
    return sum(
        x.size for x in jax.tree.leaves(params) if hasattr(x, "size")
    )
