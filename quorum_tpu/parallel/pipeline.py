"""Pipeline parallelism: transformer layer stages over the ``pp`` mesh axis.

GPipe-style schedule, TPU-first (scaling-book pipelining recipe): the stacked
layer pytree ``params["blocks"]`` (leading ``n_layers`` dim) is sharded over
``pp`` — each device holds a contiguous stage of ``L/pp`` layers — and the
batch is split into M microbatches that flow stage→stage. Everything runs
under one ``shard_map`` over the mesh:

  tick t ∈ [0, M + pp - 1):   stage s runs its layers on microbatch (t - s),
                              then hands its activation to stage s+1 with ONE
                              ``lax.ppermute`` (nearest-neighbor ICI hop —
                              the pp axis is placed next to tp in the mesh).

The bubble is the standard (pp-1)/(M+pp-1) fraction — idle ticks still
execute (static shapes; their writes are masked), which is what keeps the
whole schedule a single compiled XLA program: no host round-trips between
ticks, no per-stage dispatch.

Embedding, final norm, and unembed run *outside* the shard_map under plain
GSPMD (they are not layer-staged). Composes with dp (microbatches shard
their batch dim over dp); tp/sp compose at the GSPMD level only, so the
manual pipeline path requires tp == sp == 1 — the mesh for pp training is
``dp × pp`` (checked at call time).

Everything is differentiable (``ppermute``/``scan``/``psum`` have transpose
rules), so :func:`make_pp_train_step` trains through the pipeline.

The reference has no distributed execution of any kind (its only
"communication backend" is HTTP, SURVEY.md §5.8); this is north-star
multi-chip functionality, driver-validated via ``dryrun_multichip``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.ops.attention import attention, causal_mask
from quorum_tpu.ops.rotary import rope_cos_sin_for
from quorum_tpu.parallel.mesh import AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP

# NOTE: quorum_tpu.models.transformer is imported lazily inside functions —
# the transformer itself imports quorum_tpu.parallel (ring attention), so a
# module-level import here would be circular.


def _check_pp_mesh(mesh: Mesh, spec: ModelSpec) -> int:
    npp = mesh.shape[AXIS_PP]
    if mesh.shape[AXIS_TP] != 1 or mesh.shape[AXIS_SP] != 1:
        raise ValueError(
            "the pipelined path composes with dp only; build the mesh as "
            f"dp×pp (got tp={mesh.shape[AXIS_TP]}, sp={mesh.shape[AXIS_SP]})"
        )
    if spec.n_layers % npp:
        raise ValueError(
            f"n_layers={spec.n_layers} must divide into pp={npp} stages"
        )
    return npp


def pp_param_shardings(mesh: Mesh, params) -> dict:
    """Placement for the pipelined model: every stacked-layer leaf sharded
    over ``pp`` on its leading (layers) axis; everything else replicated
    (embeddings/norms live outside the staged region)."""
    staged = NamedSharding(mesh, P(AXIS_PP))
    rep = NamedSharding(mesh, P())
    out = dict(params)
    out["blocks"] = jax.tree.map(lambda _: staged, params["blocks"])
    for k, v in params.items():
        if k != "blocks":
            out[k] = jax.tree.map(lambda _: rep, v)
    return out


def shard_pytree_pp(mesh: Mesh, params) -> dict:
    """Place params for pipelining (see :func:`pp_param_shardings`)."""
    return jax.tree.map(jax.device_put, dict(params),
                        pp_param_shardings(mesh, params))


def _pipeline_blocks(blocks, xs, spec: ModelSpec, mesh: Mesh, remat: bool):
    """Run the staged layers over microbatches ``xs`` [M, mb, T, D]."""
    npp = mesh.shape[AXIS_PP]
    n_micro, mb, t_len, _ = xs.shape
    baxis = AXIS_DP if mb % mesh.shape[AXIS_DP] == 0 else None
    positions = jnp.arange(t_len)
    mask = causal_mask(t_len, t_len, window=spec.sliding_window)

    from quorum_tpu.models.transformer import _layer_body

    def local(blocks_local, xs_local):
        s = lax.axis_index(AXIS_PP)
        cos, sin = rope_cos_sin_for(spec)

        def stage(x):
            def body(c, blk):
                return _layer_body(
                    c, blk, spec, positions, cos, sin,
                    lambda q, k, v: attention(q, k, v, mask),
                )
            if remat:
                body = jax.checkpoint(body)
            x, _ = lax.scan(body, x, blocks_local)
            return x

        fwd_perm = [(i, i + 1) for i in range(npp - 1)]

        def tick(carry, t):
            cur, outbuf = carry
            # stage 0 injects microbatch t from the input queue; every other
            # stage consumes what its predecessor ppermuted last tick.
            m_in = jnp.clip(t, 0, n_micro - 1)
            x_in = lax.dynamic_index_in_dim(xs_local, m_in, 0, keepdims=False)
            y = stage(jnp.where(s == 0, x_in, cur))
            # the last stage commits microbatch t-(pp-1) to the output buffer
            m_out = t - (npp - 1)
            valid = (m_out >= 0) & (s == npp - 1)
            m_c = jnp.clip(m_out, 0, n_micro - 1)
            old = lax.dynamic_index_in_dim(outbuf, m_c, 0, keepdims=True)
            outbuf = lax.dynamic_update_slice_in_dim(
                outbuf, jnp.where(valid, y[None], old), m_c, axis=0)
            nxt = lax.ppermute(y, AXIS_PP, fwd_perm) if npp > 1 else y
            return (nxt, outbuf), None

        # derive the carries from xs_local (inherits its dp vma), then mark
        # them pp-varying — the tick body makes them so (axis_index/ppermute)
        cur0 = lax.pcast(xs_local[0] * 0, (AXIS_PP,), to="varying")
        out0 = lax.pcast(xs_local * 0, (AXIS_PP,), to="varying")
        (_, outbuf), _ = lax.scan(
            tick, (cur0, out0), jnp.arange(n_micro + npp - 1))
        # only the last stage wrote anything; psum replicates it back to all
        # pp ranks (every other stage's buffer is still zero)
        return lax.psum(outbuf, AXIS_PP)

    xspec = P(None, baxis, None, None)
    blocks_specs = jax.tree.map(lambda _: P(AXIS_PP), blocks)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(blocks_specs, xspec),
        out_specs=xspec,
    )
    return fn(blocks, xs)


def pipeline_forward_logits(
    params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, T], B divisible by n_micro (× dp ideally)
    mesh: Mesh,
    n_micro: int = 2,
    remat: bool = False,
) -> jnp.ndarray:
    """Full-sequence logits [B, T, V], layers pipelined over ``pp``.

    Semantics match :func:`quorum_tpu.models.transformer.forward_logits`
    exactly (same math, different schedule) — pinned by
    tests/test_pipeline.py.
    """
    from quorum_tpu.models.transformer import _embed, _final_norm, _unembed

    npp = _check_pp_mesh(mesh, spec)
    b, t_len = tokens.shape
    if b % n_micro:
        raise ValueError(f"batch {b} must divide into {n_micro} microbatches")
    del npp
    positions = jnp.arange(t_len)
    x = _embed(params, spec, tokens, positions)  # [B, T, D]
    mb = b // n_micro
    xs = x.reshape(n_micro, mb, t_len, -1)
    out = _pipeline_blocks(params["blocks"], xs, spec, mesh, remat)
    x = out.reshape(b, t_len, -1)
    x = _final_norm(params, spec, x)
    return _unembed(params, spec, x)


def pp_loss_fn(params, spec: ModelSpec, tokens, mesh, n_micro: int,
               remat: bool = True):
    """Mean next-token cross-entropy through the pipeline (same contract as
    quorum_tpu.training.trainer.loss_fn)."""
    logits = pipeline_forward_logits(
        params, spec, tokens[:, :-1], mesh, n_micro, remat=remat)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    m = (targets != 0).astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def pp_train_init(spec: ModelSpec, mesh: Mesh, *, seed: int = 0,
                  optimizer=None):
    """Sharded TrainState with blocks staged over pp (optimizer moments
    inherit the layout through jit output propagation)."""
    from quorum_tpu.models.init import init_params
    from quorum_tpu.training.trainer import TrainState, make_optimizer

    opt = optimizer or make_optimizer()
    params = shard_pytree_pp(mesh, init_params(spec, seed))
    opt_state = jax.jit(opt.init)(params)
    rep = NamedSharding(mesh, P())
    opt_state = jax.tree.map(
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, rep),
        opt_state,
    )
    return TrainState(params=params, opt_state=opt_state,
                      step=jax.device_put(jnp.zeros((), jnp.int32), rep))


def make_pp_train_step(spec: ModelSpec, mesh: Mesh, *, n_micro: int = 2,
                       optimizer=None, remat: bool = True):
    """One pipelined SGD step: ``step(state, tokens [B, T]) → (state, loss)``.

    Gradients flow backward through the same pipeline (ppermute/scan/psum
    transpose to the reverse schedule); AdamW updates run where each stage's
    weights live.
    """
    import optax  # lazy: serving installs don't ship the training deps

    from quorum_tpu.training.trainer import TrainState, make_optimizer

    opt = optimizer or make_optimizer()
    token_sharding = NamedSharding(mesh, P(AXIS_DP, None))

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, tokens: jnp.ndarray):
        loss, grads = jax.value_and_grad(pp_loss_fn)(
            state.params, spec, tokens, mesh, n_micro, remat)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    def run(state, tokens):
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), token_sharding)
        return step(state, tokens)

    return run
