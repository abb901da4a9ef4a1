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
from typing import Any

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


# ---- pipeline-staged DECODE (serving) --------------------------------------
#
# The inference twin of the training pipeline above, for models whose
# weight+KV footprint exceeds one device group's HBM (ROADMAP item 4; MPMD
# placement per PAPERS.md, the stage-pipelined decode shape Jupiter applies
# at the edge). Stage s holds layers [s·L/pp, (s+1)·L/pp) AND those layers'
# KV-cache shard (kv_cache_sharding shards the layer axis over pp); the
# microbatch slots are DECODE ROWS: the engine's slot batch splits into pp
# contiguous row groups, and at tick t stage s advances row group
# (t−s) mod pp by one layer-stage — one ring ppermute per tick carries the
# activation forward (stage s→s+1) and the freshly sampled token back
# (last stage→0), so in steady state every stage is busy every tick and
# each group emits one token per pp ticks. Everything — n_steps token
# steps, sampling with the engine's full sampler closure (penalties,
# logit bias, constrained-DFA masks, logprobs), on-device finish
# accounting — runs inside ONE compiled program; under decode_loop=C the
# tick scan nests inside the fused megachunk scan, so the staged schedule
# keeps the decode_pipeline=K × decode_loop=C dispatch ring semantics of
# the unstaged engine bit for bit (tests/test_pp_decode.py pins pp=2
# token-for-token against a single-device engine).
#
# Per-layer math is decode_step's exactly: each stage runs
# transformer.decode_step_blocks on its layer shard, embed/unembed run at
# stage 0 / the last stage on replicated non-block params. Known
# inefficiency, documented: every stage traces the unembed+sample block,
# but a lax.cond on the stage index skips its execution off the last
# stage. Also documented: the last stage runs sample_fn at FULL batch
# width once per tick (the group's logits scattered into a zero [B,vocab]
# lane) — pp× the unstaged path's sampler FLOPs, with all but the tick's
# sg rows merged away. The full-width call is what keeps the engine's
# row-indexed sampler closures (RNG key rows, DFA state rows, bias rows)
# bit-identical to decode_chunk's without re-deriving a group-local
# indexing contract; slot batches are small next to the layer stack, so
# the win of a sliced sampler has not yet justified that second contract.


def _row_groups(mesh: Mesh, batch: int) -> int:
    npp = mesh.shape[AXIS_PP]
    if batch % npp:
        raise ValueError(
            f"staged decode needs the slot batch ({batch}) divisible by "
            f"pp={npp} (the row groups are the pipeline's microbatches)")
    return npp


def staged_decode_chunk(
    params,
    spec: ModelSpec,
    mesh: Mesh,
    n_steps: int,
    token,    # [B] current token ids
    lengths,  # [B]
    live,     # [B] bool
    budget,   # [B] int32
    eos,      # [B] int32
    cache_k,  # [L, B, K, max_seq, hd] — layer axis sharded over pp
    cache_v,
    sample_fn,
    sample_carry,
    history: int | None = None,
    flash: str | None = None,
):
    """One pipeline-staged decode chunk; same contract as
    :func:`quorum_tpu.models.transformer.decode_chunk` (tokens, per-row
    ``n_valid``, on-device finish accounting, ``sample_fn`` carry/aux
    threading), scheduled as a row-group pipeline over the mesh's ``pp``
    axis. ``sample_fn`` may close over replicated engine state (sampler
    knobs, bias rows, grammar tables) — closures enter shard_map as
    replicated values."""
    from quorum_tpu.models.transformer import (
        decode_step_blocks,
        decode_token_embed,
        _final_norm,
        _unembed,
    )

    npp = _row_groups(mesh, token.shape[0])
    b = token.shape[0]
    sg = b // npp
    n_ticks = npp * n_steps + npp - 1
    ring = [(i, (i + 1) % npp) for i in range(npp)]
    blocks = params["blocks"]
    other = {k: v for k, v in params.items() if k != "blocks"}

    # Aux output shapes (logprob triples, masked-token counts, …) come from
    # one abstract evaluation of the engine's sampler — trace-free, exactly
    # the decode_loop skip-branch pattern.
    aux_shapes = jax.eval_shape(
        lambda lg, lv, c: sample_fn(lg, lv, c)[2],
        jax.ShapeDtypeStruct((b, spec.vocab_size), jnp.float32),
        jax.ShapeDtypeStruct((b,), jnp.bool_),
        sample_carry,
    )

    def state0():
        # The ONE source of truth for the scan's state pytree: local()'s
        # st0 initialization and the shard_map out_specs (via eval_shape)
        # both build from here, so they can never drift apart.
        return dict(
            live=live, budget=budget, lens=lengths, carry=sample_carry,
            toks=jnp.zeros((n_steps, b), jnp.int32),
            valid=jnp.zeros((n_steps, b), bool),
            aux=tuple(jnp.zeros((n_steps,) + tuple(sh.shape), sh.dtype)
                      for sh in jax.tree.leaves(aux_shapes)),
        )

    def local(blocks_local, ck_l, cv_l):
        s = lax.axis_index(AXIS_PP)
        is_first_stage = s == 0
        is_last = s == npp - 1

        def embed_group(tok_g, lens_g):
            # ``other`` = the replicated non-block params (embed/unembed/
            # final-norm live outside the staged region, like the training
            # pipeline's).
            return decode_token_embed(other, spec, tok_g, lens_g)

        def slice_rows(arr, rows0, width=None):
            w = sg if width is None else width
            starts = (rows0,) + (0,) * (arr.ndim - 1)
            sizes = (w,) + arr.shape[1:]
            return lax.dynamic_slice(arr, starts, sizes)

        def scat_rows(arr, val, rows0, gate):
            starts = (rows0,) + (0,) * (arr.ndim - 1)
            old = lax.dynamic_slice(arr, starts, val.shape)
            return lax.dynamic_update_slice(
                arr, jnp.where(gate, val, old), starts)

        def tick(carry, t):
            bundle, st, ck_l, cv_l = carry
            rel = t - s
            valid = (rel >= 0) & (rel < npp * n_steps)
            g = rel % npp          # row group this stage advances this tick
            k = rel // npp         # that group's token index in the chunk
            rows0 = g * sg

            # Stage 0 input: the group's chunk-entry state for its first
            # token, else the token+state the LAST stage sampled last tick
            # (the ring half of the ppermute). Later stages consume their
            # predecessor's activation with the row state forwarded along.
            first = rel < npp
            init_tok = slice_rows(token, rows0)
            init_live = slice_rows(live, rows0)
            init_lens = slice_rows(lengths, rows0)
            in_tok = jnp.where(first, init_tok, bundle["tok"])
            in_live = jnp.where(first, init_live, bundle["live"])
            in_lens = jnp.where(first, init_lens, bundle["lens"])
            cur_tok = jnp.where(is_first_stage, in_tok, bundle["tok"])
            cur_live = jnp.where(is_first_stage, in_live, bundle["live"])
            cur_lens = jnp.where(is_first_stage, in_lens, bundle["lens"])
            # Dead rows run the static batch lane at position 0, exactly as
            # decode_chunk's `pos = where(lv, lens, 0)` does — keeps the
            # two schedules' forwards (and their aux records) bit-equal.
            pos_rows = jnp.where(cur_live, cur_lens, 0)
            x0 = embed_group(in_tok, pos_rows)
            x_in = jnp.where(is_first_stage, x0, bundle["x"])

            # This stage's layers on its cache slab for the group's rows;
            # fill/drain ticks run the same static-shape program with
            # writes masked off (the training pipeline's idle-tick rule).
            allow = cur_live & valid
            ck_rows = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, rows0, sg, axis=1),
                ck_l)
            cv_rows = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, rows0, sg, axis=1),
                cv_l)
            y, ck_rows, cv_rows = decode_step_blocks(
                blocks_local, spec, x_in, pos_rows, ck_rows, cv_rows,
                write_mask=allow, history=history, flash=flash)
            ck_l = jax.tree.map(
                lambda a, r: lax.dynamic_update_slice_in_dim(
                    a, r, rows0, axis=1), ck_l, ck_rows)
            cv_l = jax.tree.map(
                lambda a, r: lax.dynamic_update_slice_in_dim(
                    a, r, rows0, axis=1), cv_l, cv_rows)

            kc = jnp.clip(k, 0, n_steps - 1)

            def do_sample(op):
                st, y = op
                h = _final_norm(other, spec, y)
                logits_g = _unembed(other, spec, h[:, 0, :]).astype(
                    jnp.float32)
                logits_full = lax.dynamic_update_slice(
                    jnp.zeros((b, spec.vocab_size), jnp.float32),
                    logits_g, (rows0, jnp.int32(0)))
                lv_full = scat_rows(jnp.zeros((b,), bool), allow, rows0,
                                    True)
                nxt_full, new_carry, aux = sample_fn(
                    logits_full, lv_full, st["carry"])
                # Merge ONLY this tick's group rows into the sampler carry
                # (keys/counts/DFA are all row-indexed): every row's RNG
                # chain splits exactly once per token, exactly as the
                # unstaged chunk's batched split does.
                rows_m = ((jnp.arange(b) >= rows0)
                          & (jnp.arange(b) < rows0 + sg) & valid)

                def merge(new, old):
                    m = rows_m.reshape((b,) + (1,) * (new.ndim - 1))
                    return jnp.where(m, new, old)

                carry2 = jax.tree.map(merge, new_carry, st["carry"])
                # decode_chunk's finish accounting, verbatim on the group.
                nxt_g = slice_rows(nxt_full, rows0)
                nxt_g = jnp.where(cur_live, nxt_g, cur_tok)
                eos_g = slice_rows(eos, rows0)
                bud_g = slice_rows(st["budget"], rows0)
                lens_new = cur_lens + cur_live.astype(cur_lens.dtype)
                bud_new = bud_g - cur_live.astype(bud_g.dtype)
                fin = cur_live & ((nxt_g == eos_g) | (bud_new <= 0))
                live_new = cur_live & ~fin
                st2 = dict(st)
                st2["carry"] = carry2
                st2["live"] = scat_rows(st["live"], live_new, rows0, valid)
                st2["budget"] = scat_rows(st["budget"], bud_new, rows0,
                                          valid)
                st2["lens"] = scat_rows(st["lens"], lens_new, rows0, valid)
                old_t = lax.dynamic_slice(st["toks"], (kc, rows0), (1, sg))
                st2["toks"] = lax.dynamic_update_slice(
                    st["toks"], jnp.where(valid, nxt_g[None], old_t),
                    (kc, rows0))
                old_v = lax.dynamic_slice(st["valid"], (kc, rows0), (1, sg))
                st2["valid"] = lax.dynamic_update_slice(
                    st["valid"], jnp.where(valid, cur_live[None], old_v),
                    (kc, rows0))
                bufs = []
                for buf, leaf in zip(st["aux"], jax.tree.leaves(aux)):
                    if leaf.ndim and leaf.shape[0] == b:
                        starts = (kc,) + (0,) * leaf.ndim
                        oldb = lax.dynamic_slice(buf, starts,
                                                 (1,) + leaf.shape)
                        m = rows_m.reshape((b,) + (1,) * (leaf.ndim - 1))
                        bufs.append(lax.dynamic_update_slice(
                            buf, jnp.where(m, leaf, oldb[0])[None], starts))
                    else:  # per-step scalar (masked-entry counts): sum the
                        bufs.append(  # group ticks of token k together
                            buf.at[kc].add(jnp.where(valid, leaf, 0)))
                st2["aux"] = tuple(bufs)
                return st2, nxt_g, live_new, lens_new

            def skip_sample(op):
                st, _y = op
                return st, cur_tok, cur_live, cur_lens

            st, out_tok, out_live, out_lens = lax.cond(
                is_last, do_sample, skip_sample, (st, y))
            out_bundle = {"x": y, "tok": out_tok, "live": out_live,
                          "lens": out_lens}
            out_bundle = jax.tree.map(
                lambda v: lax.ppermute(v, AXIS_PP, ring), out_bundle)
            return (out_bundle, st, ck_l, cv_l), None

        st0 = state0()
        bundle0 = dict(
            x=jnp.zeros((sg, 1, spec.d_model), jnp.dtype(spec.dtype)),
            tok=jnp.zeros((sg,), jnp.int32),
            live=jnp.zeros((sg,), bool),
            lens=jnp.zeros((sg,), jnp.int32),
        )
        carry0 = (*lax.pcast((bundle0, st0), (AXIS_PP,), to="varying"),
                  ck_l, cv_l)
        (_, st, ck_l, cv_l), _ = lax.scan(
            tick, carry0, jnp.arange(n_ticks))

        # Only the LAST stage's full-width state/output copies are
        # authoritative (it owns sampling); psum-select replicates them
        # back to every stage — the training pipeline's outbuf pattern.
        def from_last(v):
            if v.dtype == jnp.bool_:
                z = lax.psum(jnp.where(is_last, v.astype(jnp.int32), 0),
                             AXIS_PP)
                return z.astype(jnp.bool_)
            return lax.psum(jnp.where(is_last, v, jnp.zeros_like(v)),
                            AXIS_PP)

        out = jax.tree.map(from_last, st)
        return ck_l, cv_l, out

    staged = jax.tree.map(lambda _: P(AXIS_PP), blocks)
    cache_specs_k = jax.tree.map(lambda _: P(AXIS_PP), cache_k)
    cache_specs_v = jax.tree.map(lambda _: P(AXIS_PP), cache_v)
    rep_out = jax.tree.map(lambda _: P(), jax.eval_shape(state0))
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(staged, cache_specs_k, cache_specs_v),
        out_specs=(cache_specs_k, cache_specs_v, rep_out),
        check_vma=False,
    )
    cache_k, cache_v, out = fn(blocks, cache_k, cache_v)
    toks = out["toks"].T                       # [B, n_steps]
    valid_t = out["valid"].T
    n_valid = jnp.sum(valid_t.astype(jnp.int32), axis=1)
    return (toks, valid_t, n_valid, out["live"], out["budget"],
            cache_k, cache_v, out["lens"], out["carry"],
            tuple(out["aux"]))


def staged_decode_loop(
    params,
    spec: ModelSpec,
    mesh: Mesh,
    n_steps: int,
    n_chunks: int,
    token, lengths, live, budget, eos,
    cache_k, cache_v,
    sample_fn, sample_carry,
    history: int | None = None,
    flash: str | None = None,
):
    """Megachunk wrapper for the staged chunk — decode_loop's contract
    (leading per-chunk axis on tokens/n_valid/aux, all-rows-finished early
    exit, carry passthrough on skipped chunks) with the ppermute tick scan
    nested inside the fused C-chunk scan: one dispatch, C×n_steps tokens,
    the stage ring full the whole way."""
    def run_chunk(op):
        tok, lens, lv, bud, ck, cv, s_carry = op
        (toks, _valid, n_valid, lv, bud, ck, cv, lens, s_carry, aux) = \
            staged_decode_chunk(params, spec, mesh, n_steps, tok, lens, lv,
                                bud, eos, ck, cv, sample_fn, s_carry,
                                history=history, flash=flash)
        return (toks[:, -1], lens, lv, bud, ck, cv, s_carry), \
            (toks, n_valid, aux)

    carry0 = (token, lengths, live, budget, cache_k, cache_v, sample_carry)
    out_shapes = jax.eval_shape(lambda op: run_chunk(op)[1], carry0)

    def skip_chunk(op):
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             out_shapes)
        return op, zeros

    def body(carry, _):
        return lax.cond(jnp.any(carry[2]), run_chunk, skip_chunk, carry)

    carry, (toks, n_valid, aux) = lax.scan(body, carry0, None,
                                           length=n_chunks)
    token, lengths, live, budget, cache_k, cache_v, sample_carry = carry
    return (toks, n_valid, token, live, budget, cache_k, cache_v, lengths,
            sample_carry, aux)


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
