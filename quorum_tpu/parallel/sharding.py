"""Logical-axis → PartitionSpec rules for model state.

The scaling-book recipe: name the logical axes of every array once, map
logical axes to mesh axes in one table, and let GSPMD insert collectives.
Megatron-style tensor parallelism falls out of two rules:

  - project *into* parallel subspaces (heads, MLP hidden, experts, vocab)
    with the output dimension sharded over ``tp``  → no communication;
  - project *back* to the model dimension with the input dimension sharded
    over ``tp`` → one psum (all-reduce) per block, inserted by XLA.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quorum_tpu.parallel.mesh import AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP

# Logical axis name → mesh axis (None = replicated).
LOGICAL_RULES: dict[str, str | None] = {
    "batch": AXIS_DP,
    "seq": None,          # sequence is replicated except under ring attention
    "seq_shard": AXIS_SP,  # ring attention: sequence blocks over the sp axis
    "model": None,         # d_model stays replicated (activations all-reduced)
    "heads": AXIS_TP,
    "kv_heads": AXIS_TP,
    "head_dim": None,
    "ff": AXIS_TP,         # MLP hidden
    "experts": AXIS_TP,    # expert parallelism shares the tp axis
    "vocab": AXIS_TP,
    # Scanned-layer leading dim: stage-sharded over pp (a no-op placement on
    # every mesh whose pp axis is 1 — i.e. everything except the pp
    # training mesh; the engine refuses a decode mesh with pp > 1).
    "layers": AXIS_PP,
    "pos": None,
}


def logical_to_spec(axes: tuple[str | None, ...]) -> P:
    """``("layers", "model", "ff")`` → ``P(None, None, "tp")``."""
    return P(*(LOGICAL_RULES.get(a) if a else None for a in axes))


def logical_to_sharding(mesh: Mesh, axes: tuple[str | None, ...]) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes))


# Logical axes for every parameter leaf the transformer uses
# (see quorum_tpu.models.transformer for the pytree layout).
PARAM_LOGICAL_AXES: dict[str, tuple[str | None, ...]] = {
    # embeddings
    "tok_emb": ("vocab", "model"),
    "pos_emb": ("pos", "model"),
    "lm_head": ("model", "vocab"),
    "final_norm_w": ("model",),
    "final_norm_b": ("model",),
    # per-layer (leading "layers" dim — scanned)
    "attn_norm_w": ("layers", "model"),
    "attn_norm_b": ("layers", "model"),
    "wq": ("layers", "model", "heads"),
    "wk": ("layers", "model", "kv_heads"),
    "wv": ("layers", "model", "kv_heads"),
    "wo": ("layers", "heads", "model"),
    "bq": ("layers", "heads"),
    "bk": ("layers", "kv_heads"),
    "bv": ("layers", "kv_heads"),
    "bo": ("layers", "model"),
    "mlp_norm_w": ("layers", "model"),
    "mlp_norm_b": ("layers", "model"),
    "w_gate": ("layers", "model", "ff"),
    "w_up": ("layers", "model", "ff"),
    "w_down": ("layers", "ff", "model"),
    "b_up": ("layers", "ff"),
    "b_down": ("layers", "model"),
    # MoE
    "router": ("layers", "model", "experts"),
    "moe_w_gate": ("layers", "experts", "model", None),
    "moe_w_up": ("layers", "experts", "model", None),
    "moe_w_down": ("layers", "experts", None, "model"),
}

# KV cache: [layers, batch, max_seq, kv_heads · head_dim] (a position's heads
# flattened into one line, models/transformer.py ``init_cache``: whole heads
# shard over tp as blocks of the line); an int8 side's scales
# [layers, batch, max_seq, kv_heads] take the same axes
KV_CACHE_AXES: tuple[str | None, ...] = ("layers", "batch", "seq", "kv_heads")


def kv_cache_sharding(mesh: Mesh, n_kv_heads: int, batch: int | None = None,
                      *, seq_shard: bool = False) -> "NamedSharding":
    """KV-cache sharding that degrades gracefully for GQA: when the kv-head
    count doesn't divide the tp axis (e.g. 2 KV heads on tp=4), the head axis
    is replicated — attention q·K still runs tp-sharded over query heads.

    The leading layer axis carries the ``pp`` rule of the weights' layer
    axis, a no-op on every serving mesh (the engine refuses ``pp`` > 1).

    ``seq_shard=True`` additionally shards the position axis over ``sp`` —
    the disagg PREFILL group's staging cache under ``sp>1``: a 100k-token
    admission's staged KV occupies O(max_seq/sp) HBM per device while the
    decode group keeps its latency-shaped replicated-sequence layout (the
    handoff reshards on the fly)."""
    axes = list(KV_CACHE_AXES)
    if n_kv_heads % mesh.shape[AXIS_TP] != 0:
        axes[3] = None
    if batch is not None and batch % mesh.shape[AXIS_DP] != 0:
        axes[1] = None
    if seq_shard and mesh.shape[AXIS_SP] > 1:
        axes[2] = "seq_shard"
    return logical_to_sharding(mesh, tuple(axes))


def paged_kv_sharding(mesh: Mesh, n_kv_heads: int) -> "NamedSharding":
    """Page-pool sharding for the paged KV layout (``kv_pages=1``):
    ``[layers, pages, kv_heads, page_size, head_dim]``. The physical page
    axis never shards — pages are the allocation unit and a row's chain
    scatters arbitrarily across the pool, so a sharded page axis would turn
    every table gather into a cross-device shuffle. kv_heads shard over tp
    with the same GQA degrade rule as :func:`kv_cache_sharding`; the layer
    axis shards over pp (rejected >1 by the engine under kv_pages, so in
    practice a no-op kept for shape symmetry)."""
    axes: list = ["layers", None, "kv_heads", None, None]
    if n_kv_heads % mesh.shape[AXIS_TP] != 0:
        axes[2] = None
    return logical_to_sharding(mesh, tuple(axes))
# Activations: [batch, seq, model]
ACT_AXES: tuple[str | None, ...] = ("batch", "seq", "model")
# Token ids: [batch, seq]
TOKEN_AXES: tuple[str | None, ...] = ("batch", "seq")


# ---- member-stacked trees (a ``members=M`` engine) ---------------------------
#
# The M members' weights are one tree of stacked leaves, and every model call
# is a ``jax.vmap`` over the member axis. The block leaves are the layer
# scan's ``xs``, and ``vmap`` of a ``lax.scan`` wants a batched ``xs`` at axis
# 1 (it scans over axis 0): handed ``[M, L, …]`` it transposes every leaf
# first, which the TPU compiler carries out as a copy of all the block
# weights at the head of every program (PERF.md section 5 item 2). So the
# blocks are held layers-major, ``[L, M, …]``: a layer's ``[M, …]`` slice is
# contiguous and the scan reads it where it lies. The leaves outside the
# blocks are no scan's ``xs`` and stay ``[M, …]``.

BLOCKS = "blocks"


def member_axes(params: Mapping[str, Any]) -> dict[str, int]:
    """Where the member axis of a member-stacked parameter tree is, as a
    tree prefix for ``jax.vmap``'s ``in_axes`` / ``out_axes``: 1 on every
    leaf under ``blocks``, 0 elsewhere. THE one statement of the rule: the
    stacked init, its shardings, every member-vmapped program and the
    by-member readers take the axis from here."""
    return {k: 1 if k == BLOCKS else 0 for k in params}


def member_params(params: Mapping[str, Any], member) -> dict[str, Any]:
    """One member's tree out of a member-stacked one (``member`` a Python
    int or a traced scalar). Inside a program a block leaf's ``[:, m]`` is a
    strided slice the compiler materializes, a member's share of the block
    weights: for the by-member forwards (scoring, embeddings), never for a
    program of the serving loop."""
    return {k: jax.tree.map(
        lambda x: lax.dynamic_index_in_dim(x, member, axis, keepdims=False),
        params[k]) for k, axis in member_axes(params).items()}


def stack_members(members: list) -> dict[str, Any]:
    """Single-member trees stacked into the member-stacked layout (what the
    stacked init program yields without the stack: models/init.py)."""
    first = members[0]
    return {k: jax.tree.map(lambda *leaves: jnp.stack(leaves, axis=axis),
                            *(m[k] for m in members))
            for k, axis in member_axes(first).items()}


def param_partition_specs(
    params: Mapping[str, Any], stacked: bool = False,
    *, replicate_kv_heads: bool = False
) -> dict[str, Any]:
    """PartitionSpec pytree matching a parameter pytree (same nesting).

    ``stacked`` says the tree is member-stacked (``members=M``): every
    leaf's spec gets one more replicated dim where :func:`member_axes` puts
    the member axis (it is vmapped, never sharded).

    ``replicate_kv_heads`` replicates every leaf whose logical axes include
    ``kv_heads`` (wk/wv/bk/bv). The kv projection's output dim is the *flat*
    ``K·hd``, so ``_fit_spec``'s divisibility check can't see head
    boundaries: 2 KV heads × hd=16 on tp=4 passes (32 % 4 == 0) but shards
    each KV head across two devices. Sub-head-sharded kv projections
    miscompiled under GSPMD for batch-1 prefill (the engine's
    slot-mode admission path) — wrong logits, deterministic, mesh-dependent
    (dp=2×tp=4 yes, tp=4 no) — which was half of the PR 16 "MoE EP
    divergence" quarantine. Replicating mirrors ``kv_cache_sharding``'s GQA
    degrade rule: when kv heads don't divide tp, whole-head sharding is
    impossible and sharding half a head buys nothing."""

    def spec_for(name: str, member_axis: int | None) -> P:
        axes = PARAM_LOGICAL_AXES.get(name)
        if axes is None:
            return P()  # unknown leaf → replicate
        if replicate_kv_heads and "kv_heads" in axes:
            axes = tuple(None if a == "kv_heads" else a for a in axes)
        spec = list(logical_to_spec(axes))
        if member_axis is not None:
            spec.insert(member_axis, None)
        return P(*spec)

    def walk(tree: Mapping[str, Any], member_axis: int | None) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for k, v in tree.items():
            if isinstance(v, Mapping):
                if "q8" in v:
                    # int8-quantized leaf (models/quant.py): q8 has the parent
                    # leaf's shape → parent spec; the scale keeps the same
                    # logical axes with reduced dims at size 1, which
                    # _fit_spec auto-replicates (1 % mesh_size != 0).
                    spec = spec_for(k, member_axis)
                    out[k] = {"q8": spec, "qs": spec}
                else:
                    out[k] = walk(v, member_axis)
            elif v is None:
                out[k] = None
            else:
                out[k] = spec_for(k, member_axis)
        return out

    axes = member_axes(params) if stacked else dict.fromkeys(params)
    return {k: walk({k: v}, axes[k])[k] for k, v in params.items()}


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on any dim the mesh doesn't divide (e.g. vocab 50257 on
    tp=4) — replicate that dim instead of failing. XLA still shards the rest."""
    fitted = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            fitted.append(None)
        else:
            size = mesh.shape[axis] if isinstance(axis, str) else 1
            fitted.append(axis if dim % size == 0 else None)
    return P(*fitted)


def param_shardings(
    mesh: Mesh, params: Mapping[str, Any], stacked: bool = False,
    n_kv_heads: int | None = None,
) -> dict[str, Any]:
    """Shardings for a param pytree (``stacked``: a member-stacked one);
    pass ``n_kv_heads`` so GQA kv projections degrade to replicated (whole
    leaf) when the head count doesn't divide tp — see
    :func:`param_partition_specs`."""
    replicate_kv = (n_kv_heads is not None
                    and n_kv_heads % mesh.shape[AXIS_TP] != 0)
    specs = param_partition_specs(params, stacked,
                                  replicate_kv_heads=replicate_kv)
    return jax.tree.map(
        lambda x, s: None if x is None else NamedSharding(mesh, _fit_spec(s, x.shape, mesh)),
        dict(params),
        specs,
        is_leaf=lambda x: x is None or not isinstance(x, Mapping),
    )


def shard_pytree(mesh: Mesh, params: Mapping[str, Any],
                 n_kv_heads: int | None = None) -> dict[str, Any]:
    """Place a host/param pytree onto the mesh with the standard TP layout."""
    shardings = param_shardings(mesh, params, n_kv_heads=n_kv_heads)
    return jax.tree.map(
        lambda x, s: x if x is None else jax.device_put(x, s),
        dict(params),
        shardings,
        is_leaf=lambda x: x is None or not isinstance(x, Mapping),
    )
