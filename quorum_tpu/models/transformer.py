"""Decoder-only transformer forward: pure functions over a parameter pytree.

TPU-first design choices (SURVEY.md §7):

  - **Scanned layers**: all per-layer weights are stacked with a leading
    ``n_layers`` dim and the depth loop is one ``lax.scan`` — compile time and
    HLO size are O(1) in depth, and XLA pipelines the layers. The decode
    step's scan carries the hidden states AND the KV cache, which each layer
    updates in place and reads where it lies (``decode_step_blocks``; on a
    TPU one Pallas call a layer, ops/flash_decode.py); the prefill-side
    scans still take the cache as ``xs`` and stack it back as ``ys``.
  - **Static shapes everywhere**: prompts are right-padded to a bucket length
    and masked by ``lengths``; the KV cache is a preallocated ``max_seq``
    buffer indexed by position *data*. One compiled program per (batch,
    bucket) serves every request.
  - **bf16 activations/weights, f32 softmax & norms**; matmuls request
    ``preferred_element_type=float32`` so the MXU accumulates in f32.
  - **GQA without repeat_kv copies** (see quorum_tpu.ops.attention).
  - **MoE as dense einsum over an ``experts`` axis** sharded on the tp/ep mesh
    axis: every expert's matmul is an MXU-shaped contraction; the top-k gate
    only weights the combine. No gather/scatter in the hot path.

Parameter pytree layout (leaf names are what the sharding table in
quorum_tpu.parallel.sharding keys on):

A spec with a ``layer_pattern`` (a kind of attention and of MLP per layer, a
cache per layer kind) runs the functions of the same names in
models/patterned.py: each entry point here hands it over in its first line,
and a spec without a pattern compiles what it always compiled.

  tok_emb [V, D] · pos_emb [max_seq, D]? · final_norm_w/b [D] · lm_head [D, V]?
  blocks: attn_norm_w/b [L,D] · wq [L,D,H·hd] · wk/wv [L,D,K·hd] · wo [L,H·hd,D]
          bq/bk/bv/bo? · mlp_norm_w/b [L,D]
          dense: w_gate? w_up [L,D,F] · w_down [L,F,D] · b_up/b_down?
          moe:   router [L,D,E] · moe_w_gate/up [L,E,D,F] · moe_w_down [L,E,F,D]
          mixer: ssm_in [L,D,2d+2GN+H] · ssm_conv_w [L,taps,d+2GN] · ssm_conv_b
                 ssm_dt_bias/ssm_a_log/ssm_d [L,H] · ssm_norm_w [L,d] · ssm_out [L,d,D]

A spec with ``ssm_heads`` runs a Mamba-2 mixer (models/ssm.py) beside
attention in every block, on the same normed input, and both add to the
stream. Each side of its cache is a :class:`StateKV`: the K or V rectangle
and what the mixer carries per layer and row (the state, the convolution's
tail). A decode step carries both leaves through the layer scan, and so does
a prefill program, which writes one row's lines, state and tail in place.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from quorum_tpu.cache.paging import (
    kv_is_paged,
    page_read,
    page_read_row,
    page_write_prefill,
    page_write_seg,
    page_write_step,
)
from quorum_tpu.models import patterned, ssm
from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.ssm import StateKV
from quorum_tpu.models.quant import is_quantized, qeinsum
from quorum_tpu.ops.attention import (
    attention,
    causal_mask,
    decode_attention,
    decode_attention_q8,
    quantize_rows,
)
from quorum_tpu.ops.flash_attention import flash_prefill_attention
from quorum_tpu.ops.flash_decode import cache_decode_attention
from quorum_tpu.parallel.ring_attention import ring_prefill_attention
from quorum_tpu.parallel.ulysses import ulysses_prefill_attention
from quorum_tpu.ops.norms import layernorm, rmsnorm
from quorum_tpu.ops.rotary import apply_rope, rope_cos_sin_for

Params = dict[str, Any]

# ---- the dense KV cache's representation ------------------------------------
#
# A cache side (k or v) is stored positions-major with the heads flattened:
# EITHER a bf16 array [L, B, max_seq, K·hd] (the default) OR, with
# ``kv_quant="int8"``, a tuple ``(q8, scale)`` of [L, B, max_seq, K·hd] int8
# and [L, B, max_seq, K] f32 with ``value ≈ q8 * scale`` per token and head
# (symmetric amax/127, the same formulation as the int8 weight quantizer in
# models/quant.py). A position's K (or V) of every head is one contiguous
# line: a decode step writes a row's line with one scatter, a prefill segment
# writes T lines in one block, and attention contracts a [B, T, K, hd] view
# of the store as it lies (``rows_major`` in ops/attention.py), so nothing
# re-lays the cache between its write and its read (PERF.md §5 item 1).
# Every cache op below dispatches on the representation; jax pytree
# machinery (lax.scan carries, jit donation, vmap) handles the tuple leaves
# transparently. Decode — the bandwidth-bound path — contracts an int8 side
# NATIVELY in int8 (ops.attention.decode_attention_q8); the cold
# prefill-segment path dequantizes its bounded history window
# instead. A paged pool (cache/paging.py) keeps its own K-major pages.


# ``jax.named_scope`` names the parts of a step — embed, norm, attn.qkv,
# attn.cache_write, attn.core, attn.out, mlp, lm_head, sample — so that a
# device operation's ``op_name`` in a profile says which part it belongs to.
# Scopes are metadata: they add no HLO operation, and programs already in the
# persistent compile cache keep the names they were compiled with.


def kv_is_q8(cache) -> bool:
    """True when a cache side uses the int8 (q8, scale) representation —
    dense tuples and paged pools alike (a PagedKV's int8-ness lives in its
    pool leaf)."""
    if kv_is_paged(cache):
        return cache.is_q8
    return isinstance(cache, tuple)


def _kv_quantize(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., T, hd] bf16 → (int8 [..., T, hd], scale [..., T])."""
    q8, s = quantize_rows(x, axis=-1)
    return q8, s[..., 0]


def _kv_dequant(q8: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _kv_lines(cache, value: jnp.ndarray):
    """A block's K or V, [B, K, T, hd], as the lines a dense cache side
    stores: [B, T, K·hd] in the side's dtype, or for an int8 side the pair
    (int8 [B, T, K·hd], scale [B, T, K])."""
    b, k, t, hd = value.shape

    def lines(x):
        return x.transpose(0, 2, 1, 3).reshape(b, t, k * hd)

    if kv_is_q8(cache):
        q8, s = _kv_quantize(value)
        return lines(q8), s.transpose(0, 2, 1).astype(cache[1].dtype)
    return lines(value.astype(cache.dtype))


def _kv_rows(window, n_kv: int, dtype):
    """The [B, T, K, hd] view of a window of a dense cache side's lines
    ([B, T, K·hd]); an int8 side (the pair with scales [B, T, K]) is
    dequantized to ``dtype`` (the cold paths)."""
    def view(x):
        return x.reshape(x.shape[:-1] + (n_kv, x.shape[-1] // n_kv))

    if isinstance(window, tuple):
        return _kv_dequant(view(window[0]), window[1], dtype)
    return view(window)


def _emb_rows(leaf, tokens, dtype):
    """Embedding-table gather that understands quantized tables: gather the
    int8 rows and their per-row scales, dequantize the (tiny) gathered slice.
    HBM traffic for the gather is int8."""
    if is_quantized(leaf):
        return leaf["q8"][tokens].astype(dtype) * leaf["qs"][tokens].astype(dtype)
    return leaf[tokens].astype(dtype)


@jax.named_scope("norm")
def _norm(x, w, b, spec: ModelSpec):
    if spec.norm == "rmsnorm":
        # gemma stores norm weights as w with the model applying (1 + w)
        # (norm_offset=1.0); llama-family stores the multiplier directly.
        if spec.norm_offset:
            w = w + jnp.asarray(spec.norm_offset, w.dtype)
        return rmsnorm(x, w, spec.norm_eps)
    return layernorm(x, w, b, spec.norm_eps)


def _maybe(block: Params, name: str, layer_slice):
    v = block.get(name)
    return None if v is None else layer_slice(v)


def _dense_mlp_core(x, block, spec: ModelSpec):
    if spec.gated_mlp:
        gate = qeinsum("btd,df->btf", x, block["w_gate"])
        if spec.mlp_gate_mult != 1.0:
            gate = gate * spec.mlp_gate_mult
        up = qeinsum("btd,df->btf", x, block["w_up"])
        # swiglu (llama/mistral) gates with SiLU; geglu (gemma) with
        # tanh-approximated GELU (HF act_fn "gelu_pytorch_tanh").
        gated = jax.nn.silu(gate) if spec.act == "swiglu" else jax.nn.gelu(gate, approximate=True)
        h = (gated * up).astype(x.dtype)
    else:
        up = qeinsum("btd,df->btf", x, block["w_up"])
        if block.get("b_up") is not None:
            up = up + block["b_up"]
        h = jax.nn.gelu(up, approximate=True).astype(x.dtype)
    out = qeinsum("btf,fd->btd", h, block["w_down"])
    if spec.mlp_down_mult != 1.0:
        out = out * spec.mlp_down_mult
    if block.get("b_down") is not None:
        out = out + block["b_down"]
    return out.astype(x.dtype)


# the patterned family's shared expert is the same product under a scope of
# its own (models/patterned.py)
_dense_mlp = jax.named_scope("mlp")(_dense_mlp_core)


def _moe_router(x, block, spec: ModelSpec):
    """Top-k routing (Mixtral convention: softmax over the selected logits).
    Returns (top_probs [B,T,k] f32, top_idx [B,T,k] int)."""
    router_logits = jnp.einsum("btd,de->bte", x, block["router"],
                               preferred_element_type=jnp.float32)
    top_vals, top_idx = lax.top_k(router_logits, spec.experts_per_token)
    return jax.nn.softmax(top_vals, axis=-1), top_idx


@jax.named_scope("mlp")
def _moe_mlp_dense(x, block, spec: ModelSpec):
    """Top-k MoE computed densely: every expert runs on every token; the
    combine weight (zero outside the top-k) reproduces sparse routing.

    This is the decode path and the correctness oracle. For decode (T == 1,
    a handful of slot rows) it is near-optimal on TPU: any static-shape MoE
    must read all E experts' weights from HBM anyway, decode is
    bandwidth-bound, and the extra FLOPs are free under the weight reads.
    For prompt-sized T the FLOPs dominate — see :func:`_moe_mlp_grouped`.
    """
    top_probs, top_idx = _moe_router(x, block, spec)
    one_hot = jax.nn.one_hot(top_idx, spec.n_experts, dtype=top_probs.dtype)
    combine = jnp.einsum("btk,btke->bte", top_probs, one_hot)

    gate = qeinsum("btd,edf->ebtf", x, block["moe_w_gate"])
    up = qeinsum("btd,edf->ebtf", x, block["moe_w_up"])
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    expert_out = qeinsum("ebtf,efd->ebtd", h, block["moe_w_down"])
    out = jnp.einsum("bte,ebtd->btd", combine.astype(expert_out.dtype), expert_out)
    return out.astype(x.dtype)


@jax.named_scope("mlp")
def _moe_mlp_grouped(x, block, spec: ModelSpec, token_mask=None):
    """Sparse top-k MoE: tokens are dispatched to per-expert buffers and only
    the selected experts compute (VERDICT r2 weakness 4 — the dense path does
    E/k× the needed FLOPs, 4× for Mixtral top-2-of-8).

    GShard-style static capacity design, TPU-first:
      - Each expert processes a fixed-capacity buffer ``[C, D]`` with
        ``C = min(N, ceil(cf · k · N / E))`` — all shapes static, the expert
        MLP is one batched ``[E,C,D]×[E,D,F]`` contraction the MXU tiles
        directly, sharded over the ``tp``(=ep) mesh axis like the dense path.
      - Dispatch/combine are O(N) scatter/gathers of *row indices* — not the
        quadratic one-hot dispatch einsum (O(N²k·cf·D/E), which would exceed
        the expert matmuls themselves at prompt sizes).
      - Picks that overflow an expert's capacity are dropped (their combine
        weight contributes nothing) — the standard capacity-factor contract;
        ``spec.moe_capacity_factor`` ≥ E/k disables drops entirely, which is
        what the tiny presets use so tests match the dense oracle.
    FLOPs/token: 3·k·cf·D·F vs the dense path's 3·E·D·F — an E/(k·cf)
    reduction (2× for Mixtral at cf=2, 4× at cf=1).
    """
    b, t, d = x.shape
    n = b * t
    e, k = spec.n_experts, spec.experts_per_token
    cap = min(n, max(1, -(-int(spec.moe_capacity_factor * k * n) // e)))
    p = n * k

    top_probs, top_idx = _moe_router(x, block, spec)
    xf = x.reshape(n, d)
    e_p = top_idx.reshape(p)                       # expert of each pick
    prob_p = top_probs.reshape(p)
    if token_mask is not None:
        # Right-padding rows must not consume expert capacity (they would
        # evict real tokens' picks from the fixed-size buffers): route their
        # picks to expert index E, which the one-hot zeroes and the capacity
        # scatter drops as out-of-bounds.
        pick_valid = jnp.repeat(token_mask.reshape(n), k)
        e_p = jnp.where(pick_valid, e_p, e)
        prob_p = prob_p * pick_valid.astype(prob_p.dtype)
    # rank of each pick within its expert (its buffer row)
    oh = jax.nn.one_hot(e_p, e, dtype=jnp.int32)   # [P,E] (e_p == E → zeros)
    ranks = jnp.cumsum(oh, axis=0) - 1             # [P,E]
    c_p = jnp.take_along_axis(
        ranks, jnp.minimum(e_p, e - 1)[:, None], axis=1)[:, 0]

    # expert buffers of token rows: scatter pick→(expert, rank); overflow
    # picks (rank ≥ C) drop out of the scatter; unfilled rows gather a
    # clamped in-bounds row and are zeroed by the mask below. (Not the
    # concatenate-a-zero-row + out-of-bounds-index idiom: gathering from a
    # concat of a batch-sharded token matrix with a replicated pad row
    # miscompiled under GSPMD — the partitioned gather read the wrong
    # shard — which was the PR 16 "MoE EP divergence" quarantine.)
    pick_buf = jnp.full((e, cap), p, jnp.int32)
    pick_buf = pick_buf.at[e_p, c_p].set(
        jnp.arange(p, dtype=jnp.int32), mode="drop")
    tok_buf = jnp.where(pick_buf < p, pick_buf // k, n)
    expert_in = (xf[jnp.minimum(tok_buf, n - 1)]
                 * (tok_buf < n).astype(xf.dtype)[..., None])  # [E,C,D] gather

    gate = qeinsum("ecd,edf->ecf", expert_in, block["moe_w_gate"])
    up = qeinsum("ecd,edf->ecf", expert_in, block["moe_w_up"])
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    expert_out = qeinsum("ecf,efd->ecd", h, block["moe_w_down"])  # [E,C,D]

    # combine: gather each pick's output row, weight by its router prob,
    # sum over the k picks per token; dropped/masked picks contribute zero
    # (their prob_p is zeroed and/or valid is False — the clamped gather
    # index only keeps shapes in bounds).
    valid = c_p < cap
    out_p = expert_out[jnp.minimum(e_p, e - 1), jnp.minimum(c_p, cap - 1)]
    out_p = out_p * (prob_p * valid).astype(out_p.dtype)[:, None]
    return out_p.reshape(n, k, d).sum(axis=1).reshape(b, t, d).astype(x.dtype)


def _moe_mlp(x, block, spec: ModelSpec, token_mask=None):
    # T == 1 is the decode path: dense is bandwidth-optimal there (all expert
    # weights are read either way) and keeps generation exact vs the oracle.
    if x.shape[1] == 1:
        return _moe_mlp_dense(x, block, spec)
    return _moe_mlp_grouped(x, block, spec, token_mask=token_mask)


@jax.named_scope("attn.qkv")
def _qkv(x, block, spec: ModelSpec):
    """Project to q [B,H,T,hd], k/v [B,K,T,hd]."""
    b, t, _ = x.shape
    if spec.attn_in_mult != 1.0:
        x = x * jnp.asarray(spec.attn_in_mult, x.dtype)
    q = qeinsum("btd,dh->bth", x, block["wq"])
    k = qeinsum("btd,dh->bth", x, block["wk"])
    if spec.key_mult != 1.0:
        k = k * spec.key_mult
    v = qeinsum("btd,dh->bth", x, block["wv"])
    if block.get("bq") is not None:
        q, k, v = q + block["bq"], k + block["bk"], v + block["bv"]
    # Keep the head split OUT of the projection dots. Fused into them,
    # XLA:TPU (libtpu 0.0.34) wants the weights contraction-minor and, the
    # layers being one scanned [L, D, H·hd] stack, copies all L layers of
    # wq/wk/wv into that layout at the top of every decode program: 1.5 GiB
    # of HBM temp at mistral-7b, which does not fit beside its bf16 weights
    # on a 16 GB chip (compile-time RESOURCE_EXHAUSTED, PERF.md Findings).
    # wo and the MLP weights, whose dots feed no reshape, get no such copy.
    q, k, v = lax.optimization_barrier(
        (q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)))
    q = q.reshape(b, t, spec.n_heads, spec.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, spec.n_kv_heads, spec.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, spec.n_kv_heads, spec.head_dim).transpose(0, 2, 1, 3)
    return q, k, v


@jax.named_scope("attn.out")
def _attn_out(attn, block, x_dtype, mult: float = 1.0):
    b, h, t, d = attn.shape
    merged = attn.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    out = qeinsum("bth,hd->btd", merged, block["wo"])
    if mult != 1.0:
        out = out * mult
    if block.get("bo") is not None:
        out = out + block["bo"]
    return out.astype(x_dtype)


@jax.named_scope("embed")
def _embed(params, spec: ModelSpec, tokens, positions):
    x = _emb_rows(params["tok_emb"], tokens, jnp.dtype(spec.dtype))
    if spec.emb_scale != 1.0:  # gemma scales embeddings by sqrt(d_model)
        x = x * jnp.asarray(spec.emb_scale, x.dtype)
    if spec.pos == "learned":
        x = x + params["pos_emb"][positions][None, :, :].astype(x.dtype)
    return x


@jax.named_scope("lm_head")
def _unembed(params, spec: ModelSpec, x):
    w = params.get("lm_head")
    if w is not None:
        logits = qeinsum("...d,dv->...v", x, w)
    else:
        # tied head: contract against the embedding table's rows directly —
        # the quantized table's per-row scales become per-vocab output scales.
        logits = qeinsum("...d,vd->...v", x, params["tok_emb"])
    if spec.lm_head_mult != 1.0:
        logits = logits * spec.lm_head_mult
    return logits


def _final_norm(params, spec: ModelSpec, x):
    return _norm(x, params["final_norm_w"], params.get("final_norm_b"), spec)


@jax.named_scope("attn.cache_write")
def _prefill_write(cache, value, cache_row, write_gate):
    """Write a prompt block's K or V into one cache row, handling both
    representations. ``value`` [B, K, T, hd] (B = 1 in slot mode) lands at
    position 0 of row ``cache_row`` (:func:`_block_write`)."""
    if kv_is_paged(cache):
        max_seq = cache.page_size * cache.table.shape[-1]
        return page_write_prefill(cache, value, cache_row, write_gate, max_seq)
    return _block_write(cache, value, cache_row, 0, write_gate)


def _block_write(cache, value, row, offset, write_gate, layer=None):
    """Write ``value`` [B, K, T, hd] as T contiguous lines of one layer's
    dense cache side ([B or S, max_seq, K·hd], or the int8 pair) from
    ``(row, offset)``; with ``layer``, of that layer of a whole side
    ([L, B or S, max_seq, K·hd]). ``write_gate`` (scalar bool) writes the
    touched region back unchanged when False (one extra region read — never
    a full-cache select)."""
    def gated(arr, new):
        idx = (row, offset, 0)
        if layer is not None:
            idx, new = (layer,) + idx, new[None]
        if write_gate is not None:
            old = lax.dynamic_slice(arr, idx, new.shape)
            new = jnp.where(write_gate, new, old)
        return lax.dynamic_update_slice(arr, new, idx)

    return jax.tree.map(gated, cache, _kv_lines(cache, value))


def _mixer_row(h, block, spec: ModelSpec, held, layer, row, n_valid, fresh):
    """The mixer's branch of one prefill program's block over ``h``
    [B, T, D], rows ``row ..`` of layer ``layer`` of the cache ``held`` (its
    two :class:`StateKV` sides): from a zero state and tail where ``fresh``
    (True, or a bool scalar: the row's first position is this program's),
    else from what the rows carry. Returns the branch's output and ``held``
    with the rows' new state and tail written."""
    b = h.shape[0]
    carried = (held[0].carry, held[1].carry)
    state, tail = ssm.init_carry(spec, b, carried[1].dtype)
    if fresh is not True:
        state, tail = (jnp.where(fresh, zero, ssm.rows_read(leaf, layer, row, b))
                       for zero, leaf in zip((state, tail), carried))
    out, state, tail = ssm.mixer(h, block, spec, state, tail, n_valid)
    with jax.named_scope("ssm.scan"):  # the rows' carry, written in place
        return out, tuple(
            StateKV(side.kv, ssm.rows_write(side.carry, new, layer, row))
            for side, new in zip(held, (state, tail)))


def _held_write(held, k, v, row, offset, layer):
    """``held`` with a block's K and V written into layer ``layer`` of its
    two rectangles, from ``(row, offset)``."""
    return tuple(
        StateKV(_block_write(side.kv, value, row, offset, None, layer),
                side.carry) for side, value in zip(held, (k, v)))


def prefill(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,   # [B, T] right-padded
    lengths: jnp.ndarray,  # [B] true prompt lengths
    cache_k: jnp.ndarray,  # [L, B, max_seq, K·hd]; [L, S, max_seq, K·hd] with slot
    cache_v: jnp.ndarray,
    remat: bool = False,
    slot: jnp.ndarray | None = None,
    mesh=None,
    write_gate: jnp.ndarray | None = None,  # scalar bool: False → cache unchanged
    sp_impl: str = "ring",  # "ring" | "ulysses" — SP attention strategy
    tp_mesh=None,  # the caller's mesh when heads are sharded over tp > 1
    block_member: int | None = None,  # blocks are stacked [L, M, …]: take m
    sharded: bool = False,  # the caller's program is partitioned over devices
):
    """Process the full prompt; returns (last-token logits [B,V], cache_k, cache_v).

    With ``slot`` (a traced int32 scalar), K/V is written into cache position
    ``slot`` of a slot-batched cache instead of position 0 — the continuous-
    batching admission path: no per-request cache allocation, no host↔device
    cache transfer; the compiled program fills the preallocated slot in place
    (the engine donates the cache args). One program per prompt bucket serves
    every slot. ``tokens`` must then be batch-1.

    ``write_gate`` (a traced bool scalar) gates the cache write without
    branching the program: when False, the touched region is written back
    with its existing contents (one extra region-sized read, no full-cache
    copy). The stacked-members engine admits under a member vmap with one
    gate per member, so a prompt admitted for member m never clobbers the
    co-located members' cache rows at the same slot index.

    With ``mesh`` (and its ``sp`` axis > 1), prompt attention runs as ring
    attention with the sequence sharded over ``sp`` — the serving engine's
    long-context admission path (SURVEY.md §5.7): per-device attention
    memory is O(T/sp), KV blocks ride the ICI ring at KV-head width, and
    the K/V written to the cache is unchanged (the cache's seq axis stays
    replicated, so decode is sp-agnostic).

    ``block_member`` (a static int): ``params["blocks"]`` is a stacked
    engine's, layers-major ``[L, M, …]``, and the layer scan takes that
    member's weights out of each layer's slice as it reads it: one member's
    prefill outside the member vmap (the dedup admit) without slicing the
    member out of every block leaf first.
    """
    if spec.layer_pattern:
        return patterned.prefill(params, spec, tokens, lengths, cache_k,
                                 cache_v, slot=slot, sharded=sharded)
    b, t = tokens.shape
    cache_row = slot if slot is not None else 0
    if mesh is not None and spec.sliding_window > 0 and sp_impl == "ring":
        raise ValueError(
            "sliding_window specs cannot use ring-attention admission "
            "(sp>1): the ring computes full causal attention and would "
            "silently widen the receptive field (use sp_impl=ulysses — "
            "each device sees the full sequence, windows apply unchanged)")
    positions = jnp.arange(t)
    x = _embed(params, spec, tokens, positions)
    cos, sin = rope_cos_sin_for(spec)
    moe_mask = jnp.arange(t)[None, :] < lengths[:, None]  # [B,T] real tokens

    # A mixer spec's whole cache rides the scan's carry, and each layer
    # writes its row's lines, state and tail where they lie; every other
    # spec's K and V are the scan's xs and ys.
    held = (cache_k, cache_v) if spec.ssm_heads else None
    assert held is None or (write_gate is None and block_member is None)

    def body(carry, per_layer):
        carry_x, held = carry
        block, ck, cv, layer = per_layer  # ck/cv: [B or S, max_seq, K·hd]
        if block_member is not None:
            block = jax.tree.map(lambda w: w[block_member], block)
        h = _norm(carry_x, block["attn_norm_w"], block.get("attn_norm_b"), spec)
        q, k, v = _qkv(h, block, spec)
        if spec.pos == "rope":
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        with jax.named_scope("attn.core"):
            if mesh is not None and sp_impl == "ulysses":
                # Sequence-parallel admission via head↔sequence all-to-alls:
                # full-sequence local attention, so windows apply unchanged.
                attn = ulysses_prefill_attention(
                    q, k, v, lengths, mesh, window=spec.sliding_window)
            elif mesh is not None:
                # Sequence-parallel admission: ring attention over the sp
                # axis. (Windowed specs were rejected above — the ring is
                # full-causal.)
                attn = ring_prefill_attention(q, k, v, lengths, mesh)
            else:
                # Flash kernel on TPU (causal + length mask fused, O(S)
                # VMEM); XLA-native reference path elsewhere.
                attn = flash_prefill_attention(q, k, v, lengths,
                                               window=spec.sliding_window,
                                               tp_mesh=tp_mesh)
        added = _attn_out(attn, block, carry_x.dtype, spec.attn_out_mult)
        if held is not None:
            # a single-shot prefill is the row's whole prompt: from zero
            mix_out, held = _mixer_row(h, block, spec, held, layer,
                                       cache_row, lengths, True)
            added = added + mix_out
        carry_x = carry_x + added
        h2 = _norm(carry_x, block["mlp_norm_w"], block.get("mlp_norm_b"), spec)
        mlp = (_moe_mlp(h2, block, spec, token_mask=moe_mask)
               if spec.is_moe else _dense_mlp(h2, block, spec))
        carry_x = carry_x + mlp
        if held is not None:
            with jax.named_scope("attn.cache_write"):
                held = _held_write(held, k, v, cache_row, 0, layer)
            return (carry_x, held), None
        new_ck = _prefill_write(ck, k, cache_row, write_gate)
        new_cv = _prefill_write(cv, v, cache_row, write_gate)
        return (carry_x, None), (new_ck, new_cv)

    if remat:
        body = jax.checkpoint(body)
    per_layer = ((params["blocks"], cache_k, cache_v, None) if held is None
                 else (params["blocks"], None, None,
                       jnp.arange(spec.n_layers)))
    (x, held), written = lax.scan(body, (x, held), per_layer)
    cache_k, cache_v = written if held is None else held
    x = _final_norm(params, spec, x)
    # Only the last real token's logits matter for generation; gather per row.
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0, :]
    return _unembed(params, spec, last), cache_k, cache_v


def prefill_segment(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,   # [1, T] one segment of one slot's prompt, right-padded
    offset: jnp.ndarray,   # scalar int32: absolute position of tokens[:, 0]
    n_valid: jnp.ndarray,  # scalar int32: real (unpadded) tokens in this segment
    cache_k: jnp.ndarray,  # [L, S, max_seq, K·hd] slot-batched cache
    cache_v: jnp.ndarray,
    slot: jnp.ndarray,     # scalar int32
    history: int | None = None,  # static: attend over cache[:history] only
    write_gate: jnp.ndarray | None = None,  # scalar bool: False → cache unchanged
    sharded: bool = False,  # the caller's program is partitioned over devices
):
    """Chunked prefill: process prompt positions [offset, offset+T) of one slot.

    The chunked-admission path (VERDICT r2 weakness 6): long prompts are
    prefillled in fixed-size segments interleaved with decode chunks, so one
    admission can never stall in-flight generations for its whole prompt.
    Unlike :func:`prefill` (segment-local flash attention), each segment's
    queries attend over the *cache row* — history [0, offset) written by
    earlier segments plus this segment's own K/V — masked causally. Returns
    ``(cache_k, cache_v)`` only; the caller samples the first token with a
    decode step on the final prompt token, which recomputes that position's
    logits against the finished cache.

    ``history`` (a static length ≥ offset + T, typically the next power of
    two) bounds the attention reads: without it every segment would scan the
    full max_seq row — O(chunk · max_seq) reads per segment even when only
    the first few KB of the cache hold history. One program compiles per
    (segment bucket, history bucket) pair — log²-many, not per-length.

    Padded tail positions write garbage K/V at positions ≥ the true prompt
    length; every later read masks ``ki < length`` (decode) or ``ki ≤ qi``
    (causal, here), and generation overwrites those positions one by one, so
    the garbage is never observed. ``n_valid`` additionally keeps those padded
    rows out of MoE expert capacity (they'd otherwise evict real tokens'
    picks from the fixed-size expert buffers).
    """
    if spec.layer_pattern:
        return patterned.prefill_segment(params, spec, tokens, offset,
                                         n_valid, cache_k, cache_v, slot,
                                         history=history, sharded=sharded)
    b, t = tokens.shape
    hist = spec.max_seq if history is None else min(history, spec.max_seq)
    positions = offset + jnp.arange(t)
    x = _embed(params, spec, tokens, positions)
    cos, sin = rope_cos_sin_for(spec)
    # causal over absolute positions: key j visible to query i iff j <= i
    qi = positions[:, None]
    ki = jnp.arange(hist)[None, :]
    keep = ki <= qi
    if spec.sliding_window > 0:
        keep = keep & (ki > qi - spec.sliding_window)
    mask = keep[None, None, None, :, :]  # [1,1,1,T,hist]
    moe_mask = (jnp.arange(t) < n_valid)[None, :]  # [1,T]

    held = (cache_k, cache_v) if spec.ssm_heads else None  # as in prefill
    assert held is None or write_gate is None
    paged = kv_is_paged(cache_k)

    @jax.named_scope("attn.cache_write")
    def seg_write(cache, value):
        # value [1, K, t, hd] at absolute position offset of row `slot`;
        # write_gate (stacked-members segment coalescing) writes the touched
        # region back unchanged when False — region-sized extra read only.
        if paged:
            return page_write_seg(cache, value, slot, offset, write_gate,
                                  spec.max_seq)
        return _block_write(cache, value, slot, offset, write_gate)

    def seg_read(cache, dtype):
        # the slot's history window: [1, hist, K, hd] of the dense store as
        # it lies ([1, K, hist, hd] gathered from a paged pool); int8 caches
        # dequantize the bounded window (cold path — decode uses the
        # native-int8 dot)
        if paged:
            return page_read_row(cache, slot, hist, dtype)
        window = jax.tree.map(
            lambda leaf: lax.dynamic_slice(
                leaf, (slot, 0, 0), (1, hist, leaf.shape[-1])), cache)
        return _kv_rows(window, spec.n_kv_heads, dtype)

    def held_window(side, value, layer, dtype):
        # A carried side's window of the slot as the earlier segments left
        # it, with this segment's lines laid into the copy: the products
        # read a small array of their own and the carried side is only
        # written (read after its write, the compiler re-laid each whole
        # side at the program's entry and exit: 0.8 GB each way at 64 rows
        # of 2,048).
        window = lax.dynamic_slice(side, (layer, slot, 0, 0),
                                   (1, 1, hist, side.shape[-1]))[0]
        return _kv_rows(_block_write(window, value, 0, offset, None),
                        spec.n_kv_heads, dtype)

    def body(carry, per_layer):
        carry_x, held = carry
        block, ck, cv, layer = per_layer  # ck/cv: [S, max_seq, K·hd] (or (q8, scale))
        h = _norm(carry_x, block["attn_norm_w"], block.get("attn_norm_b"), spec)
        q, k, v = _qkv(h, block, spec)
        if spec.pos == "rope":
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        if held is None:
            new_ck = seg_write(ck, k)
            new_cv = seg_write(cv, v)
        with jax.named_scope("attn.core"):
            if held is None:
                row_k = seg_read(new_ck, q.dtype)
                row_v = seg_read(new_cv, q.dtype)
            else:
                row_k, row_v = (held_window(side.kv, value, layer, q.dtype)
                                for side, value in zip(held, (k, v)))
            attn = attention(q, row_k, row_v, mask, rows_major=not paged)
        added = _attn_out(attn, block, carry_x.dtype, spec.attn_out_mult)
        if held is not None:
            with jax.named_scope("attn.cache_write"):
                held = _held_write(held, k, v, slot, offset, layer)
            # the row's state as its last segment left it; a segment at
            # offset 0 is a new tenant's first, whatever the row held
            mix_out, held = _mixer_row(h, block, spec, held, layer, slot,
                                       jnp.reshape(n_valid, (1,)),
                                       offset == 0)
            added = added + mix_out
        carry_x = carry_x + added
        h2 = _norm(carry_x, block["mlp_norm_w"], block.get("mlp_norm_b"), spec)
        mlp = (_moe_mlp(h2, block, spec, token_mask=moe_mask)
               if spec.is_moe else _dense_mlp(h2, block, spec))
        carry_x = carry_x + mlp
        if held is not None:
            return (carry_x, held), None
        return (carry_x, None), (new_ck, new_cv)

    per_layer = ((params["blocks"], cache_k, cache_v, None) if held is None
                 else (params["blocks"], None, None,
                       jnp.arange(spec.n_layers)))
    (_, held), written = lax.scan(body, (x, held), per_layer)
    return written if held is None else held


def decode_step(
    params: Params,
    spec: ModelSpec,
    token: jnp.ndarray,    # [B] current token ids
    lengths: jnp.ndarray,  # [B] #tokens already in cache (current token's position)
    cache_k: jnp.ndarray,  # [L, B, max_seq, K·hd] (donated by the engine's jit
    cache_v: jnp.ndarray,  #   and updated in place inside the program)
    write_mask: jnp.ndarray | None = None,  # [B] bool: rows allowed to write
    history: int | None = None,  # static: attend over cache[:history] only
    sharded: bool = False,  # the caller's program is partitioned over devices
):
    """One autoregressive step. Returns (logits [B,V], cache_k, cache_v).

    ``write_mask`` guards the K/V write per row: a masked-out row writes the
    value already in the cache back (a no-op). The engine uses this for
    inactive slots — without it, a slot mid-chunked-admission would have its
    position-0 K/V clobbered by every interleaved decode chunk (the dead
    rows' dummy writes land at position 0).

    ``history`` (static, ≥ every row's ``lengths``+1) bounds the attention
    read to the cache prefix that can hold valid entries. Decode is
    HBM-bandwidth-bound; without the bound every step streams the full
    padded ``max_seq`` K/V (VERDICT r2 weakness 5) — at 8B/8k that is ~16×
    the needed bytes for a 512-token conversation. The engine picks a
    power-of-two bucket per chunk, so log-many programs cover every length.

    ``sharded`` says what the model code cannot observe from inside a
    trace: that GSPMD partitions the caller's program over more than one
    device. A Mosaic kernel has no partitioning rule, so attention then
    reads the store through XLA's einsums (ops/flash_decode.py)."""
    if spec.layer_pattern:
        return patterned.decode_step(params, spec, token, lengths, cache_k,
                                     cache_v, write_mask=write_mask,
                                     history=history, sharded=sharded)
    x = decode_token_embed(params, spec, token, lengths)
    x, cache_k, cache_v = decode_step_blocks(
        params["blocks"], spec, x, lengths, cache_k, cache_v,
        write_mask=write_mask, history=history, sharded=sharded)
    x = _final_norm(params, spec, x)
    return _unembed(params, spec, x[:, 0, :]), cache_k, cache_v


@jax.named_scope("embed")
def decode_token_embed(params: Params, spec: ModelSpec, token, lengths):
    """Embed one decode step's tokens: ``[B] → [B, 1, D]`` (scaled, plus the
    learned position embedding at each row's position when the spec uses
    one). Shared by :func:`decode_step` and the patterned decode step
    (models/patterned.py)."""
    x = _emb_rows(params["tok_emb"], token, jnp.dtype(spec.dtype))[:, None, :]
    if spec.emb_scale != 1.0:  # gemma scales embeddings by sqrt(d_model)
        x = x * jnp.asarray(spec.emb_scale, x.dtype)
    if spec.pos == "learned":
        x = x + params["pos_emb"][lengths][:, None, :].astype(x.dtype)
    return x


def decode_step_blocks(
    blocks,
    spec: ModelSpec,
    x: jnp.ndarray,        # [B, 1, D] embedded hidden states
    lengths: jnp.ndarray,  # [B] current token's position per row
    cache_k: jnp.ndarray,  # [L', B, max_seq, K·hd] (L' = the layers given)
    cache_v: jnp.ndarray,
    write_mask: jnp.ndarray | None = None,
    history: int | None = None,
    sharded: bool = False,
):
    """The layer-scan core of :func:`decode_step` on pre-embedded hidden
    states: per-row K/V write at ``lengths``, history-bounded read,
    attention + MLP residual per layer — scanned over whatever layer slice
    ``blocks``/``cache_[kv]`` carry; :func:`decode_step` runs it on the full
    stack. Returns ``(x, cache_k, cache_v)`` with ``x`` still pre-final-norm.

    A dense cache (bf16 array or int8 tuple) rides the scan's **carry**, the
    layer index counting within the slice given: each layer writes its rows'
    ``[B, K·hd]`` lines into the carried buffer with one scatter a leaf and
    attends over the carried buffer where it lies
    (``ops.flash_decode.cache_decode_attention``: on a TPU one Pallas call a
    layer that reads each live row to its own length, elsewhere and for an
    int8 side XLA's einsums over a view of the layer's history window), so
    nothing of the cache's size is sliced out as ``xs``, stacked back as
    ``ys``, copied into the caller's step loop or re-laid between the write
    and the read (PERF.md §5 item 1; ``analysis/decode_static.py`` shows
    what a program moves in the compiler's text). The scatter is one
    operation for all rows because a member ``vmap`` turns per-row
    ``dynamic_update_slice``s into scatters the compiler will not chain in
    place. A paged pool (cache/paging.py) writes and gathers through its own
    page table, K-major, and keeps the ``xs``/``ys`` form."""
    b = x.shape[0]
    cos, sin = rope_cos_sin_for(spec)
    allow = (jnp.ones((b,), bool) if write_mask is None else write_mask)
    hist = spec.max_seq if history is None else min(history, spec.max_seq)
    rows = jnp.arange(b)

    paged = kv_is_paged(cache_k)

    def write_leaf(leaf, line, layer):
        # leaf [L', B, max_seq, K·hd] (or [L', B, max_seq, K] scale), line
        # [B, 1, K·hd] (or [B, 1, K]). A masked row writes back what it
        # holds; ``clip`` is the start-clamping of a dynamic_update_slice.
        at = leaf.at[layer, rows, lengths]
        return at.set(jnp.where(allow[:, None], line[:, 0],
                                at.get(mode="clip")), mode="clip",
                      indices_are_sorted=True, unique_indices=True)

    @jax.named_scope("attn.cache_write")
    def step_write(cache, value, layer):
        # value [B, K, 1, hd] at each row's own position
        if paged:
            return page_write_step(cache, value, lengths, allow, spec.max_seq)
        return jax.tree.map(lambda leaf, line: write_leaf(leaf, line, layer),
                            cache, _kv_lines(cache, value))

    def layer_step(carry_x, block, ck, cv, layer):
        if spec.ssm_heads:  # the two StateKV sides: (state, tail) apart
            carried, ck, cv = (ck.carry, cv.carry), ck.kv, cv.kv
        h = _norm(carry_x, block["attn_norm_w"], block.get("attn_norm_b"), spec)
        q, k, v = _qkv(h, block, spec)  # q [B,H,1,hd], k/v [B,K,1,hd]
        if spec.pos == "rope":
            # per-row positions: vmap the table gather over the batch
            rope_row = jax.vmap(lambda xr, p: apply_rope(xr[None], cos, sin, p[None])[0])
            q = rope_row(q, lengths)
            k = rope_row(k, lengths)
        ck = step_write(ck, k, layer)
        cv = step_write(cv, v, layer)
        with jax.named_scope("attn.core"):
            if not paged:
                # The carried and already written buffer, where it lies
                # (the write above landed at lengths < hist). An int8 side
                # contracts natively in int8: HALF the cache bytes per step,
                # no dequantized HBM copy.
                attn = cache_decode_attention(
                    q, ck, cv, layer, lengths + 1, allow, history=hist,
                    window=spec.sliding_window, sharded=sharded)
            else:
                # Gather the history window's pages into a dense [B, K,
                # hist, hd] window — attention runs unchanged on it.
                read_k, read_v = page_read(ck, hist), page_read(cv, hist)
                attend = (decode_attention_q8 if kv_is_q8(ck)
                          else decode_attention)
                attn = attend(q, *jax.tree.leaves((read_k, read_v)),
                              lengths + 1, window=spec.sliding_window)
        added = _attn_out(attn, block, carry_x.dtype, spec.attn_out_mult)
        if spec.ssm_heads:
            # every row's state is read and written once, in the carried leaf
            # where it lies (the mixer takes leaf and layer); a row the step
            # may not write takes no position, which leaves it as it was
            tail = lax.dynamic_index_in_dim(carried[1], layer, 0, False)
            mix_out, state, tail = ssm.mixer(
                h, block, spec, carried[0], tail, allow.astype(jnp.int32),
                layer=layer, sharded=sharded)
            added, ck = added + mix_out, StateKV(ck, state)
            with jax.named_scope("ssm.step"):  # the tail lands in place
                cv = StateKV(cv, ssm.rows_write(carried[1], tail, layer, 0))
        carry_x = carry_x + added
        h2 = _norm(carry_x, block["mlp_norm_w"], block.get("mlp_norm_b"), spec)
        mlp = _moe_mlp(h2, block, spec) if spec.is_moe else _dense_mlp(h2, block, spec)
        return carry_x + mlp, ck, cv

    if paged:
        def paged_body(carry_x, per_layer):
            carry_x, ck, cv = layer_step(carry_x, *per_layer, None)
            return carry_x, (ck, cv)

        x, (cache_k, cache_v) = lax.scan(
            paged_body, x, (blocks, cache_k, cache_v))
        return x, cache_k, cache_v

    def body(carry, per_layer):
        block, layer = per_layer
        return layer_step(carry[0], block, carry[1], carry[2], layer), None

    n_layers = jax.tree.leaves(cache_k)[0].shape[0]
    (x, cache_k, cache_v), _ = lax.scan(
        body, (x, cache_k, cache_v), (blocks, jnp.arange(n_layers)))
    return x, cache_k, cache_v


def decode_chunk(
    params: Params,
    spec: ModelSpec,
    n_steps: int,
    token: jnp.ndarray,    # [B] current token ids
    lengths: jnp.ndarray,  # [B] #tokens already in cache per row
    live: jnp.ndarray,     # [B] bool: rows decoding in this chunk
    budget: jnp.ndarray,   # [B] int32: tokens each row may still produce
    eos: jnp.ndarray,      # [B] int32: per-row EOS id (-1 = none)
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    sample_fn,
    sample_carry,
    history: int | None = None,
    model_call=None,
):
    """``n_steps`` decode steps with **on-device finish accounting**.

    The chunked-decode program of the depth-K dispatch pipeline: the host
    keeps several of these in flight and blocks only on the oldest, so the
    device must know — without a host round trip — when a row is done.
    After a row samples its EOS (``eos``, −1 disables) or its remaining
    token ``budget`` reaches zero, the row's ``live`` flag drops: it stops
    sampling (its token freezes), stops writing cache, and stops advancing
    ``lengths`` — overrun tokens are never produced, only the forward's
    static batch lanes still run. Each chunk therefore returns per-row
    ``n_valid``: how many of its ``n_steps`` tokens are real.

    ``sample_fn(logits_f32 [B, V], live [B], carry) -> (next [B] int32,
    carry, aux)`` supplies sampling — the engine threads its PRNG keys and
    penalty counts through ``carry`` and collects per-step ``aux`` (logprob
    records) stacked over steps. ``model_call(ck, cv, tok, pos, live)``
    overrides the forward for member-vmapped engines; the default is
    :func:`decode_step` on ``params``.

    Returns ``(tokens [B, n_steps], valid [B, n_steps] bool, n_valid [B],
    live, budget, cache_k, cache_v, lengths, sample_carry, aux)`` — the
    finish state (``live``/``budget``) is device-resident engine state, so
    a later in-flight chunk dispatched before the host has read this one
    still skips the rows that finished here.
    """
    if model_call is None:
        def model_call(ck, cv, tok, pos, wm):
            return decode_step(params, spec, tok, pos, ck, cv,
                               write_mask=wm, history=history)

    def step(carry, _):
        tok, lens, lv, bud, ck, cv, s_carry = carry
        pos = jnp.where(lv, lens, 0)
        logits, ck, cv = model_call(ck, cv, tok, pos, lv)
        with jax.named_scope("sample"):
            nxt, s_carry, aux = sample_fn(
                logits.astype(jnp.float32), lv, s_carry)
        nxt = jnp.where(lv, nxt, tok)
        lens = lens + lv.astype(lens.dtype)
        bud = bud - lv.astype(bud.dtype)
        # The row's own finish check, applied AFTER this step's token (the
        # EOS token itself is valid and delivered): next step it is dead.
        fin = lv & ((nxt == eos) | (bud <= 0))
        out = (nxt, lv) + tuple(aux)
        return (nxt, lens, lv & ~fin, bud, ck, cv, s_carry), out

    (token, lengths, live, budget, cache_k, cache_v, sample_carry), ys = \
        lax.scan(step, (token, lengths, live, budget, cache_k, cache_v,
                        sample_carry), None, length=n_steps)
    toks, valid = ys[0].T, ys[1].T                    # [B, n_steps]
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    return (toks, valid, n_valid, live, budget, cache_k, cache_v, lengths,
            sample_carry, ys[2:])


def decode_loop(
    params: Params,
    spec: ModelSpec,
    n_steps: int,
    n_chunks: int,
    token: jnp.ndarray,    # [B] current token ids
    lengths: jnp.ndarray,  # [B] #tokens already in cache per row
    live: jnp.ndarray,     # [B] bool: rows decoding in this dispatch
    budget: jnp.ndarray,   # [B] int32: tokens each row may still produce
    eos: jnp.ndarray,      # [B] int32: per-row EOS id (-1 = none)
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    sample_fn,
    sample_carry,
    history: int | None = None,
    model_call=None,
):
    """Megachunk decode: up to ``n_chunks`` :func:`decode_chunk` bodies in
    ONE device-resident program ("Kernel Looping", PAPERS.md — after
    per-step syncs are gone, the chunk-dispatch boundary itself is the
    next tax on the token critical path).

    The outer ``lax.scan`` replays the exact per-chunk body back to back
    with no host dispatch in between; an **all-rows-finished early exit**
    (``lax.cond`` on ``any(live)``) skips the remaining chunk bodies'
    forwards once every row has finished on device, so a batch that
    completes in chunk 1 does not burn ``n_chunks`` chunks of compute —
    the skipped iterations pass the carry through untouched. Sampled
    tokens land in a device-resident ``[n_chunks, B, n_steps]`` ring
    buffer with per-chunk ``n_valid`` counts, which is what lets the host
    drain completed chunk segments incrementally instead of pacing every
    chunk boundary.

    ``n_chunks == 1`` is NOT special-cased here on purpose: the engine
    dispatches plain :func:`decode_chunk` for ``decode_loop=1`` so unfused
    users compile the exact pre-existing program (the cache-key pin in
    tests/test_decode_loop.py).

    Returns ``(toks [n_chunks, B, n_steps], n_valid [n_chunks, B],
    token [B], live, budget, cache_k, cache_v, lengths, sample_carry,
    aux)`` — ``token`` is the final carried token per row (frozen at each
    row's last real token), and every ``aux`` leaf gains a leading
    ``n_chunks`` axis over its per-chunk ``[n_steps, ...]`` shape.
    """
    def run_chunk(op):
        tok, lens, lv, bud, ck, cv, s_carry = op
        (toks, _valid, n_valid, lv, bud, ck, cv, lens, s_carry, aux) = \
            decode_chunk(params, spec, n_steps, tok, lens, lv, bud, eos,
                         ck, cv, sample_fn, s_carry, history=history,
                         model_call=model_call)
        # toks[:, -1] IS the carried token (dead rows freeze theirs).
        return (toks[:, -1], lens, lv, bud, ck, cv, s_carry), \
            (toks, n_valid, aux)

    carry0 = (token, lengths, live, budget, cache_k, cache_v, sample_carry)
    # The dead branch must emit the same output pytree as a real chunk;
    # eval_shape is trace-free, so tracing decode_loop inside jit costs
    # one abstract pass, never a second compile of the chunk body.
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


def _layer_body(carry_x, block, spec: ModelSpec, positions, cos, sin, attn_fn,
                token_mask=None):
    """One transformer block: norm → qkv(+rope) → attn_fn → norm → mlp.

    Shared by every cache-free forward variant; ``attn_fn(q, k, v)`` is the
    only thing that differs (dense XLA attention, ring attention, ...).
    ``token_mask`` keeps right-padding rows out of MoE expert capacity.
    The prefill path has its own body — it additionally threads the KV cache
    through the scan carry."""
    h = _norm(carry_x, block["attn_norm_w"], block.get("attn_norm_b"), spec)
    q, k, v = _qkv(h, block, spec)
    if spec.pos == "rope":
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    with jax.named_scope("attn.core"):
        attn = attn_fn(q, k, v)
    added = _attn_out(attn, block, carry_x.dtype, spec.attn_out_mult)
    if spec.ssm_heads:
        # no cache: every row from a zero state, through its real positions
        b, t = carry_x.shape[:2]
        state, tail = ssm.init_carry(spec, b, carry_x.dtype)
        n_valid = (jnp.full((b,), t, jnp.int32) if token_mask is None
                   else jnp.sum(token_mask, axis=1, dtype=jnp.int32))
        added = added + ssm.mixer(h, block, spec, state, tail, n_valid)[0]
    carry_x = carry_x + added
    h2 = _norm(carry_x, block["mlp_norm_w"], block.get("mlp_norm_b"), spec)
    mlp = (_moe_mlp(h2, block, spec, token_mask=token_mask)
           if spec.is_moe else _dense_mlp(h2, block, spec))
    return carry_x + mlp, None


def _scan_layers(params, spec: ModelSpec, tokens, attn_fn, remat: bool,
                 lengths=None, unembed: bool = True):
    if spec.layer_pattern:
        raise NotImplementedError(
            "a spec with a layer_pattern has no cache-free forward pass "
            "(embeddings, scoring, training): it is served through the cache")
    b, t = tokens.shape
    positions = jnp.arange(t)
    x = _embed(params, spec, tokens, positions)
    cos, sin = rope_cos_sin_for(spec)
    token_mask = (None if lengths is None
                  else jnp.arange(t)[None, :] < lengths[:, None])

    def body(carry_x, block):
        return _layer_body(carry_x, block, spec, positions, cos, sin, attn_fn,
                           token_mask=token_mask)

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["blocks"])
    x = _final_norm(params, spec, x)
    return _unembed(params, spec, x) if unembed else x


def forward_logits(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,  # [B, T]
    remat: bool = False,
    lengths: jnp.ndarray | None = None,  # [B] — gates MoE capacity for pads
) -> jnp.ndarray:
    """Full-sequence logits [B, T, V] — the training-step / eval forward
    (no KV cache; used by the multi-chip dry run's loss+grad and by tests
    that check prefill/decode consistency against a cache-free ground
    truth). Right-padded batches of MoE models must pass ``lengths`` —
    pad rows would otherwise consume expert capacity ahead of later rows'
    real tokens (see _moe_mlp_grouped)."""
    mask = causal_mask(tokens.shape[1], tokens.shape[1],
                       window=spec.sliding_window)
    return _scan_layers(
        params, spec, tokens, lambda q, k, v: attention(q, k, v, mask),
        remat, lengths=lengths,
    )


def forward_hidden(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,   # [B, T]
    lengths: jnp.ndarray | None = None,  # [B] — gates MoE capacity for pads
) -> jnp.ndarray:
    """Final-norm hidden states [B, T, D] — the embeddings forward.

    Same scanned body as :func:`forward_logits` minus the unembed matmul
    (a [T, D]·[D, V] save — at 128k vocab the unembed dwarfs the pooled
    read the embeddings path actually needs). Causal attention means a
    valid position's state never depends on the right-padding behind it;
    the caller masks pads out of its pooling instead.
    """
    mask = causal_mask(tokens.shape[1], tokens.shape[1],
                       window=spec.sliding_window)
    return _scan_layers(
        params, spec, tokens, lambda q, k, v: attention(q, k, v, mask),
        remat=False, lengths=lengths, unembed=False,
    )


def forward_logits_sp(
    params: Params,
    spec: ModelSpec,
    tokens: jnp.ndarray,   # [B, T] — T divisible by the mesh's sp axis
    lengths: jnp.ndarray,  # [B]
    mesh,
    remat: bool = False,
    sp_impl: str = "ring",
) -> jnp.ndarray:
    """Sequence-parallel full-sequence logits via ring attention.

    Long-context path (SURVEY.md §5.7): attention runs under shard_map with
    the sequence sharded over the mesh's ``sp`` axis — per-device K/V memory
    is O(T/sp) inside the ring; everything else is left to GSPMD (dp/tp).

    Sliding-window specs are rejected: the ring computes full causal
    attention, and silently widening a windowed model's receptive field
    would change its output (window support inside the ring — where ≥
    W-distant hops could skip entirely — is future work).
    GQA is grouped inside the ring — the blocks riding the ICI ring stay at
    KV-head width (no repeat_kv broadcast)."""
    if spec.sliding_window > 0 and sp_impl != "ulysses":
        raise ValueError(
            "sliding_window specs cannot use ring attention (sp>1): the "
            "ring computes full causal attention and would silently widen "
            "the model's receptive field (sp_impl=ulysses supports windows)")
    if sp_impl == "ulysses":
        def sp_attn(q, k, v):
            return ulysses_prefill_attention(
                q, k, v, lengths, mesh, window=spec.sliding_window)
    else:
        def sp_attn(q, k, v):
            return ring_prefill_attention(q, k, v, lengths, mesh)

    return _scan_layers(params, spec, tokens, sp_attn, remat, lengths=lengths)


def init_cache(spec: ModelSpec, batch: int, dtype=None, kv_quant: str | None = None):
    """Preallocated KV cache: [L, B, max_seq, K·hd] × 2 (positions-major,
    the heads flattened: the representation's note at the top of this file).

    ``kv_quant="int8"`` stores each side as ``(int8 values, f32 per-token
    scales)`` — HALF the cache HBM capacity and half the bytes every decode
    step streams from the history window (decode attention contracts
    natively in int8, ops.attention.decode_attention_q8). At llama-3-8b /
    8k window the bf16 cache is 1.07 GB per slot; int8 is 0.54 GB.

    A spec with a ``layer_pattern`` gets a cache per layer kind instead
    (models/patterned.py: ``KindKV``), in bf16 only. A spec with a mixer
    (``ssm_heads``) gets each side as a :class:`StateKV`: the rectangle and,
    per layer and row, the float32 state (K side) or the convolution's tail
    (V side), in bf16 only."""
    if spec.layer_pattern:
        assert kv_quant is None, "a patterned spec's cache is not quantized"
        return patterned.init_cache(spec, batch, dtype)
    dt = jnp.dtype(dtype or spec.dtype)
    shape = (spec.n_layers, batch, spec.max_seq,
             spec.n_kv_heads * spec.head_dim)
    if spec.ssm_heads:
        assert kv_quant is None, "a cache that holds a state is not quantized"
        state, tail = ssm.init_carry(spec, batch, dt, (spec.n_layers,))
        return (StateKV(jnp.zeros(shape, dt), state),
                StateKV(jnp.zeros(shape, dt), tail))
    if kv_quant == "int8":
        side = lambda: (jnp.zeros(shape, jnp.int8),  # noqa: E731
                        jnp.zeros(shape[:-1] + (spec.n_kv_heads,),
                                  jnp.float32))
        return side(), side()
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)
