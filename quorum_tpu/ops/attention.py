"""Causal attention with grouped-query (GQA) support, XLA-native reference path.

Layout: q [B, H, S, hd]; k/v [B, K, S_kv, hd] with H = K * G query groups.
GQA is expressed by reshaping q to [B, K, G, S, hd] and contracting against
the shared K/V heads — no materialized repeat_kv copies (which would burn HBM
bandwidth); the grouping lives in the einsum and XLA tiles it onto the MXU.
``rows_major=True`` takes k/v as [B, S_kv, K, hd] instead: a view of the dense
cache's positions-major store (models/transformer.py ``init_cache``),
contracted as it lies — the same sums, the axes named in another order.

Softmax runs in float32 regardless of activation dtype. The Pallas
flash-attention kernel (quorum_tpu.ops.flash_attention) replaces the prefill
path on real TPUs; this module is the always-available fallback and the
numerical ground truth the kernel is tested against.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -2.0**30  # large-but-finite: keeps masked softmax NaN-free in bf16/f32


def _group_heads(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    b, h, s, d = q.shape
    return q.reshape(b, n_kv, h // n_kv, s, d)


def attention(
    q: jnp.ndarray,  # [B, H, S, hd]
    k: jnp.ndarray,  # [B, K, S_kv, hd]
    v: jnp.ndarray,  # [B, K, S_kv, hd]
    mask: jnp.ndarray | None = None,  # broadcastable to [B, 1, 1, S, S_kv], bool (True=keep)
    rows_major: bool = False,  # k/v are [B, S_kv, K, hd]
    positions_minor: bool = False,  # k/v are [B, K, hd, S_kv]
) -> jnp.ndarray:
    """Full attention over the given K/V. Returns [B, H, S, hd]."""
    kv = "btkd" if rows_major else "bkdt" if positions_minor else "bktd"
    n_kv = k.shape[2 if rows_major else 1]
    qg = _group_heads(q, n_kv)  # [B, K, G, S, hd]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        f"bkgsd,{kv}->bkgst", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum(f"bkgst,{kv}->bkgsd", probs.astype(v.dtype), v)
    b, k_, g, s, d = out.shape
    return out.reshape(b, k_ * g, s, d)


def causal_mask(s_q: int, s_kv: int, q_offset: jnp.ndarray | int = 0,
                window: int = 0) -> jnp.ndarray:
    """[1, 1, 1, s_q, s_kv] boolean causal mask; query i sits at absolute
    position q_offset + i. ``window`` > 0 adds sliding-window attention
    (mistral): key j visible to query at position p iff p-window < j <= p."""
    qi = jnp.arange(s_q)[:, None] + q_offset
    ki = jnp.arange(s_kv)[None, :]
    keep = ki <= qi
    if window and window > 0:
        keep = keep & (ki > qi - window)
    return keep[None, None, None, :, :]


def prefill_attention(q, k, v, lengths: jnp.ndarray | None = None,
                      window: int = 0) -> jnp.ndarray:
    """Causal self-attention over a [B, ·, S, hd] prompt block.

    ``lengths`` ([B]) masks out right-padding so batched prompts of unequal
    length share one compiled program (static shapes — SURVEY.md §7);
    ``window`` > 0 adds the sliding-window constraint.
    """
    mask = causal_mask(q.shape[2], k.shape[2], window=window)
    if lengths is not None:
        valid = (jnp.arange(k.shape[2])[None, :] < lengths[:, None])  # [B, S_kv]
        mask = mask & valid[:, None, None, None, :]
    return attention(q, k, v, mask)


def decode_attention(
    q: jnp.ndarray,  # [B, H, 1, hd]
    k_cache: jnp.ndarray,  # [B, K, max_seq, hd]; rows_major [B, max_seq, K, hd]
    v_cache: jnp.ndarray,
    length: jnp.ndarray,  # [B] or scalar: #valid cache entries (incl. current token)
    window: int = 0,
    rows_major: bool = False,
    positions_minor: bool = False,  # the caches are [B, K, hd, max_seq]
) -> jnp.ndarray:
    """One decode step against the KV cache (static max_seq, masked by
    length; ``window`` > 0 restricts to the last ``window`` positions)."""
    mask = _decode_keep(
        length,
        k_cache.shape[1 if rows_major else 3 if positions_minor else 2],
        window)
    return attention(q, k_cache, v_cache, mask, rows_major=rows_major,
                     positions_minor=positions_minor)


def _decode_keep(length, n_keys: int, window: int) -> jnp.ndarray:
    """[B, 1, 1, 1, n_keys] bool: the cache entries a decode query at
    position ``length - 1`` sees."""
    length = jnp.asarray(length)
    if length.ndim == 0:
        length = length[None]
    ki = jnp.arange(n_keys)[None, :]
    valid = ki < length[:, None]  # [B, n_keys]
    if window and window > 0:
        valid = valid & (ki >= length[:, None] - window)
    return valid[:, None, None, None, :]


def quantize_rows(x: jnp.ndarray, axis: int = -1) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization along ``axis``: returns ``(q8, scale)``
    with ``x ≈ q8 * scale`` (scale keeps the reduced dim, size 1).
    Deliberately the same amax/127 formulation — including the 1e-30
    all-zero-row floor — as models/quant.py's weight/activation quantizers
    (kept separate only because ops/ must not import models/); a change to
    the formulation belongs in both places. Used by the int8 KV cache's
    write path and its dynamic query/probability quantization."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 127.0
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q8, s


def decode_attention_q8(
    q: jnp.ndarray,        # [B, H, 1, hd] bf16/f32
    k8: jnp.ndarray,       # [B, K, T, hd] int8 cache; rows_major [B, T, K, hd]
    k_scale: jnp.ndarray,  # [B, K, T] f32: k ≈ k8 * k_scale[..., None]
    v8: jnp.ndarray,       # [B, K, T, hd] int8 cache
    v_scale: jnp.ndarray,  # [B, K, T] f32; rows_major [B, T, K]
    length: jnp.ndarray,   # [B] or scalar
    window: int = 0,
    rows_major: bool = False,
) -> jnp.ndarray:
    """One decode step against an int8-quantized KV cache, with the
    contractions run NATIVELY in int8 (int8×int8→int32 on the MXU) — never
    dequantize-into-dot, which materializes a bf16 copy in HBM and made
    int8 *slower* than bf16 for weights (PERF.md §2, the measured-first
    rule this module inherits).

    The per-token scales factor cleanly out of both dots:
      q·kᵀ: k's scale indexes the OUTPUT position t → logits · ks[t].
      p·v:  v's scale indexes the CONTRACTION position t → fold vs[t] into
            the probabilities BEFORE quantizing them over t.
    q (one row per head) and p (one row per query) are dynamically
    quantized amax/127, like activations in models/quant.qeinsum."""
    b, h, s, d = q.shape
    kv = "btkd" if rows_major else "bktd"
    if rows_major:
        k_scale, v_scale = (x.transpose(0, 2, 1) for x in (k_scale, v_scale))
    n_kv, n_keys = k_scale.shape[1:]
    qg = _group_heads(q, n_kv)                        # [B, K, G, 1, hd]
    q8, qs = quantize_rows(qg, axis=-1)               # qs [B, K, G, 1, 1]
    logits_i = jnp.einsum(
        f"bkgsd,{kv}->bkgst", q8, k8, preferred_element_type=jnp.int32)
    scale = d ** -0.5
    logits = (logits_i.astype(jnp.float32) * qs
              * k_scale[:, :, None, None, :]) * scale  # [B, K, G, 1, T]
    logits = jnp.where(_decode_keep(length, n_keys, window), logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    pv = probs * v_scale[:, :, None, None, :]          # fold v's scale in
    p8, ps = quantize_rows(pv, axis=-1)                # ps [B, K, G, 1, 1]
    out_i = jnp.einsum(
        f"bkgst,{kv}->bkgsd", p8, v8, preferred_element_type=jnp.int32)
    out = out_i.astype(jnp.float32) * ps               # [B, K, G, 1, hd]
    return out.reshape(b, h, s, d).astype(q.dtype)
