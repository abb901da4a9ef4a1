"""A plain reference of the Mistral decoder block, for ``correct``.

Written from the published description (Jiang et al. 2023, "Mistral 7B", and
the model's ``config.json``): pre-norm residual blocks of RMSNorm, rotary
position embedding, grouped-query attention under a causal sliding-window
mask, and a SwiGLU feed-forward; a final RMSNorm and an untied output head.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, and no code shared with ``quorum_tpu/models``.

One departure, noted: the rotary embedding rotates the pairs
``(x[i], x[i + head_dim/2])`` (the layout of the Hugging Face checkpoints)
and not the interleaved pairs of the original release. The two differ by a
fixed permutation of the q/k projections' columns, and the served model uses
the same layout, so on random weights only this one agrees.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """x [T, H, hd] -> rotated by position; frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q [T, H, hd], k/v [T, K, hd]: each query head reads the key/value head
    of its group; position i attends j <= i with i - j < window."""
    t, h, hd = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, axis=-1), v)


def block(x, w, cfg):
    """One decoder layer. ``w``: float32 matrices wq [D, H*hd], wk/wv
    [D, K*hd], wo [H*hd, D], w_gate/w_up [D, F], w_down [F, D], and the two
    norm vectors."""
    t = x.shape[0]
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(t)
    a = rms_norm(x, w["attn_norm"], cfg["eps"])
    q = rotary((a @ w["wq"]).reshape(t, h, hd), pos, cfg["theta"])
    k = rotary((a @ w["wk"]).reshape(t, kv, hd), pos, cfg["theta"])
    v = (a @ w["wv"]).reshape(t, kv, hd)
    x = x + attention(q, k, v, cfg["window"]).reshape(t, h * hd) @ w["wo"]
    m = rms_norm(x, w["mlp_norm"], cfg["eps"])
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def logprobs_at(x, position, final_norm, lm_head, cfg):
    """log-softmax over the vocabulary at one position of the last hidden
    state."""
    hid = rms_norm(x[position], final_norm, cfg["eps"])
    return jax.nn.log_softmax(hid @ lm_head)
