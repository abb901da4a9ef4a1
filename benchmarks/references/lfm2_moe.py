"""A plain reference of LFM2-8B-A1B's language model, for ``correct``.

Written from the published ``config.json`` (LiquidAI/LFM2-8B-A1B,
``model_type`` ``lfm2_moe``) and its description: a stream of
``hidden_size``, pre-norm RMSNorm blocks, each an operator and then a
feed-forward part, the operator by ``layer_types``. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
the whole sequence at once, the convolution a direct sum over three shifted
copies of its input, attention in blocks of queries, the experts a loop, a
layer's parts and the head's columns one piece at a time so that fourteen
layers at the published widths fit beside the program's bfloat16 weights on
one chip. No cache, no carried tail, no batching, no expert tiles, and no
code shared with ``quorum_tpu/models``.

The equations (``h`` the stream, ``D`` the hidden size):

    u = RMSNorm_operator(h)
    "conv":            [B | C | X] = u W_in          three parts of D
                       z = B * X                     elementwise
                       v[t] = sum_{k<L} w[k] z[t - (L-1) + k]   z[<0] = 0
                       h = h + (C * v) W_out         no activation, no bias
    "full_attention":  q, k, v = u Wq, u Wk, u Wv    32 heads over 8 of 64
                       q, k = RMSNorm over each head's 64, THEN rotary
                       h = h + softmax(q k^T / 8, causal) v Wo
    g = RMSNorm_ffn(h)
    layer < num_dense_layers:  h = h + W2 (silu(W1 g) * (W3 g))
    else:  s = sigmoid(g Wr);  P = top-k of (s + expert_bias)
           w_j = routed_scaling_factor * s_j / (sum_P s + 1e-6)
           h = h + sum_{j in P} w_j E_j(g),   E_j a SwiGLU
    log-softmax(RMSNorm_embedding(h) Embed^T)

What the published ``config`` leaves open, *assumed* (the configuration file
lists the same under ``assumed``; the served program implements the same
choices):

  (a) the input projection's three parts are B, C, X in that order (the
      Hugging Face module's ``chunk(3)``);
  (b) convolution tap ``k`` of ``conv_L_cache`` meets the input
      ``conv_L_cache - 1 - k`` positions back (the last tap the current one);
  (c) a head is ``hidden_size / num_attention_heads`` = 64 wide;
  (d) the rotary embedding rotates the pairs ``(x[i], x[i + 32])``, the
      layout of the Hugging Face checkpoints, on every attention layer, after
      the heads' norms;
  (e) the embedding is the head (the 8.3B of the model card is the tied
      count);
  (f) ``expert_bias`` enters the pick only; the weights come from the scores
      themselves (``norm_topk_prob``).

Departure of the served program, carried here as published: the ``1e-6`` in
the weights' denominator, which the program leaves out (a sum of four
sigmoids is 2 to 3: a relative 4e-7, under float32's own rounding).

``CHANGES`` are the controls of the tier-1 tests and of PERF.md section 2a:
each turns the model into something a fault of the served path would
compute, and has to come out as not correct.

  ``conv`` False: a conv layer without its operator (the stream passes).
  ``reset_at`` p: the convolution's input taken as zero before position p
      (a tail lost between two prefill segments, or at the register).
  ``pads`` (p, n): n pad positions (token 0, at rotary positions p ..
      p + n - 1, as a padded segment lays them) run through every layer
      between position p - 1 and position p: the convolution meets them,
      attention never sees them (a padded bucket's pads let into the tail).
  ``rope_full`` False: no rotary embedding on the attention layers.
  ``bias_in_weights`` True: the weights made from score + bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHANGES = ("conv", "reset_at", "pads", "rope_full", "bias_in_weights")
HEAD_ROWS = 16384
QUERY_BLOCK = 512


def config_of(spec) -> dict:
    """The plain numbers of the program's spec."""
    n = spec.n_layers
    return {"n_layers": n, "eps": spec.norm_eps, "heads": spec.n_heads,
            "kv_heads": spec.n_kv_heads, "hd": spec.head_dim,
            "theta": spec.rope_theta, "taps": spec.conv_taps,
            "kinds": [spec.layer_pattern[i % len(spec.layer_pattern)]
                      for i in range(n)],
            "dense": spec.first_dense, "n_experts": spec.n_experts,
            "top_k": spec.experts_per_token, "scale": spec.router_scale,
            # the controls' to change
            "conv": True, "reset_at": None, "pads": None, "rope_full": True,
            "bias_in_weights": False}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """x [T, H, hd] rotated by ``positions``; frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(u, w, since, cfg: dict):
    """u [T, D], the block's normed input -> [T, D]. ``since`` [T] int: row
    t's convolution meets no input before row ``since[t]``."""
    t, taps = u.shape[0], cfg["taps"]
    b, c, x = jnp.split(u @ w["conv_in"], 3, axis=-1)
    z = b * x
    at = jnp.arange(t)
    v = jnp.zeros_like(z)
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype),
                                   z[:t - back]], axis=0)
        met = (at - back >= since)[:, None]
        v = v + w["conv_w"][k] * jnp.where(met, shifted, 0.0)
    return (c * v) @ w["conv_out"]


def attention(u, w, positions, seen, cfg: dict):
    """u [T, D] -> [T, D]. ``seen`` [T, T]: row i attends row j."""
    t = u.shape[0]
    h, kv, hd = cfg["heads"], cfg["kv_heads"], cfg["hd"]
    q = rms_norm((u @ w["wq"]).reshape(t, h, hd), w["q_norm_w"], cfg["eps"])
    k = rms_norm((u @ w["wk"]).reshape(t, kv, hd), w["k_norm_w"], cfg["eps"])
    v = (u @ w["wv"]).reshape(t, kv, hd)
    if cfg["rope_full"]:
        q = rotary(q, positions, cfg["theta"])
        k = rotary(k, positions, cfg["theta"])
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        a = jnp.einsum("ihd,jhd->hij", q[rows], k) / jnp.sqrt(jnp.float32(hd))
        a = jnp.where(seen[rows][None], a, -jnp.inf)
        out.append(jnp.einsum("hij,jhd->ihd", jax.nn.softmax(a, axis=-1), v))
    return jnp.concatenate(out, axis=0).reshape(t, h * hd) @ w["wo"]


def swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def route(g, router, bias, cfg: dict):
    """Per token the weight of every expert, zero where it was not picked:
    [T, n_experts]."""
    s = jax.nn.sigmoid(g @ router)
    order = jnp.argsort(-(s + bias), axis=-1)[:, : cfg["top_k"]]
    picked = jnp.zeros_like(s).at[jnp.arange(g.shape[0])[:, None],
                                  order].set(1.0)
    w = (s + bias if cfg["bias_in_weights"] else s) * picked
    return cfg["scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)


CONV = ("conv_in", "conv_w", "conv_out")
ATTENTION = ("wq", "wk", "wv", "wo", "q_norm_w", "k_norm_w")
MLP = ("w_gate", "w_up", "w_down")
EXPERT = ("moe_w_gate", "moe_w_up", "moe_w_down")


def rows_of(n_tokens: int, cfg: dict):
    """How the sequence lies in the arrays the layers see: (token index or -1
    for a pad, rotary position, is a pad) per row, and the row of each token.
    Without the ``pads`` control the rows are the tokens."""
    if not cfg["pads"]:
        at = np.arange(n_tokens)
        return at, at, np.zeros(n_tokens, bool), at
    p, n = cfg["pads"]
    p = min(p, n_tokens)
    token = np.concatenate([np.arange(p), np.full(n, -1),
                            np.arange(p, n_tokens)])
    position = np.concatenate([np.arange(p), p + np.arange(n),
                               np.arange(p, n_tokens)])
    row_of = np.concatenate([np.arange(p), n + np.arange(p, n_tokens)])
    return token, position, token < 0, row_of


def forward_for(backend, f32, take, changes: dict | None = None):
    """``forward(tokens, position)``: float32 log-probabilities over the
    vocabulary at ``position``. ``f32`` turns a weight leaf of the program
    to float32 (or to the control's precision), ``take`` indexes one;
    ``changes`` overrides numbers of :func:`config_of` (the controls)."""
    spec, params = backend.engine.spec, backend.engine.params
    assert set(changes or {}) <= set(CHANGES), changes
    cfg = dict(config_of(spec), **(changes or {}))

    def part(fn):
        @jax.jit
        def run(*args, w):
            with jax.default_matmul_precision("highest"):
                return fn(*args, {k: f32(v) for k, v in w.items()}, cfg)
        return run

    conv_part = part(lambda u, since, w, cfg: short_conv(u, w, since, cfg))
    attn_part = part(lambda u, positions, seen, w, cfg: attention(
        u, w, positions, seen, cfg))

    @jax.jit
    def normed(x, norm_w):
        return rms_norm(x, f32(norm_w), cfg["eps"])

    @jax.jit
    def mlp(g, w_gate, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return swiglu(g, f32(w_gate), f32(w_up), f32(w_down))

    @jax.jit
    def router(g, w, bias):
        with jax.default_matmul_precision("highest"):
            return route(g, f32(w), bias.astype(jnp.float32), cfg)

    @jax.jit
    def head_rows(hid, rows):
        with jax.default_matmul_precision("highest"):
            return f32(rows) @ hid

    def leaves_of(i):
        """Layer ``i``'s leaves in the program's tree and its index in
        them: a leaf a layer with a leading 1, or, where the program holds
        the layers of whole periods stacked (``<first layer>x<count>``, a
        period's slot a leaf), the stack and the layer's place in it."""
        layers = params["layers"]
        if f"{i:02d}" in layers:
            return layers[f"{i:02d}"], 0
        for key, lyr in layers.items():
            first, _, count = key.partition("x")
            if count:
                stride = (cfg["n_layers"] - cfg["dense"]) // int(count)
                at, rest = divmod(i - int(first), stride)
                if rest == 0 and 0 <= at < int(count):
                    return lyr, at
        raise KeyError(i)

    def feed_forward(g, lyr, at, i):
        if i < cfg["dense"]:
            return mlp(g, *(take(lyr[k], at) for k in MLP))
        weights = router(g, take(lyr["router"], at),
                         take(lyr["router_bias"], at))
        out = jnp.zeros_like(g)
        for e in range(cfg["n_experts"]):
            out = out + weights[:, e][:, None] * mlp(
                g, *(take(lyr[k], at, e) for k in EXPERT))
        return out

    def forward(tokens, position):
        token, where, pad, row_of = rows_of(len(tokens), cfg)
        ids = np.where(pad, 0, np.asarray(tokens, np.int64)[token])
        x = take(params["tok_emb"], jnp.asarray(ids, jnp.int32)).astype(
            jnp.float32)
        at = np.arange(len(ids))
        # a row attends the rows before it and itself; no token attends a pad
        seen = jnp.asarray((at[None, :] <= at[:, None])
                           & (~pad[None, :] | pad[:, None]))
        positions = jnp.asarray(where, jnp.int32)
        since = np.zeros(len(ids), np.int32)
        if cfg["reset_at"] is not None and cfg["reset_at"] < len(tokens):
            since[row_of[cfg["reset_at"]]:] = row_of[cfg["reset_at"]]
        since = jnp.asarray(since)
        for i in range(cfg["n_layers"]):
            lyr, at = leaves_of(i)
            u = normed(x, take(lyr["attn_norm_w"], at))
            if cfg["kinds"][i] == "C":
                if cfg["conv"]:
                    x = x + conv_part(u, since, w={
                        k: take(lyr[k], at) for k in CONV})
            else:
                x = x + attn_part(u, positions, seen, w={
                    k: take(lyr[k], at) for k in ATTENTION})
            x = x + feed_forward(normed(x, take(lyr["mlp_norm_w"], at)),
                                 lyr, at, i)
        hid = normed(x[row_of[position]], params["final_norm_w"])
        emb = params["tok_emb"]
        logits = jnp.concatenate([
            head_rows(hid, emb[r:r + HEAD_ROWS])
            for r in range(0, emb.shape[0], HEAD_ROWS)])
        return np.asarray(jax.nn.log_softmax(logits))

    return forward
