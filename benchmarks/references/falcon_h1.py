"""A plain reference of Falcon-H1's language model, for ``correct``.

Written from the published ``config.json`` (tiiuae/Falcon-H1-34B-Instruct,
``model_type`` ``falcon_h1``) and the papers its keys follow: a Mamba-2
mixer (Dao & Gu, "Transformers are SSMs", 2024) beside grouped-query
attention in every block, muP multipliers on every product (Falcon-H1
technical report, 2025). Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence at once, the
recurrence as one ``lax.scan`` over the positions (no chunks, no carried
state, no cache, no batching), a block's three parts and the head's columns
one piece at a time so that six blocks at the published widths and the whole
head fit beside the program's bfloat16 weights on one chip. No code shared
with ``quorum_tpu/models``.

The equations (``d`` the hidden size). Token embedding times
``embedding_multiplier``. Every block, pre-norm RMSNorm:

    u = RMSNorm_in(h)
    a = Attn(u * attention_in_multiplier) * attention_out_multiplier
    m = Mamba2(u * ssm_in_multiplier)     * ssm_out_multiplier
    h = h + a + m
    v = RMSNorm_ff(h)
    h = h + W_down(silu(W_gate v * mlp_multipliers[0]) * (W_up v))
            * mlp_multipliers[1]

``Attn``: query heads over fewer key/value heads, no bias, keys times
``key_multiplier`` after their projection, rotary embedding over the whole
head, causal, scores / sqrt(head size).

``Mamba2`` (``d_ssm`` = H heads of P channels, state N, G groups):
``[z | x | B | C | dt] = W_in u`` of widths ``d_ssm | d_ssm | G N | G N | H``,
each part times its entry of ``ssm_multipliers``; ``[x | B | C]`` through a
depthwise causal convolution of ``d_conv`` taps with bias, then SiLU; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A)
S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y =
RMSNorm_grouped(y * silu(z))``; ``W_out y``.

Final RMSNorm, untied head, logits times ``lm_head_multiplier``.

What the published ``config`` leaves open, *assumed* (the configuration file
lists the same under ``assumed``; the served program implements the same
choices):

  (a) the five parts of the input projection are z, x, B, C, dt in that
      order, and ``ssm_multipliers`` are theirs in that order;
  (b) ``mlp_multipliers[0]`` scales the gate's product before its SiLU,
      ``mlp_multipliers[1]`` the down-projection's output;
  (c) ``mamba_norm_before_gate`` false: the gate first, then the norm, over
      each group's ``d_ssm / G`` channels, with a learned weight;
  (d) the rotary embedding rotates the pairs ``(x[i], x[i + hd/2])``, the
      layout of the Hugging Face checkpoints;
  (e) convolution tap ``k`` of ``d_conv`` meets the input ``d_conv - 1 - k``
      positions back (the last tap the current position);
  (f) the recurrent state is float32; ``dt`` is not clipped.

``CHANGES`` are the controls of the tier-1 tests and of PERF.md section 2a:
each turns the model into something a fault of the served path would
compute, and has to come out as not correct.

  ``mixer`` False: the block without the mixer's branch.
  ``reset_at`` p: the recurrent state set to zero before position p (a
      state lost between two prefill segments).
  ``pads`` (p, n): n pad positions (token 0, at rotary positions p ..
      p + n - 1, as a padded segment lays them) run through the recurrence
      and the convolution between position p - 1 and position p; attention
      never sees them (a padded bucket's pad positions let into the state).
  ``state_dtype``: the state rounded to that dtype after every position
      (what a bfloat16 state reads).
  ``keys_from`` p: positions p and after attend no key before p (the K and
      V of an earlier prefill segment lost; the mixer untouched).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHANGES = ("mixer", "reset_at", "pads", "state_dtype", "keys_from")
HEAD_COLUMNS = 32768
QUERY_BLOCK = 512


def config_of(spec) -> dict:
    """The plain numbers of the program's spec."""
    return {"n_layers": spec.n_layers, "d": spec.d_model,
            "eps": spec.norm_eps, "heads": spec.n_heads,
            "kv_heads": spec.n_kv_heads, "hd": spec.head_dim,
            "theta": spec.rope_theta, "ssm_heads": spec.ssm_heads,
            "ssm_p": spec.ssm_head_dim, "ssm_n": spec.ssm_state,
            "groups": spec.ssm_groups, "taps": spec.ssm_conv,
            "emb": spec.emb_scale, "attn_in": spec.attn_in_mult,
            "attn_out": spec.attn_out_mult, "key": spec.key_mult,
            "ssm_in": spec.ssm_in_mult, "ssm_out": spec.ssm_out_mult,
            "ssm_parts": tuple(spec.ssm_mults), "gate": spec.mlp_gate_mult,
            "down": spec.mlp_down_mult, "head": spec.lm_head_mult,
            # the controls' to change
            "mixer": True, "reset_at": None, "pads": None,
            "state_dtype": "float32", "keys_from": None}


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


def attention(u, w, positions, seen, cfg: dict):
    """u [T, D], the block's normed input -> [T, D]. ``seen`` [T, T]: row i
    attends row j."""
    t = u.shape[0]
    h, kv, hd = cfg["heads"], cfg["kv_heads"], cfg["hd"]
    u = u * cfg["attn_in"]
    q = rotary((u @ w["wq"]).reshape(t, h, hd), positions, cfg["theta"])
    k = rotary(((u @ w["wk"]) * cfg["key"]).reshape(t, kv, hd), positions,
               cfg["theta"])
    v = (u @ w["wv"]).reshape(t, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        a = jnp.einsum("ihd,jhd->hij", q[rows], k) / jnp.sqrt(jnp.float32(hd))
        a = jnp.where(seen[rows][None], a, -jnp.inf)
        out.append(jnp.einsum("hij,jhd->ihd", jax.nn.softmax(a, axis=-1), v))
    out = jnp.concatenate(out, axis=0).reshape(t, h * hd)
    return (out @ w["wo"]) * cfg["attn_out"]


def mamba2(u, w, reset, cfg: dict):
    """u [T, D], the block's normed input -> [T, D]; the recurrence one
    position at a time. ``reset`` [T] bool: the state is zero before that
    row."""
    t = u.shape[0]
    heads, p, n, g = cfg["ssm_heads"], cfg["ssm_p"], cfg["ssm_n"], cfg["groups"]
    d_ssm, gn, taps = heads * p, g * n, cfg["taps"]
    m_z, m_x, m_b, m_c, m_dt = cfg["ssm_parts"]
    proj = (u * cfg["ssm_in"]) @ w["ssm_in"]
    z = proj[:, :d_ssm] * m_z
    xbc = jnp.concatenate([
        proj[:, d_ssm:2 * d_ssm] * m_x,
        proj[:, 2 * d_ssm:2 * d_ssm + gn] * m_b,
        proj[:, 2 * d_ssm + gn:2 * d_ssm + 2 * gn] * m_c], axis=-1)
    dt = proj[:, 2 * d_ssm + 2 * gn:] * m_dt                       # [T, H]
    # depthwise causal convolution: tap k meets the input taps-1-k back
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = w["ssm_conv_b"] + sum(
        w["ssm_conv_w"][k] * padded[k:k + t] for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_ssm].reshape(t, heads, p)
    b = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(t, g, n), heads // g, 1)
    c = jnp.repeat(xbc[:, d_ssm + gn:].reshape(t, g, n), heads // g, 1)
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"])
    a = -jnp.exp(w["ssm_a_log"])                                   # [H]
    # reduce_precision, not a cast there and back: the TPU's compiler takes
    # a pair of converts out (excess precision is allowed), and the control
    # would then be the reference itself
    kept = jnp.finfo(jnp.dtype(cfg["state_dtype"]))

    def position(state, at):
        x_t, b_t, c_t, dt_t, zero = at
        state = jnp.where(zero, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = jax.lax.reduce_precision(state, kept.nexp, kept.nmant)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(position, jnp.zeros((heads, p, n), jnp.float32),
                        (x, b, c, dt, reset))
    y = y + w["ssm_d"][:, None] * x
    y = (y.reshape(t, d_ssm) * jax.nn.silu(z)).reshape(t, g, d_ssm // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg["eps"])
    y = y.reshape(t, d_ssm) * w["ssm_norm_w"]
    return (y @ w["ssm_out"]) * cfg["ssm_out"]


def swiglu(v, w, cfg: dict):
    gate = jax.nn.silu((v @ w["w_gate"]) * cfg["gate"])
    return ((gate * (v @ w["w_up"])) @ w["w_down"]) * cfg["down"]


ATTENTION = ("wq", "wk", "wv", "wo")
MIXER = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
         "ssm_d", "ssm_norm_w", "ssm_out")
MLP = ("w_gate", "w_up", "w_down")


def rows_of(n_tokens: int, cfg: dict):
    """How the sequence lies in the arrays the blocks see: (token index or -1
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
    blocks = params["blocks"]

    def part(fn):
        @jax.jit
        def run(*args, w):
            with jax.default_matmul_precision("highest"):
                return fn(*args, {k: f32(v) for k, v in w.items()}, cfg)
        return run

    attn_part = part(lambda u, positions, seen, w, cfg: attention(
        u, w, positions, seen, cfg))
    mixer_part = part(lambda u, reset, w, cfg: mamba2(u, w, reset, cfg))
    mlp_part = part(lambda v, w, cfg: swiglu(v, w, cfg))

    @jax.jit
    def normed(x, norm_w):
        return rms_norm(x, f32(norm_w), cfg["eps"])

    @jax.jit
    def head_columns(hid, columns):
        with jax.default_matmul_precision("highest"):
            return (hid @ f32(columns)) * cfg["head"]

    def forward(tokens, position):
        token, where, pad, row_of = rows_of(len(tokens), cfg)
        ids = np.where(pad, 0, np.asarray(tokens, np.int64)[token])
        x = take(params["tok_emb"], jnp.asarray(ids, jnp.int32)).astype(
            jnp.float32) * cfg["emb"]
        at = np.arange(len(ids))
        # a row attends the rows before it and itself; no token attends a pad
        seen = jnp.asarray((at[None, :] <= at[:, None])
                           & (~pad[None, :] | pad[:, None]))
        if cfg["keys_from"] is not None:
            late = where >= cfg["keys_from"]
            seen = seen & jnp.asarray(~late[:, None] | late[None, :])
        positions = jnp.asarray(where, jnp.int32)
        reset = np.zeros(len(ids), bool)
        if cfg["reset_at"] is not None and cfg["reset_at"] < len(tokens):
            reset[row_of[cfg["reset_at"]]] = True
        reset = jnp.asarray(reset)
        for i in range(cfg["n_layers"]):
            def of(names):
                return {k: take(blocks[k], i) for k in names}

            u = normed(x, take(blocks["attn_norm_w"], i))
            added = attn_part(u, positions, seen, w=of(ATTENTION))
            if cfg["mixer"]:
                added = added + mixer_part(u, reset, w=of(MIXER))
            x = x + added
            x = x + mlp_part(normed(x, take(blocks["mlp_norm_w"], i)),
                             w=of(MLP))
        hid = normed(x[row_of[position]], params["final_norm_w"])
        lm_head = params["lm_head"]
        logits = jnp.concatenate([
            head_columns(hid, lm_head[:, c:c + HEAD_COLUMNS])
            for c in range(0, lm_head.shape[1], HEAD_COLUMNS)])
        return np.asarray(jax.nn.log_softmax(logits))

    return forward
