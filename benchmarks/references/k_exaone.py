"""A plain reference of K-EXAONE-236B-A23B's language model, for ``correct``.

Written from the published ``config.json`` (LGAI-EXAONE/K-EXAONE-236B-A23B,
``model_type`` ``exaone_moe``) and its description: 64 query heads of 128 over
8 key/value heads; layers in the pattern ``LLLG``, ``L`` attending the last
``sliding_window`` positions and ``G`` all of them; layer 0 a SwiGLU MLP of
``intermediate_size``, every later layer 128 routed experts of
``moe_intermediate_size`` scored by a sigmoid, the 8 largest picked, weighted
``routed_scaling_factor * s_i / sum_picked s``, beside one shared expert;
RMSNorm; an untied head. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one layer at a time so that it
fits beside the weights: the whole sequence at once, no cache, no kernel, no
batching, no expert buffers, and no code shared with ``quorum_tpu/models``.

What the published ``config`` does not say and the family's convention
settles (a configuration file lists the same four under ``assumed``; the
served program implements the same choices):

  (a) full-attention layers apply no rotary embedding, window layers do;
  (b) RMSNorm over each q and k head, weight [head_dim], before the rotary
      step;
  (c) each sub-layer's *output* is normalised before the residual add:
      ``h + norm(attn(h))``, ``h + norm(mlp(h))`` (EXAONE 4.0);
  (d) the router has a per-expert selection bias, added to the score for the
      pick only; the weights come from the scores themselves.

Departures, each because of what this chip holds, and the same in the served
program:

  - one chip's share of a layer that several chips hold: the router
    scores all 128 experts, only the picks on the experts held here are
    computed, and what the absent experts would add is left out of the
    layer's output, which goes on to the next layer as it is;
  - the head is this chip's rows of the vocabulary, and the log-softmax is
    over them;
  - the multi-token-prediction layer is not loaded;
  - the rotary embedding rotates the pairs ``(x[i], x[i + head_dim/2])``, the
    layout of the Hugging Face checkpoints.

``CHANGES`` are the controls of the tier-1 tests and of PERF.md section 2a:
each turns one of the above into something else, and has to come out as not
correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHANGES = ("scoring", "scale", "routed", "rope_full", "window")


def config_of(spec) -> dict:
    """The plain numbers of the program's spec."""
    return {"n_layers": spec.n_layers, "n_heads": spec.n_heads,
            "n_kv_heads": spec.n_kv_heads, "head_dim": spec.head_dim,
            "eps": spec.norm_eps, "theta": spec.rope_theta,
            "window": spec.sliding_window, "pattern": spec.layer_pattern,
            "first_dense": spec.first_dense, "n_experts": spec.n_experts,
            "top_k": spec.experts_per_token, "scale": spec.router_scale,
            "held": spec.held, "expert_first": spec.expert_first,
            "shared": spec.n_shared_experts,
            # these three, "scale" and "window" are the controls' to change
            "scoring": "sigmoid", "rope_full": False, "routed": True}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta):
    """x [T, H, hd], position t = row t; frequencies theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, window: int, rope: bool, cfg: dict):
    """x [T, D] -> [T, D]. ``window`` 0: causal over everything; else key j
    is seen from query i iff i - window < j <= i."""
    t = x.shape[0]
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = (x @ w["wq"]).reshape(t, h, hd)
    k = (x @ w["wk"]).reshape(t, kv, hd)
    v = (x @ w["wv"]).reshape(t, kv, hd)
    q = rms_norm(q, w["q_norm"], cfg["eps"])
    k = rms_norm(k, w["k_norm"], cfg["eps"])
    if rope:
        q, k = rotary(q, cfg["theta"]), rotary(k, cfg["theta"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask = mask & (j > i - window)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, h * hd) @ w["wo"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, router, bias, cfg: dict):
    """Per token the weight of every expert, zero where it was not picked:
    [T, n_experts]."""
    logits = x @ router
    if cfg["scoring"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:  # a control: some other score
        s = jax.nn.softmax(logits, axis=-1)
    order = jnp.argsort(-(s + bias), axis=-1)[:, : cfg["top_k"]]
    picked = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                  order].set(1.0)
    w = s * picked
    return cfg["scale"] * w / jnp.sum(w, axis=-1, keepdims=True)


def forward_for(backend, f32, take, changes: dict | None = None):
    """``forward(tokens, position)``: float32 log-probabilities over the
    vocabulary rows held here at ``position``. ``f32`` turns a weight leaf of
    the program to float32 (or to the control's precision), ``take`` indexes
    one; ``changes`` overrides numbers of :func:`config_of` (the controls)."""
    spec, params = backend.engine.spec, backend.engine.params
    assert set(changes or {}) <= set(CHANGES), changes
    cfg = dict(config_of(spec), **(changes or {}))
    static = functools.partial(jax.jit, static_argnames=("window", "rope"))

    @static
    def attn_part(x, norm_w, w, window, rope):
        with jax.default_matmul_precision("highest"):
            w = {k: f32(v) for k, v in w.items()}
            return x + rms_norm(attention(x, w, window, rope, cfg),
                                f32(norm_w), cfg["eps"])

    @jax.jit
    def mlp(x, w_gate, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return swiglu(x, f32(w_gate), f32(w_up), f32(w_down))

    @jax.jit
    def router(x, w, bias):
        with jax.default_matmul_precision("highest"):
            return route(x, f32(w), bias.astype(jnp.float32), cfg)

    @jax.jit
    def add_normed(x, out, norm_w):
        return x + rms_norm(out, f32(norm_w), cfg["eps"])

    @jax.jit
    def head(x, position, norm_w, lm_head):
        with jax.default_matmul_precision("highest"):
            hid = rms_norm(x[position], f32(norm_w), cfg["eps"])
            return jax.nn.log_softmax(hid @ f32(lm_head))

    def mlp_out(x, lyr, i):
        if i < cfg["first_dense"]:
            return mlp(x, *(take(lyr[k], 0)
                            for k in ("w_gate", "w_up", "w_down")))
        out = jnp.zeros_like(x)
        if cfg["routed"]:
            weights = router(x, take(lyr["router"], 0),
                             take(lyr["router_bias"], 0))
            for e in range(cfg["held"]):
                col = weights[:, cfg["expert_first"] + e]
                out = out + col[:, None] * mlp(x, *(
                    take(lyr[k], 0, e)
                    for k in ("moe_w_gate", "moe_w_up", "moe_w_down")))
        if cfg["shared"]:
            out = out + mlp(x, *(take(lyr["shared"][k], 0)
                                 for k in ("w_gate", "w_up", "w_down")))
        return out

    def forward(tokens, position):
        x = take(params["tok_emb"], jnp.asarray(tokens, jnp.int32)).astype(
            jnp.float32)
        for i in range(cfg["n_layers"]):
            lyr = params["layers"][f"{i:02d}"]
            window_layer = cfg["pattern"][i % len(cfg["pattern"])] == "L"
            names = {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
                     "q_norm": "q_norm_w", "k_norm": "k_norm_w"}
            x = attn_part(
                x, take(lyr["attn_norm_w"], 0),
                {k: take(lyr[v], 0) for k, v in names.items()},
                window=cfg["window"] if window_layer else 0,
                rope=bool(window_layer or cfg["rope_full"]))
            x = add_normed(x, mlp_out(x, lyr, i), take(lyr["mlp_norm_w"], 0))
        return np.asarray(head(x, position, params["final_norm_w"],
                               params["lm_head"]))

    return forward
