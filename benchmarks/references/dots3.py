"""A plain reference of dots3-note-prev's language model, for ``correct``.

Written from the published ``config.json`` (dots-studio/dots3-note-prev,
``model_type`` ``dots3_note``) and the papers its keys follow: multi-head
latent attention (DeepSeek-V2/V3), the lightning indexer and top-k selection
of DeepSeek-V3.2-Exp on the full layers, a head-wise sigmoid gate at the
attention output (Qiu et al., arXiv:2505.06708), DeepSeek-V3's ``noaux_tc``
router. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one layer at a time: the whole
sequence at once, no cache, no absorbed products, no kernel, no batching, no
expert buffers; attention in blocks of queries, each against every key, so
that 4,096 positions fit beside the weights. No code shared with
``quorum_tpu/models``.

The equations. ``h = RMSNorm(x)``; blocks ``x + attn(h)``, then
``x + mlp(RMSNorm(x))``.

*Full layer* (H heads, ranks ``q_lora_rank`` / ``kv_lora_rank``, head sizes
nope / rope / v, base ``rope_theta``): ``c_q = RMSNorm(W_qa h) sqrt(D /
q_rank)``; ``q_i = W_qb,i c_q = [q_i^n ; q_i^r]``, ``q_i^r`` rotated.
``[c_kv ; k_r] = W_kva h``, ``c_kv <- RMSNorm(c_kv) sqrt(D / kv_rank)``,
``k_r`` rotated, one for all heads. ``k_i^n = W_kb,i c_kv``, ``v_i = W_vb,i
c_kv``. ``a_tsi = (q_i^n . k_si^n + q_i^r . k_rs) / sqrt(nope + rope)``.
Indexer: ``qI_tj = W_Iq,j c_q``, ``kI_s = LayerNorm(W_Ik h_s)``, the first
``rope`` dims of both rotated, ``w_t = W_Iw h_t``, ``I_ts = sum_j w_tj
relu(qI_tj . kI_s) / sqrt(J) / sqrt(d_I)``; ``S_t`` the ``index_topk``
positions ``s <= t`` of largest ``I_ts`` (all while ``t < index_topk``).
``o_i = sum_{s in S_t} softmax_{S_t}(a_tsi) v_si``. Gate: ``g = sigmoid(W_g
h)``, ``o_i <- g_i o_i``. ``out = W_o [o_1 .. o_H]``.

*Window layer*: the same latent form with the ``swa_*`` sizes and base, its
own matrices, ``s`` in ``(t - window, t]``, no indexer.

*MLP*: the first ``first_k_dense_replace`` layers SwiGLU of
``intermediate_size``; later ones ``s = sigmoid(W_r h)``, the
``num_experts_per_tok`` largest of ``s + b`` picked, weights ``s_i /
sum_picked s`` times ``routed_scaling_factor``, SwiGLU experts of
``moe_intermediate_size``, plus one shared expert.

What the published ``config`` does not say, *assumed* (the configuration
file lists the same under ``assumed``; the served program implements the
same choices):

  (a) pre-norm blocks (the DeepSeek-V3 convention the keys follow);
  (b) ``apply_mla_qkv_lora_rescale``: both latents are multiplied by
      ``sqrt(hidden / rank)`` after their norms (the reading of LongCat-
      Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``);
  (c) ``sliding_window_size`` 513 counts the current position: ``s`` in
      ``(t - 513, t]``;
  (d) the gate is head-wise at the attention output, from the layer's
      normed input, one weight row a head, no bias;
  (e) the indexer's key norm is a LayerNorm with bias, its rotated dims are
      the first ``qk_rope_head_dim``, with the full layers' base;
  (f) the router's selection bias enters the pick only (``noaux_tc``), one
      group.

Departures, each because of what this chip holds, and the same in the served
program: one chip's share of a layer (the router scores all 256 experts, only
the picks on the experts held here are computed, what the absent ones would
add is left out); the head is this chip's rows of the vocabulary and the
log-softmax is over them; the vision and audio towers and the multi-token-
prediction layer are not loaded; the rotary embedding rotates the pairs
``(x[i], x[i + r/2])``; the key/value up-projection ``W_kvb`` is held as its
two halves ``W_kb`` and ``W_vb``.

``CHANGES`` are the controls of the tier-1 tests and of PERF.md section 2a:
each turns one of the above into something else, and has to come out as not
correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHANGES = ("selection", "window", "gate", "rescale", "index_rope", "scoring")
QUERY_BLOCK = 512


def config_of(spec) -> dict:
    """The plain numbers of the program's spec."""
    def kind(prefix, heads, theta):
        def get(name):
            return getattr(spec, prefix + name)
        return {"heads": heads, "q_rank": get("q_lora_rank"),
                "kv_rank": get("kv_lora_rank"),
                "nope": get("qk_nope_head_dim"),
                "rope": get("qk_rope_head_dim"), "v": get("v_head_dim"),
                "theta": theta}

    return {"n_layers": spec.n_layers, "d": spec.d_model,
            "eps": spec.norm_eps, "pattern": spec.layer_pattern,
            "G": kind("", spec.n_heads, spec.rope_theta),
            "L": kind("swa_", spec.swa_n_heads, spec.swa_rope_theta),
            "index_heads": spec.index_n_heads,
            "index_dim": spec.index_head_dim, "topk": spec.index_topk,
            "first_dense": spec.first_dense, "n_experts": spec.n_experts,
            "top_k": spec.experts_per_token, "scale": spec.router_scale,
            "held": spec.held, "expert_first": spec.expert_first,
            "shared": spec.n_shared_experts,
            # the controls' to change, with "window"
            "window": spec.sliding_window, "selection": True, "gate": True,
            "rescale": True, "index_rope": True, "scoring": "sigmoid"}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rotary(x, theta):
    """x [T, ..., r], position t = row t; frequencies theta^(-2i/r)."""
    t, r = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(h, c_q, w, cfg: dict):
    """I [T, T]: every position's score of every position."""
    t = h.shape[0]
    j, d, r = cfg["index_heads"], cfg["index_dim"], cfg["G"]["rope"]
    q = (c_q @ w["w_iq"]).reshape(t, j, d)
    k = layer_norm(h @ w["w_ik"], w["ik_norm_w"], w["ik_norm_b"], cfg["eps"])
    if cfg["index_rope"]:
        theta = cfg["G"]["theta"]
        q = jnp.concatenate([rotary(q[..., :r], theta), q[..., r:]], -1)
        k = jnp.concatenate([rotary(k[..., :r], theta), k[..., r:]], -1)
    weights = h @ w["w_iw"]                                     # [T, J]
    out = jnp.zeros((t, t), jnp.float32)
    for head in range(j):  # a head at a time: [T, T] and not [T, J, T]
        out = out + weights[:, head, None] * jax.nn.relu(q[:, head] @ k.T)
    return out / jnp.sqrt(jnp.float32(j)) / jnp.sqrt(jnp.float32(d))


def attention(h, w, kind: str, cfg: dict):
    """h [T, D], the layer's normed input -> [T, D]."""
    g, t = cfg[kind], h.shape[0]
    heads, nope, rope, v_dim = g["heads"], g["nope"], g["rope"], g["v"]
    up = (lambda rank: jnp.sqrt(jnp.float32(cfg["d"] / rank))) \
        if cfg["rescale"] else (lambda rank: 1.0)
    c_q = rms_norm(h @ w["w_qa"], w["q_a_norm_w"], cfg["eps"]) * up(
        g["q_rank"])
    q = (c_q @ w["w_qb"]).reshape(t, heads, nope + rope)
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], g["theta"])
    kv = h @ w["w_kva"]
    c_kv = rms_norm(kv[:, :g["kv_rank"]], w["kv_a_norm_w"], cfg["eps"]) * up(
        g["kv_rank"])
    k_r = rotary(kv[:, g["kv_rank"]:], g["theta"])              # [T, rope]
    k_n = (c_kv @ w["w_kb"]).reshape(t, heads, nope)
    v = (c_kv @ w["w_vb"]).reshape(t, heads, v_dim)
    i = jnp.arange(t)[:, None]
    s = jnp.arange(t)[None, :]
    seen = s <= i
    if kind == "L":
        seen = seen & (s > i - cfg["window"])
    elif cfg["selection"] and t > cfg["topk"]:
        scores = jnp.where(seen, index_scores(h, c_q, w, cfg), -jnp.inf)
        # the topk largest of each row, the earlier position first of equals
        # (a ReLU makes exact zeros)
        order = jnp.argsort(-scores, axis=-1)[:, : cfg["topk"]]
        seen = seen & jnp.zeros_like(seen).at[i, order].set(True)
    out = []
    for at in range(0, t, QUERY_BLOCK):
        rows = slice(at, at + QUERY_BLOCK)
        a = (jnp.einsum("ihd,jhd->hij", q_n[rows], k_n)
             + jnp.einsum("ihd,jd->hij", q_r[rows], k_r)
             ) / jnp.sqrt(jnp.float32(nope + rope))
        a = jnp.where(seen[rows][None], a, -jnp.inf)
        out.append(jnp.einsum("hij,jhd->ihd", jax.nn.softmax(a, axis=-1), v))
    out = jnp.concatenate(out, axis=0)
    if cfg["gate"]:
        out = out * jax.nn.sigmoid(h @ w["w_head_gate"])[:, :, None]
    return out.reshape(t, heads * v_dim) @ w["wo"]


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


ATTENTION = ("w_qa", "q_a_norm_w", "w_qb", "w_kva", "kv_a_norm_w", "w_kb",
             "w_vb", "w_head_gate", "wo")
INDEXER = ("w_iq", "w_ik", "ik_norm_w", "ik_norm_b", "w_iw")


def forward_for(backend, f32, take, changes: dict | None = None):
    """``forward(tokens, position)``: float32 log-probabilities over the
    vocabulary rows held here at ``position``. ``f32`` turns a weight leaf of
    the program to float32 (or to the control's precision), ``take`` indexes
    one; ``changes`` overrides numbers of :func:`config_of` (the controls)."""
    spec, params = backend.engine.spec, backend.engine.params
    assert set(changes or {}) <= set(CHANGES), changes
    cfg = dict(config_of(spec), **(changes or {}))

    @functools.partial(jax.jit, static_argnames=("kind",))
    def attn_part(x, norm_w, w, kind):
        with jax.default_matmul_precision("highest"):
            w = {k: f32(v) for k, v in w.items()}
            return x + attention(rms_norm(x, f32(norm_w), cfg["eps"]), w,
                                 kind, cfg)

    @jax.jit
    def normed(x, norm_w):
        return rms_norm(x, f32(norm_w), cfg["eps"])

    @jax.jit
    def mlp(x, w_gate, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return swiglu(x, f32(w_gate), f32(w_up), f32(w_down))

    @jax.jit
    def router(x, w, bias):
        with jax.default_matmul_precision("highest"):
            return route(x, f32(w), bias.astype(jnp.float32), cfg)

    @jax.jit
    def head(x, position, norm_w, lm_head):
        with jax.default_matmul_precision("highest"):
            hid = rms_norm(x[position], f32(norm_w), cfg["eps"])
            return jax.nn.log_softmax(hid @ f32(lm_head))

    def mlp_out(h, lyr, i):
        if i < cfg["first_dense"]:
            return mlp(h, *(take(lyr[k], 0)
                            for k in ("w_gate", "w_up", "w_down")))
        out = mlp(h, *(take(lyr["shared"][k], 0)
                       for k in ("w_gate", "w_up", "w_down"))) \
            if cfg["shared"] else jnp.zeros_like(h)
        weights = router(h, take(lyr["router"], 0),
                         take(lyr["router_bias"], 0))
        for e in range(cfg["held"]):
            col = weights[:, cfg["expert_first"] + e]
            out = out + col[:, None] * mlp(h, *(
                take(lyr[k], 0, e)
                for k in ("moe_w_gate", "moe_w_up", "moe_w_down")))
        return out

    def forward(tokens, position):
        x = take(params["tok_emb"], jnp.asarray(tokens, jnp.int32)).astype(
            jnp.float32)
        for i in range(cfg["n_layers"]):
            lyr = params["layers"][f"{i:02d}"]
            kind = cfg["pattern"][i % len(cfg["pattern"])]
            names = ATTENTION + (INDEXER if kind == "G" else ())
            x = attn_part(x, take(lyr["attn_norm_w"], 0),
                          {k: take(lyr[k], 0) for k in names}, kind=kind)
            x = x + mlp_out(normed(x, take(lyr["mlp_norm_w"], 0)), lyr, i)
        return np.asarray(head(x, position, params["final_norm_w"],
                               params["lm_head"]))

    return forward
