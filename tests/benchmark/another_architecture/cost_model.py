"""A test's cost model of another shape than ``cost_model.py``'s dense
decoder: ``first_k_dense_replace`` dense layers of ``intermediate_size``, then
layers of ``num_experts`` routed experts held here (``num_experts_per_tok`` of
the published count active a token) and ``num_shared_experts`` shared ones, of
``moe_intermediate_size`` each. The five functions the roofline readers reach
through ``cost_model.for_config``."""

from __future__ import annotations

from cost_model import least_seconds, peak_ops  # noqa: F401  the same chip


def shapes(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dense = cfg["first_k_dense_replace"]
    return {"attn": d * h * hd * 2 + d * kv * hd * 2, "hd": hd, "h": h,
            "kv": kv, "layers": cfg["num_hidden_layers"], "dense": dense,
            "sparse": cfg["num_hidden_layers"] - dense,
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "held": cfg["num_experts"], "shared": cfg["num_shared_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "head": d * cfg["vocab_size"],
            "wbytes": cfg["weight_bytes_per_param"]}


def kv_bytes_per_token(cfg: dict) -> int:
    s = shapes(cfg)
    return s["layers"] * 2 * s["kv"] * s["hd"] * 2


def _step(cfg: dict, tokens: float, head: bool) -> tuple[float, float]:
    """Parameters a token multiplies, and parameters read once a program:
    an expert held here is read once if any token picked it (every one is,
    from a few tokens on), and multiplies its share of the picks."""
    s = shapes(cfg)
    picked = s["per_tok"] / cfg["layer_chips"]  # of a token's picks, here
    active = (s["layers"] * s["attn"] + s["dense"] * s["dense_mlp"]
              + s["sparse"] * (s["shared"] + picked) * s["expert"])
    read = (s["layers"] * s["attn"] + s["dense"] * s["dense_mlp"]
            + s["sparse"] * (s["shared"] + min(s["held"], picked * tokens))
            * s["expert"])
    if head:
        active, read = active + s["head"], read + s["head"]
    return active, read


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    s = shapes(cfg)
    active, read = _step(cfg, rows, head=True)
    ops = 2.0 * active * rows + 4.0 * s["layers"] * s["h"] * s["hd"] * context * rows
    return ops, read * s["wbytes"] + rows * context * kv_bytes_per_token(cfg)


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    s = shapes(cfg)
    active, read = _step(cfg, tokens, head=False)
    ops = (2.0 * active * tokens
           + 2.0 * s["layers"] * s["h"] * s["hd"] * tokens * mean_prompt)
    return ops, executions * (read + s["head"]) * s["wbytes"]
