"""Operations and bytes a step of ``k-exaone-ep8`` needs, from shapes: the
least time the chip could take, for the roofline shares. The five functions
the readers reach through ``cost_model.for_config``.

The model is the configuration file's: ``first_k_dense_replace`` layers with a
SwiGLU MLP of ``intermediate_size``, then layers of a router over the
published 128 experts, ``num_shared_experts`` always-on experts and the
``num_experts`` held here of ``moe_intermediate_size`` each; attention of
``num_attention_heads`` x ``head_dim`` over ``num_key_value_heads`` in every
layer, over the last ``sliding_window`` positions where ``layer_types`` says
``sliding_attention`` and over all of them elsewhere; a head over the
``vocab_size`` rows held here.

Counted is what any implementation of this chip's share must do. A held
expert is read only if some row of the program picked it: a row picks
``num_experts_per_tok`` of the published count, so of ``n`` rows an expert
sees none with probability ``(1 - per_tok / published) ** n``, and a row
multiplies ``per_tok * held / published`` experts on average (one, here). A
window layer reads ``min(context, sliding_window)`` cached positions a row.
A prefill execution reads its weights once and no head (only a prompt's last
segment samples). Norms, rotary, the embedding's rows and sampling are left
out: under one percent of either count at these widths.
"""

from __future__ import annotations

from cost_model import least_seconds, peak_ops  # noqa: F401  the same chip


def shapes(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    window = sum(1 for kind in cfg["layer_types"][:layers]
                 if kind == "sliding_attention")
    held = cfg["num_experts"]
    return {"attn": d * h * hd * 2 + d * kv * hd * 2, "hd": hd, "h": h,
            "kv": kv, "layers": layers, "dense": dense,
            "sparse": layers - dense, "window_layers": window,
            "full_layers": layers - window, "window": cfg["sliding_window"],
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "held": held, "routed": held * cfg["layer_chips"],
            "router": d * held * cfg["layer_chips"],
            "shared": cfg["num_shared_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "head": d * cfg["vocab_size"],
            "wbytes": cfg["weight_bytes_per_param"]}


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 keys and values of one position in the layers that keep every
    position; a window layer's ring holds ``sliding_window`` whatever the
    context."""
    s = shapes(cfg)
    return s["full_layers"] * _kv_position(s)


def _kv_position(s: dict) -> int:
    return 2 * s["kv"] * s["hd"] * 2


def _params(cfg: dict, rows: float) -> tuple[float, float]:
    """(parameters a row multiplies, parameters a program of ``rows`` rows
    reads), without the head."""
    s = shapes(cfg)
    every = s["layers"] * s["attn"] + s["dense"] * s["dense_mlp"]
    layer = s["router"] + s["shared"] * s["expert"]
    picked_by_a_row = s["per_tok"] * s["held"] / s["routed"]
    read_of_held = s["held"] * (1.0 - (1.0 - s["per_tok"] / s["routed"]) ** rows)
    active = every + s["sparse"] * (layer + picked_by_a_row * s["expert"])
    read = every + s["sparse"] * (layer + read_of_held * s["expert"])
    return active, read


def _attended(s: dict, context: float) -> float:
    """Cached positions a row attends, summed over the layers."""
    return (s["full_layers"] * context
            + s["window_layers"] * min(context, s["window"]))


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step that advances ``rows`` rows
    whose cached context is ``context`` tokens each."""
    s = shapes(cfg)
    active, read = _params(cfg, rows)
    attended = _attended(s, context)
    ops = 2.0 * (active + s["head"]) * rows + 4.0 * s["h"] * s["hd"] * attended * rows
    byts = (read + s["head"]) * s["wbytes"] + rows * attended * _kv_position(s)
    return ops, byts


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``tokens`` prompt tokens in
    ``executions`` program runs. Attention is causal: a token of a full
    layer sees half the prompt on average, of a window layer no more than
    the window."""
    s = shapes(cfg)
    active, read = _params(cfg, tokens / max(executions, 1.0))
    ops = (2.0 * active * tokens
           + 4.0 * s["h"] * s["hd"] * tokens * _attended(s, mean_prompt / 2.0))
    return ops, executions * read * s["wbytes"]
