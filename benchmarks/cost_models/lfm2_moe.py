"""Operations and bytes a step of ``lfm2-8b-a1b-l14`` needs, from shapes: the
least time the chip could take, for the roofline shares. The five functions
the readers reach through ``cost_model.for_config``.

The model is the configuration file's: ``num_hidden_layers`` layers whose
operator ``layer_types`` names: a gated short convolution (an input
projection to three parts of ``hidden_size``, ``conv_L_cache`` depthwise
taps, an output projection) or grouped-query attention
(``num_attention_heads`` over ``num_key_value_heads`` of ``hidden_size /
num_attention_heads``); then ``num_dense_layers`` SwiGLUs of
``intermediate_size`` and, in every later layer, a router over
``num_experts`` SwiGLUs of ``moe_intermediate_size`` of which a token runs
``num_experts_per_tok``; a head that is the embedding, ``vocab_size`` rows.

Counted is what any implementation must do:

  - every matrix product a row runs, two operations a parameter and row: the
    operators, the dense SwiGLUs, the router, the ``num_experts_per_tok``
    experts a row picks, the head; attention over the cached positions of
    the attention layers, ``4 H hd`` a (query, position); the taps and the
    two gates, ``(2 conv_L_cache + 2) D`` a row and conv layer;
  - bytes: a program streams its weights once, AN EXPERT'S ONLY IF SOME ROW
    OF THE PROGRAM PICKED IT: of ``n`` rows an expert sees none with
    probability ``(1 - per_tok / experts) ** n``, so the experts counted are
    the distinct ones the program's rows are expected to pick under even
    routing, never all of them where few rows are live (at 128 rows 32.0 of
    32, at 16 rows 28.2, at one row 4); a decode step reads the K and V of
    each live row's positions in the attention layers and READS AND WRITES
    EACH LIVE ROW'S TAIL ONCE A CONV LAYER (``conv_L_cache - 1`` positions of
    ``hidden_size``, bfloat16), whatever the row's length.

A prefill execution's bytes count the head: a single-shot admit, most of
them, computes its last position's logits over all 65,536 rows. A segment
program reads no head and is overcounted by it (one execution in some
sixteen in ``crowd``; 0.27 GB of 9.3). Served ASCII prompts route less
evenly than the count assumes: a program whose rows pick fewer distinct
experts than expected needs fewer bytes than counted here, and at the rows
this cell runs (28 and more a prefill execution, 100 and more a decode step)
the expected count is within 3 % of all 32. Norms, rotary, the embedding's
rows and sampling are left out: under one percent of either count at these
widths.
"""

from __future__ import annotations

from cost_model import least_seconds, peak_ops  # noqa: F401  the same chip


def shapes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    kinds = cfg["layer_types"][:layers]
    attn = sum(1 for kind in kinds if kind == "full_attention")
    return {"d": d, "h": h, "kv": kv, "hd": hd, "layers": layers,
            "attn_layers": attn, "conv_layers": layers - attn,
            "dense": dense, "sparse": layers - dense,
            "attn": d * h * hd * 2 + d * kv * hd * 2,
            "conv": d * 3 * d + d * d + cfg["conv_L_cache"] * d,
            "taps": cfg["conv_L_cache"],
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"],
            "router": d * cfg["num_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "head": d * cfg["vocab_size"],
            "wbytes": cfg["weight_bytes_per_param"]}


def _kv_position(s: dict) -> int:
    """bf16 keys and values of one position in one attention layer."""
    return 2 * s["kv"] * s["hd"] * 2


def kv_bytes_per_token(cfg: dict) -> int:
    """What the cache grows by a position: the attention layers' K and V. A
    conv layer's tail does not grow."""
    s = shapes(cfg)
    return s["attn_layers"] * _kv_position(s)


def state_bytes_per_row(cfg: dict) -> int:
    """The convolution tails of one row, all conv layers."""
    s = shapes(cfg)
    return s["conv_layers"] * (s["taps"] - 1) * s["d"] * 2


def experts_read(cfg: dict, rows: float) -> float:
    """Distinct experts a layer's router is expected to pick for ``rows``
    rows under even routing."""
    s = shapes(cfg)
    return s["experts"] * (1.0 - (1.0 - s["per_tok"] / s["experts"]) ** rows)


def _params(cfg: dict, rows: float) -> tuple[float, float]:
    """(parameters a row multiplies, parameters a program of ``rows`` rows
    reads), without the head."""
    s = shapes(cfg)
    every = (s["attn_layers"] * s["attn"] + s["conv_layers"] * s["conv"]
             + s["dense"] * s["dense_mlp"] + s["sparse"] * s["router"])
    active = every + s["sparse"] * s["per_tok"] * s["expert"]
    read = every + s["sparse"] * experts_read(cfg, rows) * s["expert"]
    return active, read


def _elementwise(s: dict) -> float:
    """A row's operations in the conv layers besides the products."""
    return s["conv_layers"] * (2.0 * s["taps"] + 2.0) * s["d"]


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step that advances ``rows`` rows
    whose cached context is ``context`` tokens each."""
    s = shapes(cfg)
    active, read = _params(cfg, rows)
    ops = ((2.0 * (active + s["head"]) + _elementwise(s)) * rows
           + 4.0 * s["attn_layers"] * s["h"] * s["hd"] * context * rows)
    byts = ((read + s["head"]) * s["wbytes"]
            + rows * context * kv_bytes_per_token(cfg)
            + 2.0 * rows * state_bytes_per_row(cfg))
    return ops, byts


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``tokens`` prompt tokens in
    ``executions`` program runs. Attention is causal: half of tokens x
    context per head, in the attention layers."""
    s = shapes(cfg)
    active, read = _params(cfg, tokens / max(executions, 1.0))
    ops = ((2.0 * active + _elementwise(s)) * tokens
           + 2.0 * s["attn_layers"] * s["h"] * s["hd"] * tokens * mean_prompt)
    byts = executions * ((read + s["head"]) * s["wbytes"]
                         + 2.0 * state_bytes_per_row(cfg))
    return ops, byts
