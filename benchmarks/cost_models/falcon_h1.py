"""Operations and bytes a step of ``falcon-h1-34b-l6`` needs, from shapes: the
least time the chip could take, for the roofline shares. The five functions
the readers reach through ``cost_model.for_config``.

The model is the configuration file's: ``num_hidden_layers`` blocks, all
alike, each with grouped-query attention (``num_attention_heads`` over
``num_key_value_heads`` of ``head_dim``), a Mamba-2 mixer (``mamba_n_heads``
heads of ``mamba_d_head`` with a state of ``mamba_d_state``,
``mamba_n_groups`` groups, a fused input projection to z, x, B, C and dt, an
output projection) and a SwiGLU of ``intermediate_size``; a head over
``vocab_size`` rows.

Counted is what any implementation must do:

  - every matrix product of a block and the head, two operations a parameter
    and row;
  - attention over the cached positions, ``4 H hd`` a (query, position);
  - the recurrence. One step a row and layer: decay, outer product and
    readout over the ``H P N`` state, ``5 H P N``. A prefill execution, in
    chunks of ``mamba_chunk_size`` Q: the causal half of the C B^T scores and
    of their product with x inside a chunk, ``Q (G N + H P)`` a position, and
    the state's gain and readout at the chunk's edge, ``4 H P N`` a position:
    the chunked form's count, under the one-step form's ``5 H P N``;
  - bytes: every program run streams its weights once; a decode step reads
    the K and V of each row's live positions and READS AND WRITES EACH LIVE
    ROW'S RECURRENT STATE ONCE A LAYER (float32 ``H P N``, and the
    convolution's ``d_conv - 1`` bfloat16 inputs), whatever the row's length.

A prefill execution's bytes count the head: a single-shot admit, most of
them, computes its last position's logits over all 261,120 rows. A segment
program reads no head and is overcounted by it (one execution in some
sixteen in ``manychat``; a 512-token one is bound by its operations either
way). Norms, rotary, the convolution, the gate and sampling are left out:
under one percent of either count at these widths.
"""

from __future__ import annotations

from cost_model import least_seconds, peak_ops  # noqa: F401  the same chip


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g, d_ssm = cfg["mamba_n_groups"], cfg["mamba_d_ssm"]
    conv = d_ssm + 2 * g * n
    mixer = d * (2 * d_ssm + 2 * g * n + heads) + d_ssm * d
    layer = d * h * hd * 2 + d * kv * hd * 2 + mixer + 3 * d * f
    return {"layers": cfg["num_hidden_layers"], "layer_params": layer,
            "head_params": d * cfg["vocab_size"], "h": h, "kv": kv, "hd": hd,
            "state": heads * p * n, "pairs": g * n + heads * p,
            "chunk": cfg["mamba_chunk_size"],
            "state_bytes": heads * p * n * 4
            + (cfg["mamba_d_conv"] - 1) * conv * 2,
            "wbytes": cfg["weight_bytes_per_param"]}


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 keys and values of one position, all layers: what the cache
    grows by a position. The state does not grow."""
    s = shapes(cfg)
    return s["layers"] * 2 * s["kv"] * s["hd"] * 2


def state_bytes_per_row(cfg: dict) -> int:
    """The recurrent state and convolution tail of one row, all layers."""
    s = shapes(cfg)
    return s["layers"] * s["state_bytes"]


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step that advances ``rows`` rows
    whose cached context is ``context`` tokens each."""
    s = shapes(cfg)
    params = s["layers"] * s["layer_params"] + s["head_params"]
    ops = (2.0 * params * rows
           + 4.0 * s["layers"] * s["h"] * s["hd"] * context * rows
           + 5.0 * s["layers"] * s["state"] * rows)
    byts = (params * s["wbytes"] + rows * context * kv_bytes_per_token(cfg)
            + 2.0 * rows * state_bytes_per_row(cfg))
    return ops, byts


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``tokens`` prompt tokens in
    ``executions`` program runs. Attention is causal: half of tokens x
    context per head."""
    s = shapes(cfg)
    params = s["layers"] * s["layer_params"]
    ops = (2.0 * params * tokens
           + 2.0 * s["layers"] * s["h"] * s["hd"] * tokens * mean_prompt
           + s["layers"] * tokens * (s["chunk"] * s["pairs"]
                                     + 4.0 * s["state"]))
    byts = executions * ((params + s["head_params"]) * s["wbytes"]
                         + 2.0 * state_bytes_per_row(cfg))
    return ops, byts
