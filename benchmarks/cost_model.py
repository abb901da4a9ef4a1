"""Operations and bytes a step needs, from shapes: the least time the chip
could take, for the roofline shares. Kept with the benchmark.

The model is the configuration file's (published Mistral widths). Counted
are the matrix products of the decoder blocks and the output head, the
attention products, the weight bytes each executed program must stream from
HBM once, and the cached keys and values a decode step must read. Norms,
rotary and sampling are left out: they are under one percent of either
count at these widths.
"""

from __future__ import annotations

import sys

import named


def for_config(cfg: dict):
    """The cost model of a configuration: this module (a dense decoder at
    the file's widths) unless the file names its own,
    ``"cost_model": "<name>"``, found as ``cost_models/<name>.py`` with the
    same five functions (``decode_step``, ``prefill``, ``peak_ops``,
    ``kv_bytes_per_token``, ``least_seconds``)."""
    name = cfg.get("cost_model")
    if name is None:
        return sys.modules[__name__]
    return named.load("cost_models", name)


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f  # q,o + k,v + mlp
    return {"layers": cfg["num_hidden_layers"], "layer_params": layer,
            "head_params": d * cfg["vocab_size"], "hd": hd, "h": h, "kv": kv,
            "members": len(cfg["serve"]["backends"]),
            "wbytes": cfg["weight_bytes_per_param"]}


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 keys and values of one position, all layers, one member."""
    s = shapes(cfg)
    return s["layers"] * 2 * s["kv"] * s["hd"] * 2


def peak_ops(cfg: dict, peaks: dict) -> float:
    """int8 weights run w8a8 on the int8 MXU path; bf16 on the bf16 one."""
    return peaks["int8_ops"] if cfg["weight_bytes_per_param"] == 1 else peaks[
        "bf16_flops"]


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step that advances ``rows`` rows
    (summed over members) whose cached context is ``context`` tokens each:
    every member's weights once, each row's keys and values once."""
    s = shapes(cfg)
    params = s["layers"] * s["layer_params"] + s["head_params"]
    ops = 2.0 * params * rows + 4.0 * s["layers"] * s["h"] * s["hd"] * context * rows
    byts = (s["members"] * params * s["wbytes"]
            + rows * context * kv_bytes_per_token(cfg))
    return ops, byts


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``tokens`` prompt tokens (summed over
    members) in ``executions`` program runs, each of which streams every
    member's weights once. Attention is causal: half of tokens x context per
    head."""
    s = shapes(cfg)
    params = s["layers"] * s["layer_params"]
    ops = (2.0 * params * tokens
           + 2.0 * s["layers"] * s["h"] * s["hd"] * tokens * mean_prompt)
    byts = executions * s["members"] * (params + s["head_params"]) * s["wbytes"]
    return ops, byts


def least_seconds(ops: float, byts: float, cfg: dict, peaks: dict) -> float:
    return max(ops / peak_ops(cfg, peaks), byts / peaks["hbm_bytes_per_s"])
