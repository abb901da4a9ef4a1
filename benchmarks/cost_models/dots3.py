"""Operations and bytes a step of ``dots3-ep8`` needs, from shapes: the least
time the chip could take, for the roofline shares. The five functions the
readers reach through ``cost_model.for_config``.

The model is the configuration file's: ``first_k_dense_replace`` layers with a
SwiGLU MLP of ``intermediate_size``, then layers of a router over the
published 256 experts, ``n_shared_experts`` always-on experts and the
``n_routed_experts`` held here of ``moe_intermediate_size`` each. Attention
is latent, by ``layer_types``: a full layer projects a query latent of
``q_lora_rank`` and a key/value latent of ``kv_lora_rank`` (plus one rotated
key of ``qk_rope_head_dim``), scores every earlier position with
``index_n_heads`` index heads of ``index_head_dim`` and attends the
``index_topk`` positions of largest score; a window layer (``swa_*`` sizes)
attends the last ``sliding_window_size``. A head over the ``vocab_size`` rows
held here.

Counted is what any implementation of this chip's share must do, the model's
least work whatever implements it:

  - the index scores over the whole history: ``2 J d_I`` a position and query;
  - attention over ``min(history, index_topk)`` positions (a window layer:
    ``min(history, window)``) at the cheaper form's price for a (query,
    position, head): keys and values as the up-projections make them,
    ``2 (nope + rope + v)``; the latent-space form costs ``2 (2 kv_rank +
    rope)``, 3.4 times that on a full layer. The up-projection of each
    position's own latent is in the parameters a token multiplies, once;
  - a held expert is read only if some row of the program picked it, as
    ``cost_models/k_exaone.py`` counts it;
  - of the cache a decode step reads every index key of the row's history
    (``d_I`` each), the selected latent rows (``kv_rank + rope`` each) and the
    window layers' last ``window`` rows.

A prefill execution reads its weights once and no head. Norms, rotary, the
gate's product and sigmoid, the embedding's rows and sampling are left out:
under one percent of either count at these widths.
"""

from __future__ import annotations

from cost_model import least_seconds, peak_ops  # noqa: F401  the same chip


def _attention(cfg: dict, prefix: str, heads: int, indexer: bool) -> dict:
    d = cfg["hidden_size"]
    q_rank, kv_rank = cfg[prefix + "q_lora_rank"], cfg[prefix + "kv_lora_rank"]
    nope, rope = cfg[prefix + "qk_nope_head_dim"], cfg[prefix + "qk_rope_head_dim"]
    v = cfg[prefix + "v_head_dim"]
    params = (d * q_rank + q_rank * heads * (nope + rope)
              + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
              + heads * v * d + d * heads)
    index = 0
    if indexer:
        j, di = cfg["index_n_heads"], cfg["index_head_dim"]
        index = q_rank * j * di + d * di + d * j
    return {"params": params + index, "heads": heads, "row": kv_rank + rope,
            "pair": 2 * (nope + rope + v)}


def shapes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    window = sum(1 for kind in cfg["layer_types"][:layers]
                 if kind == "sliding_attention")
    held = cfg["n_routed_experts"]
    return {"full": _attention(cfg, "", cfg["num_attention_heads"], True),
            "swa": _attention(cfg, "swa_", cfg["swa_num_attention_heads"],
                              False),
            "layers": layers, "dense": dense, "sparse": layers - dense,
            "window_layers": window, "full_layers": layers - window,
            "window": cfg["sliding_window_size"], "topk": cfg["index_topk"],
            "index_pair": 2 * cfg["index_n_heads"] * cfg["index_head_dim"],
            "index_key": cfg["index_head_dim"],
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "held": held, "routed": held * cfg["layer_chips"],
            "router": d * held * cfg["layer_chips"],
            "shared": cfg["n_shared_experts"],
            "per_tok": cfg["num_experts_per_tok"],
            "head": d * cfg["vocab_size"],
            "wbytes": cfg["weight_bytes_per_param"]}


def kv_bytes_per_token(cfg: dict) -> int:
    """bf16 bytes the cache grows by a position: a latent row and an index
    key in each full layer; a window layer's ring holds 1024 whatever the
    context."""
    s = shapes(cfg)
    return s["full_layers"] * (s["full"]["row"] + s["index_key"]) * 2


def _params(cfg: dict, rows: float) -> tuple[float, float]:
    """(parameters a row multiplies, parameters a program of ``rows`` rows
    reads), without the head."""
    s = shapes(cfg)
    every = (s["full_layers"] * s["full"]["params"]
             + s["window_layers"] * s["swa"]["params"]
             + s["dense"] * s["dense_mlp"])
    layer = s["router"] + s["shared"] * s["expert"]
    picked_by_a_row = s["per_tok"] * s["held"] / s["routed"]
    read_of_held = s["held"] * (1.0 - (1.0 - s["per_tok"] / s["routed"]) ** rows)
    active = every + s["sparse"] * (layer + picked_by_a_row * s["expert"])
    read = every + s["sparse"] * (layer + read_of_held * s["expert"])
    return active, read


def _attention_ops(s: dict, scored: float, attended: float) -> float:
    """One query's attention operations over all layers: ``scored`` earlier
    positions a full layer's indexer scores, ``attended`` it attends; a
    window layer attends no more than its window."""
    full, swa = s["full"], s["swa"]
    return (s["full_layers"] * (s["index_pair"] * scored
                                + full["heads"] * full["pair"] * attended)
            + s["window_layers"] * swa["heads"] * swa["pair"]
            * min(scored, s["window"]))


def decode_step(cfg: dict, rows: float, context: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step that advances ``rows`` rows
    whose cached context is ``context`` tokens each."""
    s = shapes(cfg)
    active, read = _params(cfg, rows)
    attended = min(context, s["topk"])
    ops = (2.0 * (active + s["head"]) * rows
           + _attention_ops(s, context, attended) * rows)
    cache = 2 * (s["full_layers"] * (s["index_key"] * context
                                     + s["full"]["row"] * attended)
                 + s["window_layers"] * s["swa"]["row"]
                 * min(context, s["window"]))
    return ops, (read + s["head"]) * s["wbytes"] + rows * cache


def mean_attended(prompt: float, topk: float) -> float:
    """The mean over a prompt's positions of ``min(position, topk)``."""
    if prompt <= topk:
        return prompt / 2.0
    return topk - topk * topk / (2.0 * prompt)


def prefill(cfg: dict, tokens: float, mean_prompt: float,
            executions: float) -> tuple[float, float]:
    """(operations, bytes) of prefilling ``tokens`` prompt tokens in
    ``executions`` program runs. Attention is causal: a token's indexer sees
    half the prompt on average, and it attends ``min(position, index_topk)``
    of it."""
    s = shapes(cfg)
    active, read = _params(cfg, tokens / max(executions, 1.0))
    ops = (2.0 * active * tokens
           + _attention_ops(s, mean_prompt / 2.0,
                            mean_attended(mean_prompt, s["topk"])) * tokens)
    return ops, executions * read * s["wbytes"]
