"""A test's reference, found as ``references/later_moe.py``: the one function
a configuration's ``"reference"`` gives ``reference_check.py``.

No program serves the configuration that names it, so in the CPU rehearsal
the program's tiny dense preset stands in, and this file computes that
preset's forward pass from ``reference.py``'s block: it shows the interface
and lets the lookup be followed through a whole run, nothing more. A real
one writes its architecture's layers here (a dense first layer, expert
layers over the experts held, each kind of attention) from the published
description."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference

NAMES = {"attn_norm": "attn_norm_w", "mlp_norm": "mlp_norm_w", "wq": "wq",
         "wk": "wk", "wv": "wv", "wo": "wo", "w_gate": "w_gate",
         "w_up": "w_up", "w_down": "w_down"}


def forward_for(backend, f32, take):
    """``forward(tokens, position)``: float32 log-probabilities over the
    vocabulary at ``position``, layer by layer under the highest matmul
    precision. ``f32`` turns a weight leaf of the program (quantized or not)
    to float32, ``take`` indexes one."""
    spec, params = backend.engine.spec, backend.engine.params
    cfg = {"n_heads": spec.n_heads, "n_kv_heads": spec.n_kv_heads,
           "head_dim": spec.head_dim, "eps": spec.norm_eps,
           "theta": spec.rope_theta, "window": spec.sliding_window}

    def forward(tokens, position):
        with jax.default_matmul_precision("highest"):
            x = f32(take(params["tok_emb"], jnp.asarray(tokens, jnp.int32)))
            for l in range(spec.n_layers):
                x = reference.block(x, {k: f32(take(params["blocks"][v], l))
                                        for k, v in NAMES.items()}, cfg)
            return np.asarray(reference.logprobs_at(
                x, position, f32(params["final_norm_w"]),
                f32(params["lm_head"]), cfg))

    return forward
