"""Child process of ``run.py``: compares the served probe with the reference.

Runs after the server has stopped, so the chip is free. The weights are the
server's own: the same ``tpu://`` URL constructs the same engine, whose init
program makes the same seeded arrays on the same device (for ``quant=int8``
the same int8 values and scales). Only the arrays are taken from it; the
forward pass is ``reference.py``, layer by layer, each layer's weights turned
to float32 as it is used (a 7B model in float32 does not fit the chip whole).

The probe was served greedily (``temperature=0``) through ``/completions``
with ``logprobs``, one probe per backend, i.e. per quorum member: prefill of
the prompt gives the first token, decode through the cache the rest. The wire
gives the served tokens as text, not ids, and the byte tokenizer folds 32000
ids onto 256 bytes (and every byte above 127 onto U+FFFD), so the ids are
searched for: an id fits a position if its byte is the served text, the
reference's log-probability for it is within ``tol["max"]`` of the served
value, and it is within ``tol["max"]`` of the reference's largest (the probe
is greedy). The
reference is teacher-forced along the fitting ids, depth first; a wrong id
that happens to fit one position makes the next positions' values disagree,
so the search backs out of it. The probe agrees if some chain of ids fits
every position and the median of the chain's errors is within
``tol["median"]``; it disagrees if no id fits some position. Two limits,
because quantization noise and a fault differ in shape (``run.PROBE_TOL``):
noise is small at most positions with a rare large one, a fault moves every
position it touches.

Prints one JSON line: {"ok", "compared", "max_abs_err", "median_abs_err",
"detail"}.
"""

from __future__ import annotations

import json
import os
import sys

MAX_BRANCH = 4      # ids tried per position, nearest served value first
MAX_FORWARDS = 60   # reference forward passes per probe


def median(values: list) -> float:
    s = sorted(values)
    return s[(len(s) - 1) // 2] if s else 0.0  # nearest rank, as e2e.py

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)


def main() -> int:
    with open(sys.argv[1]) as f:
        job = json.load(f)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.compile_cache import enable_persistent_compile_cache
    from quorum_tpu.config import BackendSpec

    enable_persistent_compile_cache()
    want = job["platform"]
    got = jax.devices()[0].platform
    if got != want:
        print(f"reference_check: jax reports platform {got!r}, this run "
              f"requires {want!r}", file=sys.stderr)
        return 1

    def f32(leaf):
        """A program weight leaf as float32: int8 values times their scales
        where the leaf is quantized."""
        if isinstance(leaf, dict):
            return leaf["q8"].astype(jnp.float32) * leaf["qs"].astype(
                jnp.float32)
        return leaf.astype(jnp.float32)

    def byte_matches(token_id: int, text: str) -> bool:
        """The byte tokenizer shows id i as byte (i - 3) % 256: one ASCII
        character, or U+FFFD for a byte above 127; ids below 3 show nothing."""
        if token_id < 3:
            return text == ""
        byte = (token_id - 3) % 256
        if text == "\ufffd":
            return byte >= 128
        return len(text) == 1 and ord(text) == byte

    def take(leaf, *idx):
        if isinstance(leaf, dict):
            return {k: v[idx] for k, v in leaf.items()}
        return leaf[idx]


    compiled: dict = {}

    def programs(cfg: dict):
        """The reference's three jitted pieces, built once per distinct
        model shape (every member of a quorum shares them)."""
        key = tuple(sorted(cfg.items()))
        if key not in compiled:
            @jax.jit
            def layer(x, lw):
                with jax.default_matmul_precision("highest"):
                    return reference.block(
                        x, {k: f32(v) for k, v in lw.items()}, cfg)

            @jax.jit
            def head(x, position, fn, lm):
                with jax.default_matmul_precision("highest"):
                    return reference.logprobs_at(x, position, f32(fn),
                                                 f32(lm), cfg)

            @jax.jit
            def embed(table, tokens):
                return f32(take(table, tokens))

            compiled[key] = (layer, head, embed)
        return compiled[key]

    tol = float(job["tol"]["max"])
    detail, errors, compared, ok = [], [], 0, True
    backends = {}
    for probe in job["probes"]:
        b = job["backends"][probe["backend"]]
        if b["name"] not in backends:
            # One slot row is enough here: the weights depend on the seed and
            # the spec, not on how many rows the cache has.
            backends[b["name"]] = TpuBackend.from_spec(BackendSpec(
                name=b["name"], url=b["url"] + "&slots=1", model=b["model"]))
        backend = backends[b["name"]]
        engine, spec = backend.engine, backend.engine.spec
        params = engine.params
        member = backend.member if engine.members > 1 else None
        lead = () if member is None else (member,)
        cfg = {"n_heads": spec.n_heads, "n_kv_heads": spec.n_kv_heads,
               "head_dim": spec.head_dim, "eps": spec.norm_eps,
               "theta": spec.rope_theta, "window": spec.sliding_window}
        names = {"attn_norm": "attn_norm_w", "mlp_norm": "mlp_norm_w",
                 "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
                 "w_gate": "w_gate", "w_up": "w_up", "w_down": "w_down"}

        layer, head, embed = programs(cfg)

        blocks = params["blocks"]
        tok_emb = take(params["tok_emb"], *lead)
        final_norm = take(params["final_norm_w"], *lead)
        lm_head = take(params["lm_head"], *lead)

        def forward(tokens, position):
            x = embed(tok_emb, jnp.asarray(tokens, jnp.int32))
            for l in range(spec.n_layers):
                x = layer(x, {k: take(blocks[v], *lead, l)
                              for k, v in names.items()})
            return np.asarray(head(x, position, final_norm, lm_head))

        prompt = list(probe["prompt"])
        served = probe["token_logprobs"]
        texts = probe["tokens"]
        total = len(prompt) + len(served)
        budget = [MAX_FORWARDS]

        def search(tokens: list, t: int) -> tuple[int, list]:
            """Depth-first over the ids that fit position t and after: how
            many positions the best chain matched, and its errors."""
            if t == len(served) or budget[0] <= 0:
                return t, []
            budget[0] -= 1
            lp = forward(tokens + [0] * (total - len(tokens)),
                         len(tokens) - 1)
            # the probe was served greedily: the served id had the largest
            # served value, so the reference holds it within tol of its own
            # largest (under random weights the values lie close together,
            # and without this some other id fits almost any served number)
            fits = sorted(
                (abs(float(lp[i]) - served[t]), int(i))
                for i in np.nonzero((np.abs(lp - served[t]) <= tol)
                                    & (lp >= lp.max() - tol))[0]
                if byte_matches(int(i), texts[t]))[:MAX_BRANCH]
            best = (t, [abs(float(lp.max()) - served[t])])
            for err, token_id in fits:
                depth, later = search(tokens + [token_id], t + 1)
                if depth > best[0]:
                    best = (depth, [err] + later)
                if depth == len(served):
                    break
            return best

        n_cmp, errs = search(prompt, 0)
        stopped = ""
        if n_cmp < len(served):
            exhausted = budget[0] <= 0
            stopped = (f"no id fits position {n_cmp}"
                       + (" (forward budget spent)" if exhausted else ""))
            ok = ok and exhausted and n_cmp > 0
        errors += errs
        compared += n_cmp
        detail.append({"backend": b["name"], "positions": n_cmp,
                       "of": len(served), "stopped": stopped,
                       "forwards": MAX_FORWARDS - budget[0]})
    for backend in backends.values():
        backend.engine.shutdown()
    mid = median(errors)
    print(json.dumps({"ok": (ok and compared > 0
                             and mid <= float(job["tol"]["median"])),
                      "compared": compared, "max_abs_err": max(errors or [0]),
                      "median_abs_err": mid, "tol": job["tol"],
                      "detail": detail}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
