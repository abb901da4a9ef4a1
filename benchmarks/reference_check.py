"""Child process of ``run.py``: compares the served probe with the reference.

Runs after the server has stopped, so the chip is free. The weights are the
server's own: the same ``tpu://`` URL constructs the same engine, whose init
program makes the same seeded arrays on the same device (for ``quant=int8``
the same int8 values and scales). Only the arrays are taken from it; the
forward pass is ``reference.py``, layer by layer, each layer's weights turned
to float32 as it is used (a 7B model in float32 does not fit the chip whole).

What differs by architecture is that forward pass and nothing else. A
configuration file may name its own, ``"reference": "<name>"`` (``run.py``
writes the name into the probe's job): ``references/<name>.py`` gives one
function, ``forward_for(backend, f32, take)``, which returns
``forward(tokens, position)``: the log-probabilities over the vocabulary at
that position as a float32 numpy array, computed in float32 under
``jax.default_matmul_precision("highest")`` layer by layer so that it fits
the chip. With no name the forward pass is the Mistral block below. The
platform check, the engine, the search and the two limits are the same for
every architecture.

The probe was served greedily (``temperature=0``) through ``/completions``
with ``logprobs``, ``run.PROBE_TOL``'s ``prompts`` probes per backend, i.e.
per quorum member: prefill of the prompt gives the first token, decode
through the cache the rest. The wire
gives the served tokens as text, not ids, and the byte tokenizer folds 32000
ids onto 256 bytes (and every byte above 127 onto U+FFFD), so the ids are
searched for: an id fits a position if its byte is the served text, the
reference's log-probability for it is within ``tol["max"]`` of the served
value, and it is within ``tol["max"]`` of the reference's largest (the probe
is greedy); where the limits name a narrower ``tol["first"]``, within that
first. The
reference is teacher-forced along the fitting ids, depth first; a wrong id
that happens to fit one position makes the next positions' values disagree,
so the search backs out of it. The probe agrees if some chain of ids fits
every position, and disagrees if no id fits some position; the probes agree
if the median of all their chains' errors is within ``tol["median"]``. Two
limits, because quantization noise and a fault differ in shape
(``run.PROBE_TOL``):
noise is small at most positions with a rare large one, a fault moves every
position it touches.

Prints one JSON line: {"ok", "compared", "max_abs_err", "median_abs_err",
"tol", "detail"}; ``detail`` has each probe's positions and their errors.

With ``"control": "int4"`` (or ``"int8"``) in the job, which no benchmark run
sets, the line also has ``control``: at the ids the search found, the
reference with its matrices rounded to that precision against the reference
itself, held to the same limits. It has to come out not ``ok``: the limits
are set between the served path's readings and the control's (``run.py``).
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

    import named
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

    def lowered(levels: int):
        """``f32`` for the control's weights: a matrix rounded to ``levels``
        steps either side of zero per output channel (7: int4, 127: int8)."""
        def to_f32(leaf):
            w = f32(leaf)
            if w.ndim < 2:
                return w
            scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / levels
            return jnp.round(w / scale) * scale
        return to_f32

    compiled: dict = {}

    def programs(cfg: dict, weight=f32):
        """The reference's three jitted pieces, built once per distinct
        model shape (every member of a quorum shares them). ``weight`` turns
        a layer's or the head's leaf to float32: ``f32``, or the control's."""
        key = (weight,) + tuple(sorted(cfg.items()))
        if key not in compiled:
            @jax.jit
            def layer(x, lw):
                with jax.default_matmul_precision("highest"):
                    return reference.block(
                        x, {k: weight(v) for k, v in lw.items()}, cfg)

            @jax.jit
            def head(x, position, fn, lm):
                with jax.default_matmul_precision("highest"):
                    return reference.logprobs_at(x, position, weight(fn),
                                                 weight(lm), cfg)

            @jax.jit
            def embed(table, tokens):
                return f32(take(table, tokens))

            compiled[key] = (layer, head, embed)
        return compiled[key]

    def mistral_forward(backend, weight=f32):
        """``forward(tokens, position)`` of ``reference.py`` over the
        engine's own arrays: what runs where the configuration names no
        reference."""
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

        layer, head, embed = programs(cfg, weight)

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

        return forward

    if job.get("reference") is None:
        forward_for = mistral_forward
    else:
        named_reference = named.load("references", job["reference"])

        def forward_for(backend, weight=f32):
            return named_reference.forward_for(backend, weight, take)

    # the control, never part of a benchmark run (PERF.md section 2a): the
    # reference at the next precision down in the program's place
    control = job.get("control")
    control_weight = lowered({"int4": 7, "int8": 127}[control]) \
        if control else None
    control_errors: list = []

    tol = float(job["tol"]["max"])
    # Under a wide limit a wrong id that fits one position can fit the next
    # ones too (under random weights the next token's values lean on the
    # whole context more than on the last id), and its chain's errors would
    # be reported: so where the limits name a narrower width, "first", ids
    # are sought within that first, and within "max" only for a probe that
    # has no chain there.
    widths = [float(job["tol"][k]) for k in ("first", "max")
              if k in job["tol"]]
    detail, errors, compared, ok = [], [], 0, True
    backends = {}
    for probe in job["probes"]:
        b = job["backends"][probe["backend"]]
        if b["name"] not in backends:
            # One slot row is enough here: the weights depend on the seed and
            # the spec, not on how many rows the cache has.
            backends[b["name"]] = TpuBackend.from_spec(BackendSpec(
                name=b["name"], url=b["url"] + "&slots=1", model=b["model"]))
        forward = forward_for(backends[b["name"]])

        prompt = list(probe["prompt"])
        served = probe["token_logprobs"]
        texts = probe["tokens"]
        total = len(prompt) + len(served)
        seen: dict = {}  # the reference's values after each chain of ids

        def search(tokens: list, t: int) -> tuple[int, list, list]:
            """Depth-first over the ids that fit position t and after, within
            ``width``: how many positions the best chain matched, its errors
            and its ids."""
            if t == len(served):
                return t, [], []
            chain_so_far = tuple(tokens[len(prompt):])
            if chain_so_far not in seen:
                if budget[0] <= 0:
                    return t, [], []
                budget[0] -= 1
                seen[chain_so_far] = forward(
                    tokens + [0] * (total - len(tokens)), len(tokens) - 1)
            lp = seen[chain_so_far]
            # the probe was served greedily: the served id had the largest
            # served value, so the reference holds it within tol of its own
            # largest (under random weights the values lie close together,
            # and without this some other id fits almost any served number)
            fits = sorted(
                (abs(float(lp[i]) - served[t]), int(i))
                for i in np.nonzero((np.abs(lp - served[t]) <= width)
                                    & (lp >= lp.max() - width))[0]
                if byte_matches(int(i), texts[t]))[:MAX_BRANCH]
            best = (t, [abs(float(lp.max()) - served[t])], [])
            for err, token_id in fits:
                depth, later, ids = search(tokens + [token_id], t + 1)
                if depth > best[0]:
                    best = (depth, [err] + later, [token_id] + ids)
                if depth == len(served):
                    break
            return best

        for width in widths:
            budget = [MAX_FORWARDS]
            n_cmp, errs, chain = search(prompt, 0)
            if n_cmp == len(served):
                break
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
                       "forwards": len(seen),
                       "abs_err": [round(e, 4) for e in errs]})
        if control:
            # at the served ids, the control's value for each against the
            # reference's: what the probe would read had the program
            # computed at that precision
            lower = forward_for(backends[b["name"]], control_weight)
            lows = []
            for t, token_id in enumerate(chain):
                at = len(prompt) + t - 1
                lows.append(abs(float(
                    lower(prompt + chain, at)[token_id]
                    - forward(prompt + chain, at)[token_id])))
            control_errors += lows
            detail[-1]["control_abs_err"] = [round(e, 4) for e in lows]
    for backend in backends.values():
        backend.engine.shutdown()
    mid = median(errors)
    out = {"ok": (ok and compared > 0
                  and mid <= float(job["tol"]["median"])),
           "compared": compared, "max_abs_err": max(errors or [0]),
           "median_abs_err": mid, "tol": job["tol"], "detail": detail}
    if control:
        worst, mid = max(control_errors or [0]), median(control_errors)
        out["control"] = {
            "precision": control, "compared": len(control_errors),
            "max_abs_err": worst, "median_abs_err": mid,
            "ok": (bool(control_errors) and worst <= tol
                   and mid <= float(job["tol"]["median"]))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
