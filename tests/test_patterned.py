"""A spec with a layer pattern (models/patterned.py) against its plain
reference, ``benchmarks/references/k_exaone.py``: the file the benchmark's
``correct`` uses, not a second one.

At the family's tiny preset (a dense layer, two ``LLLG`` periods, 16 experts of
which 4 are held, top-4, window 8, ring 8) a prompt of 40 wraps every ring five
times, and 12 decoded positions go through both kinds of cache. Logits are
compared, not tokens.
"""

import functools
import hashlib
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import patterned
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import resolve_spec

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import named  # noqa: E402

REFERENCE = named.load("references", "k_exaone")
N_PROMPT, N_NEW, SLOT, SLOTS = 40, 12, 1, 3
STAT = {name: i for i, name in enumerate(patterned.STATS)}
# float32 activations: what is left is the order of the sums (1e-6 read).
# bfloat16: the benchmark's limits for two bytes a weight (run.PROBE_TOL[2]),
# at the reference's own most likely id as the probe reads them; at this tiny
# width they read 0.003 and 0.0015 (the stream is float32, the sub-layers
# bfloat16); a fault moves every position by 0.04 and more (the controls).
TIGHT = 2e-4
BF16_MAX, BF16_MEDIAN = 0.1, 0.02


def f32(leaf):
    return leaf.astype(jnp.float32)


def take(leaf, *idx):
    return leaf[idx]


def reference_of(spec, params, changes=None):
    backend = types.SimpleNamespace(
        engine=types.SimpleNamespace(spec=spec, params=params))
    return REFERENCE.forward_for(backend, f32, take, changes)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(3, 512, size=N_PROMPT + N_NEW)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model(request):
    spec = resolve_spec("k-exaone-tiny", {"dtype": request.param})
    return spec, init_params(spec, 3)


@pytest.fixture(scope="module")
def model32():
    spec = resolve_spec("k-exaone-tiny", {"dtype": "float32"})
    return spec, init_params(spec, 3)


@functools.partial(jax.jit, static_argnums=(1,))
def _segment(params, spec, seg, off, n, ck, cv):
    return tr.prefill_segment(params, spec, seg, off, n, ck, cv,
                              jnp.int32(SLOT), history=64)


@functools.partial(jax.jit, static_argnums=(1,))
def _step(params, spec, tok, lens, live, ck, cv):
    return tr.decode_step(params, spec, tok, lens, ck, cv, write_mask=live,
                          history=64)


def served(spec, params, tokens, segment: int):
    """Log-probabilities at positions N_PROMPT-1 .. N_PROMPT+N_NEW-2 as the
    engine's programs compute them: the prompt admitted in one shot
    (``segment`` 0) or in segments, then one decode step a position."""
    ck, cv = tr.init_cache(spec, SLOTS)
    out = []
    if segment:
        for off in range(0, N_PROMPT, segment):
            n = min(segment, N_PROMPT - off)
            seg = np.zeros((1, segment), np.int32)
            seg[0, :n] = tokens[off:off + n]
            ck, cv = _segment(params, spec, jnp.asarray(seg), jnp.int32(off),
                              jnp.int32(n), ck, cv)
        start = N_PROMPT - 1
    else:
        pad = np.zeros((1, 64), np.int32)
        pad[0, :N_PROMPT] = tokens[:N_PROMPT]
        logits, ck, cv = tr.prefill(
            params, spec, jnp.asarray(pad), jnp.array([N_PROMPT]), ck, cv,
            slot=jnp.int32(SLOT))
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
        start = N_PROMPT
    for p in range(start, N_PROMPT + N_NEW - 1):
        tok = np.zeros((SLOTS,), np.int32)
        lens = np.zeros((SLOTS,), np.int32)
        live = np.zeros((SLOTS,), bool)
        tok[SLOT], lens[SLOT], live[SLOT] = tokens[p], p, True
        logits, ck, cv = _step(params, spec, jnp.asarray(tok),
                               jnp.asarray(lens), jnp.asarray(live), ck, cv)
        out.append(jax.nn.log_softmax(logits[SLOT].astype(jnp.float32)))
    return np.stack([np.asarray(o) for o in out]), ck


def reference_rows(forward, tokens):
    return np.stack([forward(list(tokens), p)
                     for p in range(N_PROMPT - 1, N_PROMPT + N_NEW - 1)])


def errors_at_best(got, want):
    best = want.argmax(-1)
    rows = np.arange(len(want))
    return np.abs(got[rows, best] - want[rows, best])


@pytest.mark.parametrize("segment", [0, 16], ids=["single_shot", "segmented"])
def test_prefill_then_decode_through_both_caches(model, tokens, segment):
    spec, params = model
    want = reference_rows(reference_of(spec, params), tokens)
    got, ck = served(spec, params, tokens, segment)
    if spec.dtype == "float32":
        assert np.abs(got - want).max() < TIGHT
    else:
        err = errors_at_best(got, want)
        assert err.max() < BF16_MAX and np.median(err) < BF16_MEDIAN
    # every real token of every expert layer was counted, none dropped
    stats = np.asarray(ck.stats)
    held, rest = stats[:, :spec.held], stats[:, spec.held:]
    steps = N_NEW if segment else N_NEW - 1  # segmented: position 39 again
    assert (rest[:, STAT["picks"]]
            == (N_PROMPT + steps) * spec.experts_per_token).all()
    assert (rest[:, STAT["dropped"]] == 0).all()
    assert 0 < held.sum() < rest[:, STAT["picks"]].sum()


@pytest.mark.parametrize("change", [
    {"scoring": "softmax"}, {"scale": 1.0}, {"routed": False},
    {"rope_full": True}, {"window": 7}], ids=lambda c: next(iter(c)))
def test_a_control_comes_out_as_not_the_served_model(model32, tokens, change):
    """Each control turns one stated choice of the reference into something
    else; the served path has to differ from it by far more than from the
    reference itself (the test above: under 2e-4)."""
    spec, params = model32
    got, _ = served(spec, params, tokens, segment=16)
    want = reference_rows(reference_of(spec, params, change), tokens)
    err = errors_at_best(got, want)
    assert np.median(err) > 50 * TIGHT, (change, err)


@pytest.mark.parametrize("preset", ["k-exaone-tiny", "dots3-tiny"])
def test_the_shares_add_up_to_the_uncut_layer(preset):
    """The routed parts that the four shares of four experts give, plus the
    shared expert once, are the layer with every expert held: in both
    families, whose expert layer is one body."""
    whole = resolve_spec(preset, {"dtype": "float32", "experts_held": "0"})
    lyr = patterned.layer_of(init_params(whole, 5), 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, whole.d_model))
    ok = jnp.ones((2, 9), bool)
    uncut, counts = patterned.moe_layer(x, lyr, whole, ok)
    picks = int(counts[whole.held + STAT["picks"]])
    assert int(counts[:whole.held].sum()) == picks  # every pick is held
    no_shared = resolve_spec(preset, {
        "dtype": "float32", "n_shared_experts": "0"})
    total = tr._dense_mlp_core(x, lyr["shared"], whole)
    held_picks = 0
    for share in range(4):
        spec = resolve_spec(preset, {
            "dtype": "float32", "n_shared_experts": "0",
            "expert_first": str(4 * share)})
        mine = dict(lyr, **{k: lyr[k][4 * share:4 * share + 4] for k in (
            "moe_w_gate", "moe_w_up", "moe_w_down")})
        part, c = patterned.moe_layer(x, mine, spec, ok)
        total = total + part
        held_picks += int(c[:spec.held].sum())
    assert no_shared.held == 4
    assert held_picks == picks
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)


def _all_on_the_held_experts(monkeypatch):
    """128 tokens whose four picks all fall on the four held experts, in
    tiles of 32 rows: every expert fills four tiles of its own."""
    monkeypatch.setattr(patterned, "TILE", 32)
    spec = resolve_spec("k-exaone-tiny", {"dtype": "float32"})
    lyr = patterned.layer_of(init_params(spec, 7), 1)
    lyr["router_bias"] = jnp.where(jnp.arange(spec.n_experts) < 4, 10.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, spec.d_model))
    assert 128 > patterned.DENSE_ROWS
    return spec, lyr, x, jnp.ones((1, 128), bool)


def test_no_pick_is_dropped_when_every_token_picks_the_same_experts(
        monkeypatch):
    """Sixteen times the mean load on each held expert: the loop runs as many
    tiles as the picks fill, the answer is the dense form's, and the
    products took every held pick."""
    spec, lyr, x, ok = _all_on_the_held_experts(monkeypatch)
    grouped, counts = jax.jit(
        lambda x: patterned.moe_layer(x, lyr, spec, ok))(x)
    dense, _ = patterned.moe_layer(x, lyr, spec, ok, dense=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               atol=1e-5)
    # four tiles of 128 rows, every row a pick
    assert np.asarray(counts).tolist() == [128] * 4 + [128 * 4, 0, 4 * 128]


def test_a_tile_the_loop_leaves_out_counts_as_dropped(monkeypatch):
    """The counter is no constant: it is the held picks less the rows the
    products took, so a loop that stops one tile short drops that tile's."""
    spec, lyr, x, ok = _all_on_the_held_experts(monkeypatch)
    whole = jax.lax.fori_loop
    monkeypatch.setattr(
        patterned.lax, "fori_loop",
        lambda lo, hi, body, init: whole(lo, hi - 1, body, init))
    _, counts = patterned.moe_layer(x, lyr, spec, ok)
    assert int(counts[spec.held + STAT["dropped"]]) == patterned.TILE


def test_grouped_experts_are_the_dense_ones_under_even_routing():
    spec = resolve_spec("k-exaone-tiny", {"dtype": "float32"})
    lyr = patterned.layer_of(init_params(spec, 7), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 96, spec.d_model))
    ok = (jnp.arange(96) < 80)[None, :]  # padding stays out of the buffers
    grouped, counts = patterned.moe_layer(x, lyr, spec, ok, dense=False)
    dense, _ = patterned.moe_layer(x, lyr, spec, ok, dense=True)
    np.testing.assert_allclose(np.asarray(grouped)[:, :80],
                               np.asarray(dense)[:, :80], atol=1e-5)
    # every held expert's picks fit one tile, part-filled
    assert np.asarray(counts)[spec.held:].tolist() == [
        80 * spec.experts_per_token, 0, spec.held * patterned.TILE]


def test_ring_positions_and_write():
    held = np.asarray(patterned.ring_positions(jnp.array([-1, 2, 8, 21]), 8))
    assert (held[0] < 0).all()
    assert held[1].tolist()[:3] == [0, 1, 2] and (held[1][3:] < 0).all()
    assert sorted(held[2].tolist()) == list(range(1, 9))
    assert sorted(held[3].tolist()) == list(range(14, 22))
    assert all(p % 8 == j for row in held[1:] for j, p in enumerate(row)
               if p >= 0)
    # 20 positions from 5, of which 13 are real, into a ring of 8
    ring = jnp.full((1, 1, 8, 1), -1.0)
    value = jnp.arange(5, 25, dtype=jnp.float32).reshape(1, 1, 20, 1)
    out = np.asarray(patterned.ring_write(
        ring, value, jnp.array([5]), jnp.array([13])))[0, 0, :, 0]
    assert sorted(out.tolist()) == list(range(10, 18))
    assert all(int(p) % 8 == j for j, p in enumerate(out))


REFUSED = {
    "kv_quant=int8": dict(kv_quant="int8"),
    "quant=int8": dict(quant="int8"),
    "kv_pages=1": dict(kv_pages=True, kv_page_size=16),
    "prefix_store": dict(prefix_store="host"),
    "members>1": dict(members=2),
    "zero_drain=1": dict(zero_drain=True),
}


@pytest.mark.parametrize("preset", ["k-exaone-tiny", "dots3-tiny"])
@pytest.mark.parametrize("option", sorted(REFUSED))
def test_a_patterned_spec_refuses_what_does_not_compose(option, preset):
    """What reads the cache as a K/V rectangle stays refused: for K and V by
    layer kind and for latent rows, index keys and rings alike."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec(preset)
    with pytest.raises(ValueError, match="layer_pattern spec"):
        InferenceEngine(spec, n_slots=2, **REFUSED[option])


def test_the_benchmarks_comparison_runs_over_this_reference_by_name(tmp_path):
    """``reference_check.py``, the child that decides a cell's ``correct``,
    finds this file by the name a configuration would give it, and holds a
    greedy probe (a segmented prompt, then decode steps) of the engine's own
    seeded weights inside the limits for two bytes a weight. Its ``int8``
    control reads inside them too, here as at the published widths (PERF.md
    section 2a): the cell's ``correct`` does not tell the two precisions
    apart, and a ``benchmark`` PR owes it limits of its own."""
    import json
    import subprocess

    import run as bench_run
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    b = {"name": "LLM1", "model": "k-exaone-tiny",
         "url": "tpu://k-exaone-tiny?seed=5"}
    backend = TpuBackend.from_spec(BackendSpec(
        name=b["name"], url=b["url"] + "&slots=1", model=b["model"]))
    try:
        spec, params = backend.engine.spec, backend.engine.params
        prompt = np.random.default_rng(5).integers(3, 512, size=N_PROMPT)
        ck, cv = tr.init_cache(spec, SLOTS)
        for off in range(0, N_PROMPT - 1, 13):
            n = min(13, N_PROMPT - 1 - off)
            seg = np.zeros((1, 16), np.int32)
            seg[0, :n] = prompt[off:off + n]
            ck, cv = _segment(params, spec, jnp.asarray(seg), jnp.int32(off),
                              jnp.int32(n), ck, cv)
        ids, values, last = [], [], int(prompt[-1])
        for p in range(N_PROMPT - 1, N_PROMPT + N_NEW - 1):
            tok = np.zeros((SLOTS,), np.int32)
            lens = np.zeros((SLOTS,), np.int32)
            tok[SLOT], lens[SLOT] = last, p
            logits, ck, cv = _step(params, spec, jnp.asarray(tok),
                                   jnp.asarray(lens),
                                   jnp.asarray(np.arange(SLOTS) == SLOT),
                                   ck, cv)
            lp = jax.nn.log_softmax(logits[SLOT].astype(jnp.float32))
            last = int(jnp.argmax(lp))
            ids.append(last)
            values.append(float(lp[last]))
    finally:
        backend.engine.shutdown()

    def shown(i: int) -> str:  # the byte tokenizer, as the wire shows an id
        byte = (i - 3) % 256
        return "" if i < 3 else chr(byte) if byte < 128 else "\ufffd"

    job = {"platform": "cpu", "reference": "k_exaone", "control": "int8",
           "tol": bench_run.PROBE_TOL[2], "backends": [b],
           "probes": [{"backend": 0, "prompt": [int(t) for t in prompt],
                       "token_logprobs": values,
                       "tokens": [shown(i) for i in ids]}]}
    path = tmp_path / "probe.json"
    path.write_text(json.dumps(job))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check.py"),
         str(path)], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 QUORUM_TPU_COMPILE_CACHE="0"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True and verdict["compared"] == N_NEW, verdict
    control = verdict["control"]
    assert control["compared"] == N_NEW
    assert control["median_abs_err"] > verdict["median_abs_err"]
    assert control["ok"] is True  # the finding, kept where it can be seen


def test_a_patterned_spec_has_no_cache_free_forward(model32):
    spec, params = model32
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        tr.forward_logits(params, spec, jnp.zeros((1, 8), jnp.int32))


# What an accepted cell's spec lowers to, as the parent of PR 30 lowered it
# (as PR 35 left all three: the dense cache stored positions-major with the
# heads flattened, ``[L, B, max_seq, K·hd]``, and read where it lies;
# sha256 of ``jit(...).lower(...).as_text()``, jax 0.9.0): a spec without a
# layer_pattern compiles the programs it compiled before the patterned family
# was added. A change of transformer.py that is meant to change them, or a
# new jax, writes the new values here.
UNPATTERNED = {
    "decode": "451cdb28f603831cecc0bbdc4177bfb6083f4dc3d702a706b42682574ee4c11a",
    "admit": "8da61f804ade4d48fd674f4f8c83ff4e5bff705860cdd08c26676a349c061535",
    "segment": "89484f518a8d2e3e418e2595b9e258c672f5787484a8ff19c12c3fddf05d93a5",
}


@pytest.mark.parametrize("program", sorted(UNPATTERNED))
def test_a_spec_without_a_pattern_lowers_to_what_it_lowered_to(program):
    spec = resolve_spec("llama-tiny", {"sliding_window": "64"})
    params = jax.eval_shape(lambda: init_params(spec, 0))
    ck, cv = jax.eval_shape(lambda: tr.init_cache(spec, 4))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lowered = {
        "decode": lambda: jax.jit(
            lambda p, t, l, k, v: tr.decode_step(
                p, spec, t, l, k, v, history=64)).lower(
            params, i32(4), i32(4), ck, cv),
        "admit": lambda: jax.jit(
            lambda p, t, l, s, k, v: tr.prefill(
                p, spec, t, l, k, v, slot=s)).lower(
            params, i32(1, 32), i32(1), i32(), ck, cv),
        "segment": lambda: jax.jit(
            lambda p, t, o, n, s, k, v: tr.prefill_segment(
                p, spec, t, o, n, k, v, s, history=64)).lower(
            params, i32(1, 16), i32(), i32(), i32(), ck, cv),
    }[program]()
    text = lowered.as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == UNPATTERNED[program]


def test_a_patterned_decode_step_carries_its_scopes(model32):
    """Device operations of a patterned program name their layer part:
    attention by layer kind, the router, the held experts, the shared one."""
    from quorum_tpu.analysis import hlo_names

    spec, params = model32
    ck, cv = tr.init_cache(spec, SLOTS)
    text = _step.lower(params, spec, jnp.zeros((SLOTS,), jnp.int32),
                       jnp.zeros((SLOTS,), jnp.int32),
                       jnp.ones((SLOTS,), bool), ck, cv).as_text(
                           debug_info=True)
    for scope in hlo_names.PATTERNED + ("attn.core", "mlp", "lm_head"):
        assert f"/{scope}/" in text or f'{scope}"' in text, scope
    assert hlo_names.part_of(
        "jit(chunk)/attn.core/attn.window/dot_general") == "attn.window"
    assert hlo_names.part_of("jit(seg)/moe.shared/dot_general") == \
        "moe.shared"


def test_the_engine_serves_it_and_counts_its_picks():
    """Chunked and single-shot admission, decode chunks, the counters on
    metrics() and the cache's bytes by kind on health()."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec("k-exaone-tiny")
    eng = InferenceEngine(spec, n_slots=4, prefill_chunk=16, decode_chunk=4,
                          seed=1)
    try:
        long = list(eng.generate_stream(list(range(3, 43)), max_new_tokens=9))
        short = list(eng.generate_stream(list(range(3, 13)),
                                         max_new_tokens=9))
        again = list(eng.generate_stream(list(range(3, 43)),
                                         max_new_tokens=9))
        assert len(long) == len(short) == 9 and long == again
        assert not eng.prefix_cache  # a ring holds no prefix to reuse
        m = eng.metrics()
        assert m["moe_dropped_picks_total"] == 0
        assert m["moe_picks_total"] % spec.experts_per_token == 0
        assert 0 < m["moe_picks_held_total"] < m["moe_picks_total"]
        per_expert = m["moe_expert_picks_total"]
        assert len(per_expert) == 7 * spec.held
        assert sum(per_expert.values()) == m["moe_picks_held_total"]
        assert 'layer="1",expert="0"' in per_expert
        kinds = eng.health()["kv_cache_bytes"]
        assert kinds == {"full": m["kv_cache_full_bytes"],
                         "window": m["kv_cache_window_bytes"],
                         "index": m["kv_cache_index_bytes"]}
        row = 2 * 4 * spec.n_kv_heads * spec.head_dim * 2
        assert kinds == {"full": 2 * spec.max_seq * row,
                         "window": 6 * spec.ring * row, "index": 0}
    finally:
        eng.shutdown()
