"""A layer kind that is not attention and keeps no positions (``"C"``, the
gated short convolution of family ``lfm2_moe``; models/shortconv.py under
models/patterned.py): the served programs against the plain reference
(benchmarks/references/lfm2_moe.py, no code shared), what the slot cache owes
a row that holds a tail, the two expert paths against each other, the
engine's rules for a row that holds a state, the seeded init, the counters
and the scopes."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.analysis import hlo_names
from quorum_tpu.models import patterned, shortconv
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import MODEL_PRESETS, resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import named  # noqa: E402
import published_widths  # noqa: E402

REFERENCE = named.load("references", "lfm2_moe")
COST = named.load("cost_models", "lfm2_moe")
N_PROMPT, N_NEW, SLOT, SLOTS, SEGMENT = 40, 12, 1, 3, 16
# float32 activations: what is left is the order of the sums (3e-6 read);
# a fault of the tail moves a log-probability by 1e-2 and more (the controls)
TIGHT = 1e-4
GREEDY = SamplerConfig(temperature=0.0)
TINY = "lfm2-moe-tiny"


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


@pytest.fixture(scope="module")
def model32():
    spec = resolve_spec(TINY, {"dtype": "float32"})
    return spec, init_params(spec, 3)


@functools.partial(jax.jit, static_argnums=(1,))
def _admit(params, spec, padded, n, ck, cv):
    return tr.prefill(params, spec, padded, n, ck, cv, slot=jnp.int32(SLOT))


@functools.partial(jax.jit, static_argnums=(1,))
def _segment(params, spec, seg, off, n, ck, cv):
    return tr.prefill_segment(params, spec, seg, off, n, ck, cv,
                              jnp.int32(SLOT), history=64)


@functools.partial(jax.jit, static_argnums=(1,))
def _step(params, spec, tok, lens, live, ck, cv):
    return tr.decode_step(params, spec, tok, lens, ck, cv, write_mask=live,
                          history=64)


def admitted(spec, params, prompt, bucket: int, ck, cv, pad: int = 0):
    padded = np.full((1, bucket), pad, np.int32)
    padded[0, :len(prompt)] = prompt
    return _admit(params, spec, jnp.asarray(padded),
                  jnp.array([len(prompt)]), ck, cv)


def segmented(spec, params, prompt, ck, cv, segment: int = SEGMENT,
              pad: int = 0):
    """As the engine admits a row that holds a state: all but the prompt's
    last token, in segments; the register's decode step runs that one."""
    end = len(prompt) - 1
    for off in range(0, end, segment):
        n = min(segment, end - off)
        seg = np.full((1, segment), pad, np.int32)
        seg[0, :n] = prompt[off:off + n]
        ck, cv = _segment(params, spec, jnp.asarray(seg), jnp.int32(off),
                          jnp.int32(n), ck, cv)
    return ck, cv


def step(spec, params, token, position, ck, cv, live=True):
    tok = np.zeros((SLOTS,), np.int32)
    lens = np.zeros((SLOTS,), np.int32)
    mask = np.zeros((SLOTS,), bool)
    tok[SLOT], lens[SLOT], mask[SLOT] = token, position, live
    return _step(params, spec, jnp.asarray(tok), jnp.asarray(lens),
                 jnp.asarray(mask), ck, cv)


def served(spec, params, tokens, segment: bool, cache=None, pad: int = 0):
    """Log-probabilities at positions N_PROMPT-1 .. N_PROMPT+N_NEW-2 as the
    engine's programs compute them, and the cache after them."""
    ck, cv = cache or tr.init_cache(spec, SLOTS)
    out = []
    if segment:
        ck, cv = segmented(spec, params, tokens[:N_PROMPT], ck, cv, pad=pad)
        start = N_PROMPT - 1
    else:
        logits, ck, cv = admitted(spec, params, tokens[:N_PROMPT], 64, ck, cv,
                                  pad=pad)
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
        start = N_PROMPT
    for p in range(start, N_PROMPT + N_NEW - 1):
        logits, ck, cv = step(spec, params, tokens[p], p, ck, cv)
        out.append(jax.nn.log_softmax(logits[SLOT].astype(jnp.float32)))
    return np.stack([np.asarray(o) for o in out]), (ck, cv)


def reference_rows(forward, tokens):
    return np.stack([forward(list(tokens), p)
                     for p in range(N_PROMPT - 1, N_PROMPT + N_NEW - 1)])


def held(cache, spec=None):
    """A row's tails, every conv layer, and its K and V, every full layer:
    a leaf a written-out layer ``[slots, ...]``, then a leaf a slot of the
    period: tails ``[count, slots, ...]``, K and V ``[slots, count x K,
    ...]``."""
    spec = spec or resolve_spec(TINY)
    ck, cv = cache
    written = sum(1 for i in spec.layers_of("C") if i < spec.periods[0])
    return ([np.asarray(leaf[SLOT]) for leaf in ck.conv[:written]]
            + [np.asarray(leaf[:, SLOT]) for leaf in ck.conv[written:]]
            + [np.asarray(leaf[SLOT]) for leaf in ck.full + cv.full])


@functools.lru_cache(maxsize=None)
def _served32(segment: bool, n_layers: int = 8):
    spec = resolve_spec(TINY, {"dtype": "float32",
                               "n_layers": str(n_layers)})
    tokens = np.random.default_rng(0).integers(3, 512, size=N_PROMPT + N_NEW)
    return served(spec, init_params(spec, 3), tokens, segment)


# ---- the served path against the plain reference --------------------------------


@pytest.mark.parametrize("n_layers", [8, 7],
                         ids=["periods_scanned", "written_out"])
@pytest.mark.parametrize("segment", [False, True],
                         ids=["single_shot", "segmented"])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        tokens, segment, n_layers):
    """A bucket of 64 around a prompt of 40 (24 pad positions), or two whole
    segments of 16, a padded one of 7 and the register's decode step, then
    twelve decode steps: the reference's full forward pass in float32, to
    1e-4. At eight layers the six behind the dense ones are two whole
    periods and run as a scan over stacked leaves; at seven the loop is
    written out."""
    spec = resolve_spec(TINY, {"dtype": "float32",
                               "n_layers": str(n_layers)})
    assert spec.periods == ((2, 3, 2) if n_layers == 8 else (7, 0, 0))
    want = reference_rows(reference_of(spec, init_params(spec, 3)), tokens)
    got, _ = _served32(segment, n_layers)
    assert np.abs(got - want).max() < TIGHT


CONTROLS = [
    ({"conv": False}, 0.05), ({"reset_at": 2 * SEGMENT}, 0.002),
    ({"reset_at": N_PROMPT - 1}, 0.01),
    ({"pads": (N_PROMPT - 1, SEGMENT - (N_PROMPT - 1) % SEGMENT)}, 0.01),
    ({"rope_full": False}, 0.01), ({"bias_in_weights": True}, 0.002)]


@pytest.mark.parametrize("change,margin", CONTROLS, ids=[
    "conv", "reset_at_a_segment", "reset_at_the_register", "pads",
    "rope_full", "bias_in_weights"])
def test_a_control_comes_out_as_not_the_served_model(model32, tokens, change,
                                                     margin):
    """The controls of PERF.md section 2a: the reference without the conv
    operator, with the tail lost where one segment hands over to the next
    or to the register, with a padded segment's pads let into the tail,
    without rotary on the full layers, with the selection bias let into the
    weights. The served path agrees with the reference to 1e-4 (above);
    from each control its largest difference over the twelve positions is
    over the stated margin, 20 times that limit and more."""
    spec, params = model32
    want = reference_rows(reference_of(spec, params, change), tokens)
    best = want.argmax(-1)
    rows = np.arange(len(want))
    err = np.abs(_served32(True)[0][rows, best] - want[rows, best])
    assert err.max() > margin, (change, err)
    assert set(change) <= set(REFERENCE.CHANGES)


# ---- what the slot cache owes a row that holds a tail ---------------------------------


def test_pads_never_reach_the_tail(model32, tokens):
    """A prompt of 40 in a bucket of 64, and of 23 in a padded segment of
    32, whatever token the pad positions hold: the tail is what a program of
    exactly that many positions leaves, the row's true last two inputs."""
    spec, params = model32
    fresh = tr.init_cache(spec, SLOTS)
    n_conv = len(fresh[0].conv)  # the tails' leaves come first in held()
    _, ck0, cv0 = admitted(spec, params, tokens[:40], 40, *fresh)
    for pad in (0, 77):
        _, ck, cv = admitted(spec, params, tokens[:40], 64, *fresh, pad=pad)
        for got, want in zip(held((ck, cv)), held((ck0, cv0))[:n_conv]):
            assert np.abs(got - want).max() < 1e-5
    exact = tr.prefill_segment(
        params, spec, jnp.asarray(tokens[None, :23], jnp.int32), jnp.int32(0),
        jnp.int32(23), *fresh, jnp.int32(SLOT), history=64)
    for pad in (0, 77):
        seg = np.full((1, 32), pad, np.int32)
        seg[0, :23] = tokens[:23]
        padded = _segment(params, spec, jnp.asarray(seg), jnp.int32(0),
                          jnp.int32(23), *fresh)
        for got, want in zip(held(padded)[:n_conv], held(exact)[:n_conv]):
            assert np.abs(got - want).max() < 1e-5
    assert np.abs(held(exact)[0]).max() > 0.01  # and it did move
    assert held(exact)[0].shape == (spec.conv_taps - 1, spec.d_model)


def test_segments_give_the_tail_and_the_logits_of_one_admission(
        model32, tokens):
    """After the register's decode step on the prompt's last token, a row
    admitted in segments holds the tails and the log-probabilities of the
    same prompt admitted at once: each segment read the tail the one before
    left, the register the last one's."""
    spec, params = model32
    logits, ck, cv = admitted(spec, params, tokens[:N_PROMPT], 64,
                              *tr.init_cache(spec, SLOTS))
    sk, sv = segmented(spec, params, tokens[:N_PROMPT],
                       *tr.init_cache(spec, SLOTS))
    seg_logits, sk, sv = step(spec, params, tokens[N_PROMPT - 1],
                              N_PROMPT - 1, sk, sv)
    assert np.abs(np.asarray(seg_logits[SLOT] - logits[0])).max() < TIGHT
    n_conv = len(ck.conv)
    for got, want in zip(held((sk, sv))[:n_conv], held((ck, cv))[:n_conv]):
        assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("segment", [False, True],
                         ids=["single_shot", "segmented"])
def test_a_claimed_slot_starts_from_zero_whatever_its_last_tenant_left(
        model32, tokens, segment):
    """The slot's last tenant was longer and left tails, keys and values
    everywhere: a single-shot admit and a segment at offset 0 start from
    zeros all the same."""
    spec, params = model32
    dirty = jax.tree.map(
        lambda leaf: leaf + 1 if jnp.issubdtype(leaf.dtype, jnp.floating)
        else leaf, tr.init_cache(spec, SLOTS))
    got, _ = served(spec, params, tokens, segment, cache=dirty)
    assert np.abs(got - _served32(segment)[0]).max() < 1e-5


def test_a_row_the_step_may_not_write_is_not_moved(model32, tokens):
    """A row mid-admission or dead rides every decode chunk dispatched
    meanwhile: with its ``write_mask`` off its tails, K and V come back bit
    for bit, while a live row beside it shifts its tail by one."""
    spec, params = model32
    cache = segmented(spec, params, tokens[:N_PROMPT],
                      *tr.init_cache(spec, SLOTS))
    before = held(cache)
    tok = np.full((SLOTS,), 7, np.int32)
    lens = np.full((SLOTS,), 5, np.int32)
    live = np.ones((SLOTS,), bool)
    live[SLOT] = False
    after = _step(params, spec, jnp.asarray(tok), jnp.asarray(lens),
                  jnp.asarray(live), *cache)[1:]
    for got, want in zip(held(after), before):
        assert (got == want).all()
    tail0, tail1 = cache[0].conv[0][0], after[0].conv[0][0]
    assert (np.asarray(tail1[0]) == np.asarray(tail0[1])).all()  # shifted
    assert np.abs(np.asarray(tail1[1])).max() > 0


def test_many_rows_group_their_picks_and_few_run_every_expert(model32):
    """128 rows decode through the grouped tiles, 4 rows through every held
    expert over every row: the same four rows give the same logits on both,
    and the counters say which products ran."""
    spec, params = model32
    # a period's slots are read an expert at a time: grouped at any rows
    assert spec.periods[2] and not patterned.dense_experts(spec, 4)
    spec = resolve_spec(TINY, {"dtype": "float32", "n_layers": "7"})
    params = init_params(spec, 3)     # written out: both expert paths run
    assert patterned.dense_experts(spec, 4)
    assert not patterned.dense_experts(spec, 128)
    rng = np.random.default_rng(5)
    tok = rng.integers(3, 512, size=128).astype(np.int32)
    lens = rng.integers(0, 40, size=128).astype(np.int32)
    step_fn = jax.jit(lambda t, n, ck, cv: tr.decode_step(
        params, spec, t, n, ck, cv, history=64))
    outs = {}
    for rows in (4, 128):
        ck, cv = tr.init_cache(spec, rows)
        logits, ck, _ = step_fn(jnp.asarray(tok[:rows]),
                                jnp.asarray(lens[:rows]), ck, cv)
        outs[rows] = np.asarray(logits), np.asarray(ck.stats)
    assert np.abs(outs[128][0][:4] - outs[4][0]).max() < TIGHT
    # the same 128 rows through the tiles' kernel (the Pallas interpreter):
    # the decode step as a chip serves it, the counters the loop's
    ck, cv = tr.init_cache(spec, 128)
    logits, ck, _ = jax.jit(lambda t, n, ck, cv: patterned.decode_step(
        params, spec, t, n, ck, cv, history=64, interpret=True))(
            jnp.asarray(tok), jnp.asarray(lens), ck, cv)
    assert np.abs(np.asarray(logits) - outs[128][0]).max() < TIGHT
    assert (np.asarray(ck.stats) == outs[128][1]).all()
    names = patterned.stats_of(spec)
    col = {n: spec.held + names.index(n) for n in names}
    few, many = outs[4][1], outs[128][1]
    k, held_experts = spec.experts_per_token, spec.held
    assert (few[:, col["picks"]] == 4 * k).all()
    assert (few[:, col["tile_rows"]] == held_experts * 4).all()
    assert (many[:, col["picks"]] == 128 * k).all()
    # 128 rows' picks are 32 an expert of sixteen: whole tiles of 128 rows
    tile = patterned.tile_rows(spec, 128)
    assert tile == patterned.TILE
    assert patterned.tile_rows(spec, 4) == 16 < patterned.tile_rows(spec, 32)
    assert (many[:, col["tile_rows"]] % tile == 0).all()
    # every expert's picks fill whole tiles of its own
    assert (many[:, col["tile_rows"]] >= 128 * k).all()
    assert (many[:, col["tile_rows"]] <= 128 * k + held_experts * tile).all()
    assert (many[:, col["dropped"]] == 0).all()
    assert (few[:, col["dropped"]] == 0).all()


def test_overshoot_steps_and_rows_admitted_at_different_times(model32, tokens):
    """The engine's continuous batching: three requests of different
    lengths, admitted one after another into rows that decode side by side
    (single-shot and segmented; the third waits for a row and takes over a
    slot whose tenant overshot its budget inside a chunk), generate what
    each generates alone."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec, _ = model32
    prompts = [[int(t) for t in tokens[:12]], [int(t) for t in tokens[5:38]],
               [int(t) for t in tokens[20:45]]]
    news = (9, 14, 11)
    eng = InferenceEngine(spec, n_slots=2, seed=3, prefill_chunk=16)
    try:
        reqs = [eng.submit(p, max_new_tokens=n, sampler=GREEDY, seed=0)
                for p, n in zip(prompts, news)]
        got = [list(eng.stream_results(r)) for r in reqs]
        # then each alone, on the idle engine: the same programs, one row live
        want = [eng.generate(p, max_new_tokens=n, sampler=GREEDY,
                             seed=0).token_ids
                for p, n in zip(prompts, news)]
        assert got == want
    finally:
        eng.shutdown()


# ---- the engine: refusals, counters, spans -------------------------------------------

REFUSED = {
    "kv_quant=int8": dict(kv_quant="int8"),
    "quant=int8": dict(quant="int8"),
    "kv_pages=1": dict(kv_pages=True, kv_page_size=16),
    "prefix_store": dict(prefix_store="host"),
    "members>1": dict(members=2),
    "zero_drain=1": dict(zero_drain=True),
    "tp>1": dict(tp=2),
    "sp>1": dict(sp=2),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_what_cannot_carry_a_tail_is_refused_at_start_up(option):
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.parallel.mesh import MeshConfig, make_mesh

    asked = dict(REFUSED[option])
    axes = {k: asked.pop(k) for k in ("tp", "sp") if k in asked}
    mesh = make_mesh(MeshConfig(**axes), jax.devices()[:2]) if axes else None
    with pytest.raises(ValueError, match="does not compose") as said:
        InferenceEngine(resolve_spec(TINY), mesh, n_slots=2, **asked)
    assert option.split("=")[0].split(">")[0] in str(said.value)


def test_one_property_says_whether_a_row_holds_a_state():
    """``ModelSpec.row_state``: a mixer, or a pattern with a conv layer in
    the layers served; nothing else."""
    assert resolve_spec(TINY).row_state
    assert resolve_spec(TINY, {"n_layers": "7"}).row_state
    assert resolve_spec("falcon-h1-tiny").row_state
    assert resolve_spec("lfm2-8b-a1b", {"n_layers": "14"}).row_state
    for other in ("llama-tiny", "k-exaone-tiny", "dots3-tiny", "mixtral-tiny"):
        assert not resolve_spec(other).row_state
    with pytest.raises(AssertionError, match="short convolution"):
        resolve_spec(TINY, {"layer_pattern": "CXG"})


def test_the_engine_serves_it_and_counts_its_state():
    """Single-shot and segmented admissions through the scheduler generate
    the reference's greedy tokens; a row that holds a state is never taken
    up at a prefix; the gauges and the span's attributes count the tails."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec(TINY, {"dtype": "float32"})
    eng = InferenceEngine(spec, n_slots=3, seed=3, prefill_chunk=16)
    try:
        assert not eng.prefix_cache
        tokens = [int(t) for t in np.random.default_rng(1).integers(
            3, 512, size=40)]
        forward = reference_of(spec, eng.params)
        # 12: one admit; 30: a whole segment of 16 and a padded one of 13
        for n in (12, 30):
            got = eng.generate(tokens[:n], max_new_tokens=3, sampler=GREEDY,
                               seed=0).token_ids
            seq = tokens[:n] + got  # one length: the reference is causal
            for i, t in enumerate(got):
                assert t == int(forward(seq, n - 1 + i).argmax())
        m = eng.metrics()
        rows, conv = 3, len(spec.layers_of("C"))
        state = rows * conv * (spec.conv_taps - 1) * spec.d_model * 4
        assert m["kv_cache_state_bytes"] == state
        assert eng.health()["kv_cache_bytes"]["state"] == state
        assert m["kv_cache_full_bytes"] == (
            2 * len(spec.layers_of("G")) * rows * spec.max_seq
            * spec.n_kv_heads * spec.head_dim * 4)
        assert eng._state_carried(True) == {
            "state_carried": True, "state_bytes": state // rows}
        assert not [k for k in m if k.startswith("ssm_")]
        assert m["moe_picks_held_total"] == m["moe_picks_total"] > 0
        assert m["moe_dropped_picks_total"] == 0
        # the period's slots group their picks into tiles whatever the rows
        # (an expert is read where it lies, one at a time); nothing here
        # fills a tile, so the rows multiplied are many times the picks; a
        # tile's rows follow the program's, from 16 up
        assert m["moe_tile_rows_total"] % 16 == 0
        assert m["moe_tile_rows_total"] > 4 * m["moe_picks_held_total"]
    finally:
        eng.shutdown()


def test_a_spec_without_conv_layers_has_no_tail_leaf():
    """The leaf is absent there, not empty: the cache of the other patterned
    specs is the leaves it was, but for the counters' new column."""
    spec = resolve_spec("k-exaone-tiny")
    ck, _ = tr.init_cache(spec, 2)
    assert ck.conv == () and len(jax.tree.leaves(ck)) == len(
        ck.full + ck.window) + 1
    assert ck.full[0].shape == (2, spec.n_kv_heads, spec.max_seq,
                                spec.head_dim)
    assert ck.stats.shape[1] == spec.held + 3
    assert patterned.stats_of(spec)[-1] == "tile_rows"


# ---- the spec, the init, the scopes, the configuration --------------------------------


@pytest.mark.parametrize("preset, lanes", [
    ("lfm2-8b-a1b", True), ("lfm2-moe-tiny", True),
    ("k-exaone-236b-a23b", False), ("k-exaone-tiny", False),
    ("dots3-note-prev", False), ("dots3-tiny", False), ("mistral-7b", False)])
def test_positions_are_the_lanes_only_under_heads_narrower_than_the_lanes(
        preset, lanes):
    """The layout of a full side follows the spec's shapes and is nobody's
    to set: no field, so no ``tpu://`` option; the other patterned presets
    keep the K-major sides they had, the tiny ones at their models' 128."""
    spec = resolve_spec(preset, {"kv_positions_minor": str(int(not lanes))})
    assert spec.kv_positions_minor is lanes
    assert "kv_positions_minor" not in {
        f.name for f in dataclasses.fields(spec)}


def test_the_cache_is_the_attention_layers_and_a_tail_a_conv_layer(model32):
    spec = model32[0]
    ck, cv = tr.init_cache(spec, 2)
    tail = (2, spec.conv_taps - 1, spec.d_model)
    # two written-out conv layers, then the period's two conv slots, each
    # its two layers stacked; the period's one attention slot
    assert len(spec.layers_of("C")) == 6 and spec.periods == (2, 3, 2)
    assert [t.shape for t in ck.conv] == [tail, tail, (2,) + tail,
                                          (2,) + tail]
    assert cv.conv == () and len(ck.full) == len(cv.full) == 1
    # positions in the lanes (heads narrower than the chip's 128), the two
    # periods' heads side by side
    assert ck.full[0].shape == (2, 2 * spec.n_kv_heads, spec.head_dim,
                                spec.max_seq)
    written = resolve_spec(TINY, {"n_layers": "7"})
    ck7, _ = tr.init_cache(written, 2)
    assert [t.shape for t in ck7.conv] == [tail] * 5
    assert [t.shape for t in ck7.full] == [
        (2, spec.n_kv_heads, spec.head_dim, spec.max_seq)] * 2
    assert ck.stats.shape == (6, spec.held + len(patterned.STATS))
    assert patterned.STATS[-1] == "tile_rows"


def test_the_published_preset_is_the_published_config():
    spec = MODEL_PRESETS["lfm2-8b-a1b"]
    with open(os.path.join(BENCH, "configs", "published",
                           "lfm2-8b-a1b.json")) as f:
        pub = json.load(f)["config"]
    assert (spec.d_model, spec.n_layers, spec.n_heads, spec.n_kv_heads,
            spec.d_ff, spec.vocab_size, spec.d_ff_expert) == (
        pub["hidden_size"], pub["num_hidden_layers"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["intermediate_size"], pub["vocab_size"],
        pub["moe_intermediate_size"])
    assert spec.head_dim == pub["hidden_size"] // pub["num_attention_heads"]
    assert (spec.n_experts, spec.experts_per_token, spec.first_dense,
            spec.router_scale, spec.conv_taps, spec.rope_theta,
            spec.norm_eps) == (
        pub["num_experts"], pub["num_experts_per_tok"],
        pub["num_dense_layers"], pub["routed_scaling_factor"],
        pub["conv_L_cache"], pub["rope_theta"], pub["norm_eps"])
    kinds = {"conv": "C", "full_attention": "G"}
    assert spec.layer_pattern == "".join(
        kinds[k] for k in pub["layer_types"])
    assert spec.held == spec.n_experts and not spec.n_shared_experts
    assert spec.tied_lm_head and spec.rope_full and not spec.post_norm
    assert spec.init_depth == pub["num_hidden_layers"]


def test_the_configuration_file_is_held_to_its_source():
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-8b-a1b-l14")
    with open(os.path.join(BENCH, "..", entry["file"])) as f:
        data = json.load(f)
    assert published_widths.problems(entry, data) == []
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "max_position_embeddings"]


def test_the_seeded_init_leaves_something_to_compare(model32, tokens):
    """The head is the embedding: the final norm's gain is seeded at
    1/sqrt(D) with a random sign a channel, so a position's log-probabilities
    spread by nats and the token just read does not meet its own row in the
    head; the embedding's rows stay of unit rms, the larger part of the
    stream; the conv leaves are there, at 1/sqrt(fan_in)."""
    spec, params = model32
    forward = reference_of(spec, params)
    lp = forward(list(tokens), N_PROMPT)
    assert 1.5 < lp.max() - np.median(lp) < 8.0
    assert int(lp.argmax()) != int(tokens[N_PROMPT])  # no spike on the input
    assert params["lm_head"] is None
    assert abs(np.asarray(params["tok_emb"]).std() - 1.0) < 0.05
    gain = np.asarray(params["final_norm_w"])
    assert np.allclose(np.abs(gain), spec.d_model ** -0.5)
    assert 0.25 < (gain > 0).mean() < 0.75
    assert (np.asarray(init_params(resolve_spec("k-exaone-tiny"), 3)[
        "final_norm_w"]) == 1).all()
    lyr = params["layers"]["00"]
    assert lyr["conv_in"].shape == (1, spec.d_model, 3 * spec.d_model)
    assert lyr["conv_w"].shape == (1, spec.conv_taps, spec.d_model)
    assert abs(np.asarray(lyr["conv_w"]).std() * spec.conv_taps ** 0.5
               - 1.0) < 0.2
    assert "wq" not in lyr and "w_gate" in lyr           # a dense conv layer
    # the period's slots, their two layers stacked
    assert sorted(params["layers"]) == ["00", "01", "02x2", "03x2", "04x2"]
    slot = params["layers"]["02x2"]
    assert "wq" in slot and slot["router"].shape == (2, spec.d_model,
                                                     spec.n_experts)
    assert np.abs(np.asarray(slot["router_bias"])).max() > 0
    written = init_params(resolve_spec(TINY, {"dtype": "float32",
                                              "n_layers": "7"}), 3)
    assert sorted(written["layers"]) == [f"{i:02d}" for i in range(7)]
    # stacked or written out, a layer's weights are its own key's
    assert (np.asarray(written["layers"]["02"]["wq"][0])
            == np.asarray(slot["wq"][0])).all()


def test_a_conv_program_carries_its_scopes(model32):
    spec, params = model32
    ck, cv = tr.init_cache(spec, SLOTS)
    seg = _segment.lower(params, spec, jnp.zeros((1, 16), jnp.int32),
                         jnp.int32(0), jnp.int32(16), ck, cv).as_text(
                             debug_info=True)
    dec = _step.lower(params, spec, jnp.zeros((SLOTS,), jnp.int32),
                      jnp.zeros((SLOTS,), jnp.int32),
                      jnp.ones((SLOTS,), bool), ck, cv).as_text(
                          debug_info=True)
    assert set(hlo_names.SHORTCONV) == {"conv.in_proj", "conv.taps",
                                        "conv.out_proj"}
    for text in (seg, dec):
        for scope in hlo_names.SHORTCONV:
            assert f"{scope}/" in text, scope
            assert hlo_names.part_of(f"jit(f)/while/body/{scope}/mul") == scope


def _location(text: str, ref: str) -> str:
    """What a ``#locN`` of a lowered module's text stands for, the
    locations it names in their place."""
    found = re.search(rf"^{re.escape(ref)} = loc\((.*)\)$", text, re.M)
    body = found.group(1) if found else ""
    return body + "".join(_location(text, inner)
                          for inner in re.findall(r"#loc\d+", body))


def test_the_tiles_kernel_carries_the_experts_scope(caplog):
    """A decode step of the cell's shape (64 rows, layers 2-13 a scan over
    three periods, published widths), lowered for a TPU without one: a
    Mosaic call a period's expert slot, each handed the stacked leaves whole
    and each inside ``moe.experts``, the scope ``hlo_names`` reads a profile
    by; the program says once which form its tiles take."""
    caplog.set_level("INFO", logger="quorum_tpu.ops.grouped_experts")
    spec = resolve_spec("lfm2-8b-a1b", {
        "n_layers": "14", "max_seq": "256", "vocab_size": "1024"})
    params = jax.eval_shape(lambda: init_params(spec, 0))
    ck, cv = jax.eval_shape(lambda: tr.init_cache(spec, 64))
    rows = jax.ShapeDtypeStruct((64,), jnp.int32)
    text = jax.jit(lambda p, t, n, ck, cv: tr.decode_step(
        p, spec, t, n, ck, cv, history=128)).trace(
            params, rows, rows, ck, cv).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln
             and "tensor<3x32x2048x1792xbf16>" in ln]
    assert len(calls) == 4          # the period's four expert slots
    for line in calls:
        assert "tensor<3x32x1792x2048xbf16>" in line     # no slice before it
        where = _location(text, re.search(r"loc\((#loc\d+)\)$",
                                          line).group(1))
        assert "moe.experts/" in where and "grouped_experts" in where
        op_name = re.search(r'"([^"]*moe\.experts/[^"]*)"', where).group(1)
        assert hlo_names.part_of(op_name) == "moe.experts"
    # as the compiler's text names the call after optimisation
    table = hlo_names.instructions(
        "ENTRY %main.1 (p: f32[2]) -> f32[2] {\n"
        "  %custom-call.7 = f32[4352,2048]{1,0} custom-call(s32[34]{0} %a), "
        'custom_call_target="tpu_custom_call", backend_config={"x": {}}, '
        'metadata={op_name="jit(chunk)/while/body/moe.experts/'
        'grouped_experts" source_file="x.py"}\n}')
    assert table == {"custom-call.7": (
        "custom-call", "jit(chunk)/while/body/moe.experts/grouped_experts")}
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("moe_tiles")]
    assert len(lines) == 1          # one a program, not one a layer
    assert "path=kernel" in lines[0] and "rows=64 tile_rows=32" in lines[0]
    assert "experts=32x[2048, 1792]" in lines[0]
    # the CPU's lowering of the same program keeps the loop: no Mosaic call
    assert "tpu_custom_call" not in jax.jit(lambda p, t, n, ck, cv: (
        tr.decode_step(p, spec, t, n, ck, cv, history=128))).lower(
            params, rows, rows, ck, cv).as_text()


def test_a_traced_program_logs_its_conv_path(model32, caplog):
    spec, params = model32
    caplog.set_level("INFO", logger="quorum_tpu.models.shortconv")
    ck, cv = tr.init_cache(spec, SLOTS)
    jax.eval_shape(lambda: tr.prefill_segment(
        params, spec, jnp.zeros((1, 16), jnp.int32), jnp.int32(0),
        jnp.int32(16), ck, cv, jnp.int32(0), history=64))
    jax.eval_shape(lambda: tr.decode_step(
        params, spec, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), ck, cv, history=64))
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("conv-path")]
    assert len(lines) == 2  # one a program, not one a layer
    assert any("positions=16 " in ln and "layers=6" in ln for ln in lines)
    assert any("positions=1 " in ln and "tail[2, 64]" in ln for ln in lines)


def test_the_mixer_and_the_short_convolution_share_the_tail_arithmetic():
    """``shortconv.causal_taps`` is what ``ssm._conv`` runs: with a bias and
    without, the sums over ``[tail ; x]`` and the tail at each row's true
    length (0: the tail as it came)."""
    from quorum_tpu.models import ssm

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 5, 6)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    n_valid = jnp.asarray([3, 0])
    out, new_tail = shortconv.causal_taps(x, tail, w, n_valid)
    seq = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    want = sum(np.asarray(w)[k] * seq[:, k:k + 5] for k in range(4))
    assert np.abs(np.asarray(out) - want).max() < 1e-5
    assert (np.asarray(new_tail[0]) == seq[0, 3:6]).all()
    assert (np.asarray(new_tail[1]) == np.asarray(tail[1])).all()
    act, mixer_tail = ssm._conv(x, tail, {"ssm_conv_w": w, "ssm_conv_b": b},
                                n_valid)
    assert np.abs(np.asarray(act) - np.asarray(
        jax.nn.silu(want + np.asarray(b)))).max() < 1e-5
    assert (np.asarray(mixer_tail) == np.asarray(new_tail)).all()


def test_the_cost_model_counts_the_experts_the_rows_are_expected_to_pick():
    """A decode step's least bytes at the cell's size: 9.33 GB of weights at
    128 rows (every expert), far fewer experts where few rows are live, so
    that no roofline share reads over 100 % in the ramp."""
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-l14.json")) as f:
        cfg = json.load(f)
    assert abs(COST.experts_read(cfg, 128) - 32.0) < 1e-4
    assert abs(COST.experts_read(cfg, 1) - 4.0) < 1e-9
    assert 28.0 < COST.experts_read(cfg, 16) < 28.5
    _, full = COST.decode_step(cfg, 128, 0)
    _, one = COST.decode_step(cfg, 1, 0)
    assert abs(full / 1e9 - 9.33) < 0.04      # 23 MB of tails beside them
    assert one < 0.25 * full
    assert COST.kv_bytes_per_token(cfg) == 3 * 2 * 8 * 64 * 2
    assert COST.state_bytes_per_row(cfg) == 11 * 2 * 2048 * 2
    ops, byts = COST.prefill(cfg, 185, 185, 1)
    assert byts > 9.0e9 and ops < 0.5e12   # an admit is bound by its bytes
