"""A Mamba-2 mixer beside attention in every block of the dense family
(``ssm_heads``; family ``falcon_h1``; models/ssm.py): the served programs
against the plain reference (benchmarks/references/falcon_h1.py, no code
shared), the two forms of the recurrence against each other, what the slot
cache owes a row that holds a state, the start-up refusals, the seeded init,
the counters and the scopes."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.analysis import hlo_names
from quorum_tpu.models import ssm
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import MODEL_PRESETS, resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import named  # noqa: E402
import published_widths  # noqa: E402

REFERENCE = named.load("references", "falcon_h1")
N_PROMPT, N_NEW, SLOT, SLOTS, SEGMENT = 40, 12, 1, 3, 16
# float32 activations: what is left is the order of the sums (2e-6 read)
TIGHT = 1e-4
GREEDY = SamplerConfig(temperature=0.0)


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
    return np.random.default_rng(0).integers(3, 256, size=N_PROMPT + N_NEW)


@pytest.fixture(scope="module")
def model32():
    spec = resolve_spec("falcon-h1-tiny", {"dtype": "float32"})
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


def admitted(spec, params, prompt, bucket: int, ck, cv):
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :len(prompt)] = prompt
    return _admit(params, spec, jnp.asarray(pad), jnp.array([len(prompt)]),
                  ck, cv)


def segmented(spec, params, prompt, ck, cv, segment: int = SEGMENT):
    """As the engine admits a row that holds a state: all but the prompt's
    last token, in segments; the register's decode step runs that one."""
    end = len(prompt) - 1
    for off in range(0, end, segment):
        n = min(segment, end - off)
        seg = np.zeros((1, segment), np.int32)
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


def served(spec, params, tokens, segment: bool, cache=None):
    """Log-probabilities at positions N_PROMPT-1 .. N_PROMPT+N_NEW-2 as the
    engine's programs compute them, and the cache after them."""
    ck, cv = cache or tr.init_cache(spec, SLOTS)
    out = []
    if segment:
        ck, cv = segmented(spec, params, tokens[:N_PROMPT], ck, cv)
        start = N_PROMPT - 1
    else:
        logits, ck, cv = admitted(spec, params, tokens[:N_PROMPT], 64, ck, cv)
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
        start = N_PROMPT
    for p in range(start, N_PROMPT + N_NEW - 1):
        logits, ck, cv = step(spec, params, tokens[p], p, ck, cv)
        out.append(jax.nn.log_softmax(logits[SLOT].astype(jnp.float32)))
    return np.stack([np.asarray(o) for o in out]), (ck, cv)


def reference_rows(forward, tokens):
    return np.stack([forward(list(tokens), p)
                     for p in range(N_PROMPT - 1, N_PROMPT + N_NEW - 1)])


def carried(cache):
    """A row's state and tail, every layer, and its K and V lines."""
    ck, cv = cache
    return [np.asarray(leaf[:, SLOT]) for leaf in (
        ck.carry, cv.carry, ck.kv, cv.kv)]


@functools.lru_cache(maxsize=None)
def _served32(segment: bool):
    spec = resolve_spec("falcon-h1-tiny", {"dtype": "float32"})
    tokens = np.random.default_rng(0).integers(3, 256, size=N_PROMPT + N_NEW)
    return served(spec, init_params(spec, 3), tokens, segment)


# ---- the served path against the plain reference --------------------------------


@pytest.mark.parametrize("segment", [False, True],
                         ids=["single_shot", "segmented"])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        model32, tokens, segment):
    """A bucket of 64 around a prompt of 40 (24 pad positions), or two whole
    segments of 16 and a padded one of 7, then twelve decode steps: the
    reference's full forward pass in float32, to 1e-4."""
    spec, params = model32
    want = reference_rows(reference_of(spec, params), tokens)
    got, _ = _served32(segment)
    assert np.abs(got - want).max() < TIGHT


def test_the_cache_free_forward_is_the_reference(model32, tokens):
    spec, params = model32
    got = jax.nn.log_softmax(tr.forward_logits(
        params, spec, jnp.asarray(tokens[None]))[0].astype(jnp.float32))
    forward = reference_of(spec, params)
    for p in (0, 7, N_PROMPT + N_NEW - 1):  # 52 is off the chunk of 8
        assert np.abs(np.asarray(got[p]) - forward(list(tokens), p)).max() \
            < TIGHT


@pytest.mark.parametrize("change", [
    {"mixer": False}, {"reset_at": 2 * SEGMENT},
    {"pads": (N_PROMPT - 1, SEGMENT - (N_PROMPT - 1) % SEGMENT)},
    {"keys_from": 2 * SEGMENT}],
    ids=lambda c: next(iter(c)))
def test_a_control_comes_out_as_not_the_served_model(model32, tokens, change):
    """The controls of PERF.md section 2a: the reference without the
    mixer's branch, with the state zeroed at a segment boundary, with a
    padded segment's pad positions let into the state, with an earlier
    segment's keys and values lost to attention. The served path
    differs from each by far more than from the reference itself (under
    1e-4 above; the nearest control, ``reset_at``, reads a median of
    0.005)."""
    spec, params = model32
    want = reference_rows(reference_of(spec, params, change), tokens)
    best = want.argmax(-1)
    rows = np.arange(len(want))
    err = np.abs(_served32(True)[0][rows, best] - want[rows, best])
    assert np.median(err) > 20 * TIGHT, (change, err)


def test_a_bfloat16_state_is_within_reach_of_the_float32_one(model32, tokens):
    """What PERF.md section 2a reports beside the controls: the reference
    with its state rounded to bfloat16 after every position moves a served
    log-probability by 3e-4 at this size: over the float32 test's limit,
    far under the benchmark's limits for two bytes a weight."""
    spec, params = model32
    want = reference_rows(
        reference_of(spec, params, {"state_dtype": "bfloat16"}), tokens)
    err = np.abs(_served32(True)[0] - want).max()
    assert TIGHT < err < 0.02


# ---- the recurrence's two forms -----------------------------------------------------


@pytest.mark.parametrize("length", [1, 5, 8, 13, 24, 37])
def test_the_chunked_form_is_the_sequential_recurrence(length):
    """Lengths on and off a multiple of the chunk of 8, from a state that is
    not zero: the chunked scan against one step a position."""
    b, g, per, p, n = 2, 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(length), 6)
    x = jax.random.normal(ks[0], (b, length, g, per, p))
    bm = jax.random.normal(ks[1], (b, length, g, n))
    cm = jax.random.normal(ks[2], (b, length, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, length, g, per)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[4], (g, per)))
    state = jax.random.normal(ks[5], (b, g, per, p, n))
    want, at = [], state
    for t in range(length):
        y, at = ssm._scan_step(x[:, t:t + 1], bm[:, t:t + 1], cm[:, t:t + 1],
                               dt[:, t:t + 1], a, at)
        want.append(y)
    got, end = ssm._scan_chunked(x, bm, cm, dt, a, state, chunk=8)
    assert np.abs(np.asarray(got) - np.concatenate(want, 1)).max() < 1e-4
    assert np.abs(np.asarray(end) - np.asarray(at)).max() < 1e-4


# ---- what the slot cache owes a row that holds a state --------------------------------


def test_pad_positions_leave_state_and_tail_where_the_last_token_left_them(
        model32, tokens):
    """A prompt of 40 in a bucket of 64, and of 23 in a padded segment of
    32: state and convolution tail are what a program of exactly that many
    positions leaves."""
    spec, params = model32
    fresh = tr.init_cache(spec, SLOTS)
    _, ck, cv = admitted(spec, params, tokens[:40], 64, *fresh)
    _, ck0, cv0 = admitted(spec, params, tokens[:40], 40, *fresh)
    for got, want in zip(carried((ck, cv))[:2], carried((ck0, cv0))[:2]):
        assert np.abs(got - want).max() < 1e-5
    seg = np.zeros((2, 1, 32), np.int32)
    seg[:, 0, :23] = tokens[:23]
    padded = _segment(params, spec, jnp.asarray(seg[0]), jnp.int32(0),
                      jnp.int32(23), *fresh)
    exact = tr.prefill_segment(
        params, spec, jnp.asarray(seg[1][:, :23]), jnp.int32(0),
        jnp.int32(23), *fresh, jnp.int32(SLOT), history=64)
    for got, want in zip(carried(padded)[:2], carried(exact)[:2]):
        assert np.abs(got - want).max() < 1e-5
    assert np.abs(carried(padded)[0]).max() > 0.01  # and it did move


def test_segments_give_the_state_and_the_logits_of_one_admission(
        model32, tokens):
    """After the register's decode step on the prompt's last token, a row
    admitted in segments holds the state, the tail and the log-probabilities
    of the same prompt admitted at once."""
    spec, params = model32
    logits, ck, cv = admitted(spec, params, tokens[:N_PROMPT], 64,
                              *tr.init_cache(spec, SLOTS))
    sk, sv = segmented(spec, params, tokens[:N_PROMPT],
                       *tr.init_cache(spec, SLOTS))
    seg_logits, sk, sv = step(spec, params, tokens[N_PROMPT - 1],
                              N_PROMPT - 1, sk, sv)
    assert np.abs(np.asarray(seg_logits[SLOT] - logits[0])).max() < TIGHT
    for got, want in zip(carried((sk, sv))[:2], carried((ck, cv))[:2]):
        assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("segment", [False, True],
                         ids=["single_shot", "segmented"])
def test_a_claimed_slot_starts_from_zero_whatever_its_last_tenant_left(
        model32, tokens, segment):
    spec, params = model32
    dirty = jax.tree.map(lambda leaf: leaf + 1, tr.init_cache(spec, SLOTS))
    got, _ = served(spec, params, tokens, segment, cache=dirty)
    assert np.abs(got - _served32(segment)[0]).max() < 1e-5


def test_a_row_the_step_may_not_write_is_not_moved(model32, tokens):
    """A row mid-admission or dead rides every decode chunk dispatched
    meanwhile: with its ``write_mask`` off its state, tail, K and V come
    back bit for bit, while a live row beside it moves."""
    spec, params = model32
    cache = segmented(spec, params, tokens[:N_PROMPT],
                      *tr.init_cache(spec, SLOTS))
    before = carried(cache)
    tok = np.full((SLOTS,), 7, np.int32)
    lens = np.full((SLOTS,), 5, np.int32)
    live = np.ones((SLOTS,), bool)
    live[SLOT] = False
    after = _step(params, spec, jnp.asarray(tok), jnp.asarray(lens),
                  jnp.asarray(live), *cache)[1:]
    for got, want in zip(carried(after), before):
        assert (got == want).all()
    assert np.abs(np.asarray(after[0].carry[:, 0])).max() > 0


def test_overshoot_steps_of_a_finished_row_do_no_harm(model32, tokens):
    """A row that finishes inside a decode chunk stops moving its state with
    its last real token (the chunk's remaining steps run with the row dead),
    and the slot's next tenant generates what it generates on a fresh
    engine."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec, params = model32
    cache = segmented(spec, params, tokens[:N_PROMPT],
                      *tr.init_cache(spec, SLOTS))
    row = lambda value, dt: jnp.zeros((SLOTS,), dt).at[SLOT].set(value)  # noqa: E731

    def sample(logits, live, carry):
        return jnp.argmax(logits, -1).astype(jnp.int32), carry, ()

    def chunk(steps, budget):
        out = tr.decode_chunk(
            params, spec, steps, row(tokens[N_PROMPT - 1], jnp.int32),
            row(N_PROMPT - 1, jnp.int32), row(True, bool),
            row(budget, jnp.int32), jnp.full((SLOTS,), -1, jnp.int32),
            *cache, sample, (), history=64)
        return out[2], carried(out[5:7])

    n_valid, overshot = chunk(8, 3)
    assert int(n_valid[SLOT]) == 3
    for got, want in zip(overshot[:2], chunk(3, 3)[1][:2]):
        assert np.abs(got - want).max() < 1e-6

    first, second = [int(t) for t in tokens[:20]], [int(t) for t in tokens[20:45]]
    shared = InferenceEngine(spec, n_slots=1, seed=3, prefill_chunk=16)
    alone = InferenceEngine(spec, n_slots=1, seed=3, prefill_chunk=16)
    try:
        shared.generate(first, max_new_tokens=3, sampler=GREEDY, seed=0)
        got = shared.generate(second, max_new_tokens=10, sampler=GREEDY, seed=0)
        want = alone.generate(second, max_new_tokens=10, sampler=GREEDY, seed=0)
        assert got.token_ids == want.token_ids
    finally:
        shared.shutdown(), alone.shutdown()


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
def test_what_cannot_carry_a_state_refuses_it_at_start_up(option):
    """Each names its option and the state it has no place for."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.parallel.mesh import MeshConfig, make_mesh

    asked = dict(REFUSED[option])
    axes = {k: asked.pop(k) for k in ("tp", "sp") if k in asked}
    mesh = make_mesh(MeshConfig(**axes), jax.devices()[:2]) if axes else None
    with pytest.raises(ValueError, match="recurrent state") as said:
        InferenceEngine(resolve_spec("falcon-h1-tiny"), mesh, n_slots=2,
                        **asked)
    assert option.split("=")[0].split(">")[0] in str(said.value)


def test_the_engine_serves_it_and_counts_its_state():
    """Single-shot and segmented admissions through the scheduler generate
    the reference's greedy tokens; the counters, the gauge and the span's
    attributes say what the programs did to the rows' states."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec("falcon-h1-tiny", {"dtype": "float32"})
    eng = InferenceEngine(spec, n_slots=3, seed=3, prefill_chunk=16)
    try:
        tokens = [int(t) for t in np.random.default_rng(1).integers(
            3, 256, size=40)]
        forward = reference_of(spec, eng.params)
        # 12: one admit; 30: segments of 16 and 13; 33: two whole segments
        for n in (12, 30, 33):
            got = eng.generate(tokens[:n], max_new_tokens=4, sampler=GREEDY,
                               seed=0).token_ids
            seq = tokens[:n]
            for t in got:
                assert t == int(forward(seq, len(seq) - 1).argmax())
                seq = seq + [t]
        m = eng.metrics()
        layers, rows = spec.n_layers, 3
        # the scan's pad share is the programs': no counter of its own
        assert not [k for k in m if k.startswith("ssm_scan")]
        assert m["ssm_state_rows_stepped_total"] % (layers * rows) == 0
        assert 0 < m["ssm_state_rows_live_total"] \
            < m["ssm_state_rows_stepped_total"]
        state = rows * layers * (spec.ssm_heads * spec.ssm_head_dim
                                 * spec.ssm_state * 4
                                 + 3 * spec.ssm_conv_width * 4)
        assert m["kv_cache_state_bytes"] == state
        assert eng.health()["kv_cache_bytes"]["state"] == state
        assert m["kv_cache_full_bytes"] == (
            2 * layers * rows * spec.max_seq * spec.n_kv_heads
            * spec.head_dim * 4)
    finally:
        eng.shutdown()


def test_the_prefill_span_says_whether_the_state_was_carried():
    """What ``_admit`` (one program) and ``_finish_admission`` (segments and
    the register's decode step) put on a request's ``prefill`` span."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec("falcon-h1-tiny")
    eng = InferenceEngine(spec, n_slots=2, seed=3, prefill_chunk=16)
    try:
        row = spec.n_layers * (spec.ssm_heads * spec.ssm_head_dim
                               * spec.ssm_state * 4
                               + 3 * spec.ssm_conv_width * 2)
        assert eng._state_carried(False) == {"state_carried": False,
                                             "state_bytes": row}
        assert eng._state_carried(True) == {"state_carried": True,
                                            "state_bytes": row}
    finally:
        eng.shutdown()


def test_an_engine_without_a_mixer_says_nothing_of_states():
    from quorum_tpu.engine.engine import InferenceEngine

    eng = InferenceEngine(resolve_spec("llama-tiny"), n_slots=2, seed=0)
    try:
        assert not [k for k in eng.metrics()
                    if "ssm" in k or k == "kv_cache_state_bytes"]
        assert "state" not in eng.health()["kv_cache_bytes"]
    finally:
        eng.shutdown()


# ---- the spec, the init, the scopes, the configuration --------------------------------


def test_a_spec_without_a_mixer_has_no_state_leaf(model32):
    """The leaf is absent there, not empty: the cache of every other dense
    spec is the two arrays it was, and its programs the ones they were."""
    ck, cv = tr.init_cache(resolve_spec("llama-tiny"), 2)
    assert isinstance(ck, jax.Array) and isinstance(cv, jax.Array)
    ck, cv = tr.init_cache(model32[0], 2)
    assert isinstance(ck, ssm.StateKV) and ck.carry.dtype == jnp.float32
    spec = model32[0]
    assert ck.carry.shape == (3, 2, spec.ssm_heads, spec.ssm_head_dim,
                              spec.ssm_state)
    assert cv.carry.shape == (3, 2, 3, spec.ssm_conv_width)


def test_the_published_preset_is_the_published_config():
    spec = MODEL_PRESETS["falcon-h1-34b"]
    with open(os.path.join(BENCH, "configs", "published",
                           "falcon-h1-34b-instruct.json")) as f:
        pub = json.load(f)["config"]
    assert (spec.d_model, spec.n_layers, spec.n_heads, spec.n_kv_heads,
            spec.head_dim, spec.d_ff, spec.vocab_size) == (
        pub["hidden_size"], pub["num_hidden_layers"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["head_dim"], pub["intermediate_size"], pub["vocab_size"])
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_groups, spec.ssm_conv, spec.ssm_chunk) == (
        pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"],
        pub["mamba_n_groups"], pub["mamba_d_conv"], pub["mamba_chunk_size"])
    assert spec.ssm_width == pub["mamba_d_ssm"]
    assert (spec.emb_scale, spec.attn_in_mult, spec.attn_out_mult,
            spec.key_mult, spec.ssm_in_mult, spec.ssm_out_mult,
            spec.lm_head_mult, spec.rope_theta, spec.norm_eps) == (
        pub["embedding_multiplier"], pub["attention_in_multiplier"],
        pub["attention_out_multiplier"], pub["key_multiplier"],
        pub["ssm_in_multiplier"], pub["ssm_out_multiplier"],
        pub["lm_head_multiplier"], pub["rope_theta"], pub["rms_norm_eps"])
    assert list(spec.ssm_mults) == pub["ssm_multipliers"]
    assert [spec.mlp_gate_mult, spec.mlp_down_mult] == pub["mlp_multipliers"]
    assert not spec.tied_lm_head and not pub["tie_word_embeddings"]


def test_the_configuration_file_is_held_to_its_source():
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "falcon-h1-34b-l6")
    with open(os.path.join(BENCH, "..", entry["file"])) as f:
        data = json.load(f)
    assert published_widths.problems(entry, data) == []
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]


def test_the_seeded_init_leaves_something_to_compare(model32, tokens):
    """Every multiplier is off 1 in the tiny preset and the products come out
    at the dense family's size all the same: a position's log-probabilities
    spread by nats, not by the logits' multiplier; the recurrence's numbers
    are Mamba-2's own."""
    spec, params = model32
    lp = reference_of(spec, params)(list(tokens), N_PROMPT)
    assert 2.0 < lp.max() - np.median(lp) < 12.0
    blocks = params["blocks"]
    a = np.exp(np.asarray(blocks["ssm_a_log"]))
    step_size = np.asarray(jax.nn.softplus(blocks["ssm_dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 0.001 <= step_size.min() * 1.001 and step_size.max() <= 0.1001
    assert (np.asarray(blocks["ssm_d"]) == 1).all()


def test_a_mixer_program_carries_its_scopes(model32):
    spec, params = model32
    ck, cv = tr.init_cache(spec, SLOTS)
    seg = _segment.lower(params, spec, jnp.zeros((1, 16), jnp.int32),
                         jnp.int32(0), jnp.int32(16), ck, cv).as_text(
                             debug_info=True)
    dec = _step.lower(params, spec, jnp.zeros((SLOTS,), jnp.int32),
                      jnp.zeros((SLOTS,), jnp.int32),
                      jnp.ones((SLOTS,), bool), ck, cv).as_text(
                          debug_info=True)
    shared = {"ssm.in_proj", "ssm.conv", "ssm.gate_norm", "ssm.out_proj"}
    assert shared | {"ssm.scan", "ssm.step"} == set(hlo_names.MIXER)
    for text, form, other in ((seg, "ssm.scan", "ssm.step"),
                              (dec, "ssm.step", "ssm.scan")):
        for scope in shared | {form}:
            assert f"{scope}/" in text, scope
            assert hlo_names.part_of(f"jit(f)/while/body/{scope}/mul") == scope
        assert f"{other}/" not in text


def test_a_traced_program_logs_its_mixer_path(model32, caplog):
    spec, params = model32
    caplog.set_level("INFO", logger="quorum_tpu.models.ssm")
    ck, cv = tr.init_cache(spec, SLOTS)
    jax.eval_shape(lambda: tr.prefill_segment(
        params, spec, jnp.zeros((1, 16), jnp.int32), jnp.int32(0),
        jnp.int32(16), ck, cv, jnp.int32(0), history=64))
    jax.eval_shape(lambda: tr.decode_step(
        params, spec, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), ck, cv, history=64))
    jax.eval_shape(lambda: tr.decode_step(
        params, spec, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), ck, cv, history=64, sharded=True))
    chunked, step, sharded = [r.getMessage() for r in caplog.records
                              if r.getMessage().startswith("mixer-path")]
    assert "form=chunked " in chunked and "positions=16 " in chunked
    assert "reason=more than one position" in chunked
    # the tiny preset's heads of [16, 16] are no tiles of Mosaic's
    assert "form=step " in step and "positions=1 " in step
    assert "reason=heads of [16, 16]" in step
    assert "form=step " in sharded and "partitioned over devices" in sharded
    # a float32 state of whole tiles takes the kernel where it is lowered
    # for a TPU (tests/test_ssm_step.py has the CPU's side of it)
    tiled = dataclasses.replace(spec, ssm_state=128).validate()
    caplog.clear()
    jax.eval_shape(lambda: tr.decode_step(
        init_params(tiled, 3), tiled, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), *tr.init_cache(tiled, SLOTS),
        history=64))
    (fused,) = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("mixer-path")]
    assert "form=fused " in fused and "positions=1 " in fused
    assert "reason=pallas where lowered for a tpu" in fused
