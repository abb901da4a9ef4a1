"""Zero-drain continuous batching (``zero_drain=0|1``, ISSUE 11).

Fast tier: knob parsing/validation, the drain-based cache-key pin
(zero_drain off compiles the EXACT pre-existing program variants — no
staging state, single-shot admission for short prompts), a colocated
smoke pinned token-for-token against the drain-based engine with live
reap-boundary injection (admission registers onto a live ring,
``admission_overlap_total`` > 0, ``admission_stall_seconds_total``
structurally 0), the injection-path fault containment contract (a failed
``engine.admit``/``engine.prefill_segment`` dooms ONLY the injecting
request — staging is the blast-radius boundary, exactly like a disagg
prefill fault), and the drain-based engine's stall accounting (the
retired C=1/K=1 coupling is measurable where it still applies).

Slow tier: the full acceptance pins at ``decode_pipeline=4 ×
decode_loop=4`` across the greedy / sampled / EOS-mid-chunk /
constrained / members / prefix-restore legs, each against the
drain-based engine.
"""

import asyncio

import pytest

from quorum_tpu import faults
from quorum_tpu.analysis import budget
from quorum_tpu.engine.engine import InferenceEngine, _SegmentRoom
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

TINY = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
SAMPLED = SamplerConfig(temperature=0.8, top_p=0.9)
GREEDY = SamplerConfig(temperature=0.0)


def _gen(eng, prompt, seed=0, n=8, sampler=SAMPLED, **kw):
    return eng.generate(prompt, max_new_tokens=n, sampler=sampler,
                        seed=seed, **kw).token_ids


# ---- fast: config validation ------------------------------------------------


def test_zero_drain_engine_validation():
    # zero_drain rides chunked prefill; an engine without it must reject
    with pytest.raises(ValueError, match="chunked prefill"):
        InferenceEngine(TINY, prefill_chunk=0, zero_drain=True)


def test_zero_drain_url_knob_validation():
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    def build(url):
        return TpuBackend.from_spec(
            BackendSpec(name="t", url=url, model="m"))

    for url, frag in [
        ("tpu://llama-tiny?zero_drain=1&disagg=1+1", "zero_drain=1 does"),
        ("tpu://llama-tiny?zero_drain=1&prefill_chunk=0",
         "chunked prefill"),
        ("tpu://llama-tiny?zero_drain=maybe", "zero_drain"),
    ]:
        with pytest.raises(ValueError, match=frag):
            build(url)


# ---- fast: drain-based cache-key pin + smoke --------------------------------


@pytest.fixture(scope="module")
def smoke_engines():
    """One drain-based + one zero_drain engine over identical knobs,
    shared by the fast smoke tests (compiles once per module)."""
    kw = dict(decode_chunk=4, n_slots=2, decode_pipeline=2,
              prefill_chunk=16, seed=11300)
    eng_c = InferenceEngine(TINY, **kw)
    eng_z = InferenceEngine(TINY, zero_drain=True, **kw)
    yield eng_c, eng_z
    eng_c.shutdown()
    eng_z.shutdown()


def test_drain_based_compiles_exact_preexisting_variants(smoke_engines):
    """zero_drain off = byte-for-byte the old engine: no staging cache,
    no injection program variants, single-shot admission for short
    prompts, and the unconstrained decode programs under their exact
    pre-existing 3-tuple keys."""
    eng_c, _ = smoke_engines
    _gen(eng_c, [3, 4, 5], seed=1)
    assert not eng_c.zero_drain and not eng_c.staged
    assert eng_c.prefill_params is None
    assert not hasattr(eng_c, "_sck")
    # program families against the shared budget (classifying also pins
    # each key's exact shape — analysis/compile_budget.json)
    assert budget.admit_families(eng_c._admit_cache) == {"single_shot"}
    assert budget.decode_families(eng_c._decode_cache) == {"plain"}
    # one end-to-end literal sentinel: the plain decode key is still the
    # pre-existing (n_steps, want_lp, history) 3-tuple
    assert any(isinstance(k, tuple) and len(k) == 3
               and isinstance(k[0], int) for k in eng_c._decode_cache)
    assert eng_c.n_admission_overlap == 0
    assert eng_c.metrics()["zero_drain"] == 0


def test_zero_drain_smoke_pinned_with_live_injection(smoke_engines):
    """Greedy and sampled streams (short AND multi-segment prompts) equal
    the drain-based engine token for token, with every admission riding
    the staged seg→inject→register path on ONE device group (zero
    handoff bytes — nothing crosses a group boundary)."""
    eng_c, eng_z = smoke_engines
    long_p = [(3 + 5 * i) % 500 for i in range(40)]
    legs = [([3, 4, 5], GREEDY, 0), ([7, 8, 9], SAMPLED, 11),
            (long_p, SAMPLED, 3)]
    for prompt, sampler, seed in legs:
        assert (_gen(eng_z, prompt, seed=seed, sampler=sampler)
                == _gen(eng_c, prompt, seed=seed, sampler=sampler))
    # one group: injection moves no bytes across any boundary
    assert eng_z.n_kv_handoffs == 0 and eng_z.kv_handoff_bytes == 0
    # never a single-shot admit program; every admission rides
    # seg+inject+register (compile_budget.json gates)
    fams = budget.admit_families(eng_z._admit_cache)
    assert "single_shot" not in fams
    assert {"seg", "register", "hslice", "hput"} <= fams, fams
    m = eng_z.metrics()
    assert m["zero_drain"] == 1 and m["disagg"] == 0
    # the structural contract: the ring NEVER clamped for an admission
    assert m["admission_stall_seconds_total"] == 0.0
    with eng_z._cond:
        assert eng_z._admission_pressure() is False
    h = eng_z.health()
    assert h["scheduler_alive"] and h["prefill_scheduler_alive"]


def test_zero_drain_injection_overlaps_live_ring(smoke_engines):
    """Two concurrent streams: the second's staged admission registers
    while the first decodes at full ring depth — admission_overlap_total
    advances and the stall counter stays structurally 0."""
    _, eng_z = smoke_engines
    over0 = eng_z.n_admission_overlap
    a = eng_z.submit([9, 8, 7], max_new_tokens=40, sampler=GREEDY)
    b = eng_z.submit([5, 6, 7], max_new_tokens=40, sampler=GREEDY)
    ta = list(eng_z.stream_results(a))
    tb = list(eng_z.stream_results(b))
    assert len(ta) == 40 and len(tb) == 40
    assert eng_z.n_admission_overlap > over0
    assert eng_z.admission_stall_s == 0.0


def test_zero_drain_injection_fault_dooms_only_its_request(smoke_engines):
    """The injection path's containment: a prefill-segment failure while
    other rows decode dooms only the injecting request — the queued
    bystander completes unchanged, nothing is requeued, no device-state
    rebuild (staging is the blast-radius boundary)."""
    eng_c, eng_z = smoke_engines
    base = _gen(eng_z, [3, 4, 5], seed=1)
    assert base == _gen(eng_c, [3, 4, 5], seed=1)
    rebuilds0 = eng_z.n_rebuilds
    faults.arm("engine.prefill_segment", times=1)
    try:
        bad = eng_z.submit([5, 6, 7], max_new_tokens=8, sampler=SAMPLED,
                           seed=2)
        bystander = eng_z.submit([3, 4, 5], max_new_tokens=8,
                                 sampler=SAMPLED, seed=1)
        with pytest.raises(faults.FaultInjected):
            list(eng_z.stream_results(bad))
        assert list(eng_z.stream_results(bystander)) == base
    finally:
        faults.disarm()
    assert _gen(eng_z, [3, 4, 5], seed=1) == base
    assert eng_z.n_rebuilds == rebuilds0  # staging survived: no rebuild
    assert eng_z.health()["scheduler_alive"]


def test_drain_based_engine_accumulates_admission_stall():
    """The coupling zero_drain retires is measurable where it still
    applies: a chunked admission under a live stream clamps the K=4·C=4
    ring to depth 1 across consecutive turns, and the stall counter
    records the window. (The zero_drain twin of this scenario is pinned
    to 0.0 in the smoke above.) A segment a turn, as before the segment
    rule's paces are timed (ISSUE 27): an admission whose segments fit one
    turn clamps the ring for that turn only, and the counter, which reads
    between consecutive clamped turns, then books nothing."""
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2,
                          decode_pipeline=4, decode_loop=4,
                          prefill_chunk=16, seed=11310)
    eng._segment_room = lambda: _SegmentRoom(0.0, 0.0)
    try:
        churn_p = [(7 + 3 * i) % 500 for i in range(48)]
        eng.generate([9, 8, 7], max_new_tokens=8, sampler=GREEDY)  # warm
        eng.generate(churn_p, max_new_tokens=2, sampler=GREEDY)
        # distinct churn prompt per admission — a repeat would tier-0
        # reuse its resident prefix and shrink the clamp window
        churn2 = [(11 + 5 * i) % 500 for i in range(48)]
        # the stream is live before the admission is submitted: submitted
        # the other way round, a loaded machine could finish the admission
        # before the stream's first chunk and book no stall
        stream = eng.submit([9, 8, 7], max_new_tokens=256, sampler=GREEDY)
        tokens = eng.stream_results(stream)
        next(tokens)
        pre = eng.submit(churn2, max_new_tokens=2, sampler=GREEDY)
        list(tokens)
        list(eng.stream_results(pre))
        assert eng.admission_stall_s > 0.0
        assert eng.metrics()["admission_stall_seconds_total"] > 0.0
        assert eng.n_admission_overlap == 0  # drain-based: structurally 0
    finally:
        eng.shutdown()


# ---- slow: acceptance legs at K=4·C=4 ---------------------------------------


@pytest.fixture(scope="module")
def accept_engines():
    """Drain-based vs zero_drain at decode_pipeline=4 × decode_loop=4
    (the deep-fused acceptance shape)."""
    kw = dict(decode_chunk=4, n_slots=2, decode_pipeline=4, decode_loop=4,
              prefill_chunk=16, seed=11320)
    eng_c = InferenceEngine(TINY, **kw)
    eng_z = InferenceEngine(TINY, zero_drain=True, **kw)
    yield eng_c, eng_z
    eng_c.shutdown()
    eng_z.shutdown()


@pytest.mark.slow
def test_zero_drain_greedy_sampled_chunked_pin(accept_engines):
    eng_c, eng_z = accept_engines
    long_p = [(3 + 5 * i) % 500 for i in range(40)]
    for prompt, sampler, seed in [([3, 4, 5], GREEDY, 0),
                                  ([7, 8, 9], SAMPLED, 11),
                                  (long_p, SAMPLED, 3)]:
        assert (_gen(eng_z, prompt, seed=seed, n=12, sampler=sampler)
                == _gen(eng_c, prompt, seed=seed, n=12, sampler=sampler))
    assert eng_z.admission_stall_s == 0.0


@pytest.mark.slow
def test_zero_drain_eos_mid_chunk_pin(accept_engines):
    """A row finishing ON DEVICE mid-megachunk (EOS at a non-boundary
    position) retires identically on both engines — finish_reason stop,
    zero overrun at any K·C."""
    eng_c, eng_z = accept_engines
    probe = _gen(eng_c, [5, 6, 7], seed=2, n=12)
    eos = next((t for i, t in enumerate(probe)
                if i >= 4 and i % 4 != 3 and t not in probe[:i]), None)
    assert eos is not None, probe
    over0 = eng_z.n_overrun
    r_z = eng_z.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    r_c = eng_c.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    assert r_z.token_ids == r_c.token_ids
    assert r_z.finish_reason == r_c.finish_reason == "stop"
    assert eng_z.n_overrun == over0


@pytest.mark.slow
def test_zero_drain_constrained_pin():
    """response_format JSON mode through the full backend: the zero-drain
    engine's constrained stream (grammar placed at register time in the
    injection drain, DFA state installed by the register program) equals
    the drain-based engine's byte for byte."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    def build(url):
        return TpuBackend.from_spec(BackendSpec(name="t", url=url,
                                                model="m"))

    opts = ("n_kv_heads=4&seed=11330&decode_pipeline=4&decode_loop=4"
            "&prefill_chunk=16&decode_chunk=4&slots=2")
    b_z = build(f"tpu://llama-tiny?{opts}&zero_drain=1")
    b_c = build(f"tpu://llama-tiny?{opts}")
    body = {"model": "m", "max_tokens": 24, "temperature": 0.0, "seed": 3,
            "messages": [{"role": "user", "content": "json please"}],
            "response_format": {"type": "json_object"}}

    async def run(b):
        res = await b.complete(dict(body), {}, timeout=300)
        return res.body["choices"][0]["message"]["content"]

    assert asyncio.run(run(b_z)) == asyncio.run(run(b_c))
    assert b_z.engine.n_constrained >= 1
    assert b_z.engine is not b_c.engine  # structural key split


@pytest.mark.slow
def test_zero_drain_members_pin():
    """members=M under zero_drain: each member's stream equals the
    members=1 engine with that member's seed — the member-stacked staging
    cache and the member-aware injection slice/write address the right
    flat rows."""
    eng_m = InferenceEngine(TINY, members=2, zero_drain=True,
                            decode_chunk=4, n_slots=2, decode_pipeline=4,
                            decode_loop=4, prefill_chunk=16, seed=0)
    singles = [InferenceEngine(TINY, seed=i, decode_chunk=4, n_slots=2)
               for i in range(2)]
    try:
        want = [_gen(singles[i], [3, 4, 5], seed=9, n=6) for i in range(2)]
        got = [_gen(eng_m, [3, 4, 5], seed=9, n=6, member=i)
               for i in range(2)]
        assert got == want
    finally:
        eng_m.shutdown()
        for e in singles:
            e.shutdown()


@pytest.mark.slow
def test_zero_drain_prefix_restore_pin():
    """prefix_store=host under zero_drain: a churn-evicted conversation's
    follow-up restores host→STAGING, rides the tail prefill at an offset,
    and injects the whole prefix into the decode slot — still equal to a
    cold drain-based prefill token for token."""
    eng_z = InferenceEngine(TINY, zero_drain=True, decode_chunk=4,
                            n_slots=1, prefill_chunk=16,
                            prefix_store="host", prefix_store_chunk=16,
                            seed=11350)
    eng_c = InferenceEngine(TINY, decode_chunk=4, n_slots=1,
                            prefill_chunk=16, seed=11350)
    try:
        conv = [(3 + 5 * i) % 500 for i in range(33)]
        other = [(9 + 7 * i) % 500 for i in range(33)]
        out1 = _gen(eng_z, conv, seed=4, n=6)
        eng_z.drain_prefix_store()
        _gen(eng_z, other, seed=5, n=6)  # churn the single slot
        eng_z.drain_prefix_store()
        follow = conv + out1 + [17, 19]
        assert (_gen(eng_z, follow, seed=6, n=6)
                == _gen(eng_c, follow, seed=6, n=6))
        assert eng_z.prefix_store_hits >= 1
        assert eng_z.prefix_store_tokens_restored > 0
    finally:
        eng_z.shutdown()
        eng_c.shutdown()
