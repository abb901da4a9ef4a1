"""Chunked prefill + bounded admission queue (VERDICT r2 weakness 6).

Long-prompt admissions must not stall in-flight decodes: the scheduler
advances each admission by a few prompt segments per iteration, as many as
one decode chunk's device time holds and at least one (ISSUE 27), running a
decode chunk for active slots in between. And the pending queue is bounded
— overload surfaces as a 503, not unbounded memory growth.
"""

import importlib.util
import os
import threading
import time

import pytest

from quorum_tpu.engine.engine import (InferenceEngine, QueueFullError,
                                      _SegmentRoom)
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.observability import RequestTrace, use_trace
from quorum_tpu.ops.sampling import SamplerConfig

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6). The segment
# rule's own cases, further down, are small and run in the fast tier.
slow = pytest.mark.slow

TINY = resolve_spec("llama-tiny")  # max_seq 128


@slow
def test_chunked_matches_single_shot_prefill():
    """A long prompt admitted in 16-token segments must generate exactly the
    same tokens as single-shot prefill: the segment path writes the same
    K/V, and the first token is sampled from the same logits and the same
    PRNG stream (see InferenceEngine._register_fn)."""
    prompt = [(7 + 13 * i) % 500 for i in range(100)]
    eng_one = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=0)
    eng_seg = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=16)
    assert eng_one.prefill_chunk == 0  # chunking disabled → single-shot
    assert eng_seg.prefill_chunk == 16

    for sampler in (SamplerConfig(temperature=0.0),
                    SamplerConfig(temperature=0.8, top_p=0.9)):
        one = eng_one.generate(prompt, max_new_tokens=12, sampler=sampler,
                               seed=3).token_ids
        seg = eng_seg.generate(prompt, max_new_tokens=12, sampler=sampler,
                               seed=3).token_ids
        assert seg == one


def _freeze_paces(eng, step_s, tok_s):
    """Pin the two paces the segment rule reads (``_segment_room``) as if
    the device ledger's landings had timed them so: a decode step's seconds
    in the last chunk that ran alone, a segment token's in the last
    intervals that held segments."""
    led = eng._ledger
    led.step_alone_s, led.seg_tok_s = step_s, tok_s
    led._time_paces = lambda *interval: None


def _admit_under_decode(eng, prompts, resident_tokens=60):
    """Chunked admissions of ``prompts`` (one per member, all entering the
    same scheduler turn) while a resident stream decodes. Returns each
    admission's ``prefill`` span, how many of the resident's decode chunks
    ended inside each, and by how much the engine's counters rose."""
    m0 = eng.metrics()
    resident_trace = RequestTrace("resident")

    def resident():
        with use_trace(resident_trace):
            req = eng.submit([5, 6, 7], max_new_tokens=resident_tokens)
        list(eng.stream_results(req))

    thread = threading.Thread(target=resident)
    thread.start()
    deadline = time.monotonic() + 60
    while not any(eng._slots) and time.monotonic() < deadline:
        time.sleep(0.001)
    traces, reqs = [], []
    with eng._cond:  # the scheduler meets all of them in one turn
        for member, prompt in enumerate(prompts):
            traces.append(RequestTrace(f"long-{member}"))
            with use_trace(traces[-1]):
                reqs.append(eng.submit(
                    prompt, max_new_tokens=4,
                    **({"member": member} if eng.members > 1 else {})))
    done = [len(list(eng.stream_results(r))) for r in reqs]
    alive = any(eng._slots)  # the resident outlived the admissions
    thread.join(timeout=120)
    assert not thread.is_alive() and alive and done == [4] * len(prompts)
    m1 = eng.metrics()
    spans = [next(sp for sp in t.spans if sp.name == "prefill")
             for t in traces]
    ends = [sp.end + resident_trace._t0 for sp in resident_trace.spans
            if sp.name == "decode"]
    inside = [sum(sp.start + t._t0 < end <= sp.end + t._t0 for end in ends)
              for sp, t in zip(spans, traces)]
    rose = {k: m1[k] - m0[k] for k in (
        "prefill_segments_total", "prefill_segment_turns_total")}
    return spans, inside, rose


def _prompt(n, salt):
    return [(salt + 11 * i) % 500 for i in range(n)]


def test_long_admission_does_not_stall_active_stream():
    """While a 100-token prompt is being admitted in 16-token segments, an
    already-active stream must keep emitting tokens (the round-2 engine ran
    every admission to completion before the next decode chunk). The bound
    the segment rule gives: a turn dispatches no more segments than the
    active stream's own decode chunk takes on the device (here three, the
    admission's seven going out as 3 + 3 + 1), so the stream's longest gap
    is at most what one segment a turn gave it plus one chunk's time, and a
    decode chunk of its own lands in every turn of the admission."""
    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=2, prefill_chunk=16)
    try:
        # a chunk of 2 steps is 2.0 s; a 16-token segment 0.64 s: 3 fit
        _freeze_paces(eng, 1.0, 0.04)
        (span,), (inside,), _ = _admit_under_decode(eng, [_prompt(100, 3)])
    finally:
        eng.shutdown()
    assert span.meta["segments"] == 7
    assert span.meta["turns"] == 3
    # The active stream's decode chunks that ended inside the admission:
    # one per turn before the register's own.
    assert inside >= span.meta["turns"] - 1, (
        "active stream starved during long admission")


@pytest.mark.parametrize("members", [1, 3])
@pytest.mark.parametrize("paces, turns", [
    ((1.0, 1e-6), 1),    # the chunk's time holds every segment
    ((1e-6, 1.0), 4),    # one segment costs more than the chunk
    ((0.0, 0.0), 4),     # nothing timed yet
], ids=["roomy", "tight", "untimed"])
def test_segments_per_turn_follow_the_timed_paces(members, paces, turns):
    """A four-segment admission beside a resident stream: one turn where
    the paces allow it, a segment a turn (the floor) where a segment costs
    more than the chunk or before either pace was timed. A stacked engine's
    lockstep fan-out takes the rule from the same helper, one vmapped
    program a segment. The two counters rise by what the spans say."""
    kw = {"members": members, "seed": 0} if members > 1 else {}
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=16,
                          **kw)
    try:
        _freeze_paces(eng, *paces)
        spans, _, rose = _admit_under_decode(
            eng, [_prompt(64, 3)] * members)
    finally:
        eng.shutdown()
    assert [s.meta["segments"] for s in spans] == [4] * members
    assert [s.meta["turns"] for s in spans] == [turns] * members
    if turns == 1:  # the span ends in the turn that opened it
        assert [s.meta["decode_wait_ms"] for s in spans] == [0] * members
    else:  # the chunks of the turns it waited out
        assert all(s.meta["decode_wait_ms"] > 0 for s in spans)
    for s in spans:  # what the span waited for adds up to its length
        parts = sum(s.meta[k] for k in (
            "own_ms", "peer_ms", "decode_wait_ms", "decode_ahead_ms",
            "starved_ms"))
        assert parts == pytest.approx((s.end - s.start) * 1e3, abs=0.01)
    assert rose == {"prefill_segments_total": 4,
                    "prefill_segment_turns_total": turns}


def test_two_admissions_share_a_turns_room_oldest_first():
    """Room for five segments and two four-segment admissions open in one
    turn: each gets its floor segment, the older takes the three it still
    needs and registers in that turn, the younger has its other three in
    the next."""
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=3, prefill_chunk=16)
    try:
        _freeze_paces(eng, 0.81, 0.04)  # 3.24 s of chunk, 0.64 s a segment
        (a, b), _, rose = _admit_under_decode(
            eng, [_prompt(64, 5), _prompt(64, 7)])
    finally:
        eng.shutdown()
    assert (a.meta["segments"], a.meta["turns"]) == (4, 1)
    assert (b.meta["segments"], b.meta["turns"]) == (4, 2)
    assert rose == {"prefill_segments_total": 8,
                    "prefill_segment_turns_total": 2}


def test_no_live_row_keeps_one_segment_a_turn():
    """With nothing decoding there is no chunk to protect and turns do not
    block: whatever the paces say, a segment a turn as before."""
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=16)
    try:
        _freeze_paces(eng, 1.0, 1e-6)
        trace = RequestTrace("alone")
        with use_trace(trace):
            req = eng.submit(_prompt(64, 3), max_new_tokens=2)
        list(eng.stream_results(req))
    finally:
        eng.shutdown()
    (span,) = [s for s in trace.spans if s.name == "prefill"]
    assert (span.meta["segments"], span.meta["turns"]) == (4, 4)


def test_the_engine_times_both_paces_on_itself():
    """No pace is given here: after one admission beside a resident stream
    the reaps have timed a decode step alone and a segment token (on a CPU
    the latter may drown in the former's noise and read 0, which is
    "untimed"), and the next admission's turns follow from the two."""
    eng = InferenceEngine(TINY, decode_chunk=8, n_slots=2, prefill_chunk=16)
    try:
        eng.generate(_prompt(100, 1), max_new_tokens=2)  # compile
        _admit_under_decode(eng, [_prompt(100, 2)], resident_tokens=100)
        step_s, tok_s = eng._ledger.step_alone_s, eng._ledger.seg_tok_s
        assert step_s > 0 and tok_s >= 0
        room = eng._segment_room()
        assert (room.left_s, room.tok_s) == (0.0, 0.0)  # idle: no live row
        _freeze_paces(eng, step_s, tok_s)
        (span,), _, rose = _admit_under_decode(
            eng, [_prompt(100, 3)], resident_tokens=100)
    finally:
        eng.shutdown()
    room, per_turn = _SegmentRoom(step_s * 8, tok_s), 0
    while room.take(16, floor=not per_turn):
        per_turn += 1
    assert span.meta["segments"] == 7
    assert span.meta["turns"] == -(-7 // per_turn)
    assert rose["prefill_segment_turns_total"] == span.meta["turns"]


def test_prefill_segments_per_turn_reads_the_two_counters():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "layer_metrics",
        "prefill_segments_per_turn.py")
    spec = importlib.util.spec_from_file_location("segs_per_turn", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    m0 = {reader.SEGMENTS: 10.0, reader.TURNS: 10.0}
    m1 = {reader.SEGMENTS: 41.0, reader.TURNS: 20.0}
    assert reader.read({"m0": m0, "m1": m1}) == pytest.approx(3.1)
    assert reader.read({"m0": m0, "m1": m0}) is None  # no segment turn
    assert reader.read({"m0": {}, "m1": m1}) is None  # the parent's scrape


@slow
def test_chunked_admission_correct_under_concurrent_decode():
    """The critical interleaving property: while a chunked admission is in
    progress, interleaved decode chunks for OTHER slots must not corrupt the
    admitted prompt's K/V (decode's dummy writes for inactive rows used to
    land at position 0 — exactly where segment 0 had just written). The long
    request's tokens under load must equal its tokens when run alone."""
    long_prompt = [(3 + 11 * i) % 500 for i in range(100)]
    solo = InferenceEngine(TINY, decode_chunk=2, n_slots=2, prefill_chunk=16)
    expect = solo.generate(long_prompt, max_new_tokens=6,
                           sampler=SamplerConfig(temperature=0.0)).token_ids

    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=2, prefill_chunk=16)
    eng.generate([1] * 100, max_new_tokens=4)  # warm compile caches
    eng.generate([1, 2, 3], max_new_tokens=4)

    got = {}
    started = threading.Event()

    def active_stream():
        for i, _ in enumerate(eng.generate_stream([5, 6, 7], max_new_tokens=60)):
            started.set()
            time.sleep(0.001)

    def long_request():
        started.wait(timeout=30)
        got["toks"] = eng.generate(long_prompt, max_new_tokens=6,
                                   sampler=SamplerConfig(temperature=0.0)).token_ids

    t1 = threading.Thread(target=active_stream)
    t2 = threading.Thread(target=long_request)
    t1.start(); t2.start()
    t1.join(timeout=120); t2.join(timeout=120)
    assert not t1.is_alive() and not t2.is_alive()
    assert got["toks"] == expect


@slow
def test_history_bucketed_decode_matches_full_cache_read():
    """Decode attention reads only the live cache prefix (a power-of-two
    'history' bucket ≪ max_seq for short conversations — the decode-side
    HBM-bandwidth fix). The generated tokens must be identical to an engine
    whose bucket equals max_seq."""
    import dataclasses

    big = dataclasses.replace(TINY, max_seq=128)
    eng = InferenceEngine(big, decode_chunk=4, n_slots=2)
    prompt = [5, 6, 7]  # bucket stays at 16 while max_seq is 128
    toks = eng.generate(prompt, max_new_tokens=8,
                        sampler=SamplerConfig(temperature=0.0)).token_ids
    assert ((4, False, 16) in eng._decode_cache
            or (4, False, 32) in eng._decode_cache), (
        f"expected a small history bucket, got {list(eng._decode_cache)}")

    # Force the full-width bucket by generating near max_seq, same engine:
    # correctness across bucket sizes is covered by continuing generation.
    long_prompt = [(3 + i) % 500 for i in range(100)]
    toks_long = eng.generate(long_prompt, max_new_tokens=8,
                             sampler=SamplerConfig(temperature=0.0)).token_ids
    assert (4, False, 128) in eng._decode_cache
    assert len(toks_long) == 8

    # Cross-check: an engine built with max_seq equal to the bucket (16) has
    # NO padding to skip — its output for the short prompt must match.
    small = dataclasses.replace(TINY, max_seq=16)
    eng_small = InferenceEngine(small, decode_chunk=4, n_slots=2)
    toks_small = eng_small.generate(prompt, max_new_tokens=8,
                                    sampler=SamplerConfig(temperature=0.0)).token_ids
    assert toks == toks_small


@slow
def test_admission_queue_bound_raises_queue_full():
    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=1, max_pending=2)
    blocker = threading.Event()
    threads = []

    def occupy():
        for _ in eng.generate_stream([1, 2], max_new_tokens=64):
            if blocker.wait(timeout=30):
                return

    t = threading.Thread(target=occupy)
    t.start()
    threads.append(t)
    time.sleep(0.5)  # let it claim the only slot
    # Fill the pending queue to its bound...
    queued = [eng._submit([3], max_new_tokens=1, sampler=SamplerConfig(),
                          seed=0, eos_id=None, cancel=None, decode_chunk=None)
              for _ in range(2)]
    # ...and the next submission must be rejected, not enqueued.
    with pytest.raises(QueueFullError):
        eng.generate([4], max_new_tokens=1)
    blocker.set()
    for q in queued:
        q.cancel.set()
    t.join(timeout=30)
    assert not t.is_alive()


@slow
def test_queue_full_maps_to_503():
    import asyncio

    from quorum_tpu.backends.base import BackendError
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    backend = TpuBackend.from_spec(BackendSpec(
        name="busy", url="tpu://llama-tiny?slots=1&queue=1&seed=9", model="t"))
    eng = backend.engine
    blocker = threading.Event()

    def occupy():
        for _ in eng.generate_stream([1, 2], max_new_tokens=64):
            if blocker.wait(timeout=30):
                return

    t = threading.Thread(target=occupy)
    t.start()
    time.sleep(0.5)
    held = eng._submit([3], max_new_tokens=1, sampler=SamplerConfig(),
                       seed=0, eos_id=None, cancel=None, decode_chunk=None)

    async def call():
        body = {"model": "t", "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 4}
        with pytest.raises(BackendError) as exc:
            await backend.complete(body, {}, timeout=30)
        return exc.value

    err = asyncio.run(call())
    assert err.status_code == 503
    assert err.body["error"]["type"] == "overloaded_error"

    async def call_stream():
        body = {"model": "t", "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 4, "stream": True}
        chunks = []
        with pytest.raises(BackendError) as exc:
            async for c in backend.stream(body, {}, timeout=30):
                chunks.append(c)
        # the 503 must arrive BEFORE any SSE chunk — a started 200 stream
        # can't be turned into an error status
        assert chunks == []
        return exc.value

    err2 = asyncio.run(call_stream())
    assert err2.status_code == 503
    blocker.set()
    held.cancel.set()
    t.join(timeout=30)
    assert not t.is_alive()
