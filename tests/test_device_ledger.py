"""The device ledger (quorum_tpu/telemetry/device_ledger.py, ISSUE 40): one
account of device time in the engine, booked landing to landing on the
scheduler's clock; a dry device's time booked to the phase of the turn that
was open; a ``prefill`` span's wait split into its own programs, its peers',
decode and the rest; the two paces the segment rule reads; the stall
witness.

The ledger's own cases run on a stubbed clock (no engine, no device); the
closure cases run tiny engines on the CPU and check that every second of the
loop's wall clock is booked once.
"""

import logging
import statistics
import threading
import time

import pytest

from quorum_tpu.engine import engine as engine_mod
from quorum_tpu.engine.engine import (TURN_PHASES, InferenceEngine,
                                      _SegmentRoom)
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.observability import RequestTrace, use_trace
from quorum_tpu.telemetry import device_ledger
from quorum_tpu.telemetry.device_ledger import (DECODE, OTHER, PREFILL,
                                                DeviceLedger)
from quorum_tpu.telemetry.recorder import RECORDER

TINY = resolve_spec("llama-tiny")  # max_seq 128


class Clock:
    def __init__(self, monkeypatch):
        self.now = 100.0
        monkeypatch.setattr(device_ledger.time, "perf_counter",
                            lambda: self.now)
        monkeypatch.setattr(device_ledger.compile_watch, "thread_seconds",
                            lambda: self.built)
        monkeypatch.setattr(device_ledger, "_is_ready",
                            lambda witness: witness in self.ready)
        self.built = 0.0
        self.ready = set()  # the witnesses that are already in


@pytest.fixture
def clock(monkeypatch):
    return Clock(monkeypatch)


def _ledger(clock, **kw):
    led = DeviceLedger(TURN_PHASES, **kw)
    led.switch("fill", clock.now)
    return led


def _total(led):
    snap = led.snapshot(led.head)
    return (sum(snap["decode_s"].values()) + sum(snap["prefill_s"].values())
            + snap["other_s"] + snap["idle_s"]
            + sum(snap["starved_s"].values()))


# ---- the rule, on a stubbed clock ------------------------------------------


def test_ring_depth_two_does_not_double_a_chunks_seconds(clock):
    """Two chunks in flight, each 1.0 s of device time: dispatch→ready reads
    1.0 and 2.0 (the fault the ledger replaces); landing to landing each is
    booked 1.0."""
    led = _ledger(clock)
    a = led.dispatch(DECODE, "plain", 512, 8, t=100.0)
    b = led.dispatch(DECODE, "plain", 512, 8, t=100.001)
    a.land(101.0)
    c = led.dispatch(DECODE, "plain", 512, 8, t=101.002)
    b.land(102.0)
    c.land(103.0)
    assert [round(p.seconds, 6) for p in (a, b, c)] == [1.0, 1.0, 1.0]
    assert b.t1 - b.t == pytest.approx(1.999)  # what dispatch→ready said
    assert led.decode_s == {"plain": pytest.approx(3.0)}
    assert led.step_alone_s == pytest.approx(1.0 / 8)
    assert sum(led.starved_s.values()) == 0.0
    assert led.inexact_s == {"split": 0.0, "probe": 0.0}


def test_starved_time_lands_on_the_phase_that_was_open(clock):
    """The device runs dry at a landing; the host's turn until the next
    dispatch is split at the phase switches, compile seconds apart."""
    led = _ledger(clock)
    a = led.dispatch(DECODE, "plain", 512, 8, t=100.0)
    led.switch("reap_block", 100.0)
    a.land(101.0)
    led.switch("emit", 101.0)      # reap_block closes, emit resumes
    led.switch("sweep", 101.3)
    led.switch("admit", 101.4)
    clock.built = 0.25             # a first-use compile inside the dispatch
    b = led.dispatch(PREFILL, "single_shot", 64, 64, t=102.0)
    b.land(102.5)
    assert led.starved_s["emit"] == pytest.approx(0.3)
    assert led.starved_s["sweep"] == pytest.approx(0.1)
    assert led.starved_s["admit"] == pytest.approx(0.35)
    assert led.starved_s["compile"] == pytest.approx(0.25)
    assert led.starved_s["reap_block"] == 0.0
    assert b.starved_before == 0.0  # a stretch that met a compile
    assert led.prefill_s == {("single_shot", 64): pytest.approx(0.5)}
    assert _total(led) == pytest.approx(2.5)


def test_idle_and_compile_are_no_stall(clock):
    led = _ledger(clock)
    led.switch("idle", 100.0)
    led.switch("fill", 110.0)  # 10 s with no work, then a 3 s compile
    clock.built = 3.0
    a = led.dispatch(DECODE, "plain", 512, 8, t=113.5)
    assert led.idle_s == pytest.approx(10.0) and "idle" not in led.starved_s
    assert led.starved_s["compile"] == pytest.approx(3.0)
    assert a.starved_before == 0.0  # a compile's tracing is no stall either
    a.land(114.0)
    led.switch("idle", 114.0)
    led.switch("admit", 120.0)
    b = led.dispatch(PREFILL, "single_shot", 64, 64, t=122.5)
    assert b.starved_before == pytest.approx(2.5)  # the loop was not idle


def test_a_witness_between_prefill_and_decode_splits_exactly(clock):
    """Segments, the marker, the chunk: the blocking reap waits on the
    marker first, so each class gets an interval of its own and both paces
    are timed with no subtraction."""
    led = _ledger(clock)
    s1 = led.dispatch(PREFILL, "seg", 512, 512, t=100.0)
    s2 = led.dispatch(PREFILL, "seg", 256, 256, t=100.001)
    reg = led.dispatch(OTHER, "register", t=100.002)
    mark = led.dispatch(OTHER, "mark", witness="m", t=100.003)
    chunk = led.dispatch(DECODE, "plain", 2048, 8, witness="c", t=100.004)

    def block(witness):
        assert witness == "m"
        clock.now = 100.6

    led.wait_before(chunk, block)
    chunk.land(101.0)
    assert (s1.seconds, s2.seconds) == (pytest.approx(0.4),
                                        pytest.approx(0.2))
    assert reg.seconds == mark.seconds == 0.0  # nothing from their company
    assert chunk.seconds == pytest.approx(0.4)
    assert (chunk.t0, chunk.t1) == (pytest.approx(100.6), 101.0)
    assert led.seg_tok_s == pytest.approx(0.6 / 768)
    assert led.step_alone_s == pytest.approx(0.4 / 8)
    assert led.inexact_s == {"split": 0.0, "probe": 0.0}


def test_a_witness_already_in_falls_back_to_subtraction(clock):
    """The prefill programs had landed before the reap looked: no landing
    to be had, the interval is split by the pace of the last chunk that ran
    alone and counted as inexact."""
    led = _ledger(clock)
    led.dispatch(DECODE, "plain", 512, 8, t=100.0).land(100.4)
    led.switch("admit", 100.4)
    seg = led.dispatch(PREFILL, "seg", 512, 512, t=100.5)
    mark = led.dispatch(OTHER, "mark", witness="m", t=100.501)
    chunk = led.dispatch(DECODE, "plain", 512, 8, t=100.502)
    clock.ready.add("m")
    led.wait_before(chunk, lambda w: pytest.fail("nothing to wait for"))
    chunk.land(101.5)
    assert chunk.seconds == pytest.approx(0.4)      # 8 steps at 0.05
    assert seg.seconds == pytest.approx(0.6)
    assert mark.seconds == 0.0
    assert led.inexact_s["split"] == pytest.approx(1.0)
    assert led.seg_tok_s == pytest.approx(0.6 / 512)
    assert led.starved_s["admit"] == pytest.approx(0.1)


def test_a_probed_landing_is_late_and_says_so(clock):
    """``ready()`` succeeds some time after the program landed. With a
    program queued behind it the device cannot have starved; with none the
    stretch is booked busy and added to the ledger's error bar."""
    led = _ledger(clock)
    a = led.dispatch(DECODE, "plain", 512, 8, t=100.0)
    b = led.dispatch(DECODE, "plain", 512, 8, t=100.1)
    a.land(101.0, exact=False)
    assert led.inexact_s["probe"] == 0.0
    b.land(102.5, exact=False)
    assert led.inexact_s["probe"] == pytest.approx(1.5)
    assert led.step_alone_s == 0.0  # a late landing times no pace
    assert led.decode_s["plain"] == pytest.approx(2.5)


def test_a_prefill_spans_parts_add_up_to_its_length(clock):
    """Two admissions open at once: each one's segments are the other's
    peer time; the chunk between them is both's decode wait; a span that
    ends before its last segment has landed is settled when it has."""
    led = _ledger(clock)
    parts = {}
    a, b = led.open(100.0), led.open(100.2)
    led.switch("admit", 100.0)
    sa = led.dispatch(PREFILL, "seg", 512, 512, [a], t=100.3)
    sb = led.dispatch(PREFILL, "seg", 512, 512, [b], witness="m", t=100.301)
    chunk = led.dispatch(DECODE, "plain", 2048, 8, t=100.302)
    sb.land(101.3)
    chunk.land(101.8)
    led.switch("admit", 101.8)
    sa2 = led.dispatch(PREFILL, "seg", 512, 512, [a], t=101.9)
    a.close(101.95, lambda s: parts.update(a=s))   # register dispatched
    assert "a" not in parts                        # its segment is still out
    sa2.land(102.4)
    b.close(102.4, lambda s: parts.update(b=s))
    pa, pb = parts["a"], parts["b"]
    assert (pa.own, pa.peer) == (pytest.approx(0.55), pytest.approx(0.5))
    assert pa.decode == pytest.approx(0.5)
    assert pa.starved == pytest.approx(0.4)        # 0.3 + 0.1 of admit
    assert pa.own + pa.peer + pa.decode + pa.starved + pa.other == \
        pytest.approx(1.95)
    assert (pb.own, pb.peer) == (pytest.approx(0.5), pytest.approx(1.0))
    assert pb.own + pb.peer + pb.decode + pb.starved == pytest.approx(2.2)
    assert sa.seconds == sb.seconds == pytest.approx(0.5)


def test_a_span_waits_only_for_the_chunks_that_landed_while_it_was_open(
        clock):
    """``decode_wait`` is the decode dispatches an admission waited out:
    those that landed while its span was open and a program of its own was
    out. An admission whose segments and register go out in one turn waits
    for none: the chunk in flight, still on the device when its span ends,
    is ``ahead``."""
    led = _ledger(clock)
    chunk = led.dispatch(DECODE, "plain", 2048, 8, t=100.0)  # ring depth 2
    one, two = led.open(100.1), led.open(100.1)
    s1 = led.dispatch(PREFILL, "seg", 512, 512, [one], t=100.2)
    s2 = led.dispatch(PREFILL, "seg", 512, 512, [two], witness="m", t=100.21)
    one.close(100.3)                       # registered in the same turn
    nxt = led.dispatch(DECODE, "plain", 2048, 8, t=100.31)
    chunk.land(100.6)
    s2.land(101.6)
    nxt.land(102.1)
    s3 = led.dispatch(PREFILL, "seg", 512, 512, [two], t=102.1)
    two.close(102.2)                       # registered: s3 is still out
    s3.land(102.6)
    assert (one.decode, one.ahead) == (0.0, pytest.approx(0.2))
    assert (two.decode, two.ahead) == (pytest.approx(1.0), 0.0)
    assert two.own == pytest.approx(0.6) and two.peer == pytest.approx(0.5)
    assert sum(two.parts_ms().values()) == pytest.approx(2100.0)
    assert s1.seconds == pytest.approx(0.5)
    late = led.open(102.6)                 # a claim with no program yet
    led.dispatch(DECODE, "plain", 2048, 8, t=102.6).land(103.1)
    assert (late.decode, late.ahead) == (0.0, pytest.approx(0.5))


def test_what_is_queued_when_the_loop_goes_idle_lands_there(clock):
    """A program with no landing of its own (a snapshot slice after the
    last row finished, a register, what a contained failure left) must not
    make the idle stretch the next admit's prefill time."""
    led = _ledger(clock)
    booked = []
    led.on_booked = booked.append
    a = led.dispatch(DECODE, "plain", 512, 8, t=100.0)
    a.land(101.0)
    snap = led.dispatch(OTHER, "snap", t=101.1)
    led.switch("idle", 101.2)
    assert snap.landed and led.other_s == pytest.approx(0.1)
    led.switch("admit", 106.2)             # 5 s with no request
    admit = led.dispatch(PREFILL, "single_shot", 64, 64, t=106.3)
    admit.land(106.8)
    assert led.prefill_s == {("single_shot", 64): pytest.approx(0.5)}
    assert led.idle_s == pytest.approx(5.0)
    assert led.starved_s["fill"] == pytest.approx(0.1)
    assert led.starved_s["admit"] == pytest.approx(0.1)
    assert [p.seconds for p in booked] == [pytest.approx(1.0),
                                           pytest.approx(0.1),
                                           pytest.approx(0.5)]
    assert _total(led) == pytest.approx(6.8)


def test_a_program_booked_nothing_is_no_observation(clock):
    """A register or snapshot in a chunk's company takes 0 s of it: that
    is no reading of its family's latency."""
    led = _ledger(clock)
    booked = []
    led.on_booked = booked.append
    led.dispatch(OTHER, "register", t=100.0)
    chunk = led.dispatch(DECODE, "plain", 512, 8, t=100.001)
    chunk.land(101.0)
    assert [p.family for p in booked] == ["plain"]


def test_a_scrape_in_the_middle_of_a_chunk_moves_no_pace(clock):
    """The stretch ahead of the cursor is counted provisionally, a decode
    dispatch's with steps at the pace booked so far."""
    led = _ledger(clock)
    a = led.dispatch(DECODE, "plain", 512, 8, t=100.0)
    b = led.dispatch(DECODE, "plain", 512, 8, t=100.001)
    a.land(101.0)
    snap = led.snapshot(101.5)
    assert snap["decode_steps"] == 12
    assert sum(snap["decode_s"].values()) == pytest.approx(1.5)
    assert led.snapshot(105.0)["decode_steps"] == 16  # no more than it has
    b.land(102.0)
    assert led.snapshot(102.0)["decode_steps"] == 16


def _old_paces(landings):
    """The parent's ``_book_segment_time`` on a recorded series of
    (dispatch stamp, steps, segment tokens queued ahead, landing, probed)."""
    ready_prev = step_alone = tok_s = 0.0
    samples = []
    for t0, steps, seg_tokens, t_ready, probed in landings:
        own = t_ready - max(ready_prev, t0)
        ready_prev = t_ready
        if probed:
            continue
        if not seg_tokens:
            step_alone = own / steps
        elif step_alone:
            samples.append(max(0.0, own - step_alone * steps) / seg_tokens)
            tok_s = statistics.median(samples[-5:])
    return step_alone, tok_s


# The landings of tests/test_chunked_prefill.py's cases as one recorded
# series: a resident stream's chunks of 4 steps, then a 100-token admission's
# seven 16-token segments going out 3 + 3 + 1 ahead of three of them, a
# chunk the drain found landed, and chunks alone again.
RECORDED = [
    (0.000, 4, 0, 0.080, False), (0.081, 4, 0, 0.161, False),
    (0.170, 4, 48, 0.310, False), (0.312, 4, 48, 0.447, False),
    (0.449, 4, 16, 0.551, False), (0.552, 4, 0, 0.640, True),
    (0.641, 4, 0, 0.722, False), (0.730, 4, 32, 0.870, False),
]


def test_segment_room_decides_as_before_on_the_recorded_landings(clock):
    """Without a marker's landing the ledger sees what the parent's reaps
    saw, times the same two paces on them, and ``_SegmentRoom`` therefore
    lets the same segments into a turn."""
    led = _ledger(clock)
    for t0, steps, seg_tokens, t_ready, probed in RECORDED:
        if seg_tokens:
            led.dispatch(PREFILL, "seg", 16, seg_tokens, t=100.0 + t0 - 0.001)
        chunk = led.dispatch(DECODE, "plain", 128, steps, t=100.0 + t0)
        chunk.land(100.0 + t_ready, exact=not probed)
    step_s, tok_s = _old_paces(RECORDED)
    assert led.step_alone_s == pytest.approx(step_s, rel=1e-9)
    # the ledger's intervals start at the first program's dispatch, 1 ms
    # before the chunk's, where the device was dry: within that of the old
    assert led.seg_tok_s == pytest.approx(tok_s, abs=0.001 / 16)

    def per_turn(step_s, tok_s):
        room, n = _SegmentRoom(step_s * 4, tok_s), 0
        while room.take(16, floor=not n):
            n += 1
        return n

    assert per_turn(led.step_alone_s, led.seg_tok_s) == per_turn(step_s,
                                                                 tok_s)


# ---- closure: every second of the loop's wall clock is booked once ----------


def _device_total(m):
    return (sum(m["device_decode_seconds_total"].values())
            + sum(m["device_prefill_seconds_total"].values())
            + m["device_other_seconds_total"]
            + m["device_idle_seconds_total"]
            + sum(m["device_starved_seconds_total"].values()))


def _serve(eng, prompts, n_new=10, **kw):
    reqs, traces = [], []
    for i, prompt in enumerate(prompts):
        traces.append(RequestTrace(f"r{i}"))
        with use_trace(traces[-1]):
            reqs.append(eng.submit(prompt, max_new_tokens=n_new,
                                   **(kw.get("each", lambda i: {})(i))))
    for r in reqs:
        assert len(list(eng.stream_results(r))) == n_new
    return [next(s for s in t.spans if s.name == "prefill") for t in traces]


def _prompt(n, salt):
    return [(salt + 11 * i) % 500 for i in range(n)]


CASES = {
    "single_shot": (dict(decode_chunk=4, n_slots=2, prefill_chunk=0),
                    [_prompt(20, 1), _prompt(30, 2)], {}),
    "chunked": (dict(decode_chunk=4, n_slots=3, prefill_chunk=16),
                [_prompt(5, 1), _prompt(70, 2), _prompt(60, 3)], {}),
    "coalesced": (dict(decode_chunk=4, n_slots=2, prefill_chunk=0,
                       members=3, seed=0),
                  [_prompt(20, 1)] * 3, {"each": lambda i: {"member": i}}),
    "stacked_chunked": (dict(decode_chunk=4, n_slots=2, prefill_chunk=16,
                             members=3, seed=0),
                        [_prompt(60, 1)] * 3,
                        {"each": lambda i: {"member": i}}),
}


@pytest.mark.parametrize("case", sorted(CASES) + ["patterned"])
def test_the_four_accounts_add_up_to_the_loops_wall_clock(case):
    """decode + prefill + other + starved (+ idle: the loop with no work)
    rise by the wall clock between two scrapes, within 2 %; a ``prefill``
    span's parts add up to its length; nothing is booked twice."""
    if case == "patterned":
        spec = resolve_spec("k-exaone-tiny")
        kw, prompts, how = (dict(decode_chunk=4, n_slots=2,
                                 prefill_chunk=16),
                            [_prompt(12, 1), _prompt(40, 2)], {})
    else:
        spec = TINY
        kw, prompts, how = CASES[case]
    eng = InferenceEngine(spec, **kw)
    try:
        _serve(eng, prompts, **how)  # compile
        t0, m0 = time.perf_counter(), eng.metrics()
        spans = _serve(eng, prompts, **how)
        t1, m1 = time.perf_counter(), eng.metrics()
    finally:
        eng.shutdown()
    # (a scrape counts up to its own moment, some 0.1 ms after the stamp)
    assert _device_total(m1) - _device_total(m0) == pytest.approx(
        t1 - t0, rel=0.02, abs=0.002)
    assert m1["device_decode_steps_total"] > m0["device_decode_steps_total"]
    programs = sum(m1["device_prefill_programs_total"].values()) - sum(
        m0["device_prefill_programs_total"].values())
    assert programs >= 1
    for s in spans:
        parts = sum(s.meta[k] for k in (
            "own_ms", "peer_ms", "decode_wait_ms", "decode_ahead_ms",
            "starved_ms"))
        assert parts == pytest.approx((s.end - s.start) * 1e3, abs=0.01)
        # a single-shot span ends where its token is fetched; a chunked one
        # where its register is dispatched, maybe before its programs ran
        assert s.meta["own_ms"] > 0 or s.meta.get("chunked")
    assert m1["stalls_total"] == 0


def test_two_open_admissions_are_each_others_peers():
    """Two chunked admissions entering one turn beside a resident stream:
    each span has the other's segments as ``peer_ms``, the sums beside
    ``prefill_span_seconds_total`` rise by what the spans say, and the
    recorder's ``reap`` events carry the booked seconds of segment and
    chunk programs alike."""
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=3, prefill_chunk=16)
    try:
        eng.generate(_prompt(64, 9), max_new_tokens=2)  # compile
        RECORDER.reset()
        m0 = eng.metrics()
        done = threading.Event()

        def resident():
            list(eng.stream_results(eng.submit([5, 6, 7],
                                               max_new_tokens=60)))
            done.set()

        threading.Thread(target=resident).start()
        deadline = time.monotonic() + 60
        while not any(eng._slots) and time.monotonic() < deadline:
            time.sleep(0.001)
        traces, reqs = [], []
        with eng._cond:  # the scheduler meets both in one turn
            for i in range(2):
                traces.append(RequestTrace(f"long-{i}"))
                with use_trace(traces[-1]):
                    reqs.append(eng.submit(_prompt(64, 3 + i),
                                           max_new_tokens=4))
        for r in reqs:
            list(eng.stream_results(r))
        assert done.wait(60)
        m1 = eng.metrics()
        events = [e for e in RECORDER.snapshot()
                  if e.get("engine") == eng._tag and e["kind"] == "reap"]
    finally:
        eng.shutdown()
    spans = [next(s for s in t.spans if s.name == "prefill") for t in traces]
    assert all(s.meta["peer_ms"] > 0 for s in spans)
    assert any(s.meta["own_ms"] > 0 for s in spans)
    rose = {k: (m1[f"prefill_{k}_seconds_total"]
                - m0[f"prefill_{k}_seconds_total"]) * 1e3
            for k in ("own", "peer", "decode_wait", "span")}
    for k, attr in (("own", "own_ms"), ("peer", "peer_ms"),
                    ("decode_wait", "decode_wait_ms")):
        assert rose[k] == pytest.approx(sum(s.meta[attr] for s in spans),
                                        abs=0.01)
    assert rose["span"] == pytest.approx(
        sum(s.end - s.start for s in spans) * 1e3, abs=0.01)
    segs = [e for e in events if e["family"] == "seg"]
    chunks = [e for e in events if "seq" in e]
    assert len(segs) == 8 and chunks
    assert all(e["t_ready"] - e["t_start"] == pytest.approx(e["booked_s"],
                                                            abs=2e-6)
               for e in segs + chunks)


# ---- the stall witness -------------------------------------------------------


def test_the_stall_witness_fires_once_on_a_long_wait_not_on_a_compile(
        monkeypatch, tmp_path, caplog):
    """A blocking wait on a landing of 3 s (stubbed) is counted once, logged
    and dumped; the first request's compiles, seconds of a dry device, are
    not."""
    monkeypatch.setenv("QUORUM_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("QUORUM_TPU_FLIGHT_DUMP_INTERVAL", "0")
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=0)
    try:
        # compiles hold the device dry for seconds: no stall
        monkeypatch.setattr(engine_mod, "STALL_MIN_S", 0.05)
        eng.generate(_prompt(20, 1), max_new_tokens=6)
        assert eng.metrics()["stalls_total"] == 0
        monkeypatch.setattr(engine_mod, "STALL_MIN_S", 2.0)
        real_fetch, slow = engine_mod._host_fetch, [True]

        def fetch(*arrays):
            out = real_fetch(*arrays)
            if slow and len(arrays) == 2:  # one decode chunk's reap
                slow.clear()
                clock = time.perf_counter
                monkeypatch.setattr(engine_mod.time, "perf_counter",
                                    lambda: clock() + 3.0)
            return out

        monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
        with caplog.at_level(logging.WARNING,
                             logger=engine_mod.logger.name):
            eng.generate(_prompt(20, 2), max_new_tokens=12)
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert m["stalls_total"] == 1
    lines = [r.getMessage() for r in caplog.records
             if "engine stall" in r.getMessage()]
    assert len(lines) == 1 and "family plain" in lines[0]
    assert "rows live" in lines[0] and "ring depth" in lines[0]
    assert list(tmp_path.glob("flightrec-stall-*.json"))
