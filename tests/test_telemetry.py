"""Engine flight recorder, per-family device-time attribution, SLO
accounting (ISSUE 12, quorum_tpu/telemetry/, docs/observability.md).

Covers the acceptance criteria:
  - a K=4·C=4 run records overlapped in-flight dispatches tagged with
    their compile-budget family, exportable as a Perfetto trace; a
    zero_drain=1 run's admission/injection/register events correlate with
    its decode reaps by request id (and a disagg 1+1 run correlates
    prefill-loop and decode-loop events);
  - every decode program family the engine compiled appears in
    quorum_tpu_dispatch_device_seconds;
  - recorder on vs off produces identical streams, and per-event recorder
    cost stays under a measured per-dispatch budget;
  - the recorder ring is bounded (drop accounting), dumps parse, and the
    dump rate limit holds;
  - SLO classification/scoring/burn-rate, and the /debug/profile
    single-flight 409 + the maybe_profile skip counter.
"""

import json
import os
import time

import pytest

from quorum_tpu import observability as obs
from quorum_tpu.analysis import budget
from quorum_tpu.telemetry.latency import LatencyModel
from quorum_tpu.telemetry.recorder import RECORDER, FlightRecorder
from quorum_tpu.telemetry import slo
from tests.conftest import make_client


# ---- recorder unit ---------------------------------------------------------


def test_recorder_ring_is_bounded_and_counts_drops():
    dropped = []
    rec = FlightRecorder(capacity=32, enabled=True)
    rec.on_drop = lambda: dropped.append(1)
    for i in range(100):
        rec.record("tick", rid=f"r{i}", n=i)
    assert rec.depth() == 32
    assert rec.total() == 100
    assert len(dropped) == 100 - 32
    events = rec.snapshot()
    assert len(events) == 32
    assert events[-1]["n"] == 99  # newest kept, oldest overwritten
    assert events[0]["n"] == 68


def test_recorder_disabled_records_nothing():
    rec = FlightRecorder(capacity=32, enabled=False)
    rec.record("tick")
    assert rec.depth() == 0 and rec.total() == 0
    assert rec.dump("test") is None


def test_recorder_dump_writes_parseable_artifact(tmp_path, monkeypatch):
    monkeypatch.setenv("QUORUM_TPU_FLIGHT_DUMP_INTERVAL", "0.2")
    rec = FlightRecorder(capacity=32, enabled=True)
    rec.record("containment", rid="r1", engine="e1",
               error="FaultInjected: injected fault at 'engine.admit'")
    path = rec.dump("containment", log_dir=str(tmp_path))
    assert path is not None and os.path.exists(path)
    body = json.loads(open(path).read())
    assert body["reason"] == "containment"
    assert any("engine.admit" in json.dumps(e) for e in body["events"])
    # rate limit: an immediate second dump for the same reason is skipped;
    # a different reason is not
    assert rec.dump("containment", log_dir=str(tmp_path)) is None
    assert rec.dump("fail-all", log_dir=str(tmp_path)) is not None


def test_recorder_perfetto_export_shapes():
    rec = FlightRecorder(capacity=64, enabled=True)
    t0 = time.perf_counter()
    rec.record("dispatch", engine="e1", loop="decode", t=t0, seq=1,
               family="loop", depth=0, rids=["r1"])
    rec.record("reap", engine="e1", loop="decode", seq=1, family="loop",
               depth=0, t_issue=t0, t_ready=t0 + 0.25, rids=["r1"])
    rec.record("admit", rid="r1", engine="e1", loop="prefill")
    te = rec.to_trace_events()
    meta = [e for e in te if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    xs = [e for e in te if e["ph"] == "X"]
    assert len(xs) == 1
    x = xs[0]
    assert x["name"] == "loop" and x["args"]["rids"] == ["r1"]
    assert abs(x["dur"] - 0.25e6) < 1e3  # microseconds
    instants = [e for e in te if e["ph"] == "i"]
    assert any(e["name"] == "admit" and e["args"]["rid"] == "r1"
               for e in instants)


def test_recorder_overhead_under_per_dispatch_budget():
    """Bounded overhead: the mean cost of one record() must sit far below
    anything a dispatch costs. Budget: 200 microseconds per event — a
    dispatch's host turnaround is measured in the hundreds of
    microseconds at best, so the recorder stays < ~0.1% of a dispatch
    even on a loaded CI core (typical measured cost is ~2 us)."""
    rec = FlightRecorder(capacity=4096, enabled=True)
    n = 5000
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("dispatch", engine="e", loop="decode", seq=i,
                   family="loop", depth=i % 4, rids=["r1", "r2"])
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 200e-6, f"record() cost {per_event * 1e6:.1f}us/event"


# ---- latency model ---------------------------------------------------------


def test_latency_model_ewma_and_percentiles():
    m = LatencyModel(alpha=0.5)
    for v in (0.1, 0.2, 0.3, 0.4):
        m.observe("loop", v)
    m.observe("plain", 0.05)
    assert m.families() == ["loop", "plain"]
    # ewma: 0.1 -> 0.15 -> 0.225 -> 0.3125
    assert abs(m.ewma("loop") - 0.3125) < 1e-9
    assert m.ewma("missing") == 0.0
    snap = m.snapshot()
    assert snap["loop"]["count"] == 4
    # nearest-rank: p50 of 4 samples is the 2nd value, p99 the 4th
    assert snap["loop"]["p50_ms"] == 200.0
    assert snap["loop"]["p99_ms"] == 400.0
    assert snap["plain"]["count"] == 1
    assert snap["plain"]["p50_ms"] == snap["plain"]["p99_ms"] == 50.0


# ---- SLO accounting --------------------------------------------------------


def test_slo_classification_by_deadline_headroom(monkeypatch):
    monkeypatch.setenv("QUORUM_TPU_SLO_INTERACTIVE_S", "30")
    assert slo.classify(5.0) == "interactive"
    assert slo.classify(30.0) == "interactive"
    assert slo.classify(31.0) == "batch"
    assert slo.classify(None) == "batch"


def test_slo_score_trace_and_burn_rate(monkeypatch):
    monkeypatch.setenv("QUORUM_TPU_SLO_TTFT_INTERACTIVE_S", "0.5")
    monkeypatch.setenv("QUORUM_TPU_SLO_GAP_INTERACTIVE_S", "0.1")
    tracker = slo.SloTracker()
    good0 = obs.SLO_GOOD.value
    breach0 = obs.SLO_BREACHED.value

    t = obs.RequestTrace("req-slo-good")
    t.meta["slo"] = "interactive"
    t.ttft = 0.2
    t.max_token_gap = 0.05
    t.status = 200
    tracker.score_trace(t)
    t2 = obs.RequestTrace("req-slo-bad")
    t2.meta["slo"] = "interactive"
    t2.ttft = 2.0                       # breaches ttft
    t2.max_token_gap = 0.5              # breaches inter_token
    t2.status = 504                     # breaches deadline
    tracker.score_trace(t2)

    snap = tracker.snapshot()
    st = snap["interactive"]["stages"]
    assert st["ttft"] == {"good": 1, "breached": 1}
    assert st["inter_token"] == {"good": 1, "breached": 1}
    assert st["deadline"] == {"good": 1, "breached": 1}
    assert snap["interactive"]["burn_rate"] == 0.5
    assert snap["batch"]["stages"] == {}
    # the process-global counters advanced with class/stage labels
    assert obs.SLO_GOOD.value == good0 + 3
    assert obs.SLO_BREACHED.value == breach0 + 3
    assert obs.SLO_GOOD.value_of(**{"class": "interactive",
                                    "stage": "ttft"}) >= 1


def test_slo_untagged_and_client_gone_traces_not_scored():
    tracker = slo.SloTracker()
    t = obs.RequestTrace("req-untagged")
    t.ttft = 0.1
    t.status = 200
    tracker.score_trace(t)             # no meta.slo -> ignored
    gone = obs.RequestTrace("req-gone")
    gone.meta["slo"] = "interactive"
    gone.status = 499                  # client disconnect: no deadline score
    tracker.score_trace(gone)
    assert tracker.snapshot()["interactive"]["stages"].get("deadline") \
        is None


def test_slo_ready_burn_threshold_parsing(monkeypatch):
    monkeypatch.delenv("QUORUM_TPU_SLO_READY_BURN", raising=False)
    assert slo.ready_burn_threshold() is None
    assert slo.burning_class() is None
    monkeypatch.setenv("QUORUM_TPU_SLO_READY_BURN", "0.5")
    assert slo.ready_burn_threshold() == 0.5
    monkeypatch.setenv("QUORUM_TPU_SLO_READY_BURN", "junk")
    assert slo.ready_burn_threshold() is None
    monkeypatch.setenv("QUORUM_TPU_SLO_READY_BURN", "1.5")
    assert slo.ready_burn_threshold() is None


# ---- engine integration ----------------------------------------------------


def _tiny_engine(**kw):
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS

    return InferenceEngine(MODEL_PRESETS["llama-tiny"], **kw)


def _greedy():
    from quorum_tpu.ops.sampling import SamplerConfig

    return SamplerConfig(temperature=0.0)


def test_megachunk_run_records_family_tagged_overlapped_dispatches():
    """The K=4·C=4 acceptance: dispatch/reap events tagged with the
    "loop" compile-budget family, some dispatched at ring depth > 0
    (overlap), and the Perfetto export renders them as X slices."""
    eng = _tiny_engine(decode_chunk=4, decode_pipeline=4, decode_loop=4)
    # Warm the programs first: the ring only dispatches AHEAD onto warm
    # programs, so overlap is observable from the second generation on.
    eng.generate([5, 6, 7], max_new_tokens=32, sampler=_greedy())
    RECORDER.reset()
    res = eng.generate([5, 6, 7], max_new_tokens=32, sampler=_greedy())
    assert len(res.token_ids) == 32
    events = RECORDER.snapshot()
    mine = [e for e in events if e.get("engine") == eng._tag]
    # a ring entry's reap carries its seq; the admit's, booked by the same
    # device ledger, carries none and renders on a track of its own
    landed = [e for e in mine if e["kind"] == "reap"]
    reaps = [e for e in landed if "seq" in e]
    assert reaps, mine
    assert all(e["family"] == "loop" for e in reaps), reaps
    assert {e["family"] for e in landed if "seq" not in e} == {"single_shot"}
    assert all(e["t_ready"] >= e["t_issue"] for e in landed)
    # dispatch/reap pair by seq
    disp = {e["seq"] for e in mine if e["kind"] == "dispatch"}
    assert {e["seq"] for e in reaps} <= disp
    assert any(e["depth"] > 0 for e in reaps) or eng.n_overlapped > 0
    # booked landing to landing: the intervals follow one another, where
    # dispatch→ready of overlapped entries stacked up with the ring's depth
    landed.sort(key=lambda e: e["t_start"])
    assert all(a["t_ready"] <= b["t_start"] + 2e-6
               for a, b in zip(landed, landed[1:]))
    assert all(e["booked_s"] == pytest.approx(e["t_ready"] - e["t_start"],
                                              abs=2e-6) for e in landed)
    xs = [e for e in RECORDER.to_trace_events() if e.get("ph") == "X"]
    assert any(e["name"] == "loop" for e in xs)
    # the per-engine latency model saw the same family
    assert "loop" in eng.latency.snapshot()
    assert eng.latency.ewma("loop") > 0.0
    eng.shutdown()


def test_every_compiled_decode_family_appears_in_device_seconds():
    """Acceptance: every family in compile_budget.json that EXECUTES
    appears in quorum_tpu_dispatch_device_seconds — checked as: every
    family classified from this engine's decode program cache has a
    labeled series after traffic (a constrained request adds the dfa
    family)."""
    from quorum_tpu.constrain import compile_response_format
    from quorum_tpu.engine.tokenizer import ByteTokenizer

    eng = _tiny_engine(decode_chunk=4, decode_pipeline=2)
    tok = ByteTokenizer(eng.spec.vocab_size)
    wildcard = compile_response_format(
        {"type": "regex", "pattern": "[\\x00-\\xff]*"}, tok,
        eng.spec.vocab_size)
    for grammar in (None, wildcard):
        req = eng.submit([7, 7, 7, 7], max_new_tokens=16, sampler=_greedy(),
                         eos_id=tok.eos_id if grammar is not None else None,
                         grammar=grammar)
        assert list(eng.stream_results(req))
    compiled = budget.decode_families(eng._decode_cache)
    assert compiled == {"plain", "dfa"}
    observed = {dict(k).get("family")
                for k in obs.DISPATCH_DEVICE_SECONDS.snapshot()}
    missing = compiled - observed
    assert not missing, (compiled, observed)
    # admission-path families attribute too (single-shot admit here)
    assert "single_shot" in observed
    eng.shutdown()


def test_recorder_on_vs_off_streams_identical():
    """Token-for-token pin: the recorder observes, never steers."""
    prompt, n = [3, 4, 5], 24

    def run_with(enabled):
        old = RECORDER.enabled
        RECORDER.enabled = enabled
        try:
            eng = _tiny_engine(decode_chunk=4, decode_pipeline=4,
                               decode_loop=4, seed=11)
            out = eng.generate(prompt, max_new_tokens=n,
                               sampler=_greedy()).token_ids
            sampled = eng.generate(prompt, max_new_tokens=n,
                                   sampler=_greedy().__class__(
                                       temperature=0.9), seed=7).token_ids
            eng.shutdown()
            return out, sampled
        finally:
            RECORDER.enabled = old

    on = run_with(True)
    off = run_with(False)
    assert on == off


def test_zero_drain_injection_events_correlate_by_rid():
    """The zero_drain=1 acceptance half: staged admission events
    (stage-admit → inject → register) and the decode ring's reaps carry
    the SAME request id, so the injection path is one correlated
    timeline."""
    RECORDER.reset()
    eng = _tiny_engine(decode_chunk=4, decode_pipeline=4, decode_loop=2,
                       n_slots=2, prefill_chunk=16, zero_drain=True)
    prompt = [(7 + 3 * i) % eng.spec.vocab_size for i in range(40)]
    res = eng.generate(prompt, max_new_tokens=8, sampler=_greedy())
    assert len(res.token_ids) == 8
    events = [e for e in RECORDER.snapshot()
              if e.get("engine") == eng._tag]
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["kind"], []).append(e)
    assert by_kind.get("stage-admit"), by_kind.keys()
    assert by_kind.get("inject"), by_kind.keys()
    assert by_kind.get("register"), by_kind.keys()
    rid = by_kind["stage-admit"][0]["rid"]
    assert any(e["rid"] == rid for e in by_kind["inject"])
    assert any(e["rid"] == rid for e in by_kind["register"])
    assert any(rid in e.get("rids", ()) for e in by_kind.get("reap", []))
    eng.shutdown()


def test_disagg_prefill_and_decode_loop_events_correlate_by_rid():
    """Dual-loop correlation: under disagg the admit/handoff events come
    from the prefill loop and the register/reap from the decode loop —
    one request id ties them together across threads."""
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.parallel.mesh import disagg_meshes
    from quorum_tpu.engine.engine import InferenceEngine

    RECORDER.reset()
    pm, dm = disagg_meshes(1, 1)
    tiny = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
    eng = InferenceEngine(tiny, dm, prefill_mesh=pm, decode_chunk=4,
                          n_slots=2, prefill_chunk=16, seed=3)
    res = eng.generate([3, 4, 5], max_new_tokens=6, sampler=_greedy())
    assert len(res.token_ids) == 6
    events = [e for e in RECORDER.snapshot()
              if e.get("engine") == eng._tag]
    handoffs = [e for e in events if e["kind"] == "handoff"]
    registers = [e for e in events if e["kind"] == "register"]
    assert handoffs and registers
    assert all(e["loop"] == "prefill" for e in handoffs)
    assert all(e["loop"] == "decode" for e in registers)
    rid = handoffs[0]["rid"]
    assert any(e["rid"] == rid for e in registers)
    reaps = [e for e in events if e["kind"] == "reap"]
    assert any(rid in e.get("rids", ()) for e in reaps)
    eng.shutdown()


# ---- server endpoints ------------------------------------------------------


def _config():
    return {
        "settings": {"timeout": 60},
        "primary_backends": [
            {"name": "T", "url": "tpu://llama-tiny?seed=3&slots=2",
             "model": "t"},
        ],
    }


async def test_timeline_endpoint_json_and_perfetto():
    async with make_client(_config()) as client:
        r = await client.post(
            "/chat/completions",
            json={"model": "t", "max_tokens": 4,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"Authorization": "Bearer x"})
        assert r.status_code == 200
        body = (await client.get("/debug/engine/timeline")).json()
        assert body["clock"] == "perf_counter"
        assert any(e["kind"] == "reap" for e in body["events"])
        # per-engine per-family device-time stats ride the JSON form
        assert "T" in body["device_time"]
        assert body["device_time"]["T"], body["device_time"]
        assert set(body["slo"]) == {"interactive", "batch"}
        perf = (await client.get(
            "/v1/debug/engine/timeline?format=perfetto")).json()
        assert any(e.get("ph") == "X" for e in perf["traceEvents"])
        bad = await client.get("/debug/engine/timeline?format=nope")
        assert bad.status_code == 400


async def test_profile_endpoint_single_flight_409():
    async with make_client(_config()) as client:
        skipped0 = obs.PROFILE_SKIPPED.value
        assert obs._profile_lock.acquire(blocking=False)
        try:
            busy = await client.post("/debug/profile?seconds=0.01")
        finally:
            obs._profile_lock.release()
        assert busy.status_code == 409
        assert busy.json()["error"]["type"] == "conflict_error"
        assert "retry-after" in {k.lower() for k in busy.headers}
        assert obs.PROFILE_SKIPPED.value == skipped0 + 1
        bad = await client.post("/debug/profile?seconds=oops")
        assert bad.status_code == 400


def test_maybe_profile_skip_is_visible(monkeypatch, tmp_path):
    """The PR's satellite fix: a concurrent-profile skip used to be a
    silent DEBUG line; now it ticks the counter and records an event."""
    monkeypatch.setenv("QUORUM_TPU_PROFILE_DIR", str(tmp_path))
    RECORDER.reset()
    skipped0 = obs.PROFILE_SKIPPED.value
    assert obs._profile_lock.acquire(blocking=False)
    try:
        with obs.maybe_profile("req-skip-test"):
            pass
    finally:
        obs._profile_lock.release()
    assert obs.PROFILE_SKIPPED.value == skipped0 + 1
    assert any(e["kind"] == "profile-skipped"
               and e.get("rid") == "req-skip-test"
               for e in RECORDER.snapshot())


def test_health_carries_slo_block_and_burn_shedding(monkeypatch):
    # burning_class flips /health to degraded and /ready to 503 only when
    # the opt-in threshold is set AND a class is burning. A FRESH tracker
    # is swapped in: the process-global one accumulates scores from every
    # other suite test's requests, which would dilute the burn rate.
    monkeypatch.setenv("QUORUM_TPU_SLO_READY_BURN", "0.5")
    tracker = slo.SloTracker()
    monkeypatch.setattr(slo, "SLO", tracker)
    assert slo.burning_class() is None
    for _ in range(4):
        tracker.record("interactive", "ttft", False)
    assert slo.burning_class() == "interactive"
    tracker.reset()
    assert slo.burning_class() is None


async def test_health_slo_block_present_with_engine_backend():
    async with make_client(_config()) as client:
        body = (await client.get("/health")).json()
        assert "slo" in body
        assert set(body["slo"]) == {"interactive", "batch"}


@pytest.mark.slow
async def test_slo_counters_score_served_requests():
    """End to end: a served chat request is classified from its timeout
    headroom and scored at teardown."""
    async with make_client(_config()) as client:
        good0 = obs.SLO_GOOD.value_of(**{"class": "interactive",
                                         "stage": "deadline"})
        r = await client.post(
            "/chat/completions",
            json={"model": "t", "max_tokens": 4, "timeout": 20,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"Authorization": "Bearer x"})
        assert r.status_code == 200
        assert obs.SLO_GOOD.value_of(**{"class": "interactive",
                                        "stage": "deadline"}) == good0 + 1
