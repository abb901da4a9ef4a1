"""Native quorum serving (quorum_tpu/quorum/, docs/quorum.md).

Fast tier: fanout-knob units, leg failover/4xx-relay units over stub
replicas, and the router-tier quorum end-to-end over jax-free fake
replicas — full fan-out, member-kill degradation (with and without a
spare), token-exact member resume, the streaming chunk contract, and the
single-cell server's explicit quorum rejection. Slow tier: shared-prefix
member dedup on REAL stacked engines — outputs pinned token-for-token
against the M-prefill path (dense + paged, greedy + sampled) with the
(M-1)·n_prompt savings counted, plus the config-time composition
rejections and the engine-cache key split.
"""

import time
from types import SimpleNamespace

import httpx
import pytest

from quorum_tpu import oai
from quorum_tpu.backends.base import BackendError
from quorum_tpu.observability import (
    QUORUM_DEDUP_TOKENS,
    QUORUM_DEGRADED,
    QUORUM_REQUESTS,
)
from quorum_tpu.quorum import fanout
from tests.test_router import _Cluster, _collect, _conv

slow = pytest.mark.slow

SEP = "\n\n---\n\n"  # RouterConfig.quorum_separator default
AUTH = {"Authorization": "Bearer sk-test"}


# ---- knob validation units --------------------------------------------------


def test_validate_quorum_shapes():
    ok = [{}, {"quorum": None}, {"quorum": 1}, {"quorum": 3},
          {"quorum": fanout.MAX_QUORUM}, {"quorum": 3, "n": 1}]
    for body in ok:
        assert fanout.validate_quorum(body) is None, body
    bad = [{"quorum": 0}, {"quorum": fanout.MAX_QUORUM + 1},
           {"quorum": True}, {"quorum": "3"}, {"quorum": 2.5},
           {"quorum": 2, "n": 2}, {"quorum": 2, "logprobs": 3},
           {"quorum": 2, "resume_tokens": [1]},
           {"quorum": 2, "stream_token_ids": True}]
    for body in bad:
        assert fanout.validate_quorum(body) is not None, body
    # the shared request validator carries the same checks (server + router)
    assert oai.validate_request_body({"quorum": 3}) is None
    assert oai.validate_request_body({"quorum": 99}) is not None
    assert oai.validate_request_body({"quorum": 3, "n": 2}) is not None


def test_pop_quorum_strips_the_knob():
    body = {"quorum": 3, "messages": []}
    assert fanout.pop_quorum(body) == 3
    assert "quorum" not in body  # never forwarded: would recurse at replicas
    assert fanout.pop_quorum({}) == 1
    assert fanout.pop_quorum({"quorum": None}) == 1
    assert fanout.pop_quorum({"quorum": True}) == 1


def test_choose_members_splits_ring_order():
    assert fanout.choose_members(["a", "b", "c", "d"], 2) == \
        (["a", "b"], ["c", "d"])
    assert fanout.choose_members(["a", "b"], 3) == (["a", "b"], [])


def test_summarize_and_headers():
    legs = [fanout.QuorumLeg(index=i) for i in range(3)]
    assert fanout.summarize(3, legs) == ("failed", [])
    legs[0].ok = True
    legs[0].content = "x"
    legs[0].replica = "r0"
    legs[1].ok = True
    legs[1].content = "y"
    legs[1].replica = "r2"
    legs[2].degraded_reason = "stream_broken"
    outcome, served = fanout.summarize(3, legs)
    assert outcome == "degraded" and len(served) == 2
    h = fanout.quorum_headers(3, legs, outcome)
    assert h["X-Quorum-Members"] == "3"
    assert h["X-Quorum-Served"] == "2"
    assert h["X-Quorum-Replicas"] == "r0,r2"
    assert h["X-Quorum-Degraded"] == "stream_broken"
    legs[2].ok = True
    legs[2].content = "z"
    legs[2].degraded_reason = None
    outcome, _ = fanout.summarize(3, legs)
    assert outcome == "full"
    assert "X-Quorum-Degraded" not in fanout.quorum_headers(3, legs, outcome)


# ---- leg units over stub replicas -------------------------------------------


class _StubBreaker:
    def allow(self):
        return True

    def record_success(self):
        pass

    def record_failure(self):
        pass


def _stub_replica(name, complete):
    async def _complete(body, headers, timeout):
        return complete()

    return SimpleNamespace(
        name=name, inflight=0, requests=0, breaker=_StubBreaker(),
        backend=SimpleNamespace(complete=_complete))


def _ok_result(text):
    return SimpleNamespace(
        status_code=200,
        body={"id": "chatcmpl-1", "object": "chat.completion",
              "created": 1, "model": "m",
              "choices": [{"index": 0, "message": {
                  "role": "assistant", "content": text},
                  "finish_reason": "stop"}]},
        usage={"prompt_tokens": 2, "completion_tokens": 3,
               "total_tokens": 5})


async def test_leg_retries_5xx_on_spare_then_serves():
    def die():
        raise BackendError("boom", status_code=503)

    replicas = {"a": _stub_replica("a", die),
                "b": _stub_replica("b", lambda: _ok_result("B"))}
    body, status, hdrs = await fanout.quorum_complete(
        replicas, ["a", "b"], 1, {"messages": []}, {},
        time.monotonic() + 5, "rid-1", SEP)
    assert status == 200
    assert body["choices"][0]["message"]["content"] == "B"
    assert body["quorum"] == {"members": 1, "served": 1,
                              "replicas": ["b"], "degraded": []}
    assert hdrs["X-Quorum-Replicas"] == "b"


async def test_all_4xx_quorum_relays_the_client_error():
    """An all-4xx quorum is the CLIENT's error: the real upstream body and
    status come back, not a 502 proxy_error wrapper."""
    err = {"error": {"message": "bad knob", "type": "invalid_request_error"}}

    def reject():
        raise BackendError("bad knob", status_code=422, body=err)

    replicas = {n: _stub_replica(n, reject) for n in ("a", "b")}
    before = QUORUM_REQUESTS.value_of(outcome="failed")
    body, status, _ = await fanout.quorum_complete(
        replicas, ["a", "b"], 2, {"messages": []}, {},
        time.monotonic() + 5, "rid-2", SEP)
    assert (status, body) == (422, err)
    assert QUORUM_REQUESTS.value_of(outcome="failed") == before + 1


async def test_empty_member_drops_as_no_content():
    replicas = {"a": _stub_replica("a", lambda: _ok_result("")),
                "b": _stub_replica("b", lambda: _ok_result("B"))}
    before = QUORUM_DEGRADED.value_of(reason="no_content")
    body, status, hdrs = await fanout.quorum_complete(
        replicas, ["a", "b"], 2, {"messages": []}, {},
        time.monotonic() + 5, "rid-3", SEP)
    assert status == 200
    assert body["choices"][0]["message"]["content"] == "B"
    assert body["quorum"]["degraded"] == [
        {"member": 0, "reason": "no_content"}]
    assert hdrs["X-Quorum-Degraded"] == "no_content"
    assert QUORUM_DEGRADED.value_of(reason="no_content") == before + 1


# ---- router e2e over fake replicas ------------------------------------------


async def test_quorum_complete_full_over_three_replicas():
    async with _Cluster(3) as c:
        single = await c.chat(_conv(0))
        assert single.status_code == 200
        t = single.json()["choices"][0]["message"]["content"]
        u = single.json()["usage"]

        before = QUORUM_REQUESTS.value_of(outcome="full")
        r = await c.chat(_conv(0), quorum=3)
        assert r.status_code == 200, r.text
        assert r.headers["x-quorum-members"] == "3"
        assert r.headers["x-quorum-served"] == "3"
        assert "x-quorum-degraded" not in r.headers
        served = r.headers["x-quorum-replicas"].split(",")
        assert sorted(served) == ["r0", "r1", "r2"]  # distinct cells
        data = r.json()
        # every member runs the same scripted prompt → identical answers,
        # combined in member order with the configured separator
        assert data["choices"][0]["message"]["content"] == SEP.join([t] * 3)
        assert data["quorum"]["members"] == 3
        assert data["quorum"]["served"] == 3
        assert data["quorum"]["degraded"] == []
        assert sorted(data["quorum"]["replicas"]) == ["r0", "r1", "r2"]
        assert data["usage"]["completion_tokens"] == \
            3 * u["completion_tokens"]
        assert QUORUM_REQUESTS.value_of(outcome="full") == before + 1
        # the knob never reached a replica (it would recurse the fan-out)
        assert all("quorum" not in call
                   for st in c.states for call in st.seen_bodies)


async def test_quorum_member_kill_with_spare_stays_full():
    async with _Cluster(4) as c:
        base = await c.chat(_conv(1), quorum=3)
        assert base.status_code == 200
        assigned = base.headers["x-quorum-replicas"].split(",")
        spare = ({"r0", "r1", "r2", "r3"} - set(assigned)).pop()
        victim = assigned[0]
        c.states[int(victim[1:])].shedding = True  # every request now 503s

        before = QUORUM_REQUESTS.value_of(outcome="full")
        r = await c.chat(_conv(1), quorum=3)
        assert r.status_code == 200, r.text
        assert r.headers["x-quorum-served"] == "3"  # spare covered the kill
        assert "x-quorum-degraded" not in r.headers
        now_served = r.headers["x-quorum-replicas"].split(",")
        assert victim not in now_served and spare in now_served
        assert r.json()["choices"][0]["message"]["content"] == \
            base.json()["choices"][0]["message"]["content"]
        assert QUORUM_REQUESTS.value_of(outcome="full") == before + 1


async def test_quorum_member_kill_without_spare_degrades():
    async with _Cluster(3) as c:
        single = await c.chat(_conv(2))
        t = single.json()["choices"][0]["message"]["content"]
        c.states[0].shedding = True  # one member down, no spare exists

        d_before = QUORUM_DEGRADED.value_of(reason="member_failed")
        o_before = QUORUM_REQUESTS.value_of(outcome="degraded")
        r = await c.chat(_conv(2), quorum=3)
        assert r.status_code == 200, r.text  # served, never failed
        assert r.headers["x-quorum-served"] == "2"
        assert r.headers["x-quorum-degraded"] == "member_failed"
        data = r.json()
        assert data["choices"][0]["message"]["content"] == SEP.join([t] * 2)
        assert [d["reason"] for d in data["quorum"]["degraded"]] == \
            ["member_failed"]
        assert QUORUM_DEGRADED.value_of(reason="member_failed") \
            == d_before + 1
        assert QUORUM_REQUESTS.value_of(outcome="degraded") == o_before + 1


async def test_quorum_all_members_dead_fails_with_502():
    async with _Cluster(3) as c:
        for srv in c.servers:
            srv.close()
            await srv.wait_closed()
        before = QUORUM_REQUESTS.value_of(outcome="failed")
        r = await c.chat(_conv(3), quorum=3)
        assert r.status_code == 502
        assert "quorum failed" in r.json()["error"]["message"]
        assert r.headers["x-quorum-served"] == "0"
        assert QUORUM_REQUESTS.value_of(outcome="failed") == before + 1


async def test_quorum_router_validation_and_passthrough():
    async with _Cluster(2) as c:
        for bad in ({"quorum": 99}, {"quorum": 3, "n": 2},
                    {"quorum": 3, "stream_token_ids": True}):
            r = await c.chat(_conv(4), **bad)
            assert r.status_code == 400, bad
            assert r.json()["error"]["type"] == "invalid_request_error"
        # quorum=1 is a no-op: the plain single-replica path, knob stripped
        r = await c.chat(_conv(4), quorum=1)
        assert r.status_code == 200
        assert "x-routed-to" in r.headers
        assert "x-quorum-members" not in r.headers
        assert "quorum" not in r.json()


# ---- router e2e: streaming contract -----------------------------------------


def _by_id(events, id_):
    return "".join((ch.get("delta") or {}).get("content") or ""
                   for e in events if e.get("id") == id_
                   for ch in e.get("choices") or [])


def _final_events(events):
    return [e for e in events if e.get("id") == oai.PARALLEL_FINAL_ID]


async def test_quorum_stream_contract_full():
    async with _Cluster(3) as c:
        plain = {"model": "m", "stream": True, "messages": _conv(5)}
        base_events, _ = await _collect(c, plain)
        t = "".join((ch.get("delta") or {}).get("content") or ""
                    for e in base_events for ch in e.get("choices") or [])
        assert t

        before = QUORUM_REQUESTS.value_of(outcome="full")
        events, headers = await _collect(c, {**plain, "quorum": 3})
        assert headers["x-quorum-members"] == "3"
        assert len(headers["x-quorum-replicas"].split(",")) == 3
        # parallel-proxy chunk contract: one role chunk leads, member
        # deltas ride per-member ids, one combined final closes it
        assert events[0]["id"] == oai.PARALLEL_ID
        assert events[0]["choices"][0]["delta"]["role"] == "assistant"
        for i in range(3):
            assert _by_id(events, f"chatcmpl-parallel-{i}") == t
        finals = _final_events(events)
        assert len(finals) == 1 and finals[-1] is events[-1]
        assert finals[0]["choices"][0]["finish_reason"] == "stop"
        assert finals[0]["choices"][0]["delta"]["content"] == \
            SEP.join([t] * 3)
        assert not any(e.get("id") == "error" for e in events)
        # router-internal resume metadata never reaches the client
        assert not any("qt_tokens" in e or "qt_error" in e for e in events)
        assert QUORUM_REQUESTS.value_of(outcome="full") == before + 1


async def test_quorum_stream_suppress_individual_responses():
    async with _Cluster(3) as c:
        events, _ = await _collect(c, {
            "model": "m", "stream": True, "messages": _conv(6),
            "quorum": 3, "suppress_individual_responses": True})
        ids = {e.get("id") for e in events}
        assert ids == {oai.PARALLEL_ID, oai.PARALLEL_FINAL_ID}
        assert _final_events(events)[0]["choices"][0]["delta"]["content"]


async def test_quorum_stream_member_kill_resumes_token_exact():
    """A member killed mid-stream finishes token-exact on the spare cell:
    the combined answer is identical to the unbroken run and the quorum
    stays full — no degradation counted."""
    async with _Cluster(4) as c:
        body = {"model": "m", "stream": True, "messages": _conv(7),
                "quorum": 3}
        base_events, base_h = await _collect(c, body)
        base_final = _final_events(base_events)[0]
        assigned = base_h["x-quorum-replicas"].split(",")
        spare = ({"r0", "r1", "r2", "r3"} - set(assigned)).pop()
        victim = assigned[0]
        c.states[int(victim[1:])].abort_after = 2

        d_before = QUORUM_DEGRADED.value
        o_before = QUORUM_REQUESTS.value_of(outcome="full")
        spare_reqs = c.states[int(spare[1:])].requests
        events, _ = await _collect(c, body)
        assert not any(e.get("id") == "error" for e in events)
        assert _final_events(events)[0]["choices"][0]["delta"]["content"] \
            == base_final["choices"][0]["delta"]["content"]
        assert c.states[int(spare[1:])].requests > spare_reqs  # resume ran
        assert QUORUM_DEGRADED.value == d_before
        assert QUORUM_REQUESTS.value_of(outcome="full") == o_before + 1


async def test_quorum_stream_member_kill_without_spare_degrades():
    """With no spare left the killed member is dropped — but its already-
    delivered partial answer joins the combine, and the request never sees
    an error chunk."""
    async with _Cluster(3) as c:
        body = {"model": "m", "stream": True, "messages": _conv(8),
                "quorum": 3}
        base_events, base_h = await _collect(c, body)
        t = _by_id(base_events, "chatcmpl-parallel-0")
        victim = base_h["x-quorum-replicas"].split(",")[0]
        c.states[int(victim[1:])].abort_after = 2

        d_before = QUORUM_DEGRADED.value_of(reason="stream_broken")
        o_before = QUORUM_REQUESTS.value_of(outcome="degraded")
        events, _ = await _collect(c, body)
        assert not any(e.get("id") == "error" for e in events)
        pieces = _final_events(events)[0]["choices"][0]["delta"][
            "content"].split(SEP)
        assert len(pieces) == 3  # the partial still contributes
        assert pieces.count(t) == 2
        partial = next(p for p in pieces if p != t)
        assert partial and t.startswith(partial)
        assert QUORUM_DEGRADED.value_of(reason="stream_broken") \
            == d_before + 1
        assert QUORUM_REQUESTS.value_of(outcome="degraded") == o_before + 1


async def test_quorum_stream_all_dead_degrades_to_error_chunk():
    async with _Cluster(3) as c:
        for srv in c.servers:
            srv.close()
            await srv.wait_closed()
        before = QUORUM_REQUESTS.value_of(outcome="failed")
        events, _ = await _collect(c, {
            "model": "m", "stream": True, "messages": _conv(9), "quorum": 3})
        assert events[0]["id"] == oai.PARALLEL_ID
        errors = [e for e in events if e.get("id") == "error"]
        assert len(errors) == 1
        assert "quorum failed" in errors[0]["choices"][0]["delta"]["content"]
        assert QUORUM_REQUESTS.value_of(outcome="failed") == before + 1


# ---- single-cell server rejects the knob ------------------------------------


async def test_single_cell_server_rejects_quorum():
    from quorum_tpu.backends import FakeBackend
    from tests.conftest import make_client

    cfg = {"settings": {"timeout": 7},
           "primary_backends": [{"name": "LLM1", "url": "http://x/v1",
                                 "model": "m"}]}
    fake = FakeBackend("LLM1", text="ok")
    async with make_client(cfg, LLM1=fake) as client:
        r = await client.post(
            "/chat/completions",
            json={"model": "m", "quorum": 2,
                  "messages": [{"role": "user", "content": "q"}]},
            headers=AUTH)
        assert r.status_code == 400
        assert "router tier" in r.json()["error"]["message"]
        assert fake.calls == []  # rejected before any backend dispatch
        # quorum=1 is the no-op spelling everywhere
        r = await client.post(
            "/chat/completions",
            json={"model": "m", "quorum": 1,
                  "messages": [{"role": "user", "content": "q"}]},
            headers=AUTH)
        assert r.status_code == 200
        assert "quorum" not in fake.calls[0].body


# ---- shared-prefix member dedup (slow: engine-scale) ------------------------


def _fan(eng, prompt, sampler, seed=7, n=8):
    """The quorum fan-out shape: one submit per member, same prompt.
    Per-member seeds (``seed+m``) — on a shared-weights stack one seed
    would collapse every sampled stream into member 0's."""
    reqs = [eng.submit(list(prompt), max_new_tokens=n, sampler=sampler,
                       seed=seed + m, member=m)
            for m in range(eng.members)]
    return [list(eng.stream_results(r)) for r in reqs]


def _fan_until_dedup(eng, want, prompt, sampler, attempts=10, **kw):
    """Outputs must match ``want`` on EVERY attempt (dedup or fallback —
    the path taken is timing-dependent: a group only dedups when all M
    submits coalesce into one admission); returns once a dedup admission
    was actually counted."""
    for _ in range(attempts):
        before = eng.quorum_dedup_prefills
        assert _fan(eng, prompt, sampler, **kw) == want
        if eng.quorum_dedup_prefills > before:
            return
    raise AssertionError(
        f"no coalesced dedup admission in {attempts} fan-outs")


@slow
def test_dedup_dense_token_identity_and_savings():
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig

    tiny = MODEL_PRESETS["llama-tiny"]
    m = 3
    kw = dict(seed=0, members=m, decode_chunk=4, n_slots=2,
              member_seeds="shared", prefix_cache=False)
    ref = InferenceEngine(tiny, **kw)
    dd = InferenceEngine(tiny, quorum_dedup=True, **kw)
    prompt = [3, 4, 5, 6]
    greedy = SamplerConfig(temperature=0.0)
    sampled = SamplerConfig(temperature=0.8, top_p=0.9)
    try:
        obs_before = QUORUM_DEDUP_TOKENS.value
        want_g = _fan(ref, prompt, greedy)
        # shared weights + greedy: every member IS the same stream
        assert len({tuple(w) for w in want_g}) == 1
        want_s = _fan(ref, prompt, sampled)
        # shared weights + per-member PRNG: the samples usually diverge
        assert len({tuple(w) for w in want_s}) > 1

        _fan_until_dedup(dd, want_g, prompt, greedy)
        _fan_until_dedup(dd, want_s, prompt, sampled)
        # the gate: every dedup admission skipped (M-1)·n_prompt tokens
        assert dd.quorum_dedup_prefills >= 2
        assert dd.quorum_dedup_tokens == \
            dd.quorum_dedup_prefills * (m - 1) * len(prompt)
        assert QUORUM_DEDUP_TOKENS.value - obs_before \
            == dd.quorum_dedup_tokens
        assert ref.quorum_dedup_prefills == 0  # knob off → path never taken

        # partial groups fall back: a lone member admission cannot dedup
        # but stays token-for-token
        before = dd.quorum_dedup_prefills
        one = list(dd.stream_results(dd.submit(
            list(prompt), max_new_tokens=8, sampler=greedy, seed=8,
            member=1)))
        assert one == want_g[1]
        assert dd.quorum_dedup_prefills == before
        # per-member prompt edits fall back too
        other = [9, 8, 7]
        want_mixed = [
            list(ref.stream_results(ref.submit(
                list(p), max_new_tokens=8, sampler=sampled, seed=7,
                member=i)))
            for i, p in enumerate([prompt, other, prompt])]
        got_mixed = [
            list(dd.stream_results(dd.submit(
                list(p), max_new_tokens=8, sampler=sampled, seed=7,
                member=i)))
            for i, p in enumerate([prompt, other, prompt])]
        assert got_mixed == want_mixed
    finally:
        ref.shutdown()
        dd.shutdown()


@slow
def test_dedup_paged_token_identity_and_savings():
    """kv_pages=1: the broadcast rides the slot group's ONE shared page
    chain (page aliasing) — same token-for-token pin, same savings."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = resolve_spec("llama-tiny", {"max_seq": "128"})
    m = 3
    kw = dict(seed=0, members=m, decode_chunk=4, n_slots=2,
              member_seeds="shared", prefix_cache=False,
              kv_pages=True, kv_page_size=16)
    ref = InferenceEngine(spec, **kw)
    dd = InferenceEngine(spec, quorum_dedup=True, **kw)
    prompt = [(3 + 7 * i) % 500 for i in range(20)]  # spans >1 page
    greedy = SamplerConfig(temperature=0.0)
    sampled = SamplerConfig(temperature=0.8, top_p=0.9)
    try:
        want_g = _fan(ref, prompt, greedy)
        want_s = _fan(ref, prompt, sampled)
        _fan_until_dedup(dd, want_g, prompt, greedy)
        _fan_until_dedup(dd, want_s, prompt, sampled)
        assert dd.quorum_dedup_tokens == \
            dd.quorum_dedup_prefills * (m - 1) * len(prompt)
    finally:
        ref.shutdown()
        dd.shutdown()


def test_quorum_dedup_config_rejections():
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS

    tiny = MODEL_PRESETS["llama-tiny"]
    with pytest.raises(ValueError, match="unknown member_seeds"):
        InferenceEngine(tiny, members=2, member_seeds="same")
    with pytest.raises(ValueError, match="requires members>1"):
        InferenceEngine(tiny, quorum_dedup=True)
    with pytest.raises(ValueError, match="member_seeds=shared"):
        InferenceEngine(tiny, members=2, quorum_dedup=True)
    with pytest.raises(ValueError, match="kv_quant"):
        InferenceEngine(tiny, members=2, member_seeds="shared",
                        quorum_dedup=True, kv_quant="int8")


@slow
def test_dedup_engine_url_and_cache_key():
    """tpu:// knob plumbing: member_seeds=shared&quorum_dedup=1 reach the
    engine, and the shared-engine cache keys distinct/shared/dedup
    variants apart (a shared-weights stack must never be handed to a
    distinct-seeds member fan)."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec
    from quorum_tpu.engine.engine import get_engine
    from quorum_tpu.models.model_config import resolve_spec

    b = TpuBackend.from_spec(BackendSpec(
        name="Q0",
        url="tpu://llama-tiny?members=2&member=0&member_seeds=shared"
            "&quorum_dedup=1&slots=1&max_seq=64",
        model="m"))
    assert b.engine.member_seeds == "shared"
    assert b.engine.quorum_dedup is True

    spec = resolve_spec("llama-tiny", {"max_seq": "64"})
    shared = get_engine(spec, seed=401, members=2, n_slots=1,
                        member_seeds="shared")
    distinct = get_engine(spec, seed=401, members=2, n_slots=1)
    dedup = get_engine(spec, seed=401, members=2, n_slots=1,
                       member_seeds="shared", quorum_dedup=True)
    assert len({id(shared), id(distinct), id(dedup)}) == 3
