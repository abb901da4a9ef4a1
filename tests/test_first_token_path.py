"""The first token's path and the engine's turn, as the program measures
them (ISSUE 25): spans with parents from the root ``request`` down to the
engine's queue-wait and prefill, the instants from submit to the wire and
the four stage histograms, what a chunked ``prefill`` span waited for, the
prefill token counters, the scheduler turn's phases on the host clock and on
the profiler's, the scopes that name a device operation's layer part, and
program names as the interface the benchmark's trace reduction reads."""

import glob
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from tests.conftest import make_client

from quorum_tpu import observability as obs
from quorum_tpu.analysis import budget, hlo_names
from quorum_tpu.engine.engine import (TURN_PHASES, InferenceEngine,
                                      _SegmentRoom, prefill_bucket)
from quorum_tpu.models import transformer as T
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.observability import RequestTrace, use_trace

TINY = resolve_spec("llama-tiny")  # max_seq 128
AUTH = {"Authorization": "Bearer x"}
STAGES = ("submit", "queue_wait", "prefill", "backend", "strategy", "wire")
HISTOGRAMS = {"queue_wait": obs.QUEUE_WAIT, "prefill": obs.FIRST_TOKEN_PREFILL,
              "backend": obs.FIRST_TOKEN_BACKEND,
              "strategy": obs.FIRST_TOKEN_STRATEGY,
              "wire": obs.FIRST_TOKEN_WIRE}


def _config(*urls):
    return {
        "settings": {"timeout": 60},
        "primary_backends": [{"name": f"LLM{i + 1}", "url": u, "model": "t"}
                             for i, u in enumerate(urls)],
        "iterations": {"aggregation": {"strategy": "concatenate"}},
        "strategy": {"concatenate": {"separator": "\n---\n"},
                     "aggregate": {"source_backends": "all",
                                   "aggregator_backend": ""}},
    }


QUORUM = _config(*(f"tpu://llama-tiny?seed=3&slots=2&members=3&member={m}"
                   for m in range(3)))
SINGLE = _config("tpu://llama-tiny?seed=5&slots=2")
CHUNKED = _config("tpu://llama-tiny?seed=6&slots=2&prefill_chunk=16")


def _counts():
    return {name: sum(s["count"] for s in h.snapshot().values())
            for name, h in HISTOGRAMS.items()}


async def _streamed_trace(config, content, max_tokens=12):
    """One streamed chat request; its trace, and by how much each stage
    histogram's count rose."""
    async with make_client(config) as client:
        before = _counts()
        resp = await client.post(
            "/v1/chat/completions", headers=AUTH,
            json={"model": "t", "stream": True, "max_tokens": max_tokens,
                  "messages": [{"role": "user", "content": content}]})
        assert resp.status_code == 200 and "data: [DONE]" in resp.text
        after = _counts()
        got = await client.get(f"/debug/traces/{resp.headers['x-request-id']}")
        assert got.status_code == 200
    return got.json(), {k: after[k] - before[k] for k in after}


# ---- (a) spans form a tree --------------------------------------------------


async def test_streamed_quorum_trace_is_a_tree_under_request():
    trace, _ = await _streamed_trace(QUORUM, "tree of spans")
    spans = {s["id"]: s for s in trace["spans"]}
    assert len(spans) == len(trace["spans"])  # ids are unique in the trace
    roots = [s for s in spans.values() if s["parent"] is None]
    assert [s["name"] for s in roots] == ["request"]
    for s in spans.values():  # every span reaches the root by parent
        hops = 0
        while s["parent"] is not None:
            s, hops = spans[s["parent"]], hops + 1
            assert hops <= len(spans)
        assert s is roots[0]
    hops = [s for s in spans.values() if s["name"] == "fanout-stream"]
    assert len(hops) == 3
    for hop in hops:  # each member's engine spans hang under its own hop
        under = [s["name"] for s in spans.values() if s["parent"] == hop["id"]]
        assert under.count("queue-wait") == 1 and under.count("prefill") == 1
        assert "decode" in under
    members = trace["first_token_path"]["members"]
    assert sorted(m["member"] for m in members) == [0, 1, 2]
    assert sorted(m["span"] for m in members) == sorted(h["id"] for h in hops)


def test_span_parent_defaults_to_the_innermost_open_span():
    trace = RequestTrace("req-parents")
    root = trace.open_root()
    assert (root.id, root.parent, trace.root_id) == (0, None, 0)
    with use_trace(trace):
        with trace.span("fanout") as outer:
            assert trace.context_parent() == outer.id
            with trace.span("fanout-call") as inner:
                leaf = trace.add_span("backend-generate", trace.now())
            sibling = trace.add_span("aggregate", trace.now())
        late = trace.add_span("sse-flush", trace.now())
        assert trace.context_parent() == root.id
    assert (outer.parent, inner.parent, leaf.parent) == (root.id, outer.id,
                                                         inner.id)
    assert sibling.parent == outer.id and late.parent == root.id
    # an explicit parent wins; a thread that inherited no context gets the root
    assert trace.add_span("decode", 0.0, 0.1, parent=inner.id).parent == inner.id
    seen = []
    worker = threading.Thread(
        target=lambda: seen.append(trace.add_span("queue-wait", 0.0, 0.1)))
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive() and seen[0].parent == root.id
    exported = trace.to_dict()["spans"]
    assert sorted(s["id"] for s in exported) == list(range(len(exported)))


def test_summary_line_phases_leave_the_root_out():
    trace = RequestTrace("req-phases")
    trace.open_root()
    with trace.span("fanout"):
        pass
    trace.finish(status=200)
    assert set(trace.phases) == {"fanout"}
    assert trace.total >= trace.phases["fanout"]
    trace.log("complete", status=200)  # must not raise


def test_first_token_path_without_stamps_exports_nulls():
    trace = RequestTrace("req-empty")
    path = trace.to_dict()["first_token_path"]
    assert path == {"members": [], "strategy_first_delta_s": None,
                    "wire_first_content_s": None, "stages_ms": None}
    trace.mark_strategy_delta()  # an HTTP upstream: no member ever stamps
    trace.mark_flush(1)
    path = trace.first_token_path()
    assert path["strategy_first_delta_s"] <= path["wire_first_content_s"]
    assert path["stages_ms"] is None


# ---- (b) the stages add up to the wire's first content -----------------------


@pytest.mark.parametrize("config,content,members,chunked", [
    (SINGLE, "single-shot admission", 1, False),
    (QUORUM, "coalesced admission", 3, False),
    (CHUNKED, "chunked admission: " + "a long prompt " * 6, 1, True)],
    ids=["single-shot", "coalesced", "chunked"])
async def test_stages_sum_to_the_wires_first_content(config, content, members,
                                                     chunked):
    trace, rose = await _streamed_trace(config, content)
    path = trace["first_token_path"]
    assert len(path["members"]) == members
    for m in path["members"]:
        assert (0 <= m["submit_s"] <= m["admit_s"] <= m["engine_first_token_s"]
                <= m["backend_first_delta_s"])
    first = min(m["backend_first_delta_s"] for m in path["members"])
    assert first <= path["strategy_first_delta_s"] <= path[
        "wire_first_content_s"]
    assert path["wire_first_content_s"] * 1000 == pytest.approx(
        trace["ttft_ms"], abs=0.002)
    stages = path["stages_ms"]
    assert tuple(stages) == STAGES and all(v >= 0 for v in stages.values())
    assert sum(stages.values()) == pytest.approx(trace["ttft_ms"], abs=1.0)
    prefill = [s for s in trace["spans"] if s["name"] == "prefill"]
    assert all(bool(s["meta"].get("chunked")) is chunked for s in prefill)
    # per engine submission: queue wait, prefill, backend; per request: the rest
    assert rose == {"queue_wait": members, "prefill": members,
                    "backend": members, "strategy": 1, "wire": 1}


# ---- (c) the chunked prefill span says what it waited for --------------------


def _traced_generate(eng, trace, prompt, n):
    with use_trace(trace):
        req = eng.submit(prompt, max_new_tokens=n)
    return list(eng.stream_results(req))


def test_chunked_prefill_span_counts_its_decode_wait():
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=16)
    # A segment a turn, as before either pace of the segment rule is timed
    # (ISSUE 27): the span then holds the other row's decode chunks.
    eng._segment_room = lambda: _SegmentRoom(0.0, 0.0)
    try:
        long_prompt = [(3 + 11 * i) % 500 for i in range(100)]
        eng.generate([9] + long_prompt[1:], max_new_tokens=2)  # compile
        eng.generate([5, 6, 7], max_new_tokens=2)
        m0 = eng.metrics()
        resident = threading.Thread(
            target=lambda: eng.generate([5, 6, 7], max_new_tokens=110))
        resident.start()
        while not any(eng._slots):
            time.sleep(0.001)
        trace = RequestTrace("req-chunked")
        _traced_generate(eng, trace, long_prompt, 2)
        resident.join(timeout=120)
        assert not resident.is_alive()
        m1 = eng.metrics()
    finally:
        eng.shutdown()
    (span,) = [s for s in trace.spans if s.name == "prefill"]
    assert span.meta["chunked"] is True
    assert span.meta["segments"] == 7  # 100 tokens in segments of 16
    assert span.meta["turns"] >= span.meta["segments"]
    # the raw value, unclamped: decode chunks' time inside the span
    wait = span.meta["decode_wait_ms"]
    assert 0 < wait <= span.duration * 1000
    span_s = m1["prefill_span_seconds_total"] - m0["prefill_span_seconds_total"]
    wait_s = (m1["prefill_decode_wait_seconds_total"]
              - m0["prefill_decode_wait_seconds_total"])
    assert span_s == pytest.approx(span.duration, abs=1e-4)
    assert wait_s == pytest.approx(wait / 1000, abs=1e-4)


# ---- (d) prefill tokens asked for against tokens computed --------------------


def _count_prefill_programs(eng):
    """Every prefill program the engine dispatches from now on, as
    (family, bucket): the ``engine.dispatch`` annotation's arguments."""
    seen, dispatch = [], eng._prefill_dispatch

    def counting(family, bucket, tokens, rows=1):
        seen.append((family, bucket))
        return dispatch(family, bucket, tokens, rows)

    eng._prefill_dispatch = counting
    return seen


def test_prefill_counters_equal_the_padding_of_a_fixed_request_list():
    prompts = [[1 + i] * n for i, n in enumerate((5, 16, 21, 40, 100))]
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=32)
    programs = _count_prefill_programs(eng)
    try:
        m0 = eng.metrics()
        for p in prompts:
            eng.generate(p, max_new_tokens=2)
        m1 = eng.metrics()
    finally:
        eng.shutdown()
    # 5, 16, 21 admit single-shot in buckets 16, 16, 32; 40 and 100 go in
    # segments of 32 with tails of 8 and 4, each padded to the bucket 16
    padded = 16 + 16 + 32 + (32 + 16) + (32 * 3 + 16)
    assert padded == sum(
        prefill_bucket(n, 128) if n <= 32 else
        32 * (n // 32) + prefill_bucket(n % 32, 32) for n in map(len, prompts))
    rose = {k: m1[k] - m0[k] for k in (
        "prefill_tokens_total", "prefill_padded_tokens_total")}
    assert rose == {"prefill_tokens_total": 5 + 16 + 21 + 40 + 100,
                    "prefill_padded_tokens_total": padded}
    assert [family for family, _ in programs] == (
        ["single_shot"] * 3 + ["seg"] * (2 + 4))


def test_member_admit_counts_every_member_row_it_computes():
    eng = InferenceEngine(TINY, seed=0, members=3, decode_chunk=4, n_slots=2)
    programs = _count_prefill_programs(eng)
    try:
        m0 = eng.metrics()
        req = eng.submit([4] * 20, max_new_tokens=2, member=1)
        list(eng.stream_results(req))
        m1 = eng.metrics()
    finally:
        eng.shutdown()
    # one member asked for 20 tokens; the vmapped program computes 3 x 32
    assert m1["prefill_tokens_total"] - m0["prefill_tokens_total"] == 20
    assert (m1["prefill_padded_tokens_total"]
            - m0["prefill_padded_tokens_total"]) == 3 * 32
    assert len(programs) == 1 and programs[0][1] == 32


# ---- (e) the turn's phases account for the turn -------------------------------


def test_turn_phases_sum_to_the_loops_wall_time():
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2, prefill_chunk=32)
    try:
        eng.generate([1] * 40, max_new_tokens=4)  # compile outside the window
        m0, t0 = eng.metrics(), time.perf_counter()
        for i in range(3):
            eng.generate([2 + i] * 40, max_new_tokens=40)
            time.sleep(0.05)  # the loop idles between requests
        m1, t1 = eng.metrics(), time.perf_counter()
    finally:
        eng.shutdown()
    rose = {p: m1[f"turn_{p}_seconds_total"] - m0[f"turn_{p}_seconds_total"]
            for p in TURN_PHASES}
    assert all(v >= 0 for v in rose.values())
    for phase in ("idle", "admit", "fill", "reap_block", "emit"):
        assert rose[phase] > 0, phase
    assert sum(rose.values()) == pytest.approx(t1 - t0, rel=0.02)


def test_compile_seconds_leave_the_phase_that_met_the_new_shape():
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2)
    try:
        t0 = time.perf_counter()
        eng.generate([1, 2, 3], max_new_tokens=4)  # compiles admit and chunk
        wall = time.perf_counter() - t0
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert 0 < m["turn_compile_seconds_total"] < wall


# ---- (f) the profile holds the phases and stops in seconds --------------------


def test_profile_holds_engine_phases_and_returns_soon(tmp_path):
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2)
    stop = threading.Event()

    def decode():
        while not stop.is_set():
            eng.generate([1, 2, 3], max_new_tokens=60)

    try:
        eng.generate([1, 2, 3], max_new_tokens=8)  # compile before the trace
        worker = threading.Thread(target=decode)
        worker.start()
        t0 = time.perf_counter()
        out = obs.profile_process(0.5, str(tmp_path))
        took = time.perf_counter() - t0
    finally:
        stop.set()
        worker.join(timeout=120)
        eng.shutdown()
    # The fault this guards (a stop that waits on the Python tracer) cost
    # 48-109 s; the bound sits well under it and well over what five
    # neighbouring test workers' load adds to a 0.5 s profile (5-8 s seen).
    assert 0.5 <= took < 20.0
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = [p for p in data.planes if p.name.startswith("/host:")]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert {"engine.fill", "engine.reap_block", "engine.emit"} <= names


# ---- (h) scopes name the parts of a step and add no operation -----------------

# the scopes of a spec without a layer pattern and without a mixer
# (tests/test_patterned.py has the patterned family's, tests/test_falcon_h1.py
# the mixer's)
SCOPES = tuple(p for p in hlo_names.PARTS
               if p not in hlo_names.PATTERNED + hlo_names.LATENT
               + hlo_names.MIXER + hlo_names.SHORTCONV)


def _lowered(program):
    params = jax.eval_shape(lambda: init_params(TINY, 0))
    ck, cv = jax.eval_shape(lambda: T.init_cache(TINY, 2))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def greedy(logits, live, carry):
        return jnp.argmax(logits, -1).astype(jnp.int32), carry, ()

    if program == "decode":
        def fn(p, tok, lens, live, bud, eos, k, v):
            return T.decode_chunk(p, TINY, 4, tok, lens, live, bud, eos, k, v,
                                  greedy, ())[:3]
        args = (params, i32(2), i32(2), jax.ShapeDtypeStruct((2,), jnp.bool_),
                i32(2), i32(2), ck, cv)
    elif program == "prefill":
        def fn(p, tok, lens, k, v):
            return T.prefill(p, TINY, tok, lens, k, v, slot=jnp.int32(1))
        args = (params, i32(1, 32), i32(1), ck, cv)
    else:
        def fn(p, tok, k, v):
            return T.prefill_segment(p, TINY, tok, jnp.int32(16),
                                     jnp.int32(16), k, v, jnp.int32(1),
                                     history=32)
        args = (params, i32(1, 16), ck, cv)
    return jax.jit(fn).lower(*args)


# HLO operations in the lowered text, counted on the parent commit (36af30b,
# no scope anywhere) with this same function: scopes are metadata. ``decode``
# was 442 there; PR 31's layer scan carries the cache and writes it with a
# gather and a scatter a side (index clamping included), which lowers to 492.
# PR 35 stores a cache side positions-major with the heads flattened: the
# decode step transposes its new rows into lines and the CPU path's read
# views the window by heads (496); a block's write and the window's read of
# the prefill-side bodies need two operations fewer (356, 324).
@pytest.mark.parametrize("program,parent_ops,absent", [
    ("decode", 496, ()),
    ("prefill", 356, ("sample",)),          # the engine's admit samples
    ("segment", 324, ("sample", "lm_head"))],  # a segment returns the cache
    ids=["decode", "prefill", "segment"])
def test_lowered_program_holds_the_scopes_and_the_parents_op_count(
        program, parent_ops, absent):
    text = _lowered(program).as_text(debug_info=True)
    ops = sum(1 for line in text.splitlines()
              if re.search(r"\b(stablehlo|chlo)\.\w+", line.split("loc(")[0]))
    assert ops == parent_ops
    for scope in SCOPES:
        found = re.search(r'[/"]' + re.escape(scope) + r'[/"]', text)
        assert bool(found) is (scope not in absent), scope


def test_hlo_names_reads_an_operations_layer_part_off_the_optimized_text():
    # what a device trace cannot say: which scope a numbered operation is of
    table = hlo_names.instructions(_lowered("decode").compile().as_text())
    parts = {hlo_names.part_of(op_name) for _, op_name in table.values()}
    assert set(SCOPES) - {"embed"} <= parts  # XLA:CPU fuses the embedding away
    assert not any(name.startswith("param_") for name in table)  # fused bodies
    name, (opcode, op_name) = next(
        (k, v) for k, v in table.items() if "/mlp/" in v[1])
    assert hlo_names.part_of(op_name) == "mlp" and opcode.islower()
    assert hlo_names.part_of("jit(chunk)/while/body/squeeze") is None
    assert hlo_names.part_of("jit(admit)/attn.core/mlp/dot_general") == "mlp"


# ---- (i) program names are an interface ---------------------------------------

FAMILY_CLASS = {"plain": "decode", "single_shot": "prefill",
                "members": "prefill", "seg": "prefill", "mseg": "prefill",
                "register": "register"}


@pytest.mark.parametrize("members", [1, 3], ids=["plain", "stacked"])
def test_jitted_program_names_class_as_the_engines_family_says(members):
    """``benchmarks/trace_reduce.program_class`` sorts a profile's programs
    by substrings of their jit names; the engine's own family of each
    program says what the class has to be."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from trace_reduce import program_class

    eng = InferenceEngine(TINY, seed=0, members=members, decode_chunk=4,
                          n_slots=2, prefill_chunk=16)
    try:
        for prompt in ([1, 2, 3], [(7 + 13 * i) % 500 for i in range(40)]):
            req = eng.submit(prompt, max_new_tokens=6, member=members - 1)
            list(eng.stream_results(req))
        programs = [(budget.classify_admit_key(k), fn)
                    for k, fn in eng._admit_cache.items()]
        programs += [(budget.classify_decode_key(k), fn)
                     for k, fn in eng._decode_cache.items()]
    finally:
        eng.shutdown()
    assert {f for f, _ in programs} == (
        {"plain", "members", "mseg", "register"} if members > 1
        else {"plain", "single_shot", "seg", "register"})
    for family, fn in programs:
        assert program_class("jit_" + fn.__name__) == FAMILY_CLASS[family], (
            family, fn.__name__)
