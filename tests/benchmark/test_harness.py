"""CPU tests of the benchmark's own arithmetic under ``benchmarks/``: the seed
invariants of the traffic generator, the order statistics and the token rate,
the client against the wire shapes the server sends, the trace reduction on a
small trace recorded on the chip, the cost model, every per-layer reader, the
strict reading of a result line, BENCHMARK.json against its contract, and the
ways the command stops. They check the harness, never a speed. Nothing here
imports jax in this process.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from collections import Counter

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import (BENCH, BENCH_DIR, CELLS, REPO, TRAFFIC, cell_metrics,  # noqa: E402,E501
                      child_env, load, traffic_file)

import check_line  # noqa: E402
import cost_model  # noqa: E402
import e2e  # noqa: E402
import loadgen  # noqa: E402
import published_widths  # noqa: E402
import serving  # noqa: E402
import trace_reduce  # noqa: E402
import traced  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# ---- the schedule is the file's; the seed only fills in the bytes -------------


@pytest.mark.parametrize("mix", TRAFFIC)
def test_every_seed_is_offered_the_file_s_schedule(mix):
    """Lengths, order and arrival times come from the traffic file alone, so
    two seeds run the same work at the same moments (PERF.md: with the order
    left to the seed, seeds differed by 5 % on one multiset)."""
    traffic = traffic_file(mix)
    grid = [tuple(p) for p in traffic["grid"]]
    if traffic["loop"] == "open":
        a = loadgen.open_schedule(traffic, 45.0, None)
        assert a == loadgen.open_schedule(traffic, 45.0, None)
        assert [(p, c) for _, _, p, c in a] == [
            grid[i % len(grid)] for i in range(len(a))]  # file order, cycled
        in_window = [d for d, ph, _, _ in a if ph == "window"]
        assert len(in_window) == round(traffic["rate_per_s"] * 45.0)
        assert all(0.0 <= d < 45.0 for d in in_window)
        assert [d for d, *_ in a] == sorted(d for d, *_ in a)
        other = dict(traffic, arrival_seed=traffic["arrival_seed"] + 1)
        assert [d for d, *_ in loadgen.open_schedule(other, 45.0, None)] != [
            d for d, *_ in a]
    else:
        n = len(grid)
        seq = loadgen.closed_sequence(traffic, None)
        first = [next(seq) for _ in range(n)]
        assert first == grid
        assert [next(seq) for _ in range(n)] == first  # cycled in that order


@pytest.mark.parametrize("mix", TRAFFIC)
def test_the_seed_chooses_the_prompt_bytes_and_nothing_else(mix):
    traffic = traffic_file(mix)
    runs = [loadgen.LoadRun(0, traffic, seed, 45.0) for seed in (5, 5, 2 ** 31 + 11)]
    made = [[run._new_record("window", 0.0, p, c) for p, c in traffic["grid"][:4]]
            for run in runs]
    texts = [[t for _, t in m] for m in made]
    assert texts[0] == texts[1] != texts[2]  # the same seed, the same inputs
    assert [[len(t) for t in ts] for ts in texts] == [
        [p - loadgen.TEMPLATE_TOKENS for p, _ in traffic["grid"][:4]]] * 3
    assert len({t[:6] for t in texts[0]}) == 4  # no shared prefix to reuse


def test_a_traffic_file_names_its_public_source():
    for mix in TRAFFIC:
        traffic = traffic_file(mix)
        assert len(traffic["source"]) > 40 and traffic["clip_note"]
        p, c = traffic["probe"]
        assert any(p <= gp for gp, _ in traffic["grid"]) and c >= 2


@pytest.mark.parametrize("mix", TRAFFIC)
def test_warmup_covers_every_program_the_grid_reaches(mix):
    """Buckets as the engine forms them today (powers of two from 16; a
    prompt over prefill_chunk=512 runs in 512-token segments)."""
    traffic = traffic_file(mix)

    def bucket(n: int) -> int:
        b = 16
        while b < n:
            b <<= 1
        return b

    def programs(p: int) -> set:
        if p <= 512:
            return {("admit", bucket(p))}
        out, off = set(), 0
        while off < p:
            seg = min(512, p - off)
            out.add(("seg", bucket(seg), bucket(off + seg)))
            off += seg
        return out

    def histories(p: int, c: int) -> set:
        """Decode history buckets a row passes through: from its first
        chunk (prompt + first token + 8 steps) to its last, dispatched one
        chunk ahead."""
        lo, hi, out = bucket(p + 9), bucket(p + c + 16), set()
        while lo <= hi:
            out.add(lo)
            lo <<= 1
        return out

    need = set().union(*(programs(p) for p, _ in traffic["grid"]))
    have = set().union(*(programs(p) for p, _ in traffic["warmup"]))
    assert need <= have
    assert (set().union(*(histories(p, c) for p, c in traffic["grid"]))
            <= set().union(*(histories(p, c) for p, c in traffic["warmup"])))


# ---- order statistics and the token rate ---------------------------------------


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 0.5, 2), ([1, 2, 3, 4, 5], 0.5, 3), ([5, 1, 3], 0.9, 5),
    (list(range(1, 101)), 0.9, 90), ([7], 0.9, 7), ([], 0.5, None)])
def test_percentile_is_the_nearest_rank(values, q, want):
    assert e2e.percentile(values, q) == want


def test_kth_largest():
    assert e2e.kth_largest([5, 9, 1, 7], 3) == 5
    assert e2e.kth_largest([5, 9], 3) is None


def _rec(phase, due, first, last, end, n, key="single", status=200):
    return {"phase": phase, "due": due, "sent": due + 0.001, "status": status,
            "error": "", "end": end, "first": first, "max_tokens": n,
            "prompt_tokens": 32, "rid": "r", "streams": {key: {
                "first": first, "last": last, "deltas": 3, "tokens": n,
                "finish": "length"}}}


@pytest.mark.parametrize("first,last,n,want", [
    (1.0, 3.0, 11, 5),    # tokens at 1.0, 1.2 ... 3.0; the window is [0, 2)
    (-1.0, 1.0, 11, 6),   # 0.0, 0.2 ... 1.0 lie inside
    (2.5, 3.0, 4, 0), (0.5, 0.5, 1, 1)])
def test_tokens_in_window_counts_what_arrived_inside(first, last, n, want):
    rec = _rec("tail", first, first, last, last, n)
    assert e2e.tokens_in_window([rec], 2.0) == want


def test_end_to_end_takes_window_requests_only():
    recs = [_rec("ramp", -1.0, -0.5, 0.5, 0.5, 11),
            _rec("window", 0.5, 1.0, 3.0, 3.0, 11),
            _rec("window", 1.0, 1.2, 3.2, 3.2, 21),
            _rec("tail", 2.5, 2.6, 2.9, 3.0, 4)]
    out = e2e.end_to_end(recs, 2.0, 9.0)
    assert out["ttft_p50_ms"] == pytest.approx(200.0)
    assert out["ttft_p90_ms"] == pytest.approx(500.0)
    assert out["ttft_mean_ms"] == pytest.approx(350.0)
    assert out["tpot_p50_ms"] == pytest.approx(100.0)
    assert out["setup_s"] == 9.0
    assert out["tokens_per_s"] > 0


def test_a_failed_request_counts_as_failed_and_gives_no_latency():
    bad = _rec("window", 0.1, None, None, None, 8, status=503)
    bad["streams"] = {}
    assert e2e.failed(bad)
    assert e2e.ttft_ms([bad]) == [] and e2e.tpot_ms([bad]) == []


def test_quorum_streams_are_one_sample_each_and_final_is_none():
    rec = _rec("window", 0.0, 0.2, 1.2, 1.3, 11, key="member-0")
    rec["streams"]["member-1"] = dict(rec["streams"]["member-0"], last=2.2,
                                      tokens=None)
    rec["streams"]["final"] = dict(rec["streams"]["member-0"])
    assert sorted(e2e.tpot_ms([rec])) == pytest.approx([100.0, 200.0])


def _sse_server(frames: list):
    """A local server that answers any POST with the given SSE frames."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("X-Request-Id", "req-test")
            self.end_headers()
            for frame in frames:
                data = frame if isinstance(frame, str) else json.dumps(frame)
                self.wfile.write(b"data: " + data.encode() + b"\n\n")
            self.wfile.flush()

        def log_message(self, *args):
            pass

    server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def _chunk(cid: str, content=None, finish=None, role=None) -> dict:
    delta = {}
    if role:
        delta["role"] = role
    if content:
        delta["content"] = content
    return {"id": cid, "choices": [{"index": 0, "delta": delta,
                                    "finish_reason": finish}]}


QUORUM_WIRE = [
    _chunk("chatcmpl-parallel", role="assistant"),  # opens no stream
    _chunk("chatcmpl-parallel-0", "a"), _chunk("chatcmpl-parallel-1", "b"),
    _chunk("chatcmpl-parallel-0", "c"), _chunk("chatcmpl-parallel-2", "d"),
    _chunk("chatcmpl-parallel-final", "ac\n---\nb\n---\nd", "stop"),
    "[DONE]"]
SINGLE_WIRE = [
    _chunk("chatcmpl-1", role="assistant"), _chunk("chatcmpl-1", "a"),
    _chunk("chatcmpl-1", "b"), _chunk("chatcmpl-1", finish="length"),
    {"id": "chatcmpl-1", "choices": [], "usage": {"completion_tokens": 2}},
    "[DONE]"]


# the first sampled token was the end-of-sequence id: answered, no content
EOS_WIRE = [
    _chunk("chatcmpl-1", role="assistant"), _chunk("chatcmpl-1", finish="stop"),
    {"id": "chatcmpl-1", "choices": [], "usage": {"completion_tokens": 0}},
    "[DONE]"]


@pytest.mark.parametrize("frames,keys,asked", [
    (QUORUM_WIRE, ["member-0", "member-1", "member-2"], 3 * 7),
    (SINGLE_WIRE, ["single"], 2),
    (EOS_WIRE, ["single"], 0)])
def test_client_keeps_one_stream_per_member_and_none_for_a_role_chunk(
        frames, keys, asked):
    """What the engine's token count is held against: every stream that
    delivered something, and nothing else. A role-only chunk that opened a
    stream of its own made a quorum request ask for a fourth member's
    tokens, and every run of the quorum cell incorrect (PR 23's refusal)."""
    import threading
    server = _sse_server(frames)
    try:
        rec = loadgen.Record(index=0, phase="window", due=0.0, sent=None,
                             prompt_tokens=20, max_tokens=7)
        loadgen.stream_request(server.server_address[1], "x", 7, rec, 0.0,
                               threading.Event())
    finally:
        server.shutdown()
        server.server_close()
    assert not e2e.failed(rec), rec
    assert sorted(k for k in rec["streams"] if k != "final") == keys
    assert [s for s in e2e.member_streams(rec)
            if not s["deltas"] and s["finish"] != "stop"] == []
    if not asked:
        assert e2e.ttft_ms([rec]) == [] and e2e.tpot_ms([rec]) == []
    assert sum(e2e.stream_tokens(rec, s)
               for s in e2e.member_streams(rec)) == asked


# ---- /metrics and the trace reduction -----------------------------------------


def test_parse_metrics_sums_label_sets_and_skips_buckets():
    text = ('# TYPE x counter\nquorum_tpu_engine_tokens_total{backend="A"} 5\n'
            'quorum_tpu_engine_tokens_total{backend="B"} 7\n'
            'quorum_tpu_queue_wait_seconds_bucket{le="0.1"} 3\n'
            'quorum_tpu_queue_wait_seconds_sum 1.5\n'
            'quorum_tpu_queue_wait_seconds_count 3\n')
    m = serving.parse_metrics(text)
    assert m["quorum_tpu_engine_tokens_total"] == 12
    assert m["quorum_tpu_queue_wait_seconds_sum"] == 1.5
    assert not any(k.endswith("_bucket") for k in m)


@pytest.mark.parametrize("intervals,want", [
    ([(0, 2), (1, 3)], 3), ([(0, 1), (2, 3)], 2), ([(0, 5), (1, 2)], 5),
    ([], 0)])
def test_union_of_intervals(intervals, want):
    assert trace_reduce.union_ns(intervals) == want


def test_self_time_takes_nested_operations_out_of_their_parent():
    events = [["while.1", 0.0, 10.0], ["fusion.2", 1.0, 3.0],
              ["copy.3", 5.0, 2.0], ["fusion.2", 20.0, 1.0]]
    assert trace_reduce.self_times(events) == {
        "while.1": 5.0, "fusion.2": 4.0, "copy.3": 2.0}


@pytest.mark.parametrize("name,want", [
    ("jit_chunk(8441740186099857647)", "decode"),
    ("jit_admit(452864973538555465)", "prefill"), ("jit_seg(1)", "prefill"),
    ("jit_register(9)", "register"), ("jit_zero_cache(3)", "other")])
def test_programs_are_classed_by_the_engine_names(name, want):
    assert trace_reduce.program_class(name) == want


def test_short_name_drops_the_hlo_text():
    assert trace_reduce.short_name(
        "%fusion.264 = f32[3,8,14336]{2,1,0:T(8,128)S(1)} fusion(bf16[5] %x)"
    ) == "fusion.264"


def test_reduce_on_a_synthetic_trace():
    ms = 1e6
    trace = {"t_min": 0.0, "t_max": 1000 * ms, "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_chunk(1)", 0.0, 400 * ms], ["jit_admit(2)", 500 * ms,
                                                  100 * ms],
                ["jit_chunk(1)", 600 * ms, 400 * ms]]},
            {"name": "XLA Ops", "events": [
                ["while.1", 0.0, 400 * ms], ["fusion.2", 50 * ms, 100 * ms],
                ["fusion.9", 500 * ms, 100 * ms],
                ["while.1", 600 * ms, 400 * ms],
                ["all-reduce.4", 700 * ms, 100 * ms]]}]}]}
    out = trace_reduce.reduce(trace)
    assert out["busy_s"] == pytest.approx(0.9)
    assert out["window_s"] == pytest.approx(1.0)
    assert out["programs"]["decode"] == {"count": 2.0, "seconds": 0.8}
    assert out["programs"]["prefill"]["seconds"] == pytest.approx(0.1)
    assert out["idle_gaps"][0] == ["decode_to_prefill", pytest.approx(0.1)]
    assert out["device_ops"][0] == ["while.1", pytest.approx(0.6)]
    assert out["collective_exposed_s"] == pytest.approx(0.1)
    assert trace_reduce.reduce({"t_min": 0, "t_max": 1, "planes": []}) is None
    assert out["prefill_executions"] == [[0, 1.0, pytest.approx(0.1)]]
    trace["planes"][0]["ffn_rows"] = [[60 * ms, 24], [510 * ms, 96],
                                      [520 * ms, 3], [900 * ms, 24]]
    out = trace_reduce.reduce(trace)  # the widest product inside the admit
    assert out["prefill_executions"] == [[96, 1.0, pytest.approx(0.1)]]


# Operation names as the v5e's profile gave them (PR 24's chip runs, call A:
# longprompt's 256-token segment and 6-row decode chunk in int8, the quorum's
# 64-token coalesced admit and its weight re-layout in bf16), operands
# shortened to "..." where they ran on.
@pytest.mark.parametrize("text,rows", [
    ("%fusion.250 = f32[256,14336]{1,0:T(8,128)S(1)} fusion(s8[32,4096,14336]"
     "{2,1,0:T(8,128)(4,1)} %get-tuple-element.888, s32[]{:T(128)} "
     "%get-tuple-element.850, f32[14336]{0:T(1024)S(1)} %bitcast.272, "
     "f32[256]{0:T(256)S(1)} %maximum_multiply_fusion.10, s8[1,256,4096]"
     "{2,1,0:T(8,128)(4,1)S(1)} %fusion.249), kind=kOutput, "
     "calls=%fused_computation.37.clone.clone", 256),
    ("%fusion.275 = s32[6,14336]{1,0:T(8,128)S(1)} fusion(s8[32,4096,14336]"
     "{2,1,0:T(8,128)(4,1)} %get-tuple-element.2718, s32[]{:T(128)} "
     "%get-tuple-element.2673, s8[6,1,4096]{2,0,1:T(8,128)(4,1)S(1)} "
     "%fusion.274), kind=kOutput, calls=%fused_computation.50.clone.clone",
     6),
    ("%fusion.256 = f32[3,64,14336]{2,1,0:T(8,128)S(1)} fusion(bf16[5,3,4096,"
     "14336]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.946, s32[]{:T(128)} "
     "%get-tuple-element.913, bf16[3,1,64,4096]{3,2,0,1:T(8,128)(2,1)S(1)} "
     "%get-tuple-element.872, ...), kind=kOutput, calls=%fused_computation.48",
     192),
    ("%convolution.7 = s32[512,14336]{1,0:T(8,128)} convolution(s8[512,4096]"
     "{1,0} %a, s8[4096,14336]{1,0} %w), dim_labels=bf_io->bf", 512),
    ("%fusion.9 = bf16[1,256,28672]{2,1,0} fusion(bf16[1,256,4096]{2,1,0} "
     "%x), kind=kOutput, calls=%fc", 256),
    # a quantizing pass over the product, a slice of the scales, a weight laid
    # out anew, a tuple, another width, a program's name
    ("%fusion.252 = s8[256,14336]{1,0:T(8,128)(4,1)S(1)} fusion(bf16[256,"
     "14336]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.819, f32[256]"
     "{0:T(256)S(1)} %maximum_multiply_fusion.11), kind=kLoop, "
     "calls=%fused_computation.34.clone.clone", None),
    ("%constant_dynamic-slice_fusion.33 = f32[1,1,14336]{2,1,0:T(1,128)S(1)} "
     "fusion(f32[32,1,14336]{2,1,0:T(1,128)} %get-tuple-element.2719, s32[]"
     "{:T(128)} %get-tuple-element.2673), kind=kLoop, "
     "calls=%fused_computation.163.clone.clone", None),
    ("%copy.91 = bf16[3,5,14336,4096]{3,2,0,1:T(8,128)(2,1)} copy(bf16[3,5,"
     "14336,4096]{3,2,1,0:T(8,128)(2,1)} %params__blocks____w_down__.1)",
     None),
    ("%slice-start.3 = ((bf16[3,8,14336]{2,1,0}), bf16[1,8,14336]{2,1,0}) "
     "async-start(bf16[3,8,14336]{2,1,0} %c), calls=%ac", None),
    ("%fusion.227 = bf16[24,4096]{1,0:T(8,128)(2,1)S(1)} fusion(f32[24] %x),"
     " kind=kOutput, calls=%fc.3", None),
    ("jit_seg(14848759746369940015)", None)])
def test_rows_of_reads_the_feed_forward_products_off_the_hlo_text(text, rows):
    assert trace_reduce.rows_of(text, 14336) == rows


FIXTURE = os.path.join(BENCH_DIR, "fixtures", "longprompt_600ms.xplane.pb")


def test_reduce_the_trace_recorded_on_the_chip():
    """0.6 s cut from a traced run of mistral-7b-2k.longprompt on the v5e in
    PR 23 (``trace_reduce.py --cut``; PERF.md), read back through jax's
    ProfileData in a child process and reduced."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "trace_reduce.py"), FIXTURE],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=child_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.6, abs=1e-6)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["programs"]["decode"]["count"] == 1  # one chunk of 8 steps
    assert out["programs"]["prefill"]["count"] == 1  # one 512-token segment
    assert out["programs"]["register"]["count"] == 1
    assert 0 < out["programs"]["decode"]["seconds"] <= out["busy_s"] + 1e-9
    assert len(out["device_ops"]) == 10
    assert all(" = " not in name and secs > 0
               for name, secs in out["device_ops"])
    with open(FIXTURE + ".expected.json") as f:
        want = json.load(f)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["programs"] == want["programs"]
    assert [n for n, _ in out["device_ops"]] == [
        n for n, _ in want["device_ops"]]


# ---- the cost model and the per-layer readers -----------------------------------


def test_cost_model_counts_the_published_widths():
    cfg = load(os.path.join(BENCH_DIR, "configs", "mistral-7b.json"))
    s = cost_model.shapes(cfg)
    assert s["layer_params"] == 218_103_808  # 41.9M attention + 176.2M mlp
    assert cost_model.kv_bytes_per_token(cfg) == 131072
    ops, byts = cost_model.decode_step(cfg, rows=12, context=0)
    assert byts == 32 * 218_103_808 + 4096 * 32000  # int8: a byte a weight
    peaks = load(os.path.join(BENCH_DIR, "peaks.json"))["TPU v5 lite"]
    assert cost_model.least_seconds(ops, byts, cfg, peaks) == pytest.approx(
        byts / 819e9)  # HBM-bound


def test_cost_model_streams_every_member_of_a_quorum():
    cfg = load(os.path.join(BENCH_DIR, "configs", "mistral-7b-x3.json"))
    _, byts = cost_model.decode_step(cfg, rows=24, context=0)
    assert byts == 3 * 2 * (5 * 218_103_808 + 4096 * 32000)


def _artefacts() -> dict:
    recs = [_rec("window", 0.5, 1.0, 3.0, 3.0, 11),
            _rec("window", 1.0, 1.2, 3.2, 3.2, 21),
            _rec("window", 1.5, 2.5, 3.5, 3.6, 11)]
    return {"records": recs, "window_s": 2.0,
            "traffic": traffic_file("chat"),
            "config": load(os.path.join(BENCH_DIR, "configs",
                                        "mistral-7b.json")),
            "m0": {"quorum_tpu_queue_wait_seconds_count": 2,
                   "quorum_tpu_queue_wait_seconds_sum": 0.2,
                   "quorum_tpu_engine_decode_chunks_total": 10,
                   "quorum_tpu_engine_decode_busy_rows_total": 50,
                   "quorum_tpu_recompiles_total": 13},
            "m1": {"quorum_tpu_queue_wait_seconds_count": 6,
                   "quorum_tpu_queue_wait_seconds_sum": 1.0,
                   "quorum_tpu_engine_decode_chunks_total": 30,
                   "quorum_tpu_engine_decode_busy_rows_total": 250,
                   "quorum_tpu_recompiles_total": 13},
            "log_compiles0": 13, "log_compiles1": 13,
            "memory_peak_bytes": 8_943_233_024, "spans": {}, "trace": None,
            "chips": 1, "read_until_s": 2.0,
            "peaks": load(os.path.join(BENCH_DIR, "peaks.json"))[
                "TPU v5 lite"]}


@pytest.mark.parametrize("name,want", [
    ("gen_late_p90_ms", 1.0), ("queue_wait_ms", 200.0),
    ("rows_per_chunk", 10.0), ("window_compiles", 0.0),
    ("hbm_peak_gb", 8.943233024), ("ttft_max3_ms", 200.0),
    ("decode_step_ms", None),
    ("prefill_ms_per_ktok", None), ("decode_step_roofline", None),
    ("prefill_roofline", None), ("device_idle_share", None),
    ("first_token_host_ms", None)])
def test_reader_on_synthetic_artefacts(name, want):
    value = traced.load_reader(name).read(_artefacts())
    if want is None:
        assert value is None  # nothing to read: the harness leaves it out
    else:
        assert value == pytest.approx(want)


def test_trace_readers_on_a_reduced_trace():
    art = _artefacts()
    art["trace"] = {"window_s": 4.0, "busy_s": 3.9, "programs": {
        "decode": {"count": 20.0, "seconds": 3.2},
        "prefill": {"count": 10.0, "seconds": 0.6}},
        "prefill_executions": [[0, 1.0, 0.02], [64, 4.0, 0.08],
                               [512, 5.0, 0.5]]}
    read = {n: traced.load_reader(n).read(art)
            for n in ("decode_step_ms", "prefill_ms_per_ktok",
                      "decode_step_roofline", "prefill_roofline",
                      "device_idle_share")}
    assert read["decode_step_ms"] == pytest.approx(3200.0 / (20 * 8))
    assert read["device_idle_share"] == pytest.approx(2.5)
    assert 0 < read["decode_step_roofline"] < 100
    # tokens and time of the executions whose rows the trace gives
    assert read["prefill_ms_per_ktok"] == pytest.approx(
        580.0 / ((4 * 64 + 5 * 512) / 1000.0))
    # int8 at the published widths: 64 rows are bound by the weights' bytes,
    # 512 by their operations
    cfg, peaks = art["config"], art["peaks"]
    byts = (32 * 218_103_808 + 4096 * 32000) / peaks["hbm_bytes_per_s"]
    grid = art["traffic"]["grid"]
    ops512, _ = cost_model.prefill(
        cfg, 512, sum(p for p, _ in grid) / len(grid), 1)
    assert ops512 / peaks["int8_ops"] > byts
    assert read["prefill_roofline"] == pytest.approx(
        100.0 * (4 * byts + 5 * ops512 / peaks["int8_ops"]) / 0.58)
    # rows unknown in over a twentieth of the prefill time: nothing is read
    art["trace"]["prefill_executions"][0][2] = 0.2
    assert traced.load_reader("prefill_roofline").read(art) is None
    assert traced.load_reader("prefill_ms_per_ktok").read(art) is None


def test_first_token_host_reads_the_engine_spans():
    from layer_metrics import first_token_host_ms
    art = _artefacts()
    for r in art["records"]:
        r["rid"] = f"req-{r['due']}"
        art["spans"][r["rid"]] = {"spans": [
            {"name": "queue-wait", "start_s": 0.01, "end_s": 0.11,
             "duration_ms": 100.0, "meta": {"member": 0}},
            {"name": "prefill", "start_s": 0.11, "end_s": 0.21,
             "duration_ms": 100.0, "meta": {"tokens": 32}}]}
    # client TTFT from the send is 499, 199 and 999 ms; the engine had 200
    assert first_token_host_ms.read(art) == pytest.approx(299.0)


# ---- BENCHMARK.json against its contract -----------------------------------------


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile, 1200 spare
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_found_by_name(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    data = load(os.path.join(REPO, cfg["file"]))
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    traffic = traffic_file(w["traffic"])
    assert traffic["loop"] in ("open", "closed") and traffic["warmup"]
    e2e_names = cell_metrics("end_to_end", cell)
    assert "setup_s" in e2e_names and len(e2e_names) >= 2
    assert cell_metrics("per_layer", cell)
    assert "latency_mean_ms" not in e2e_names
    assert ("tokens_per_s" in e2e_names) == (traffic["loop"] == "closed")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_keeps_the_published_widths(cfg):
    """The source's config.json, as ``configs/published/`` has it; only the
    keys in ``reduced`` may differ, and none of them is a width."""
    data = load(os.path.join(REPO, cfg["file"]))
    assert published_widths.problems(cfg, data) == []
    urls = [b["url"] for b in data["serve"]["backends"]]
    opt_ins = ("decode_loop", "decode_pipeline", "zero_drain", "kv_pages",
               "flash_decode", "kv_quant", "spec_")
    assert not any(o in u for o in opt_ins for u in urls)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_is_a_reader_of_its_own(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"])
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       metric["name"] + ".py"))
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    for cell in metric.get("workloads", CELLS):
        assert metric["moves"] in cell_metrics("end_to_end", cell)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_is_bounded(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert metric["better"] in ("lower", "higher")
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])


# ---- readers over the traced run's read interval, and what cannot be read ------


def test_readers_look_only_at_requests_due_before_the_profile():
    art = _artefacts()
    art["read_until_s"] = 1.2  # the third request was due at 1.5
    assert [r["due"] for r in e2e.layer_records(art)] == [0.5, 1.0]
    assert traced.load_reader("gen_late_p90_ms").read(art) == \
        traced.load_reader("gen_late_p90_ms").read(dict(art, records=art[
            "records"][:2]))
    assert traced.load_reader("ttft_max3_ms").read(art) is None  # two left


def test_a_stream_with_no_first_token_gives_no_sample_and_no_error():
    art = _artefacts()
    traced.strip_first_token(art["records"][2])
    assert not e2e.failed(art["records"][2])
    for name in ("gen_late_p90_ms", "ttft_max3_ms", "first_token_host_ms"):
        traced.load_reader(name).read(art)  # must not raise
    assert e2e.ttft_ms(art["records"]) == pytest.approx([500.0, 200.0])


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_reader_with_lost_scrapes_and_no_trace_reads_nothing(name):
    """Both scrapes lost, no profile, no span, an empty window: every reader
    returns None or raises into the guard; none returns a made-up number."""
    art = dict(_artefacts(), m0={}, m1={}, records=[], memory_peak_bytes=0,
               log_compiles0=0, log_compiles1=0)
    try:
        value = traced.load_reader(name).read(art)
    except Exception:
        return
    assert value is None or (name == "window_compiles" and value == 0.0)


def test_report_leaves_out_what_cannot_be_read_and_says_so(capsys):
    t = traced.Tracing(server=None, window_s=45.0, out_dir="/nonexistent")
    assert t.read_until_s == pytest.approx(42.5)
    result = {"metrics": {}, "device": {}}
    art = _artefacts()
    del art["spans"], art["trace"]
    parts = t.report(art, BENCH["per_layer"], result)
    assert parts["trace"] is False and parts["spans"] == 0
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert set(result["metrics"]) == {
        "gen_late_p90_ms", "gen_late_p90_ms.open", "ttft_max3_ms",
        "ttft_max3_ms.open", "queue_wait_ms", "queue_wait_ms.open",
        "rows_per_chunk", "window_compiles", "hbm_peak_gb"}
    assert result["metrics"]["queue_wait_ms.open"] == result["metrics"][
        "queue_wait_ms"]  # one reading under the open loop's name
    err = capsys.readouterr().err
    for name in ("decode_step_ms", "prefill_roofline", "device_idle_share",
                 "first_token_host_ms", "first_token_host_ms.open"):
        assert f"traced run: {name} left out" in err


# ---- traffic files name only what the generator knows ---------------------------


@pytest.mark.parametrize("mix", TRAFFIC)
def test_traffic_file_is_known_to_the_generator(mix):
    loadgen.check_traffic(traffic_file(mix))


@pytest.mark.parametrize("change,said", [
    ({"loop": "burst"}, "unknown loop kind"),
    ({"burst_every_s": 5}, "unknown ['burst_every_s']"),
    ({"sessions": 4}, "unknown ['sessions']"),
    ({"grid": []}, "grid is a non-empty list"),
    ({"warmup": [[32, 0]]}, "warmup is a non-empty list")])
def test_unknown_traffic_parameter_is_an_error_not_a_default(change, said):
    with pytest.raises(ValueError, match=re.escape(said)):
        loadgen.check_traffic(dict(traffic_file("chat"), **change))


def test_missing_traffic_parameter_is_an_error():
    traffic = traffic_file("quorum")
    del traffic["clients"]
    with pytest.raises(ValueError, match="missing"):
        loadgen.check_traffic(traffic)


# ---- the strict reading of a result line ------------------------------------------

GOOD_UNTRACED = {
    "correct": True, "attempted": 126, "failed": 0,
    "metrics": {"tpot_p50_ms": {"value": 29.9, "unit": "ms"},
                "latency_p50_ms": {"value": 6141.6, "unit": "ms"},
                "setup_s": {"value": 41.5, "unit": "s"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 8943233024}}


def _problems(result, trace=0, cell="mistral-7b.chat"):
    return check_line.problems(json.dumps(result), BENCH, cell, trace)


def test_a_line_that_keeps_the_contract_has_no_problems():
    assert _problems(GOOD_UNTRACED) == []


@pytest.mark.parametrize("change,said", [
    ({"smoke": 1}, "extra"), ({"correct": "yes"}, "boolean"),
    ({"attempted": 0}, "attempted is 0"), ({"failed": -1}, "not a count"),
    ({"metrics": {"tpot_p50_ms": {"value": 29.9, "unit": "ms"}}}, "missing"),
    ({"metrics": dict(GOOD_UNTRACED["metrics"],
                      tokens_per_s={"value": 1.0, "unit": "tokens/s"})},
     "not a end_to_end metric"),
    ({"metrics": dict(GOOD_UNTRACED["metrics"],
                      setup_s={"value": 41.5, "unit": "seconds"})}, "unit"),
    ({"metrics": dict(GOOD_UNTRACED["metrics"],
                      setup_s={"value": float("nan"), "unit": "s"})},
     "finite"),
    ({"device": dict(GOOD_UNTRACED["device"], platform="cpu")}, "not tpu"),
    ({"device": dict(GOOD_UNTRACED["device"], count="1")}, "count"),
    ({"device": dict(GOOD_UNTRACED["device"], memory_peak_bytes=0)},
     "memory_peak_bytes")])
def test_a_departure_from_the_contract_is_named(change, said):
    found = _problems(dict(GOOD_UNTRACED, **change))
    assert any(said in p for p in found), found


def test_a_traced_line_needs_device_time_and_keeps_rooflines_under_105():
    traced_line = dict(
        GOOD_UNTRACED,
        metrics={"decode_step_roofline": {"value": 106.0, "unit": "%"}},
        device=dict(GOOD_UNTRACED["device"], busy_s=0.0, window_s=4.0))
    found = _problems(traced_line, trace=1)
    assert any("above 105" in p for p in found)
    assert any("busy_s" in p for p in found)
    assert any("missing" in p for p in found)
    assert any("breakdown" in p for p in found)
    assert _problems("not an object") != []


# ---- the ways the command stops ---------------------------------------------------


def _run(argv, cwd=REPO, env=None, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py")] + argv,
        capture_output=True, text=True, timeout=timeout, cwd=cwd,
        env=env or child_env())


def test_no_result_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__", "out"))
    proc = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"], cwd=str(tmp_path))
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert proc.stderr.startswith("benchmark FAILED: checkout:")


@pytest.mark.parametrize("argv,said", [
    (["--workload", "no-such.cell", "--trace", "1"],
     "benchmark FAILED: arguments: no workload"),
    (["--workload", CELLS[0], "--trace", "0", "--inject-fault", "profile"],
     "benchmark FAILED: arguments: --inject-fault")])
def test_every_refusal_has_its_line_on_stderr(argv, said):
    proc = _run(argv)
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert proc.stderr.startswith(said), proc.stderr[:300]


def test_a_run_that_finds_no_tpu_fails_with_no_result(tmp_path):
    """No CPU fallback: without ``--rehearsal`` the server refuses a CPU that
    was not asked for by name, and the benchmark reports that and stops."""
    env = child_env()
    del env["JAX_PLATFORMS"]
    proc = _run(["--workload", "mistral-7b.chat", "--seed", "1", "--seconds",
                 "1", "--trace", "1", "--out", str(tmp_path)], env=env,
                timeout=300)
    assert proc.returncode == 1
    assert "benchmark FAILED: server start:" in proc.stderr
    assert not any(ln.startswith('{"correct"') or "REHEARSAL" in ln
                   for ln in proc.stdout.splitlines())


def test_sigterm_stops_the_server_and_kills_as_the_signal_would(tmp_path):
    """A run the driver stops for time must look like one: the children are
    stopped, a line says so, and the process dies of SIGTERM (143 in a
    shell), not with exit code 1."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "mistral-7b.chat", "--seed", "1", "--seconds", "4", "--trace", "1",
         "--rehearsal", "--out", str(tmp_path)], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=REPO, env=child_env())
    try:
        spawned = json.loads(proc.stdout.readline())
        assert spawned["step"] == "server spawned"
        time.sleep(1.0)
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGTERM
    assert "benchmark FAILED: signal: stopped by signal 15" in err
    with pytest.raises(ProcessLookupError):
        os.kill(spawned["pid"], 0)


def test_no_exit_path_lacks_its_line():
    """Read off the source: the command exits non-zero only through
    ``main``'s one ``benchmark FAILED:`` print or the signal handler's, and
    nothing in the traced part exits or raises ``Failed`` at all."""
    with open(os.path.join(BENCH_DIR, "run.py")) as f:
        run_src = f.read()
    with open(os.path.join(BENCH_DIR, "traced.py")) as f:
        traced_src = f.read()
    assert run_src.count("sys.exit(") == 1  # sys.exit(main())
    assert "os._exit" not in run_src + traced_src
    assert "sys.exit" not in traced_src and "Failed" not in traced_src
    assert run_src.count('print(f"benchmark FAILED:') == 2
    handler = run_src[run_src.index("def on_signal"):run_src.index(
        "def load_json")]
    assert "SIG_DFL" in handler and "os.kill(os.getpid(), signum)" in handler
    assert "exit(" not in handler
