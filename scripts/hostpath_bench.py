"""Host-path microbench: what the decode-dispatch pipeline and the
megachunk decode loop buy on CPU.

Runs a tiny random-init engine (no checkpoint, no TPU) through the same
compiled serving programs the real chip runs — once per pipeline depth and
once with ``decode_loop=C`` megachunk fusion — and reports the dispatch
accounting the PR-1 counters expose:

  - ``dispatches_per_request``  decode dispatches the generation cost
                                (under decode_loop=C one dispatch covers up
                                to C chunks, so this drops ~C×)
  - ``syncs_per_request``       dispatches the host BLOCKED on (chunk
                                dispatched with an empty ring); the pipelined
                                remainder overlapped the host turnaround
  - ``overrun_tokens``          tokens produced but discarded (0 when rows
                                finish on device — EOS/budget at any depth)
  - ``drain_gap_ms_per_dispatch`` host time between a dispatch's payload
                                landing on host and its last token handed to
                                the consumer queues — the per-dispatch host
                                tax megachunking amortizes over C chunks
  - ``host_turnaround_share``   fraction of the K=1 wall time the deeper
                                pipeline hid (≈ turnaround/(turnaround +
                                chunk time) when fully hidden — PERF.md §2)

It additionally measures **prefill interference** (the disagg=P+D
acceptance number, docs/tpu_backends.md): the inter-token p50/p95/p99 gap
of one streaming request while admission churn runs concurrently, colocated
vs disaggregated — on the colocated engine every admission clamps the
decode ring and interleaves its prefill segments between decode chunks,
while the disagg engine prefills on its own device group and hands the KV
off device→device, so the streaming gaps stay flat.

The **qos leg** (`--only-qos`, docs/scheduling.md) is the scheduler's
A/B: interactive TTFT p50/p99 under a batch-churn backlog, FIFO vs
``qos=1`` (WFQ admission + mid-decode preemption), against an
uncontended solo floor, plus the batch-throughput cost and the
preemption/replay counters.

Usage:  python scripts/hostpath_bench.py [--tokens N] [--chunk C]
        [--depth K] [--loop C] [--skip-interference] [--skip-qos]
Prints one human-readable block and one machine-parsable JSON line.
``make hostpath-bench`` runs it; tests/test_hostpath_bench.py is the suite's
smoke over the same entry points.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

# Runnable as `python scripts/hostpath_bench.py` from a checkout without
# `pip install -e`: the repo root (not scripts/) must be importable.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The interference legs need >= 2 virtual CPU devices (one per disagg
# group) and the sharded legs >= 4 (disagg=2+2&tp=2 vs colocated tp=4 at
# matched device count). Effective only before the first `import jax` —
# standalone runs; under pytest the suite conftest already forces an
# 8-device mesh.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()


def run(tokens: int = 64, chunk: int = 4, depth: int = 4,
        repeats: int = 3, loop: int = 4) -> dict:
    """Generate ``tokens`` greedily at decode_pipeline=1 and =``depth``
    (both unfused) plus decode_loop=``loop`` megachunks on fresh tiny
    engines; return the dispatch/sync/overrun/drain-gap accounting plus
    wall times (median of ``repeats`` after a compile warm-up)."""
    if depth < 2:
        # depth 1 IS the K=1 baseline leg — comparing it against itself
        # would report run-to-run noise as a pipeline win.
        raise ValueError("depth must be >= 2 (1 is the baseline leg)")
    if loop < 2:
        raise ValueError("loop must be >= 2 (1 is the unfused baseline)")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    prompt = [5, 6, 7]
    out: dict = {"tokens": tokens, "decode_chunk": chunk, "depth": depth,
                 "loop": loop}
    streams: dict[str, list[int]] = {}

    # Legs: (tag, pipeline depth, decode_loop). The loop leg keeps the deep
    # ring — megachunks compose with pipelining (C chunks per in-flight
    # entry), and the acceptance number is dispatches/request at loop=C.
    legs = [("k1", 1, 1), (f"k{depth}", depth, 1),
            (f"loop{loop}", depth, loop)]
    for tag, k, c in legs:
        eng = InferenceEngine(spec, decode_chunk=chunk, decode_pipeline=k,
                              decode_loop=c)
        eng.generate(prompt, max_new_tokens=tokens, sampler=greedy)  # warm-up
        c0, o0, v0 = eng.n_decode_chunks, eng.n_overlapped, eng.n_overrun
        g0 = eng.drain_gap_s
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = eng.generate(prompt, max_new_tokens=tokens, sampler=greedy)
            walls.append(time.perf_counter() - t0)
        streams[tag] = res.token_ids
        dispatches = (eng.n_decode_chunks - c0) / repeats
        overlapped = (eng.n_overlapped - o0) / repeats
        out[f"{tag}_dispatches_per_request"] = dispatches
        out[f"{tag}_syncs_per_request"] = dispatches - overlapped
        out[f"{tag}_overrun_tokens"] = eng.n_overrun - v0
        out[f"{tag}_drain_gap_ms_per_dispatch"] = round(
            (eng.drain_gap_s - g0) / max(1.0, dispatches * repeats) * 1e3, 3)
        out[f"{tag}_wall_s"] = round(statistics.median(walls), 4)
        out[f"{tag}_tok_s"] = round(tokens / statistics.median(walls), 1)
        # Per-family device-seconds: the leg's device time as the engine's
        # device ledger booked it, landing to landing, by compile-budget
        # program family (p50/p99 from the engine's LatencyModel
        # reservoir; a ring K deep no longer multiplies a chunk's
        # reading by its depth) — an A/B arm's win is
        # attributable to the family that moved (the loop leg's time
        # lives under "loop", the unfused legs' under "plain").
        out[f"{tag}_device_seconds"] = eng.latency.snapshot()
        eng.shutdown()

    t1, tk = out["k1_wall_s"], out[f"k{depth}_wall_s"]
    # The wall time the deeper ring hid is host turnaround that K=1 spent
    # synchronized: its share of the K=1 request is the measured stand-in
    # for turnaround/(turnaround + chunk time).
    out["host_turnaround_share"] = round(max(0.0, t1 - tk) / t1, 3) if t1 else 0.0
    out["loop_dispatch_reduction"] = round(
        out["k1_dispatches_per_request"]
        / max(1e-9, out[f"loop{loop}_dispatches_per_request"]), 2)
    out["tokens_match"] = (streams["k1"] == streams[f"k{depth}"]
                           == streams[f"loop{loop}"])
    return out


def interference(tokens: int = 64, chunk: int = 4, depth: int = 4,
                 loop: int = 4, churn: int = 4,
                 churn_prompt_tokens: int = 48) -> dict:
    """Streaming inter-token gaps under concurrent admission churn, three
    arms: colocated (drain-based), colocated + ``zero_drain=1`` (staged
    in-flight row injection, ISSUE 11), and ``disagg=1+1``. One long
    greedy stream's token-arrival gaps (ms percentiles over the per-chunk
    reap gaps) while ``churn`` chunked admissions (prompts of
    ``churn_prompt_tokens`` ≫ prefill_chunk) are submitted back to back.
    The acceptance number is the p99 gap: drain-based colocated
    admissions clamp the ring to depth 1 and interleave prefill segments
    between decode chunks; the zero-drain arm keeps the ring at full
    K×C depth and injects at reap boundaries (admission stall
    structurally 0); the disagg arm's prefill runs on its own device
    group entirely. The gate (ISSUE 11): zero-drain p99 within ~2× of
    disagg's, all three streams token-for-token identical."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig
    from quorum_tpu.parallel.mesh import disagg_meshes

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the interference bench needs >= 2 virtual devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    stream_prompt = [5, 6, 7]

    def churn_ids(i: int) -> list[int]:
        # DISTINCT prompt per churn admission: a repeated prompt is
        # slot-resident after its first admission, so the colocated arm
        # would tier-0-reuse all but one segment of every later churn
        # admission (the staged arms cannot reuse) — the arms would stop
        # measuring the same admission work.
        return [(11 + 3 * j + 5 * i) % spec.vocab_size
                for j in range(churn_prompt_tokens)]

    # The measured stream must OUTLIVE the dispatch ring: a budget within
    # K×C×chunk tokens fits entirely in one ring fill, finishing before
    # any churn admission can interfere — the phase would measure nothing.
    tokens = max(tokens, 2 * depth * loop * chunk)
    out: dict = {"tokens": tokens, "churn_admissions": churn,
                 "churn_prompt_tokens": churn_prompt_tokens}
    streams: dict[str, list[int]] = {}

    for tag in ("colocated", "zero_drain", "disagg"):
        kw = dict(decode_chunk=chunk, decode_pipeline=depth,
                  decode_loop=loop, n_slots=2, prefill_chunk=16)
        if tag == "disagg":
            pm, dm = disagg_meshes(1, 1)
            eng = InferenceEngine(spec, dm, prefill_mesh=pm, **kw)
        elif tag == "zero_drain":
            eng = InferenceEngine(spec, zero_drain=True, **kw)
        else:
            eng = InferenceEngine(spec, **kw)
        # Warm every program the measured pass dispatches (stream decode
        # buckets, churn segment/handoff buckets): first-use XLA compiles
        # would otherwise dominate the gap percentiles. The churn runs
        # CONCURRENTLY with the warmup stream so the drain-based arm also
        # compiles its clamped (C=1, deep-history) decode variants — the
        # admission-pressure window is exactly what the measured pass
        # spends its time in there.
        warm = eng.submit(stream_prompt, max_new_tokens=tokens,
                          sampler=greedy, seed=0)
        eng.generate(churn_ids(0), max_new_tokens=2, sampler=greedy)
        list(eng.stream_results(warm))

        req = eng.submit(stream_prompt, max_new_tokens=tokens,
                         sampler=greedy, seed=0)
        # One churn admission enqueued BEFORE the stream is consumed (same
        # in every arm): a fused K×C stream can finish in a handful of
        # dispatches, and a churner thread that loses the startup race
        # would leave the admission-interference window unexercised. This
        # one is guaranteed to admit while the stream decodes.
        pre = eng.submit(churn_ids(1), max_new_tokens=2, sampler=greedy)
        stamps: list[float] = []
        toks: list[int] = []
        done = threading.Event()
        n_churned = 1

        def churn_loop():
            nonlocal n_churned
            while not done.is_set() and n_churned < churn * 4:
                eng.generate(churn_ids(1 + n_churned), max_new_tokens=2,
                             sampler=greedy)
                n_churned += 1

        churner = threading.Thread(target=churn_loop, daemon=True)
        churner.start()
        for t in eng.stream_results(req):
            toks.append(t)
            stamps.append(time.perf_counter())
        list(eng.stream_results(pre))
        done.set()
        churner.join()
        streams[tag] = toks
        # A decode chunk's k tokens reach the consumer microseconds apart;
        # the per-chunk reap gap is the signal. Keep only gaps above 0.1ms
        # so the intra-chunk deliveries don't dilute the percentiles.
        gaps = sorted(b - a for a, b in zip(stamps, stamps[1:])
                      if b - a > 1e-4)
        if not gaps:
            gaps = [0.0]

        def pct(p):
            return round(gaps[min(len(gaps) - 1,
                                  int(p / 100 * len(gaps)))] * 1e3, 3)

        out[f"{tag}_intertoken_p50_ms"] = pct(50)
        out[f"{tag}_intertoken_p95_ms"] = pct(95)
        out[f"{tag}_intertoken_p99_ms"] = pct(99)
        out[f"{tag}_churn_completed"] = n_churned
        if tag == "disagg":
            out["disagg_kv_handoffs"] = eng.n_kv_handoffs
            out["disagg_kv_handoff_bytes"] = eng.kv_handoff_bytes
        elif tag == "zero_drain":
            # The zero-drain acceptance counters: injections that landed
            # on a live ring, and the structural-0 admission stall.
            out["zero_drain_admission_overlap"] = eng.n_admission_overlap
            out["zero_drain_admission_stall_s"] = round(
                eng.admission_stall_s, 6)
        else:
            # Wall time the drain-based ring spent clamped for admissions
            # — what zero_drain removes (structurally 0 there).
            out["colocated_admission_stall_s"] = round(
                eng.admission_stall_s, 6)
        # Per-family attribution per arm: the colocated arm's admission
        # cost shows under seg/single_shot against its clamped decode
        # families; the staged arms split theirs across seg/hslice/hput/
        # register while loop keeps full-depth time.
        out[f"{tag}_device_seconds"] = eng.latency.snapshot()
        eng.shutdown()

    out["interference_tokens_match"] = (
        streams["colocated"] == streams["disagg"]
        and streams["colocated"] == streams["zero_drain"])
    c99, z99, d99 = (out["colocated_intertoken_p99_ms"],
                     out["zero_drain_intertoken_p99_ms"],
                     out["disagg_intertoken_p99_ms"])
    # Floor the denominator at the gap filter (0.1 ms): a tiny-budget leg
    # whose reap gaps all fell under the filter reports d99 = 0.0, and an
    # unfloored ratio would record a billions-x artifact as the headline.
    out["interference_p99_ratio"] = round(c99 / max(0.1, d99), 2)
    # The ISSUE 11 gate: zero-drain p99 within ~2x of the disagg number.
    out["zero_drain_p99_vs_disagg"] = round(z99 / max(0.1, d99), 2)
    out["zero_drain_p99_vs_colocated"] = round(c99 / max(0.1, z99), 2)
    return out


def sharded(tokens: int = 48, chunk: int = 4, depth: int = 2,
            loop: int = 2, repeats: int = 2) -> dict:
    """Per-group sharding under disagg (ISSUE 14): two arms at the SAME
    device count (4) — colocated ``tp=4`` and ``disagg=2+2&tp=2`` (both
    groups tp-sharded, the handoff resharding between the two layouts on
    the fly). Reports per arm: decode tok/s, handoff bytes/s across the
    group boundary, dispatch counts, and the per-family device-seconds
    attribution — tokens asserted identical across the arms (sharding
    moves bytes, never samples)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig
    from quorum_tpu.parallel.mesh import MeshConfig, disagg_meshes, make_mesh

    if len(jax.devices()) < 4:
        raise RuntimeError(
            "the sharded legs need >= 4 virtual devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    prompt = [(3 + 5 * i) % spec.vocab_size for i in range(40)]
    kw = dict(decode_chunk=chunk, decode_pipeline=depth, decode_loop=loop,
              n_slots=2, prefill_chunk=16)
    out: dict = {"sharded_tokens": tokens, "sharded_devices": 4}
    streams: dict[str, list[int]] = {}
    for tag in ("colocated_tp4", "disagg_tp2"):
        if tag == "colocated_tp4":
            eng = InferenceEngine(
                spec, make_mesh(MeshConfig(tp=4), jax.devices()[:4]), **kw)
        else:
            pm, dm = disagg_meshes(2, 2, tp=2)
            eng = InferenceEngine(spec, dm, prefill_mesh=pm, **kw)
        eng.generate(prompt, max_new_tokens=tokens, sampler=greedy)  # warm
        c0, b0 = eng.n_decode_chunks, eng.kv_handoff_bytes
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = eng.generate(prompt, max_new_tokens=tokens, sampler=greedy)
            walls.append(time.perf_counter() - t0)
        streams[tag] = res.token_ids
        wall = statistics.median(walls)
        pre = f"sharded_{tag}"
        out[f"{pre}_tok_s"] = round(tokens / wall, 1)
        out[f"{pre}_dispatches_per_request"] = (
            (eng.n_decode_chunks - c0) / repeats)
        handoff_b = eng.kv_handoff_bytes - b0
        out[f"{pre}_handoff_bytes_per_s"] = round(
            handoff_b / max(1e-9, wall * repeats), 1)
        out[f"{pre}_handoff_bytes"] = handoff_b
        # Per-family device-seconds: the handoff halves live under
        # hslice/hput.
        out[f"{pre}_device_seconds"] = eng.latency.snapshot()
        eng.shutdown()
    out["sharded_tokens_match"] = (
        streams["colocated_tp4"] == streams["disagg_tp2"])
    return out


def paged(tokens: int = 8, streams: int = 24, page_size: int = 16,
          pool_pages: int = 32) -> dict:
    """Rows-per-chip at FIXED KV HBM (ISSUE 17, the paged-layout headline):
    dense vs ``kv_pages=1`` on a short-stream mix, same position budget.

    The budget is ``pool_pages × page_size`` cache positions. The dense
    rectangle spends it on ``budget // max_seq`` slots — every row pays
    ``max_seq`` whether it uses it or not — while the paged engine spends
    it on a page pool and admits as many rows as their ACTUAL spans fit
    (each short stream here spans ≲ 2 pages). Reports per arm: peak
    concurrently-resident rows, completed streams, wall time, and for the
    paged arm the peak page occupancy — with every stream's tokens
    asserted identical to its dense twin (capacity, never semantics).
    The acceptance gate: peak paged rows ≥ 4× the dense slot count."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    positions = pool_pages * page_size
    dense_slots = max(1, positions // spec.max_seq)
    # Short streams: ~10-token prompts + the decode budget span ≲ 2 pages,
    # so the pool admits pool_pages // 2 of them at once.
    paged_slots = max(dense_slots, pool_pages // 2)
    prompts = [[(3 + 7 * i + j) % (spec.vocab_size - 1) + 1
                for j in range(8 + (i % 3))] for i in range(streams)]
    out: dict = {"paged_streams": streams, "paged_pool_pages": pool_pages,
                 "paged_page_size": page_size,
                 "paged_dense_rows": dense_slots}
    results: dict[str, dict[int, list[int]]] = {}
    for tag, kw in (("dense", dict(n_slots=dense_slots)),
                    ("paged", dict(n_slots=paged_slots, kv_pages=True,
                                   kv_page_size=page_size,
                                   kv_pool_pages=pool_pages))):
        eng = InferenceEngine(spec, decode_chunk=4, prefill_chunk=16, **kw)
        eng.generate(prompts[0], max_new_tokens=tokens,
                     sampler=greedy)  # warm-up
        peak = {"rows": 0, "pages": 0}
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                with eng._cond:
                    rows = sum(1 for r in eng._slots if r is not None)
                    pages = (eng._page_alloc.allocated_pages
                             if eng.kv_pages else 0)
                peak["rows"] = max(peak["rows"], rows)
                peak["pages"] = max(peak["pages"], pages)
                time.sleep(0.0005)

        outs: dict[int, list[int]] = {}

        def one(i: int) -> None:
            outs[i] = [t for t in eng.generate_stream(
                prompts[i], max_new_tokens=tokens, sampler=greedy, seed=i)]

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        t0 = time.perf_counter()
        ths = [threading.Thread(target=one, args=(i,))
               for i in range(streams)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        poller.join()
        results[tag] = outs
        out[f"paged_{tag}_peak_rows"] = peak["rows"]
        out[f"paged_{tag}_completed"] = len(outs)
        out[f"paged_{tag}_wall_s"] = round(wall, 3)
        if eng.kv_pages:
            out["paged_peak_page_occupancy"] = round(
                peak["pages"] / pool_pages, 3)
        eng.shutdown()
    out["paged_rows_per_chip_ratio"] = round(
        out["paged_paged_peak_rows"] / max(1, out["paged_dense_peak_rows"]),
        2)
    out["paged_tokens_match"] = results["dense"] == results["paged"]
    return out


def dedup(prompt_len: int = 48, tokens: int = 8, members: int = 3,
          rounds: int = 8) -> dict:
    """Shared-prefix member dedup (docs/quorum.md): a ``members=M``
    shared-weights engine fans one prompt into M sampling streams; with
    ``quorum_dedup=1`` a coalesced member-complete admission prefills the
    prompt ONCE and broadcasts the K/V into all M cache rows. A
    prefill-heavy fan-out mix (long prompt, short decode) measures the
    headline: prefill tokens computed per fan-out down ~M×, outputs
    token-for-token identical to the M-prefill baseline. A round only
    dedups when all M submits coalesce into one admission group, so the
    reported ratio is the honest mixed-traffic number; ``dedup_rounds``
    says how many of ``rounds`` took the fast path."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    prompt = [(5 + 11 * j) % (spec.vocab_size - 1) + 1
              for j in range(prompt_len)]
    kw = dict(seed=0, members=members, decode_chunk=4, n_slots=2,
              member_seeds="shared", prefix_cache=False)

    def fan(eng) -> list[list[int]]:
        reqs = [eng.submit(list(prompt), max_new_tokens=tokens,
                           sampler=greedy, seed=7 + m, member=m)
                for m in range(members)]
        return [list(eng.stream_results(r)) for r in reqs]

    out: dict = {"dedup_members": members, "dedup_prompt_len": prompt_len,
                 "dedup_rounds_driven": rounds}
    results: dict[str, list] = {}
    nominal = rounds * members * prompt_len
    for tag, extra in (("off", {}), ("on", {"quorum_dedup": True})):
        eng = InferenceEngine(spec, **kw, **extra)
        try:
            fan(eng)  # warm-up (compiles both prefill variants)
            tokens_before = eng.quorum_dedup_tokens
            prefills_before = eng.quorum_dedup_prefills
            t0 = time.perf_counter()
            results[tag] = [fan(eng) for _ in range(rounds)]
            wall = time.perf_counter() - t0
            # savings over the measured rounds only (warm-up excluded)
            saved = eng.quorum_dedup_tokens - tokens_before
            out[f"dedup_{tag}_wall_s"] = round(wall, 3)
            out[f"dedup_{tag}_prefill_tokens"] = nominal - saved
            if tag == "on":
                out["dedup_rounds"] = (eng.quorum_dedup_prefills
                                       - prefills_before)
        finally:
            eng.shutdown()
    out["dedup_prefill_token_ratio"] = round(
        out["dedup_off_prefill_tokens"]
        / max(1, out["dedup_on_prefill_tokens"]), 2)
    out["dedup_tokens_match"] = results["off"] == results["on"]
    return out


def qos(tokens: int = 24, churn: int = 3, arrivals: int = 8) -> dict:
    """QoS scheduler A/B (ISSUE 18, docs/scheduling.md): interactive TTFT
    under a batch backlog, FIFO vs ``qos=1``, on one llama-tiny engine.

    Both arms run the SAME mixed load — ``churn`` threads submitting
    ``priority="batch"`` streams of ``tokens`` tokens back-to-back, with
    ``arrivals`` sequential ``priority="interactive"`` requests measured
    for TTFT (submit → first token). The FIFO arm queues each interactive
    arrival behind whole batch generations; the qos arm admits it past
    the backlog (WFQ order) and, with every slot busy, parks a batch
    resident (mid-decode preemption — victims resume token-exactly, the
    contract tests/test_sched.py pins). Reports per arm: interactive
    TTFT p50/p99, batch churn throughput (the degradation cost), and for
    the qos arm the preemption/replay counters. A solo (uncontended)
    TTFT floor anchors the comparison."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = MODEL_PRESETS["llama-tiny"]
    greedy = SamplerConfig(temperature=0.0)
    iprompt = [17, 23, 31, 47, 53]

    def churn_ids(i: int) -> list[int]:
        return [(5 + 3 * i + j) % (spec.vocab_size - 1) + 1
                for j in range(10)]

    def pct(xs: list[float], p: float) -> float:
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(p * len(xs)))], 1)

    def ttft_one(eng, prio: "str | None") -> float:
        t0 = time.perf_counter()
        req = eng.submit(list(iprompt), max_new_tokens=4, sampler=greedy,
                         seed=1, priority=prio)
        it = eng.stream_results(req)
        next(it, None)
        ttft_ms = (time.perf_counter() - t0) * 1000.0
        for _ in it:
            pass
        return ttft_ms

    def wait_backlog(eng, budget_s: float = 2.0) -> None:
        """Admit the next interactive arrival against a FORMED backlog
        (every slot batch-resident): both arms measure the same contended
        moment instead of racing the churn threads' re-submit gap."""
        t_end = time.perf_counter() + budget_s
        while time.perf_counter() < t_end:
            with eng._cond:
                if all(r is not None for r in eng._slots):
                    return
            time.sleep(0.001)

    out: dict = {"qos_arrivals": arrivals, "qos_churn_threads": churn,
                 "qos_churn_tokens": tokens}
    for tag, qos_on in (("fifo", False), ("qos", True)):
        eng = InferenceEngine(spec, n_slots=2, decode_chunk=4,
                              prefill_chunk=16, qos=qos_on)
        eng.generate(iprompt, max_new_tokens=4, sampler=greedy)  # warm
        eng.generate(churn_ids(0), max_new_tokens=tokens, sampler=greedy)
        if not qos_on:
            solo = [ttft_one(eng, None) for _ in range(arrivals)]
            out["qos_solo_ttft_p50_ms"] = pct(solo, 0.5)
            out["qos_solo_ttft_p99_ms"] = pct(solo, 0.99)
        stop = threading.Event()
        done = {"streams": 0, "tokens": 0}

        def churn_loop(k: int) -> None:
            i = k
            while not stop.is_set():
                req = eng.submit(churn_ids(i), max_new_tokens=tokens,
                                 sampler=greedy, seed=i, priority="batch")
                n = sum(1 for _ in eng.stream_results(req))
                done["streams"] += 1
                done["tokens"] += n
                i += churn
        ths = [threading.Thread(target=churn_loop, args=(k,), daemon=True)
               for k in range(churn)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        time.sleep(0.3)  # let the backlog form: both slots batch-resident
        ttfts = []
        for _ in range(arrivals):
            wait_backlog(eng)
            ttfts.append(ttft_one(eng, "interactive"))
            time.sleep(0.05)
        stop.set()
        for t in ths:
            t.join(30)
        wall = time.perf_counter() - t0
        out[f"qos_{tag}_interactive_ttft_p50_ms"] = pct(ttfts, 0.5)
        out[f"qos_{tag}_interactive_ttft_p99_ms"] = pct(ttfts, 0.99)
        out[f"qos_{tag}_churn_streams"] = done["streams"]
        out[f"qos_{tag}_churn_tok_s"] = round(done["tokens"] / wall, 1)
        if qos_on:
            m = eng.metrics()
            out["qos_preemptions"] = m["preemptions_total"]
            out["qos_preempted_tokens"] = m["preempted_tokens_total"]
            out["qos_replayed_tokens"] = m["replayed_tokens_total"]
        eng.shutdown()
    out["qos_ttft_p99_ratio"] = round(
        out["qos_fifo_interactive_ttft_p99_ms"]
        / max(1e-9, out["qos_qos_interactive_ttft_p99_ms"]), 2)
    out["qos_batch_degradation"] = round(
        out["qos_qos_churn_tok_s"]
        / max(1e-9, out["qos_fifo_churn_tok_s"]), 2)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--loop", type=int, default=4,
                    help="decode_loop=C for the megachunk leg (>= 2)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--skip-interference", action="store_true",
                    help="skip the colocated-vs-disagg interference legs")
    ap.add_argument("--skip-sharded", action="store_true",
                    help="skip the per-group-sharding legs (disagg+tp "
                         "vs colocated tp at matched devices)")
    ap.add_argument("--only-interference", action="store_true",
                    help="run ONLY the interference legs (bench.py's "
                         "subprocess phase — the depth/megachunk sweep "
                         "would be compiled and thrown away)")
    ap.add_argument("--only-sharded", action="store_true",
                    help="run ONLY the per-group-sharding legs (bench.py's "
                         "subprocess phase)")
    ap.add_argument("--skip-paged", action="store_true",
                    help="skip the paged-KV rows-per-chip legs")
    ap.add_argument("--only-paged", action="store_true",
                    help="run ONLY the paged-KV rows-per-chip legs "
                         "(bench.py's subprocess phase)")
    ap.add_argument("--skip-qos", action="store_true",
                    help="skip the QoS scheduler A/B legs")
    ap.add_argument("--only-qos", action="store_true",
                    help="run ONLY the QoS scheduler A/B legs (bench.py's "
                         "subprocess phase)")
    ap.add_argument("--skip-dedup", action="store_true",
                    help="skip the shared-prefix member-dedup legs")
    ap.add_argument("--only-dedup", action="store_true",
                    help="run ONLY the shared-prefix member-dedup legs "
                         "(bench.py's subprocess phase)")
    args = ap.parse_args()
    if args.only_dedup:
        md = dedup()
        _print_dedup(md)
        print(json.dumps(md), flush=True)
        return 0
    if args.only_qos:
        mq = qos()
        _print_qos(mq)
        print(json.dumps(mq), flush=True)
        return 0
    if args.only_paged:
        mp = paged()
        _print_paged(mp)
        print(json.dumps(mp), flush=True)
        return 0
    if args.only_sharded:
        try:
            msh = sharded(args.tokens, args.chunk, args.depth, args.loop,
                          args.repeats)
        except RuntimeError as e:
            msh = {"sharded_skipped": str(e)}
            print(f"sharded legs skipped: {e}")
        else:
            _print_sharded(msh)
        print(json.dumps(msh), flush=True)
        return 0
    if args.only_interference:
        mi = interference(args.tokens, args.chunk, args.depth, args.loop)
        print("prefill interference (streaming inter-token gap under "
              "admission churn):")
        for tag in ("colocated", "zero_drain", "disagg"):
            print(f"  {tag:10}: p50 {mi[f'{tag}_intertoken_p50_ms']} ms, "
                  f"p95 {mi[f'{tag}_intertoken_p95_ms']} ms, "
                  f"p99 {mi[f'{tag}_intertoken_p99_ms']} ms "
                  f"({mi[f'{tag}_churn_completed']} churn admissions)")
        print(f"  p99 colocated/disagg: {mi['interference_p99_ratio']:.2f}x"
              f", zero_drain/disagg: {mi['zero_drain_p99_vs_disagg']:.2f}x"
              f" (gate: ~2x), colocated/zero_drain: "
              f"{mi['zero_drain_p99_vs_colocated']:.2f}x")
        print(json.dumps(mi), flush=True)
        return 0
    if args.depth < 2:
        ap.error("--depth must be >= 2 (1 is the K=1 baseline both legs run)")
    if args.loop < 2:
        ap.error("--loop must be >= 2 (1 is the unfused baseline)")
    m = run(args.tokens, args.chunk, args.depth, args.repeats, args.loop)
    k, c = args.depth, args.loop
    print(f"host-path microbench (llama-tiny, {m['tokens']} tokens, "
          f"decode_chunk={m['decode_chunk']}):")
    for tag, label in (("k1", "K=1      "), (f"k{k}", f"K={k}      "),
                       (f"loop{c}", f"K={k} C={c}")):
        print(f"  {label}: {m[f'{tag}_dispatches_per_request']:.1f} "
              f"dispatches/req, {m[f'{tag}_syncs_per_request']:.1f} blocking "
              f"syncs/req, {m[f'{tag}_tok_s']} tok/s, "
              f"{m[f'{tag}_drain_gap_ms_per_dispatch']:.2f} ms drain "
              "gap/dispatch")
        fams = m.get(f"{tag}_device_seconds", {})
        decode_fams = {f: s for f, s in fams.items()
                       if f in ("plain", "loop", "dfa", "loop_dfa",
                                "unknown")}
        if decode_fams:
            parts = ", ".join(
                f"{f} p50 {s['p50_ms']}ms / p99 {s['p99_ms']}ms "
                f"(n={s['count']})" for f, s in sorted(decode_fams.items()))
            print(f"             device-seconds by family: {parts}")
    print(f"  overrun tokens: K=1 {m['k1_overrun_tokens']}, "
          f"K={k} {m[f'k{k}_overrun_tokens']}, "
          f"C={c} {m[f'loop{c}_overrun_tokens']} (on-device finish)")
    print(f"  host-turnaround share hidden by K={k}: "
          f"{m['host_turnaround_share']:.1%}")
    print(f"  dispatch reduction at decode_loop={c}: "
          f"{m['loop_dispatch_reduction']:.1f}x")
    print(f"  token-for-token identical: {m['tokens_match']}")
    if not args.skip_interference:
        mi = interference(args.tokens, args.chunk, args.depth, args.loop)
        m.update(mi)
        print("prefill interference (streaming inter-token gap under "
              "admission churn):")
        for tag in ("colocated", "zero_drain", "disagg"):
            print(f"  {tag:10}: p50 {mi[f'{tag}_intertoken_p50_ms']} ms, "
                  f"p95 {mi[f'{tag}_intertoken_p95_ms']} ms, "
                  f"p99 {mi[f'{tag}_intertoken_p99_ms']} ms "
                  f"({mi[f'{tag}_churn_completed']} churn admissions)")
        print(f"  p99 colocated/disagg: {mi['interference_p99_ratio']:.2f}x"
              f" (higher = disagg insulates better); KV handed off: "
              f"{mi['disagg_kv_handoff_bytes']} bytes in "
              f"{mi['disagg_kv_handoffs']} transfers")
        print(f"  p99 zero_drain/disagg: "
              f"{mi['zero_drain_p99_vs_disagg']:.2f}x (gate: ~2x, in "
              "software on one device group); injections onto a live "
              f"ring: {mi['zero_drain_admission_overlap']}, admission "
              f"stall {mi['zero_drain_admission_stall_s']}s "
              f"(drain-based arm: {mi['colocated_admission_stall_s']}s)")
        print(f"  token-for-token identical: "
              f"{mi['interference_tokens_match']}")
    if not args.skip_sharded:
        # A box with XLA_FLAGS preset to fewer than 4 virtual devices
        # (the pre-sharded-leg setting was 2) banks the skip instead of
        # losing every other leg's numbers to a crash before the final
        # JSON line.
        try:
            msh = sharded(args.tokens, args.chunk, args.depth, args.loop,
                          args.repeats)
        except RuntimeError as e:
            msh = {"sharded_skipped": str(e)}
            print(f"sharded legs skipped: {e}")
        else:
            _print_sharded(msh)
        m.update(msh)
    if not args.skip_paged:
        mp = paged()
        _print_paged(mp)
        m.update(mp)
    if not args.skip_qos:
        mq = qos()
        _print_qos(mq)
        m.update(mq)
    if not args.skip_dedup:
        md = dedup()
        _print_dedup(md)
        m.update(md)
    print(json.dumps(m), flush=True)
    return 0


def _print_dedup(md: dict) -> None:
    print(f"shared-prefix member dedup (members={md['dedup_members']}, "
          f"{md['dedup_prompt_len']}-token prompt, "
          f"{md['dedup_rounds_driven']} fan-outs):")
    print(f"  prefill tokens computed: {md['dedup_off_prefill_tokens']} -> "
          f"{md['dedup_on_prefill_tokens']} "
          f"({md['dedup_prefill_token_ratio']:.2f}x fewer; "
          f"{md['dedup_rounds']}/{md['dedup_rounds_driven']} fan-outs "
          "coalesced)")
    print(f"  wall: {md['dedup_off_wall_s']}s -> {md['dedup_on_wall_s']}s, "
          f"token-for-token identical: {md['dedup_tokens_match']}")


def _print_paged(mp: dict) -> None:
    print(f"paged KV rows-per-chip (fixed {mp['paged_pool_pages']}-page "
          f"HBM budget, {mp['paged_streams']} short streams):")
    print(f"  dense rectangle: {mp['paged_dense_rows']} rows, peak "
          f"resident {mp['paged_dense_peak_rows']}, "
          f"wall {mp['paged_dense_wall_s']}s")
    print(f"  kv_pages=1     : peak resident {mp['paged_paged_peak_rows']}"
          f", page occupancy {mp['paged_peak_page_occupancy']:.0%}, "
          f"wall {mp['paged_paged_wall_s']}s")
    print(f"  rows/chip: {mp['paged_rows_per_chip_ratio']:.1f}x "
          f"(gate: >= 4x), token-for-token identical: "
          f"{mp['paged_tokens_match']}")


def _print_qos(mq: dict) -> None:
    print(f"qos scheduler A/B ({mq['qos_churn_threads']}-thread batch "
          f"churn, {mq['qos_arrivals']} interactive arrivals):")
    print(f"  solo floor : interactive TTFT p50 "
          f"{mq['qos_solo_ttft_p50_ms']} ms, p99 "
          f"{mq['qos_solo_ttft_p99_ms']} ms (uncontended)")
    for tag, label in (("fifo", "fifo (qos=0)"), ("qos", "qos=1      ")):
        print(f"  {label}: interactive TTFT p50 "
              f"{mq[f'qos_{tag}_interactive_ttft_p50_ms']} ms, p99 "
              f"{mq[f'qos_{tag}_interactive_ttft_p99_ms']} ms; batch "
              f"{mq[f'qos_{tag}_churn_tok_s']} tok/s "
              f"({mq[f'qos_{tag}_churn_streams']} streams)")
    print(f"  p99 fifo/qos: {mq['qos_ttft_p99_ratio']:.2f}x (higher = qos "
          f"insulates better); batch cost: "
          f"{mq['qos_batch_degradation']:.2f}x of fifo throughput; "
          f"preemptions {mq['qos_preemptions']} "
          f"({mq['qos_preempted_tokens']} tokens parked, "
          f"{mq['qos_replayed_tokens']} replayed token-exactly)")


def _print_sharded(msh: dict) -> None:
    print("per-group sharding under disagg (4 devices, matched count):")
    for tag in ("colocated_tp4", "disagg_tp2"):
        pre = f"sharded_{tag}"
        fams = msh.get(f"{pre}_device_seconds", {})
        decode = ", ".join(
            f"{f} p50 {s['p50_ms']}ms (n={s['count']})"
            for f, s in sorted(fams.items()) if f in ("plain", "loop"))
        print(f"  {tag:13}: {msh[f'{pre}_tok_s']} tok/s, "
              f"{msh[f'{pre}_dispatches_per_request']:.1f} dispatches/req, "
              f"{msh[f'{pre}_handoff_bytes_per_s']} handoff B/s "
              f"({msh[f'{pre}_handoff_bytes']} B); {decode}")
    print(f"  token-for-token identical: {msh['sharded_tokens_match']}")


if __name__ == "__main__":
    raise SystemExit(main())
