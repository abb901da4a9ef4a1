"""Smoke over the host-path microbench (``make hostpath-bench``).

Runs the same entry point the Makefile target runs, at a budget small
enough for the fast tier (NOT slow-marked — this is the CPU-measurable
proof of the decode-dispatch pipeline and the megachunk decode loop, wired
into every suite run), and pins the dispatch accounting the bench reports:

  - strictly fewer blocking host syncs per request at K=4 than K=1 for a
    >=8-chunk generation (the ISSUE acceptance counter check)
  - dispatches/request reduced ~C× at decode_loop=C with blocking
    syncs/request still <= 1 (the megachunk acceptance)
  - zero overrun tokens when rows finish on device
  - token-for-token identical output across depths AND fusion
  - the prefill-interference legs (colocated vs colocated+zero_drain vs
    disagg=1+1, ISSUE 11) produce the streamed tokens identically with a
    live device→device KV handoff on the disagg arm and zero admission
    stall on the zero-drain arm (the p99-gap ORDERING is the bench's
    printed acceptance number, not a suite assertion — wall-clock
    percentiles on a shared CI core flake)
"""

from scripts.hostpath_bench import (dedup, interference, paged, qos, run,
                                    sharded)


def test_hostpath_bench_counters():
    m = run(tokens=32, chunk=4, depth=4, repeats=1, loop=4)
    assert m["k1_dispatches_per_request"] >= 8
    assert m["k4_syncs_per_request"] < m["k1_syncs_per_request"]
    assert m["k1_overrun_tokens"] == 0
    assert m["k4_overrun_tokens"] == 0
    assert m["loop4_overrun_tokens"] == 0
    # Megachunk acceptance: one dispatch covers ~C chunks (8 chunks at
    # C=4 → 2-3 dispatches), and the host still blocks at most about once
    # per request (the first dispatch of each generation).
    assert m["loop4_dispatches_per_request"] <= m["k1_dispatches_per_request"] / 2
    assert m["loop4_syncs_per_request"] <= 1.5
    assert m["loop_dispatch_reduction"] >= 2.0
    assert m["tokens_match"] is True
    assert 0.0 <= m["host_turnaround_share"] < 1.0
    assert m["loop4_drain_gap_ms_per_dispatch"] >= 0.0
    # Per-family device-seconds attribution (ISSUE 12): the unfused legs'
    # decode time lives under "plain", the megachunk leg's under "loop",
    # with sane percentiles from the engine's LatencyModel reservoir.
    assert "plain" in m["k1_device_seconds"], m["k1_device_seconds"]
    assert "loop" in m["loop4_device_seconds"], m["loop4_device_seconds"]
    for leg in ("k1", "k4", "loop4"):
        for fam, stats in m[f"{leg}_device_seconds"].items():
            assert stats["count"] > 0, (leg, fam)
            assert 0.0 <= stats["p50_ms"] <= stats["p99_ms"], (leg, fam)


def test_interference_bench_smoke():
    m = interference(tokens=24, chunk=4, depth=4, loop=4, churn=2,
                     churn_prompt_tokens=40)
    for tag in ("colocated", "zero_drain", "disagg"):
        for p in ("p50", "p95", "p99"):
            assert m[f"{tag}_intertoken_{p}_ms"] >= 0.0
    # The disagg leg really ran disaggregated: its stream equals the
    # colocated stream token for token, and KV crossed the group boundary.
    assert m["interference_tokens_match"] is True
    assert m["disagg_kv_handoffs"] >= 1
    assert m["disagg_kv_handoff_bytes"] > 0
    # The zero-drain leg really injected: zero admission stall
    # (structurally — pressure never clamps the ring), zero handoff bytes
    # (one device group), and the p99 ratios are finite numbers (their
    # ORDERING is the bench's printed acceptance; wall-clock percentiles
    # on a shared CI core flake).
    assert m["zero_drain_admission_stall_s"] == 0.0
    assert m["zero_drain_p99_vs_disagg"] >= 0.0
    assert m["zero_drain_p99_vs_colocated"] >= 0.0
    assert m["zero_drain_admission_overlap"] >= 0
    # Per-family device-seconds per arm (ISSUE 12): every arm decoded
    # fused megachunks ("loop"), and the staged arms' segments are booked
    # under their own family. (The injection programs, "hput", run in a
    # decode chunk's company and take none of its seconds: the device
    # ledger makes no observation of a program it booked nothing.)
    for tag in ("colocated", "zero_drain", "disagg"):
        assert "loop" in m[f"{tag}_device_seconds"], (
            tag, m[f"{tag}_device_seconds"])
    assert "seg" in m["zero_drain_device_seconds"]
    assert "seg" in m["disagg_device_seconds"]


def test_sharded_bench_smoke():
    """The per-group-sharding legs (ISSUE 14): both arms stream
    token-for-token identical output at matched device count, and the
    disagg arm moves KV across the group boundary via the on-the-fly
    reshard route (tok/s ORDERING is the bench's printed number —
    wall-clock on a shared CI core flakes)."""
    m = sharded(tokens=16, chunk=4, depth=2, loop=2, repeats=1)
    assert m["sharded_tokens_match"] is True
    assert m["sharded_disagg_tp2_handoff_bytes"] > 0, m
    assert m["sharded_disagg_tp2_handoff_bytes_per_s"] > 0, m
    assert m["sharded_colocated_tp4_handoff_bytes"] == 0
    for tag in ("colocated_tp4", "disagg_tp2"):
        assert m[f"sharded_{tag}_tok_s"] > 0
        assert m[f"sharded_{tag}_dispatches_per_request"] > 0


def test_paged_bench_smoke():
    """The paged-KV rows-per-chip legs (ISSUE 17): at a fixed cache
    position budget the paged engine keeps strictly more short streams
    resident than the dense rectangle's slot count, fills the page pool,
    and every stream's tokens match its dense twin (the >= 4x ratio is
    the bench's printed acceptance gate; the suite asserts the ordering
    — peak concurrency sampling on a shared CI core flakes)."""
    m = paged(tokens=8, streams=24, page_size=16, pool_pages=32)
    assert m["paged_tokens_match"] is True
    assert m["paged_dense_completed"] == m["paged_paged_completed"] == 24
    # the fixed budget buys the dense arm max_seq-sized rows only
    assert m["paged_dense_peak_rows"] <= m["paged_dense_rows"]
    # strictly more rows resident at once under paging, pool never over-
    # committed (admission pre-reserves each row's whole span)
    assert m["paged_paged_peak_rows"] > m["paged_dense_rows"]
    assert m["paged_rows_per_chip_ratio"] >= 2.0
    assert 0.0 < m["paged_peak_page_occupancy"] <= 1.0


def test_qos_bench_smoke():
    """The QoS scheduler A/B legs (ISSUE 18, docs/scheduling.md): both
    arms complete mixed interactive+batch churn, preemptions fire on the
    qos arm with every parked token replayed (token-exactness itself is
    pinned by tests/test_sched.py), and the ratios are finite numbers
    (the fifo/qos p99 ORDERING is the bench's printed acceptance —
    wall-clock percentiles on a shared CI core flake)."""
    m = qos(tokens=24, churn=3, arrivals=4)
    for tag in ("fifo", "qos"):
        assert m[f"qos_{tag}_interactive_ttft_p50_ms"] >= 0.0
        assert m[f"qos_{tag}_interactive_ttft_p99_ms"] >= \
            m[f"qos_{tag}_interactive_ttft_p50_ms"] - 1e-9
        assert m[f"qos_{tag}_churn_streams"] > 0
        assert m[f"qos_{tag}_churn_tok_s"] > 0
    assert m["qos_solo_ttft_p50_ms"] >= 0.0
    # The qos arm really scheduled: preemptions fired and every parked
    # token was regenerated through the replay guard.
    assert m["qos_preemptions"] >= 1, m
    assert m["qos_preempted_tokens"] >= 1
    assert m["qos_replayed_tokens"] == m["qos_preempted_tokens"]
    assert m["qos_ttft_p99_ratio"] > 0.0
    assert m["qos_batch_degradation"] > 0.0


def test_dedup_bench_smoke():
    """The shared-prefix member dedup A/B leg (docs/quorum.md): dedup-on
    output stays token-for-token identical to dedup-off, every coalesced
    fan-out saves exactly (members-1)*prompt_len prefill tokens, and the
    reported ratio reflects a real reduction (the WALL ordering is the
    bench's printed acceptance — wall-clock on a shared CI core flakes)."""
    m = dedup(prompt_len=24, tokens=4, members=3, rounds=4)
    assert m["dedup_tokens_match"] is True
    assert 1 <= m["dedup_rounds"] <= m["dedup_rounds_driven"]
    # Exact per-admission savings arithmetic: each coalesced fan-out
    # prefills the prompt once instead of `members` times.
    assert (m["dedup_off_prefill_tokens"] - m["dedup_on_prefill_tokens"]
            == m["dedup_rounds"] * (3 - 1) * 24)
    assert m["dedup_prefill_token_ratio"] > 1.0
    assert m["dedup_off_wall_s"] >= 0.0 and m["dedup_on_wall_s"] >= 0.0
