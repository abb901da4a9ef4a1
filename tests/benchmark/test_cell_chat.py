"""``mistral-7b.chat`` in rehearsal: the whole command on the CPU at a tiny
preset, untraced and traced, and a traced run with three of its parts broken.
The plain reference against the engine rides on the untraced run's probe."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import (BENCH_DIR, CHIP_ONLY, REPO, WINDOW_S, cell_metrics,  # noqa: E402,E501
                      child_env, load, rehearse, traffic_file)

CELL = "mistral-7b.chat"


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("chat_u")))


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("chat_t")),
                    seed=2147483650)


@pytest.fixture(scope="module")
def broken(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("chat_b")),
                    faults=("profile", "span", "first_token"))


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced):
    result = untraced["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(cell_metrics("end_to_end", CELL))
    assert "tokens_per_s" not in result["metrics"]  # an open loop's is the
    assert "ttft_mean_ms" not in result["metrics"]  # generator's; 38 requests'
    assert "latency_p50_ms" in result["metrics"]    # TTFT spreads 10 %: PERF.md
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"


def test_untraced_run_prints_the_clients_view_on_an_earlier_line(untraced):
    view = untraced["steps"]["window client view"]
    assert view["requests"] == untraced["result"]["attempted"]
    for key in ("ttft_mean_ms", "ttft_p50_ms", "ttft_p90_ms",
                "latency_p50_ms", "offered_tokens_per_s"):
        assert view[key] > 0, key
    assert view["ttft_p50_ms"] <= view["ttft_p90_ms"]


def test_window_counts_only_requests_due_inside_it_all_completed(untraced):
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    assert window and len(window) == untraced["result"]["attempted"]
    assert len(window) == round(traffic_file("chat")["rate_per_s"] * 4  # the
                                # rehearsal's rate_mul
                                * WINDOW_S)
    for r in window:
        assert 0.0 <= r["due"] < WINDOW_S
        assert r["end"] is not None and r["status"] == 200
        s = r["streams"]["single"]
        assert s["tokens"] == r["max_tokens"] or (
            s["finish"] == "stop" and s["tokens"] < r["max_tokens"])
    others = [r for r in untraced["records"] if r["phase"] != "window"]
    assert all(r["due"] < 0 or r["due"] >= WINDOW_S for r in others)
    assert any(r["phase"] == "ramp" for r in others)


def test_setup_steps_are_timed_on_earlier_lines(untraced):
    steps = untraced["steps"]
    assert steps["server ready"]["seconds_from_spawn"] > 0
    warm = steps["warm-up done"]["requests"]
    assert len(warm) == len(traffic_file("chat")["warmup"])
    assert all(w["seconds"] > 0 for w in warm)
    assert steps["window starts"]["setup_s"] == pytest.approx(
        untraced["result"]["metrics"]["setup_s"]["value"], abs=0.01)
    assert len(steps["window ends"]["queue_wait_ms_by_third"]) == 3


def test_reference_agrees_with_the_engine_at_a_tiny_preset(untraced):
    ref = untraced["steps"]["reference compared"]
    assert ref["ok"] is True
    assert ref["compared"] >= 4 * 6  # 4 prompts: prefill, then decode through
    assert len(ref["detail"]) == 4   # the cache; each position's error is said
    assert all(len(d["abs_err"]) == d["positions"] for d in ref["detail"])
    assert ref["median_abs_err"] <= ref["max_abs_err"] <= ref["tol"]["max"]
    assert ref["median_abs_err"] <= ref["tol"]["median"]


@pytest.mark.parametrize("shift,positions,want", [
    (-1.0, "all", False),   # a fault: past the limit on any one position
    (-0.3, "all", False),   # a fault: every position off, each under it
    (-0.4, "one", True),    # noise: one position far off, the rest exact
    (-0.7, "one", True)],   # the tail: sought again within the wider limit
    ids=["all-off-by-1.0", "all-off-by-0.3", "one-off-by-0.4",
         "one-off-by-0.7"])
def test_reference_tells_a_fault_from_quantization_noise(
        untraced, tmp_path, shift, positions, want):
    job = load(os.path.join(untraced["out"], "probe.json"))
    assert job["tol"] == {"max": 1.0, "first": 0.5, "median": 0.1,
                          "prompts": 4}  # int8 weights
    for p in job["probes"]:
        n = len(p["token_logprobs"]) if positions == "all" else 1
        p["token_logprobs"] = [v + shift if i >= len(p["token_logprobs"]) - n
                               else v
                               for i, v in enumerate(p["token_logprobs"])]
    bad = tmp_path / "probe_shifted.json"
    bad.write_text(json.dumps(job))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "reference_check.py"),
         str(bad)], capture_output=True, text=True, timeout=300, cwd=REPO,
        env=child_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is want, verdict


def test_the_control_at_the_next_precision_down_is_not_correct(untraced,
                                                              tmp_path):
    """The reference with int4 weights in the int8 program's place, at the
    served ids: outside the limits the served path keeps (on the chip at the
    cell's own size: PERF.md section 2a). No benchmark run runs it."""
    job = dict(load(os.path.join(untraced["out"], "probe.json")),
               control="int4")
    path = tmp_path / "probe_control.json"
    path.write_text(json.dumps(job))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "reference_check.py"),
         str(path)], capture_output=True, text=True, timeout=300, cwd=REPO,
        env=child_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    control = verdict["control"]
    assert verdict["ok"] is True and control["ok"] is False, verdict
    assert control["compared"] == verdict["compared"]
    assert control["median_abs_err"] > 3 * job["tol"]["median"]
    assert control["median_abs_err"] > 10 * verdict["median_abs_err"]


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want
    assert {"ttft_max3_ms.open", "queue_wait_ms.open"} <= want
    assert not {"queue_wait_ms", "prefill_roofline"} & want
    for name in CHIP_ONLY & set(cell_metrics("per_layer", CELL)):
        assert f"traced run: {name} left out" in traced_run["stderr"]
    assert result["metrics"]["window_compiles"]["value"] == 0.0


def test_traced_run_reads_before_the_profile_and_profiles_to_the_windows_end(
        traced_run):
    steps = traced_run["steps"]
    parts = steps["traced parts"]
    read_until = steps["window ends"]["read_until_s"]
    assert read_until == pytest.approx(WINDOW_S - WINDOW_S / 2 - 0.5)
    assert parts["profile"]["status"] == 200
    # after the last scrape, and early enough to end with the window
    assert read_until <= parts["profile"]["posted_at_s"] <= read_until + 0.5
    assert parts["spans"] >= 1
    # the profile is deleted once reduced: the tree stays small enough to copy
    assert not os.path.exists(os.path.join(traced_run["out"], "profiles"))


def test_broken_profile_span_and_first_token_still_end_in_a_valid_line(broken):
    """rehearse() has held the run to exit 0 and the contract's line."""
    err = broken["stderr"]
    assert "traced run: device trace left out: /debug/profile answered 400" \
        in err
    assert "traced run: spans left out: 1 of" in err
    assert "benchmark FAILED" not in err
    assert broken["steps"]["traced parts"]["profile"]["status"] == 400
    result = broken["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert "first_token_host_ms.open" in result["metrics"]
    stripped = [r for r in broken["records"]
                if r["phase"] == "window" and r["first"] is None]
    assert len(stripped) == 1
