"""Shared by the benchmark's CPU tests: paths, the child environment, and the
whole command run in rehearsal. Nothing here imports jax: the command's
children do."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import check_line  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAFFIC = sorted({w["traffic"] for w in BENCH["workloads"]})
WINDOW_S = 4.0
TAG = "[REHEARSAL on the CPU at tiny size - not a chip result] "
# what only a device trace or device memory gives: absent from a CPU rehearsal
CHIP_ONLY = {"hbm_peak_gb", "decode_step_ms", "prefill_ms_per_ktok",
             "decode_step_roofline", "prefill_roofline", "device_idle_share"}


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_file(mix: str) -> dict:
    return load(os.path.join(BENCH_DIR, "traffic", mix + ".json"))


def cell_metrics(kind: str, cell: str) -> list[str]:
    return [m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]]


def child_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", QUORUM_TPU_COMPILE_CACHE="0")
    env.pop("XLA_FLAGS", None)  # one CPU device is what the server gets
    return env


def rehearse(cell: str, trace: int, out: str, seed: int = 3000000019,
             faults=(), repo: str = REPO, bench: dict = BENCH) -> dict:
    """The whole command at tiny size on the CPU. Asserts exit 0 and a last
    line that keeps the contract; returns the run's parts."""
    argv = [sys.executable, os.path.join(repo, "benchmarks", "run.py"),
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(WINDOW_S), "--trace", str(trace), "--rehearsal", "--out", out]
    for fault in faults:
        argv += ["--inject-fault", fault]
    proc = subprocess.run(argv, capture_output=True, text=True, timeout=600,
                          cwd=repo, env=child_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith(TAG), lines[-1][:200]
    last = lines[-1][len(TAG):]
    assert check_line.problems(last, bench, cell, trace, on_chip=False) == []
    return {"steps": {d["step"]: d for d in map(json.loads, lines[:-1])},
            "result": json.loads(last), "stderr": proc.stderr, "out": out,
            "records": load(os.path.join(out, "records.json"))["records"]}
