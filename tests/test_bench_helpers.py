"""bench.py's orchestration: phase children in order, failing when one fails.

bench.py is not what the round's driver measures with; these pins keep it
honest — a failed phase never yields a result line, utilisation is quoted
only against a device it knows, and every line names the device it ran on.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_last_json_line():
    bench = _load_bench()
    stdout = 'log noise\n{"a": 1}\n{"trunca'
    assert bench._last_json_line(stdout) == {"a": 1}
    assert bench._last_json_line("no json at all") is None
    assert bench._last_json_line(None) is None
    # latest intact line wins
    assert bench._last_json_line('{"a":1}\n{"b":2}') == {"b": 2}


def test_ab_keys_rekeys_top_level_schema():
    """The separate-engines A/B arm must merge BESIDE the stacked headline
    (ab_* keys), never clobber it."""
    bench = _load_bench()
    got = {"metric": "p50_ttft_ms", "value": 91.0, "unit": "ms",
           "p50_total_ms": 300.0, "req_per_s": 2.5, "tokens_per_s": 290.0,
           "mfu_pct": 0.1, "stacked": False, "platform": "tpu"}
    out = bench._ab_keys(got)
    assert out == {"ab_p50_ttft_ms": 91.0, "ab_p50_total_ms": 300.0,
                   "ab_req_per_s": 2.5, "ab_tokens_per_s": 290.0,
                   "ab_stacked": False}


def test_orchestrator_runs_phase_children_in_order(monkeypatch, capsys):
    """Off the CPU every enabled phase runs as a child, one after another
    (headline → int8 b7q → A/B arm with STACKED=0 → b7 → ckpt), and the A/B
    arm's schema lands re-keyed BESIDE the headline."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    bench = _load_bench()
    calls = []

    def fake_child(flag, prefix, budget, env_extra=None):
        calls.append((prefix, env_extra))
        device = {"platform": "tpu", "device_kind": "TPU v5 lite",
                  "device_count": 1}
        if prefix == "phase12":
            return {**device, "metric": "p50_ttft_ms", "value": 50.0,
                    "tokens_per_s": 400.0, "stacked": True}
        if prefix == "ab":
            return {**device, "metric": "p50_ttft_ms", "value": 80.0,
                    "tokens_per_s": 300.0, "stacked": False}
        return {**device, f"{prefix}_decode_tok_s": 1.0}

    monkeypatch.setattr(bench, "run_child_phase", fake_child)
    bench.main()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [c[0] for c in calls] == ["phase12", "b7q", "ab", "b7", "ckpt"]
    assert calls[2][1] == {"QUORUM_TPU_BENCH_STACKED": "0"}
    assert rec["value"] == 50.0 and rec["ab_p50_ttft_ms"] == 80.0
    assert rec["stacked"] is True and rec["ab_stacked"] is False
    assert rec["b7_decode_tok_s"] == 1.0 and rec["b7q_decode_tok_s"] == 1.0
    assert rec["device_kind"] == "TPU v5 lite" and rec["platform"] == "tpu"


def test_orchestrator_stays_off_jax():
    """A chip belongs to one process at a time: nothing at bench.py's module
    level, nor the orchestrator's own functions, imports jax — only the
    phase children do."""
    import ast

    with open(os.path.join(REPO, "bench.py")) as f:
        tree = ast.parse(f.read())

    def imports_jax(nodes):
        for node in nodes:
            for sub in ast.walk(node):
                names = ([a.name for a in sub.names]
                         if isinstance(sub, ast.Import)
                         else [sub.module or ""]
                         if isinstance(sub, ast.ImportFrom) else [])
                if any(n == "jax" or n.startswith(("jax.", "quorum_tpu"))
                       for n in names):
                    return True
        return False

    top = [n for n in tree.body
           if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    parent = [n for n in tree.body if isinstance(n, ast.FunctionDef)
              and n.name in ("main", "run_child_phase", "_run_json_subprocess",
                             "_last_json_line", "_ab_keys")]
    assert len(parent) == 5
    assert not imports_jax(top) and not imports_jax(parent)


def test_failed_phase_fails_the_run(monkeypatch, capsys):
    """No path prints a result after a phase failed: a child that exits
    non-zero (or prints no JSON, or outlives its budget) raises, the later
    phases do not run, and nothing reaches stdout."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    bench = _load_bench()
    ran = []

    def fake_run(argv, **kw):
        ran.append(argv[-1])
        if argv[-1] == "--7bq":
            return subprocess.CompletedProcess(
                argv, 1, stdout='{"b7q_decode_tok_s": 12.5}\n',
                stderr="RuntimeError: device lost")
        return subprocess.CompletedProcess(
            argv, 0, stdout='{"value": 50.0}\n', stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(bench.PhaseFailed, match="b7q: exit code 1"):
        bench.main()
    assert ran == ["--phase12", "--7bq"]
    assert capsys.readouterr().out == ""

    def no_json(argv, **kw):
        return subprocess.CompletedProcess(argv, 0, stdout="noise\n",
                                           stderr="")

    monkeypatch.setattr(subprocess, "run", no_json)
    with pytest.raises(bench.PhaseFailed):
        bench.run_child_phase("--phase12", "phase12", 5)

    def hangs(argv, **kw):
        raise subprocess.TimeoutExpired(cmd=argv, timeout=kw["timeout"])

    monkeypatch.setattr(subprocess, "run", hangs)
    with pytest.raises(bench.PhaseFailed, match="no result after 5s"):
        bench.run_child_phase("--phase12", "phase12", 5)


def test_peaks_are_keyed_by_device_kind(monkeypatch):
    """One table with its source, keyed by jax's device_kind; a device that
    is not in it is an error, and utilisation keys are absent off-TPU."""
    bench = _load_bench()
    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1})
    assert bench._peaks()["bf16_flops"] == 197e12
    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v9 imaginary",
        "device_count": 1})
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        bench._peaks()
    # this process runs on the CPU: no utilisation keys at all
    monkeypatch.undo()
    core = bench._core_7b_metrics(
        "llama-tiny", "b7", False, [10.0], 15.0, [0.05], 0.2, 0.1)
    assert "b7_decode_tok_s" in core
    assert not [k for k in core if k.endswith(("_util_pct", "_mfu_pct"))]


@pytest.mark.slow  # engine-scale: int8 engine + 8192 window + 5k prefill
def test_7bq_child_end_to_end_tiny():
    """The int8 child (--7bq: quantized serving + prefix-cache + co-batch +
    5k-token chunked-prefill long-context) end to end on a tiny model; the
    JSON line names the device and carries the b7q_* schema including the
    long-context keys, without utilisation keys on the CPU."""
    bench = _load_bench()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["QUORUM_TPU_BENCH_7B_QUANT"] = "1"
    env["QUORUM_TPU_BENCH_7B_QUANT_MODEL"] = "llama-tiny"
    env["QUORUM_TPU_BENCH_7B_MAX_TOKENS"] = "24"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--7bq"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = bench._last_json_line(proc.stdout)
    assert rec, proc.stdout[-500:]
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert rec["b7q_model"] == "llama-tiny+int8"
    assert rec["b7q_decode_tok_s"] > 0 and rec["b7q_ttft_ms"] > 0
    assert rec["b7q_tok_s_c2"] > 0
    assert rec["b7q_prefix_cold_ttft_ms"] >= rec["b7q_prefix_warm_ttft_ms"] > 0
    assert "b7q_hbm_bw_util_pct" not in rec
    # the long-context phase really ran against the 8192 window
    assert rec["b7q_long_prompt_tokens"] == 5000
    assert rec["b7q_long_ttft_ms"] > 0 and rec["b7q_long_decode_tok_s"] > 0
