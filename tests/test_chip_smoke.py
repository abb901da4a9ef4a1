"""chip_smoke.py and the no-fallback rules it relies on.

The smoke itself needs a chip; what Tier-1 can hold is its CPU rehearsal (the
same legs at tiny presets, kernels interpreted), the refusals — no TPU and no
``JAX_PLATFORMS=cpu`` means no server and no smoke — and that a quorum missing
a member says so on ``/health`` and ``/ready`` instead of answering 200 with
the survivors' text.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import make_client, two_backend_parallel_config
from quorum_tpu.backends.fake import FakeBackend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(args, env, timeout):
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_passes_at_tiny_size(tmp_path):
    """Every leg — kernels (interpreted), the shipped quorum config, its warm
    restart, the single full-width backend — through ``serve`` over a socket;
    every line says it is a rehearsal; the cache lands where it was put."""
    smoke = _load_smoke()
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = _run_smoke(["--rehearsal", "--out", str(tmp_path / "out")], env,
                      timeout=600)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    assert all(ln.startswith(smoke.REHEARSAL_TAG) for ln in lines)
    # The last line is the verdict with exactly the keys the driver parses;
    # the smoke's own detail is the line before it.
    got = json.loads(lines[-1][len(smoke.REHEARSAL_TAG):])
    assert set(got) == {"ok", "device"} and got["ok"] is True
    assert set(got["device"]) == {"platform", "kind", "count"}
    assert got["device"]["platform"] == "cpu"
    assert isinstance(got["device"]["kind"], str)
    assert isinstance(got["device"]["count"], int)
    run = json.loads(lines[-2][len(smoke.REHEARSAL_TAG):])["smoke"]
    assert run["rehearsal"] is True
    assert run["compile_cache_dir"] == str(cache) and os.listdir(cache)
    legs = run["legs"]
    assert set(legs) == {"kernels", "quorum", "quorum_restart", "full_width"}
    assert legs["kernels"]["interpret"] is True
    assert legs["quorum_restart"]["cache"]["hits"] > 0
    for leg, members in (("quorum", 3), ("full_width", 1)):
        assert legs[leg]["device"]["platform"] == "cpu"
        assert legs[leg]["non_streaming_usage"]["completion_tokens"] >= members
        assert legs[leg]["rows_per_decode_chunk"] > 1
        assert set(legs[leg]["prefill_attention_paths"].values()) == {"xla"}
    assert all(d.keys() >= {"member-0", "member-1", "member-2", "final"}
               for d in legs["quorum"]["stream_deltas"])


def test_default_invocation_requires_a_tpu(tmp_path):
    """No chip: non-zero exit and no result line — with JAX_PLATFORMS unset
    (jax falls back to the CPU by itself) and with the CPU asked for (a CPU
    is still not what the default run is for)."""
    for platforms in (None, "cpu"):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        if platforms:
            env["JAX_PLATFORMS"] = platforms
        proc = _run_smoke(["--out", str(tmp_path / "out")], env, timeout=300)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "requires 'tpu'" in proc.stderr


def test_serve_refuses_a_silent_cpu(tmp_path):
    """JAX_PLATFORMS unset and no chip: a tpu:// config does not start."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "quorum_tpu.server.serve", "--config",
         os.path.join(REPO, "config.yaml"), "--port", "0",
         "--log-dir", str(tmp_path)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "jax found no TPU" in proc.stderr


def test_no_accelerator_error_is_not_swallowed(monkeypatch):
    from quorum_tpu.backends.registry import build_registry
    from quorum_tpu.config import Config
    from quorum_tpu.devices import NoAcceleratorError, serving_devices

    assert serving_devices()[0].platform == "cpu"  # asked for by name
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(NoAcceleratorError):
        serving_devices()
    cfg = Config(raw={"primary_backends": [
        {"name": "LLM1", "url": "tpu://gpt2-tiny", "model": "m"}]})
    with pytest.raises(NoAcceleratorError):
        build_registry(cfg)


async def test_missing_member_is_degraded_and_unready():
    """A configured backend that failed to construct: requests still
    degrade to the survivors, but /health names it and /ready stays 503."""
    raw = two_backend_parallel_config()
    raw["primary_backends"].append(
        {"name": "LLM3", "url": "tpu://no-such-model", "model": "m"})
    async with make_client(
            raw, LLM1=FakeBackend("LLM1", text="a"),
            LLM2=FakeBackend("LLM2", text="b")) as client:
        health = (await client.get("/health")).json()
        assert health["status"] == "degraded"
        row, = [r for r in health["checks"] if r["backend"] == "LLM3"]
        assert row["constructed"] is False and "no-such-model" in row["error"]
        ready = await client.get("/ready")
        assert ready.status_code == 503
        assert ready.json()["reason"] == "degraded"
        resp = await client.post(
            "/chat/completions", headers={"Authorization": "Bearer t"},
            json={"model": "m", "messages": [{"role": "user", "content": "q"}]})
        assert resp.status_code == 200  # the reference's contract stays


def test_smoke_refuses_a_quorum_with_a_missing_member(tmp_path):
    """The same condition seen from the smoke: the leg fails on the missing
    member instead of accepting two members' text with a 200."""
    smoke = _load_smoke()
    with open(os.path.join(REPO, "config.yaml")) as f:
        shipped = f.read()
    bad = tmp_path / "config.yaml"
    bad.write_text(
        shipped.replace("tpu://gpt2?", "tpu://gpt2-tiny?")
        .replace("tpu://gpt2-tiny?members=3&member=2",
                 "tpu://no-such-model?members=3&member=2"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with pytest.raises(smoke.SmokeFailure, match="LLM3.*constructed"):
        smoke.serve_leg("quorum", str(bad), 3, True, str(tmp_path), env,
                        full=True)
