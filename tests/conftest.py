"""Shared test configuration.

JAX-dependent tests run on CPU with a virtual 8-device mesh — the standard way
to exercise sharding logic without TPU hardware (see SURVEY.md §4). The env vars
must be set before the first ``import jax`` anywhere in the test process, hence
this conftest sets them at import time.
"""

import os

# Force, don't setdefault: the suite runs on the virtual CPU mesh whatever
# the caller's environment says (a tpu:// backend refuses to construct on a
# CPU that was not asked for by name — parallel/mesh.serving_devices).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compile cache: OFF for the suite. It used to default on
# here for warm-run speed, and that was the root cause of the flaky
# determinism failures in tests/test_engine.py (and friends): with the
# cache enabled, the FIRST generation on a fresh engine occasionally runs a
# decode program deserialized from an entry another engine instance's
# compile wrote, while later calls recompile a layout-specialized variant —
# two numerically different (both valid) executables of the same program,
# whose float reassociation flips near-tie samples. Two identical
# back-to-back generations then disagree (reproduced ~50% per engine with
# the cache on, 0/12 with it off; see compile_cache.py's CPU caveat).
# Correctness of the determinism contract beats warm-suite time; an
# explicit QUORUM_TPU_COMPILE_CACHE=<dir> in the env still wins for anyone
# who wants the speed and accepts the flake.
os.environ.setdefault("QUORUM_TPU_COMPILE_CACHE", "0")

# Runtime sync sentinel (docs/static_analysis.md): every engine in the
# suite runs its decode loop under jax.transfer_guard("disallow") — an
# implicit host<->device transfer on the token critical path raises
# instead of silently stalling the dispatch ring. The static half is
# `make qlint`; an explicit QUORUM_TPU_TRANSFER_GUARD in the env wins.
os.environ.setdefault("QUORUM_TPU_TRANSFER_GUARD", "disallow")

# Lowering-counter hook (quorum_tpu/analysis/compile_watch.py): registered
# before any engine exists so compiles_total() covers the whole suite. The
# warmed-engine zero-recompile sentinel in tests/test_qlint.py snapshots it
# around a second identical generation — any new program family (a cache-key
# drift compile_budget.json missed) fails loudly.
from quorum_tpu.analysis import compile_watch  # noqa: E402

compile_watch.install()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _shutdown_engines_between_modules():
    """Join every engine's scheduler thread and drop its device state after
    each test module. Without this the suite accumulates dozens of live
    threads + parameter/cache buffers across ~30 modules, and a straggler
    thread running device work while the next module compiles can segfault
    XLA's CPU client (observed on the 1-core CI box)."""
    yield
    from quorum_tpu.engine.engine import shutdown_all_engines

    shutdown_all_engines()


def make_client(config_raw: dict, **fake_backends):
    """Build the ASGI app over FakeBackends and an httpx client bound to it.

    The idiomatic replacement for the reference suite's httpx monkeypatching
    (see SURVEY.md §4): tests inject Backend-protocol doubles by name.
    """
    import httpx

    from quorum_tpu.config import Config
    from quorum_tpu.server.app import create_app

    app = create_app(Config(raw=config_raw), **fake_backends)
    transport = httpx.ASGITransport(app=app)
    return httpx.AsyncClient(transport=transport, base_url="http://testserver")


def two_backend_parallel_config(strategy: str = "concatenate", **strategy_overrides):
    """A 2-backend parallel config skeleton used across endpoint tests."""
    concatenate = {
        "separator": "\n---\n",
        "hide_intermediate_think": True,
        "hide_final_think": False,
        "thinking_tags": ["think"],
        "skip_final_aggregation": False,
    }
    aggregate = {
        "source_backends": "all",
        "aggregator_backend": "",
        "intermediate_separator": "\n\n---\n\n",
        "include_source_names": False,
        "thinking_tags": ["think"],
    }
    if strategy == "concatenate":
        concatenate.update(strategy_overrides)
    else:
        aggregate.update(strategy_overrides)
    return {
        "settings": {"timeout": 5},
        "primary_backends": [
            {"name": "LLM1", "url": "http://test1.example.com/v1", "model": "model-1"},
            {"name": "LLM2", "url": "http://test2.example.com/v1", "model": "model-2"},
        ],
        "iterations": {"aggregation": {"strategy": strategy}},
        "strategy": {"concatenate": concatenate, "aggregate": aggregate},
    }


class StubRequest:
    """What a stub engine's ``submit`` hands a TpuBackend: the fields of the
    engine's request handle that the backend reads or stamps."""

    parked = False
    t_delta = None

    def __init__(self, script=(), cancel=None):
        self.script, self.cancel, self.lp = script, cancel, []

    def mark_first_delta(self) -> None:
        import time

        self.t_delta = time.perf_counter()


class ParallelStreamCollector:
    """Buckets a parallel quorum's SSE stream by chunk id: per-member
    ``chatcmpl-parallel-{i}`` content deltas into ``texts[i]`` and the
    ``chatcmpl-parallel-final`` combined text into ``final`` — the
    streaming wire contract several endpoint tests assert against."""

    def __init__(self):
        self.texts: dict[int, list[str]] = {}
        self.final: list[str] = []

    def feed_line(self, line: str) -> None:
        import json

        if not line.startswith("data: ") or line == "data: [DONE]":
            return
        chunk = json.loads(line[len("data: "):])
        cid = chunk.get("id", "")
        for ch in chunk.get("choices") or []:
            delta = (ch.get("delta") or {}).get("content")
            if not delta:
                continue
            if cid == "chatcmpl-parallel-final":
                self.final.append(delta)
            elif cid.startswith("chatcmpl-parallel-"):
                self.texts.setdefault(
                    int(cid.rsplit("-", 1)[1]), []).append(delta)

    def stream(self, i: int) -> str:
        return "".join(self.texts[i])


# Minimal built-in async-test support (pytest-asyncio is not in this image):
# run ``async def`` tests via asyncio.run.
@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    if inspect.iscoroutinefunction(pyfuncitem.obj):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(pyfuncitem.obj(**kwargs))
        return True
    return None
