"""The system under test as one child process, and the plain HTTP the
benchmark speaks to it. Nothing here imports jax: the child holds the chip.

Copied in pattern from ``chip_smoke.py`` (JAX-free parent, ``serve`` as the
one child, SIGINT then SIGKILL of the whole session, ``/metrics`` scraping);
the original stays where it is as the start-up check.
"""

from __future__ import annotations

import contextlib
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

HEADERS = {"Authorization": "Bearer benchmark",
           "Content-Type": "application/json"}

# The shipped config.yaml's strategy section, restated: concatenate, the
# thinking-tag filter on intermediate answers, the final join kept.
STRATEGY_YAML = """\
iterations:
  aggregation:
    strategy: "concatenate"
strategy:
  concatenate:
    separator: "\\n-------------\\n"
    hide_intermediate_think: true
    hide_final_think: false
    thinking_tags: ["think", "reason", "reasoning", "thought"]
    skip_final_aggregation: false
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def weight_seed(seed: int) -> int:
    """The weights' seed: the run's ``--seed`` modulo 2. The engine's init
    program closes over its seed, so every new weight seed is a new program:
    27 s of compilation inside set-up the first time a seed is met (dense
    configurations, PR 23's chip runs; PERF.md). Random weights of one
    distribution run at one speed, so two weight sets lose nothing, and after
    a checkout's first two runs set-up compiles nothing. Token ids, order and
    arrival times take the whole seed."""
    return seed % 2


def write_server_config(path: str, backends: list[dict], timeout_s: float,
                        seed: int) -> None:
    """The served configuration as a config.yaml literal, the weight seed
    put into every backend's URL."""
    lines = ["# written by benchmarks/run.py", "settings:",
             f"  timeout: {timeout_s}", "primary_backends:"]
    for b in backends:
        url = b["url"].replace("{seed}", str(weight_seed(seed)))
        lines += [f"  - name: {b['name']}", f"    url: \"{url}\"",
                  f"    model: \"{b['model']}\""]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n" + STRATEGY_YAML)


class Server:
    """``python -m quorum_tpu.server.serve`` on a free port, logging to a
    file; stopped with SIGINT, then SIGKILL of its session."""

    def __init__(self, config_path: str, out_dir: str, env: dict):
        """``env`` carries PYTHONPATH to the checkout; the working directory
        is ``out_dir``, so what the server writes by relative path (its
        on-demand profiles) lands there."""
        self.port = free_port()
        self.log_path = os.path.join(out_dir, "server.log")
        self.t_spawn = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "quorum_tpu.server.serve", "--config",
                 config_path, "--host", "127.0.0.1", "--port", str(self.port),
                 "--log-dir", os.path.join(out_dir, "server_logs")],
                stdout=log, stderr=subprocess.STDOUT, cwd=out_dir, env=env,
                start_new_session=True)

    def call(self, method: str, path: str, body: dict | None = None,
             timeout: float = 30.0) -> tuple[int, str]:
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path,
                         body=None if body is None else json.dumps(body),
                         headers=HEADERS)
            resp = conn.getresponse()
            return resp.status, resp.read().decode("utf-8", "replace")
        finally:
            conn.close()

    def wait_ready(self, deadline_s: float) -> float:
        """Seconds from spawn to the first 200 on /ready. Raises if the
        server exits (no accelerator, bad config) or never gets there."""
        deadline = self.t_spawn + deadline_s
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} before "
                    f"/ready: {self.log_tail()}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"server not ready after {deadline_s}s: "
                                   f"{self.log_tail()}")
            try:
                status, text = self.call("GET", "/ready", timeout=5)
            except OSError:
                status, text = 0, ""
            if status == 200:
                return time.monotonic() - self.t_spawn
            # Backends construct before the socket opens, so a 503 that names
            # a backend that failed to construct will never clear.
            try:
                rows = json.loads(text).get("checks") or [] if text else []
            except ValueError:
                rows = []
            if any(r.get("constructed") is False for r in rows):
                raise RuntimeError(
                    "a configured backend failed to construct: "
                    f"{self.log_tail()}")
            time.sleep(0.25)

    def patiently(self, method: str, path: str, body: dict | None = None,
                  each_timeout: float = 30.0,
                  deadline_s: float = 30.0) -> tuple[int, str]:
        """``call``, tried again until it gets an answer or ``deadline_s``
        has passed: after a traced window the profiler's stop can hold the
        server up for tens of seconds. (0, reason) where no answer came."""
        deadline = time.monotonic() + deadline_s
        while True:
            try:
                return self.call(method, path, body, timeout=each_timeout)
            except (OSError, http.client.HTTPException) as e:
                if self.proc.poll() is not None or time.monotonic() > deadline:
                    return 0, f"{type(e).__name__}: {e}"
                time.sleep(0.5)

    def metrics(self, deadline_s: float = 30.0) -> dict[str, float]:
        status, text = self.patiently("GET", "/metrics",
                                      deadline_s=deadline_s)
        if status != 200:
            raise RuntimeError(f"/metrics answered {status}: {text[:300]}")
        return parse_metrics(text)

    def try_metrics(self) -> dict[str, float]:
        """One scrape, or {} where none came: a scrape inside the window may
        be lost, and what reads it then reads nothing."""
        try:
            status, text = self.call("GET", "/metrics", timeout=10.0)
        except (OSError, http.client.HTTPException):
            return {}
        return parse_metrics(text) if status == 200 else {}

    def health(self, deadline_s: float = 30.0) -> dict:
        status, text = self.patiently("GET", "/health",
                                      deadline_s=deadline_s)
        if status != 200:
            raise RuntimeError(f"/health answered {status}: {text[:300]}")
        return json.loads(text)

    def log_tail(self, n: int = 12) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return " | ".join(f.read().strip().splitlines()[-n:])
        except OSError:
            return ""

    def errors_in_log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            lines = [ln.strip() for ln in f if "RESOURCE_EXHAUSTED" in ln
                     or "Traceback" in ln or " ERROR" in ln]
        return " | ".join(lines[-2:])[:600]

    def kill(self) -> None:
        self.stop(grace_s=0.0)

    def stop(self, grace_s: float = 30.0) -> int:
        if self.proc.poll() is None and grace_s > 0:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        with contextlib.suppress(ProcessLookupError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()
        return self.proc.returncode


def parse_metrics(text: str) -> dict[str, float]:
    """A /metrics exposition as {family: sum over its label sets}. Histogram
    ``_bucket`` samples are left out; ``_sum`` and ``_count`` are kept."""
    out: dict[str, float] = {}
    for m in re.finditer(r"^([a-zA-Z_:][\w:]*)(?:\{[^}]*\})? (\S+)$", text,
                         re.M):
        name, val = m.group(1), m.group(2)
        if name.endswith("_bucket"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(val)
        except ValueError:
            pass
    return out


def cache_log_counts(log_path: str) -> tuple[int, int]:
    """(hits, misses) of jax's persistent compilation cache, from the lines
    ``JAX_DEBUG_LOG_MODULES=jax._src.compiler`` puts into the server log."""
    with open(log_path, errors="replace") as f:
        text = f.read()
    return (len(re.findall(r"compiler:\d+: Persistent compilation cache hit",
                           text)),
            len(re.findall(r"compiler:\d+: PERSISTENT COMPILATION CACHE MISS",
                           text)))


def cache_log_total(log_path: str) -> int:
    """Programs compiled or loaded so far, by the log."""
    return sum(cache_log_counts(log_path))
