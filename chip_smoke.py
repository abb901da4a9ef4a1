#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on the chip.

Drives the system's main path once, through the entry point a user would call
(``python -m quorum_tpu.server.serve --config <file> --port <p>``), over a real
socket, from a parent process that never imports jax (a chip belongs to one
process at a time — each leg below is a child, run strictly one after another):

1. *kernel leg*: the Pallas prefill and decode kernels, compiled by Mosaic
   (``interpret=False``) at the shapes the two serving legs use, compared with
   the XLA references in ``quorum_tpu/ops/attention.py`` on the chip.
2. *quorum leg*: the shipped ``config.yaml`` unchanged — three stacked
   ``tpu://gpt2`` members, concatenate, thinking-tag filter. Then the same
   server started a second time, which must hit the compile cache.
3. *full-width leg*: ``mistral-7b`` at its published widths and all 32 layers,
   bf16, weights random from a seed, as a single backend.

Each serving leg: wait for ``/ready``; read the platform from ``/health`` (what
the *server* reports — a CPU fallback fails here); warm each prompt bucket
(cold compile is set-up time, reported, never hidden by a longer backend
timeout); one non-streaming request; four concurrent SSE streams of two prompt
lengths; then ``/metrics`` and the server log. Any failed check ends the run
at once with a non-zero exit and no result line. A run that passed ends with
two JSON lines: ``{"smoke": {...}}`` (versions, compile cache, per-leg
detail), then the verdict, which holds these keys and no other:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py                 # requires a TPU; exits non-zero without one
    python chip_smoke.py --rehearsal     # CPU, tiny presets, kernels interpreted

The rehearsal exists to debug the script's own plumbing without spending chip
time. It shares every line of the legs with the chip run except the model ids
and the platform assertions, and says that it is a rehearsal on every line it
prints. The numbers either mode prints are smoke output: set-up and
first-token times of one cold run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import importlib.metadata
import json
import os
import re
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Model ids per mode: the only thing the rehearsal changes besides the
# platform it asserts. The full-width URL starts from the shape the old
# bench used for a 16 GB chip (max_seq=1024, decode_chunk=16) with four
# slots, so four concurrent streams co-batch: bf16 weights 14.5 GB + KV
# 32 L x 4 slots x 8 kv-heads x 1024 x 128 x 2 B x 2 = 0.54 GB.
MODELS = {
    "chip": {
        "quorum": "gpt2",
        "full_width": "mistral-7b?max_seq=1024&slots=4&decode_chunk=16",
    },
    "rehearsal": {
        "quorum": "gpt2-tiny?max_seq=1024",
        "full_width": ("llama-tiny?sliding_window=64&max_seq=1024&slots=4"
                       "&decode_chunk=16"),
    },
}
QUORUM_MEMBERS = 3  # config.yaml: LLM1..LLM3, members=3
REHEARSAL_TAG = "[REHEARSAL on the CPU at tiny size - not a chip result] "

# The chat template is "user: <content>\nassistant:" and the random-init
# models use the byte tokenizer, so a prompt is len(content) + 17 tokens:
# 30 + 17 = 47 lands in the 64 bucket, 150 + 17 = 167 in the 256 bucket —
# both single-shot admissions (<= prefill_chunk 512), two compiled programs.
TEMPLATE_TOKENS = len("user: ") + len("\nassistant:")
PROMPT_CHARS = {64: 30, 256: 150}
MAX_TOKENS = 32
N_STREAMS = 4

# Kernel-vs-reference tolerance on bf16 inputs: outputs are softmax-weighted
# means of N(0, 1) values (|out| < ~1); bf16 rounding of the output alone is
# 2**-8 relative, the references accumulate in a different order, and a wrong
# mask or head mapping shows as an O(0.3) error.
KERNEL_ATOL = 3e-2
KERNEL_RTOL = 3e-2

# Set-up deadlines. Programs compile lazily at the first request and the
# shipped config gives each backend call 120 s (settings.timeout): a cold
# compile that outlasts it comes back as a failed member. Warm-up therefore
# repeats a request that merely TIMED OUT (the engine counts no failure)
# until every member answered or this deadline passes; an engine failure
# ends the run at once, and the measured requests get no second chance.
READY_DEADLINE_S = 420
WARMUP_DEADLINE_S = 480
TOTAL_DEADLINE_S = 1170  # the driver allows 1200 s, compilation included
REQUEST_TIMEOUT_S = 150  # socket timeout: above the server's own 120 s

_children: list[subprocess.Popen] = []
_t_start = time.monotonic()
_tag = ""


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"{_tag}[{time.monotonic() - _t_start:6.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def remaining(limit_s: float) -> float:
    """Seconds a wait starting now may take: its own limit, capped by what
    is left of the run's."""
    left = TOTAL_DEADLINE_S - (time.monotonic() - _t_start)
    check(left > 0, f"run exceeded its {TOTAL_DEADLINE_S}s budget")
    return min(limit_s, left)


# ---- child processes --------------------------------------------------------


def spawn(argv: list[str], log_path: str, env: dict) -> subprocess.Popen:
    log = open(log_path, "wb")
    try:
        proc = subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT,
                                cwd=REPO, env=env, start_new_session=True)
    finally:
        log.close()  # the child holds its own descriptor
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 60.0) -> int:
    """SIGINT (serve's graceful teardown), then SIGKILL the whole session."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    with contextlib.suppress(ProcessLookupError):
        os.killpg(proc.pid, signal.SIGKILL)
    proc.wait()
    return proc.returncode


def stop_all() -> None:
    for proc in _children:
        stop(proc, grace_s=0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---- HTTP client (stdlib only) ------------------------------------------------

HEADERS = {"Authorization": "Bearer chip-smoke",
           "Content-Type": "application/json"}


def http_call(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 30.0) -> tuple[int, str]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers=HEADERS)
        resp = conn.getresponse()
        return resp.status, resp.read().decode("utf-8", "replace")
    finally:
        conn.close()


def chat_body(content: str, stream: bool) -> dict:
    body = {"model": "chip-smoke",
            "messages": [{"role": "user", "content": content}],
            "max_tokens": MAX_TOKENS, "stream": stream}
    if stream:
        body["stream_options"] = {"include_usage": True}
    return body


def prompt(bucket: int, tag: str) -> str:
    """A prompt for ``bucket`` whose FIRST characters are unique to ``tag``:
    the slot-resident prefix cache reuses matches of 16+ tokens by routing
    the request through chunked prefill, and this smoke is about the
    single-shot path (the Pallas kernel)."""
    n = PROMPT_CHARS[bucket]
    return (f"{tag} " + "say something about serving models on a chip. " * 8)[:n]


def stream_chat(port: int, content: str) -> dict:
    """One SSE request. Returns status, whether [DONE] arrived, content-delta
    counts per chunk-id class (``member-i`` / ``final`` / ``single`` /
    ``error``), the usage chunk if any, and first-content / total seconds."""
    t0 = time.monotonic()
    out = {"status": 0, "done": False, "deltas": {}, "usage": None,
           "first_content_s": None, "total_s": None, "error_text": ""}
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/chat/completions",
                     body=json.dumps(chat_body(content, stream=True)),
                     headers=HEADERS)
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error_text"] = resp.read().decode("utf-8", "replace")[:500]
            return out
        for raw in resp:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                out["done"] = True
                break
            chunk = json.loads(line[len("data: "):])
            if chunk.get("usage"):
                out["usage"] = chunk["usage"]
            cid = chunk.get("id", "")
            for choice in chunk.get("choices") or []:
                text = (choice.get("delta") or {}).get("content")
                if not text:
                    continue
                m = re.fullmatch(r"chatcmpl-parallel-(\d+)", cid)
                if cid == "error" or choice.get("finish_reason") == "error":
                    key = "error"
                    out["error_text"] = text[:500]
                elif m:
                    key = f"member-{m.group(1)}"
                elif cid == "chatcmpl-parallel-final":
                    key = "final"
                else:
                    key = "single"
                out["deltas"][key] = out["deltas"].get(key, 0) + 1
                if out["first_content_s"] is None and key != "error":
                    out["first_content_s"] = time.monotonic() - t0
    finally:
        conn.close()
    out["total_s"] = time.monotonic() - t0
    return out


def stream_complete(r: dict, members: int) -> str:
    """'' when the stream carried tokens from every configured member, else
    what is missing."""
    if r["status"] != 200:
        return f"HTTP {r['status']}: {r['error_text']}"
    if not r["done"]:
        return "no [DONE]"
    if "error" in r["deltas"]:
        return f"error chunk: {r['error_text']}"
    want = (["single"] if members == 1
            else [f"member-{i}" for i in range(members)] + ["final"])
    missing = [k for k in want if r["deltas"].get(k, 0) < 1]
    if missing:
        return f"no content deltas under {missing} (got {r['deltas']})"
    if members == 1 and not (r["usage"] or {}).get("completion_tokens", 0) > 0:
        return f"usage.completion_tokens not > 0 ({r['usage']})"
    return ""


def metric_values(text: str, name: str) -> list[float]:
    """Every sample of one family in a /metrics exposition."""
    return [float(v) for v in re.findall(
        rf"^{name}(?:\{{[^}}]*\}})? (\S+)$", text, re.M)]


def engine_metric(text: str, name: str) -> float:
    """Sum of ``quorum_tpu_engine_<name>`` over the engines on /metrics."""
    vals = metric_values(text, f"quorum_tpu_engine_{name}")
    check(bool(vals), f"/metrics has no quorum_tpu_engine_{name}")
    return sum(vals)


# ---- compile cache ------------------------------------------------------------


def cache_dir() -> str:
    """Where the servers' compile cache goes: quorum_tpu/compile_cache.py's
    rule, restated here because this parent must not import jax."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir()) if not n.startswith("."))
    except FileNotFoundError:
        return 0


def cache_log_counts(log_path: str) -> tuple[int, int]:
    """(hits, misses) from jax's own compiler log lines in a server log —
    the handler JAX_DEBUG_LOG_MODULES installs (``name:lineno: message``;
    serve's root handler repeats each line without the line number)."""
    with open(log_path, errors="replace") as f:
        text = f.read()
    return (len(re.findall(r"compiler:\d+: Persistent compilation cache hit",
                           text)),
            len(re.findall(r"compiler:\d+: PERSISTENT COMPILATION CACHE MISS",
                           text)))


# ---- the serving leg ----------------------------------------------------------


def serve_leg(name: str, config_path: str, members: int, rehearsal: bool,
              out_dir: str, env: dict, *, full: bool) -> dict:
    """Start ``serve`` on ``config_path``, check it end to end, stop it.
    ``full=False`` is the warm restart: ready, one warmed request per
    bucket, the cache-hit count — no load phase."""
    port = free_port()
    log_path = os.path.join(out_dir, f"{name}.server.log")
    entries_before = cache_entries()
    t0 = time.monotonic()
    proc = spawn(
        [sys.executable, "-m", "quorum_tpu.server.serve", "--config",
         config_path, "--host", "127.0.0.1", "--port", str(port),
         "--log-dir", os.path.join(out_dir, f"{name}.logs")],
        log_path, env)
    report: dict = {"config": os.path.relpath(config_path, REPO),
                    "server_log": os.path.relpath(log_path, REPO)}
    try:
        # /ready, failing at once if the server exits (no TPU, bad config).
        deadline = t0 + remaining(READY_DEADLINE_S)
        while True:
            check(proc.poll() is None,
                  f"{name}: server exited with code {proc.returncode} before "
                  f"/ready — see {log_path}")
            check(time.monotonic() < deadline,
                  f"{name}: not ready after {deadline - t0:.0f}s")
            try:
                status, text = http_call(port, "GET", "/ready", timeout=5)
            except OSError:
                status, text = 0, ""  # not listening yet
            if status == 200:
                break
            # Backends construct before the socket opens, so a 503 that
            # names a backend that failed to construct will never clear.
            missing = [row for row in (json.loads(text).get("checks") or [])
                       if row.get("constructed") is False] if text else []
            check(not missing, f"{name}: /ready answers {status}: a "
                               f"configured backend is missing: {missing}")
            time.sleep(0.5)
        report["ready_s"] = round(time.monotonic() - t0, 1)
        say(f"{name}: /ready after {report['ready_s']}s")

        # Where the SERVER says it runs, and that nothing is missing.
        status, text = http_call(port, "GET", "/health")
        check(status == 200, f"{name}: /health answered {status}")
        health = json.loads(text)
        check(health["status"] == "healthy",
              f"{name}: /health says {health['status']}: {text[:800]}")
        rows = health.get("checks") or []
        check(len(rows) == 1 and "platform" in rows[0],
              f"{name}: expected one engine row with a platform: {text[:800]}")
        device = {k: rows[0][k]
                  for k in ("platform", "device_kind", "device_count", "mesh")}
        want_platform = "cpu" if rehearsal else "tpu"
        check(device["platform"] == want_platform,
              f"{name}: server reports platform {device['platform']!r}, "
              f"this run requires {want_platform!r}")
        report["device"] = device
        if rows[0].get("device_memory"):
            report["device_memory"] = rows[0]["device_memory"]
        status, text = http_call(port, "GET", "/models")
        check(status == 200, f"{name}: /models answered {status}")
        owners = [n for m in json.loads(text)["data"]
                  for n in m["owned_by"].split(",")]
        check(len(owners) == members,
              f"{name}: {members} backends configured, /models lists "
              f"{owners}")
        say(f"{name}: {members} backend(s) constructed on {device}")

        # Warm each bucket: cold compile is set-up time.
        report["cold_first_token_s"] = {}
        report["warmup_attempts"] = {}
        for bucket in PROMPT_CHARS:
            t_b = time.monotonic()
            deadline = t_b + remaining(WARMUP_DEADLINE_S)
            attempts = 0
            while True:
                attempts += 1
                r = stream_chat(port, prompt(bucket, f"w{bucket}.{attempts}"))
                missing = stream_complete(r, members)
                if not missing:
                    break
                say(f"{name}: warm-up of bucket {bucket}, attempt {attempts}: "
                    f"{missing[:300]}")
                check(proc.poll() is None, f"{name}: server died in warm-up")
                status, text = http_call(port, "GET", "/metrics")
                check(status == 200 and not any(
                    engine_metric(text, k) for k in (
                        "failures_total", "rebuilds_total", "breaker_state")),
                      f"{name}: warm-up of bucket {bucket} failed in the "
                      f"engine, not by timing out: {missing}")
                check(time.monotonic() < deadline,
                      f"{name}: bucket {bucket} not warm after "
                      f"{deadline - t_b:.0f}s: {missing}")
                time.sleep(1.0)
            cold = time.monotonic() - t_b - r["total_s"] + r["first_content_s"]
            report["cold_first_token_s"][str(bucket)] = round(cold, 1)
            report["warmup_attempts"][str(bucket)] = attempts
            say(f"{name}: bucket {bucket} cold first token after "
                f"{cold:.1f}s ({attempts} attempt(s))")
        hits, misses = cache_log_counts(log_path)
        report["cache"] = {"entries_before": entries_before,
                           "entries_after": cache_entries(),
                           "hits": hits, "misses": misses}
        if not full:
            return report

        status, text = http_call(port, "GET", "/metrics")
        check(status == 200, f"{name}: /metrics answered {status}")
        compiles_before = sum(
            metric_values(text, "quorum_tpu_recompiles_total"))
        chunks_before = engine_metric(text, "decode_chunks_total")
        rows_before = engine_metric(text, "decode_busy_rows_total")

        # One non-streaming request. Byte tokenizer: every member reports
        # exactly len(prompt) + template tokens, so the summed prompt_tokens
        # counts the members that answered.
        content = prompt(64, "n0")
        status, text = http_call(port, "POST", "/chat/completions",
                                 chat_body(content, stream=False),
                                 timeout=REQUEST_TIMEOUT_S)
        check(status == 200, f"{name}: non-streaming answered {status}: "
                             f"{text[:500]}")
        usage = json.loads(text)["usage"]
        per_member = len(content) + TEMPLATE_TOKENS
        check(usage["prompt_tokens"] == members * per_member,
              f"{name}: prompt_tokens {usage['prompt_tokens']} != {members} "
              f"members x {per_member}")
        check(usage["completion_tokens"] >= members,
              f"{name}: completion_tokens {usage['completion_tokens']} < "
              f"{members} members")
        report["non_streaming_usage"] = usage

        # Four concurrent SSE streams, two prompt lengths.
        results: list = [None] * N_STREAMS
        buckets = [list(PROMPT_CHARS)[i % 2] for i in range(N_STREAMS)]

        def run(i: int) -> None:
            results[i] = stream_chat(port, prompt(buckets[i], f"s{i}"))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(N_STREAMS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=remaining(REQUEST_TIMEOUT_S + 30))
            check(not t.is_alive(), f"{name}: an SSE stream hung")
        for i, r in enumerate(results):
            check(r is not None, f"{name}: stream {i} raised")
            missing = stream_complete(r, members)
            check(not missing, f"{name}: stream {i}: {missing}")
        report["warm_ttft_s"] = round(statistics.median(
            r["first_content_s"] for r in results), 3)
        report["stream_deltas"] = [r["deltas"] for r in results]
        report["stream_completion_tokens"] = [
            (r["usage"] or {}).get("completion_tokens") for r in results]

        status, text = http_call(port, "GET", "/metrics")
        check(status == 200, f"{name}: /metrics answered {status}")
        engine = {k: engine_metric(text, k) for k in (
            "requests_total", "tokens_total", "decode_chunks_total",
            "decode_busy_rows_total", "failures_total", "rebuilds_total",
            "breaker_state")}
        check(engine["requests_total"] > 0 and engine["tokens_total"] > 0
              and engine["decode_chunks_total"] > 0,
              f"{name}: engine counters did not move: {engine}")
        check(engine["breaker_state"] == 0 and engine["rebuilds_total"] == 0
              and engine["failures_total"] == 0,
              f"{name}: breaker/rebuilds/failures not clean: {engine}")
        rows_per_chunk = ((engine["decode_busy_rows_total"] - rows_before)
                          / max(1.0, engine["decode_chunks_total"]
                                - chunks_before))
        check(rows_per_chunk > 1.0,
              f"{name}: {rows_per_chunk:.2f} rows per decode chunk — the "
              "concurrent streams did not co-batch")
        report["engine"] = engine
        report["rows_per_decode_chunk"] = round(rows_per_chunk, 2)
        report["compiles_after_warmup"] = int(sum(
            metric_values(text, "quorum_tpu_recompiles_total"))
            - compiles_before)

        # The program's own trace-time lines: which attention path each
        # single-shot prefill bucket took; nothing interpreted.
        with open(log_path, errors="replace") as f:
            log = f.read()
        check("Traceback" not in log, f"{name}: traceback in {log_path}")
        paths = re.findall(
            r"attention-path program=(\S+) kernel=(\S+) path=(\S+) "
            r"interpret=(\S+)", log)
        check(all(interp == "False" for *_, interp in paths),
              f"{name}: a program ran in interpret mode: {paths}")
        prefill_paths = {prog: path for prog, kernel, path, _ in paths
                         if kernel == "flash_prefill"}
        seen = {int(prog.rsplit("/", 1)[1]) for prog in prefill_paths}
        check(seen >= set(PROMPT_CHARS),
              f"{name}: prefill buckets traced {sorted(seen)}, expected "
              f"{sorted(PROMPT_CHARS)}")
        if not rehearsal:
            check(set(prefill_paths.values()) == {"pallas"},
                  f"{name}: a single-shot prefill bucket did not run the "
                  f"Pallas kernel: {prefill_paths}")
        report["prefill_attention_paths"] = prefill_paths
        return report
    finally:
        rc = stop(proc)
        say(f"{name}: server stopped (exit {rc})")


# ---- the kernel leg (the one code path here that imports jax) -----------------


def kernel_child(mode: str) -> None:
    """Compile both Pallas kernels with Mosaic at the serving legs' shapes
    and compare with ops/attention.py on the device. Prints one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quorum_tpu.config import BackendSpec
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.attention import decode_attention, prefill_attention
    from quorum_tpu.ops.flash_attention import DEFAULT_BLOCK_Q, _flash_call
    from quorum_tpu.ops.flash_decode import (
        _decode_call,
        cache_decode_attention,
        decode_tile,
    )

    rehearsal = mode == "rehearsal"
    dev = jax.devices()[0]
    want_platform = "cpu" if rehearsal else "tpu"
    if dev.platform != want_platform:
        raise SystemExit(f"kernel leg: jax reports platform {dev.platform!r}, "
                         f"this run requires {want_platform!r}")
    interpret = rehearsal  # on the chip: Mosaic, never the interpreter
    worst = {"err": 0.0, "case": ""}
    n_cases = 0

    def rand(seed, shape):
        return jax.random.normal(
            jax.random.PRNGKey(seed), shape, jnp.float32).astype(jnp.bfloat16)

    def compare(case, got, ref, valid=None):
        nonlocal n_cases
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        if got.shape != ref.shape or not np.isfinite(got).all():
            raise SystemExit(f"kernel leg: {case}: shape {got.shape} vs "
                             f"{ref.shape}, or non-finite values")
        if valid is not None:  # rows past the length are unspecified
            got, ref = got[..., :valid, :], ref[..., :valid, :]
        err = np.abs(got - ref)
        if not (err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)).all():
            raise SystemExit(f"kernel leg: {case}: max abs error "
                             f"{err.max():.4f} exceeds atol {KERNEL_ATOL} / "
                             f"rtol {KERNEL_RTOL}")
        n_cases += 1
        if err.max() > worst["err"]:
            worst.update(err=float(err.max()), case=case)

    def prefill_case(case, h, kv, hd, s, window, members=0):
        lead = (members,) if members else ()
        q = rand(1, lead + (1, h, s, hd))
        k = rand(2, lead + (1, kv, s, hd))
        v = rand(3, lead + (1, kv, s, hd))
        length = s - s // 4 - 1  # ragged: the length mask has work to do
        lengths = jnp.full(lead + (1,), length, jnp.int32)
        block = min(DEFAULT_BLOCK_Q, s)

        def kernel(q, k, v, n):
            return _flash_call(q, k, v, n, block_q=block, block_k=block,
                               interpret=interpret, window=window)

        def ref(q, k, v, n):
            return prefill_attention(q, k, v, n, window=window)

        if members:  # the stacked quorum vmaps admission over its members
            kernel, ref = jax.vmap(kernel), jax.vmap(ref)
        compare(case, kernel(q, k, v, lengths), jax.jit(ref)(q, k, v, lengths),
                valid=length)

    def decode_case(case, h, kv, hd, t, window, slots=4, members=0):
        # the carried leaves as the engine holds them: two layers of
        # [slots, max_seq, K*hd], the second one read
        lead = (members,) if members else ()
        q = rand(4, lead + (slots, h, 1, hd))
        k = rand(5, lead + (2, slots, t, kv * hd))
        v = rand(6, lead + (2, slots, t, kv * hd))
        # skewed rows: near-empty, mid, full, short
        lengths = jnp.asarray([1, t // 2 - 3, t, 7][:slots], jnp.int32)
        live = jnp.ones((slots,), bool)

        def kernel(q, k, v):
            return _decode_call(q, k, v, jnp.int32(1), lengths, history=t,
                                tile=decode_tile(t), window=window,
                                interpret=interpret)

        def k_major(leaf):
            return leaf[1].reshape(slots, t, kv, hd).transpose(0, 2, 1, 3)

        def ref(q, k, v):
            return decode_attention(q, k_major(k), k_major(v), lengths,
                                    window=window)

        if members:
            # the stacked quorum vmaps the step: the call's own batching
            # rule reads the stacked store through XLA's einsums
            def kernel(q, k, v):
                return cache_decode_attention(
                    q, k, v, jnp.int32(1), lengths, live, history=t,
                    window=window)

            kernel, ref = jax.vmap(kernel), jax.vmap(ref)
        compare(case, jax.jit(kernel)(q, k, v), jax.jit(ref)(q, k, v))

    for leg, members in (("quorum", QUORUM_MEMBERS), ("full_width", 0)):
        url = BackendSpec(name=leg, url="tpu://" + MODELS[mode][leg])
        spec = resolve_spec(url.tpu_model_id, url.tpu_options)
        h, kv, hd, win = (spec.n_heads, spec.n_kv_heads, spec.head_dim,
                          spec.sliding_window)
        tag = f"{url.tpu_model_id} h{h}/{kv} hd{hd} w{win}"
        s = 16  # every single-shot bucket: MIN_BUCKET .. prefill_chunk
        while s <= 512:
            prefill_case(f"prefill {tag} s{s}", h, kv, hd, s, win)
            s *= 2
        for t in (128, 256, spec.max_seq):  # decode history buckets
            decode_case(f"decode {tag} t{t}", h, kv, hd, t, win)
        if members:
            for s in PROMPT_CHARS:
                prefill_case(f"prefill {tag} s{s} vmap{members}", h, kv, hd,
                             s, win, members=members)
            decode_case(f"decode {tag} t256 vmap{members}", h, kv, hd, 256,
                        win, members=members)
        if win:
            # Sequences ABOVE the window (serving stays below it at
            # max_seq=1024): two kv heads' worth, so the f32 reference's
            # [H, S, S] scores stay near 2 GB at S = 8192.
            g = h // kv
            prefill_case(f"prefill {tag} s{2 * win} above-window", 2 * g, 2,
                         hd, 2 * win, win)
            decode_case(f"decode {tag} t{2 * win} above-window", 2 * g, 2,
                        hd, 2 * win, win)

    print(json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()), "interpret": interpret,
        "cases": n_cases, "max_abs_err": round(worst["err"], 5),
        "worst_case": worst["case"],
        "atol": KERNEL_ATOL, "rtol": KERNEL_RTOL}), flush=True)


def kernel_leg(mode: str, out_dir: str, env: dict) -> dict:
    log_path = os.path.join(out_dir, "kernels.log")
    proc = spawn([sys.executable, os.path.abspath(__file__),
                  "--kernel-child", mode], log_path, env)
    try:
        proc.wait(timeout=remaining(600))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernel leg: no result after 600s") from None
    finally:
        stop(proc, grace_s=5)
    with open(log_path, errors="replace") as f:
        lines = f.read().strip().splitlines()
    check(proc.returncode == 0,
          f"kernel leg exited {proc.returncode}: {' | '.join(lines[-3:])}")
    return json.loads(lines[-1])


# ---- main ---------------------------------------------------------------------


def main() -> int:
    global _tag
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at tiny presets (debugging this "
                         "script; proves nothing about the chip)")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="configs written here, with the server logs")
    ap.add_argument("--legs", default="kernels,quorum,full_width",
                    help="comma-separated subset, for builder runs")
    ap.add_argument("--full-width-model", default=None,
                    help="model id and URL options of the full-width leg "
                         "(builder runs on a four-chip host: "
                         "'mistral-7b?tp=4&max_seq=4096&slots=8')")
    ap.add_argument("--kernel-child", choices=sorted(MODELS),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernel_child:
        kernel_child(args.kernel_child)
        return 0

    mode = "rehearsal" if args.rehearsal else "chip"
    _tag = REHEARSAL_TAG if args.rehearsal else ""
    legs = args.legs.split(",")
    if not os.path.isdir(os.path.join(REPO, "quorum_tpu")):
        print(f"chip_smoke FAILED: {REPO} holds no quorum_tpu/ — this script "
              "drives the checkout it sits in", file=sys.stderr)
        return 1
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    env = dict(os.environ, PYTHONUNBUFFERED="1",
               # jax's own hit/miss lines, so a warm restart can be SHOWN
               JAX_DEBUG_LOG_MODULES="jax._src.compiler")
    if args.rehearsal:
        # The CPU, asked for by name; the cache forced on (CPU runs are
        # opt-in) so the restart check is the same code as on the chip.
        env.update(JAX_PLATFORMS="cpu", QUORUM_TPU_COMPILE_CACHE="1")

    # Configs: the shipped file, and literals written beside the logs.
    with open(os.path.join(REPO, "config.yaml")) as f:
        shipped = f.read()
    quorum_config = os.path.join(REPO, "config.yaml")
    if args.rehearsal:
        check(shipped.count("tpu://gpt2?") == QUORUM_MEMBERS,
              "config.yaml no longer lists three tpu://gpt2 members")
        quorum_config = os.path.join(out_dir, "config.quorum.yaml")
        model, _, opts = MODELS[mode]["quorum"].partition("?")
        with open(quorum_config, "w") as f:
            f.write(shipped.replace("tpu://gpt2?", f"tpu://{model}?{opts}&"))
    full_model = args.full_width_model or MODELS[mode]["full_width"]
    full_config = os.path.join(out_dir, "config.full_width.yaml")
    with open(full_config, "w") as f:
        f.write("# written by chip_smoke.py\n"
                "settings:\n  timeout: 120\n"
                "primary_backends:\n"
                "  - name: FULL\n"
                f"    url: \"tpu://{full_model}\"\n"
                f"    model: \"{full_model.partition('?')[0]}\"\n")

    smoke: dict = {
        "note": "smoke output: one cold run's set-up and first-token "
                "seconds, not benchmark metrics",
        "rehearsal": args.rehearsal,
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir(),
        "compile_cache_entries_at_start": cache_entries(),
        "legs": {},
    }
    try:
        if "kernels" in legs:
            smoke["legs"]["kernels"] = k = kernel_leg(mode, out_dir, env)
            say(f"kernels: {k['cases']} cases within tolerance on "
                f"{k['device_kind']} (worst {k['max_abs_err']} at "
                f"{k['worst_case']})")
        if "quorum" in legs:
            smoke["legs"]["quorum"] = q = serve_leg(
                "quorum", quorum_config, QUORUM_MEMBERS, args.rehearsal,
                out_dir, env, full=True)
            check(q["cache"]["entries_after"] > q["cache"]["entries_before"]
                  or q["cache"]["hits"] > 0,
                  f"quorum: nothing was written to or read from the compile "
                  f"cache at {cache_dir()}: {q['cache']}")
            # The same server a second time, in the same call: the programs
            # the first start compiled must now come from the cache.
            smoke["legs"]["quorum_restart"] = r = serve_leg(
                "quorum_restart", quorum_config, QUORUM_MEMBERS,
                args.rehearsal, out_dir, env, full=False)
            check(r["cache"]["hits"] > 0,
                  f"quorum_restart: no compile-cache hit on the second start "
                  f"({r['cache']}, cache at {cache_dir()})")
            say(f"quorum_restart: {r['cache']['hits']} cache hits, "
                f"{r['cache']['misses']} misses")
        if "full_width" in legs:
            smoke["legs"]["full_width"] = serve_leg(
                "full_width", full_config, 1, args.rehearsal, out_dir, env,
                full=True)
    except SmokeFailure as e:
        print(f"{_tag}chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_all()

    smoke["compile_cache_entries_at_end"] = cache_entries()
    smoke["wall_s"] = round(time.monotonic() - _t_start, 1)
    devices = [leg["device"] for leg in smoke["legs"].values()
               if "device" in leg]
    if "kernels" in smoke["legs"]:
        k = smoke["legs"]["kernels"]
        devices.append({"platform": k["platform"],
                        "device_kind": k["device_kind"],
                        "device_count": k["device_count"]})
    first = devices[0]
    if any((d["platform"], d["device_kind"], d["device_count"])
           != (first["platform"], first["device_kind"], first["device_count"])
           for d in devices):
        print(f"{_tag}chip_smoke FAILED: legs disagree on the device: "
              f"{devices}", file=sys.stderr, flush=True)
        return 1
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump(smoke, f, indent=1)
    # Two lines: the smoke's own detail, then — last, and with exactly these
    # keys, because that is what the driver parses — the verdict.
    print(_tag + json.dumps({"smoke": smoke}), flush=True)
    print(_tag + json.dumps({
        "ok": True,
        "device": {"platform": first["platform"],
                   "kind": first["device_kind"],
                   "count": first["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
