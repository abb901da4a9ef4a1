"""Chaos harness: inject faults at every named site and assert containment.

``make chaos-check`` runs the full sweep on a tiny CPU engine behind the real
ASGI app (no network, httpx ASGITransport). For each injection site
(quorum_tpu/faults.py) it drives concurrent load, arms the fault, and
asserts the containment contract of docs/robustness.md:

  - only the affected request(s) error; a co-batched or queued bystander
    either completes or is requeued and completes;
  - the immediately following request succeeds (the engine rebuilt);
  - deadline-exceeded requests get their timeout response within
    deadline + slack and release their slots;
  - a failure storm opens the engine breaker (503 + Retry-After) and
    /health reports it; a cooldown probe closes it again;
  - with faults disarmed, greedy AND sampled outputs are pinned
    token-for-token against the pre-chaos baseline (fault machinery is
    inert when disarmed);
  - the HTTP backend retry ladder recovers from transient connect
    errors / 5xx within its budget;
  - the router replica-kill drill (phase 6, docs/scaling.md): SIGKILL one
    replica under load — the survivor's in-flight stream completes
    untouched, the dead replica's requests fail over and complete
    elsewhere within their deadlines, the /ready poller rotates the
    corpse out of the ring, and with every replica dead the router sheds
    503 + Retry-After instead of hanging;
  - the quorum member-kill drill (phase 10, docs/quorum.md): SIGKILL one
    member of a quorum=3 fan-out mid-generation — with a spare cell the
    member finishes token-exact elsewhere and the quorum stays full, with
    no spare the request is served degraded from the survivors, never
    failed.

Exit codes: 0 = all checks passed, 1 = at least one failed, 2 = the harness
itself hung (watchdog). ``tests/test_robustness.py`` runs the quick subset
as a suite smoke; the full sweep is wired into ``make chaos-check``.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("QUORUM_TPU_COMPILE_CACHE", "0")
# The disagg handoff phase needs one virtual device per group.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

SCRIPT_TIMEOUT_S = 600.0   # watchdog over the whole sweep
DEADLINE_SLACK_S = 2.0     # acceptance: timeout response within deadline + 2s

_CHECKS: list[tuple[str, bool, str]] = []


def check(name: str, ok: bool, detail: str = "") -> None:
    _CHECKS.append((name, bool(ok), detail))
    print(f"  [{'ok' if ok else 'FAIL'}] {name}" + (f" — {detail}" if detail and not ok else ""), flush=True)


def _flight_dump_check(label: str, needle: str) -> None:
    """Containments are no longer post-mortem-blind (ISSUE 12): after a
    containment phase, a flight-recorder dump artifact must exist in the
    sweep's dump dir, parse as JSON, and hold an event mentioning the
    faulted site (the containment/fail-all event's error carries the
    FaultInjected message, which names the site). Dumps are cumulative
    ring snapshots, so any artifact written at-or-after the phase holds
    its events — newest first."""
    files = sorted(glob.glob(os.path.join(
        os.environ.get("QUORUM_TPU_FLIGHT_DIR", "logs"),
        "flightrec-*.json")), reverse=True)
    ok, detail = False, "no flightrec-*.json dump artifacts found"
    for path in files:
        try:
            with open(path) as f:
                body = json.load(f)
        except Exception as e:
            detail = f"unparseable dump {path}: {e}"
            continue
        events = body.get("events")
        if not isinstance(events, list):
            detail = f"dump {path} has no events list"
            continue
        if any(needle in json.dumps(ev) for ev in events):
            ok = True
            detail = os.path.basename(path)
            break
        detail = f"site {needle!r} in none of {len(files)} dumps"
    check(f"{label}: flight-recorder dump holds the faulted site", ok,
          detail)


def _spawn_fake_replica(name: str, *, chunk_delay: float = 0.0,
                        tokens: int = 8):
    """Spawn a killable jax-free fake replica process; returns
    ``(proc, base_url)`` once it prints its bound port."""
    import subprocess

    env = dict(os.environ)
    proc = subprocess.Popen(
        [sys.executable, "-m", "quorum_tpu.router.fake_replica",
         "--name", name, "--port", "0",
         "--chunk-delay", str(chunk_delay), "--tokens", str(tokens)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)
    deadline = time.time() + 30.0
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("PORT="):
            port = int(line.strip().split("=", 1)[1])
            return proc, f"http://127.0.0.1:{port}"
    proc.kill()
    raise RuntimeError(f"fake replica {name} never bound a port")


async def _router_kill_drill(check) -> None:
    """Phase 6 body: two fake replica processes behind the real router
    app; SIGKILL one mid-stream and assert the containment contract."""
    import httpx

    from quorum_tpu.router import affinity as aff
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.telemetry.recorder import RECORDER

    proc_a = proc_b = None
    try:
        proc_a, url_a = _spawn_fake_replica("kill-a", chunk_delay=0.05,
                                            tokens=60)
        proc_b, url_b = _spawn_fake_replica("kill-b", chunk_delay=0.05,
                                            tokens=60)
        rcfg = RouterConfig(
            replicas=[("kill-a", url_a), ("kill-b", url_b)],
            ready_interval=0.25, retries=1, timeout=20.0,
            breaker_threshold=2, breaker_cooldown=0.5,
            migrate_on_rotation=False,
            # This phase pins the RESUME-OFF degrade contract (exactly one
            # error chunk on the killed stream, never a re-send); phase 9
            # runs the same kill with resume ON and asserts zero loss.
            stream_resume=False)
        router_app = create_router_app(rcfg)
        mgr = router_app.state["replica_set"]

        def body_keyed_to(target: str, *, stream: bool,
                          max_tokens: int = 60, salt: str = "") -> dict:
            """A conversation whose affinity primary is ``target``."""
            for i in range(200):
                msgs = [{"role": "user",
                         "content": f"drill{salt} conversation {i}: "
                                    "please answer at length"}]
                key = aff.conversation_key({"messages": msgs},
                                           rcfg.affinity_chunk)
                if mgr.ring.primary(key) == target:
                    return {"model": "m", "messages": msgs,
                            "stream": stream, "max_tokens": max_tokens}
            raise RuntimeError(f"no key found for {target}")

        transport = httpx.ASGITransport(app=router_app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://router",
                                     timeout=30.0) as rc:

            async def consume_stream(body: dict) -> dict:
                out = {"tokens": 0, "done": False, "error_chunks": 0,
                       "routed": None}
                async with rc.stream("POST", "/chat/completions",
                                     json=body) as resp:
                    out["status"] = resp.status_code
                    out["routed"] = resp.headers.get("x-routed-to")
                    async for line in resp.aiter_lines():
                        if not line.startswith("data: "):
                            continue
                        data = line[len("data: "):]
                        if data.strip() == "[DONE]":
                            out["done"] = True
                            continue
                        ev = json.loads(data)
                        choice = (ev.get("choices") or [{}])[0]
                        delta = choice.get("delta") or {}
                        if choice.get("finish_reason") == "error":
                            out["error_chunks"] += 1
                        elif delta.get("content"):
                            out["tokens"] += 1
                return out

            # In-flight streams on BOTH replicas (~3s each at 60 tokens
            # x 50ms), then SIGKILL replica A mid-stream.
            # Keys computed BEFORE the kill: the poller may rotate the
            # corpse out at any tick, after which no key maps to it.
            queued_bodies = [
                body_keyed_to("kill-a", stream=False, max_tokens=4,
                              salt=f"q{i}")
                for i in range(3)]
            stream_a = asyncio.create_task(consume_stream(
                body_keyed_to("kill-a", stream=True)))
            stream_b = asyncio.create_task(consume_stream(
                body_keyed_to("kill-b", stream=True)))
            await asyncio.sleep(0.6)  # both streams well under way
            proc_a.kill()
            proc_a.wait()
            # "Queued for A" requests arriving after the kill: they must
            # fail over to B and complete within their deadline.
            t0 = time.time()
            queued = await asyncio.wait_for(asyncio.gather(
                *(rc.post("/chat/completions", json=body)
                  for body in queued_bodies)), timeout=15.0)
            failover_wall = time.time() - t0
            got_a = await asyncio.wait_for(stream_a, timeout=30.0)
            got_b = await asyncio.wait_for(stream_b, timeout=30.0)
            check("router kill: survivor stream unharmed",
                  got_b["routed"] == "kill-b" and got_b["tokens"] == 60
                  and got_b["done"] and got_b["error_chunks"] == 0,
                  f"{got_b}")
            check("router kill: killed stream errors, never hangs or "
                  "double-delivers",
                  got_a["routed"] == "kill-a" and got_a["tokens"] < 60
                  and got_a["error_chunks"] == 1 and got_a["done"],
                  f"{got_a}")
            check("router kill: queued requests complete elsewhere in "
                  "deadline",
                  all(r.status_code == 200
                      and r.headers.get("x-routed-to") == "kill-b"
                      for r in queued) and failover_wall < 10.0,
                  f"statuses={[r.status_code for r in queued]} "
                  f"wall={failover_wall:.1f}s")
            # The /ready poller rotates the corpse out of the ring.
            poll_deadline = time.time() + 5.0
            while time.time() < poll_deadline and "kill-a" in mgr.ring:
                await asyncio.sleep(0.1)
            check("router kill: dead replica rotated out of the ring",
                  "kill-a" not in mgr.ring and "kill-b" in mgr.ring,
                  f"ring={sorted(mgr.ring.members)}")
            after = await rc.post(
                "/chat/completions",
                json=body_keyed_to("kill-b", stream=False, max_tokens=4))
            check("router kill: post-rotation requests serve from the "
                  "survivor", after.status_code == 200
                  and after.headers.get("x-routed-to") == "kill-b")
            events = json.dumps(RECORDER.snapshot())
            check("router kill: failover visible on metrics + flight "
                  "recorder",
                  "router-failover" in events
                  and "router-replica-out" in events)
            # Kill the survivor too: the router must shed, never hang.
            proc_b.kill()
            proc_b.wait()
            while time.time() < poll_deadline + 5.0 and len(mgr.ring):
                await asyncio.sleep(0.1)
            shed = await asyncio.wait_for(
                rc.post("/chat/completions",
                        json={"model": "m", "max_tokens": 4,
                              "messages": [{"role": "user",
                                            "content": "anyone alive?"}]}),
                timeout=15.0)
            check("router kill: all replicas dead -> 503 + Retry-After, "
                  "no hang",
                  shed.status_code == 503
                  and "retry-after" in {k.lower() for k in shed.headers},
                  f"status={shed.status_code}")
            await mgr.aclose()
    finally:
        for proc in (proc_a, proc_b):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()


async def _fleet_trace_drill(check) -> None:
    """Phase 7 body: trace continuity through failover, fleet-wide.

    Two fake replica processes behind the real router; SIGKILL one while
    a stream is in flight, then send a request keyed to the corpse. The
    failed-over request's W3C trace-id must name it in the router's own
    timeline (failover + serving hop), in the SURVIVOR's flight
    recorder, and in the merged /debug/fleet/timeline — one id, three
    processes (docs/observability.md "Fleet plane")."""
    import httpx

    from quorum_tpu.router import affinity as aff
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.telemetry.recorder import RECORDER

    proc_a = proc_b = None
    try:
        proc_a, url_a = _spawn_fake_replica("trace-a", chunk_delay=0.05,
                                            tokens=60)
        proc_b, url_b = _spawn_fake_replica("trace-b", chunk_delay=0.05,
                                            tokens=60)
        rcfg = RouterConfig(
            replicas=[("trace-a", url_a), ("trace-b", url_b)],
            ready_interval=0.25, retries=1, timeout=20.0,
            breaker_threshold=2, breaker_cooldown=0.5,
            migrate_on_rotation=False)
        router_app = create_router_app(rcfg)
        mgr = router_app.state["replica_set"]

        def body_keyed_to(target: str, *, stream: bool,
                          max_tokens: int = 60) -> dict:
            for i in range(200):
                msgs = [{"role": "user",
                         "content": f"trace conversation {i}: "
                                    "please answer at length"}]
                key = aff.conversation_key({"messages": msgs},
                                           rcfg.affinity_chunk)
                if mgr.ring.primary(key) == target:
                    return {"model": "m", "messages": msgs,
                            "stream": stream, "max_tokens": max_tokens}
            raise RuntimeError(f"no key found for {target}")

        transport = httpx.ASGITransport(app=router_app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://router",
                                     timeout=30.0) as rc:
            # one poll sweep up front: telemetry (and clock offsets) for
            # both replicas while both are alive
            await mgr.poll_once()
            failover_body = body_keyed_to("trace-a", stream=False,
                                          max_tokens=4)

            async def consume(body: dict) -> None:
                async with rc.stream("POST", "/chat/completions",
                                     json=body) as resp:
                    async for _line in resp.aiter_lines():
                        pass

            stream_a = asyncio.create_task(consume(
                body_keyed_to("trace-a", stream=True)))
            await asyncio.sleep(0.6)  # stream well under way
            proc_a.kill()
            proc_a.wait()
            failed_over = await asyncio.wait_for(
                rc.post("/chat/completions", json=failover_body),
                timeout=15.0)
            await asyncio.wait_for(stream_a, timeout=30.0)
            trace_id = failed_over.headers.get("x-request-id", "")
            check("fleet trace: failed-over request serves from the "
                  "survivor with a 32-hex trace-id",
                  failed_over.status_code == 200
                  and failed_over.headers.get("x-routed-to") == "trace-b"
                  and len(trace_id) == 32,
                  f"status={failed_over.status_code} rid={trace_id!r}")
            tp = failed_over.headers.get("traceparent", "")
            check("fleet trace: response traceparent carries the same "
                  "trace-id", tp.startswith(f"00-{trace_id}-"), tp)

            # 1/3 — router timeline: failed attempt on the corpse, serving
            # hop on the survivor marked failover=1, distinct spans
            mine = [ev for ev in RECORDER.snapshot()
                    if ev.get("rid") == trace_id]
            failed = [ev for ev in mine
                      if ev["kind"] == "router-failover"]
            routed = [ev for ev in mine if ev["kind"] == "router-route"]
            check("fleet trace: router timeline joins failover + serving "
                  "hop on the trace-id",
                  bool(failed) and bool(routed)
                  and failed[0].get("replica") == "trace-a"
                  and routed[0].get("replica") == "trace-b"
                  and routed[0].get("failover") == 1
                  and routed[0].get("span") != failed[0].get("span"),
                  f"failover={failed} route={routed}")

            # 2/3 — the survivor's own recorder saw the same trace-id
            async with httpx.AsyncClient(timeout=10.0) as direct:
                tl = (await direct.get(
                    f"{url_b}/debug/engine/timeline")).json()
            surv = [ev for ev in tl.get("events", [])
                    if ev.get("rid") == trace_id]
            check("fleet trace: survivor's recorder carries the "
                  "trace-id",
                  {"dispatch", "reap"} <= {ev["kind"] for ev in surv},
                  f"kinds={sorted({ev['kind'] for ev in surv})}")

            # 3/3 — the merged fleet timeline joins both processes on it
            fleet = (await rc.get("/debug/fleet/timeline")).json()
            merged = [ev for ev in fleet["events"]
                      if ev.get("rid") == trace_id]
            procs = {ev.get("process") for ev in merged}
            aligned = {row["name"]: row.get("clock_aligned")
                       for row in fleet.get("replicas", [])}
            check("fleet trace: merged fleet timeline joins router + "
                  "survivor on the trace-id, clock-aligned",
                  procs == {"router", "trace-b"}
                  and aligned.get("trace-b") is True,
                  f"procs={sorted(p or '?' for p in procs)} "
                  f"aligned={aligned}")
            stamps = [ev["t"] for ev in merged]
            check("fleet trace: aligned events sit within one request's "
                  "duration",
                  bool(stamps) and max(stamps) - min(stamps) < 5.0,
                  f"spread={max(stamps) - min(stamps):.3f}s"
                  if stamps else "no events")
            await mgr.aclose()
    finally:
        for proc in (proc_a, proc_b):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()


async def _qos_preemption_drill(check) -> None:
    """Phase 8 (docs/scheduling.md): the preemption contract under fault.

    Own app on a dedicated qos=1 engine — slots=1 so an interactive
    arrival NEVER finds a free slot (the preemption path is the only way
    in), kv_pages=1 so the drill also audits page accounting across
    park/resume. Three checks:

      1. an interactive arrival mid-decode preempts the batch resident
         and admits (the beneficiary finishes first);
      2. the parked victim's stream is token-for-token identical to its
         solo (uncontended) run — the preemption contract;
      3. with ``engine.preempt`` armed, the park fault dooms ONLY the
         victim: the beneficiary still admits and completes, the next
         request is clean, and the page pool drains to zero (no leaked
         pages from the half-parked row).
    """
    import queue as _queue

    from quorum_tpu import faults
    from quorum_tpu.config import Config
    from quorum_tpu.server.app import create_app

    cfg = {
        "settings": {"timeout": 60},
        "primary_backends": [{
            "name": "Q",
            # d_model=96 ≠ the main engine's 128: a distinct cache key,
            # so this drill never flips qos on the shared phase-0 engine.
            "url": ("tpu://llama-tiny?d_model=96&max_seq=128"
                    "&slots=1&queue=8&decode_chunk=4&max_tokens=64"
                    "&qos=1&kv_pages=1&kv_page_size=16"),
            "model": "chaos-qos",
        }],
    }
    app = create_app(Config(raw=cfg), watch_config=False)
    backend = app.state["registry"].get("Q")
    eng = backend.engine
    check("qos: engine flag set via URL opt", bool(eng.qos))
    tok = backend.tokenizer
    victim_ids = tok.encode("the quick brown fox jumps over")
    bene_ids = tok.encode("hello there")

    def run_solo(ids, n, *, priority=None):
        req = eng.submit(list(ids), max_new_tokens=n, seed=5,
                         eos_id=None, priority=priority)
        return list(eng.stream_results(req))

    def drain_async(req, sink):
        try:
            for t in eng.stream_results(req):
                sink.append(t)
        except Exception:
            # Armed arm: the doomed victim's stream raises FaultInjected
            # here — the drill inspects the err frame / short stream
            # directly, so the thread just exits quietly.
            pass

    solo = run_solo(victim_ids, 48)
    check("qos: solo baseline nonempty", len(solo) > 0)

    async def drill(label, armed):
        if armed:
            faults.reset_counts()
            faults.arm("engine.preempt", times=1)
        # The tiny model decodes its whole 48-token budget in tens of
        # milliseconds: on a loaded core the victim can finish before the
        # interactive arrival's admission attempt ever flags it. Retry
        # the attempt until a preemption (or the armed fault) is actually
        # observed — every attempt still checks the full contract.
        for attempt in range(5):
            before = eng.n_preemptions
            victim = eng.submit(list(victim_ids), max_new_tokens=48,
                                seed=5, eos_id=None, priority="batch")
            got: list[int] = []
            th = threading.Thread(target=drain_async, args=(victim, got),
                                  daemon=True)
            th.start()
            # The victim must be mid-decode when the interactive request
            # lands, or there is nothing to preempt.
            deadline_t = time.time() + 30
            while victim.emitted < 6 and time.time() < deadline_t:
                await asyncio.sleep(0.01)
            bene = eng.submit(list(bene_ids), max_new_tokens=8, seed=9,
                              eos_id=None, priority="interactive")
            bene_got = list(await asyncio.to_thread(
                lambda: list(eng.stream_results(bene))))
            await asyncio.to_thread(th.join, 60)
            hit = (faults.fired("engine.preempt") >= 1 if armed
                   else eng.n_preemptions > before)
            if hit:
                break
        if armed:
            faults.disarm()
            check("qos: preempt fault fired",
                  faults.fired("engine.preempt") >= 1)
            # The fault lands between flag and park: the victim alone is
            # doomed (an err frame ended its stream mid-generation).
            err = None
            try:
                while True:
                    kind, val = victim.out.get_nowait()
                    if kind == "err":
                        err = val
            except _queue.Empty:
                pass
            check("qos: faulted park dooms only the victim",
                  err is not None or len(got) < len(solo),
                  f"err={err!r} got={len(got)}/{len(solo)}")
        else:
            check("qos: preemption occurred",
                  eng.n_preemptions == before + 1,
                  f"preemptions {before}->{eng.n_preemptions}")
            check("qos: victim stream token-exact across park/resume",
                  got == solo, f"lens {len(got)} vs {len(solo)}")
        check(f"qos: beneficiary admitted and completed ({label})",
              len(bene_got) == 8, f"got {len(bene_got)}")

    await drill("clean", armed=False)
    await drill("faulted", armed=True)

    # Post-drill hygiene: a fresh request is clean, and page accounting
    # is exact — allocated pages are retained prefix donors only (live
    # claims all zero, pool conserved); a conservation miss means the
    # faulted park lost a row's pages (the exact-accounting half of the
    # phase).
    again = run_solo(victim_ids, 48)
    check("qos: next request after fault matches solo", again == solo)
    m = eng.metrics()
    with eng._cond:
        live_claims = sum(eng._page_claims)
    check("qos: page accounting exact (no leaked pages or claims)",
          m.get("kv_pages_allocated", 0) + m.get("kv_pages_free", 0)
          == eng.kv_pool_pages and live_claims == 0,
          f"allocated={m.get('kv_pages_allocated')} "
          f"free={m.get('kv_pages_free')} pool={eng.kv_pool_pages} "
          f"claims={live_claims}")
    check("qos: preemption metrics exported",
          m.get("qos") == 1 and m.get("preemptions_total", 0) >= 1
          and m.get("preempted_tokens_total", 0) >= 1)


async def _stream_resume_drill(check) -> None:
    """Phase 9 body (ISSUE 19, docs/robustness.md "Zero-loss streams"):
    with resume ON, a SIGKILLed replica's live stream continues on the
    survivor with the client-visible token sequence IDENTICAL to an
    uninterrupted run; a survivor whose replay guard refuses the journal
    degrades to the PR 12 error-chunk contract with no duplicate frames
    (likewise a fault injected at ``router.resume``); and a scripted
    drain of 1-of-2 replicas under live traffic finishes every request —
    zero failures — with the parked stream proactively resumed."""
    import httpx

    from quorum_tpu import faults
    from quorum_tpu.observability import ROUTER_STREAM_RESUMES
    from quorum_tpu.router import affinity as aff
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.telemetry.recorder import RECORDER

    async def consume(rc, body: dict) -> dict:
        out = {"text": "", "frames": [], "done": False, "error_chunks": 0,
               "error_text": "", "roles": 0, "routed": None, "ids": set()}
        async with rc.stream("POST", "/chat/completions",
                             json=body) as resp:
            out["status"] = resp.status_code
            out["routed"] = resp.headers.get("x-routed-to")
            async for line in resp.aiter_lines():
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data.strip() == "[DONE]":
                    out["done"] = True
                    continue
                ev = json.loads(data)
                if ev.get("id"):
                    out["ids"].add(ev["id"])
                choice = (ev.get("choices") or [{}])[0]
                delta = choice.get("delta") or {}
                if choice.get("finish_reason") == "error":
                    out["error_chunks"] += 1
                    out["error_text"] += delta.get("content") or ""
                elif delta.get("role"):
                    out["roles"] += 1
                elif delta.get("content"):
                    out["text"] += delta["content"]
                    out["frames"].append(delta["content"])
        return out

    async def cluster(tag: str):
        """Spawn a killable pair + a resume-ON router over them."""
        proc_a, url_a = _spawn_fake_replica(f"{tag}-a", chunk_delay=0.05,
                                            tokens=60)
        proc_b, url_b = _spawn_fake_replica(f"{tag}-b", chunk_delay=0.05,
                                            tokens=60)
        rcfg = RouterConfig(
            replicas=[(f"{tag}-a", url_a), (f"{tag}-b", url_b)],
            ready_interval=0.25, retries=1, timeout=20.0,
            breaker_threshold=3, breaker_cooldown=0.5,
            migrate_on_rotation=False)
        router_app = create_router_app(rcfg)
        return (proc_a, url_a), (proc_b, url_b), rcfg, router_app

    def keyed_to(target: str, mgr, rcfg, *, salt: str = "") -> dict:
        for i in range(200):
            msgs = [{"role": "user",
                     "content": f"resume{salt} conversation {i}: "
                                "please answer at length"}]
            key = aff.conversation_key({"messages": msgs},
                                       rcfg.affinity_chunk)
            if mgr.ring.primary(key) == target:
                return {"model": "m", "messages": msgs,
                        "stream": True, "max_tokens": 60}
        raise RuntimeError(f"no key found for {target}")

    # ---- arm 1: SIGKILL mid-stream -> token-exact resume on survivor ----
    procs = []
    try:
        (proc_a, _), (proc_b, _), rcfg, router_app = await cluster("res")
        procs += [proc_a, proc_b]
        mgr = router_app.state["replica_set"]
        transport = httpx.ASGITransport(app=router_app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://router",
                                     timeout=60.0) as rc:
            body = keyed_to("res-a", mgr, rcfg)
            base = await asyncio.wait_for(consume(rc, body), timeout=30.0)
            check("resume: uninterrupted baseline streams clean",
                  base["done"] and base["error_chunks"] == 0
                  and len(base["text"]) > 0, f"{base['status']}")
            resumed_before = ROUTER_STREAM_RESUMES.value_of(
                outcome="resumed")
            task = asyncio.create_task(consume(rc, body))
            await asyncio.sleep(0.6)  # well mid-stream (60 x 50ms)
            proc_a.kill()
            proc_a.wait()
            got = await asyncio.wait_for(task, timeout=30.0)
            check("resume: killed stream finishes token-exact on survivor",
                  got["text"] == base["text"] and got["done"]
                  and got["error_chunks"] == 0,
                  f"len={len(got['text'])}/{len(base['text'])} "
                  f"errors={got['error_chunks']}")
            check("resume: one role chunk, one chunk identity, no "
                  "duplicate frames",
                  got["roles"] == 1 and len(got["ids"]) == 1
                  and "".join(got["frames"]) == got["text"])
            check("resume: outcome counted and recorder-evented",
                  ROUTER_STREAM_RESUMES.value_of(outcome="resumed")
                  == resumed_before + 1
                  and "router-stream-resume"
                  in json.dumps(RECORDER.snapshot()))
            await mgr.aclose()
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # ---- arm 2: divergence + router.resume fault -> error-chunk degrade -
    procs = []
    try:
        (proc_a, url_a), (proc_b, url_b), rcfg, router_app = \
            await cluster("div")
        procs += [proc_a, proc_b]
        mgr = router_app.state["replica_set"]
        transport = httpx.ASGITransport(app=router_app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://router",
                                     timeout=60.0) as rc, \
                httpx.AsyncClient(timeout=10.0) as direct:
            body = keyed_to("div-a", mgr, rcfg)
            base = await asyncio.wait_for(consume(rc, body), timeout=30.0)
            # every replica's replay guard refuses the journal
            for url in (url_a, url_b):
                await direct.post(f"{url}/admin/diverge")
            await direct.post(f"{url_a}/admin/abort?after=2")
            divergence_before = ROUTER_STREAM_RESUMES.value_of(
                outcome="divergence")
            got = await asyncio.wait_for(consume(rc, body), timeout=30.0)
            check("resume divergence: degrades to the error-chunk "
                  "contract, no duplicate frames",
                  got["error_chunks"] == 1 and got["done"]
                  and "diverged" in got["error_text"]
                  and base["text"].startswith(got["text"])
                  and got["text"] != base["text"],
                  f"errors={got['error_chunks']} "
                  f"text={got['text'][:40]!r}")
            check("resume divergence: outcome counted",
                  ROUTER_STREAM_RESUMES.value_of(outcome="divergence")
                  == divergence_before + 1)
            # fault injection AT the resume site: the single sibling's
            # attempt burns, candidates exhaust, same degrade contract
            await direct.post(f"{url_b}/admin/diverge?off=1")
            await direct.post(f"{url_a}/admin/diverge?off=1")
            await direct.post(f"{url_a}/admin/abort?after=2")
            fired_before = faults.fired("router.resume")
            faults.arm("router.resume", times=1)
            try:
                got = await asyncio.wait_for(consume(rc, body),
                                             timeout=30.0)
            finally:
                faults.disarm()
            check("resume fault site: router.resume fired and degraded "
                  "cleanly",
                  faults.fired("router.resume") == fired_before + 1
                  and got["error_chunks"] == 1 and got["done"]
                  and base["text"].startswith(got["text"]),
                  f"fired={faults.fired('router.resume')} "
                  f"errors={got['error_chunks']}")
            await mgr.aclose()
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # ---- arm 3: graceful drain of 1-of-2 under live traffic ------------
    procs = []
    try:
        (proc_a, url_a), (proc_b, url_b), rcfg, router_app = \
            await cluster("drn")
        procs += [proc_a, proc_b]
        mgr = router_app.state["replica_set"]
        transport = httpx.ASGITransport(app=router_app)
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://router",
                                     timeout=60.0) as rc, \
                httpx.AsyncClient(timeout=10.0) as direct:
            body_a = keyed_to("drn-a", mgr, rcfg)
            body_b = keyed_to("drn-b", mgr, rcfg, salt="x")
            base_a = await asyncio.wait_for(consume(rc, body_a),
                                            timeout=30.0)
            base_b = await asyncio.wait_for(consume(rc, body_b),
                                            timeout=30.0)
            stream_a = asyncio.create_task(consume(rc, body_a))
            stream_b = asyncio.create_task(consume(rc, body_b))
            await asyncio.sleep(0.6)  # both streams live
            r = await rc.post("/router/drain?replica=drn-a")
            report = r.json()
            # live traffic THROUGH the drain window: all must complete
            extra = await asyncio.wait_for(asyncio.gather(
                *(rc.post("/chat/completions",
                          json={"model": "m", "max_tokens": 4,
                                "messages": [{"role": "user",
                                              "content": f"drain load "
                                                         f"{i}"}]})
                  for i in range(4))), timeout=20.0)
            got_a = await asyncio.wait_for(stream_a, timeout=30.0)
            got_b = await asyncio.wait_for(stream_b, timeout=30.0)
            check("drain: reported drained with zero residents",
                  r.status_code == 200 and report.get("drained") is True
                  and report.get("resident") == 0, f"{report}")
            check("drain: parked stream resumed token-exact — zero loss",
                  got_a["text"] == base_a["text"] and got_a["done"]
                  and got_a["error_chunks"] == 0,
                  f"len={len(got_a['text'])}/{len(base_a['text'])}")
            check("drain: survivor stream untouched",
                  got_b["text"] == base_b["text"] and got_b["done"]
                  and got_b["error_chunks"] == 0)
            check("drain: zero failed requests under live traffic",
                  all(x.status_code == 200 for x in extra)
                  and all(x.headers.get("x-routed-to") == "drn-b"
                          for x in extra),
                  f"statuses={[x.status_code for x in extra]}")
            check("drain: replica out of the ring, drain on the recorder",
                  "drn-a" not in mgr.ring
                  and "router-drain" in json.dumps(RECORDER.snapshot()))
            # undrain + recovery: the replica rejoins on a /ready tick
            await direct.post(f"{url_a}/admin/undrain")
            deadline = time.time() + 5.0
            while time.time() < deadline and "drn-a" not in mgr.ring:
                await asyncio.sleep(0.1)
            check("drain: undrained replica rejoins the ring",
                  "drn-a" in mgr.ring, f"ring={sorted(mgr.ring.members)}")
            await mgr.aclose()
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


async def _quorum_member_kill_drill(check) -> None:
    """Phase 10 body (docs/quorum.md): a ``quorum=3`` fan-out loses one
    member to SIGKILL mid-generation. With a spare cell in the ring the
    member finishes token-exact elsewhere and the quorum stays FULL;
    with no spare the member is dropped and the request is SERVED from
    the survivors plus the dead member's partial answer — degraded,
    never failed, no error chunk."""
    import httpx

    from quorum_tpu.observability import QUORUM_DEGRADED, QUORUM_REQUESTS
    from quorum_tpu.router.app import RouterConfig, create_router_app

    sep = "\n\n---\n\n"  # RouterConfig.quorum_separator default
    body = {"model": "m", "stream": True, "quorum": 3, "max_tokens": 60,
            "messages": [{"role": "user", "content":
                          "quorum chaos drill: answer at length"}]}

    async def consume(rc) -> dict:
        out = {"streams": {}, "final": None, "errors": 0, "done": False,
               "assigned": [], "status": 0}
        async with rc.stream("POST", "/chat/completions",
                             json=body) as resp:
            out["status"] = resp.status_code
            out["assigned"] = (resp.headers.get("x-quorum-replicas")
                               or "").split(",")
            async for line in resp.aiter_lines():
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data.strip() == "[DONE]":
                    out["done"] = True
                    continue
                ev = json.loads(data)
                choice = (ev.get("choices") or [{}])[0]
                delta = choice.get("delta") or {}
                if (ev.get("id") == "error"
                        or choice.get("finish_reason") == "error"):
                    out["errors"] += 1
                elif ev.get("id") == "chatcmpl-parallel-final":
                    out["final"] = delta.get("content") or ""
                elif delta.get("content"):
                    out["streams"].setdefault(ev.get("id"), "")
                    out["streams"][ev.get("id")] += delta["content"]
        return out

    async def cluster(tag: str, n: int):
        pairs = [_spawn_fake_replica(f"{tag}{i}", chunk_delay=0.05,
                                     tokens=60) for i in range(n)]
        rcfg = RouterConfig(
            replicas=[(f"{tag}{i}", url)
                      for i, (_, url) in enumerate(pairs)],
            ready_interval=0.25, retries=1, timeout=30.0,
            breaker_threshold=3, breaker_cooldown=0.5,
            migrate_on_rotation=False)
        return [p for p, _ in pairs], create_router_app(rcfg)

    async def arm(tag: str, n: int, drill) -> None:
        procs, router_app = await cluster(tag, n)
        mgr = router_app.state["replica_set"]
        try:
            transport = httpx.ASGITransport(app=router_app)
            async with httpx.AsyncClient(transport=transport,
                                         base_url="http://router",
                                         timeout=60.0) as rc:
                await drill(rc, procs)
            await mgr.aclose()
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()

    # ---- arm 1: kill with a spare -> token-exact resume, quorum FULL ----
    async def with_spare(rc, procs):
        base = await asyncio.wait_for(consume(rc), timeout=30.0)
        texts = set(base["streams"].values())
        check("quorum: uninterrupted 3-member fan-out combines clean",
              base["done"] and base["errors"] == 0
              and len(base["streams"]) == 3 and len(texts) == 1
              and base["final"] == sep.join([texts.pop()] * 3),
              f"status={base['status']} members={len(base['streams'])}")
        degraded_before = QUORUM_DEGRADED.value
        full_before = QUORUM_REQUESTS.value_of(outcome="full")
        task = asyncio.create_task(consume(rc))
        await asyncio.sleep(0.6)  # well mid-stream (60 x 50ms chunks)
        victim = procs[int(base["assigned"][0].removeprefix("qs"))]
        victim.kill()
        victim.wait()
        got = await asyncio.wait_for(task, timeout=30.0)
        check("quorum: killed member finishes token-exact on the spare "
              "(quorum stays full)",
              got["done"] and got["errors"] == 0
              and got["final"] == base["final"],
              f"errors={got['errors']} "
              f"len={len(got['final'] or '')}/{len(base['final'] or '')}")
        check("quorum: spare-covered kill counts full, not degraded",
              QUORUM_REQUESTS.value_of(outcome="full") == full_before + 1
              and QUORUM_DEGRADED.value == degraded_before)

    await arm("qs", 4, with_spare)

    # ---- arm 2: kill with NO spare -> served degraded, never failed -----
    async def no_spare(rc, procs):
        base = await asyncio.wait_for(consume(rc), timeout=30.0)
        t = next(iter(base["streams"].values()))
        broken_before = QUORUM_DEGRADED.value_of(reason="stream_broken")
        degr_before = QUORUM_REQUESTS.value_of(outcome="degraded")
        failed_before = QUORUM_REQUESTS.value_of(outcome="failed")
        task = asyncio.create_task(consume(rc))
        await asyncio.sleep(0.6)
        victim = procs[int(base["assigned"][0].removeprefix("qn"))]
        victim.kill()
        victim.wait()
        got = await asyncio.wait_for(task, timeout=30.0)
        pieces = (got["final"] or "").split(sep)
        partials = [p for p in pieces if p != t]
        check("quorum: member death with no spare serves the survivors "
              "(no error chunk, partial answer joins the combine)",
              got["done"] and got["errors"] == 0 and len(pieces) == 3
              and pieces.count(t) == 2 and len(partials) == 1
              and partials[0] and t.startswith(partials[0]),
              f"errors={got['errors']} pieces={len(pieces)}")
        check("quorum: the loss is counted degraded, never failed",
              QUORUM_DEGRADED.value_of(reason="stream_broken")
              == broken_before + 1
              and QUORUM_REQUESTS.value_of(outcome="degraded")
              == degr_before + 1
              and QUORUM_REQUESTS.value_of(outcome="failed")
              == failed_before)

    await arm("qn", 3, no_spare)


def _config() -> dict:
    return {
        "settings": {"timeout": 30},
        "primary_backends": [{
            "name": "T",
            # prefill_chunk=32: the templated short prompt (~19 tokens)
            # single-shot admits (the engine.admit site), the 30-word one
            # (~170 tokens) rides chunked prefill (engine.prefill_segment).
            # d_model=128 keeps warm decode measurably slow (~tens of ms
            # per token on CPU) so the deadline scenarios actually catch
            # requests mid-flight instead of racing a finished generation.
            "url": ("tpu://llama-tiny?d_model=128&max_seq=256"
                    "&slots=2&queue=8&decode_chunk=4"
                    "&prefill_chunk=32&prefix_store=host"
                    "&prefix_store_chunk=32&max_tokens=8"),
            "model": "chaos",
        }],
    }


async def _run(quick: bool) -> None:
    import httpx

    from quorum_tpu import faults
    from quorum_tpu.config import Config
    from quorum_tpu.server.app import create_app

    app = create_app(Config(raw=_config()), watch_config=False)
    backend = app.state["registry"].get("T")
    engine = backend.engine
    transport = httpx.ASGITransport(app=app)
    auth = {"Authorization": "Bearer chaos"}

    async with httpx.AsyncClient(transport=transport,
                                 base_url="http://chaos") as client:

        # The long-running deadline scenarios must actually run long: a
        # random-init model's greedy stream can sample EOS at any step, so
        # they bias it out (an ordinary OpenAI logit_bias knob).
        no_eos = {str(backend.tokenizer.eos_id): -100}

        async def chat(content: str = "hello", *, max_tokens: int = 8,
                       temperature: float = 0.0, seed: int = 0,
                       timeout: float | None = None,
                       ban_eos: bool = False) -> httpx.Response:
            body: dict = {
                "model": "chaos", "max_tokens": max_tokens,
                "temperature": temperature, "seed": seed,
                "messages": [{"role": "user", "content": content}],
            }
            if timeout is not None:
                body["timeout"] = timeout
            if ban_eos:
                body["logit_bias"] = no_eos
            return await client.post("/v1/chat/completions", json=body,
                                     headers=auth)

        def text(r: httpx.Response) -> str:
            return r.json()["choices"][0]["message"]["content"]

        # ---- phase 0: baseline (compiles programs, pins outputs) ---------
        print("phase 0: baseline", flush=True)
        greedy0 = text(await chat(seed=1))
        sampled0 = text(await chat(temperature=0.9, seed=7))
        check("baseline greedy nonempty", isinstance(greedy0, str))
        # Warm every decode history bucket (one full-budget generation):
        # first-use XLA compiles block the scheduler for seconds, and the
        # deadline phases below assert ~sub-second sweep latencies.
        await chat("warmup", max_tokens=235, ban_eos=True)

        # ---- phase 1: one fault per engine site under concurrent load ----
        long_prompt = "word " * 30  # > prefill_chunk tokens: chunked path
        sites = [("engine.admit", "hi"),
                 ("engine.prefill_segment", long_prompt),
                 ("engine.decode", "hi")]
        if quick:
            sites = sites[:1]
        for site, prompt in sites:
            print(f"phase 1: inject {site}", flush=True)
            faults.reset_counts()
            faults.arm(site, times=1)
            burst = await asyncio.gather(
                *(chat(prompt if i == 0 else "bystander", seed=i)
                  for i in range(4)))
            faults.disarm()
            statuses = [r.status_code for r in burst]
            check(f"{site}: fault fired", faults.fired(site) >= 1)
            check(f"{site}: at least one request failed",
                  any(s >= 500 for s in statuses), f"statuses={statuses}")
            check(f"{site}: not every request failed (bounded blast radius)",
                  any(s == 200 for s in statuses), f"statuses={statuses}")
            follow = await chat(seed=1)
            check(f"{site}: next request succeeds",
                  follow.status_code == 200 and text(follow) == greedy0,
                  f"status={follow.status_code}")
            _flight_dump_check(site, site)

        # snapshot worker: a fault there may cost one snapshot, never a
        # request or the worker thread.
        print("phase 1: inject engine.snapshot", flush=True)
        faults.arm("engine.snapshot", times=1)
        r = await chat("snapshot me " * 8, seed=3)
        engine.drain_prefix_store()
        faults.disarm()
        check("engine.snapshot: request unaffected", r.status_code == 200)
        check("engine.snapshot: worker survives",
              engine.health()["snapshot_worker_alive"])

        # ---- phase 2: deadlines ------------------------------------------
        # Latency injection (faults delay mode) makes each decode dispatch
        # stall 50ms: generation speed becomes a harness constant instead
        # of a property of the box, so the deadline windows are exact.
        print("phase 2: deadlines", flush=True)
        faults.arm("engine.decode", times=100000, delay=0.05)
        try:
            # Queue-stage shed: both slots blocked by slow generations
            # (~48 tokens x 12.5ms/token), the late request's 0.3s deadline
            # expires while it is still pending.
            blockers = [asyncio.create_task(
                chat("blocker", max_tokens=48, seed=10 + i, ban_eos=True))
                for i in range(2)]
            await asyncio.sleep(0.1)
            t0 = time.monotonic()
            shed = await chat("late", timeout=0.3, max_tokens=4)
            waited = time.monotonic() - t0
            await asyncio.gather(*blockers)
            check("deadline(queue): shed with 503",
                  shed.status_code == 503, f"status={shed.status_code}")
            check("deadline(queue): Retry-After present",
                  "retry-after" in {k.lower() for k in shed.headers})
            check("deadline(queue): answered within deadline + slack",
                  waited <= 0.3 + DEADLINE_SLACK_S, f"waited={waited:.2f}s")
            if not quick:
                # Decode-stage: admitted, then cancelled mid-generation ->
                # 504, and the slot is free for the follow-up.
                t0 = time.monotonic()
                late = await chat("slow", timeout=0.3, max_tokens=100,
                                  ban_eos=True)
                waited = time.monotonic() - t0
                check("deadline(decode): 504", late.status_code == 504,
                      f"status={late.status_code}")
                check("deadline(decode): within deadline + slack",
                      waited <= 0.3 + DEADLINE_SLACK_S,
                      f"waited={waited:.2f}s")
        finally:
            faults.disarm("engine.decode")
        if not quick:
            follow = await chat(seed=1)
            check("deadline(decode): slot released, next request ok",
                  follow.status_code == 200 and text(follow) == greedy0)

        # ---- phase 3: breaker under a failure storm ----------------------
        print("phase 3: breaker", flush=True)
        engine.breaker.threshold = 2
        engine.breaker.window = 60.0
        engine.breaker.cooldown = 0.5
        for i in range(2):
            faults.arm("engine.decode", times=1)
            await chat("poison", seed=20 + i)
            faults.disarm()
        check("breaker: open after failure storm",
              engine.breaker.state == "open", engine.breaker.state)
        rejected = await chat("during-open")
        check("breaker: rejects with 503", rejected.status_code == 503,
              f"status={rejected.status_code}")
        check("breaker: 503 carries Retry-After",
              "retry-after" in {k.lower() for k in rejected.headers})
        health = (await client.get("/health")).json()
        check("health: degraded while breaker open",
              health["status"] == "degraded", health["status"])
        ready = await client.get("/ready")
        check("ready: 503 while breaker open", ready.status_code == 503)
        await asyncio.sleep(0.6)
        probe = await chat(seed=1)
        check("breaker: cooldown probe succeeds and closes it",
              probe.status_code == 200 and engine.breaker.state == "closed",
              f"status={probe.status_code} state={engine.breaker.state}")
        health = (await client.get("/health")).json()
        check("health: healthy after recovery",
              health["status"] == "healthy", health["status"])

        # ---- phase 4: fault-free path is untouched -----------------------
        print("phase 4: disarmed pinning", flush=True)
        faults.disarm()
        check("no site left armed", not faults.armed())
        greedy1 = text(await chat(seed=1))
        sampled1 = text(await chat(temperature=0.9, seed=7))
        check("greedy output pinned across chaos", greedy1 == greedy0)
        check("sampled output pinned across chaos", sampled1 == sampled0)

        # ---- phase 4b: disagg KV-handoff fault site under load -----------
        # A small disaggregated (1+1 device group) engine beside the main
        # colocated one: the prefill→decode handoff fails for ONE
        # admission while a streaming request decodes and a bystander
        # admission queues — only the faulted request dies, the stream and
        # bystander complete unchanged, no requeue storm, no rebuild, and
        # both group loops stay alive (docs/tpu_backends.md).
        if not quick:
            print("phase 4b: disagg kv handoff", flush=True)
            from quorum_tpu.engine.engine import InferenceEngine
            from quorum_tpu.models.model_config import resolve_spec
            from quorum_tpu.ops.sampling import SamplerConfig
            from quorum_tpu.parallel.mesh import disagg_meshes

            pm, dm = disagg_meshes(1, 1)
            tiny = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
            deng = InferenceEngine(
                tiny, dm, prefill_mesh=pm, decode_chunk=4, n_slots=2,
                prefill_chunk=16, seed=77)
            samp = SamplerConfig(temperature=0.0)
            base = deng.generate([3, 4, 5], max_new_tokens=6,
                                 sampler=samp).token_ids
            streamer = deng.submit([9, 8, 7], max_new_tokens=24,
                                   sampler=samp)
            stream_it = deng.stream_results(streamer)
            # The streamer must be past its OWN admission handoff before
            # the fault arms (times=1 must hit the victim's handoff, not
            # the stream's): its first token proves it is decoding.
            stream_toks = [next(stream_it)]
            faults.reset_counts()
            faults.arm("engine.kv_handoff", times=1)
            bad = deng.submit([5, 6, 7], max_new_tokens=6, sampler=samp)
            bystander = deng.submit([3, 4, 5], max_new_tokens=6,
                                    sampler=samp)
            err = None
            try:
                list(deng.stream_results(bad))
            except Exception as e:
                err = e
            by_toks = list(deng.stream_results(bystander))
            stream_toks += list(stream_it)
            faults.disarm()
            check("kv_handoff: fault fired",
                  faults.fired("engine.kv_handoff") >= 1)
            check("kv_handoff: failed handoff dooms its own request",
                  isinstance(err, faults.FaultInjected), repr(err))
            check("kv_handoff: queued bystander completes unchanged",
                  by_toks == base, f"{by_toks} != {base}")
            check("kv_handoff: concurrent stream unaffected",
                  len(stream_toks) == 24, f"len={len(stream_toks)}")
            follow = deng.generate([3, 4, 5], max_new_tokens=6,
                                   sampler=samp).token_ids
            check("kv_handoff: follow-up matches baseline", follow == base)
            check("kv_handoff: no device-state rebuild",
                  deng.n_rebuilds == 0, f"rebuilds={deng.n_rebuilds}")
            dh = deng.health()
            check("kv_handoff: both group loops alive",
                  dh["scheduler_alive"] and dh["prefill_scheduler_alive"])
            check("kv_handoff: KV crossed the group boundary",
                  deng.kv_handoff_bytes > 0)
            _flight_dump_check("kv_handoff", "engine.kv_handoff")
            deng.shutdown()

            # ---- phase 4b2: same drill under the PAGED decode cache ------
            # kv_pages=1 reshapes the handoff's decode side: the staged
            # admission pre-reserves the row's page span on the prefill
            # thread and the decode loop uploads the table before the hput
            # scatter. A failed handoff must unwind the page CLAIM too —
            # a leaked claim would strand pool pages until restart (the
            # bystander/follow-up checks would then shed or hang).
            print("phase 4b2: disagg kv handoff (paged)", flush=True)
            peng = InferenceEngine(
                tiny, dm, prefill_mesh=pm, decode_chunk=4, n_slots=2,
                prefill_chunk=16, seed=77, kv_pages=True)
            pbase = peng.generate([3, 4, 5], max_new_tokens=6,
                                  sampler=samp).token_ids
            check("paged handoff: disagg output matches dense twin",
                  pbase == base, f"{pbase} != {base}")
            faults.reset_counts()
            faults.arm("engine.kv_handoff", times=1)
            bad = peng.submit([5, 6, 7], max_new_tokens=6, sampler=samp)
            err = None
            try:
                list(peng.stream_results(bad))
            except Exception as e:
                err = e
            faults.disarm()
            check("paged handoff: fault fired",
                  faults.fired("engine.kv_handoff") >= 1)
            check("paged handoff: failed handoff dooms its own request",
                  isinstance(err, faults.FaultInjected), repr(err))
            follow = peng.generate([3, 4, 5], max_new_tokens=6,
                                   sampler=samp).token_ids
            check("paged handoff: follow-up matches baseline",
                  follow == base, f"{follow} != {base}")
            with peng._cond:
                leaked = [i for i, c in enumerate(peng._page_claims) if c]
            check("paged handoff: no leaked page claims", not leaked,
                  f"slot groups with live claims: {leaked}")
            pm_ = peng.metrics()
            check("paged handoff: pool accounting consistent",
                  pm_["kv_pages_allocated"] + pm_["kv_pages_free"]
                  == peng.kv_pool_pages)
            peng.shutdown()

        # ---- phase 4d: zero-drain injection-path faults ------------------
        # A colocated zero_drain=1 engine (ISSUE 11): an engine.admit or
        # engine.prefill_segment failure while the decode ring is full
        # dooms ONLY the injecting request — never an in-flight megachunk
        # or the queued bystander, with no device-state rebuild (staging
        # is the blast-radius boundary, exactly like a disagg prefill
        # fault) and zero admission stall throughout (the ring never
        # clamps for an admission under zero_drain).
        if not quick:
            print("phase 4d: zero-drain injection", flush=True)
            from quorum_tpu.engine.engine import InferenceEngine
            from quorum_tpu.models.model_config import resolve_spec
            from quorum_tpu.ops.sampling import SamplerConfig

            tiny = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
            zeng = InferenceEngine(
                tiny, decode_chunk=4, n_slots=2, decode_pipeline=4,
                decode_loop=2, prefill_chunk=16, zero_drain=True, seed=81)
            samp = SamplerConfig(temperature=0.0)
            zbase = zeng.generate([3, 4, 5], max_new_tokens=6,
                                  sampler=samp).token_ids
            long_ids = [(7 + 3 * i) % tiny.vocab_size for i in range(40)]
            zeng.generate(long_ids, max_new_tokens=2, sampler=samp)
            for site in ("engine.admit", "engine.prefill_segment"):
                # Budget past the ring's K*C*chunk capacity: a stream
                # that fits one ring fill would finish before the
                # injection faults even land.
                streamer = zeng.submit([9, 8, 7], max_new_tokens=48,
                                       sampler=samp)
                stream_it = zeng.stream_results(streamer)
                # The streamer must be decoding (its own injection done)
                # before the fault arms — times=1 must hit the victim.
                stream_toks = [next(stream_it)]
                faults.reset_counts()
                faults.arm(site, times=1)
                bad = zeng.submit(long_ids, max_new_tokens=6, sampler=samp)
                bystander = zeng.submit([3, 4, 5], max_new_tokens=6,
                                        sampler=samp)
                err = None
                try:
                    list(zeng.stream_results(bad))
                except Exception as e:
                    err = e
                by_toks = list(zeng.stream_results(bystander))
                stream_toks += list(stream_it)
                faults.disarm()
                check(f"zero-drain {site}: fault fired",
                      faults.fired(site) >= 1)
                check(f"zero-drain {site}: dooms only the injecting "
                      "request", isinstance(err, faults.FaultInjected),
                      repr(err))
                check(f"zero-drain {site}: queued bystander completes "
                      "unchanged", by_toks == zbase,
                      f"{by_toks} != {zbase}")
                check(f"zero-drain {site}: concurrent stream unaffected",
                      len(stream_toks) == 48, f"len={len(stream_toks)}")
                check(f"zero-drain {site}: no device-state rebuild",
                      zeng.n_rebuilds == 0, f"rebuilds={zeng.n_rebuilds}")
                _flight_dump_check(f"zero-drain {site}", site)
            follow = zeng.generate([3, 4, 5], max_new_tokens=6,
                                   sampler=samp).token_ids
            check("zero-drain: follow-up matches baseline",
                  follow == zbase)
            check("zero-drain: ring never clamped for admission",
                  zeng.admission_stall_s == 0.0,
                  f"stall={zeng.admission_stall_s}")
            check("zero-drain: injections overlapped live work",
                  zeng.n_admission_overlap >= 1,
                  f"overlap={zeng.n_admission_overlap}")
            check("zero-drain: scheduler alive",
                  zeng.health()["scheduler_alive"])
            zeng.shutdown()

        # ---- phase 5: HTTP backend retry ladder --------------------------
        print("phase 5: http retry", flush=True)
        from quorum_tpu.backends.http_backend import HttpBackend
        from quorum_tpu.observability import BACKEND_RETRIES

        calls = {"n": 0}

        def flaky(req: httpx.Request) -> httpx.Response:
            calls["n"] += 1
            if calls["n"] <= 2:
                return httpx.Response(500, json={"error": {
                    "message": "transient", "type": "server_error"}})
            return httpx.Response(200, json={
                "choices": [{"message": {"role": "assistant",
                                         "content": "ok"}}]})

        hb = HttpBackend(
            "flaky", "http://upstream.test/v1", "m", retries=3,
            client=httpx.AsyncClient(transport=httpx.MockTransport(flaky)))
        before = BACKEND_RETRIES.value_of(backend="flaky")
        result = await hb.complete({"messages": []}, auth, 10.0)
        check("http retry: transient 5xx recovered",
              result.status_code == 200 and calls["n"] == 3,
              f"status={result.status_code} calls={calls['n']}")
        check("http retry: backend_retries_total advanced",
              BACKEND_RETRIES.value_of(backend="flaky") == before + 2)
        # Injected connect-level fault at the http.request site retries too.
        faults.arm("http.request", times=1)
        result = await hb.complete({"messages": []}, auth, 10.0)
        faults.disarm()
        check("http retry: injected transport fault recovered",
              result.status_code == 200)
        await hb.aclose()

        # ---- phase 6: router replica-kill drill --------------------------
        # The multi-replica tier's containment contract (docs/scaling.md):
        # SIGKILL one replica under load — the survivor's in-flight stream
        # is untouched, requests keyed to the dead replica fail over and
        # complete elsewhere within their deadlines, the /ready poller
        # rotates the corpse out of the ring, and with EVERY replica dead
        # the router sheds 503 + Retry-After instead of hanging. Fake
        # (jax-free, killable) replica processes keep the drill about the
        # ROUTER's behavior, not engine boot time.
        if not quick:
            print("phase 6: router replica-kill", flush=True)
            await _router_kill_drill(check)

        # ---- phase 7: fleet trace continuity through failover ------------
        # One W3C trace-id across three processes (docs/observability.md
        # "Fleet plane"): kill a replica mid-stream, fail a request over,
        # and find its trace-id in the router's timeline, the survivor's
        # flight recorder, and the merged /debug/fleet/timeline.
        if not quick:
            print("phase 7: fleet trace continuity", flush=True)
            await _fleet_trace_drill(check)

        # ---- phase 8: QoS preemption under fault -------------------------
        # The qos=1 scheduler's contract (docs/scheduling.md): a
        # mid-decode park is token-exact for the victim, admits the
        # beneficiary, and a fault AT the park point (engine.preempt)
        # dooms only the victim with page accounting exact afterwards.
        if not quick:
            print("phase 8: qos preemption", flush=True)
            await _qos_preemption_drill(check)

        # ---- phase 9: zero-loss streams (resume + drain) -----------------
        # ISSUE 19's acceptance drill: SIGKILL mid-stream with resume ON
        # -> the client-visible sequence is identical to an uninterrupted
        # run; a refusing replay guard (and a fault at router.resume)
        # degrades to the phase-6 error-chunk contract with no duplicate
        # frames; draining 1-of-2 replicas under live traffic fails zero
        # requests and proactively resumes the parked stream.
        if not quick:
            print("phase 9: zero-loss stream resume + drain", flush=True)
            await _stream_resume_drill(check)

        # ---- phase 10: quorum member-kill degradation --------------------
        # Native quorum serving's containment contract (docs/quorum.md):
        # SIGKILL one member of a quorum=3 fan-out mid-generation. With a
        # spare cell the member resumes token-exact and the quorum stays
        # full; with no spare the request is served from the survivors
        # (plus the dead member's partial answer) — degraded on the
        # counters, never failed, never an error chunk.
        if not quick:
            print("phase 10: quorum member-kill", flush=True)
            await _quorum_member_kill_drill(check)

    from quorum_tpu.engine.engine import shutdown_all_engines

    shutdown_all_engines()


def run(quick: bool = False) -> dict:
    """Entry point shared with the tests/test_robustness.py smoke: run the
    sweep, return {"passed": n, "failed": n, "failures": [names]}."""
    _CHECKS.clear()
    # Flight-recorder dumps land in a fresh sweep-local dir (not the
    # serving logs/), un-rate-limited so every containment phase leaves
    # its own artifact for _flight_dump_check. The env override is
    # restored afterwards: the tests/test_robustness.py smoke calls run()
    # inside the pytest process, and later tests' dumps must keep their
    # own dir/rate-limit.
    saved = {k: os.environ.get(k) for k in
             ("QUORUM_TPU_FLIGHT_DIR", "QUORUM_TPU_FLIGHT_DUMP_INTERVAL")}
    os.environ["QUORUM_TPU_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="chaos-flightrec-")
    os.environ["QUORUM_TPU_FLIGHT_DUMP_INTERVAL"] = "0"
    try:
        asyncio.run(asyncio.wait_for(_run(quick), SCRIPT_TIMEOUT_S))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    failures = [name for name, ok, _ in _CHECKS if not ok]
    return {"passed": sum(1 for _, ok, _ in _CHECKS if ok),
            "failed": len(failures), "failures": failures}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--quick", action="store_true",
                   help="reduced sweep (one site, queue deadline only)")
    args = p.parse_args()
    t0 = time.time()
    try:
        out = run(quick=args.quick)
    except asyncio.TimeoutError:
        print(json.dumps({"error": "chaos sweep hung past watchdog"}),
              flush=True)
        return 2
    out["seconds"] = round(time.time() - t0, 1)
    print(json.dumps(out), flush=True)
    return 0 if out["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
