"""Router bench: prefix-affinity routing vs a random baseline at N replicas.

``make router-bench`` measures what the router tier is FOR — converting
extra replicas into prefix-cache hits instead of cold prefills:

  - **fake legs** (N=2 and N=4, seconds): jax-free scripted replicas
    (quorum_tpu/router/fake_replica.py) carrying a REAL PrefixStore each,
    driven through the real router app over real sockets. Measures
    affinity-vs-random prefix-hit rate with zero engine noise.
  - **real leg** (N=2, minutes on CPU): subprocess replicas serving tiny
    ``tpu://`` engines with ``prefix_store=host`` under slot churn
    (conversations > slots — the regime where the host store carries the
    hits), plus a dedicated single-replica baseline process for
    token-for-token output pinning. ``--skip-real`` / ``--mode fake``
    skips it.

Per leg it reports aggregate tok/s, prefix-hit rate (replica-side
``quorum_tpu_engine_prefix_store_hits_total`` deltas over the turns that
COULD hit — everything after each conversation's first), and per-replica
request spread; the affinity and random legs use disjoint conversation
families so one leg's store warmth cannot subsidize the other.

Acceptance (asserted, exit 1 on failure): affinity hit rate strictly above
random at every N, and per-conversation outputs token-for-token identical
to single-replica serving. ``tests/test_router_bench.py`` runs the fake
leg as a fast smoke inside ``make verify``'s test tier.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("QUORUM_TPU_COMPILE_CACHE", "0")

import httpx  # noqa: E402

REPLICA_BOOT_TIMEOUT_S = 240.0
CONCURRENCY = 4

ENGINE_URL = ("tpu://llama-tiny?seed=7&slots=2&queue=32&decode_chunk=4"
              "&prefill_chunk=16&prefix_store=host&prefix_store_chunk=16"
              "&max_seq=512&max_tokens=24")


def conversation_opening(family: str, i: int) -> str:
    """Distinct per-conversation opening, long enough to cover several
    prefix chunks (the store only retains whole chunks)."""
    return (f"[{family}/conv-{i:02d}] You are assisting with scenario "
            f"number {i} of family {family}. The running context is a "
            "long-lived support conversation whose history must be "
            "retained across turns so the key-value prefix cache can "
            "prove itself. Opening question: what should happen next?")


async def _chat(client: httpx.AsyncClient, base: str, body: dict) -> dict:
    r = await client.post(f"{base}/chat/completions", json=body,
                          headers={"Authorization": "Bearer bench"},
                          timeout=120.0)
    if r.status_code != 200:
        raise RuntimeError(f"chat HTTP {r.status_code}: {r.text[:300]}")
    return r.json()


async def drive_conversations(
    client: httpx.AsyncClient, base: str, *, family: str,
    n_conversations: int, turns: int, max_tokens: int, model: str,
    concurrency: int = CONCURRENCY,
) -> dict:
    """Run the multi-turn conversation load; returns outputs + timing."""
    sem = asyncio.Semaphore(concurrency)
    outputs: dict[int, list[str]] = {}
    total_tokens = 0

    async def one(i: int) -> None:
        nonlocal total_tokens
        msgs = [{"role": "user", "content": conversation_opening(family, i)}]
        outs = []
        for t in range(turns):
            async with sem:
                resp = await _chat(client, base, {
                    "model": model, "messages": msgs,
                    "temperature": 0.0, "max_tokens": max_tokens})
            content = resp["choices"][0]["message"]["content"]
            outs.append(content)
            total_tokens += (resp.get("usage") or {}).get(
                "completion_tokens", 0)
            msgs = msgs + [
                {"role": "assistant", "content": content},
                {"role": "user", "content": f"[{family}] follow-up {t}: "
                                            "and after that?"}]
        outputs[i] = outs

    t0 = time.perf_counter()
    await asyncio.gather(*(one(i) for i in range(n_conversations)))
    wall = time.perf_counter() - t0
    return {"outputs": outputs, "wall_s": wall,
            "completion_tokens": total_tokens,
            "tok_s": total_tokens / wall if wall > 0 else 0.0}


_METRIC_RE = re.compile(
    r'^(quorum_tpu_engine_[a-z_]+)\{backend="([^"]+)"\}\s+([0-9.eE+-]+)$')


async def replica_metrics(client: httpx.AsyncClient, url: str) -> dict:
    out: dict[str, float] = {}
    r = await client.get(f"{url}/metrics", timeout=30.0)
    for line in r.text.splitlines():
        m = _METRIC_RE.match(line.strip())
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(3))
    return out


async def measure_leg(
    client: httpx.AsyncClient, router_base: str, replica_urls: list[str],
    *, family: str, n_conversations: int, turns: int, max_tokens: int,
    model: str, concurrency: int = CONCURRENCY,
) -> dict:
    """One policy leg: drive the load through the router, report tok/s +
    the replica-side prefix-hit rate over the eligible (non-first) turns."""
    before = [await replica_metrics(client, u) for u in replica_urls]
    run = await drive_conversations(
        client, router_base, family=family,
        n_conversations=n_conversations, turns=turns,
        max_tokens=max_tokens, model=model, concurrency=concurrency)
    after = [await replica_metrics(client, u) for u in replica_urls]
    hits = sum(
        a.get("quorum_tpu_engine_prefix_store_hits_total", 0.0)
        - b.get("quorum_tpu_engine_prefix_store_hits_total", 0.0)
        for a, b in zip(after, before))
    requests = [
        a.get("quorum_tpu_engine_requests_total",
              a.get("quorum_tpu_engine_n_completed", 0.0))
        - b.get("quorum_tpu_engine_requests_total",
                b.get("quorum_tpu_engine_n_completed", 0.0))
        for a, b in zip(after, before)]
    eligible = n_conversations * (turns - 1)
    return {
        "tok_s": round(run["tok_s"], 2),
        "wall_s": round(run["wall_s"], 3),
        "completion_tokens": run["completion_tokens"],
        "prefix_hits": int(hits),
        "eligible_turns": eligible,
        "hit_rate": round(hits / eligible, 4) if eligible else 0.0,
        "requests_per_replica": [int(r) for r in requests],
        "outputs": run["outputs"],
    }


# ---- fake mode (in-process replicas, real sockets) -------------------------


async def _run_fake_async(n_replicas: int, *, n_conversations: int,
                          turns: int, max_tokens: int) -> dict:
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.router.fake_replica import (
        FakeReplicaState,
        create_fake_replica_app,
    )
    from quorum_tpu.server.serve import start_server

    import random as _random

    _random.seed(0)  # the random-policy leg is a REPRODUCIBLE baseline
    out: dict = {"n_replicas": n_replicas}
    legs = {}
    for policy, family in (("affinity", "A"), ("random", "B")):
        # Fresh replicas per leg: store warmth must not cross legs.
        servers, urls = [], []
        for i in range(n_replicas):
            st = FakeReplicaState(f"fake-{i}", max_tokens=max_tokens)
            srv = await start_server(
                create_fake_replica_app(st), "127.0.0.1", 0)
            servers.append(srv)
            urls.append(
                f"http://127.0.0.1:{srv.sockets[0].getsockname()[1]}")
        # Single-replica pinning baseline: its own fresh fake replica.
        base_state = FakeReplicaState("fake-single", max_tokens=max_tokens)
        base_srv = await start_server(
            create_fake_replica_app(base_state), "127.0.0.1", 0)
        base_url = (
            f"http://127.0.0.1:{base_srv.sockets[0].getsockname()[1]}")
        cfg = RouterConfig(
            replicas=[(f"fake-{i}", u) for i, u in enumerate(urls)],
            policy=policy, ready_interval=0.0)
        router_app = create_router_app(cfg)
        router_srv = await start_server(router_app, "127.0.0.1", 0)
        router_url = (
            f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
        try:
            async with httpx.AsyncClient() as client:
                # Serial turns: the fake legs measure PLACEMENT (hit
                # rate), and serial driving keeps bounded-load spill out
                # of the picture so the smoke is deterministic; the real
                # leg keeps concurrency for an honest tok/s.
                leg = await measure_leg(
                    client, router_url, urls, family=family,
                    n_conversations=n_conversations, turns=turns,
                    max_tokens=max_tokens, model="fake", concurrency=1)
                single = await drive_conversations(
                    client, base_url, family=family,
                    n_conversations=n_conversations, turns=turns,
                    max_tokens=max_tokens, model="fake")
        finally:
            await app_close(router_app)
            for srv in servers + [base_srv, router_srv]:
                srv.close()
        leg["outputs_pinned_vs_single"] = leg.pop(
            "outputs") == single["outputs"]
        legs[policy] = leg
    out.update(legs)
    out["affinity_gt_random"] = (
        legs["affinity"]["hit_rate"] > legs["random"]["hit_rate"])
    return out


async def app_close(router_app) -> None:
    mgr = router_app.state.get("replica_set")
    if mgr is not None:
        await mgr.aclose()


def run_fake(n_replicas: int = 2, *, n_conversations: int = 8,
             turns: int = 3, max_tokens: int = 8) -> dict:
    """Entry point shared with tests/test_router_bench.py."""
    return asyncio.run(_run_fake_async(
        n_replicas, n_conversations=n_conversations, turns=turns,
        max_tokens=max_tokens))


# ---- zero-loss stream resume legs (ISSUE 19) -------------------------------


async def _stream_and_maybe_break(client: httpx.AsyncClient, base: str,
                                  body: dict, *, break_after: int = 0,
                                  on_break=None) -> dict:
    """Stream ``body`` through ``base``; after ``break_after`` content
    chunks call ``on_break(routed_to)`` once (SIGKILL / scripted abort).
    Returns the delivered text plus the timing the resume leg reports."""
    out = {"text": "", "done": False, "error_chunks": 0, "routed": None,
           "chunks": 0, "broke_at": None, "first_after_break": None}
    async with client.stream(
            "POST", f"{base}/chat/completions", json=body,
            headers={"Authorization": "Bearer bench"},
            timeout=120.0) as resp:
        if resp.status_code != 200:
            raise RuntimeError(f"stream HTTP {resp.status_code}")
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
                out["text"] += delta["content"]
                out["chunks"] += 1
                if (out["broke_at"] is not None
                        and out["first_after_break"] is None):
                    out["first_after_break"] = time.perf_counter()
                if (on_break is not None and out["broke_at"] is None
                        and out["chunks"] >= break_after):
                    on_break(out["routed"])
                    out["broke_at"] = time.perf_counter()
    return out


def _resume_report(base: dict, got: dict, resumed: int) -> dict:
    """The shared resume-leg report: token-for-token vs the uninterrupted
    run, the client-visible resume gap, and the replayed-journal size from
    the router's recorder event."""
    from quorum_tpu.telemetry.recorder import RECORDER

    events = [e for e in RECORDER.snapshot()
              if e.get("kind") == "router-stream-resume"]
    gap = None
    if got["broke_at"] is not None and got["first_after_break"] is not None:
        gap = got["first_after_break"] - got["broke_at"]
    return {
        "token_exact": (got["text"] == base["text"] and got["done"]
                        and got["error_chunks"] == 0),
        "resumed": resumed,
        "replayed_tokens": events[-1].get("replayed") if events else None,
        "resume_latency_s": round(gap, 4) if gap is not None else None,
        "delivered_tokens": got["chunks"],
    }


async def _run_resume_fake_async(*, max_tokens: int = 40) -> dict:
    """Fake resume leg: two scripted replicas behind the resume-ON
    router; the serving replica dies (scripted abort) mid-stream and the
    client-visible sequence must equal the uninterrupted run."""
    from quorum_tpu.observability import ROUTER_STREAM_RESUMES
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.router.fake_replica import (
        FakeReplicaState,
        create_fake_replica_app,
    )
    from quorum_tpu.server.serve import start_server

    states, servers, urls = [], [], []
    for i in range(2):
        st = FakeReplicaState(f"fake-{i}", max_tokens=max_tokens,
                              chunk_delay=0.01)
        srv = await start_server(create_fake_replica_app(st),
                                 "127.0.0.1", 0)
        states.append(st)
        servers.append(srv)
        urls.append(f"http://127.0.0.1:{srv.sockets[0].getsockname()[1]}")
    cfg = RouterConfig(
        replicas=[(f"fake-{i}", u) for i, u in enumerate(urls)],
        policy="affinity", ready_interval=0.0)
    router_app = create_router_app(cfg)
    router_srv = await start_server(router_app, "127.0.0.1", 0)
    router_url = (
        f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
    try:
        async with httpx.AsyncClient() as client:
            body = {"model": "fake", "stream": True,
                    "max_tokens": max_tokens,
                    "messages": [{"role": "user", "content":
                                  conversation_opening("R", 0)}]}
            base = await _stream_and_maybe_break(client, router_url, body)
            before = ROUTER_STREAM_RESUMES.value_of(outcome="resumed")

            def scripted_abort(name: str) -> None:
                states[int(name.rsplit("-", 1)[1])].abort_after = 0

            got = await _stream_and_maybe_break(
                client, router_url, body, break_after=4,
                on_break=scripted_abort)
            resumed = int(ROUTER_STREAM_RESUMES.value_of(outcome="resumed")
                          - before)
    finally:
        await app_close(router_app)
        for srv in servers + [router_srv]:
            srv.close()
    return _resume_report(base, got, resumed)


def run_resume_fake(*, max_tokens: int = 40) -> dict:
    """Entry point shared with tests/test_router_bench.py."""
    return asyncio.run(_run_resume_fake_async(max_tokens=max_tokens))


async def _resume_leg(client: httpx.AsyncClient,
                      replicas: list[tuple[str, str]], base_url: str,
                      procs_by_name: dict, *, model: str,
                      max_tokens: int = 24) -> dict:
    """Real resume leg (N=2): SIGKILL the serving replica mid-stream;
    the resumed stream must be token-for-token identical to the
    single-replica baseline. Runs LAST — it leaves a corpse."""
    from quorum_tpu.observability import ROUTER_STREAM_RESUMES
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.server.serve import start_server

    cfg = RouterConfig(replicas=replicas, policy="affinity",
                       ready_interval=0.25, timeout=120.0)
    router_app = create_router_app(cfg)
    router_srv = await start_server(router_app, "127.0.0.1", 0)
    router_url = (
        f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
    try:
        body = {"model": model, "stream": True, "temperature": 0.0,
                "max_tokens": max_tokens,
                "messages": [{"role": "user", "content":
                              conversation_opening("Z", 0)}]}
        # the single-replica truth for this conversation
        base = await _stream_and_maybe_break(client, base_url, body)

        def sigkill(name: str) -> None:
            procs_by_name[name].kill()

        before = ROUTER_STREAM_RESUMES.value_of(outcome="resumed")
        got = await _stream_and_maybe_break(
            client, router_url, body, break_after=4, on_break=sigkill)
        resumed = int(ROUTER_STREAM_RESUMES.value_of(outcome="resumed")
                      - before)
    finally:
        await app_close(router_app)
        router_srv.close()
    return _resume_report(base, got, resumed)


# ---- cross-cell quorum legs (docs/quorum.md) -------------------------------


async def _first_byte_latency(client: httpx.AsyncClient, base: str,
                              body: dict) -> float:
    """Seconds from POST to the first streamed content delta — the TTFT a
    quorum client actually experiences (role chunks don't count)."""
    t0 = time.perf_counter()
    async with client.stream(
            "POST", f"{base}/chat/completions", json={**body, "stream": True},
            headers={"Authorization": "Bearer bench"},
            timeout=120.0) as resp:
        if resp.status_code != 200:
            raise RuntimeError(f"stream HTTP {resp.status_code}")
        async for line in resp.aiter_lines():
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data.strip() == "[DONE]":
                break
            ev = json.loads(data)
            delta = (ev.get("choices") or [{}])[0].get("delta") or {}
            if delta.get("content"):
                return time.perf_counter() - t0
    raise RuntimeError("stream produced no content delta")


async def _quorum_measurements(client: httpx.AsyncClient, base: str, *,
                               model: str, max_tokens: int, iters: int,
                               quorum: int, family: str) -> dict:
    """The fan-out latency A/B shared by the fake and real quorum legs:
    p50 first-content-byte latency of plain requests vs ``quorum: M``
    through the same router, plus one non-streaming combine's shape."""
    def body(i: int, **kw) -> dict:
        return {"model": model, "temperature": 0.0,
                "max_tokens": max_tokens, **kw,
                "messages": [{"role": "user", "content":
                              conversation_opening(family, i)}]}

    single = [await _first_byte_latency(client, base, body(i))
              for i in range(iters)]
    fanned = [await _first_byte_latency(client, base,
                                        body(i, quorum=quorum))
              for i in range(iters)]
    single_p50 = sorted(single)[len(single) // 2]
    quorum_p50 = sorted(fanned)[len(fanned) // 2]

    r = await client.post(f"{base}/chat/completions",
                          json=body(0, quorum=quorum),
                          headers={"Authorization": "Bearer bench"},
                          timeout=120.0)
    combined = r.json()
    q = combined.get("quorum") or {}
    return {
        "single_ttft_p50_s": round(single_p50, 4),
        "quorum_ttft_p50_s": round(quorum_p50, 4),
        "ttft_ratio": round(quorum_p50 / single_p50, 3)
        if single_p50 > 0 else None,
        "ttft_delta_s": round(quorum_p50 - single_p50, 4),
        "combine_status": r.status_code,
        "combine_outcome": ("full" if q.get("served") == quorum
                            else "degraded" if q.get("served")
                            else "failed"),
        "combine_served": q.get("served"),
        "combined_content": combined.get("choices", [{}])[0]
        .get("message", {}).get("content", ""),
    }


def _ttft_within_gate(leg: dict, *, ratio: float = 1.5,
                      slack_s: float = 0.05) -> bool:
    """The fan-out latency gate: quorum p50 TTFT within ``ratio``× the
    single-member p50 — with a small absolute floor so sub-millisecond
    fake TTFTs don't fail on scheduling jitter alone."""
    return (leg["ttft_ratio"] is not None
            and (leg["ttft_ratio"] <= ratio
                 or leg["ttft_delta_s"] <= slack_s))


async def _run_quorum_fake_async(*, iters: int = 10,
                                 max_tokens: int = 12) -> dict:
    """Fake quorum leg: 4 scripted replicas (20 ms first-byte floor so the
    TTFT ratio measures fan-out overhead, not socket jitter) behind the
    real router. Measures the latency A/B, pins the combine against the
    replicas' deterministic completion, then degrades: shedding one
    assigned member must stay full (spare covers), shedding the spare too
    must serve degraded — never fail."""
    from quorum_tpu.observability import QUORUM_DEGRADED
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.router.fake_replica import (
        FakeReplicaState,
        create_fake_replica_app,
        deterministic_completion,
    )
    from quorum_tpu.server.serve import start_server

    states, servers, urls = [], [], []
    for i in range(4):
        st = FakeReplicaState(f"fake-{i}", max_tokens=max_tokens,
                              chunk_delay=0.02)
        srv = await start_server(create_fake_replica_app(st),
                                 "127.0.0.1", 0)
        states.append(st)
        servers.append(srv)
        urls.append(f"http://127.0.0.1:{srv.sockets[0].getsockname()[1]}")
    cfg = RouterConfig(
        replicas=[(f"fake-{i}", u) for i, u in enumerate(urls)],
        policy="affinity", ready_interval=0.0)
    router_app = create_router_app(cfg)
    router_srv = await start_server(router_app, "127.0.0.1", 0)
    router_url = (
        f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
    try:
        async with httpx.AsyncClient() as client:
            out = await _quorum_measurements(
                client, router_url, model="fake", max_tokens=max_tokens,
                iters=iters, quorum=3, family="Q")
            prompt = conversation_opening("Q", 0)
            rendered = states[0].tokenizer.render_chat(
                [{"role": "user", "content": prompt}])
            want = "".join(deterministic_completion(rendered, max_tokens))
            out["combined_pinned"] = (
                out.pop("combined_content")
                == cfg.quorum_separator.join([want] * 3))

            # member-kill: shed one serving member → the spare covers
            body = {"model": "fake", "temperature": 0.0,
                    "max_tokens": max_tokens, "quorum": 3,
                    "messages": [{"role": "user", "content": prompt}]}
            r0 = await client.post(f"{router_url}/chat/completions",
                                   json=body, timeout=120.0)
            assigned = r0.headers["x-quorum-replicas"].split(",")
            spare = [f"fake-{i}" for i in range(4)
                     if f"fake-{i}" not in assigned][0]
            by_name = {st.name: st for st in states}
            by_name[assigned[0]].shedding = True
            t0 = time.perf_counter()
            r1 = await client.post(f"{router_url}/chat/completions",
                                   json=body, timeout=120.0)
            out["kill_with_spare_latency_s"] = round(
                time.perf_counter() - t0, 4)
            out["kill_with_spare_outcome"] = (
                "full" if r1.json().get("quorum", {}).get("served") == 3
                else "degraded")

            # ...and with the spare gone too: served degraded, never failed
            by_name[spare].shedding = True
            before = QUORUM_DEGRADED.value
            t0 = time.perf_counter()
            r2 = await client.post(f"{router_url}/chat/completions",
                                   json=body, timeout=120.0)
            out["degraded_latency_s"] = round(time.perf_counter() - t0, 4)
            out["degraded_status"] = r2.status_code
            out["degraded_served"] = r2.json().get(
                "quorum", {}).get("served")
            out["degraded_reason"] = r2.headers.get("x-quorum-degraded")
            out["degraded_counted"] = QUORUM_DEGRADED.value > before
    finally:
        await app_close(router_app)
        for srv in servers + [router_srv]:
            srv.close()
    return out


def run_quorum_fake(*, iters: int = 10, max_tokens: int = 12) -> dict:
    """Entry point shared with tests/test_router_bench.py."""
    return asyncio.run(_run_quorum_fake_async(
        iters=iters, max_tokens=max_tokens))


async def _quorum_leg(client: httpx.AsyncClient,
                      replicas: list[tuple[str, str]], *, model: str,
                      max_tokens: int, iters: int = 5) -> dict:
    """Real quorum leg: quorum=3 over three live engine cells (the two
    bench replicas + the baseline, enrolled as a third ring member —
    identical engines, so the combine pins against 3× one member's
    greedy output). Runs before the resume leg, which leaves a corpse."""
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.server.serve import start_server

    cfg = RouterConfig(replicas=replicas, policy="affinity",
                       ready_interval=0.0, timeout=120.0)
    router_app = create_router_app(cfg)
    router_srv = await start_server(router_app, "127.0.0.1", 0)
    router_url = (
        f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
    try:
        out = await _quorum_measurements(
            client, router_url, model=model, max_tokens=max_tokens,
            iters=iters, quorum=3, family="QR")
        # identical engines + temperature 0 → every member emits the same
        # answer; the combine must be exactly three copies of it
        single = await _chat(client, replicas[0][1], {
            "model": model, "temperature": 0.0, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content":
                          conversation_opening("QR", 0)}]})
        want = single["choices"][0]["message"]["content"]
        out["combined_pinned"] = (
            out.pop("combined_content")
            == cfg.quorum_separator.join([want] * 3))
    finally:
        await app_close(router_app)
        router_srv.close()
    return out


# ---- real mode (subprocess tpu:// engine replicas) -------------------------


def _spawn_replica(name: str, model: str,
                   extra_env: dict | None = None
                   ) -> tuple[subprocess.Popen, str]:
    """Spawn one real serving replica (tiny CPU engine, host prefix
    store); returns (process, base url) once it prints PORT=."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               QUORUM_TPU_COMPILE_CACHE="0", **(extra_env or {}))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve-replica",
         "--replica-name", name, "--replica-model", model],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)
    deadline = time.time() + REPLICA_BOOT_TIMEOUT_S
    port = None
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("PORT="):
            port = int(line.strip().split("=", 1)[1])
            break
    if port is None:
        proc.kill()
        raise RuntimeError(f"replica {name} never printed PORT=")
    return proc, f"http://127.0.0.1:{port}"


def serve_replica_main(name: str, model: str) -> None:
    """Child entry (--serve-replica): a full serving app over one tiny
    real engine, bound to an ephemeral port, PORT= printed for the
    parent."""
    from quorum_tpu.config import Config
    from quorum_tpu.server.app import create_app
    from quorum_tpu.server.serve import start_server

    cfg = Config(raw={
        "settings": {"timeout": 120},
        "primary_backends": [
            {"name": name, "url": ENGINE_URL, "model": model}],
    })
    app = create_app(cfg, watch_config=False)

    async def _main() -> None:
        server = await start_server(app, "127.0.0.1", 0)
        print(f"PORT={server.sockets[0].getsockname()[1]}", flush=True)
        async with server:
            await server.serve_forever()

    try:
        asyncio.run(_main())
    except KeyboardInterrupt:
        pass


async def _run_real_async(n_replicas: int, *, n_conversations: int,
                          turns: int, max_tokens: int) -> dict:
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.server.serve import start_server

    model = "rb"
    procs: list[subprocess.Popen] = []
    out: dict = {"n_replicas": n_replicas}
    try:
        print(f"[router-bench] booting {n_replicas} real replicas + "
              "1 baseline (tiny CPU engines; first compile dominates)",
              flush=True)
        replicas = []
        for i in range(n_replicas):
            # real-0 gets a microsecond interactive TTFT/gap target: the
            # fleet leg saturates ITS interactive burn with real scored
            # requests (no fake telemetry) to drive burn-aware demotion.
            # Observational only — the measured legs' requests carry no
            # deadline, classify as batch, and never touch these targets.
            extra = ({"QUORUM_TPU_SLO_TTFT_INTERACTIVE_S": "0.000001",
                      "QUORUM_TPU_SLO_GAP_INTERACTIVE_S": "0.000001"}
                     if i == 0 else None)
            proc, url = _spawn_replica(f"real-{i}", model, extra_env=extra)
            procs.append(proc)
            replicas.append((f"real-{i}", url))
        base_proc, base_url = _spawn_replica("real-single", model)
        procs.append(base_proc)

        legs = {}
        async with httpx.AsyncClient() as client:
            # Warm every replica's XLA programs with a throwaway family
            # BEFORE the measured legs — otherwise whichever leg runs
            # first eats the cold compiles and its tok/s is meaningless.
            for url in [u for _, u in replicas] + [base_url]:
                await drive_conversations(
                    client, url, family="W", n_conversations=2, turns=2,
                    max_tokens=max_tokens, model=model)
            for policy, family in (("affinity", "A"), ("random", "B")):
                cfg = RouterConfig(replicas=replicas, policy=policy,
                                   ready_interval=0.0)
                router_app = create_router_app(cfg)
                router_srv = await start_server(router_app, "127.0.0.1", 0)
                router_url = ("http://127.0.0.1:"
                              f"{router_srv.sockets[0].getsockname()[1]}")
                try:
                    leg = await measure_leg(
                        client, router_url,
                        [u for _, u in replicas], family=family,
                        n_conversations=n_conversations, turns=turns,
                        max_tokens=max_tokens, model=model)
                    single = await drive_conversations(
                        client, base_url, family=family,
                        n_conversations=n_conversations, turns=turns,
                        max_tokens=max_tokens, model=model)
                finally:
                    await app_close(router_app)
                    router_srv.close()
                leg["outputs_pinned_vs_single"] = leg.pop(
                    "outputs") == single["outputs"]
                legs[policy] = leg
                print(f"[router-bench] real N={n_replicas} {policy}: "
                      f"hit_rate={leg['hit_rate']} tok/s={leg['tok_s']} "
                      f"pinned={leg['outputs_pinned_vs_single']}",
                      flush=True)
        out.update(legs)
        out["affinity_gt_random"] = (
            legs["affinity"]["hit_rate"] > legs["random"]["hit_rate"])

        # ---- fleet observability leg (docs/observability.md) ---------
        # Same live replicas: (1) one sampled request's trace-id must
        # name it across the router's route event, the serving replica's
        # spans, and the engine's dispatch/reap in the MERGED fleet
        # timeline; (2) saturating real-0's interactive burn with real
        # scored requests must measurably cost it placements — demotion
        # counter up, every family-G request served by real-1, outputs
        # still token-for-token identical to single-replica serving.
        async with httpx.AsyncClient() as client:
            out["fleet"] = await _fleet_leg(
                client, replicas, base_url, model=model,
                max_tokens=max_tokens)
            print(f"[router-bench] real N={n_replicas} fleet: "
                  f"{json.dumps(out['fleet'])}", flush=True)

        # ---- cross-cell quorum leg (docs/quorum.md): the baseline
        # enrolls as a third ring member for a real 3-cell fan-out
        async with httpx.AsyncClient() as client:
            out["quorum"] = await _quorum_leg(
                client, replicas + [("real-single", base_url)],
                model=model, max_tokens=max_tokens)
            print(f"[router-bench] real N=3 quorum: "
                  f"{json.dumps(out['quorum'])}", flush=True)

        # ---- zero-loss resume leg (ISSUE 19) — LAST: it kills a replica
        procs_by_name = {name: proc
                         for (name, _), proc in zip(replicas, procs)}
        async with httpx.AsyncClient() as client:
            out["resume"] = await _resume_leg(
                client, replicas, base_url, procs_by_name, model=model)
            print(f"[router-bench] real N={n_replicas} resume: "
                  f"{json.dumps(out['resume'])}", flush=True)
    finally:
        for proc in procs:
            proc.kill()
        for proc in procs:
            proc.wait(timeout=30)
    return out


async def _fleet_leg(client: httpx.AsyncClient,
                     replicas: list[tuple[str, str]], base_url: str, *,
                     model: str, max_tokens: int) -> dict:
    from quorum_tpu.router.app import RouterConfig, create_router_app
    from quorum_tpu.server.serve import start_server

    # burn_threshold 0.4: a saturated replica's interactive window is
    # all-breached TTFT (+ gap when sampled) against good deadlines —
    # burn lands in [0.5, 0.67], comfortably above.
    cfg = RouterConfig(replicas=replicas, policy="affinity",
                       ready_interval=0.0, burn_threshold=0.4)
    router_app = create_router_app(cfg)
    mgr = router_app.state["replica_set"]
    router_srv = await start_server(router_app, "127.0.0.1", 0)
    router_url = (
        f"http://127.0.0.1:{router_srv.sockets[0].getsockname()[1]}")
    leg: dict = {}
    try:
        await mgr.poll_once()  # absorb telemetry + clock offsets

        # (1) trace continuity: sample one request through the router
        r = await client.post(
            f"{router_url}/chat/completions",
            json={"model": model, "temperature": 0.0,
                  "max_tokens": max_tokens,
                  "messages": [{"role": "user", "content":
                                conversation_opening("T", 0)}]},
            headers={"Authorization": "Bearer bench"}, timeout=120.0)
        trace_id = r.headers.get("x-request-id", "")
        served_by = r.headers.get("x-routed-to", "")
        fleet = (await client.get(
            f"{router_url}/debug/fleet/timeline", timeout=30.0)).json()
        # per-request events carry rid; the engine's batched
        # dispatch/reap carry the member list in rids
        mine = [ev for ev in fleet["events"]
                if ev.get("rid") == trace_id
                or trace_id in (ev.get("rids") or [])]
        kinds_by_proc: dict[str, set] = {}
        for ev in mine:
            kinds_by_proc.setdefault(ev["process"], set()).add(ev["kind"])
        leg["sampled_trace_id"] = trace_id
        leg["trace_kinds_by_process"] = {
            p: sorted(k) for p, k in kinds_by_proc.items()}
        leg["trace_joined"] = (
            r.status_code == 200 and len(trace_id) == 32
            and "router-route" in kinds_by_proc.get("router", set())
            and {"admit", "dispatch", "reap"} <= kinds_by_proc.get(
                served_by, set()))

        # (2) burn saturation: real interactive streams at real-0 breach
        # its microsecond TTFT/gap targets; its scored burn demotes it
        burn_url = dict(replicas)["real-0"]
        for i in range(6):
            resp = await client.post(
                f"{burn_url}/chat/completions",
                json={"model": model, "temperature": 0.0, "timeout": 5,
                      "stream": True, "max_tokens": 4,
                      "messages": [{"role": "user", "content":
                                    conversation_opening("S", i)}]},
                headers={"Authorization": "Bearer bench"}, timeout=120.0)
            resp.raise_for_status()
        tele = (await client.get(f"{burn_url}/debug/telemetry",
                                 timeout=30.0)).json()
        leg["real0_interactive_burn"] = (
            tele["slo"].get("interactive") or {}).get("burn_rate")
        await mgr.poll_once()
        demotions_before = mgr.n_burn_demotions
        leg["burn_demoted"] = sorted(mgr.burn_demoted())
        routed_through = await measure_leg(
            client, router_url, [u for _, u in replicas], family="G",
            n_conversations=4, turns=2, max_tokens=max_tokens,
            model=model)
        single = await drive_conversations(
            client, base_url, family="G", n_conversations=4, turns=2,
            max_tokens=max_tokens, model=model)
        leg["burn_demotions"] = mgr.n_burn_demotions - demotions_before
        # the demoted replica lost every placement: real-1 served all
        leg["requests_per_replica"] = routed_through[
            "requests_per_replica"]
        real0_idx = [n for n, _ in replicas].index("real-0")
        leg["demoted_lost_placements"] = (
            leg["burn_demotions"] > 0
            and routed_through["requests_per_replica"][real0_idx] == 0)
        leg["outputs_pinned_vs_single"] = (
            routed_through["outputs"] == single["outputs"])
        del routed_through["outputs"]
    finally:
        await app_close(router_app)
        router_srv.close()
    return leg


def run_real(n_replicas: int = 2, *, n_conversations: int = 8,
             turns: int = 3, max_tokens: int = 16) -> dict:
    return asyncio.run(_run_real_async(
        n_replicas, n_conversations=n_conversations, turns=turns,
        max_tokens=max_tokens))


# ---- CLI --------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    parser.add_argument("--mode", choices=("fake", "real", "all"),
                        default="all")
    parser.add_argument("--skip-real", action="store_true",
                        help="alias for --mode fake")
    parser.add_argument("--conversations", type=int, default=8)
    parser.add_argument("--turns", type=int, default=3)
    parser.add_argument("--tokens", type=int, default=16)
    parser.add_argument("--serve-replica", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--replica-name", default="replica",
                        help=argparse.SUPPRESS)
    parser.add_argument("--replica-model", default="rb",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.serve_replica:
        serve_replica_main(args.replica_name, args.replica_model)
        return 0

    mode = "fake" if args.skip_real else args.mode
    out: dict = {}
    failures = []
    if mode in ("fake", "all"):
        out["fake"] = {}
        for n in (2, 4):
            leg = run_fake(n, n_conversations=args.conversations,
                           turns=args.turns, max_tokens=8)
            out["fake"][f"n{n}"] = leg
            print(f"[router-bench] fake N={n}: affinity hit_rate="
                  f"{leg['affinity']['hit_rate']} vs random "
                  f"{leg['random']['hit_rate']}", flush=True)
            if not leg["affinity_gt_random"]:
                failures.append(f"fake n{n}: affinity hit rate not above "
                                "random")
            if not leg["affinity"]["outputs_pinned_vs_single"]:
                failures.append(f"fake n{n}: outputs diverged from "
                                "single-replica serving")
        q = run_quorum_fake()
        out["fake"]["quorum"] = q
        print(f"[router-bench] fake quorum: ttft {q['single_ttft_p50_s']}s "
              f"-> {q['quorum_ttft_p50_s']}s ({q['ttft_ratio']}x), "
              f"combine={q['combine_outcome']} "
              f"degraded_served={q['degraded_served']}", flush=True)
        if not _ttft_within_gate(q):
            failures.append("fake quorum: quorum=3 p50 TTFT not within "
                            f"1.5x single-member ({json.dumps(q)})")
        if not (q["combine_outcome"] == "full" and q["combined_pinned"]):
            failures.append("fake quorum: healthy combine not full/pinned")
        if q["kill_with_spare_outcome"] != "full":
            failures.append("fake quorum: spare did not cover a killed "
                            "member")
        if not (q["degraded_status"] == 200 and q["degraded_served"] == 2
                and q["degraded_counted"]):
            failures.append("fake quorum: member kill without spare did "
                            "not serve degraded")
    if mode in ("real", "all"):
        leg = run_real(2, n_conversations=args.conversations,
                       turns=args.turns, max_tokens=args.tokens)
        out["real"] = {"n2": leg}
        if not leg["affinity_gt_random"]:
            failures.append("real n2: affinity hit rate not above random")
        if not leg["affinity"]["outputs_pinned_vs_single"]:
            failures.append("real n2: outputs diverged from "
                            "single-replica serving")
        fleet = leg.get("fleet", {})
        if not fleet.get("trace_joined"):
            failures.append("real n2 fleet: sampled trace-id not joined "
                            "across router + replica + engine in the "
                            "merged timeline")
        if not fleet.get("demoted_lost_placements"):
            failures.append("real n2 fleet: burn-saturated replica did "
                            "not measurably lose placements")
        if not fleet.get("outputs_pinned_vs_single"):
            failures.append("real n2 fleet: outputs diverged under burn "
                            "demotion")
        quorum = leg.get("quorum", {})
        # wider absolute slack than the fake leg: real CPU-engine TTFTs
        # wobble by tens of ms run to run
        if not _ttft_within_gate(quorum, slack_s=0.25):
            failures.append("real quorum: quorum=3 p50 TTFT not within "
                            f"1.5x single-member ({json.dumps(quorum)})")
        if not (quorum.get("combine_outcome") == "full"
                and quorum.get("combined_pinned")):
            failures.append("real quorum: combine not full/pinned "
                            f"({json.dumps(quorum)})")
        resume = leg.get("resume", {})
        if not (resume.get("token_exact") and resume.get("resumed")):
            failures.append("real n2 resume: mid-stream kill did not "
                            "resume token-for-token vs single-replica "
                            f"({json.dumps(resume)})")
    out["failures"] = failures
    print(json.dumps(out), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
