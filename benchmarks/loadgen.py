"""The load generator: one general reader of a traffic file's parameters.

A traffic mix is data (``benchmarks/traffic/<mix>.json``): the loop kind, its
rate or client count, the ramp, a fixed list of (prompt tokens, completion
tokens) pairs, a warm-up list, the probe's lengths and the public source the
lengths were taken from (``check_traffic``). The schedule is the file's: the
pairs go out in the file's order, cycled, and an open loop's arrival times
come from the file's ``arrival_seed``. ``--seed`` chooses the prompt bytes
(and, in ``run.py``, the weights and the probe): it never changes which
lengths are due, in which order, or when. PR 24's chip runs showed why: with
the order left to the seed, one seed repeated to 0.1 % and two seeds differed
by 5 % on the same multiset.

Times are seconds on ``time.monotonic()`` relative to the window's start
``t0``: the ramp runs at negative times, the window is ``[0, W)``, the tail
goes on after it until every window request has finished.
"""

from __future__ import annotations

import http.client
import json
import random
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from serving import HEADERS

# The byte tokenizer's chat template is "user: <content>\nassistant:", one
# token per byte: a prompt of n tokens is n - 17 characters of content.
TEMPLATE_TOKENS = len("user: ") + len("\nassistant:")
ALPHABET = "abcdefghijklmnopqrstuvwxyz "
REQUEST_TIMEOUT_S = 150.0


NOTES = {"why", "grid_note", "warmup_note", "clip_note", "assumed"}
PARAMETERS = {"open": {"loop", "rate_per_s", "arrival_seed", "ramp_s",
                       "tail_s", "grid", "warmup", "probe", "source"},
              "closed": {"loop", "clients", "ramp_s", "grid", "warmup",
                         "probe", "source"}}


def check_traffic(traffic: dict) -> None:
    """A traffic file names exactly the parameters its loop kind has. A loop
    kind, a burst or a session parameter this generator does not know is an
    error, never a default: a mix it cannot generate must not run as
    another."""
    known = PARAMETERS.get(traffic.get("loop"))
    if known is None:
        raise ValueError(f"unknown loop kind {traffic.get('loop')!r}; "
                         f"known: {sorted(PARAMETERS)}")
    keys = set(traffic) - NOTES
    if keys != known:
        raise ValueError(
            f"a {traffic['loop']} loop takes exactly {sorted(known)}: "
            f"unknown {sorted(keys - known)}, missing {sorted(known - keys)}")
    for name in ("grid", "warmup"):
        if not traffic[name] or not all(
                isinstance(p, list) and len(p) == 2
                and all(isinstance(n, int) and n > 0 for n in p)
                for p in traffic[name]):
            raise ValueError(f"{name} is a non-empty list of [prompt "
                             "tokens, completion tokens] pairs")
    probe = traffic["probe"]
    if not (isinstance(probe, list) and len(probe) == 2
            and all(isinstance(n, int) and n > 0 for n in probe)):
        raise ValueError("probe is [prompt tokens, generated tokens]")
    if not (isinstance(traffic["source"], str) and traffic["source"]):
        raise ValueError("source names the public trace or data set the "
                         "lengths were taken from")


def scale_pair(pair, div) -> tuple[int, int]:
    """A (prompt, completion) pair, divided for the CPU rehearsal."""
    p, c = pair
    if div:
        p = max(TEMPLATE_TOKENS + 3, p // div["prompt_div"])
        c = max(2, c // div["completion_div"])
    return int(p), int(c)


def cycled(grid: list, n: int) -> list:
    """The grid's multiset extended to ``n`` entries, in the grid's order."""
    return [grid[i % len(grid)] for i in range(n)]


def prompt_text(rng: random.Random, n_tokens: int, index: int) -> str:
    """Content of exactly ``n_tokens - 17`` ASCII characters whose first
    characters are unique to ``index``, so no two prompts share a prefix the
    engine's prefix cache could reuse."""
    n = n_tokens - TEMPLATE_TOKENS
    head = f"{index:06d} "
    body = "".join(rng.choice(ALPHABET) for _ in range(max(0, n - len(head))))
    return (head + body)[:n]


def sorted_uniforms(rng: random.Random, n: int, lo: float, hi: float) -> list:
    return sorted(lo + (hi - lo) * rng.random() for _ in range(n))


def open_schedule(traffic: dict, window_s: float, div) -> list:
    """[(due, phase, prompt_tokens, completion_tokens)], sorted by due time.
    The count in each phase is fixed by rate x length; arrival times are
    sorted uniforms inside the phase (a Poisson process conditioned on its
    count) drawn from the file's ``arrival_seed``; the pairs are the grid in
    the file's order, cycled on through the phases. ``--seed`` changes none
    of it."""
    rng = random.Random(int(traffic["arrival_seed"]))
    rate = float(traffic["rate_per_s"])
    out = []
    for phase, lo, hi in (("ramp", -float(traffic["ramp_s"]), 0.0),
                          ("window", 0.0, window_s),
                          ("tail", window_s,
                           window_s + float(traffic["tail_s"]))):
        n = int(round(rate * (hi - lo)))
        pairs = cycled(traffic["grid"], len(out) + n)[len(out):]
        for due, pair in zip(sorted_uniforms(rng, n, lo, hi), pairs):
            out.append((due, phase) + scale_pair(pair, div))
    return out


def closed_sequence(traffic: dict, div):
    """An endless iterator of (prompt_tokens, completion_tokens): the grid in
    the file's order, cycled; the same for every ``--seed``."""
    pairs = list(traffic["grid"])
    i = 0
    while True:
        yield scale_pair(pairs[i % len(pairs)], div)
        i += 1


class Record(dict):
    """One request as the client saw it. Keys: index, phase, due, sent,
    prompt_tokens, max_tokens, status, rid, first (first content delta, any
    stream), end, streams {key: {first, last, deltas, chars, tokens,
    finish}}, error."""


def stream_request(port: int, content: str, max_tokens: int, rec: Record,
                   t0: float, cancel: threading.Event,
                   model: str = "benchmark") -> Record:
    """One ``/chat/completions`` SSE request, timed at the client. Member
    streams of a quorum answer arrive under ids ``chatcmpl-parallel-<i>``,
    the combined answer under ``chatcmpl-parallel-final``; a single backend
    streams under its own id (key ``single``)."""
    body = {"model": model, "stream": True, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": content}],
            "stream_options": {"include_usage": True}}
    streams: dict = {}
    rec.update(status=0, rid="", first=None, end=None, streams=streams,
               error="")
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        payload = json.dumps(body)
        conn.request("POST", "/chat/completions", body=payload,
                     headers=HEADERS)
        rec["sent"] = time.monotonic() - t0
        resp = conn.getresponse()
        rec["status"] = resp.status
        rec["rid"] = resp.getheader("X-Request-Id") or ""
        if resp.status != 200:
            rec["error"] = resp.read().decode("utf-8", "replace")[:300]
            return rec
        for raw in resp:
            if cancel.is_set():
                rec["error"] = "cancelled by the generator after the window"
                break
            if not raw.startswith(b"data: "):
                continue
            now = time.monotonic() - t0
            line = raw[6:].strip()
            if line == b"[DONE]":
                rec["end"] = now
                break
            chunk = json.loads(line)
            cid = chunk.get("id", "")
            usage = chunk.get("usage")
            if usage and "single" in streams:
                streams["single"]["tokens"] = usage.get("completion_tokens")
            for choice in chunk.get("choices") or []:
                m = re.fullmatch(r"chatcmpl-parallel-(\d+)", cid)
                if cid == "error" or choice.get("finish_reason") == "error":
                    rec["error"] = str((choice.get("delta") or {})
                                       .get("content"))[:300]
                    continue
                key = (f"member-{m.group(1)}" if m else
                       "final" if cid == "chatcmpl-parallel-final" else
                       "single")
                text = (choice.get("delta") or {}).get("content")
                finish = choice.get("finish_reason")
                if not text and not finish:
                    # a role-only chunk opens no stream: a stream is what
                    # delivered content or a finish reason under its id
                    continue
                s = streams.setdefault(key, {
                    "first": None, "last": None, "deltas": 0, "chars": 0,
                    "tokens": None, "finish": None})
                if finish:
                    s["finish"] = finish
                if not text:
                    continue
                if s["first"] is None:
                    s["first"] = now
                s["last"] = now
                s["deltas"] += 1
                s["chars"] += len(text)
                if rec["first"] is None and key != "final":
                    rec["first"] = now
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        conn.close()
    return rec


class LoadRun:
    """Drives one cell's schedule against the server and keeps every
    request's record. ``run()`` returns when every request due (open loop)
    or sent (closed loop) inside the window has finished."""

    def __init__(self, port: int, traffic: dict, seed: int, window_s: float,
                 div=None):
        self.port, self.traffic, self.seed = port, traffic, seed
        self.window_s, self.div = window_s, div
        self.records: list[Record] = []
        self.cancel = threading.Event()
        self._lock = threading.Lock()
        self._rng = random.Random(seed ^ 0x5DEECE66D)
        self._index = 0

    def _new_record(self, phase, due, p, c) -> tuple[Record, str]:
        with self._lock:
            idx = self._index
            self._index += 1
            text = prompt_text(self._rng, p, idx)
            rec = Record(index=idx, phase=phase, due=due, sent=None,
                         prompt_tokens=p, max_tokens=c)
            self.records.append(rec)
        return rec, text

    def window_done(self) -> bool:
        with self._lock:
            return all(r.get("end") is not None or r.get("error")
                       or (r.get("status") or 200) != 200
                       for r in self.records if r["phase"] == "window")

    def run(self, t0: float) -> None:
        if self.traffic["loop"] == "open":
            self._run_open(t0)
        else:
            self._run_closed(t0)

    # -- open loop: requests are due on a schedule, whatever the server does

    def _run_open(self, t0: float) -> None:
        schedule = open_schedule(self.traffic, self.window_s, self.div)
        pool = ThreadPoolExecutor(max_workers=96)
        futures = []
        try:
            for due, phase, p, c in schedule:
                if phase == "tail" and self.window_done():
                    break
                delay = t0 + due - time.monotonic()
                while delay > 0:
                    # one exact sleep up to the due time; the tail alone is
                    # cut short once the window's requests have finished
                    time.sleep(min(delay, 0.25) if phase == "tail" else delay)
                    if phase == "tail" and self.window_done():
                        break
                    delay = t0 + due - time.monotonic()
                if phase == "tail" and self.window_done():
                    break
                rec, text = self._new_record(phase, due, p, c)
                futures.append(pool.submit(
                    stream_request, self.port, text, c, rec, t0, self.cancel))
            deadline = time.monotonic() + REQUEST_TIMEOUT_S
            while not self.window_done() and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            self.cancel.set()  # tail requests still in flight stop reading
            pool.shutdown(wait=True)
        for f in futures:
            f.result()

    # -- closed loop: each client sends its next request when the last one
    # -- has fully answered

    def _run_closed(self, t0: float) -> None:
        seq = closed_sequence(self.traffic, self.div)
        seq_lock = threading.Lock()
        start = t0 - float(self.traffic["ramp_s"])
        errors: list = []

        def client() -> None:
            try:
                while not self.cancel.is_set():
                    now = time.monotonic() - t0
                    if now >= self.window_s and self.window_done():
                        return
                    phase = ("ramp" if now < 0 else
                             "window" if now < self.window_s else "tail")
                    with seq_lock:
                        p, c = next(seq)
                    rec, text = self._new_record(phase, now, p, c)
                    stream_request(self.port, text, c, rec, t0, self.cancel)
                    if rec["status"] != 200 or rec["error"]:
                        time.sleep(0.2)  # never spin on a failing server
            except Exception as e:  # surfaced by run(), never swallowed
                errors.append(e)

        delay = start - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(int(self.traffic["clients"]))]
        for t in threads:
            t.start()
        deadline = t0 + self.window_s + REQUEST_TIMEOUT_S
        while time.monotonic() < deadline:
            if (time.monotonic() - t0 >= self.window_s
                    and self.window_done()):
                break
            time.sleep(0.05)
        self.cancel.set()
        for t in threads:
            t.join(timeout=REQUEST_TIMEOUT_S)
        if errors:
            raise errors[0]
