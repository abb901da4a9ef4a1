"""The device ledger: one account of what a device group was given, in
order, on the dispatching loop's ``time.perf_counter``.

The rule. A device runs the programs it is given in the order it was given
them. The loop appends every program it dispatches (:meth:`DeviceLedger.
dispatch`) and reports every *landing*, a moment the host saw a program
finished: the end of a blocking fetch, or a ``jax.Array.is_ready`` probe's
first success (:meth:`Program.land`). Between two consecutive landings
``L0 < L1`` the programs dispatched in between ran in ``[max(L0, first
dispatch), L1]``: that interval is *busy* and is booked to them. Where the
ledger was empty at ``L0``, ``[L0, first dispatch]`` is *starved* and is
booked to the phase of the scheduler turn the loop had open, split at the
phase switches (:meth:`DeviceLedger.switch`), with the seconds the loop
spent in backend compiles there booked to ``compile``; what of it the loop
spent in its first phase, waiting with no work, is *idle* and kept apart
(nobody held the device up). So the ledger is a cursor (``head``) over the
loop's clock, and every instant behind it is booked once: to a program, to
a phase, or to idleness.

What is exact and what is shared. An interval that holds programs of one
class is that class's, to the second; among its programs it is shared by
weight (a prefill program's tokens as padded, a decode dispatch's steps),
so sums over a class are exact whatever the share. An interval that holds
prefill and decode programs both (no landing could be had between them) is
split by subtraction, the decode steps at the pace of the last dispatch
that ran alone, and its seconds are added to ``inexact["split"]``.
Programs that are neither (register, snapshot, restore, the marker) take
nothing from company of another class. A landing a probe saw is late: the
device may have starved before the probe; where nothing was queued behind
it the interval is booked busy and added to ``inexact["probe"]``.

The ledger also keeps, for each open ``prefill`` span (:meth:`DeviceLedger.
open`), which booked seconds fell inside it: of programs that advanced it
(``own``), of other prefill programs (``peer``), of decode dispatches that
landed while the span was open and had an own program out (``decode``:
the chunks the admission waited out) and of the others (``ahead``: a
chunk still on the device when the span ended, as where an admission's
segments and register all go out in one turn, behind the chunk in
flight), of neither (``other``), and starved. The parts are filled in
when the cursor passes the span's end, and add up to its length.

A loop that goes idle has nothing in flight that it will look at again:
what is still queued then (a snapshot slice, a register, what a contained
failure left behind) is landed at the switch, so that the idle stretch is
nobody's busy time.

One ledger per dispatching loop (a ``disagg`` engine's prefill loop has its
own); only that loop calls the mutating methods, any thread may call
:meth:`DeviceLedger.snapshot`.
"""

from __future__ import annotations

import statistics
import threading
import time
from collections import deque

from quorum_tpu.analysis import compile_watch

DECODE, PREFILL, OTHER = "decode", "prefill", "other"
# Readings of a segment token's pace that are kept; the segment rule uses
# their median (InferenceEngine._segment_room).
SEG_PACE_SAMPLES = 5
# Programs a loop may leave unlanded before the ledger lands them itself
# (a loop with no blocking fetch of its own must not grow the list).
MAX_PENDING = 256


class Program:
    """One dispatched program: its dispatch stamp ``t``, class, family and
    bucket, its ``weight`` in a shared interval (tokens as padded for a
    prefill program, steps for a decode dispatch), the open spans it
    advances, and optionally a ``witness``: a non-donated output whose
    readiness says the program has landed. ``t0``/``t1`` are the booked
    interval once landed; ``starved_before`` the seconds the device had
    stood dry, the loop not idle, when it was handed this program: 0 where
    the stretch met a compile (tracing and lowering around it are the
    loop's too, and no stall)."""

    __slots__ = ("ledger", "t", "cls", "family", "bucket", "weight",
                 "spans", "witness", "t0", "t1", "starved_before")

    def __init__(self, ledger, t, cls, family, bucket, weight, spans,
                 witness):
        self.ledger = ledger
        self.t = t
        self.cls, self.family, self.bucket = cls, family, bucket
        self.weight = weight
        self.spans = spans
        self.witness = witness
        self.t0 = self.t1 = None
        self.starved_before = 0.0

    @property
    def landed(self) -> bool:
        return self.t1 is not None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0 if self.landed else 0.0

    def land(self, t: float, exact: bool = True) -> None:
        """This program, and every one dispatched before it, had landed by
        ``t``. ``exact``: the host was blocked on it until ``t``; else a
        probe found it landed."""
        if not self.landed:
            with self.ledger._lock:
                self.ledger._land(self, t, exact)


class OpenSpan:
    """A ``prefill`` span's account: which booked seconds fell inside
    ``[t0, t1]`` (``t1`` None while open)."""

    __slots__ = ("ledger", "t0", "t1", "first", "own", "peer", "decode",
                 "ahead", "other", "starved", "sink")

    def __init__(self, ledger, t0: float):
        self.ledger = ledger
        self.t0, self.t1 = t0, None
        self.first = None  # dispatch stamp of the first program advancing it
        self.own = self.peer = self.decode = self.ahead = self.other = 0.0
        self.starved = 0.0
        self.sink = None

    def close(self, t1: "float | None" = None, sink=None) -> None:
        """The span ended at ``t1`` (None: now, unfinished); ``sink(self)``
        is called once the cursor has passed it and its parts are whole (at
        once if it has). ``sink`` None drops the account. Once only."""
        if self.t1 is None:
            with self.ledger._lock:
                self.t1 = time.perf_counter() if t1 is None else t1
                self.sink = sink
                self.ledger._settle()

    def parts_ms(self) -> dict:
        """What the span waited for, in ms: device time of the programs
        that advanced it, of other admissions' prefill programs, of decode
        dispatches that landed while it was open with a program of its own
        out (the chunks it waited out) and of those that landed after it
        had ended or before its first program, and the rest (the device
        dry, or on a program that is neither). Whole once the cursor has
        passed the span's end."""
        return dict(own_ms=round(self.own * 1e3, 3),
                    peer_ms=round(self.peer * 1e3, 3),
                    decode_wait_ms=round(self.decode * 1e3, 3),
                    decode_ahead_ms=round(self.ahead * 1e3, 3),
                    starved_ms=round((self.starved + self.other) * 1e3, 3))

    def _take(self, a: float, b: float, part: str) -> None:
        hi = b if self.t1 is None else min(b, self.t1)
        if hi > max(a, self.t0):
            setattr(self, part, getattr(self, part) + hi - max(a, self.t0))


class DeviceLedger:
    def __init__(self, phases, on_booked=None):
        self._lock = threading.Lock()
        self.pending: deque = deque()
        self.head = time.perf_counter()
        self.phase = phases[0]
        self._idle = phases[0]
        self._built = 0.0
        self.on_booked = on_booked
        # totals, read by snapshot()
        self.decode_s: dict = {}
        self.decode_steps = 0
        self.prefill_s: dict = {}
        self.prefill_n: dict = {}
        self.other_s = 0.0
        self.idle_s = 0.0
        self.starved_s = dict.fromkeys(phases[1:], 0.0)
        self.inexact_s = {"split": 0.0, "probe": 0.0}
        # the paces the segment rule reads, timed on the landings
        self.step_alone_s = 0.0
        self.seg_tok_s = 0.0
        self._seg_tok_samples: deque = deque(maxlen=SEG_PACE_SAMPLES)
        # non-idle starved seconds since the device ran dry; -inf once the
        # stretch has met a compile
        self._starved_run = 0.0
        self._open: list[OpenSpan] = []

    # -- the loop's side -------------------------------------------------------

    def dispatch(self, cls: str, family: str, bucket: int = 0,
                 weight: int = 0, spans=(), witness=None,
                 t: "float | None" = None) -> Program:
        """Append a program the loop has just handed to the device."""
        if t is None:
            t = time.perf_counter()
        with self._lock:
            if len(self.pending) >= MAX_PENDING:
                self._land(self.pending[-1], t, False)
            if not self.pending:
                self._starve(t)
            prog = Program(self, t, cls, family, bucket, weight,
                           tuple(spans), witness)
            for span in prog.spans:
                if span.first is None:
                    span.first = t
            prog.starved_before = max(0.0, self._starved_run)
            self._starved_run = 0.0
            self.pending.append(prog)
        return prog

    def switch(self, phase: "str | None", now: float) -> None:
        """The loop opens ``phase`` (None: it closed one and is between
        phases); a starved device's time up to ``now`` goes to the phase
        that was open. A loop going idle leaves nothing in flight: what is
        still queued has no landing to come and lands here."""
        with self._lock:
            if self.pending and phase == self._idle:
                self._land(self.pending[-1], now, False)
            if not self.pending:
                self._starve(now)
            if phase is not None:
                self.phase = phase

    def wait_before(self, prog: Program, block) -> None:
        """About to block on ``prog``: first wait, in order, on each
        witness queued ahead of it that has not landed yet, and land it
        there: one more wake-up, and the programs on either side get an
        interval each. A witness that is already in has no landing to give
        (the split then falls back to subtraction)."""
        if prog.landed:
            return
        for p in list(self.pending):
            if p is prog:
                return
            w, p.witness = p.witness, None
            if w is None or _is_ready(w):
                continue
            try:
                block(w)
            except Exception:  # a failed program's output: the fetch's to say
                continue
            p.land(time.perf_counter())

    def open(self, t0: float) -> OpenSpan:
        span = OpenSpan(self, t0)
        with self._lock:
            self._open.append(span)
        return span

    # -- booking: the one place seconds go to a program or a phase ------------

    def _starve(self, now: float) -> None:
        """[head, now] with nothing queued: to the open phase, the loop's
        compile seconds inside it to ``compile``."""
        dt = now - self.head
        if dt <= 0.0:
            return
        built = compile_watch.thread_seconds()
        dc = min(dt, max(0.0, built - self._built))
        self._built = built
        if dc:
            self.starved_s["compile"] += dc
            self._starved_run = float("-inf")
        if self.phase == self._idle:
            self.idle_s += dt - dc
        else:
            self.starved_s[self.phase] += dt - dc
            self._starved_run += dt
        for span in self._open:
            span._take(self.head, now, "starved")
        self.head = now
        self._settle()

    def _land(self, prog: Program, t: float, exact: bool) -> None:
        """[head, t] to the programs queued up to ``prog`` (caller holds
        the lock)."""
        group = []
        while self.pending:
            group.append(self.pending.popleft())
            if group[-1] is prog:
                break
        if not group:
            return
        t = max(t, self.head)
        busy = t - self.head
        self._share(group, self.head, busy, exact)
        self.head = t
        if not self.pending:
            self._built = compile_watch.thread_seconds()
            self._starved_run = 0.0
            if not exact:
                self.inexact_s["probe"] += busy
        self._settle()
        if self.on_booked is not None:
            for p in group:
                if p.cls != OTHER or p.seconds:  # not what company gave 0 s
                    self.on_booked(p)

    def _share(self, group, a: float, busy: float, exact: bool) -> None:
        """Book ``busy`` seconds from ``a`` to ``group``, in dispatch
        order, and time the paces."""
        by = {DECODE: [], PREFILL: [], OTHER: []}
        for p in group:
            by[p.cls].append(p)
        steps = sum(p.weight for p in by[DECODE])
        tokens = sum(p.weight for p in by[PREFILL])
        weight = {DECODE: steps, PREFILL: tokens, OTHER: 0}
        part = {DECODE: 0.0, PREFILL: 0.0, OTHER: 0.0}
        if by[DECODE] and by[PREFILL]:
            part[DECODE] = (min(busy, self.step_alone_s * steps)
                            if self.step_alone_s else busy)
            part[PREFILL] = busy - part[DECODE]
            self.inexact_s["split"] += busy
        else:
            part[DECODE if by[DECODE] else PREFILL if by[PREFILL]
                 else OTHER] = busy
        if exact:
            self._time_paces(len(by[DECODE]), steps, part[DECODE], tokens,
                             part[PREFILL])
        at = a
        for p in group:
            share = part[p.cls] * (p.weight / weight[p.cls] if weight[p.cls]
                                   else 1.0 / len(by[p.cls]))
            p.t0, p.t1 = at, at + share
            at = p.t1
            if p.cls == DECODE:
                self.decode_s[p.family] = (
                    self.decode_s.get(p.family, 0.0) + share)
                self.decode_steps += p.weight
            elif p.cls == PREFILL:
                key = (p.family, p.bucket)
                self.prefill_s[key] = self.prefill_s.get(key, 0.0) + share
                self.prefill_n[key] = self.prefill_n.get(key, 0) + 1
            else:
                self.other_s += share
            for span in self._open:
                if p.cls == PREFILL:
                    to = "own" if span in p.spans else "peer"
                elif p.cls == DECODE and (span.first is None
                                          or span.t1 is not None):
                    to = "ahead"
                else:
                    to = p.cls
                span._take(p.t0, p.t1, to)
        group[-1].t1 = a + busy  # rounding stays out of the cursor

    def _time_paces(self, dispatches: int, steps: int, decode_s: float,
                    tokens: int, prefill_s: float) -> None:
        """The two paces the segment rule reads, from an interval a
        blocking landing closed: a decode step's seconds where one
        dispatch ran with no prefill program beside it; a prefill token's,
        as padded, the median of the last few intervals that held any
        (one that met a compile, or noise around nothing, moves no
        median). An interval split by subtraction gives a token's pace
        only where a step's had been timed."""
        if dispatches == 1 and not tokens and steps:
            self.step_alone_s = decode_s / steps
        elif tokens and (not dispatches or self.step_alone_s):
            self._seg_tok_samples.append(prefill_s / tokens)
            self.seg_tok_s = statistics.median(self._seg_tok_samples)

    def _settle(self) -> None:
        """Hand the closed spans the cursor has passed to their sinks."""
        done = [s for s in self._open
                if s.t1 is not None and s.t1 <= self.head]
        if not done:
            return
        self._open = [s for s in self._open if s not in done]
        for s in done:
            if s.sink is not None:
                s.sink(s)

    # -- any thread ----------------------------------------------------------------

    def snapshot(self, now: "float | None" = None) -> dict:
        """The totals, with the stretch from the cursor to ``now`` counted
        where it will most likely go: to the open phase if nothing is
        queued, else to the class of the oldest program queued (a decode
        dispatch's with steps at the pace booked so far, so that a scrape
        in the middle of one moves no ratio of the two)."""
        if now is None:
            now = time.perf_counter()
        with self._lock:
            out = {
                "decode_s": dict(self.decode_s),
                "decode_steps": self.decode_steps,
                "prefill_s": dict(self.prefill_s),
                "prefill_n": dict(self.prefill_n),
                "other_s": self.other_s,
                "idle_s": self.idle_s,
                "starved_s": dict(self.starved_s),
                "inexact_s": dict(self.inexact_s),
            }
            ahead = max(0.0, now - self.head)
            first = self.pending[0] if self.pending else None
            phase = self.phase
        if first is None and phase == self._idle:
            out["idle_s"] += ahead
        elif first is None:
            out["starved_s"][phase] += ahead
        elif first.cls == DECODE:
            booked = sum(out["decode_s"].values())
            if booked:
                out["decode_steps"] += min(
                    first.weight, round(ahead * out["decode_steps"] / booked))
            out["decode_s"][first.family] = (
                out["decode_s"].get(first.family, 0.0) + ahead)
        elif first.cls == PREFILL:
            key = (first.family, first.bucket)
            out["prefill_s"][key] = out["prefill_s"].get(key, 0.0) + ahead
        else:
            out["other_s"] += ahead
        return out


def device_families(*ledgers) -> dict:
    """An engine's ledgers (None skipped) as the ``device_*`` families of
    its ``metrics()``, a labelled family as ``{labels: value}``; one with
    no sample yet shows an unlabelled zero, so that a scrape has it."""
    tot = None
    for led in filter(None, ledgers):
        snap = led.snapshot()
        if tot is None:
            tot = snap
            continue
        for key, val in snap.items():
            if isinstance(val, dict):
                for k, v in val.items():
                    tot[key][k] = tot[key].get(k, 0) + v
            else:
                tot[key] += val

    def labelled(table, label, digits=6):
        return {label(k): round(v, digits)
                for k, v in sorted(table.items())} or {"": 0}

    def by_bucket(key):
        return f'family="{key[0]}",bucket="{key[1]}"'

    return {
        "device_decode_seconds_total": labelled(
            tot["decode_s"], lambda f: f'family="{f}"'),
        "device_decode_steps_total": tot["decode_steps"],
        "device_prefill_seconds_total": labelled(tot["prefill_s"], by_bucket),
        "device_prefill_programs_total": labelled(
            tot["prefill_n"], by_bucket, 0),
        "device_other_seconds_total": round(tot["other_s"], 6),
        "device_idle_seconds_total": round(tot["idle_s"], 6),
        "device_starved_seconds_total": labelled(
            tot["starved_s"], lambda p: f'phase="{p}"'),
        "device_inexact_seconds_total": labelled(
            tot["inexact_s"], lambda w: f'why="{w}"'),
    }


def _is_ready(witness) -> bool:
    try:
        import jax
        return all(x.is_ready() for x in jax.tree.leaves(witness)
                   if hasattr(x, "is_ready"))
    except Exception:
        return True
