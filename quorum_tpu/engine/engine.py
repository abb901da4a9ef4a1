"""The inference engine: continuous batching over a slot-based KV cache.

TPU-first design (SURVEY.md §7, hard parts 1-3; redesigned in round 2 per
VERDICT.md weakness 4 — the round-1 engine allocated a fresh KV cache on the
host per request and held a lock for the whole generation, fully serializing
concurrent requests):

  - **Slot-batched KV cache, allocated once**: ``[L, n_slots, max_seq, K·hd]``
    × 2 lives on device for the engine's lifetime and is donated through every
    compiled call — no per-request host zeros, no 1 GB device_put per request.
  - **Continuous batching**: a scheduler thread admits requests into free
    slots (prefill writes the prompt's K/V *directly into the slot* — see
    transformer.prefill_into_slot) and runs batched decode chunks over all
    active slots. Decode is HBM-bound on the weights, so co-batched requests
    decode at nearly the latency of one; N concurrent requests complete in
    ≪ N× serial time.
  - **Per-slot sampler state as arrays**: temperature/top_p/top_k/PRNG-key
    live in [n_slots] device arrays, so ONE compiled decode program serves
    every sampler configuration (sampling is row-independent — see
    ops.sampling.sample_token_rows). No per-config program cache.
  - **Chunked decode**: each dispatch scans ``decode_chunk`` steps, so the
    host syncs once per chunk, not per token; admission happens at chunk
    boundaries (a new request waits at most one chunk + its own prefill).
  - **Depth-K dispatch pipeline** (``decode_pipeline=K``, default 2): the
    scheduler keeps up to K decode chunks in flight and blocks only on the
    oldest, so the device rolls chunk-to-chunk while the host detokenizes,
    SSE-emits, and schedules. Safe at any depth because finish detection
    is ON DEVICE: per-row EOS and remaining-budget checks run inside the
    chunk program (a finished row stops sampling and stops writing cache),
    and each chunk returns per-row ``n_valid`` — overrun tokens are never
    produced for EOS/budget finishes, at any K (PERF.md §2).
  - **Determinism**: each request's sampling stream is keyed by its own seed
    at admission, and every op is row-independent, so results don't depend on
    which slot a request lands in or what else is co-batched with it.
  - **Mesh-agnostic**: parameters and cache are placed with NamedShardings
    from quorum_tpu.parallel.sharding; the same code runs on a 1-device CPU
    mesh (tests), a single TPU chip (bench), or a tp×dp slice (GSPMD inserts
    the collectives).
  - **Stacked fan-out members** (``members=M``): the N-model quorum's weight
    sets live stacked on ONE engine (block leaves layers-major
    ``[L, M, …]``, the rest ``[M, …]``: ``sharding.member_axes``, so that
    no program re-lays them); every decode chunk and coalesced
    admission (single-shot or chunked segment)
    advances ALL members in a single member-vmapped program — N models'
    streams for one host turnaround per dispatch.
  - **Tiered prefix caching**: each slot's resident token prefix is reusable
    zero-copy (tier 0); with ``prefix_store=host`` the engine additionally
    snapshots released slots' KV prefixes to a chunk-granular host-RAM
    store (quorum_tpu/cache/prefix_store.py, byte-budget LRU) and restores
    the longest match host→device at admission when it beats the
    slot-resident LCP — multi-turn conversations survive slot eviction
    under churn (docs/prefix_cache.md).
  - **On-device constrained decoding**: a request with a compiled grammar
    (``response_format`` JSON mode / JSON Schema / regex —
    quorum_tpu/constrain/, docs/structured_output.md) threads a per-row
    token-DFA state through every decode chunk: logits are masked by the
    state's allow-set before sampling and the state advances on the
    sampled token, all inside the chunk program — grammar-valid output
    with zero extra host round-trips at any ``decode_pipeline`` depth.
    Unconstrained batches compile and run the exact unconstrained program
    variant (the logprobs-gating pattern).
  - **Quantized representations**: ``quant=int8`` stores weights int8 with
    per-channel scales (native int8 MXU matmuls); ``kv_quant=int8`` stores
    the KV cache as (int8, per-token scale) pairs with native int8 decode
    attention. Both halve their side's HBM bytes; they compose.
  - **Disaggregated prefill/decode** (``disagg=P+D``): admission prefill
    programs compile and run on their own device group (a second weight
    copy + a staging KV cache on the prefill mesh), the decode ring owns
    the decode group, and a completed admission's KV prefix hands off
    device→device chunk-by-chunk into the claimed decode slot
    (quorum_tpu/cache/kv_transfer.py) — handoff of chunk i overlaps
    prefill of chunk i+1. The scheduler becomes two cooperating loops
    (``_prefill_scheduler`` admits/prefills/hands off; ``_scheduler``
    registers/decodes) with ``_handoffs`` as the queue between them, so
    admission bursts never stretch streaming inter-token gaps: the decode
    ring keeps its full depth regardless of admission pressure
    (docs/tpu_backends.md).

The reference has no analog — its "backends" are HTTP calls
(/root/reference/src/quorum/oai_proxy.py:182-192). This module is what makes a
``tpu://`` backend a real local model.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import queue
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quorum_tpu import faults
from quorum_tpu import observability as obs
from quorum_tpu.analysis import budget as _budget
from quorum_tpu.analysis import compile_watch
from quorum_tpu.breaker import (  # noqa: F401  (constants re-exported)
    BREAKER_COOLDOWN_S,
    BREAKER_THRESHOLD,
    BREAKER_WINDOW_S,
    Breaker,
)
from quorum_tpu.telemetry.device_ledger import (DECODE, OTHER, PREFILL,
                                                DeviceLedger,
                                                device_families)
from quorum_tpu.telemetry.latency import LatencyModel
from quorum_tpu.telemetry.recorder import RECORDER as FLIGHT
from quorum_tpu.cache import kv_transfer
from quorum_tpu.cache.paging import (
    PageAllocator,
    PagedKV,
    init_paged_cache,
    paged_copy_page,
    validate_page_config,
)
from quorum_tpu.cache.prefix_store import (
    DEFAULT_PREFIX_STORE_BYTES,
    PrefixStore,
)
from quorum_tpu.compile_cache import enable_persistent_compile_cache
from quorum_tpu.devices import device_report
from quorum_tpu.engine.prepare import Preparation
from quorum_tpu.models.init import init_params_sharded
from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.patterned import STATS as MOE_STATS
from quorum_tpu.models.patterned import KindKV
from quorum_tpu.models.patterned import stats_of as moe_stats_of
from quorum_tpu.models.ssm import StateKV
from quorum_tpu.models.transformer import (
    decode_chunk,
    decode_loop,
    decode_step,
    init_cache,
    prefill,
    prefill_segment,
)
from quorum_tpu.ops.flash_attention import tracing_program
from quorum_tpu.ops.flash_decode import (
    decode_tile,
    kernel_refusal,
    live_tiles,
)
from quorum_tpu.ops.sampling import (
    SamplerConfig,
    apply_token_mask,
    sample_token_rows,
)
from quorum_tpu.parallel.mesh import single_device_mesh
from quorum_tpu.parallel.sharding import (
    BLOCKS,
    kv_cache_sharding,
    member_axes,
    member_params,
    paged_kv_sharding,
    shard_pytree,
)
from quorum_tpu.sched import (
    PRIORITY_CLASSES,
    CostModel,
    PreemptionController,
    SchedPolicy,
)

enable_persistent_compile_cache()  # restart compiles become disk reads
compile_watch.install()  # count XLA compiles (quorum_tpu_recompiles_total)

logger = logging.getLogger(__name__)

MIN_BUCKET = 16
DEFAULT_SLOTS = 4
DEFAULT_PREFILL_CHUNK = 512
DEFAULT_MAX_PENDING = 128
# Decode-dispatch pipeline depth: how many decode chunks the scheduler keeps
# in flight on the device, blocking only on the oldest. 1 = fully
# synchronous (dispatch, read, repeat); 2 = the depth the old "paired chunk
# dispatch" special case provided; deeper hides more consecutive host
# turnarounds (PERF.md §2). Safe at any depth because finish detection is
# ON DEVICE: a row that hits EOS or its token budget mid-chunk stops
# sampling/writing inside the program, so in-flight chunks never produce
# overrun tokens for it.
DEFAULT_DECODE_PIPELINE = 2
# Megachunk decode ("Kernel Looping", PAPERS.md): how many decode chunks ONE
# dispatch may cover on device (decode_loop=C; 1 = today's one-chunk
# programs, byte-for-byte — the cache-key pin in tests/test_decode_loop.py).
# C>1 fuses the chunk-dispatch boundary itself: the device rolls chunk to
# chunk inside one program (with an all-rows-finished early exit) while the
# host only drains the token ring buffer. Bounded so a pathological config
# can't pin the device for seconds per dispatch (the deadline clamp in
# _effective_loop halves it further per dispatch as needed).
DEFAULT_DECODE_LOOP = 1
MAX_DECODE_LOOP = 64
# EWMA weight for the per-chunk device-latency estimate feeding the
# deadline clamp on the effective megachunk length.
CHUNK_EWMA_ALPHA = 0.3
# The stall witness (_note_stall): one blocking wait on a landing, or one
# stretch the device stood dry while the loop was neither idle nor
# compiling, longer than this and than this many times the family's booked
# median, is counted, logged and dumped.
STALL_MIN_S = 2.0
STALL_MEDIANS = 10.0
# Concurrent scoring/embedding device forwards per engine (see
# ``score_gate`` in InferenceEngine.__init__); excess requests 503.
SCORE_GATE_SLOTS = 2
TOP_LOGPROBS = 20  # top alternatives computed per step (OpenAI's API maximum)
# Prefix caching: reuse a free slot's resident KV prefix only when the match
# is at least this long — shorter matches aren't worth routing through the
# segment path (whose first token costs one extra decode-chunk boundary).
MIN_PREFIX_REUSE = 16
# Max dispatched-but-unfetched prefix-store snapshots: each pins a device-
# resident KV slice until the worker fetches it, so the bound is what keeps
# snapshot device memory finite under churn faster than one worker drains
# (past it, releases simply go unsnapshotted — a future store miss).
SNAP_QUEUE_MAX = 8
# Constrained decoding (docs/structured_output.md): the device-side grammar
# arena keeps every grammar's token-DFA rows at a STABLE offset while any
# request might reference them, so per-row DFA states never need remapping.
# Offsets only ever grow; when no constrained request is pending/active the
# arena may reset — but only once it exceeds this many states, so a steady
# one-grammar workload keeps its uploaded table (and its offset) warm
# across requests instead of re-uploading per admission.
CONSTRAIN_ARENA_KEEP = 4096
# Hard ceiling on arena growth: the table is [states, vocab] int32, so
# client-driven distinct-schema traffic on a server that never fully
# quiesces would otherwise grow device memory without bound (at a 128k
# vocab, 8192 states ≈ 4 GB). Past the cap a NEW grammar's admission
# fails alone (503-style GrammarArenaFull, retry after quiescence or with
# an already-resident grammar) — never the co-batched streams.
CONSTRAIN_ARENA_MAX = 8192
# The parts of a scheduler turn (InferenceEngine._phase; docs/
# observability.md "The engine's turn"): metrics() exports each as
# turn_<phase>_seconds_total, a running profile shows each as an
# ``engine.<phase>`` annotation on the scheduler thread's host line.
# ``reap_block`` is every wait in a blocking host fetch: the oldest decode
# chunk's, and inside ``admit`` a single-shot admission's first token.
TURN_PHASES = ("idle", "sweep", "admit", "fill", "reap_block", "emit",
               "compile")
_CKPT_MEMBERS_ERROR = ("stacked members are seeded random inits; a "
                       "checkpoint provides only one weight set")


class QueueFullError(Exception):
    """The engine's admission queue is at capacity (surface as HTTP 503)."""


class DeadlineExceeded(Exception):
    """A request ran past its deadline. ``stage`` names where the scheduler
    caught it: ``"queue"`` — shed while still pending, the engine never
    started serving it (surface as 503 + Retry-After, safe to retry
    elsewhere); ``"prefill"``/``"decode"`` — cancelled after admission
    (surface as 504, work was lost)."""

    def __init__(self, stage: str):
        super().__init__(f"request deadline exceeded ({stage})")
        self.stage = stage


class GrammarArenaFull(RuntimeError):
    """The device grammar arena is at capacity (CONSTRAIN_ARENA_MAX) and
    cannot place another distinct grammar until constrained traffic
    quiesces and the arena resets. Surfaced per-request (503-style —
    retryable; resident grammars keep serving)."""


class ReplayDivergence(RuntimeError):
    """A replay guard byte-compare failed: a token regenerated during a
    preemption resume (or a cross-replica stream resume submitted with
    ``resume_tokens``) did not equal the token already delivered to the
    client. The determinism contract (token sequence = f(prompt, seed,
    sampler)) broke — the stream fails LOUDLY with this distinct error so
    callers (the router's resume path above all) can tell "this resume
    must not be retried, degrade to the error-chunk contract" apart from
    an ordinary transport failure they may fail over."""

    def __init__(self, position: int, regenerated: int | None = None,
                 delivered: int | None = None, *,
                 message: str | None = None):
        super().__init__(
            message if message is not None else
            f"replay diverged at position {position}: regenerated token "
            f"{regenerated} != delivered token {delivered}")
        self.position = position


class EngineBreakerOpen(Exception):
    """The engine's failure breaker is open: repeated device-state rebuilds
    inside the sliding window mean new admissions would likely hit the same
    fault. Surface as 503 with ``Retry-After: ceil(retry_after)``."""

    def __init__(self, retry_after: float):
        super().__init__(
            f"engine circuit breaker is open; retry in {retry_after:.1f}s")
        self.retry_after = retry_after


# The sliding-window failure breaker moved to quorum_tpu/breaker.py when
# the multi-replica router tier grew its per-replica instance (the same
# state machine over upstream failures); re-exported under its
# historical private name so existing imports keep working.
_Breaker = Breaker


def _host_fetch(*arrays):
    """``jax.device_get`` for program outputs the scheduler must read.

    On a mesh that spans processes (multi-host serving, SPMD dispatch) XLA
    may shard a program output over a cross-process axis, making it
    non-addressable from any single host; every process then executes the
    same allgather (symmetric — all hosts run identical dispatch sequences,
    see tests/serving_worker.py) to assemble the global value. Addressable
    arrays — every single-process mesh — take the plain device_get path
    untouched. Returns a tuple for multiple arrays, the bare value for one.
    """
    def gather(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return multihost_utils.process_allgather(x, tiled=True)
        return x

    # THE designated device->host sync: one blocking fetch per dispatch
    # reap, nothing else on the token path may transfer implicitly.
    out = jax.device_get(  # qlint: allow-sync(the one blocking read per dispatch)
        tuple(gather(x) for x in arrays))
    return tuple(out) if len(arrays) > 1 else out[0]


def _block(witness) -> None:
    """Wait for a program's witness ahead of the turn's blocking fetch."""
    # qlint: allow-sync(inside the turn's one blocking wait: a landing between the programs queued ahead of the fetched one)
    jax.block_until_ready(witness)


def _member_vmap(fn, params, *args):
    """``fn`` vmapped over the members of a stacked engine: the stacked
    weight tree mapped where ``member_axes`` says its member axis is (block
    leaves are held layers-major, so the layer scan inside ``fn`` reads
    them where they lie), every other argument at axis 0. The one call
    every member-vmapped program family makes (decode chunk through
    :func:`_stacked_rows_call`, member admit, member segment)."""
    in_axes = (member_axes(params),) + (0,) * len(args)
    return jax.vmap(fn, in_axes=in_axes)(params, *args)


def _stacked_rows_call(mem: int, n_s: int, fn, params, ck, cv, *rows):
    """Member-vmapped model call over flat member-major row arrays.

    Each array in ``rows`` ([M·S, …]) folds to [M, S, …] for the vmap;
    ``fn(params_m, ck_m, cv_m, *rows_m)`` returns (logits, ck, cv) for one
    member; the stacked logits unfold back to flat rows. The home of the
    stacked decode chunk's fold/unfold convention."""
    folded = tuple(r.reshape((mem, n_s) + r.shape[1:]) for r in rows)
    logits, ck, cv = _member_vmap(fn, params, ck, cv, *folded)
    return logits.reshape((mem * n_s,) + logits.shape[2:]), ck, cv


class _MemberFirst:
    """A layers-major block leaf ``[L, M, …]`` of a stacked engine, indexed
    member first: ``leaf[member, layer, …]``. What ``InferenceEngine.params``
    hands a host reader that takes one member's layer by index (the
    benchmark's reference check); the slice runs on the device, the leaf is
    never re-laid."""

    __slots__ = ("leaf",)

    def __init__(self, leaf):
        self.leaf = leaf

    def __getitem__(self, idx):
        member, layer, *rest = idx
        return self.leaf[(layer, member, *rest)]


def _program(memo: str, key, kept=False):
    """A builder of one jitted program, memoised: the decorated method
    returns ``getattr(self, memo)[key(self, *args)]``, making it with the
    method's body where the memo has none (``InferenceEngine._memo``).
    ``kept``: whether the program store (engine/prepare.py) keeps the
    compiled program across starts, under the builder's name and the key;
    a predicate of the key where only some of a builder's variants are.
    The programs every request can reach are kept; the variants behind an
    option (constrained, megachunked, deduplicated, the prefix
    store's and the handoff's) are built on demand in every process."""
    def deco(make):
        @functools.wraps(make)
        def get(self, *args, **kw):
            return self._memo(get, get.key(self, *args, **kw), args, kw)
        get.memo, get.key, get.make = memo, key, make
        get.kept = kept if callable(kept) else (lambda key: kept)
        return get
    return deco


def prefill_bucket(n: int, max_seq: int) -> int:
    """Smallest power-of-two ≥ n, clamped to [MIN_BUCKET, max_seq]."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return min(b, max_seq)


@dataclass
class GenerationResult:
    token_ids: list[int] = field(default_factory=list)
    finish_reason: str = "length"  # "stop" when EOS was hit

    @property
    def completion_tokens(self) -> int:
        return len(self.token_ids)


class _Request:
    """One queued/active generation; tokens flow to the consumer via ``out``.

    When ``want_lp`` ≥ 0, per-token logprob records ``(logprob, ids, lps)``
    (the sampled token's logprob plus the step's TOP_LOGPROBS alternatives)
    are appended to ``lp`` *before* the token is queued, so a consumer that
    sees token i can always read ``lp[i]``."""

    __slots__ = (
        "prompt_ids", "budget", "temperature", "top_p", "top_k", "seed",
        "eos_id", "cancel", "chunk_hint", "out", "emitted",
        "pp", "fp", "bias_row", "want_lp", "lp", "hist", "member",
        "trace", "t_submit", "tspans", "deadline", "expired", "grammar",
        "g_start", "rid",
        "priority", "tenant", "sched_class", "n_preempts", "replay",
        "preempt_flag", "t_admit", "parked", "parent", "path", "t_first",
        "t_delta",
    )

    def __init__(self, prompt_ids, budget, sampler: SamplerConfig, seed, eos_id,
                 cancel, chunk_hint, pp=0.0, fp=0.0, bias_row=None, want_lp=-1,
                 member=0, deadline=None, grammar=None, priority=None,
                 tenant=None):
        self.prompt_ids = prompt_ids
        self.budget = budget
        self.temperature = sampler.temperature
        self.top_p = sampler.top_p
        self.top_k = sampler.top_k
        self.seed = seed
        self.eos_id = eos_id
        self.cancel = cancel
        self.chunk_hint = chunk_hint
        self.out: queue.Queue = queue.Queue()
        self.emitted = 0
        self.pp = pp                  # presence_penalty
        self.fp = fp                  # frequency_penalty
        self.bias_row = bias_row      # np [V] f32 logit_bias, or None
        self.want_lp = want_lp        # -1 = no logprobs; else #top alternatives
        self.member = member          # stacked-members engine: weight set index
        # Absolute time.monotonic() deadline (None = no deadline). Enforced
        # by the scheduler's per-turn sweep: pending requests are shed
        # (stage "queue"), admitted ones cancelled (stage "prefill"/"decode").
        # ``expired`` marks a deadline retirement already delivered (err
        # frame sent by _expire): retirement paths that see only the
        # cancel event must not re-count it as a client cancellation.
        self.deadline = deadline
        self.expired = False
        # Constrained decoding: the compiled token-DFA this request decodes
        # under (None = unconstrained) and its GLOBAL start state in the
        # engine's device arena — assigned at admission by _ensure_grammar.
        self.grammar = grammar
        self.g_start = 0
        # QoS scheduler state (quorum_tpu/sched/, docs/scheduling.md): the
        # explicit priority knob + tenant id, the resolved dispatch class
        # (assigned in _submit), how many times this request has been
        # preempted (budget against livelock), the replay list of already-
        # delivered tokens a resumed victim must regenerate (None when not
        # resuming), the park-me flag set under _cond by the admission
        # side and honored by the decode loop's _sweep_preemptions, and
        # the last admission stamp (the cost model's service clock).
        self.priority = priority
        self.tenant = tenant
        self.sched_class = "batch"
        self.n_preempts = 0
        self.replay: "list[int] | None" = None
        self.preempt_flag = False
        self.t_admit: "float | None" = None
        # Drain park marker: set (before the end frame) when a draining
        # engine retired this stream mid-generation so the consumer can
        # finish it with finish_reason "parked" — the router's cue to
        # resume the stream on a sibling replica from its journal.
        self.parked = False
        self.lp: list = []
        # Request-scoped tracing: the server's trace (when this submission
        # happens inside a traced request context) rides along so the
        # scheduler thread can append queue-wait/prefill/decode spans to it,
        # under ``parent``: the hop span that was open at the submission.
        self.trace = obs.current_trace()
        # Flight-recorder correlation id: the traced request's W3C
        # trace-id (the fleet plane's cross-tier key — router events,
        # server spans, and these engine events all join on it), falling
        # back to the request id for traces without one, and for
        # engine-direct submissions a self-minted trace-id — one id
        # follows the request across the prefill and decode loops, which
        # is what makes the dual-loop (disagg) and staged-injection
        # (zero_drain) timelines correlatable.
        if self.trace is not None:
            self.rid = (getattr(self.trace, "trace_id", "")
                        or self.trace.request_id)
        else:
            from quorum_tpu.telemetry import tracecontext

            self.rid = tracecontext.new_trace_id()
            obs.TRACE_PROPAGATED.inc(source="engine")
        self.t_submit = time.perf_counter()
        # The first token's path: perf_counter stamps set once (the first
        # _emit on the scheduler thread; the backend's first non-empty
        # delta), mirrored into the trace's row for this submission.
        self.t_first: "float | None" = None
        self.t_delta: "float | None" = None
        self.path = (self.trace.open_member(member, self.t_submit)
                     if self.trace is not None else None)
        self.parent = self.path["span"] if self.path is not None else None
        self.tspans: dict = {}  # span kind -> (last span, turn count)
        # The running token history (prompt + delivered tokens): what the
        # slot's cache rows hold at release (_release_slot), and what a
        # preemption's replay expects (begin_replay).
        self.hist: list[int] = list(prompt_ids)

    def span(self, name: str, t0: float, t1: float, **meta):
        """Record an engine span of this request on its trace (None when
        untraced), under the hop span that submitted it."""
        if self.trace is None:
            return None
        return self.trace.add_span_abs(name, t0, t1, self.parent, **meta)

    def stamp(self, key: str, t: float) -> None:
        """Set an instant of the trace's first-token row, once."""
        if self.path is not None and self.path[key] is None:
            self.path[key] = self.trace.rel(t)

    def _stage_end(self, key: str, histogram, since: "float | None") -> float:
        """A first-token stage ends now: observe it from ``since`` and stamp
        the instant on the trace's row."""
        now = time.perf_counter()
        if since is not None:
            histogram.observe(now - since)
        self.stamp(key, now)
        return now

    def mark_first_token(self) -> None:
        """The engine's first emitted token (scheduler thread)."""
        self.t_first = self._stage_end(
            "engine_first_token_s", obs.FIRST_TOKEN_PREFILL, self.t_admit)

    def mark_first_delta(self) -> None:
        """The backend's first non-empty content delta for this request
        (whichever thread detokenizes it)."""
        self.t_delta = self._stage_end(
            "backend_first_delta_s", obs.FIRST_TOKEN_BACKEND, self.t_first)

    def begin_replay(self) -> int:
        """Park this request for a preemption resume: rewind every piece of
        host state to the as-submitted request and record the already-
        delivered tokens as the replay expectation. Re-admission then rides
        the ORDINARY admission machinery (prefix reuse, chunked segments,
        staged zero-drain injection — no preemption-specific device
        program), and because the token sequence is a pure function of
        (prompt, seed, sampler) — one RNG split per emitted token — the
        resumed row regenerates the delivered tokens bit for bit;
        ``_emit``'s replay guard swallows them (byte-comparing each against
        the expectation) and the stream continues where it left off.
        Returns the parked token count."""
        generated = self.hist[len(self.prompt_ids):]
        # A second preemption mid-replay must expect the FULL delivered
        # sequence again: what was already re-swallowed plus the remainder.
        already = self.replay or []
        self.replay = generated + already
        self.hist = list(self.prompt_ids)
        self.emitted = 0
        self.n_preempts += 1
        self.t_admit = None
        return len(generated)


class _InflightChunk:
    """One dispatched-but-unread decode chunk in the scheduler's ring.

    ``payload`` holds the chunk program's output arrays (jax futures until
    fetched); ``active`` the (row, request) pairs the chunk was dispatched
    over — the reap maps rows back through it, skipping rows whose slot was
    released (or re-admitted) in the meantime. ``depth`` is the ring depth
    at dispatch (0 = the blocking chunk), recorded on the decode span."""

    __slots__ = ("payload", "active", "n_steps", "t0", "history", "depth",
                 "constrained", "n_chunks", "family", "seq", "prog", "moe")

    def __init__(self, payload, active, n_steps, t0, history, depth,
                 constrained=False, n_chunks=1, family="", seq=0, prog=None,
                 moe=None):
        self.payload = payload
        # A patterned spec's expert counters as they stood after this
        # dispatch (_moe_snapshot): a device future the reap fetches.
        self.moe = moe
        self.active = active
        self.n_steps = n_steps
        self.t0 = t0
        self.history = history
        self.depth = depth
        # Dispatched through the grammar-constrained program variant: the
        # payload carries a trailing per-step masked-entry count and the
        # reap attributes a constrained= attr to the decode span.
        self.constrained = constrained
        # Megachunk dispatch: decode chunks this ONE dispatch covers on
        # device (decode_loop). 1 = a plain decode_chunk payload; >1 = the
        # fused variant whose token/valid/aux arrays carry a leading
        # per-chunk axis the reap drains segment by segment.
        self.n_chunks = n_chunks
        # Device-time attribution: the program-key family this dispatch
        # compiled under (compile_budget.json), its flight-recorder
        # sequence number, and its entry in the device ledger
        # (telemetry/device_ledger.py), which the first observation of the
        # payload landed — the ready() probe's success, else the blocking
        # fetch's completion — books landing to landing.
        self.family = family
        self.seq = seq
        self.prog = prog

    @property
    def tokens_ahead(self) -> int:
        """Upper bound on tokens this dispatch can still produce per row."""
        return self.n_steps * self.n_chunks

    def ready(self) -> bool:
        """True when every payload array has landed (non-blocking probe) —
        the incremental-drain check: a completed dispatch behind the
        blocking oldest can be reaped without pacing the device."""
        try:
            landed = all(x.is_ready() for x in jax.tree.leaves(self.payload)
                         if isinstance(x, jax.Array))
        except Exception:
            return False
        if landed:
            self.prog.land(time.perf_counter(), exact=False)
        return landed


class _Admission:
    """An in-progress chunked prefill: one slot, advanced by as many
    segments per scheduler iteration as one decode chunk's device time holds
    (at least one: :class:`_SegmentRoom`), so active decodes keep running
    in between.

    ``offset`` starts at the reused-prefix length when prefix caching found
    a match (the slot's cache rows [0, offset) already hold this prompt's
    K/V from a previous request) — only the suffix is prefilled.
    ``restored`` is the portion of that reuse that came from the HOST
    prefix store (0 = pure slot-resident reuse); kept separate so the
    admission span can attribute cache effectiveness per tier."""

    __slots__ = ("req", "slot", "offset", "offset0", "restored", "t_start",
                 "handed", "final_sent", "dead", "segments", "turn0", "acct")

    def __init__(self, req: _Request, slot: int, offset: int = 0,
                 restored: int = 0):
        self.req = req
        self.slot = slot
        self.offset = offset
        self.offset0 = offset            # reused-prefix length (tracing)
        self.restored = restored         # of which: host-store restore
        self.t_start = time.perf_counter()
        # Disaggregated serving only: staging-cache rows [0, handed) have
        # been handed off to the claimed decode-group slot; ``final_sent``
        # marks the whole prompt staged+queued (awaiting decode-group
        # register); ``dead`` tells the decode loop to drop this
        # admission's queued handoff pieces (cancelled/expired/failed —
        # its slot claim may have been re-issued).
        self.handed = 0
        self.final_sent = False
        self.dead = False
        # What the admission's ``prefill`` span waited for: segment programs
        # dispatched so far, the loop's turn count at the first of them
        # (_segment_dispatch), and the span's account in the device ledger
        # (_open_admission).
        self.segments = 0
        self.turn0 = 0
        self.acct = None


class _SegmentRoom:
    """What one scheduler turn's prefill segments may take of the device
    ahead of the turn's decode chunk: ``left_s`` seconds, at ``tok_s``
    seconds a token as padded. Both are paces the engine times on itself
    (``InferenceEngine._segment_room``); while either is still 0 the room
    holds nothing beyond the floor."""

    __slots__ = ("left_s", "tok_s")

    def __init__(self, left_s: float, tok_s: float):
        self.left_s = left_s
        self.tok_s = tok_s

    def take(self, tokens: int, floor: bool) -> bool:
        """Book a segment program computing ``tokens`` (rows x bucket) if it
        may go out this turn. The ``floor`` (each open admission's one
        segment a turn) always may, whatever it costs."""
        cost = tokens * self.tok_s
        if not floor and not 0.0 < cost <= self.left_s:
            return False
        self.left_s -= cost
        return True


# Lock-discipline contract for the engine's cross-thread state, verified by
# static analysis (`make qlint`, quorum_tpu/analysis/qlint.py — the
# "guarded" rule family; docs/static_analysis.md). This map is the SOURCE OF
# TRUTH the "Scheduler state, guarded by _cond's lock" comment block in
# __init__ points at. Three entry shapes:
#
#   {"lock": "_cond"}            every mutation must sit lexically inside
#                                `with self._cond:`;
#   {"lock": ..., "holders": []} methods documented as "caller holds the
#                                lock" — their docstrings say so, their
#                                call sites are all inside the lock, and
#                                qlint trusts the list (keep it short);
#   {"owner": [...]}             single-owner state: only these methods
#                                (all running on ONE thread) may mutate,
#                                no lock needed.
#
# Mutations of fields named here anywhere else fail `make qlint` — exactly
# the unguarded-mutation / double-count races fixed four separate times in
# the PR 3/4/7 reviews. Suppress a deliberate exception with
# `# qlint: allow-unguarded(<reason>)`.
_GUARDED_BY = {
    # shared scheduler state: submit()/release paths vs the scheduler
    # loop(s) — and under disagg BOTH loops plus the snapshot worker
    "_pending": {"lock": "_cond"},
    "_slots": {"lock": "_cond", "holders": ["_release_slot"]},
    # QoS preemption flags: appended by whichever loop runs admissions
    # (colocated decode / disagg prefill), drained by the decode loop's
    # _sweep_preemptions — the only _slots mutator that acts on them.
    "_preempt_pending": {"lock": "_cond"},
    "_admitting": {"lock": "_cond"},
    "_claimed": {"lock": "_cond"},
    "_handoffs": {"lock": "_cond"},
    "_pending_snaps": {"lock": "_cond", "holders": ["_queue_snapshot"]},
    "_snap_backlog": {"lock": "_cond", "holders": ["_queue_snapshot"]},
    "_pending_dfa_resets": {"lock": "_cond", "holders": ["_release_slot"]},
    "_stop": {"lock": "_cond"},
    # drain lifecycle (ISSUE 19): flags flipped by drain()/undrain() on a
    # server thread, read by _submit's admission gate and the decode
    # loop's _sweep_drain_parks; the parked counter is bumped under the
    # same lock by both park sites.
    "draining": {"lock": "_cond"},
    "_draining_park": {"lock": "_cond"},
    "n_drain_parked": {"lock": "_cond"},
    # single-owner: the decode scheduler thread's dispatch ring (drained
    # by _fail_all on that same thread's exception path)
    "_inflight": {"owner": ["_fill_inflight", "_reap_oldest",
                            "_drain_inflight", "_fail_all"]},
    # single-owner: the admission-clamp stall window (scheduler thread's
    # ring-fill turn — quorum_tpu_admission_stall_seconds_total)
    "_clamp_t0": {"owner": ["_note_admission_clamp"]},
    "admission_stall_s": {"owner": ["_note_admission_clamp"]},
    # single-owner: flight-recorder state on the engine side (ISSUE 12) —
    # the dispatch sequence counter (decode scheduler thread's ring-fill
    # turn) and the program-key → compile-budget-family memo (first
    # classified at dispatch/attribution time on whichever loop owns that
    # program; the dict is only ever extended through _family_of, and a
    # racing double-classify writes the same value).
    "_dispatch_seq": {"owner": ["_next_seq"]},
    "_family_cache": {"owner": ["_family_of"]},
    # paged KV bookkeeping (kv_pages=1): the refcounted allocator, the
    # host page-table mirror + its dirty flag, and the per-slot-group
    # claim counts all mutate under the scheduler lock (submit shed /
    # prefill-loop reservation / decode-loop release all touch them);
    # the device UPLOAD of the mirror happens outside the lock on the
    # thread that owns the decode cache (_paged_sync_table).
    # The _paged_* helpers are documented "caller holds _cond" (claim /
    # reclaim / release run inside the callers' lock scopes);
    # _init_device_state rebuilds everything before any thread can race.
    "_page_alloc": {"lock": "_cond"},
    "_table_np": {"lock": "_cond", "holders": [
        "_init_device_state", "_paged_reclaim", "_paged_claim",
        "_paged_release_row"]},
    "_table_dirty": {"lock": "_cond", "holders": [
        "_init_device_state", "_paged_reclaim", "_paged_claim",
        "_paged_release_row"]},
    "_page_claims": {"lock": "_cond", "holders": [
        "_init_device_state", "_paged_claim", "_paged_release_row"]},
}


class InferenceEngine:
    """One loaded model on one mesh, serving many requests concurrently.

    All device work happens on the engine's scheduler thread; callers talk to
    it through thread-safe queues, so ``generate_stream`` can be called from
    any number of threads at once. Concurrent requests co-batch into one
    decode program (continuous batching) instead of serializing — including
    fan-out backends that share one checkpoint's engine.
    """

    def __init__(
        self,
        spec: ModelSpec,
        mesh: Mesh | None = None,
        *,
        seed: int = 0,
        decode_chunk: int = 8,
        decode_pipeline: int = DEFAULT_DECODE_PIPELINE,
        decode_loop: int = DEFAULT_DECODE_LOOP,
        params=None,
        n_slots: int = DEFAULT_SLOTS,
        prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
        max_pending: int = DEFAULT_MAX_PENDING,
        quant: str | None = None,
        prefix_cache: bool = True,
        prefix_store: str | None = None,
        prefix_store_bytes: int = DEFAULT_PREFIX_STORE_BYTES,
        prefix_store_chunk: int = 0,
        members: int = 1,
        kv_quant: str | None = None,
        sp_impl: str = "ring",
        prefill_mesh: Mesh | None = None,
        transfer_guard: str | None = None,
        zero_drain: bool = False,
        kv_pages: bool = False,
        kv_page_size: int = 0,
        kv_pool_pages: int = 0,
        qos: bool = False,
        member_seeds: str = "distinct",
        quorum_dedup: bool = False,
        prepare: bool = False,
    ):
        self.spec = spec.validate()
        self.mesh = mesh or single_device_mesh()
        # Disaggregated prefill/decode (tpu://…&disagg=P+D): ``mesh`` is the
        # DECODE group (cache, slot state, decode ring); ``prefill_mesh``
        # the disjoint prefill group (second weight copy, staging cache,
        # admission segment programs). None = colocated, byte-for-byte the
        # pre-disagg engine.
        self.prefill_mesh = prefill_mesh
        self.disagg = prefill_mesh is not None
        if self.disagg:
            overlap = (set(map(str, self.mesh.devices.flat))
                       & set(map(str, prefill_mesh.devices.flat)))
            if overlap:
                raise ValueError(
                    f"disagg device groups must be disjoint; {len(overlap)} "
                    "device(s) appear in both the prefill and decode mesh")
        if quant not in (None, "", "int8"):
            raise ValueError(f"unsupported quant mode {quant!r} (int8 or none)")
        self.quant = quant or None
        if kv_quant not in (None, "", "int8"):
            raise ValueError(
                f"unsupported kv_quant mode {kv_quant!r} (int8 or none)")
        # int8 KV cache: each side stored (int8 values, f32 per-token
        # scales) — half the cache HBM capacity AND half the bytes every
        # decode step streams from its history window; decode attention
        # contracts natively in int8 (transformer.py / ops.attention).
        # Orthogonal to weight quant= (compose freely).
        self.kv_quant = kv_quant or None
        # Stacked fan-out members: M independently-seeded weight sets serve
        # M *separate* streams from ONE set of compiled programs — params and
        # KV caches carry a leading member axis ([M, …], model calls vmapped
        # over it), and every decode chunk advances all members' active slots
        # in a single dispatch. This is what makes an N-model quorum on one
        # chip cost N× the *compute*, not N× the dispatch: three co-located
        # engines each pay their own host turnaround per chunk, while a
        # stacked engine pays one. The reference cannot
        # express this at all — its "members" are separate HTTP services
        # (/root/reference/src/quorum/oai_proxy.py:182-192).
        self.members = max(1, int(members))
        self.decode_chunk = max(1, decode_chunk)
        # Depth of the decode-dispatch ring (see DEFAULT_DECODE_PIPELINE):
        # up to this many chunks in flight; the host blocks on the oldest.
        self.decode_pipeline = max(1, int(decode_pipeline))
        # Megachunk decode (see DEFAULT_DECODE_LOOP): up to this many chunks
        # fused into ONE dispatch. _effective_loop clamps it per dispatch
        # (admission pressure, remaining budgets, in-flight deadlines).
        if not 1 <= int(decode_loop) <= MAX_DECODE_LOOP:
            raise ValueError(
                f"decode_loop={decode_loop} out of range [1, "
                f"{MAX_DECODE_LOOP}]")
        # Floored to a power of two: every per-dispatch clamp halves, so a
        # non-pow2 C would spawn a SECOND family of fused program shapes
        # (48, 24, 12, 6, 3 beside the budget cap's 2..32), each a full
        # XLA compile at 7B scale.
        self.decode_loop = 1 << (int(decode_loop).bit_length() - 1)
        # Runtime sync sentinel (docs/static_analysis.md): when set, the
        # decode loop (_run_chunk — dispatch, reap) runs under
        # jax.transfer_guard(mode), so an implicit host<->device transfer
        # on the token critical path RAISES instead of silently stalling
        # the dispatch ring. The designated explicit points (_host_fetch's
        # device_get, the dispatch mask's device_put) stay allowed.
        # tests/conftest.py defaults the env knob to "disallow" for the
        # whole suite — the runtime half of qlint's static sync-taboo rule.
        levels = ("allow", "log", "disallow",
                  "log_explicit", "disallow_explicit")
        if transfer_guard is not None:
            # Explicit knob: fail fast on a typo.
            if transfer_guard not in ("",) + levels:
                raise ValueError(
                    f"transfer_guard={transfer_guard!r} is not a jax "
                    f"transfer-guard level ({', '.join(levels)} or empty "
                    "to disable)")
            tg = transfer_guard
        else:
            # Env knob: an unparseable value is a LOGGED loud off, never a
            # construction crash (an env typo must not take serving down).
            tg = os.environ.get("QUORUM_TPU_TRANSFER_GUARD", "")
            if tg and tg not in levels:
                logger.error(
                    "QUORUM_TPU_TRANSFER_GUARD=%r is not a jax transfer-"
                    "guard level (%s); running with the guard OFF",
                    tg, ", ".join(levels))
                tg = ""
        self.transfer_guard = tg or None
        self.n_slots = max(1, n_slots)
        # Admission gate for the direct device forwards (embeddings,
        # teacher-forced scoring): chat decode is slot-queue-gated, but
        # those paths dispatch straight to the device — and a timed-out
        # client wait leaves the device thread running, so unbounded
        # submissions would pile uncancellable device work against live
        # decode (ADVICE r4). Acquire with blocking=False and 503 on
        # saturation (backends/tpu_backend.py).
        self.score_gate = threading.Semaphore(SCORE_GATE_SLOTS)
        # Queue capacity scales with members: a stacked engine absorbs the
        # whole fan-out's admissions in ONE queue, so M members must carry
        # the aggregate capacity M separate engines would have had.
        self.max_pending = max(1, max_pending) * max(1, int(members))
        # Chunked prefill needs segment offsets that never cross max_seq
        # (dynamic_update_slice clamps out-of-range starts, which would
        # silently corrupt cache history): round the chunk down to a
        # power of two that divides max_seq; 0 disables chunking.
        c = 1
        while c * 2 <= min(prefill_chunk, spec.max_seq):
            c *= 2
        while c >= MIN_BUCKET and spec.max_seq % c:
            c //= 2
        self.prefill_chunk = c if c >= MIN_BUCKET and spec.max_seq % c == 0 else 0
        # Sequence-parallel serving (tpu://…&sp=N): admission prefill runs
        # ring attention with the prompt sharded over the sp axis. Chunked
        # admission is disabled there — the ring IS the long-prompt answer
        # (O(T/sp) attention memory per device, one compiled program).
        from quorum_tpu.parallel.mesh import AXIS_PP, AXIS_SP, AXIS_TP

        self._use_sp = dict(self.mesh.shape).get(AXIS_SP, 1) > 1
        # Tensor-parallel engines hand their mesh to single-shot prefill so
        # the Pallas kernel runs per tp shard (ops/flash_attention.py).
        self._tp_mesh = (
            self.mesh if dict(self.mesh.shape).get(AXIS_TP, 1) > 1 else None)
        # The decode step's Pallas read has no such wrapper: in a program
        # GSPMD partitions over devices XLA refuses the unpartitionable
        # Mosaic call, so decode_step is told and reads the cache through
        # XLA's einsums (ops/flash_decode.py says so per traced program).
        self._sharded = self.mesh.size > 1
        # Prefill-group sequence parallelism (disagg=P+D&sp=S): the STAGING
        # cache shards its position axis over the prefill mesh's sp axis —
        # a 100k-token admission's staged KV occupies O(max_seq/sp) HBM per
        # prefill device, GSPMD partitioning the segment programs over the
        # sequence blocks, while the decode group keeps its latency-shaped
        # layout (the handoff reshards on the fly, route="reshard").
        self.prefill_sp = (dict(self.prefill_mesh.shape).get(AXIS_SP, 1)
                           if self.disagg else 1)
        if self.disagg:
            if self._use_sp:
                raise ValueError(
                    "sp>1 in the decode group does not compose with "
                    "disagg: sequence-parallel serving disables chunked "
                    "prefill, which every disaggregated admission rides — "
                    "under disagg, sp= shards the PREFILL group instead")
            if self.prefill_sp > 1 and self.spec.max_seq % self.prefill_sp:
                raise ValueError(
                    f"prefill-group sp={self.prefill_sp} does not divide "
                    f"max_seq={self.spec.max_seq}: the staging cache "
                    "shards its position axis over sp — pick a dividing "
                    "sp or pad max_seq")
            if self.prefill_chunk <= 0:
                raise ValueError(
                    "disagg requires chunked prefill (prefill_chunk >= 16 "
                    "after power-of-two alignment): admissions prefill "
                    "into the prefill group's staging cache segment by "
                    "segment and register on the decode group — the "
                    "single-shot admit program samples its first token "
                    "inside prefill, on the wrong device group")
        # A served model shards its weights over tp (and its prompts over
        # sp); pp is the training axis (parallel/pipeline.py) and no decode
        # program runs over it.
        mesh_pp = dict(self.mesh.shape).get(AXIS_PP, 1)
        if mesh_pp > 1:
            raise ValueError(
                f"the decode mesh has pp={mesh_pp}: "
                "pipeline-staged decode was removed in PR 32 — shard a "
                "served model with tp= (pp remains the training axis)")
        if sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_impl {sp_impl!r} (ring or ulysses)")
        self.sp_impl = sp_impl
        if self._use_sp:
            self.prefill_chunk = 0
            if sp_impl == "ulysses":
                from quorum_tpu.parallel.ulysses import ulysses_supported

                if not ulysses_supported(self.spec.n_heads,
                                         self.spec.n_kv_heads, self.mesh):
                    raise ValueError(
                        f"sp_impl=ulysses needs the per-device head counts "
                        f"to split over sp "
                        f"(heads={self.spec.n_heads}, "
                        f"kv_heads={self.spec.n_kv_heads}, mesh "
                        f"{dict(self.mesh.shape)}) — a silent dense "
                        "fallback would replicate full attention at "
                        "exactly the lengths sp exists for")
            if self.spec.sliding_window > 0 and sp_impl == "ring":
                raise ValueError(
                    "sliding_window specs (mistral) do not compose with "
                    "ring-attention sp>1 (full causal attention would "
                    "silently widen the receptive field); use "
                    "sp_impl=ulysses, whose full-sequence local attention "
                    "applies windows unchanged")
        # Zero-drain continuous batching (tpu://…&zero_drain=1): the
        # disagg admission split applied WITHIN one device group. Every
        # admission prefills into a staging cache (same mesh, same
        # slot-batched layout) whose dispatch chain is independent of the
        # decode state, then the staged KV is injected into the claimed
        # slot (the disagg hslice/hput programs, no cross-group transfer)
        # and the row registers at the next reap boundary — so
        # _admission_pressure is structurally False and the
        # decode_pipeline=K × decode_loop=C ring keeps its full depth
        # through any admission burst. The tradeoff mirrors disagg's:
        # admission TTFT now shares device time with resident megachunks
        # instead of clamping them to K=1/C=1 (docs/tpu_backends.md).
        self.zero_drain = bool(zero_drain)
        if self.zero_drain:
            if self.disagg:
                raise ValueError(
                    "zero_drain=1 does not compose with disagg=P+D: "
                    "disaggregated admissions already run on their own "
                    "device group with the ring at full depth — zero-drain "
                    "is structural there (drop one knob)")
            if self.prefill_chunk <= 0:
                raise ValueError(
                    "zero_drain requires chunked prefill (prefill_chunk >= "
                    "16 after power-of-two alignment): admissions prefill "
                    "into the staging cache segment by segment and inject "
                    "at a reap boundary — the single-shot admit program "
                    "blocks the host on its first-token fetch, which "
                    "behind a full dispatch ring is exactly the stall "
                    "zero_drain exists to remove")
        # Staged admissions (disagg OR zero_drain): every admission rides
        # the chunked path into the staging cache and reaches its decode
        # slot through the handoff/injection queue + register.
        self.staged = self.disagg or self.zero_drain
        if self.members > 1:
            if self._use_sp:
                raise ValueError(
                    "members does not compose with sp>1 "
                    "(ring attention inside the member vmap)")
            if params is not None:
                raise ValueError(_CKPT_MEMBERS_ERROR)
        # Quorum knobs (docs/quorum.md). member_seeds picks the stacked
        # weight init: "distinct" (default) gives member i seed+i — M
        # different models; "shared" gives every member the SAME weights
        # (seed for all), so the stack is one model fanned into M sampling
        # streams — the quorum-of-samples topology, and the precondition
        # for shared-prefix dedup (identical weights ⇒ identical K/V).
        if member_seeds not in ("distinct", "shared"):
            raise ValueError(
                f"unknown member_seeds {member_seeds!r} (distinct or shared)")
        self.member_seeds = member_seeds
        self.quorum_dedup = bool(quorum_dedup)
        if self.quorum_dedup:
            if self.members <= 1:
                raise ValueError(
                    "quorum_dedup=1 requires members>1: there is no second "
                    "member to share the prefill with")
            if self.member_seeds != "shared":
                raise ValueError(
                    "quorum_dedup=1 requires member_seeds=shared: with "
                    "distinct seeds member m's cache row must hold "
                    "K_m = f_{W_m}(prompt) — M different projections of one "
                    "prompt, which broadcasting member 0's K_0 cannot "
                    "produce; add member_seeds=shared (one weight set, M "
                    "sampling streams) or drop quorum_dedup")
            if self.staged:
                raise ValueError(
                    "quorum_dedup=1 does not compose with disagg/zero_drain: "
                    "staged engines admit every prompt through the chunked "
                    "segment path, and the dedup broadcast rides the "
                    "member-coalesced single-shot program — drop one knob")
            if self.kv_quant:
                raise ValueError(
                    "quorum_dedup=1 does not compose with kv_quant=int8: "
                    "the broadcast scatters raw K/V; the quantized cache's "
                    "(values, scales) pair would need a second quantizing "
                    "scatter the program does not carry — drop one knob")
        # Prefill tokens NOT recomputed by shared-prefix dedup, and the
        # dedup admissions that saved them (docs/quorum.md gate: tokens
        # per request down ~M× on shared prompts).
        self.quorum_dedup_tokens = 0
        self.quorum_dedup_prefills = 0
        # Paged KV slot memory (tpu://…&kv_pages=1, docs/tpu_backends.md):
        # the dense [L, n_slots, max_seq, K·hd] rectangle becomes a page
        # pool [L, P, K, page_size, hd] plus a per-row on-device page table
        # — rows allocate pages only as they grow, so slot count is no
        # longer pinned by the worst-case sequence, and tier-0 prefix reuse
        # becomes page ALIASING (refcounted, copy-on-write boundary page)
        # instead of byte copies. The page table is host-owned
        # (self._table_np, scheduler thread) and uploaded whole at
        # admission/release boundaries — never inside the decode hot loop.
        self.kv_pages = bool(kv_pages)
        self.kv_page_size = 0
        self.kv_pool_pages = 0
        self._page_alloc: PageAllocator | None = None
        if self.kv_pages:
            if self._use_sp:
                raise ValueError(
                    "kv_pages=1 does not compose with sp>1: ring attention "
                    "shards the position axis, which the page-table "
                    "indirection scatters — drop one knob")
            ps = int(kv_page_size)
            if not ps:
                ps = self.prefill_chunk or min(64, self.spec.max_seq)
            validate_page_config(self.spec.max_seq, ps)
            self.kv_page_size = ps
            mp = self.spec.max_seq // ps
            n_data = int(kv_pool_pages) or self.n_slots * mp
            if n_data < 1:
                raise ValueError(
                    f"kv_pool_pages={kv_pool_pages} must be >= 1")
            self.kv_pool_pages = n_data
            # Host-side page accounting (scheduler thread): refcounted
            # allocator + retained-chain LRU, and the [n_slots, max_pages]
            # page-table mirror uploaded to device on change.
            self._page_alloc = PageAllocator(n_data, ps)
            self._table_np = np.zeros((self.n_slots, mp), np.int32)
            # Live-claim count per SLOT GROUP (s = flat_row % n_slots). On a
            # stacked engine the M member copies of slot s share ONE page
            # chain — page ids index each member's own pool copy, so the
            # same chain addresses M independent streams; the chain releases
            # when the last member's claim drops.
            self._page_claims = [0] * self.n_slots
            self._table_dirty = False
            self.kv_page_alias_hits = 0
            self.kv_page_cow_copies = 0
        # Automatic prefix caching (zero-copy): each slot remembers the token
        # sequence whose K/V its cache rows still hold; a new request admits
        # into the free slot with the longest common prefix and prefills only
        # the suffix (the admission rides the chunked-prefill machinery with
        # a nonzero start offset — so it needs prefill_chunk > 0). Multi-turn
        # conversations re-send their whole history; the repeated prefix
        # costs nothing on device. Disabled under disagg: the resident KV
        # lives on the DECODE group, where the prefill group's segment
        # programs cannot attend over it — reuse would need a decode→
        # prefill back-transfer per admission; the prefix-store restore
        # (host→prefill staging) is the cross-admission tier instead, and
        # outputs stay token-for-token identical either way (reuse only
        # skips recompute of identical KV).
        # (Also disabled under zero_drain, for the same structural reason:
        # the resident KV lives in the decode cache, where the staging
        # segments cannot attend over it. Outputs are identical either way
        # — reuse only skips recompute — and the prefix STORE remains the
        # cross-admission tier, restored into staging.)
        self.prefix_cache = (bool(prefix_cache) and self.prefill_chunk > 0
                             and not self.staged)
        # Tiered KV prefix store (quorum_tpu/cache/prefix_store.py,
        # docs/prefix_cache.md): a host-RAM cache tier behind the
        # slot-resident prefix cache. On slot release the valid KV prefix is
        # snapshotted device→host in chunk-aligned pieces (async, off the
        # scheduler's hot turn); on admission, a store match longer than the
        # slot-resident LCP is restored host→device and the admission rides
        # the chunked-prefill machinery with a nonzero offset.
        if self.spec.layer_pattern:
            # A spec with a layer pattern keeps a cache per layer kind
            # (models/patterned.py): what reads or writes the cache as one
            # [L, slots, max_seq, K·hd] rectangle, or runs the layers as one
            # stack, does not compose with it yet (ROADMAP.md).
            mesh_shape = dict(self.mesh.shape)
            refused = [
                ("kv_pages=1", self.kv_pages),
                ("prefix_store", bool((prefix_store or "").strip())),
                ("disagg=P+D / zero_drain=1 (kv_transfer)", self.staged),
                ("kv_quant=int8", bool(self.kv_quant)),
                ("quant=int8", bool(self.quant)),
                ("sp>1 (ring or ulysses admission)", self._use_sp),
                ("tp>1", mesh_shape.get(AXIS_TP, 1) > 1),
                ("members>1 (member stacking)", self.members > 1),
            ]
            for what, asked in refused:
                if asked:
                    raise ValueError(
                        f"{what} does not compose with a layer_pattern spec "
                        f"({self.spec.family}): its cache is kept per layer "
                        "kind (a ring per window layer), which this option "
                        "does not read or write yet")
            # Slot-resident prefix reuse reads a row's first positions back:
            # a window layer's ring holds the row's last ones.
            self.prefix_cache = False
        if self.spec.row_state:
            # A row that holds a state: a mixer's recurrence and convolution
            # tail a layer beside K and V (models/ssm.py), a short
            # convolution's tail in a layer with no K and V at all
            # (models/shortconv.py). The state is the whole of what the row
            # has read: it has no prefix to take up again, cannot be taken
            # back a position, and is not copied or split by what copies or
            # splits the K/V rectangle (ROADMAP.md).
            mesh_shape = dict(self.mesh.shape)
            refused = [
                ("kv_pages=1", self.kv_pages,
                 "a page pool has no place for"),
                ("prefix_store", bool((prefix_store or "").strip()),
                 "a stored prefix of positions comes without"),
                ("disagg=P+D / zero_drain=1 (kv_transfer)", self.staged,
                 "the hand-off of a row's positions does not carry"),
                ("kv_quant=int8", bool(self.kv_quant),
                 "the quantized cache is the two rectangles only, beside"),
                ("quant=int8", bool(self.quant),
                 "the weight quantizer does not know the projections of"),
                ("sp>1 (ring or ulysses admission)", self._use_sp,
                 "a sequence split over devices does not hand on"),
                ("tp>1", mesh_shape.get(AXIS_TP, 1) > 1,
                 "no sharding rule splits the heads of"),
                ("members>1 (member stacking)", self.members > 1,
                 "the member-stacked programs do not carry"),
            ]
            for what, asked, why in refused:
                if asked:
                    raise ValueError(
                        f"{what} does not compose with a spec whose rows "
                        f"hold a state ({self.spec.family}): {why} what a "
                        "row keeps beside its K and V (a mixer's recurrent "
                        "state, a short convolution's tail)")
            # Slot-resident prefix reuse starts a row at a position past 0:
            # the state there is the last tenant's, at its own last position.
            self.prefix_cache = False
        mode = (prefix_store or "").strip().lower() or None
        if mode not in (None, "host"):
            raise ValueError(
                f"unsupported prefix_store mode {prefix_store!r} "
                "(host or none)")
        if mode:
            if self.members > 1:
                raise ValueError(
                    "prefix_store does not compose with members>1: the "
                    "stacked cache carries a member axis the single-slot "
                    "snapshot/restore programs do not address — run "
                    "separate engines or drop prefix_store")
            if self._use_sp:
                raise ValueError(
                    "prefix_store does not compose with sp>1: sequence-"
                    "parallel serving disables chunked prefill, which the "
                    "restore path's nonzero-offset tail prefill rides")
            if self.prefill_chunk <= 0:
                raise ValueError(
                    "prefix_store requires chunked prefill (prefill_chunk "
                    ">= 16 after power-of-two alignment): restoring a "
                    "prefix prefills only the tail, through the segment "
                    "machinery")
            chunk = int(prefix_store_chunk) or self.prefill_chunk
            if chunk > self.spec.max_seq:
                raise ValueError(
                    f"prefix_store_chunk={chunk} exceeds max_seq="
                    f"{self.spec.max_seq}: no prefix could ever be stored")
            self.prefix_store: PrefixStore | None = PrefixStore(
                chunk, int(prefix_store_bytes))
            # Device→host fetches run on this worker so the scheduler's hot
            # turn only *dispatches* the snapshot slices (jax futures).
            self._snap_queue: queue.Queue = queue.Queue()
            self._snap_thread = threading.Thread(
                target=self._snapshot_worker,
                name=f"prefix-store-{id(self):x}", daemon=True)
            self._snap_thread.start()
        else:
            self.prefix_store = None
        # Slot releases whose snapshot dispatch is deferred to the next
        # scheduler turn (the release sites hold _cond; a first-use XLA
        # compile of the snapshot program must not run under the lock).
        # _snap_backlog counts queued-but-not-yet-handed-to-the-worker
        # snapshots — it bridges the window between popping the list and
        # enqueueing the fetch, so drain_prefix_store can't slip through.
        self._pending_snaps: list[tuple[int, list[int]]] = []
        self._snap_backlog = 0
        self.prefix_store_hits = 0
        self.prefix_store_tokens_restored = 0
        self.prefix_store_snapshots_dropped = 0
        self.prefix_store_restore_s = 0.0
        # Host-side slot space is FLAT across members: row m·n_slots + s is
        # member m's slot s. With members == 1 this is exactly the slot axis.
        self._rows = self.members * self.n_slots
        self._resident: list[list[int]] = [[] for _ in range(self._rows)]
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        # Cached jit wrappers for the rebuild-path utility programs (the
        # zero-fills): a fresh jax.jit per failure-containment rebuild
        # would recompile them (qlint: recompile/jit-immediate-call); and
        # the two few-byte programs of the ledger and the expert counters.
        self._util_fns: dict = {}
        # The programs (:func:`_program`): a jitted function a first
        # dispatch built, a compiled program it kept or the store held, or
        # the Future of one being loaded.
        self._admit_cache: dict = {}   # bucket, or (kind, …) → admit side
        self._decode_cache: dict = {}  # _decode_key & co. → decode side
        self._tag = f"engine-{id(self):x}"
        # The program store (prepare_programs): none for an engine built
        # directly; the serving entry asks for it where the persistent
        # compile cache is on (``prepare``), and its loads start here, so
        # that they run while the init program compiles and fills the
        # device. First dispatches that had to build their own program are
        # counted beside what was loaded.
        self._prep: "Preparation | None" = None
        self.n_programs_on_demand = 0
        if prepare:
            self.prepare_programs()
        self.weights = self._build_params(self.mesh, params, seed)
        # Disaggregated serving: the prefill group needs its own weight copy
        # (its programs cannot read across the group boundary — GSPMD never
        # spans both meshes) and a staging KV cache the admission segments
        # write into before the handoff. Same seeds, same init programs →
        # identical weights on both groups.
        # Zero-drain engines stage on the SAME device group: the segment
        # programs run the one resident weight copy (prefill_params is an
        # alias, not a second allocation).
        self.prefill_params = (
            self._build_params(self.prefill_mesh, params, seed)
            if self.disagg else (self.weights if self.zero_drain else None))
        self._cache_sh = self._cache_sharding(self.mesh)
        self._rep = NamedSharding(self.mesh, P())
        # Host-side wire-format contract (prefix-store snapshot/restore and
        # cross-replica chunk import): the chunk pytree STRUCTURE and the
        # per-leaf (shape-sans-position-axis, dtype) specs, derived from the
        # model spec rather than the live cache — under kv_pages the cache
        # pytree is pool+table, not the [L, K, n, …] wire layout the store
        # speaks (kv_transfer's paged arms gather/scatter to/from the same
        # wire format, so everything downstream stays layout-blind).
        _L, _K, _hd = (self.spec.n_layers, self.spec.n_kv_heads,
                       self.spec.head_dim)
        if self.kv_quant:
            self._wire_leaf = [((_L, _K, _hd), np.dtype(np.int8)),
                               ((_L, _K), np.dtype(np.float32))] * 2
            self._wire_def = jax.tree.structure(((0, 1), (2, 3)))
        else:
            self._wire_leaf = [((_L, _K, _hd), jnp.dtype(self.spec.dtype))] * 2
            self._wire_def = jax.tree.structure((0, 1))
        self._init_device_state()
        if self.staged:
            # Disagg: the staging cache lives on the prefill mesh — with
            # its position axis sharded over the prefill group's sp axis
            # when sp>1 (a 100k-token admission's staged KV occupies
            # O(max_seq/sp) HBM per prefill device; the handoff reshards
            # to the decode group's layout on the fly). Zero-drain: same
            # slot-batched layout on the decode mesh itself — reusing
            # _cache_sh keeps one compiled zero-fill program.
            # Staging caches stay DENSE rectangles even under kv_pages=1:
            # segment programs write sequential positions of one slot, where
            # the rectangle is already tight, and the handoff wire format is
            # layout-blind — paging pays off only in the long-lived decode
            # cache where rows of wildly different lengths coexist.
            self._stage_sh = (
                self._cache_sharding(self.prefill_mesh, seq_shard=True,
                                     paged=False)
                if self.disagg
                else (self._cache_sharding(self.mesh, paged=False)
                      if self.kv_pages else self._cache_sh))
            self._init_stage_state()
        # Handoff queue between the two scheduler loops (disagg): the
        # prefill loop appends transferred KV pieces (already resident on
        # the decode mesh) + per-admission "final" markers; the decode loop
        # drains them — writes into the claimed slot, then registers.
        self._handoffs: deque = deque()
        self.n_kv_handoffs = 0
        self.kv_handoff_bytes = 0
        self.kv_handoff_s = 0.0
        # Zero-drain acceptance accounting. n_admission_overlap counts
        # injected admissions that registered onto a NON-EMPTY dispatch
        # ring (structurally 0 before this PR: colocated admissions
        # clamped the ring to depth 1 and drained it first).
        # admission_stall_s accumulates wall time the ring spent clamped
        # to K=1/C=1 for an admission (structurally 0 under zero_drain and
        # disagg — pressure never clamps there); _clamp_t0 is the
        # in-progress clamp window's last observation stamp, owned by the
        # scheduler thread (_note_admission_clamp).
        self.n_admission_overlap = 0
        self.admission_stall_s = 0.0
        self._clamp_t0: "float | None" = None
        # Engine flight recorder + per-family device-time attribution
        # (quorum_tpu/telemetry/, ISSUE 12): this engine's tag on every
        # recorder event (= its thread names), the per-dispatch sequence
        # counter pairing dispatch/reap events, the program-key →
        # compile-budget-family memo, and the per-family latency model
        # (EWMAs + percentiles — the generalization of _chunk_ewma_s that
        # open item 1's preemption cost model consumes).
        self._dispatch_seq = 0
        self._family_cache: dict = {}
        self.latency = LatencyModel(alpha=CHUNK_EWMA_ALPHA)
        # The scheduler turn's phases (_phase): self seconds per phase, each
        # loop thread's open phases with its last switch (_phase_switch).
        self._turn_lock = threading.Lock()
        self._turn_s = dict.fromkeys(TURN_PHASES, 0.0)
        self._phase_open: dict[int, dict] = {}
        # The one account of device time (telemetry/device_ledger.py):
        # every program a loop dispatches is appended (_sent), every
        # landing it sees books the interval since the one before to the
        # programs in between, and a dry device's time goes to the phase
        # the loop had open (_phase_switch). It feeds the device_* and
        # prefill_{own,peer,decode_wait} families of metrics(), the
        # per-family latency model and histogram (_booked), the parts of a
        # ``prefill`` span, and the two paces that say how many segments a
        # turn may dispatch ahead of its chunk (_segment_room). A disagg
        # engine's prefill loop books its own device group.
        self._ledger = DeviceLedger(TURN_PHASES, self._booked)
        self._prefill_ledger = (DeviceLedger(TURN_PHASES, self._booked)
                                if self.disagg else None)
        self._prefill_thread = None  # set where the loops start
        self.n_turns = 0
        self.n_stalls = 0
        # Prefill programs dispatched (admit, member-admit, segment): prompt
        # tokens they were asked to compute against tokens as padded to the
        # program's rows x bucket; segment programs, and the turns that
        # dispatched any; and over chunked admissions, the span from slot
        # claim to register and its share behind decode chunks.
        self.n_prefill_tokens = 0
        self.n_prefill_padded = 0
        self.n_prefill_segments = 0
        self.n_prefill_segment_turns = 0
        self.prefill_span_s = 0.0
        self.prefill_decode_wait_s = 0.0
        self.prefill_own_s = 0.0
        self.prefill_peer_s = 0.0

        # Scheduler state, guarded by _cond's lock. The machine-checked
        # source of truth is the module-level _GUARDED_BY map (every field
        # listed there has its mutation sites verified by `make qlint` —
        # lexically inside `with self._cond:`, a documented caller-holds-
        # the-lock helper, or a single-owner thread's allowlisted methods);
        # extend THAT map when adding shared state, not just this comment.
        self._pending: list[_Request] = []
        self._slots: list[_Request | None] = [None] * self._rows
        self._admitting: list[_Admission] = []
        self._claimed: set[int] = set()  # slots held by in-progress admissions
        self._cond = threading.Condition()
        # QoS scheduler (tpu://…&qos=1 — quorum_tpu/sched/,
        # docs/scheduling.md): weighted-fair admission ordering + victim
        # selection, both pure host-side policy objects. The cost model is
        # ALWAYS live (it is the engine's one shed-decision point and its
        # EWMAs feed /debug/telemetry), but predictive sheds, non-FIFO
        # picks, and preemption all require qos — off, the engine's
        # observable scheduling behavior is byte-identical to pre-QoS.
        self.qos = bool(qos)
        self._policy = SchedPolicy()
        self._preempt = PreemptionController()
        self.cost_model = CostModel(self.latency)
        # (row, victim, beneficiary) park orders awaiting the decode
        # loop's next reap boundary (_sweep_preemptions).
        self._preempt_pending: "list[tuple[int, _Request, _Request]]" = []
        self.n_preemptions = 0
        self.n_preempted_tokens = 0
        self.n_replayed_tokens = 0
        # Drain lifecycle (docs/robustness.md "Zero-loss streams"): while
        # ``draining`` the submit gate sheds new admissions (QueueFullError
        # → 503 → the router fails the request over pre-first-byte) and
        # /ready reports degraded so the router rotates the replica out;
        # with ``park=True`` the decode loop's _sweep_drain_parks
        # additionally retires every resident/pending stream with
        # finish_reason "parked" — the router resumes each on a sibling
        # from its journal, so a drain under live traffic loses nothing.
        self.draining = False
        self._draining_park = False
        self.n_drain_parked = 0
        # Monotonic counters for /metrics (written on the scheduler/submit
        # paths; reads are snapshots, exactness across a race is not needed).
        self.n_requests = 0
        self.n_tokens = 0
        self.n_failures = 0
        self.n_cancelled = 0   # requests retired because cancel was set
        # Fault containment (docs/robustness.md): device-state rebuilds
        # after failed dispatches, deadline sheds/cancels by the per-turn
        # sweep, and the rebuild-storm circuit breaker gating admissions.
        self.n_rebuilds = 0
        self.n_deadline_exceeded = 0
        self.breaker = _Breaker()
        self.n_overlapped = 0  # decode chunks dispatched ahead of the read
        # Tokens the device produced that never reached a consumer. With
        # on-device finish accounting this stays 0 for EOS/budget finishes
        # at ANY pipeline depth; host-side finishes the device cannot see
        # (stop-sequence hits, cancellation) still waste the tokens of
        # already-dispatched chunks.
        self.n_overrun = 0
        # The in-flight decode-chunk ring (scheduler thread only): oldest
        # first; each entry is (payload arrays, active rows at dispatch,
        # n_steps, dispatch stamp, history bucket, depth at dispatch).
        self._inflight: deque = deque()
        # Decode-path dispatches reaped (dispatches/request's denominator).
        self.n_decode_chunks = 0
        # How far the dense decode step's read of the cache follows the
        # rows: tiles of ops/flash_decode.DECODE_TILE positions a decode
        # chunk's steps fetch of the history window (each live row to its own
        # length where the Pallas read runs, every row to the bucket where
        # XLA's einsums do), and what reading every row to the bucket counts.
        self.n_kv_tiles_read = 0
        self.n_kv_tiles_bucket = 0
        # A spec with a mixer (models/ssm.py): rows x steps x layers whose
        # recurrent state a decode chunk's program read and wrote back (every
        # row of the cache, each step), and those of them that belonged to a
        # row decoding in that step by the host's plan.
        self.n_ssm_rows_stepped = 0
        self.n_ssm_rows_live = 0
        self._moe_total = None  # a patterned spec's expert counters
        # The backends' producer threads, where the default pool is too
        # small for a backend's slots (tpu_backend._stream_pool).
        self.stream_pool = None
        # Megachunk accounting: device-side chunk segments that produced at
        # least one delivered/overrun token, summed over megachunk (and
        # plain — they count 1) dispatches. decode_chunks_total keeps
        # counting DISPATCHES, so dispatches-per-request drops ~C× under
        # decode_loop=C while this stays ~constant.
        self.n_loop_chunks = 0
        # Host-drain gap: time between a dispatch's payload landing on host
        # (fetch complete) and its last token handed to the consumer
        # queues, summed in seconds — the per-dispatch host tax the bench
        # divides out (scripts/hostpath_bench.py).
        self.drain_gap_s = 0.0
        # EWMA of per-chunk dispatch-to-reap latency (seconds) feeding the
        # deadline clamp in _effective_loop. 0 until the first reap.
        self._chunk_ewma_s = 0.0
        # Constrained decoding (docs/structured_output.md): the device-side
        # grammar arena — every admitted grammar's token-DFA rows
        # concatenated at stable offsets behind the reserved FREE row 0
        # (all-allowed self-loop, accepting: the state unconstrained rows
        # sit in). Host mirrors grow; the padded [bucket, V] device pair
        # re-uploads (async) when a new grammar lands. n_constrained /
        # n_constrain_masked feed the engine /metrics block.
        self._g_offsets: dict = {}
        self._g_grammars: dict = {}
        self._g_states = 1
        self._g_trans_np = np.zeros((1, self.spec.vocab_size), np.int32)
        self._g_accept_np = np.ones((1,), bool)
        self._g_trans = None   # device [bucket, V] int32 (None until used)
        self._g_accept = None  # device [bucket] bool
        self._g_bucket = 0
        # Rows whose constrained request was released: their device DFA
        # state must return to FREE before the row can serve an
        # unconstrained request again (processed at the top of
        # _start_admissions — release sites hold _cond, and a first-use
        # XLA compile must never run under the lock).
        self._pending_dfa_resets: list[int] = []
        self.n_constrained = 0
        self.n_constrain_masked = 0
        # Occupancy accounting: active rows summed over every decode-path
        # DISPATCH — average batch occupancy is
        # decode_busy_rows_total / decode_chunks_total.
        self.n_decode_rows = 0
        self._stop = False
        self._thread = threading.Thread(
            target=self._scheduler, name=f"engine-{id(self):x}", daemon=True
        )
        self._thread.start()
        if self.disagg:
            # The second cooperating loop: admissions prefill on their own
            # device group and hand off KV; the decode loop above never
            # runs a prefill program again.
            self._prefill_thread = threading.Thread(
                target=self._prefill_scheduler,
                name=f"engine-prefill-{id(self):x}", daemon=True)
            self._prefill_thread.start()
        else:
            self._prefill_thread = None
        _ALL_ENGINES.add(self)
        # Where this engine runs, said once: what /health repeats and what
        # chip_smoke.py reads — a CPU engine must never pass for a TPU one.
        rep = self.device_report = device_report(self.mesh)
        logger.info(
            "engine up: d_model=%d layers=%d members=%d slots=%d max_seq=%d "
            "row_state=%s "
            "on platform=%s device_kind=%r device_count=%d mesh=%s",
            self.spec.d_model, self.spec.n_layers, self.members,
            self.n_slots, self.spec.max_seq,
            "%dB" % self._state_row_bytes if self.spec.row_state else "none",
            rep["platform"],
            rep["device_kind"], rep["device_count"], rep["mesh"])

    @property
    def params(self):
        """The weights for a reader that takes arrays by index (the
        benchmark's reference check, tests): ``weights``, the tree the
        programs run, except that a stacked engine's layers-major block
        leaves index the member first like every other leaf of it
        (``leaf[member, layer]``, :class:`_MemberFirst`). Programs take
        ``weights``."""
        if self.members <= 1 or self.weights is None:
            return self.weights
        view = dict(self.weights)
        view[BLOCKS] = jax.tree.map(_MemberFirst, view[BLOCKS])
        return view

    def _build_params(self, mesh: Mesh, params, seed: int):
        """One device group's weight tree: shared by the decode mesh and
        (under disagg) the prefill mesh — both groups must hold identical
        weights, so both run the same deterministic init/shard programs."""
        spec = self.spec
        if self.members > 1:
            from quorum_tpu.models.init import init_params_ensemble_sharded

            # The stacked-init program: one seed per member, quant applied
            # per member inside the init; block leaves layers-major
            # [L, M, …], the rest [M, …] (sharding.member_axes).
            # member_seeds=shared repeats ONE seed: every member holds
            # identical weights (one model, M sampling streams) — the
            # quorum_dedup precondition (docs/quorum.md).
            seeds = ([seed] * self.members if self.member_seeds == "shared"
                     else [seed + i for i in range(self.members)])
            return init_params_ensemble_sharded(
                spec, mesh, seeds, quant=self.quant)
        if params is not None:
            out = shard_pytree(mesh, params, n_kv_heads=spec.n_kv_heads)
            if self.quant == "int8":
                # Requantize in place: inputs donated, each bf16 leaf's
                # buffer dies at its quantize op (models/quant.py).
                from quorum_tpu.models.quant import quantize_params_sharded

                out = quantize_params_sharded(
                    out, mesh, n_kv_heads=spec.n_kv_heads)
            return out
        if self.quant == "int8":
            # Init + quantize fused in one program: the bf16 weights are
            # per-leaf intermediates, so llama-3-8b (16.1 GB bf16 / 8.1 GB
            # int8) comes up on a single 16 GB chip. (On XLA:CPU the
            # helper splits into two programs — see its docstring.)
            from quorum_tpu.models.quant import init_params_quantized_sharded

            return init_params_quantized_sharded(spec, mesh, seed)
        # One compiled program materializes the weights sharded in place —
        # no eager per-leaf dispatch, no replicated copy (critical at 7B:
        # bf16 weights alone are ~14 GB of a v5e's 16 GB HBM).
        return init_params_sharded(spec, mesh, seed)

    def _cache_sharding(self, mesh: Mesh, seq_shard: bool = False,
                        paged: bool | None = None):
        """Slot-cache sharding for one device group — the decode mesh's
        slot cache and the prefill mesh's staging cache share one chunk
        WIRE format even when their physical layouts differ (per-group
        ``tp=``, an sp-sharded staging cache:
        the handoff reshards on the fly, kv_transfer route="reshard").
        ``seq_shard`` shards the position axis over the mesh's sp axis —
        the disagg prefill group's staging cache under ``sp>1``.
        ``paged`` selects the page-pool layout (defaults to the engine's
        ``kv_pages``); staging caches pass ``paged=False`` — they stay
        dense rectangles, the wire format is layout-blind either way."""
        if paged is None:
            paged = self.kv_pages
        if paged:
            # Page pool [L, P, K, ps, hd]: page axis never shards (a row's
            # chain scatters across it); table replicated — it's tiny
            # ([S, max_pages] int32) and every device gathers through it.
            pool_sh = paged_kv_sharding(mesh, self.spec.n_kv_heads)
            if self.kv_quant:
                # (values, scales): the scale array drops head_dim.
                pool_sh = (pool_sh,
                           NamedSharding(mesh, P(*tuple(pool_sh.spec)[:4])))
            table_sh = NamedSharding(mesh, P())
            sh = PagedKV(pool_sh, table_sh)
            if self.members > 1:
                sh = jax.tree.map(
                    lambda s: NamedSharding(
                        mesh, P(*((None,) + tuple(s.spec)))),
                    sh, is_leaf=lambda x: isinstance(x, NamedSharding))
            return sh
        if self.spec.layer_pattern or self.spec.ssm_heads:
            # a leaf per layer, by kind, and the counters (or the two
            # rectangles and the mixer's state and tail): all replicated
            # (tp, sp and members are refused above)
            leaf = NamedSharding(mesh, P())
            return jax.tree.map(lambda _: leaf, jax.eval_shape(
                lambda: init_cache(self.spec, batch=1)))
        sh = kv_cache_sharding(mesh, self.spec.n_kv_heads,
                               batch=self.n_slots, seq_shard=seq_shard)
        if self.kv_quant:
            # (values [.., K·hd], scales [.., K]): the same axes
            sh = (sh, sh)
        if self.members > 1:
            # member-stacked cache [M, L, S, T, K·hd]: member axis
            # vmapped, never sharded
            sh = jax.tree.map(
                lambda s: NamedSharding(mesh, P(*((None,) + tuple(s.spec)))),
                sh, is_leaf=lambda x: isinstance(x, NamedSharding))
        return sh

    def _init_device_state(self) -> None:
        """(Re)allocate the slot-batched cache and per-slot state on device.

        Called at construction and after any failed compiled call: the jitted
        programs donate the cache/state buffers, so an exception mid-dispatch
        can leave ``self._ck`` & co. pointing at deleted arrays — without a
        reset, one poisoned request would brick the (shared) engine forever.
        The cache is allocated by a compiled zero-fill — no host-side
        materialization or transfer of the multi-GB buffer.
        """
        self._ck, self._cv = self._zero_cache(self._cache_sh)
        # what one row holds of state (a mixer's state and tail, a short
        # convolution's tail), all layers: a constant of the cache's shape
        # (a ``prefill`` span's ``state_bytes``)
        self._state_row_bytes = (
            self._kv_cache_bytes()["state"] // self._rows
            if self.spec.row_state else 0)
        # the expert counters ride the cache, so they start over with it;
        # snapshots of the cache that was are not to be counted
        self._moe_last = None
        self._moe_noted = self._moe_seq = getattr(self, "_moe_seq", 0) + 1
        if self.kv_pages:
            # The zero-fill points every table entry at the sink page: all
            # host page accounting restarts from empty (rebuilds drop every
            # slot, so no chain survives to re-adopt).
            self._page_alloc.reset()
            self._table_np[:] = 0
            self._page_claims = [0] * self.n_slots
            self._table_dirty = False
        s = self._rows
        rep = self._rep
        self._token = jax.device_put(np.zeros((s,), np.int32), rep)
        self._lengths = jax.device_put(np.zeros((s,), np.int32), rep)
        self._keys = jax.device_put(np.zeros((s, 2), np.uint32), rep)
        # On-device finish accounting (the state that makes depth-K dispatch
        # safe): per-row liveness, remaining token budget, and EOS id (−1 =
        # none). Set at admission/registration, updated by every decode
        # chunk ON DEVICE — a chunk dispatched before the host has read its
        # predecessor still knows which rows already finished.
        self._live = jax.device_put(np.zeros((s,), bool), rep)
        self._budget = jax.device_put(np.zeros((s,), np.int32), rep)
        self._eos = jax.device_put(np.full((s,), -1, np.int32), rep)
        # Per-row grammar-DFA state (GLOBAL arena index; 0 = FREE, the
        # all-allowed state unconstrained rows stay in). Threaded through
        # the CONSTRAINED decode variant only — the plain variant's
        # signature carries no trace of it (the gating contract).
        self._dfa = jax.device_put(np.zeros((s,), np.int32), rep)
        self._temp = jax.device_put(np.ones((s,), np.float32), rep)
        self._topp = jax.device_put(np.ones((s,), np.float32), rep)
        self._topk = jax.device_put(np.zeros((s,), np.int32), rep)
        # OpenAI sampling knobs (docs/api.md): per-slot presence/frequency
        # penalties, generated-token counts (what the penalties act on), and
        # a per-slot logit-bias row. Allocated by compiled zero-fill — the
        # [S, V] buffers never cross the host boundary.
        self._pp = jax.device_put(np.zeros((s,), np.float32), rep)
        self._fp = jax.device_put(np.zeros((s,), np.float32), rep)
        v = self.spec.vocab_size
        zero_rows = self._util_fns.get("zero_rowstate")
        if zero_rows is None:
            zero_rows = self._util_fns["zero_rowstate"] = jax.jit(
                lambda: (jnp.zeros((s, v), jnp.int32),
                         jnp.zeros((s, v), jnp.float32)),
                out_shardings=(self._rep, self._rep),
            )
        self._counts, self._bias = zero_rows()
        self._zero_bias = np.zeros((v,), np.float32)
        if self.members > 1:
            # Shared zero logit-bias template for coalesced member
            # admissions — copied only when a request actually sets
            # logit_bias (the _zero_bias copy-on-write convention).
            self._zero_bias_mem = np.zeros((self.members, v), np.float32)

    def _zero_cache(self, shardings):
        """Compiled zero-fill of one slot-batched cache onto ``shardings``
        — no host-side materialization or transfer of the multi-GB buffer.
        Used for the decode cache and (under disagg) the staging cache.
        A PagedKV sharding tree selects the page-pool layout instead —
        staging caches always pass the dense shardings."""
        if isinstance(shardings, PagedKV):
            def zero_paged():
                return init_paged_cache(
                    self.spec, batch=self.n_slots,
                    n_pages=self.kv_pool_pages,
                    page_size=self.kv_page_size, kv_quant=self.kv_quant,
                    members=self.members if self.members > 1 else None)

            key = ("zero_cache", id(shardings))
            fn = self._util_fns.get(key)
            if fn is None:
                fn = self._util_fns[key] = jax.jit(
                    zero_paged, out_shardings=(shardings, shardings))
            return fn()

        def zero_cache():
            ck, cv = init_cache(self.spec, batch=self.n_slots,
                                kv_quant=self.kv_quant)
            if self.members > 1:
                stack = lambda x: jnp.zeros(  # noqa: E731
                    (self.members,) + x.shape, x.dtype)
                ck = jax.tree.map(stack, ck)
                cv = jax.tree.map(stack, cv)
            return ck, cv

        # Wrapper cached per sharding set (decode cache vs disagg staging
        # cache — both live on self, so id() is stable): rebuilds after
        # failure containment reuse the compiled zero-fill.
        key = ("zero_cache", id(shardings))
        fn = self._util_fns.get(key)
        if fn is None:
            # a patterned spec's sharding is the (K side, V side) pair
            # already (only its K side carries the expert counters), and so
            # is a spec's with a mixer (state on one side, tail on the other)
            fn = self._util_fns[key] = jax.jit(
                zero_cache, out_shardings=(
                    shardings
                    if self.spec.layer_pattern or self.spec.ssm_heads
                    else (shardings, shardings)))
        return fn()

    def _init_stage_state(self) -> None:
        """(Re)allocate the prefill group's staging KV cache (disagg only):
        the decode cache's exact slot-batched shape, placed on the prefill
        mesh. Admission segments write prompt KV here; the handoff slices
        it chunk-granular into the claimed decode-group slot (staging row i
        mirrors decode slot row i, so one flat-row convention addresses
        both). Rebuilt after a prefill-group failure consumed the donated
        staging buffers (:meth:`_contain_prefill_failure`) — decode-group
        state is never touched on that path."""
        self._sck, self._scv = self._zero_cache(self._stage_sh)

    # ---- paged KV bookkeeping (kv_pages=1) --------------------------------
    #
    # Host half of the paged layout: admission reserves a row's FULL page
    # span up front (prompt + budget + one position), so the
    # device table for a live row never changes mid-decode and pool
    # exhaustion sheds at admission instead of OOMing a running stream.
    # Allocator / mirror mutations run under _cond; the device upload and
    # the COW boundary-page copies run OUTSIDE the lock on the thread that
    # owns the decode cache (_paged_install / _paged_sync_table).

    def _paged_note_occupancy(self) -> None:
        """Refresh the pool-occupancy gauges after an allocator mutation
        (claim / release / reclaim). Last-writer-wins across engines
        sharing the process, like the other engine gauges."""
        a = self._page_alloc
        obs.KV_PAGES_ALLOCATED.set(a.allocated_pages)
        obs.KV_PAGES_FREE.set(a.free_pages)

    def _paged_need(self, n_prompt: int, budget: int) -> int:
        """Pages covering every position a request could ever write:
        prompt and generation budget, and one position of slack."""
        need_t = min(self.spec.max_seq, n_prompt + budget + 1)
        return self._page_alloc.pages_for(need_t)

    def _paged_fits(self, row: int, req: "_Request") -> bool:
        """Whether a claim of ``row`` for ``req`` can succeed after LRU
        reclaim — the admission head-of-line check (caller holds _cond).
        Conservative: ignores prefix sharing, which only lowers the fresh
        page count."""
        a = self._page_alloc
        sg = row % self.n_slots
        n_need = self._paged_need(len(req.prompt_ids), req.budget)
        if self._page_claims[sg]:
            chain = a.chain(sg) or []
            n_need -= len(chain)
            return (n_need <= a.free_pages
                    + a.reclaimable_pages(protect=(sg,)))
        # A fresh claim of this slot group may drop (or reuse) the group's
        # OWN retained donor, so its sole-reference pages count as
        # available too — protect nothing. Without this, a donor holding
        # most of the pool wedges its own slot's next admission forever.
        return n_need <= a.free_pages + a.reclaimable_pages()

    def _paged_reclaim(self, n: int, protect=()) -> bool:
        """Evict least-recently-retained chains until ``n`` pages are free
        (caller holds _cond). Evicted rows lose their advertised resident
        prefix — the KV bytes are gone, so a tier-0 hit on them would
        splice garbage."""
        a = self._page_alloc
        while a.free_pages < n:
            victim = a.evict_lru(protect=protect)
            if victim is None:
                return False
            if not self._page_claims[victim]:
                for m in range(self.members):
                    self._resident[m * self.n_slots + victim] = []
                self._table_np[victim, :] = 0
            self._table_dirty = True
        return True

    def _paged_claim(self, row: int, req: "_Request", reuse: int):
        """Reserve flat row ``row``'s full page span for ``req`` (caller
        holds _cond). Returns ``(reuse, cow_pairs)`` — the possibly-clamped
        tier-0 reuse length and the boundary-page copy-on-write (dst, src)
        pairs ``_paged_install`` must run before the admission's first
        segment — or None when the pool can't cover the span even after
        reclaim (the admission waits).

        Tier-0 reuse SHARES the slot's retained chain (refcount bump; the
        donor entry stays, so N requests forking one prefix each alias the
        same pages); a partially-filled boundary page is replaced by a COW
        copy so the new tenant's suffix writes never leak into the shared
        original. On stacked engines (members>1) reuse is forced to 0: the
        M member copies of a slot group share one chain, and per-member
        content lineage across re-claims isn't tracked — correctness over
        aliasing there."""
        a = self._page_alloc
        sg = row % self.n_slots
        ps = self.kv_page_size
        n_need = self._paged_need(len(req.prompt_ids), req.budget)
        cow: list[tuple[int, int]] = []
        if self.members > 1:
            reuse = 0
        if self._page_claims[sg]:
            # Co-tenant (stacked engines): the slot group's chain is live
            # in every member's pool copy — extend it if this member needs
            # more pages; appending never disturbs existing entries.
            chain = a.chain(sg) or []
            extra = n_need - len(chain)
            if extra > 0:
                if not self._paged_reclaim(extra, protect=(sg,)):
                    return None
                fresh = a.alloc(extra)
                if fresh is None:  # pragma: no cover - reclaim guarantees
                    return None
                base = len(chain)
                a.extend(sg, fresh)
                self._table_np[sg, base:base + extra] = fresh
                self._table_dirty = True
            self._page_claims[sg] += 1
            self._paged_note_occupancy()
            return 0, cow
        held = a.retained_chain(sg)
        if reuse and (held is None or len(held) * ps < reuse):
            reuse = 0
        p_keep = a.pages_for(reuse)
        partial = bool(reuse % ps)
        n_new = n_need - p_keep + (1 if partial else 0)
        # Share the reuse prefix BEFORE any donor drop or reclaim: the
        # bump keeps those pages out of the free list whatever happens to
        # the donor entry below.
        keep = a.share(held[:p_keep]) if p_keep else []
        if n_new > a.free_pages:
            # The slot group's own retained donor is a legitimate page
            # source for its own re-claim (the kept prefix survives via
            # the share above); without this drop, a donor holding most
            # of the pool wedges this slot's next admission forever —
            # _paged_fits counts these pages, so the claim must be able
            # to free them.
            a.drop_retained(sg)
        fresh: list[int] = []
        if n_new > 0:
            if not self._paged_reclaim(n_new, protect=(sg,)):
                if keep:
                    a.free(keep)
                return None
            got = a.alloc(n_new)
            if got is None:  # pragma: no cover - reclaim guarantees
                if keep:
                    a.free(keep)
                return None
            fresh = got
        a.touch(sg)
        if partial:
            # The boundary page is only partially reused: the tenant's
            # suffix writes land inside it, so it must be a private copy.
            repl = fresh.pop()
            cow.append((repl, keep[-1]))
            a.free([keep[-1]])
            keep[-1] = repl
        chain = keep + fresh
        a.assign(sg, chain)
        self._table_np[sg, :] = 0
        self._table_np[sg, :len(chain)] = chain
        self._table_dirty = True
        self._page_claims[sg] = 1
        if reuse:
            self.kv_page_alias_hits += 1
            obs.KV_PAGE_ALIAS_HITS.inc()
        self._paged_note_occupancy()
        return reuse, cow

    def _paged_release_row(self, row: int) -> None:
        """Drop one live claim on ``row``'s slot group (caller holds _cond);
        when the last claim goes, retain the chain prefix covering the
        resident tokens as a prefix-reuse donor (MRU end of the LRU) and
        zero the mirror's tail. No-op on dense engines."""
        if not self.kv_pages:
            return
        a = self._page_alloc
        sg = row % self.n_slots
        if not self._page_claims[sg]:
            return
        self._page_claims[sg] -= 1
        if self._page_claims[sg]:
            return
        keep = (0 if self.members > 1 else len(self._resident[sg]))
        chain = a.chain(sg) or []
        a.release(sg, keep_tokens=keep)
        kept = min(a.pages_for(keep), len(chain))
        if len(chain) > kept:
            self._table_np[sg, kept:len(chain)] = 0
            self._table_dirty = True
        self._paged_note_occupancy()

    @_program("_admit_cache", lambda self: ("page_copy",))
    def _page_copy_fn(self):
        """Jitted physical page copy (all layers/members at once) — the
        copy-on-write program behind prefix aliasing. One admit-cache
        entry, key ``("page_copy",)`` (compile-budget family page_copy)."""
        stacked = self.members > 1

        def cp(ck, cv, dst, src):
            return (paged_copy_page(ck, dst, src, stacked=stacked),
                    paged_copy_page(cv, dst, src, stacked=stacked))

        return jax.jit(cp, donate_argnames=("ck", "cv"))

    def _paged_sync_table(self) -> None:
        """Upload the host page-table mirror into both decode-cache sides
        when dirty. Runs OUTSIDE _cond on the thread that owns the decode
        cache (scheduler thread; under disagg the decode loop, from
        _drain_handoffs before the first paged injection) — never in the
        decode hot loop. A stale device table is always safe: live rows'
        entries are immutable mid-decode, and a released row's leftovers
        are masked dead."""
        if not self.kv_pages:
            return
        with self._cond:
            if not self._table_dirty:
                return
            tab = self._table_np.copy()
            self._table_dirty = False
        lead = (((self.members,) if self.members > 1 else ())
                + (self.spec.n_layers,))
        full = np.ascontiguousarray(np.broadcast_to(tab, lead + tab.shape))
        sh = self._cache_sh.table if isinstance(self._cache_sh, PagedKV) \
            else None
        # qlint: allow-sync(page-table upload: a few KiB host→device at admission/release boundaries, off the decode hot loop by design)
        t_k = jax.device_put(full, sh)
        # qlint: allow-sync(page-table upload: second side — K and V carry separate table buffers so donation stays sound)
        t_v = jax.device_put(full.copy(), sh)
        self._ck = PagedKV(self._ck.pool, t_k)
        self._cv = PagedKV(self._cv.pool, t_v)

    def _paged_install(self, cow) -> None:
        """Device half of a paged claim: run the COW boundary-page copies,
        then upload the table mirror — called outside _cond on the
        decode-cache owner thread, strictly before the admission's first
        cache write. Data flow orders everything: the admission program
        consumes both the copied pool and the new table arrays."""
        for dst, src in cow:
            self._ck, self._cv = self._page_copy_fn()(
                self._ck, self._cv, np.int32(dst), np.int32(src))
            self._sent(OTHER, "page_copy")
            self.kv_page_cow_copies += 1
            obs.KV_PAGE_COW_COPIES.inc()
        self._paged_sync_table()

    # ---- compiled programs ------------------------------------------------

    def _memo(self, builder, key, args=(), kw=None):
        """The program ``builder`` (:func:`_program`) keeps under ``key``:
        what the memo holds; or what the preparation is loading, waited for
        (this one program, no other); or, where neither, the builder's
        body's, built by this first dispatch as it always was and counted
        as on demand. Where a preparation is open, a kept builder's program
        is then compiled whole and stored for the next start."""
        memo = getattr(self, builder.memo)
        fn = memo.get(key)
        if isinstance(fn, Future):
            fn = fn.result()  # None: the file did not load
        if fn is None:
            self.n_programs_on_demand += 1
            fn = builder.make(self, *args, **(kw or {}))
            if self._prep is not None:
                logger.info("program %r built on demand", key)
                if builder.kept(key):
                    fn = self._prep.keep(memo, builder.__name__, key, fn)
            memo[key] = fn
        return fn

    def _program_ready(self, key) -> bool:
        """Whether a decode dispatch under ``key`` would neither build nor
        wait for its program."""
        fn = self._decode_cache.get(key)
        return fn is not None and not isinstance(fn, Future)

    def _program_config(self) -> tuple:
        """What a kept program's text follows from beside the package's
        sources, its builder and its key: the program store's directory
        (engine/prepare.py) is named after it."""
        return (self.spec, self.members, self.n_slots, self._rows,
                self.quant, self.kv_quant, self.prefill_chunk,
                self.decode_chunk, self.sp_impl)

    def prepare_programs(self) -> None:
        """Start loading, on a pool of threads, every program an earlier
        start of this configuration compiled and stored (engine/prepare.py;
        once, where the persistent compile cache is on and every array has
        one possible placement). Returns at once: ``programs_preparing`` on
        :meth:`health` counts what is still out, and a dispatch that comes
        early waits for its own program. What the store lacks is its first
        dispatch's, which stores it."""
        if self._prep is not None or self.mesh.size > 1 or self.staged \
                or self.kv_pages:
            # Across devices the compiler picks the shardings a program
            # returns, so what the next one is handed (and is compiled
            # for) is known only once the first has run; a staged or paged
            # engine's programs are opt-in variants, on demand as before.
            return
        self._prep = prep = Preparation.open(
            self._tag, self.mesh.devices.flat[0], self._program_config())
        if prep is None:
            return
        for name, key in prep.stored():
            builder = getattr(type(self), name, None)
            if builder is not None and getattr(builder, "kept", None) \
                    and builder.kept(key) \
                    and key not in getattr(self, builder.memo):
                prep.load(getattr(self, builder.memo), name, key)
        prep.seal()

    @property
    def programs_preparing(self) -> int:
        """Stored programs not loaded yet: /ready waits for 0, so that
        nothing loads behind a measured window."""
        return self._prep.pending if self._prep is not None else 0

    @_program("_admit_cache", lambda self, bucket: bucket, kept=True)
    def _admit_fn(self, bucket: int):
        """Jitted: prefill one prompt into a slot + sample its first token."""
        spec = self.spec

        mesh = self.mesh if self._use_sp else None
        tp_mesh = self._tp_mesh
        n_top = min(TOP_LOGPROBS, spec.vocab_size)

        def admit(params, tokens, lengths1, slot, seed, temp1, topp1, topk1,
                  pp1, fp1, bias_row, budget1, eos1,
                  ck, cv, token_s, lengths_s, keys_s, temp_s, topp_s, topk_s,
                  pp_s, fp_s, counts_s, bias_s, live_s, budget_s, eos_s):
            with tracing_program(f"admit/{bucket}"):
                logits, ck, cv = prefill(
                    params, spec, tokens, lengths1, ck, cv, slot=slot,
                    mesh=mesh, sp_impl=self.sp_impl, tp_mesh=tp_mesh,
                    sharded=self._sharded)
            # First sampled token: no generated text yet → penalties are
            # zero; only the logit bias applies.
            with jax.named_scope("sample"):
                adj = logits.astype(jnp.float32) + bias_row[None, :]
                key = jax.random.PRNGKey(seed)
                key, sub = jax.random.split(key)
                first = sample_token_rows(
                    adj, sub[None], temp1[None], topp1[None], topk1[None]
                )[0]
                lp_all = jax.nn.log_softmax(adj[0])
                top_lp, top_ix = lax.top_k(lp_all, n_top)
            counts_row = jnp.zeros((spec.vocab_size,), jnp.int32).at[first].add(1)
            return (
                first,
                lp_all[first],
                top_ix,
                top_lp,
                ck,
                cv,
                token_s.at[slot].set(first),
                lengths_s.at[slot].set(lengths1[0]),
                keys_s.at[slot].set(key),
                temp_s.at[slot].set(temp1),
                topp_s.at[slot].set(topp1),
                topk_s.at[slot].set(topk1),
                pp_s.at[slot].set(pp1),
                fp_s.at[slot].set(fp1),
                counts_s.at[slot].set(counts_row),
                bias_s.at[slot].set(bias_row),
                # Finish state: the admit already produced token 1, so the
                # remaining budget is budget−1; the row is live unless that
                # first token exhausted it or WAS the EOS.
                live_s.at[slot].set((budget1 > 1) & (first != eos1)),
                budget_s.at[slot].set(budget1 - 1),
                eos_s.at[slot].set(eos1),
            )

        return jax.jit(
            admit,
            donate_argnames=(
                "ck", "cv", "token_s", "lengths_s", "keys_s",
                "temp_s", "topp_s", "topk_s",
                "pp_s", "fp_s", "counts_s", "bias_s",
                "live_s", "budget_s", "eos_s",
            ),
        )

    @_program("_admit_cache", lambda self, bucket: ("members", bucket),
              kept=True)
    def _admit_fn_members(self, bucket: int):
        """Jitted coalesced admission for a stacked-members engine: up to one
        prompt PER member prefills into one shared slot row in a single
        member-vmapped program. The quorum fan-out pattern submits the same
        request to every member within microseconds, so admissions naturally
        arrive in member-complete groups and the M prefills share one
        dispatch. ``enables[m]`` gates member m's cache write (see
        transformer.prefill's ``write_gate``) and state update, so a
        partially-filled group (or a lone admission) runs the same compiled
        program without touching absent members' rows."""
        spec = self.spec
        n_top = min(TOP_LOGPROBS, spec.vocab_size)
        n_s = self.n_slots
        mem = self.members
        tp_mesh = self._tp_mesh

        def admit(params, tokens, lengths, slot, enables, seeds,
                  temps, topps, topks, pps, fps, bias_rows, budgets, eoss,
                  ck, cv, token_s, lengths_s, keys_s, temp_s, topp_s, topk_s,
                  pp_s, fp_s, counts_s, bias_s, live_s, budget_s, eos_s):
            # tokens [M, 1, bucket]; lengths [M, 1]; slot scalar int32;
            # enables [M] bool; sampler knobs [M]; bias_rows [M, V].
            def one(p, tok, lens, k, v, gate):
                return prefill(p, spec, tok, lens, k, v, slot=slot,
                               write_gate=gate, tp_mesh=tp_mesh)

            with tracing_program(f"admit_members/{bucket}"):
                logits, ck, cv = _member_vmap(
                    one, params, tokens, lengths, ck, cv, enables)
            adj = logits[:, 0].astype(jnp.float32) + bias_rows  # [M, V]
            # Same PRNG stream as the single-model admit: sample the first
            # token with split row 1, carry row 0 — a member's stream is
            # token-for-token the stream a members=1 engine with that
            # member's seed would produce.
            with jax.named_scope("sample"):
                keys = jax.vmap(jax.random.PRNGKey)(seeds)          # [M, 2]
                split = jax.vmap(jax.random.split)(keys)            # [M, 2, 2]
                firsts = sample_token_rows(adj, split[:, 1], temps, topps, topks)
                lp_all = jax.nn.log_softmax(adj)
                top_lp, top_ix = lax.top_k(lp_all, n_top)
                s_lp = jnp.take_along_axis(lp_all, firsts[:, None], 1)[:, 0]
            rows = slot + n_s * jnp.arange(mem)  # flat state row per member

            def upd(arr, vals):
                en = enables.reshape((mem,) + (1,) * (vals.ndim - 1))
                return arr.at[rows].set(jnp.where(en, vals, arr[rows]))

            counts_rows = jnp.zeros(
                (mem, spec.vocab_size), jnp.int32
            ).at[jnp.arange(mem), firsts].set(1)
            return (
                firsts, s_lp, top_ix, top_lp, ck, cv,
                upd(token_s, firsts),
                upd(lengths_s, lengths[:, 0]),
                upd(keys_s, split[:, 0]),
                upd(temp_s, temps),
                upd(topp_s, topps),
                upd(topk_s, topks),
                upd(pp_s, pps),
                upd(fp_s, fps),
                upd(counts_s, counts_rows),
                upd(bias_s, bias_rows),
                upd(live_s, (budgets > 1) & (firsts != eoss)),
                upd(budget_s, budgets - 1),
                upd(eos_s, eoss),
            )

        return jax.jit(
            admit,
            donate_argnames=(
                "ck", "cv", "token_s", "lengths_s", "keys_s",
                "temp_s", "topp_s", "topk_s",
                "pp_s", "fp_s", "counts_s", "bias_s",
                "live_s", "budget_s", "eos_s",
            ),
        )

    @_program("_admit_cache", lambda self, bucket: ("dedup", bucket))
    def _dedup_admit_fn(self, bucket: int):
        """Jitted shared-prefix dedup admission (``quorum_dedup=1``,
        docs/quorum.md): a full quorum group carries the SAME prompt and
        (``member_seeds=shared``) the same weights, so member 0's K/V IS
        every member's K/V. The prompt prefills ONCE — unvmapped, into a
        ``[L, 1, bucket, K·hd]`` scratch mini-cache; prefill's attention
        runs on the in-flight q/k/v and only *writes* the cache, so the
        scratch costs one bucket of HBM, not a slot copy — and the result
        broadcasts into all M stacked rows of the shared slot: one
        dynamic_update_slice over the member axis (dense), or one scatter
        through the slot group's shared page chain (``kv_pages=1``: the M
        pool copies share ONE chain, so a single id vector addresses every
        member — the aliasing form of the broadcast). Sampling is
        per-member and bit-identical to ``_admit_fn_members``, so each
        member's stream stays token-for-token the stream the M-prefill
        path produces."""
        spec = self.spec
        n_top = min(TOP_LOGPROBS, spec.vocab_size)
        n_s = self.n_slots
        mem = self.members
        ps = self.kv_page_size
        paged = self.kv_pages
        ell, kv, hd = spec.n_layers, spec.n_kv_heads, spec.head_dim
        dt = jnp.dtype(spec.dtype)

        def admit(params, tokens, lengths, slot, enables, seeds,
                  temps, topps, topks, pps, fps, bias_rows, budgets, eoss,
                  ck, cv, token_s, lengths_s, keys_s, temp_s, topp_s, topk_s,
                  pp_s, fp_s, counts_s, bias_s, live_s, budget_s, eos_s):
            # Same signature as _admit_fn_members so the dispatch site is
            # one fn swap. ``enables`` is all-True by construction (the
            # dedup route only fires on full live groups) — unused.
            del enables
            # Member 0's weights. The leaves outside the blocks are sliced
            # here; the blocks stay stacked and the layer scan picks the
            # member out of each layer's [M, …] slice (block_member=0),
            # which it reads anyway: ``x[:, 0]`` of a layers-major leaf
            # out here is a strided slice the compiler would materialize,
            # a copy of one member's whole block weights per admit.
            p0 = member_params(
                {k: v for k, v in params.items() if k != BLOCKS}, 0)
            p0[BLOCKS] = params[BLOCKS]
            mini = jnp.zeros((ell, 1, bucket, kv * hd), dt)
            with tracing_program(f"admit_dedup/{bucket}"):
                logits, mini_k, mini_v = prefill(
                    p0, spec, tokens[0], lengths[0], mini, mini,
                    tp_mesh=self._tp_mesh, block_member=0)

            if paged:
                hp = -(-bucket // ps)
                pad = hp * ps - bucket

                def bcast(pkv, mini_c):
                    r = mini_c[:, 0]                   # [L, bucket, K·hd]
                    if pad:
                        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0)))
                    r = r.reshape(ell, hp, ps, kv, hd).transpose(
                        0, 1, 3, 2, 4)                 # [L, hp, K, ps, hd]
                    # Chain ids live in every (member, layer) table copy
                    # identically; entries past the claimed chain are the
                    # zero sink, which collects the bucket's padded tail
                    # exactly as page_write_prefill's writes do (masked by
                    # every attention length mask).
                    mp = pkv.table.shape[-1]
                    ids = lax.dynamic_slice(
                        pkv.table[0, 0], (slot, 0), (1, mp))[0][:hp]
                    pool = pkv.pool.at[:, :, ids].set(
                        r.astype(pkv.pool.dtype)[None])
                    return PagedKV(pool, pkv.table)
            else:
                def bcast(cache, mini_c):
                    upd = jnp.broadcast_to(
                        mini_c[None].astype(cache.dtype),
                        (mem,) + mini_c.shape)
                    return lax.dynamic_update_slice(
                        cache, upd, (0, 0, slot, 0, 0))

            ck = bcast(ck, mini_k)
            cv = bcast(cv, mini_v)

            adj = logits[0].astype(jnp.float32)[None, :] + bias_rows  # [M, V]
            # PRNG identical to _admit_fn_members: per-member seed, split
            # row 1 samples the first token, row 0 carries.
            with jax.named_scope("sample"):
                keys = jax.vmap(jax.random.PRNGKey)(seeds)
                split = jax.vmap(jax.random.split)(keys)
                firsts = sample_token_rows(adj, split[:, 1], temps, topps, topks)
                lp_all = jax.nn.log_softmax(adj)
                top_lp, top_ix = lax.top_k(lp_all, n_top)
                s_lp = jnp.take_along_axis(lp_all, firsts[:, None], 1)[:, 0]
            rows = slot + n_s * jnp.arange(mem)

            def upd(arr, vals):
                return arr.at[rows].set(vals)

            counts_rows = jnp.zeros(
                (mem, spec.vocab_size), jnp.int32
            ).at[jnp.arange(mem), firsts].set(1)
            return (
                firsts, s_lp, top_ix, top_lp, ck, cv,
                upd(token_s, firsts),
                upd(lengths_s, lengths[:, 0]),
                upd(keys_s, split[:, 0]),
                upd(temp_s, temps),
                upd(topp_s, topps),
                upd(topk_s, topks),
                upd(pp_s, pps),
                upd(fp_s, fps),
                upd(counts_s, counts_rows),
                upd(bias_s, bias_rows),
                upd(live_s, (budgets > 1) & (firsts != eoss)),
                upd(budget_s, budgets - 1),
                upd(eos_s, eoss),
            )

        return jax.jit(
            admit,
            donate_argnames=(
                "ck", "cv", "token_s", "lengths_s", "keys_s",
                "temp_s", "topp_s", "topk_s",
                "pp_s", "fp_s", "counts_s", "bias_s",
                "live_s", "budget_s", "eos_s",
            ),
        )

    @_program("_admit_cache",
              lambda self, bucket, history: ("seg", bucket, history),
              kept=True)
    def _seg_fn(self, bucket: int, history: int):
        """Jitted: write one prompt segment's K/V into a slot (chunked
        prefill). ``history`` (static, power-of-two) bounds the attention
        reads to the cache prefix that actually holds history — one program
        per (segment bucket, history bucket) pair."""
        spec = self.spec

        def seg(params, tokens, offset, n_valid, slot, ck, cv):
            return prefill_segment(
                params, spec, tokens, offset, n_valid, ck, cv, slot,
                history=history, sharded=self._sharded)

        return jax.jit(seg, donate_argnames=("ck", "cv"))

    @_program("_admit_cache", lambda self: "register", kept=True)
    def _register_fn(self):
        """Jitted: install a finished chunked admission's per-slot state.

        The slot's first token is then sampled by the next batched decode
        chunk — ``decode_step`` on the last prompt token at position n-1
        recomputes the logits single-shot admission samples from, and the
        PRNG stream starts from the same ``PRNGKey(seed)`` split. For dense
        models the two paths generate identical tokens (pinned by
        tests/test_chunked_prefill.py); for MoE models the prefill-side
        grouped expert compute and the decode-side dense compute differ by
        floating-point reassociation (and by capacity drops when
        ``moe_capacity_factor < E/k``), so a near-tie sample can diverge.
        """
        vocab = self.spec.vocab_size

        def register(slot, last_tok, n_minus1, seed, temp1, topp1, topk1,
                     pp1, fp1, bias_row, budget1, eos1, dfa1,
                     token_s, lengths_s, keys_s, temp_s, topp_s, topk_s,
                     pp_s, fp_s, counts_s, bias_s, live_s, budget_s, eos_s,
                     dfa_s):
            return (
                token_s.at[slot].set(last_tok),
                lengths_s.at[slot].set(n_minus1),
                keys_s.at[slot].set(jax.random.PRNGKey(seed)),
                temp_s.at[slot].set(temp1),
                topp_s.at[slot].set(topp1),
                topk_s.at[slot].set(topk1),
                pp_s.at[slot].set(pp1),
                fp_s.at[slot].set(fp1),
                counts_s.at[slot].set(jnp.zeros((vocab,), jnp.int32)),
                bias_s.at[slot].set(bias_row),
                # No token emitted yet (the first samples in the next decode
                # chunk), so the full budget remains and the row is live.
                live_s.at[slot].set(budget1 > 0),
                budget_s.at[slot].set(budget1),
                eos_s.at[slot].set(eos1),
                # Grammar-DFA start state (0 = FREE for unconstrained).
                # Constrained admissions always register through here —
                # the single-shot admit path samples its first token
                # INSIDE the prefill program, before any mask could apply,
                # so _start_admissions routes them chunked instead.
                dfa_s.at[slot].set(dfa1),
            )

        return jax.jit(
            register,
            donate_argnames=(
                "token_s", "lengths_s", "keys_s", "temp_s", "topp_s", "topk_s",
                "pp_s", "fp_s", "counts_s", "bias_s",
                "live_s", "budget_s", "eos_s", "dfa_s",
            ),
        )

    @_program("_admit_cache", lambda self, n: ("snap", n))
    def _snapshot_fn(self, n: int):
        """Jitted: slice ``n`` cache positions of one slot starting at a
        dynamic offset — the device→host snapshot's device half
        (kv_transfer.slice_rows, the shared chunk wire format). Non-
        donating (it READS the live cache); one program per chunk-aligned
        length, generic over the cache pytree (bf16 arrays or int8
        (values, scales) pairs — the host store receives the native
        representation either way). Always unstacked: the prefix store
        rejects members engines at config time."""
        return jax.jit(lambda ck, cv, slot, offset: kv_transfer.slice_rows(
            (ck, cv), slot, offset, n, stacked=False,
            n_slots=self.n_slots, n_kv_heads=self.spec.n_kv_heads))

    @_program("_admit_cache", lambda self, n: ("restore", n))
    def _restore_fn(self, n: int):
        """Jitted: write an ``n``-token host KV slice into positions
        [start, start+n) of one slot (host→device restore,
        kv_transfer.write_rows) — ``start`` is traced, so skipping a
        slot-resident overlap costs no extra compile. Donates the cache
        like every other cache-writing program; ``n`` is always a
        prefill_chunk multiple, so the program count is bounded by
        max_seq/prefill_chunk."""
        def restore(ck, cv, slot, start, host):
            return kv_transfer.write_rows(
                (ck, cv), host, slot, start,
                stacked=False, n_slots=self.n_slots)

        return jax.jit(restore, donate_argnames=("ck", "cv"))

    # ---- host prefix store (tier behind the slot-resident cache) ----------

    def _queue_snapshot(self, slot: int) -> None:
        """Note a released slot whose KV prefix should be snapshotted to the
        host store. Caller holds ``_cond``; the device dispatch is deferred
        to the next scheduler turn (``_dispatch_snapshots``) so a first-use
        XLA compile never runs under the lock — safe because only the
        scheduler thread mutates the cache, and the next admission into the
        slot happens after the deferred dispatch."""
        if self.prefix_store is None:
            return
        tokens = self._resident[slot]
        c = self.prefix_store.chunk_tokens
        n = len(tokens) - len(tokens) % c
        if n >= max(c, MIN_PREFIX_REUSE):
            self._pending_snaps.append((slot, tokens[:n]))
            self._snap_backlog += 1

    def _dispatch_snapshots(self) -> None:
        """Dispatch deferred snapshot slices (scheduler thread, lock NOT
        held) and hand the resulting jax futures to the store worker, which
        blocks on the device→host fetch off the hot turn. Only the chunks
        the store does not already cover are sliced — a conversation's
        turn-N release re-snapshots just the tokens turn N added."""
        with self._cond:
            pending, self._pending_snaps = self._pending_snaps, []
        for slot, tokens in pending:
            try:
                with self._cond:
                    # The slot may have been re-admitted this same turn; its
                    # rows [0, len(tokens)) are still the snapshot's prefix
                    # ONLY while the resident view still starts with it.
                    stale = self._resident[slot][: len(tokens)] != tokens
                if stale:
                    continue
                # Each queued item pins a device-resident slice until the
                # worker fetches it: under churn faster than one worker
                # drains, an unbounded queue would grow device memory
                # without limit. Past the cap the snapshot is dropped —
                # an unsnapshotted release is simply a future store miss.
                if self._snap_queue.qsize() >= SNAP_QUEUE_MAX:
                    self.prefix_store_snapshots_dropped += 1
                    continue
                have = self.prefix_store.covered(tokens)
                if have >= len(tokens):
                    continue
                payload = self._snapshot_fn(len(tokens) - have)(
                    self._ck, self._cv, np.int32(slot), np.int32(have))
                self._sent(OTHER, "snap")
                self._snap_queue.put((tokens, have, payload))
            except Exception:
                # Snapshots are opportunistic: a failed slice (first-use
                # compile error, poisoned cache after an engine fault)
                # loses ONE snapshot, never the scheduler turn — and the
                # finally below keeps the backlog honest either way, so
                # drain_prefix_store cannot hang on a leaked count.
                logger.exception("prefix-store snapshot dispatch failed")
            finally:
                with self._cond:
                    self._snap_backlog -= 1

    def _snapshot_worker(self) -> None:
        """Store-insert worker: fetch dispatched snapshot slices to host
        (the blocking half) and insert them chunk-split into the trie."""
        while True:
            item = self._snap_queue.get()
            try:
                if item is None:
                    return
                tokens, have, payload = item
                faults.fire("engine.snapshot")
                leaves = kv_transfer.fetch_to_host(payload)
                c = self.prefix_store.chunk_tokens
                n_chunks = (len(tokens) - have) // c
                # Contiguous copies per chunk: a view would pin the whole
                # fetched slice alive after its siblings are LRU-evicted,
                # drifting the store's byte accounting from real memory.
                chunk_payloads = [
                    [np.ascontiguousarray(leaf[:, :, i * c:(i + 1) * c])
                     for leaf in leaves]
                    for i in range(n_chunks)
                ]
                self.prefix_store.insert(tokens, have, chunk_payloads)
            except Exception:
                # A poisoned array (engine failure mid-flight) loses this
                # snapshot, never the worker: the store must keep serving.
                logger.exception("prefix-store snapshot insert failed")
            finally:
                self._snap_queue.task_done()

    def drain_prefix_store(self) -> None:
        """Block until every queued snapshot has landed in the host store —
        a test/bench affordance; serving never needs to wait (a snapshot
        still in flight is simply a store miss). Waits out three stages in
        order: engine quiescence first — a caller that just consumed its
        ``end`` sentinel can get here BEFORE the scheduler's
        ``_release_slot`` queues the snapshot (the sentinel is emitted
        inside the reap, the release happens after), and a finished request
        still occupies its slot until then — then the deferred dispatch
        list (drained by the scheduler's next turn), then the worker's
        fetch/insert queue."""
        if self.prefix_store is None:
            return
        while True:
            with self._cond:
                busy = (bool(self._pending) or bool(self._admitting)
                        or any(self._slots) or bool(self._inflight)
                        or bool(self._handoffs) or self._snap_backlog)
            if busy:
                time.sleep(0.002)
                continue
            self._snap_queue.join()
            with self._cond:
                if not self._snap_backlog:
                    return

    def export_prefix_chunks(self, max_bytes: int | None = None) -> bytes:
        """Serialize the host prefix store's restorable chunk chains into
        the migration wire format (quorum_tpu/cache/prefix_wire.py) —
        served by ``GET /debug/prefix/chunks`` so the router tier can move
        a rotating replica's hot prefixes to its ring successor. Pure host
        work: the store's payloads are already host arrays in the cache's
        native representation; no device touch, no scheduler interaction."""
        if self.prefix_store is None:
            raise ValueError(
                "no host prefix store on this engine (prefix_store=host "
                "is not configured)")
        from quorum_tpu.cache import prefix_wire

        return prefix_wire.serialize_chains(
            self.prefix_store.export_chains(max_bytes=max_bytes),
            self.prefix_store.chunk_tokens)

    def import_prefix_chunks(self, blob: bytes) -> dict:
        """Seed the host prefix store from a wire blob exported by another
        replica (``PUT /debug/prefix/chunks``). Validates the payload
        against THIS engine's cache layout — chunk granularity, leaf count,
        per-leaf dtype and chunk shape — so a blob from a differently
        configured replica is a 400, never a poisoned store (a wrong-shape
        payload would corrupt the next restore's cache write). Returns
        insert accounting. Pure host work; the seeded chains restore
        host→device through the ordinary admission path
        (``kv_transfer.write_rows`` — the same host-bounce glue snapshots
        already ride)."""
        if self.prefix_store is None:
            raise ValueError(
                "no host prefix store on this engine (prefix_store=host "
                "is not configured)")
        from quorum_tpu.cache import prefix_wire

        chunk_tokens, chains = prefix_wire.parse(blob)
        c = self.prefix_store.chunk_tokens
        if chunk_tokens != c:
            raise ValueError(
                f"payload chunk_tokens={chunk_tokens} does not match this "
                f"engine's prefix_store_chunk={c}")
        # Expected per-leaf chunk spec from the engine's wire contract:
        # [L, K, c, …] chunks (kv_transfer.slice_rows wire layout, position
        # on axis 2) — spec-derived, so dense and paged caches validate the
        # same format.
        expected = [
            (shp[:2] + (c,) + shp[2:], np.dtype(dt))
            for shp, dt in self._wire_leaf
        ]
        for chain in chains:
            for arrays in chain.payloads:
                if len(arrays) != len(expected):
                    raise ValueError(
                        f"chunk carries {len(arrays)} arrays, this cache "
                        f"has {len(expected)} leaves")
                for a, (shape, dtype) in zip(arrays, expected):
                    if a.shape != shape or a.dtype != dtype:
                        raise ValueError(
                            f"chunk leaf {a.shape}/{a.dtype} does not "
                            f"match the cache layout {shape}/{dtype}")
        tokens_imported = 0
        chains_imported = 0
        for chain in chains:
            got = self.prefix_store.import_chain(chain.tokens,
                                                 chain.payloads)
            if got:
                chains_imported += 1
                tokens_imported += got
        return {
            "chains": len(chains),
            "chains_imported": chains_imported,
            "tokens_imported": tokens_imported,
            "store_bytes": self.prefix_store.bytes_held,
            "store_entries": self.prefix_store.n_entries,
        }

    def _store_lookup(
        self, prompt: list[int], slot_reuse: int
    ) -> tuple[int, object] | None:
        """``(restore_len, host_kv_pytree)`` when the store's longest match
        beats the slot-resident reuse, else None. The restore length obeys
        the same invariants as ``_reuse_len``: capped at len(prompt)−1
        (the final token must prefill so its logits exist to sample from),
        aligned DOWN to a prefill_chunk multiple (segment offsets must stay
        aligned), floored at MIN_PREFIX_REUSE."""
        if self.prefix_store is None:
            return None
        cap = len(prompt) - 1
        matched, payloads = self.prefix_store.longest_match(prompt[:cap])
        r = min(matched, cap)
        if self.prefill_chunk:
            r -= r % self.prefill_chunk
        if r < MIN_PREFIX_REUSE or r <= slot_reuse:
            return None
        # Only the tail past the slot-resident reuse crosses host→device:
        # rows [0, slot_reuse) already hold identical KV in the claimed
        # slot (both lengths are prefill_chunk-aligned), so transferring
        # them again would just stretch the blocking restore. Concatenate
        # only the chunks that intersect [slot_reuse, r) — this runs on the
        # scheduler thread, and copying overlap/tail chunk bytes just to
        # slice them away would stall every active decode stream.
        c = self.prefix_store.chunk_tokens
        lo = slot_reuse // c
        hi = -(-r // c)
        n_leaves = len(payloads[0])
        cat = [
            np.concatenate([chunk[j] for chunk in payloads[lo:hi]],
                           axis=2)[:, :, slot_reuse - lo * c: r - lo * c]
            for j in range(n_leaves)
        ]
        host = jax.tree.unflatten(self._wire_def, cat)
        return r, host

    def _restore_into(self, slot: int, start: int, n: int, host,
                      req: _Request, stage: bool = False) -> None:
        """Write ``n`` matched host prefix tokens into the claimed slot's
        cache rows [start, start+n) (scheduler thread) — ``start`` is the
        slot-resident reuse the transfer skips. Blocks until the transfer
        lands — the honest restore latency, observed on the restore
        histogram and recorded as a ``prefix-restore`` span on the
        request's trace. Under disagg (``stage``) the restore targets the
        PREFILL group's staging cache instead: the tail segments must
        attend over the restored history, and the whole prefix then rides
        the ordinary chunk-granular handoff into the decode slot."""
        t0 = time.perf_counter()
        if stage:
            self._sck, self._scv = self._restore_fn(n)(
                self._sck, self._scv, np.int32(slot), np.int32(start), host)
            prog = self._sent(OTHER, "restore")
            # qlint: allow-sync(admission path; blocking here is the honest restore latency the histogram reports)
            jax.block_until_ready((self._sck, self._scv))
        else:
            self._ck, self._cv = self._restore_fn(n)(
                self._ck, self._cv, np.int32(slot), np.int32(start), host)
            prog = self._sent(OTHER, "restore")
            # qlint: allow-sync(admission path; blocking here is the honest restore latency the histogram reports)
            jax.block_until_ready((self._ck, self._cv))
        t1 = time.perf_counter()
        prog.land(t1)
        obs.PREFIX_STORE_RESTORE.observe(t1 - t0)
        obs.PREFIX_STORE_HITS.inc()
        obs.PREFIX_STORE_RESTORED_TOKENS.inc(n)
        self.prefix_store_hits += 1
        self.prefix_store_tokens_restored += n
        self.prefix_store_restore_s += t1 - t0
        req.span("prefix-restore", t0, t1, tokens=n, slot=slot)

    # ---- disaggregated serving: prefill loop + device↔device KV handoff ----

    @_program("_admit_cache", lambda self, n: ("hslice", n))
    def _handoff_slice_fn(self, n: int):
        """Jitted: slice ``n`` staging-cache positions of one flat row into
        the chunk wire layout (kv_transfer.slice_rows) — the prefill-mesh
        half of the handoff. Non-donating: it READS the live staging cache,
        and is dispatched BEFORE the next segment donates those buffers
        (enqueue order is execution order, so the read completes first —
        the same discipline the decode ring's payload chains rely on)."""
        stacked = self.members > 1
        n_s = self.n_slots

        return jax.jit(lambda ck, cv, row, start: kv_transfer.slice_rows(
            (ck, cv), row, start, n, stacked=stacked, n_slots=n_s,
            n_kv_heads=self.spec.n_kv_heads))

    @_program("_admit_cache", lambda self, n: ("hput", n))
    def _handoff_write_fn(self, n: int):
        """Jitted: write a transferred ``n``-position chunk into the decode
        cache's claimed slot (kv_transfer.write_rows) — the decode-mesh
        half, run by the DECODE loop only (all decode-cache mutation stays
        on one thread) and donating the cache like every other writer."""
        stacked = self.members > 1
        n_s = self.n_slots

        def put(ck, cv, chunk, row, start):
            return kv_transfer.write_rows(
                (ck, cv), chunk, row, start,
                stacked=stacked, n_slots=n_s)

        return jax.jit(put, donate_argnames=("ck", "cv"))

    def _handoff_dispatch(self, adm: _Admission, upto: int):
        """Dispatch (async) the staging slice covering rows
        [adm.handed, upto) — widened to a power-of-two window ENDING at
        ``upto`` (re-sending already-handed rows is an idempotent
        overwrite; exact tail lengths would compile one slice/write pair
        per length). Returns None when nothing new is staged."""
        if upto <= adm.handed:
            return None
        b = 1 << (upto - adm.handed - 1).bit_length()
        b = min(b, self.spec.max_seq)
        start = max(0, upto - b)
        payload = self._handoff_slice_fn(b)(
            self._sck, self._scv, np.int32(adm.slot), np.int32(start))
        return (payload, start, b, upto, self._sent(OTHER, "hslice"))

    def _handoff_commit(self, adm: _Admission, disp, final: bool = False):
        """Transfer a dispatched slice device→device onto the decode mesh
        (blocking the PREFILL thread only — the decode ring keeps rolling)
        and queue it for the decode loop; ``final`` additionally queues the
        register marker. The overlap contract: the slice for chunk i was
        dispatched before segment i+1, so this transfer proceeds while the
        prefill group computes the next segment."""
        if disp is not None:
            payload, start, b, upto, prog = disp
            faults.fire("engine.kv_handoff")
            t0 = time.perf_counter()
            if self.zero_drain:
                # Same device group: the sliced chunk is already resident
                # on the decode mesh — no transfer, no handoff bytes. The
                # queued piece is a pure data dependency the injection
                # write consumes at the next reap boundary.
                moved, n_bytes, route = payload, 0, "resident"
            else:
                moved, n_bytes, dt, route = kv_transfer.transfer(
                    payload, self._rep)
                # the transfer waited for the slice: the prefill group's
                # programs up to it have landed
                prog.land(time.perf_counter())
                self.n_kv_handoffs += 1
                self.kv_handoff_bytes += n_bytes
                self.kv_handoff_s += dt
            adm.req.span(
                "kv-handoff", t0, time.perf_counter(), tokens=b,
                slot=adm.slot, bytes=n_bytes, route=route)
            FLIGHT.record("handoff", rid=adm.req.rid, engine=self._tag,
                          loop="prefill" if self.disagg else "decode",
                          slot=adm.slot, tokens=b, bytes=n_bytes,
                          route=route)
            adm.handed = upto
            with self._cond:
                self._handoffs.append(("kv", adm, moved, start, b))
                self._cond.notify_all()
        if final:
            adm.final_sent = True
            with self._cond:
                self._handoffs.append(("final", adm, None, 0, 0))
                self._cond.notify_all()

    def _drain_handoffs(self) -> None:
        """Decode loop: write queued handoff pieces into their claimed
        slots and register admissions whose final marker arrived. Pieces of
        a ``dead`` admission are dropped — its claim may already have been
        re-issued, and a stale write would corrupt the new tenant."""
        while True:
            with self._cond:
                if not self._handoffs:
                    return
                kind, adm, chunk, start, n = self._handoffs.popleft()
            if adm.dead:
                continue
            if kind == "kv":
                try:
                    # Paged decode cache: the claim's table entries must be
                    # on device before this injection scatters through them
                    # (no-op when clean, and always on THIS loop — the
                    # decode-cache owner).
                    self._paged_sync_table()
                    self._ck, self._cv = self._handoff_write_fn(n)(
                        self._ck, self._cv, chunk,
                        np.int32(adm.slot), np.int32(start))
                    self._sent(OTHER, "hput")
                    FLIGHT.record("inject", rid=adm.req.rid,
                                  engine=self._tag, loop="decode",
                                  slot=adm.slot, tokens=n)
                except Exception as e:
                    # Same containment contract as the register branch: a
                    # failed slot write dooms only this admission when the
                    # donated decode cache survived (checked inside);
                    # escalation to _fail_all only when it was consumed.
                    adm.dead = True
                    self._contain_admission_failure([adm.req], e,
                                                    admissions=[adm])
                continue
            req = adm.req
            if req.cancel.is_set():
                with self._cond:
                    if adm.dead:
                        continue
                    adm.dead = True
                if not req.expired:  # deadline expiry already delivered err
                    self.n_cancelled += 1
                    req.out.put(("end", None))
                self._release_admission(adm)
                continue
            try:
                if req.grammar is not None:
                    # Arena placement is decode-group state (the DFA masks
                    # apply inside decode chunks), so it happens HERE, on
                    # the decode loop — never from the prefill thread.
                    req.g_start = self._ensure_grammar(req.grammar)
                    self.n_constrained += 1
                with self._cond:
                    self._resident[adm.slot] = list(req.prompt_ids)
                    live = any(r is not None for r in self._slots)
                if self._inflight or live:
                    # The injected row registers onto a LIVE ring — other
                    # rows' dispatches in flight, or resident rows decoding
                    # at full depth (on a fast device the ring can be
                    # momentarily drained-by-completion at the reap
                    # boundary; those admissions still never clamped it).
                    # The zero-drain acceptance counter: structurally 0 on
                    # drain-based colocated engines, whose admissions
                    # never ride the injection queue at all.
                    self.n_admission_overlap += 1
                    obs.ADMISSION_OVERLAP.inc()
                self._finish_admission(adm)
            except Exception as e:
                adm.dead = True
                self._contain_admission_failure([req], e, admissions=[adm])

    def _admit_staged(self, req: _Request, slot: int) -> None:
        """Claim the decode slot and start the admission against the
        staging cache (disagg: on the prefill group; zero_drain: on the
        same group, but on an independent dispatch chain the decode ring
        never blocks on). Every staged admission rides the chunked path; a
        host prefix-store match restores into the STAGING slot first (the
        tail segments attend over it there) and reaches the decode slot
        through the ordinary handoff/injection queue."""
        offset = 0
        try:
            # Inside containment: the request is already popped from
            # _pending but not yet in _admitting — an uncaught failure
            # here (host-RAM pressure in the store concatenate, say) would
            # slip past the outer catch's admitting sweep and leave the
            # consumer blocked forever.
            faults.fire("engine.admit")
            restore = self._store_lookup(req.prompt_ids, 0)
        except Exception as e:
            self._contain_prefill_failure([req], e)
            return
        if restore is not None:
            offset = restore[0]
        adm = self._open_admission(req, slot, offset=offset, restored=offset)
        FLIGHT.record("stage-admit", rid=req.rid, engine=self._tag,
                      loop="prefill" if self.disagg else "decode",
                      slot=slot, restored=offset)
        if self.kv_pages:
            # Reserve the decode slot's page span NOW, host-only (allocator
            # + mirror under _cond — legal on the prefill thread); the
            # decode loop uploads the table before the first injection.
            with self._cond:
                claim = self._paged_claim(slot, req, 0)
            if claim is None:
                # Can't happen after _start_admissions' fits-check (only
                # this thread claims; other threads only release) — contain
                # defensively rather than corrupt page accounting.
                self._contain_prefill_failure(
                    [req], RuntimeError("kv page claim failed after "
                                        "passing the fits check"))
                return
        with self._cond:
            self._claimed.add(slot)
            self._resident[slot] = []
            self._admitting.append(adm)
        if restore is not None:
            try:
                self._restore_into(slot, 0, offset, restore[1], req,
                                   stage=True)
            except Exception as e:
                self._contain_prefill_failure([req], e, admissions=[adm])

    def _stage_state_ok(self) -> bool:
        """Whether the donated staging cache survived the last failed
        prefill-group call (the prefill-side twin of _device_state_ok)."""
        try:
            leaves = jax.tree.leaves((self._sck, self._scv))
            return not any(x.is_deleted() for x in leaves
                           if isinstance(x, jax.Array))
        except Exception:
            return False

    def _contain_prefill_failure(
        self, reqs: list[_Request], exc: Exception,
        admissions: "list[_Admission] | None" = None,
    ) -> None:
        """A prefill-group dispatch failed: the group boundary IS the blast-
        radius boundary. With the staging cache intact only the named
        request(s) die; when the donated staging buffers were consumed,
        every in-flight admission's staged KV went with them — doom the
        admitting set and rebuild the STAGING cache, leaving pending
        requests queued and active decode streams completely untouched
        (the insulation disagg exists for)."""
        FLIGHT.record("containment", engine=self._tag,
                      loop="prefill" if self.disagg else "decode",
                      site="prefill",
                      error=f"{type(exc).__name__}: {exc}"[:200],
                      rids=[r.rid for r in reqs])
        FLIGHT.dump("containment")
        for adm in admissions or ():
            adm.dead = True
            self._release_admission(adm)
        if self._stage_state_ok():
            self.n_failures += len(reqs)
            for r in reqs:
                now = time.perf_counter()
                r.span("engine-failure", now, now,
                       error=type(exc).__name__, contained=True)
                r.out.put(("err", exc))
            return
        with self._cond:
            doomed_adms = list(self._admitting)
        doomed = list(reqs)
        for a in doomed_adms:
            a.dead = True
            if a.req not in doomed:
                doomed.append(a.req)
            self._release_admission(a)
        self.n_rebuilds += 1
        self._record_breaker_failure()
        self.n_failures += len(doomed)
        for r in doomed:
            now = time.perf_counter()
            r.span("engine-failure", now, now,
                   error=type(exc).__name__, contained=True, group="prefill")
            r.out.put(("err", exc))
        if not self._stop:
            self._init_stage_state()

    def _prefill_work(self) -> bool:
        """Does the prefill loop have anything to do right now? Caller
        holds ``_cond``. An admission awaiting its decode-group register
        (final_sent, not cancelled) is NOT work — the decode loop owns it;
        pending requests count only when one could actually claim a slot."""
        for a in self._admitting:
            if not a.final_sent or a.req.cancel.is_set():
                return True
        if not self._pending:
            return False
        members = {r.member for r in self._pending}
        for m in members:
            lo = m * self.n_slots
            for i in range(lo, lo + self.n_slots):
                if self._slots[i] is None and i not in self._claimed:
                    return True
        return False

    def _prefill_scheduler(self) -> None:
        """The prefill group's cooperating loop (disagg only): admit
        pending requests into staging, advance segments, hand off KV. The
        decode loop (:meth:`_scheduler`) never blocks on any of it."""
        while True:
            with self._cond:
                while not (self._stop or self._prefill_work()):
                    # Going idle: refresh the occupancy gauge so a
                    # drained prefill group reads 0, not the last burst.
                    obs.PREFILL_GROUP_ACTIVE.set(len(self._admitting))
                    with self._phase("idle"):
                        self._cond.wait()
                stopping = self._stop
                if stopping:
                    pending, self._pending = self._pending, []
                    admitting = list(self._admitting)
            if stopping:
                # Drain consumers (shutdown set every cancel event): queued
                # requests end cleanly; in-flight admissions are marked
                # dead so the decode loop drops their queued pieces.
                for r in pending:
                    r.out.put(("end", None))
                for adm in admitting:
                    adm.dead = True
                    adm.req.out.put(("end", None))
                    self._release_admission(adm)
                return
            obs.PREFILL_GROUP_ACTIVE.set(len(self._admitting))
            try:
                with self._phase("admit"):
                    self._start_admissions()
                    self._step_admissions()
            except Exception as e:  # fail open, prefill-group blast radius
                try:
                    with self._cond:
                        adms = list(self._admitting)
                    self._contain_prefill_failure(
                        [a.req for a in adms], e, admissions=adms)
                except Exception:
                    pass

    # ---- constrained decoding: grammar arena + per-row DFA state -----------

    def _ensure_grammar(self, grammar) -> int:
        """Place a compiled grammar's token-DFA rows in the device arena
        (scheduler thread, outside ``_cond``) and return the GLOBAL start
        state a request decoding under it begins in. Idempotent per
        grammar: the offset is stable for the arena's lifetime, so rows'
        device-resident DFA states stay valid as other grammars come and
        go. A new grammar re-uploads the (padded, bucketed) table pair —
        an async admission-time transfer, never a per-chunk cost."""
        key = grammar.key or ("anon", id(grammar))
        off = self._g_offsets.get(key)
        if off is None:
            if grammar.vocab_size != self.spec.vocab_size:
                raise ValueError(
                    f"grammar compiled for vocab {grammar.vocab_size} "
                    f"cannot constrain a vocab-{self.spec.vocab_size} model")
            if self._g_states + grammar.n_states > CONSTRAIN_ARENA_MAX:
                # Bounded device memory beats serving one more schema: the
                # caller contains this to the one request (active streams
                # and already-resident grammars are untouched).
                raise GrammarArenaFull(
                    f"grammar arena at capacity ({self._g_states} of "
                    f"{CONSTRAIN_ARENA_MAX} states; this grammar needs "
                    f"{grammar.n_states} more) — retry after constrained "
                    "traffic quiesces")
            off = self._g_states
            shifted = np.where(grammar.trans >= 0, grammar.trans + off,
                               -1).astype(np.int32)
            self._g_trans_np = np.concatenate(
                [self._g_trans_np, shifted], axis=0)
            self._g_accept_np = np.concatenate(
                [self._g_accept_np, grammar.accept.astype(bool)])
            self._g_offsets[key] = off
            self._g_grammars[key] = grammar
            self._g_states += grammar.n_states
            self._upload_arena()
        return off + grammar.start

    def _upload_arena(self) -> None:
        """(Re)upload the arena tables padded to a power-of-two state
        bucket — the bucket is part of the constrained program variant's
        cache key, so log-many program shapes cover any arena size.
        Padding rows allow nothing and accept nothing."""
        b = 1
        while b < self._g_states:
            b <<= 1
        trans = self._g_trans_np
        accept = self._g_accept_np
        if b > self._g_states:
            pad = b - self._g_states
            trans = np.concatenate(
                [trans, np.full((pad, trans.shape[1]), -1, np.int32)], axis=0)
            accept = np.concatenate([accept, np.zeros((pad,), bool)])
        self._g_bucket = b
        self._g_trans = jax.device_put(trans, self._rep)
        self._g_accept = jax.device_put(accept, self._rep)

    def _maybe_reset_arena(self) -> None:
        """Drop the arena once it has grown past CONSTRAIN_ARENA_KEEP
        states AND no request anywhere (active, admitting, pending) still
        references a grammar — the only moment offsets may move, because
        no device-resident row state points into the arena. Below the
        threshold the arena is kept as a warm cache: a steady
        same-grammar workload never re-uploads."""
        if self._g_states <= 1 or self._g_states <= CONSTRAIN_ARENA_KEEP:
            return
        with self._cond:
            busy = (
                any(r is not None and r.grammar is not None
                    for r in self._slots)
                or any(a.req.grammar is not None for a in self._admitting)
                or any(r.grammar is not None for r in self._pending))
        if busy:
            return
        self._g_offsets = {}
        self._g_grammars = {}
        self._g_states = 1
        self._g_trans_np = np.zeros((1, self.spec.vocab_size), np.int32)
        self._g_accept_np = np.ones((1,), bool)
        self._g_trans = self._g_accept = None
        self._g_bucket = 0

    @_program("_admit_cache", lambda self: "dfa_reset")
    def _dfa_reset_fn(self):
        return jax.jit(lambda dfa, row: dfa.at[row].set(0),
                       donate_argnums=(0,))

    def _flush_dfa_resets(self) -> None:
        """Return released constrained rows' device DFA state to FREE
        (scheduler thread, lock not held). Runs at the top of
        _start_admissions, i.e. BEFORE any admission this turn can
        activate one of those rows for an unconstrained request — the
        only reader that would mis-mask on a stale state."""
        with self._cond:
            rows, self._pending_dfa_resets = self._pending_dfa_resets, []
        for r in rows:
            self._dfa = self._dfa_reset_fn()(self._dfa, np.int32(r))
            self._sent(OTHER, "dfa_reset")

    def _decode_key(self, n_steps: int, want_lp: bool, history: int,
                    constrained: bool, n_chunks: int = 1):
        """The decode-program cache key. The UNCONSTRAINED single-chunk key
        is the pre-constrain 3-tuple — pinned by tests: batches with no
        grammar row compile and dispatch the exact program variant they
        always did, with no mask/table operands (the logprobs-gating
        contract). Megachunk variants (``n_chunks`` > 1) live under their
        own "loop"-tagged keys, so a ``decode_loop=1`` engine can never
        compile one (the decode_loop=1 cache-key pin — same gating pattern
        again)."""
        if constrained:
            base = ("dfa", n_steps, want_lp, history, self._g_bucket)
        else:
            base = (n_steps, want_lp, history)
        if n_chunks > 1:
            base = ("loop", n_chunks) + base
        if self.kv_pages:
            # Paged-layout programs gather K/V through the page table —
            # structurally different HLO, so they live under "paged"-tagged
            # keys (their own compile-budget families); every kv_pages=0
            # engine's keys stay byte-for-byte the dense tuples (the
            # dense cache-key pin in tests/test_paged_kv.py).
            return ("paged",) + base
        return base

    @_program("_decode_cache",
              lambda self, n_steps, want_lp, history, tstates=0, n_chunks=1:
              self._decode_key(n_steps, want_lp, history, tstates > 0,
                               n_chunks),
              # the plain chunk and its logprobs twin: _decode_key's 3-tuple
              kept=lambda key: len(key) == 3)
    def _decode_fn(self, n_steps: int, want_lp: bool, history: int,
                   tstates: int = 0, n_chunks: int = 1):
        """Jitted: ``n_steps`` batched decode+sample steps over all slots —
        times ``n_chunks`` when megachunked (decode_loop=C > 1): the chunk
        body runs inside a device-resident outer loop with an
        all-rows-finished early exit (transformer.decode_loop), the token/
        valid/aux outputs gain a leading per-chunk axis, and one dispatch
        covers what used to be C dispatches' worth of host turnaround.

        Variants per (chunk size, want_lp, history bucket): the ``want_lp``
        one additionally emits per-step logprobs (log_softmax over [S, V] +
        top-k) — compiled and paid only when some active request asked for
        logprobs; ``history`` (a power-of-two ≥ the longest active sequence
        after this chunk) bounds each step's attention reads to the live
        cache prefix instead of the full padded max_seq row (decode is
        HBM-bound — this is the decode-side bandwidth fix).

        ``tstates`` > 0 selects the CONSTRAINED variant (same gating
        pattern as want_lp — unconstrained batches never compile or pay
        it): the program takes the grammar arena's [tstates, V] transition
        table and [tstates] accept flags plus the per-row DFA state, masks
        each step's logits by the row's state's allow-set (EOS allowed
        exactly in accepting states), and advances the state on the
        sampled token — all inside the chunk's on-device scan, zero host
        round-trips at any pipeline depth. Unconstrained rows ride along
        in state 0 (FREE: everything allowed, self-loop). The variant
        additionally returns per-step masked-entry counts.

        The per-step model/cache/finish machinery lives in
        :func:`transformer.decode_chunk`: rows finish ON DEVICE (EOS or
        budget), so the chunk returns per-row ``n_valid`` and updated
        ``live``/``budget`` state — what lets the scheduler keep
        ``decode_pipeline`` chunks in flight without producing overrun
        tokens for rows that finish mid-window. (A constrained row that
        completes its grammar enters an accept-sink whose only allowed
        token is EOS — the existing on-device EOS finish then retires it,
        so grammar completion maps to finish_reason "stop" with no new
        host logic.)"""
        constrained = tstates > 0
        spec = self.spec
        sharded = self._sharded

        n_top = min(TOP_LOGPROBS, spec.vocab_size)
        n_rows = self._rows
        n_s = self.n_slots
        vocab = spec.vocab_size
        mem = self.members

        def chunk_core(params, active, eos_s, ck, cv, token_s, lengths_s,
                       keys_s, temp_s, topp_s, topk_s, pp_s, fp_s, counts_s,
                       bias_s, live_s, budget_s,
                       trans_t=None, accept_t=None, dfa_s=None):
            # Inactive slots run the forward (batch is static) but their
            # K/V write is masked off — a slot mid-chunked-admission must
            # not have its freshly prefilled cache clobbered by the dummy
            # position-0 write. live_s additionally drops rows that already
            # finished on device in an earlier in-flight chunk.
            live0 = (active > 0) & live_s & (budget_s > 0)

            if mem > 1:
                # Stacked members: one dispatch advances every member's
                # slots (fold/unfold via _stacked_rows_call; sampling
                # stays flat).
                def model_call(ck, cv, tok, pos, wm):
                    return _stacked_rows_call(
                        mem, n_s,
                        lambda p, k, v, t, ps, w: decode_step(
                            p, spec, t, ps, k, v, write_mask=w,
                            history=history, sharded=sharded),
                        params, ck, cv, tok, pos, wm)
            else:
                def model_call(ck, cv, tok, pos, wm):
                    return decode_step(
                        params, spec, tok, pos, ck, cv, write_mask=wm,
                        history=history, sharded=sharded)

            def sample_fn(logits, live, carry):
                if constrained:
                    keys, counts, dfa = carry
                else:
                    keys, counts = carry
                    dfa = None
                # OpenAI sampling knobs, applied per row on the f32 logits:
                # logit_bias adds; presence/frequency penalties subtract
                # based on the slot's generated-token counts.
                adj = (logits + bias_s
                       - fp_s[:, None] * counts
                       - pp_s[:, None] * (counts > 0))
                if constrained:
                    # Grammar mask: the row's current state's allow-set
                    # ([S, V] gather), with the EOS column rewritten to
                    # "allowed iff the state accepts" — EOS is the only
                    # legal move out of a completed grammar, and illegal
                    # everywhere else. Masking happens BEFORE the sampler,
                    # so temperature/top-k/top-p compose unchanged
                    # (ops/sampling.apply_token_mask) and per-row states
                    # advance on the sampled token — token after token,
                    # inside the scan, no host round-trip.
                    rowt = trans_t[dfa]                      # [S, V]
                    allow = rowt >= 0
                    eos_col = (jnp.arange(vocab)[None, :]
                               == eos_s[:, None])
                    allow = jnp.where(
                        eos_col,
                        (accept_t[dfa] & (eos_s >= 0))[:, None], allow)
                    adj = apply_token_mask(adj, allow)
                split = jax.vmap(jax.random.split)(keys)  # [S, 2, 2]
                nxt = sample_token_rows(
                    adj, split[:, 1], temp_s, topp_s, topk_s
                )
                counts = counts.at[jnp.arange(n_rows), nxt].add(
                    live.astype(jnp.int32))
                if want_lp:
                    lp_all = jax.nn.log_softmax(adj)        # [S, V]
                    s_lp = jnp.take_along_axis(
                        lp_all, nxt[:, None], axis=1)[:, 0]
                    top_lp, top_ix = lax.top_k(lp_all, n_top)  # [S, n_top]
                    aux = (s_lp, top_ix, top_lp)
                else:
                    aux = ()
                if constrained:
                    # Count masked vocab entries for live CONSTRAINED rows
                    # (dfa > 0 — grammar states start past FREE) and
                    # advance the DFA: the sampled token's transition, or
                    # stay put on EOS (the row dies via the chunk's own
                    # finish check) and for dead rows.
                    con = live & (dfa > 0)
                    masked = jnp.sum((~allow) & con[:, None],
                                     dtype=jnp.int32)
                    ndfa = jnp.take_along_axis(
                        rowt, nxt[:, None], axis=1)[:, 0]
                    dfa = jnp.where(live & (nxt != eos_s) & (ndfa >= 0),
                                    ndfa, dfa)
                    return nxt, (split[:, 0], counts, dfa), aux + (masked,)
                return nxt, (split[:, 0], counts), aux

            carry0 = ((keys_s, counts_s, dfa_s) if constrained
                      else (keys_s, counts_s))
            if n_chunks > 1:
                # Megachunk: C chunk bodies fused in one program with an
                # all-dead early exit; toks [C, B, n_steps], n_valid
                # [C, B], aux leaves [C, n_steps, ...] — the reap drains
                # the per-chunk segments in order.
                (toks, n_valid, tok_end, live_end, budget_s, ck, cv,
                 lengths_s, carry_out, aux) = decode_loop(
                    params, spec, n_steps, n_chunks, token_s, lengths_s,
                    live0, budget_s, eos_s, ck, cv, sample_fn, carry0,
                    history=history, model_call=model_call)
            else:
                (toks, _valid, n_valid, live_end, budget_s, ck, cv,
                 lengths_s, carry_out, aux) = decode_chunk(
                    params, spec, n_steps, token_s, lengths_s, live0,
                    budget_s, eos_s, ck, cv, sample_fn, carry0,
                    history=history, model_call=model_call)
                tok_end = toks[:, -1]
            if constrained:
                keys_s, counts_s, dfa_s = carry_out
            else:
                keys_s, counts_s = carry_out
            if want_lp:
                s_lp, top_ix, top_lp = aux[:3]
                if n_chunks > 1:
                    # step-major → row-major per chunk segment:
                    # [C, steps, S(, top)] → [C, S, steps(, top)]
                    lp_out = (s_lp.transpose(0, 2, 1),
                              top_ix.transpose(0, 2, 1, 3),
                              top_lp.transpose(0, 2, 1, 3))
                else:
                    lp_out = (s_lp.T, top_ix.transpose(1, 0, 2),
                              top_lp.transpose(1, 0, 2))
            else:
                lp_out = ()
            # [n_steps] int32 ([C, n_steps] megachunked — the reap sums)
            mask_out = (aux[-1],) if constrained else ()
            # Rows outside this chunk's active set keep their liveness (a
            # slot mid-admission must not be marked dead under the ring).
            live_s = jnp.where(active > 0, live_end, live_s)
            token_s = jnp.where(active > 0, tok_end, token_s)
            tail = (ck, cv, token_s, lengths_s, keys_s, counts_s,
                    live_s, budget_s)
            if constrained:
                tail = tail + (dfa_s,)
            return (toks, n_valid) + lp_out + mask_out + tail

        if constrained:
            def chunk(params, active, eos_s, trans_t, accept_t, ck, cv,
                      token_s, lengths_s, keys_s, temp_s, topp_s, topk_s,
                      pp_s, fp_s, counts_s, bias_s, live_s, budget_s, dfa_s):
                return chunk_core(
                    params, active, eos_s, ck, cv, token_s, lengths_s,
                    keys_s, temp_s, topp_s, topk_s, pp_s, fp_s, counts_s,
                    bias_s, live_s, budget_s,
                    trans_t=trans_t, accept_t=accept_t, dfa_s=dfa_s)

            return jax.jit(
                chunk,
                donate_argnames=("ck", "cv", "token_s", "lengths_s",
                                 "keys_s", "counts_s", "live_s", "budget_s",
                                 "dfa_s"),
            )
        else:
            def chunk(params, active, eos_s, ck, cv, token_s, lengths_s,
                      keys_s, temp_s, topp_s, topk_s, pp_s, fp_s, counts_s,
                      bias_s, live_s, budget_s):
                return chunk_core(
                    params, active, eos_s, ck, cv, token_s, lengths_s,
                    keys_s, temp_s, topp_s, topk_s, pp_s, fp_s, counts_s,
                    bias_s, live_s, budget_s)

            return jax.jit(
                chunk,
                donate_argnames=("ck", "cv", "token_s", "lengths_s",
                                 "keys_s", "counts_s", "live_s", "budget_s"),
            )

    # ---- public API -------------------------------------------------------

    def generate_stream(
        self,
        prompt_ids: list[int],
        *,
        max_new_tokens: int = 64,
        sampler: SamplerConfig | None = None,
        seed: int = 0,
        eos_id: int | None = None,
        cancel: threading.Event | None = None,
        decode_chunk: int | None = None,
        member: int = 0,
    ) -> Iterator[int]:
        """Yield generated token ids as the scheduler produces them (the EOS
        token, when hit, is the last id yielded). Stops at EOS,
        max_new_tokens, context exhaustion, or when ``cancel`` is set
        (honored at the next chunk boundary). ``decode_chunk`` is a latency
        hint: the scheduler chunks by the smallest hint among active
        requests. Abandoning the iterator early cancels the request's
        remaining device work."""
        req = self.submit(
            prompt_ids,
            max_new_tokens=max_new_tokens,
            sampler=sampler,
            seed=seed,
            eos_id=eos_id,
            cancel=cancel,
            decode_chunk=decode_chunk,
            member=member,
        )
        yield from self.stream_results(req)

    def submit(
        self,
        prompt_ids: list[int],
        *,
        max_new_tokens: int = 64,
        sampler: SamplerConfig | None = None,
        seed: int = 0,
        eos_id: int | None = None,
        cancel: threading.Event | None = None,
        decode_chunk: int | None = None,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        logit_bias: "np.ndarray | None" = None,  # [vocab] f32 additive bias
        logprobs: int = -1,  # ≥ 0 → record per-token logprobs + that many tops
        member: int = 0,  # stacked-members engine: which weight set serves this
        deadline: float | None = None,  # absolute time.monotonic() deadline
        grammar=None,  # CompiledGrammar: constrained decoding (structured output)
        priority: str | None = None,  # dispatch class (sched.PRIORITY_CLASSES)
        tenant: str | None = None,  # tenant id for weighted-fair admission
        resume_tokens: "list[int] | None" = None,  # already-delivered ids to replay
    ) -> _Request | None:
        """Enqueue a generation and return its handle (``None`` when there is
        nothing to generate). Raises :class:`QueueFullError` *synchronously*
        when the admission queue is at capacity, and
        :class:`EngineBreakerOpen` while the failure breaker rejects new
        admissions — callers can reject the
        request (e.g. with a 503) before committing to a response stream.
        ``deadline`` bounds the request's whole life: pending past it is
        shed (stage ``queue``), admitted past it is cancelled with a
        :class:`DeadlineExceeded` error frame (stage ``prefill``/``decode``).
        Consume tokens with :meth:`stream_results`; when ``logprobs`` ≥ 0 the
        handle's ``lp`` list carries one ``(logprob, top_ids, top_lps)``
        record per yielded token. Penalties follow the OpenAI contract
        (presence: flat once a token has been generated; frequency: scaled
        by its count), applied over this request's generated tokens.
        ``priority`` pins the QoS dispatch class (one of
        ``sched.PRIORITY_CLASSES``; default: derived from deadline headroom)
        and ``tenant`` names the weighted-fair accounting bucket — both
        inert unless the engine was built with ``qos=True``.

        ``resume_tokens`` resumes a stream another engine already served
        part of (docs/robustness.md "Zero-loss streams"): the ids ride the
        PR 18 replay guard — the request admits ordinarily (prefix-store /
        tier-0 reuse makes the replay cheap), regenerates the delivered
        prefix deterministically from (prompt, seed, sampler), and
        ``_emit`` byte-compares + swallows each replayed token before any
        new token reaches the consumer. A mismatch fails the stream with
        :class:`ReplayDivergence` — never a silent fork."""
        return self._submit(
            prompt_ids,
            max_new_tokens=max_new_tokens,
            sampler=sampler or SamplerConfig(),
            seed=seed,
            eos_id=eos_id,
            cancel=cancel,
            decode_chunk=decode_chunk,
            pp=presence_penalty,
            fp=frequency_penalty,
            bias_row=logit_bias,
            want_lp=logprobs,
            member=member,
            deadline=deadline,
            grammar=grammar,
            priority=priority,
            tenant=tenant,
            resume_tokens=resume_tokens,
        )

    def stream_results(self, req: _Request | None) -> Iterator[int]:
        """Yield a submitted request's tokens as the scheduler produces them."""
        if req is None:
            return
        try:
            while True:
                kind, val = req.out.get()
                if kind == "tok":
                    yield val
                elif kind == "end":
                    return
                else:
                    raise val
        finally:
            # Consumer gone (or done): release the slot at the next boundary.
            req.cancel.set()
            # First completed request = the process is warm; later XLA
            # compiles land on quorum_tpu_recompiles_total (idempotent).
            compile_watch.mark_warm()

    def generate(
        self,
        prompt_ids: list[int],
        *,
        max_new_tokens: int = 64,
        sampler: SamplerConfig | None = None,
        seed: int = 0,
        eos_id: int | None = None,
        member: int = 0,
    ) -> GenerationResult:
        out = GenerationResult()
        for t in self.generate_stream(
            prompt_ids,
            max_new_tokens=max_new_tokens,
            sampler=sampler,
            seed=seed,
            eos_id=eos_id,
            member=member,
        ):
            out.token_ids.append(t)
        if eos_id is not None and out.token_ids and out.token_ids[-1] == eos_id:
            out.token_ids.pop()
            out.finish_reason = "stop"
        return out

    # ---- scheduler --------------------------------------------------------

    def _submit(self, prompt_ids, *, max_new_tokens, sampler, seed, eos_id,
                cancel, decode_chunk, pp=0.0, fp=0.0, bias_row=None,
                want_lp=-1, member=0, deadline=None,
                grammar=None, priority=None, tenant=None,
                resume_tokens=None) -> _Request | None:
        spec = self.spec
        if not 0 <= member < self.members:
            raise ValueError(
                f"member {member} out of range for a {self.members}-member "
                "engine")
        if priority is not None and priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"priority must be one of {PRIORITY_CLASSES}, "
                f"got {priority!r}")
        if self.draining:
            # The drain gate: a draining engine admits nothing new — the
            # 503 this raises is exactly the pre-first-byte failure the
            # router fails over, so traffic moves to siblings on its own.
            err = QueueFullError("engine draining")
            err.retry_after = 1.0
            raise err
        if grammar is not None:
            # Constrained decoding preconditions, checked synchronously so a
            # misconfiguration is a clean rejection, not a wedged stream:
            # the grammar's terminal states emit by forcing EOS, and the
            # first token must be sampled by a masked decode chunk — which
            # means the admission rides the chunked-prefill register path.
            if eos_id is None:
                raise ValueError(
                    "constrained decoding requires an EOS id: grammar "
                    "completion finishes the row by forcing EOS on device")
            if self.prefill_chunk <= 0:
                raise ValueError(
                    "constrained decoding requires chunked prefill "
                    "(prefill_chunk >= 16 after power-of-two alignment): "
                    "the first constrained token is sampled by a masked "
                    "decode chunk, not inside the single-shot admit "
                    "program — unavailable with sp>1 or prefill_chunk=0")
            if grammar.vocab_size != spec.vocab_size:
                raise ValueError(
                    f"grammar compiled for vocab {grammar.vocab_size} does "
                    f"not match the model vocab {spec.vocab_size}")
        # Keep the most recent context if the prompt exceeds the window,
        # reserving at least one position to generate into.
        prompt = list(prompt_ids)[-(spec.max_seq - 1):]
        if not prompt:
            prompt = [0]
        budget = min(max_new_tokens, spec.max_seq - len(prompt))
        if budget <= 0 or (cancel is not None and cancel.is_set()):
            return None
        replay: "list[int] | None" = None
        if resume_tokens:
            # Cross-replica resume (docs/robustness.md): the delivered ids
            # become the replay expectation — same guard, same swallow path
            # as a preemption resume. Checked synchronously so a bad
            # journal is a clean rejection, not a wedged stream.
            replay = [int(t) for t in resume_tokens]
            if any(not 0 <= t < spec.vocab_size for t in replay):
                raise ValueError(
                    "resume_tokens contains out-of-vocabulary ids")
            if len(replay) > budget:
                raise ValueError(
                    f"resume_tokens longer ({len(replay)}) than the "
                    f"generation budget ({budget})")
        req = _Request(
            prompt, budget, sampler, seed, eos_id,
            cancel if cancel is not None else threading.Event(),
            decode_chunk,
            pp=pp, fp=fp, bias_row=bias_row, want_lp=want_lp, member=member,
            deadline=deadline, grammar=grammar, priority=priority,
            tenant=tenant,
        )
        if replay:
            # Resume admission: the journal ids are replayed token-for-token
            # through ordinary decode — _emit's replay guard byte-compares
            # and swallows each regenerated token (PR 18 machinery), so the
            # client stream picks up exactly where it died.
            req.replay = replay
        now = time.monotonic()
        req.sched_class = self._policy.classify(priority, deadline, now)
        # Every shed decision — deadline-expired, breaker, queue capacity,
        # pool span, and (qos) the predictive infeasible-deadline shed —
        # routes through the cost model: ONE decision point, one
        # Retry-After heuristic (docs/scheduling.md).
        shed = self.cost_model.presubmit(now=now, deadline=deadline,
                                         breaker=self.breaker)
        if shed is not None:
            self._raise_shed(shed)
        with self._cond:
            if self._stop:
                raise RuntimeError("engine has been shut down")
            shed = self.cost_model.queue_check(
                now=now, deadline=deadline, n_pending=len(self._pending),
                max_pending=self.max_pending, qos=self.qos,
                page_need=(self._paged_need(len(prompt), budget)
                           if self.kv_pages else 0),
                pool_pages=self.kv_pool_pages if self.kv_pages else 0)
            if shed is not None:
                # _cond is an RLock underneath — _raise_shed's counter bump
                # re-enters it safely.
                self._raise_shed(shed)
            self._pending.append(req)
            self.n_requests += 1
            # notify_all: under disagg TWO scheduler loops wait on _cond,
            # and waking only one could leave the admission loop asleep.
            self._cond.notify_all()
        return req

    def _raise_shed(self, shed) -> None:
        """Map a cost-model :class:`~quorum_tpu.sched.ShedDecision` onto the
        engine's exception contract. Deadline sheds count/stage exactly like
        the pre-QoS inline check (stage ``queue`` — the engine never served
        the request); capacity sheds carry the model's Retry-After hint on
        the exception for the HTTP layer."""
        if shed.kind == "deadline":
            # The counter bump takes _cond: this path runs on arbitrary
            # caller threads, racing the scheduler's own increments.
            with self._cond:
                self.n_deadline_exceeded += 1
            obs.DEADLINE_EXCEEDED.inc(stage="queue")
            raise DeadlineExceeded("queue")
        if shed.kind == "breaker":
            raise EngineBreakerOpen(shed.retry_after)
        err = QueueFullError(shed.detail)
        err.retry_after = shed.retry_after
        raise err

    # ---- a patterned spec's expert counters ---------------------------------
    #
    # The expert layers count on the device, into an int32 array that rides
    # the K side of the cache through every program (models/patterned.py), so
    # no program has an output more. The scheduler copies the array after a
    # decode dispatch or a single-shot admit (a program of a few hundred
    # bytes, never donated) and reads the copy with the tokens it fetches
    # anyway; /metrics reads the host's running totals and never the device.

    @_program("_util_fns", lambda self: "moe_snapshot", kept=True)
    def _moe_snapshot_fn(self):
        return jax.jit(lambda a: a + 0)

    def _moe_snapshot(self):
        if not self.spec.layer_pattern:
            return None
        self._moe_seq += 1
        counts = self._moe_snapshot_fn()(self._ck.stats)
        return self._moe_seq, counts, self._sent(OTHER, "snapshot")

    def _moe_note(self, snapshot) -> "int | None":
        """Add a fetched snapshot to the totals; returns the picks that
        fell on experts held here since the snapshot noted before (int32
        counts wrap: the difference is taken modulo 2**32). A single-shot
        admit reads its snapshot at once, ahead of the chunks dispatched
        before it: theirs, older and already counted, give None."""
        seq, counts, _ = snapshot
        if seq < self._moe_noted:
            return None
        self._moe_noted = seq
        now = np.asarray(_host_fetch(counts)).astype(np.int64) & 0xFFFFFFFF
        rise = (now if self._moe_last is None
                else (now - self._moe_last) % (1 << 32))
        self._moe_last = now
        self._moe_total = rise if self._moe_total is None \
            else self._moe_total + rise
        return int(rise[:, :self.spec.held].sum())

    def _moe_metrics(self) -> dict:
        held = self.spec.held if self.spec.layer_pattern else 0
        names = moe_stats_of(self.spec)
        total = self._moe_total
        if total is None:
            total = np.zeros((0, held + len(names)), np.int64)
        first = self.spec.first_dense
        per_expert, rest = total[:, :held], total[:, held:]
        return {
            # where full layers select what they attend: the positions their
            # queries attended and the positions their histories held
            **{f"dsa_{name}_total": int(rest[:, names.index(name)].sum())
               for name in names[len(MOE_STATS):]},
            "moe_picks_total": int(rest[:, MOE_STATS.index("picks")].sum()),
            "moe_picks_held_total": int(per_expert.sum()),
            # picks on a held expert that no product computed: held picks
            # less the rows the grouped products say they took
            "moe_dropped_picks_total": int(
                rest[:, MOE_STATS.index("dropped")].sum()),
            # rows the expert products multiplied: tiles x 128 on the
            # grouped path, held experts x counted rows on the dense one
            "moe_tile_rows_total": int(
                rest[:, MOE_STATS.index("tile_rows")].sum()),
            # per layer the most-picked held expert's count, summed over the
            # layers: over moe_picks_held_total / experts held it is how
            # uneven the load on this chip's experts is
            "moe_busiest_expert_picks_total": int(
                per_expert.max(axis=1).sum()) if per_expert.size else 0,
            # {labels: count}: one sample per expert layer and held expert
            "moe_expert_picks_total": {
                f'layer="{first + l}",expert="{self.spec.expert_first + e}"':
                    int(n)
                for l, row in enumerate(per_expert)
                for e, n in enumerate(row)},
            "moe_experts_held": held,
            **{f"kv_cache_{kind}_bytes": n
               for kind, n in self._kv_cache_bytes().items()},
        }

    def _keys_kept(self, n_prompt: int) -> dict:
        """A ``prefill`` span's ``keys_kept_share``, where full layers select
        what they attend: of the (query, earlier position) pairs of a prompt
        of ``n_prompt``, the percentage a full layer attends, by the spec's
        ``index_topk`` (the device's own counts are ``dsa_keys_*_total``)."""
        k = self.spec.index_topk
        if not k or n_prompt <= 0:
            return {}
        whole = min(n_prompt, k)
        kept = whole * (whole + 1) // 2 + (n_prompt - whole) * k
        return {"keys_kept_share": round(
            100.0 * kept / (n_prompt * (n_prompt + 1) // 2), 2)}

    def _state_carried(self, carried: bool) -> dict:
        """A ``prefill`` span's ``state_carried`` and ``state_bytes``, where
        a row holds a state: whether it went from one program to the next
        on the way (a chunked admission's segments and its register's
        decode step; a single-shot admit makes it in one program), and the
        bytes of state the row holds."""
        if not self.spec.row_state:
            return {}
        return {"state_carried": carried,
                "state_bytes": self._state_row_bytes}

    def _kv_cache_bytes(self) -> dict:
        """Bytes of the slot cache by layer kind, from the arrays the engine
        holds (a donated array still says its shape): a spec without a
        pattern has full layers only; ``index`` is the index keys a full
        layer keeps beside its latent rows where it selects what it attends
        (models/latent.py)."""
        def nbytes(tree) -> int:
            return sum(a.nbytes for a in jax.tree.leaves(tree))

        ck, cv = getattr(self, "_ck", None), getattr(self, "_cv", None)
        if isinstance(ck, KindKV):
            # ``state``: the short convolutions' tails, where there are any
            return {"full": nbytes(ck.full) + nbytes(cv.full),
                    "window": nbytes(ck.window) + nbytes(cv.window),
                    "index": nbytes(ck.index),
                    **({"state": nbytes(ck.conv)} if ck.conv else {})}
        if isinstance(ck, StateKV):
            # ``state``: the mixer's state and convolution tail, per layer
            # and row, beside the K and V rectangles
            return {"full": nbytes(ck.kv) + nbytes(cv.kv), "window": 0,
                    "index": 0,
                    "state": nbytes(ck.carry) + nbytes(cv.carry)}
        return {"full": nbytes(ck) + nbytes(cv), "window": 0, "index": 0}

    def metrics(self) -> dict:
        """Scheduler/capacity snapshot for the server's /metrics endpoint."""
        with self._cond:
            busy = sum(1 for r in self._slots if r is not None)
            return {
                "slots": self._rows,
                "members": self.members,
                "busy_slots": busy,
                "admitting": len(self._admitting),
                "pending": len(self._pending),
                "queue_limit": self.max_pending,
                "requests_total": self.n_requests,
                "tokens_total": self.n_tokens,
                "failures_total": self.n_failures,
                "cancellations_total": self.n_cancelled,
                "decode_chunks_total": self.n_decode_chunks,
                "decode_busy_rows_total": self.n_decode_rows,
                "decode_kv_tiles_read_total": self.n_kv_tiles_read,
                "decode_kv_tiles_bucket_total": self.n_kv_tiles_bucket,
                **({"ssm_state_rows_stepped_total": self.n_ssm_rows_stepped,
                    "ssm_state_rows_live_total": self.n_ssm_rows_live}
                   if self.spec.ssm_heads else {}),
                "prefix_hits_total": self.prefix_hits,
                "prefix_tokens_saved_total": self.prefix_tokens_saved,
                "prefix_store_hits_total": self.prefix_store_hits,
                "prefix_store_restored_tokens_total":
                    self.prefix_store_tokens_restored,
                "prefix_store_snapshots_dropped_total":
                    self.prefix_store_snapshots_dropped,
                "prefix_store_evictions_total": (
                    self.prefix_store.n_evictions
                    if self.prefix_store is not None else 0),
                "prefix_store_bytes": (
                    self.prefix_store.bytes_held
                    if self.prefix_store is not None else 0),
                "prefix_store_entries": (
                    self.prefix_store.n_entries
                    if self.prefix_store is not None else 0),
                "overlapped_chunks_total": self.n_overlapped,
                "overrun_tokens_total": self.n_overrun,
                "constrained_requests_total": self.n_constrained,
                "constrain_masked_tokens_total": self.n_constrain_masked,
                "decode_pipeline": self.decode_pipeline,
                "decode_loop": self.decode_loop,
                "decode_loop_chunks_total": self.n_loop_chunks,
                "drain_gap_seconds_total": round(self.drain_gap_s, 6),
                "inflight_chunks": len(self._inflight),
                # Disaggregated serving (0s when colocated): per-group
                # device counts and occupancy, plus the device↔device KV
                # handoff accounting (quorum_tpu/cache/kv_transfer.py).
                "disagg": 1 if self.disagg else 0,
                "prefill_sp": self.prefill_sp,
                "prefill_group_devices": (
                    int(self.prefill_mesh.devices.size) if self.disagg else 0),
                "decode_group_devices": (
                    int(self.mesh.devices.size) if self.disagg else 0),
                "prefill_group_active": (
                    len(self._admitting) if self.disagg else 0),
                "decode_group_active": busy if self.disagg else 0,
                "kv_handoffs_total": self.n_kv_handoffs,
                "kv_handoff_bytes_total": self.kv_handoff_bytes,
                "kv_handoff_seconds_total": round(self.kv_handoff_s, 6),
                # Zero-drain continuous batching (tpu://…&zero_drain=1):
                # staged-injection admissions that registered onto a
                # non-empty ring, and wall time the ring spent clamped to
                # depth 1 for admissions (structurally 0 with zero_drain).
                "zero_drain": 1 if self.zero_drain else 0,
                "admission_overlap_total": self.n_admission_overlap,
                "admission_stall_seconds_total": round(
                    self.admission_stall_s, 6),
                "rebuilds_total": self.n_rebuilds,
                "deadline_exceeded_total": self.n_deadline_exceeded,
                "breaker_state": self.breaker.state_code,
                # Paged KV slot memory (tpu://…&kv_pages=1): pool occupancy
                # and the prefix-aliasing economics — tier-0 hits that
                # installed page REFERENCES instead of copying bytes, and
                # the boundary pages that did get a COW copy.
                "kv_pages": 1 if self.kv_pages else 0,
                "kv_page_size": self.kv_page_size,
                "kv_pages_allocated": (
                    self._page_alloc.allocated_pages if self.kv_pages else 0),
                "kv_pages_free": (
                    self._page_alloc.free_pages if self.kv_pages else 0),
                "kv_page_alias_hits_total": (
                    self.kv_page_alias_hits if self.kv_pages else 0),
                "kv_page_cow_copies_total": (
                    self.kv_page_cow_copies if self.kv_pages else 0),
                # QoS scheduler (tpu://…&qos=1): mid-decode preemptions,
                # the delivered tokens they parked (regenerated on resume),
                # the regenerated tokens the replay guard swallowed, and
                # the cost model's predictive infeasible-deadline sheds.
                "qos": 1 if self.qos else 0,
                "preemptions_total": self.n_preemptions,
                "preempted_tokens_total": self.n_preempted_tokens,
                "replayed_tokens_total": self.n_replayed_tokens,
                "predictive_sheds_total": self.cost_model.n_predictive_sheds,
                # Drain lifecycle (ISSUE 19 / docs/robustness.md): whether
                # admissions are gated shut, and how many resident streams
                # drain-with-park retired with a ``parked`` finish (each
                # one a router-side proactive resume on a sibling).
                "draining": 1 if self.draining else 0,
                "drain_parked_total": self.n_drain_parked,
                # Prefill programs as dispatched, and what chunked
                # admissions' prefill spans waited for (_segment_dispatch).
                "prefill_tokens_total": self.n_prefill_tokens,
                "prefill_padded_tokens_total": self.n_prefill_padded,
                "prefill_segments_total": self.n_prefill_segments,
                "prefill_segment_turns_total": self.n_prefill_segment_turns,
                "prefill_span_seconds_total": round(self.prefill_span_s, 6),
                "prefill_decode_wait_seconds_total": round(
                    self.prefill_decode_wait_s, 6),
                "prefill_own_seconds_total": round(self.prefill_own_s, 6),
                "prefill_peer_seconds_total": round(self.prefill_peer_s, 6),
                "stalls_total": self.n_stalls,
                # Programs loaded from the store ahead of any request
                # (engine/prepare.py), first dispatches that built their
                # own program, and the wall clock of the loads at their end.
                "programs_prepared_total": (
                    self._prep.loaded if self._prep else 0),
                "programs_on_demand_total": self.n_programs_on_demand,
                "prepare_seconds": round(
                    self._prep.seconds if self._prep else 0.0, 3),
                **device_families(self._ledger, self._prefill_ledger),
                # The scheduler turn by phase (_phase), open phases counted
                # up to now: between two scrapes the phases of a colocated
                # engine add up to the wall time between them.
                **{f"turn_{name}_seconds_total": round(seconds, 6)
                   for name, seconds in self._turn_seconds().items()},
                **self._moe_metrics(),
            }

    def health(self) -> dict:
        """Liveness/capacity signals for the server's /health and /ready:
        every field is a real observation (thread liveness, breaker state,
        queue depth), never a hardcoded OK — a load balancer must be able to
        rotate a process whose scheduler died out of service."""
        with self._cond:
            pending = len(self._pending)
            stopped = self._stop
        return {
            "scheduler_alive": self._thread.is_alive() and not stopped,
            # Group-aware liveness (docs/tpu_backends.md): under disagg the
            # engine serves only while BOTH cooperating loops run — a dead
            # decode loop must not hide behind a live prefill loop (or vice
            # versa). True structurally when colocated (one loop).
            "prefill_scheduler_alive": (
                not self.disagg
                or (self._prefill_thread.is_alive() and not stopped)),
            "snapshot_worker_alive": (
                self.prefix_store is None or self._snap_thread.is_alive()),
            "breaker": self.breaker.state,
            "pending": pending,
            "queue_limit": self.max_pending,
            "rebuilds_total": self.n_rebuilds,
            **self.device_report,
            "kv_cache_bytes": self._kv_cache_bytes(),
            # A draining engine still answers /health but must shed
            # /ready: the fleet rotates it out while residents finish.
            "draining": self.draining,
            # Stored programs still being loaded: /ready waits.
            "programs_preparing": self.programs_preparing,
        }

    def shutdown(self, timeout: float = 30.0) -> None:
        """Stop the scheduler thread and release device state.

        Pending/active requests are cancelled (their consumers see end within
        one chunk boundary); the thread is joined, then the weights and slot
        cache are dropped so a shut-down engine holds no HBM. Used by server
        teardown and by the test suite's per-module cleanup — dozens of live
        scheduler threads executing stray device work while the next test
        compiles is exactly the kind of concurrency XLA's CPU client is not
        hardened against.
        """
        with self._cond:
            self._stop = True
            for r in self._slots:
                if r is not None:
                    r.cancel.set()
            for a in self._admitting:
                a.req.cancel.set()
            for r in self._pending:
                r.cancel.set()
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        if self._prefill_thread is not None:
            self._prefill_thread.join(timeout=timeout)
        if self._prep is not None:
            # nothing more is loaded; what the first dispatches built is
            # written to the program store before the process may go
            self._prep.close()
        if self.stream_pool is not None:
            # the backends' producer threads (tpu_backend._stream_pool):
            # their streams have just ended
            self.stream_pool.shutdown(wait=False)
            self.stream_pool = None
        if self.prefix_store is not None:
            # Stop the snapshot worker (sentinel after any queued fetches)
            # and release the host copies with the device state below.
            self._snap_queue.put(None)
            self._snap_thread.join(timeout=timeout)
            self.prefix_store.clear()
        if self._thread.is_alive() or (
                self._prefill_thread is not None
                and self._prefill_thread.is_alive()):
            # A dispatch (e.g. a long XLA compile) is still in flight: do
            # NOT null the state under it — the thread exits at its next
            # scheduler-loop boundary and the GC reclaims everything then.
            return
        self.weights = None
        self._ck = self._cv = None
        if self.staged:
            self.prefill_params = None
            self._sck = self._scv = None
            # Both loops have exited (checked above), but the guarded-by
            # contract is lexical: queue mutations hold the lock, period.
            with self._cond:
                self._handoffs.clear()

    def _scheduler(self) -> None:
        # Under disagg this loop is the DECODE group's: admissions and
        # prefill segments belong to _prefill_scheduler, and the only
        # admission work here is draining the handoff queue (slot writes +
        # registers — all decode-cache mutation stays on this one thread).
        while True:
            with self._cond:
                while not (self._stop
                           or (not self.disagg
                               and (self._pending or self._admitting))
                           or any(self._slots) or self._inflight
                           or self._pending_snaps or self._handoffs):
                    if self.disagg:
                        # Going idle: the occupancy gauge must read the
                        # truth ("right now"), not the last reaped chunk's
                        # batch size.
                        obs.DECODE_GROUP_ACTIVE.set(
                            sum(1 for r in self._slots if r is not None))
                    with self._phase("idle"):
                        self._cond.wait()
                if self._stop and not (
                    (not self.disagg
                     and (self._pending or self._admitting))
                    or any(self._slots)
                    or self._inflight or self._pending_snaps
                ):
                    # _pending_snaps blocks the exit: leaving deferred
                    # snapshots undispatched would strand _snap_backlog > 0
                    # and hang any concurrent drain_prefix_store() forever.
                    # Queued handoff pieces are safe to drop — their
                    # admissions were ended by the prefill loop's own exit.
                    self._handoffs.clear()
                    return
            self.n_turns += 1
            try:
                with self._phase("sweep"):
                    self._sweep_deadlines()
                    self._sweep_preemptions()
                    self._sweep_drain_parks()
                with self._phase("admit"):
                    if self.disagg:
                        # The deferred decode-side state work the colocated
                        # loop runs inside _start_admissions.
                        self._flush_dfa_resets()
                        self._maybe_reset_arena()
                        self._dispatch_snapshots()
                        self._drain_handoffs()
                    else:
                        self._start_admissions()
                        self._step_admissions()
                        if self.zero_drain:
                            # Reap-boundary injection: staged pieces write
                            # into their claimed slots (chained behind the
                            # in-flight ring, never draining it) and
                            # fully-staged admissions register — the row
                            # joins the batch at the very next ring fill.
                            self._drain_handoffs()
                if any(self._slots) or self._inflight:
                    self._run_chunk()
                else:
                    # No decode work this turn (the clamped stream finished
                    # and/or the admission retired without activating):
                    # discard any dangling clamp stamp NOW — _run_chunk's
                    # own discard sites never run again before the loop
                    # sleeps, and the next burst's first clamped turn would
                    # otherwise book the whole idle gap as admission stall.
                    self._note_admission_clamp(False)
            except Exception as e:  # fail open: wake every waiting consumer
                try:
                    self._fail_all(e)
                except Exception:
                    # Device-state rebuild failed too (e.g. persistent OOM).
                    # Keep the scheduler alive: waiting consumers were already
                    # failed or will fail fast on their next admission.
                    pass

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One part of a scheduler turn (TURN_PHASES), timed twice over: its
        self time lands in the table metrics() exports, and a running
        profile gets an ``engine.<name>`` annotation on the profiler's own
        clock (a flag test when none runs). A phase opened inside another
        suspends it, so the phases of a loop add up to its wall time."""
        self._phase_switch(name)
        try:
            with jax.profiler.TraceAnnotation("engine." + name):
                yield
        finally:
            self._phase_switch(None)

    def _phase_switch(self, push: "str | None") -> None:
        """Book the calling loop's time since its last switch to the phase
        it had open, then open ``push`` inside it or (None) close it. Backend
        compile seconds the thread spent in the slice (compile_watch) go to
        ``compile``, not to the phase that met the new shape."""
        with self._turn_lock:
            st = self._phase_open.setdefault(
                threading.get_ident(), {"open": [], "t": 0.0, "built": 0.0})
            now = time.perf_counter()
            built = compile_watch.thread_seconds()
            if st["open"]:
                dt = now - st["t"]
                dc = min(dt, built - st["built"])
                self._turn_s[st["open"][-1]] += dt - dc
                self._turn_s["compile"] += dc
            st["t"], st["built"] = now, built
            if push is None:
                st["open"].pop()
            else:
                st["open"].append(push)
            self._led().switch(st["open"][-1] if st["open"] else None, now)

    def _turn_seconds(self) -> dict:
        """The phase table with every open phase counted up to now."""
        with self._turn_lock:
            out = dict(self._turn_s)
            now = time.perf_counter()
            for st in self._phase_open.values():
                if st["open"]:
                    out[st["open"][-1]] += now - st["t"]
        return out

    def _prefill_dispatch(self, family: str, bucket: int, tokens: int,
                          rows: int = 1):
        """Count one prefill program execution: the prompt ``tokens`` it was
        asked to compute, and tokens as the program computes them (``rows`` x
        ``bucket``, the padding and absent members included: its weight in
        the device ledger too). Returns the ``engine.dispatch`` annotation
        that names the call in a running profile; use as ``with``."""
        self.n_prefill_tokens += tokens
        self.n_prefill_padded += rows * bucket
        return jax.profiler.TraceAnnotation("engine.dispatch", family=family,
                                            bucket=bucket)

    @contextlib.contextmanager
    def _segment_dispatch(self, adms, family: str, bucket: int, tokens: int,
                          rows: int = 1):
        """:meth:`_prefill_dispatch` for a segment program advancing the
        chunked admissions ``adms``, entered in the ledger where the call
        has returned; at an admission's first segment the loop's turn count
        is noted for its span."""
        self.n_prefill_segments += 1
        for adm in adms:
            if adm.segments == 0:
                adm.turn0 = self.n_turns
            adm.segments += 1
        with self._prefill_dispatch(family, bucket, tokens, rows):
            yield
        self._sent(PREFILL, family, bucket, rows * bucket,
                   [adm.acct for adm in adms])

    # ---- the device ledger (telemetry/device_ledger.py) ----------------------

    def _led(self) -> DeviceLedger:
        """The calling loop's ledger: a disagg engine's prefill loop hands
        its programs to a device group of its own."""
        if (self._prefill_ledger is not None
                and threading.current_thread() is self._prefill_thread):
            return self._prefill_ledger
        return self._ledger

    def _sent(self, cls: str, family: str, bucket: int = 0, weight: int = 0,
              accts=(), witness=None):
        """Enter a program the calling loop has just dispatched in its
        ledger. The device standing dry before it, with the loop neither
        idle nor compiling, for longer than a stall is the witness's."""
        prog = self._led().dispatch(cls, family, bucket, weight,
                                    [a for a in accts if a is not None],
                                    witness)
        if prog.starved_before > STALL_MIN_S:
            self._note_stall("starved", prog.starved_before, family, bucket)
        return prog

    def _fetch_landing(self, prog, *arrays):
        """The blocking fetch of ``arrays``, outputs of the programs up to
        ``prog``, as a landing: what had a witness queued ahead is waited
        on first and lands on its own (DeviceLedger.wait_before); then the
        arrays are waited on until ready, which is ``prog``'s landing (one
        that finds them already in is a late one), and fetched: the host's
        copy and wake-up after the program's end are the device's dry
        time, not the program's. A wait past the stall limits is the
        witness's."""
        t_wait = time.perf_counter()
        prog.ledger.wait_before(prog, _block)
        late = all(x.is_ready() for x in arrays if isinstance(x, jax.Array))
        if not late:
            for x in arrays:  # the copies start behind the program, as the
                if isinstance(x, jax.Array) and x.is_fully_addressable:
                    x.copy_to_host_async()  # fetch alone would start them
            _block(arrays)
        prog.land(time.perf_counter(), exact=not late)
        out = _host_fetch(*arrays)
        waited = time.perf_counter() - t_wait
        if waited > STALL_MIN_S:
            self._note_stall("wait", waited, prog.family, prog.bucket)
        return out

    def _mark(self) -> None:
        """Ahead of a decode dispatch with prefill programs queued since
        the last landing: a program of a few bytes that reads the cache
        they wrote, never donated, so that the blocking reap can wait on it
        first and give prefill and decode an interval each
        (DeviceLedger.wait_before). A staged engine's segments write
        another cache: none there."""
        if self.staged:
            return
        for prog in reversed(self._led().pending):
            if prog.cls == PREFILL:
                break
            if prog.cls == DECODE or prog.family == "mark":
                return
        else:
            return
        leaf = jax.tree.leaves(self._ck)[0]
        self._sent(OTHER, "mark", witness=self._mark_fn()(leaf))

    @_program("_util_fns", lambda self: "ledger_mark", kept=True)
    def _mark_fn(self):
        return jax.jit(lambda a: a[(0,) * a.ndim] + 0)

    def _booked(self, prog) -> None:
        """A landing booked ``prog`` its seconds: the per-family latency
        model and histogram, and the recorder's ``reap`` event for a prefill
        program, or one that is neither and had an interval to itself (a
        ring entry has _deliver_chunk's)."""
        self.latency.observe(prog.family, prog.seconds)
        obs.DISPATCH_DEVICE_SECONDS.observe(prog.seconds, family=prog.family)
        if prog.cls == PREFILL or (prog.cls == OTHER and prog.seconds):
            FLIGHT.record("reap", engine=self._tag,
                          loop=("prefill" if prog.ledger
                                is self._prefill_ledger else "decode"),
                          family=prog.family, bucket=prog.bucket,
                          t_issue=round(prog.t, 6), t_start=round(prog.t0, 6),
                          t_ready=round(prog.t1, 6),
                          booked_s=round(prog.seconds, 6))

    def _note_stall(self, what: str, seconds: float, family: str,
                    bucket: int) -> None:
        """The stall witness: ``seconds`` of one blocking wait on a landing
        (``what`` "wait"), or of the device standing dry with the loop
        neither idle nor compiling ("starved"), past STALL_MIN_S and
        STALL_MEDIANS times the family's booked median."""
        median_ms = self.latency.snapshot().get(family, {}).get("p50_ms", 0.0)
        if seconds <= max(STALL_MIN_S, STALL_MEDIANS * median_ms / 1e3):
            return
        self.n_stalls += 1
        led = self._led()
        logger.warning(
            "engine stall: %s %.3f s (phase %s, family %s, bucket %s, "
            "%d rows live, ring depth %d)", what, seconds, led.phase, family,
            bucket, len(self._active_rows()), len(self._inflight))
        FLIGHT.record("stall", engine=self._tag, what=what,
                      seconds=round(seconds, 6), phase=led.phase,
                      family=family, bucket=bucket)
        FLIGHT.dump("stall")

    def _open_admission(self, req: _Request, slot: int, **kw) -> _Admission:
        """A chunked admission, its ``prefill`` span's account opened in
        the calling loop's ledger at the slot claim."""
        adm = _Admission(req, slot, **kw)
        adm.acct = self._led().open(adm.t_start)
        return adm

    # Individual scheduler-turn spans recorded per request per kind before
    # coalescing kicks in: a multi-thousand-token generation must not fill
    # the trace's MAX_SPANS budget with identical decode entries (the
    # aggregate/sse-flush spans recorded at stream end still need room).
    TURN_SPAN_CAP = 32

    def _turn_span(self, req: _Request, name: str, t0: float, t1: float,
                   **meta) -> None:
        """Record one scheduler turn (a decode chunk) on the
        request's trace; past TURN_SPAN_CAP turns of a kind, extend that
        kind's last span (summing steps, counting the coalesced
        turns) instead of appending."""
        trace = req.trace
        if trace is None:
            return
        span, count = req.tspans.get(name, (None, 0))
        count += 1
        if span is not None and count > self.TURN_SPAN_CAP:
            span.end = trace.rel(t1)
            if "steps" in meta and isinstance(span.meta.get("steps"), int):
                span.meta["steps"] += meta["steps"]
            if "occupancy" in meta:
                span.meta["occupancy"] = max(
                    span.meta.get("occupancy", 0), meta["occupancy"])
            span.meta["coalesced_turns"] = count - self.TURN_SPAN_CAP + 1
        else:
            span = req.span(name, t0, t1, **meta)
        req.tspans[name] = (span, count)

    def _note_admitted(self, req: _Request) -> None:
        """A pending request just claimed a slot: close its queue-wait —
        the histogram observation plus (when the request is traced) the
        queue-wait span, tagged with the member whose rows it landed on —
        and record the admission on the flight recorder (under disagg this
        runs on the PREFILL loop; the rid correlates it with the decode
        loop's register/reap events)."""
        now = time.perf_counter()
        req.t_admit = now
        req.stamp("admit_s", now)
        if req.n_preempts == 0:
            # A resumed victim's submit→admit gap includes its previous
            # service time — not a queue wait; keep it out of the histogram
            # and the cost model's drain estimate.
            obs.QUEUE_WAIT.observe(now - req.t_submit)
            self.cost_model.observe_queue_wait(now - req.t_submit)
        FLIGHT.record("admit", rid=req.rid, engine=self._tag,
                      loop="prefill" if self.disagg else "decode",
                      queue_wait_s=round(now - req.t_submit, 6))
        req.span("queue-wait", req.t_submit, now, member=req.member)

    @staticmethod
    def _lcp(a: list[int], b: list[int]) -> int:
        n = min(len(a), len(b))
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i

    def _pick_slot(self, prompt: list[int], member: int = 0) -> tuple[int | None, int]:
        """(best free slot, reusable prefix length). Prefers the slot whose
        resident tokens share the longest prefix with ``prompt``; among
        equal matches (typically lcp 0), the slot with the SHORTEST resident
        content wins, so a no-match request lands on an empty slot instead
        of evicting another conversation's long reusable history. On a
        stacked engine only ``member``'s own rows are candidates (the
        chunked/reused admission route; coalesced single-shot admission
        uses ``_common_free_row`` instead)."""
        best, best_score = None, None
        lo = member * self.n_slots
        for i in range(lo, lo + self.n_slots):
            r = self._slots[i]
            if r is not None or i in self._claimed:
                continue
            lcp = self._lcp(self._resident[i], prompt) if self.prefix_cache else 0
            score = (lcp, -len(self._resident[i]))
            if best_score is None or score > best_score:
                best, best_score = i, score
        return best, best_score[0] if best_score else 0

    def _start_admissions(self) -> None:
        """Claim free slots for pending requests. Short prompts prefill in one
        shot (single program, flash attention, immediate first token); long
        prompts become chunked :class:`_Admission`s advanced a few segments
        per scheduler iteration, as many as one decode chunk's device time
        holds (``_step_admissions``), so active decodes interleave. A prompt
        whose prefix is already resident in a free slot (prefix caching) admits
        into THAT slot and prefills only the suffix — zero K/V copies. When
        the HOST prefix store holds a longer match than any slot (the slot
        that held this conversation was reclaimed under churn), the match
        is restored host→device into the claimed slot first and the
        admission starts past it.

        Constrained requests (``req.grammar``) ALWAYS route through the
        chunked path regardless of prompt length: the single-shot admit
        program samples the first token inside the prefill, before any
        grammar mask could apply; the register path leaves the first
        sample to the next (masked) decode chunk. Their grammar tables are
        placed in the device arena here, before the admission starts.

        Under disagg this runs on the PREFILL thread: every admission is
        chunked into the staging cache (``_admit_staged``), and the
        decode-side state work (DFA resets, arena, snapshots, grammar
        placement) moves to the decode loop."""
        if not self.disagg:
            self._flush_dfa_resets()
            self._maybe_reset_arena()
            self._dispatch_snapshots()
        if self.members > 1:
            self._start_admissions_members()
            return
        while True:
            with self._cond:
                if not self._pending:
                    return
                # FIFO with qos off (index 0 — byte-identical to the
                # pre-QoS engine); else the policy's WFQ pick: least
                # virtual time among backlogged classes, earliest deadline
                # headroom within the class (sched/policy.py).
                idx = (0 if not self.qos or len(self._pending) <= 1
                       else self._policy.pick(self._pending,
                                              time.monotonic()))
                head = self._pending[idx]
                slot, lcp = self._pick_slot(head.prompt_ids)
                if slot is None:
                    # Every row busy: with qos on, a strictly-lower-class
                    # resident row may be flagged for parking so this
                    # admission gets a slot at the next reap boundary.
                    self._maybe_flag_preemption_locked(head)
                    return
                if self.kv_pages and not self._paged_fits(slot, head):
                    # Head-of-line waits for pages (admission order
                    # preserved): live releases return pages and wake the
                    # scheduler. Under qos a lower-class row's claim is
                    # itself a page source — parking it both frees a slot
                    # and returns its non-shared pages to the pool.
                    self._maybe_flag_preemption_locked(head)
                    return
                req = self._pending.pop(idx)
                if self.qos:
                    self._policy.charge(req)
            if req.cancel.is_set():
                self.n_cancelled += 1
                req.out.put(("end", None))
                continue
            self._note_admitted(req)
            if self.staged:
                self._admit_staged(req, slot)
                continue
            if req.grammar is not None:
                try:
                    req.g_start = self._ensure_grammar(req.grammar)
                except Exception as e:
                    # Arena at capacity (or a poisoned table): doom this
                    # request alone; the slot was never claimed.
                    self._contain_admission_failure([req], e)
                    continue
                self.n_constrained += 1
            # Reuse caps at len(prompt)-1 (the final prompt token must run
            # through a segment so the register path's first decode step has
            # its position's logits to sample from) and is aligned DOWN to a
            # prefill_chunk multiple — segment offsets must stay multiples
            # of prefill_chunk (which divides max_seq) or the final
            # segment's bucket-padded dynamic_update_slice could cross
            # max_seq, where the clamped start silently corrupts valid
            # cache rows (see __init__'s chunk-alignment invariant).
            reuse = self._reuse_len(lcp, len(req.prompt_ids))
            if self.kv_pages:
                with self._cond:
                    claim = self._paged_claim(slot, req, reuse)
                if claim is None:
                    # Can't happen after the fits-check above (one claiming
                    # thread on a non-staged engine) — contain defensively
                    # rather than corrupt page accounting.
                    self._contain_admission_failure(
                        [req], RuntimeError("kv page claim failed after "
                                            "passing the fits check"))
                    continue
                reuse, cow = claim
                # COW copies + table upload land before the admission's
                # first cache write (same thread, data-flow ordered).
                self._paged_install(cow)
            restore = self._store_lookup(req.prompt_ids, reuse)
            if restore is not None:
                n_restore, host = restore
                if reuse:
                    # The slot-resident overlap [0, reuse) is a tier-0 hit
                    # even on the store path — only the tail past it is
                    # transferred and counted as restored.
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += reuse
                with self._cond:
                    self._claimed.add(slot)
                    # Rows [0, n_restore) hold the restored prefix once the
                    # dispatch below lands; beyond it the slot is in flux.
                    self._resident[slot] = req.prompt_ids[:n_restore]
                    self._admitting.append(self._open_admission(
                        req, slot, offset=n_restore,
                        restored=n_restore - reuse))
                self._restore_into(slot, reuse, n_restore - reuse, host, req)
            elif reuse or req.grammar is not None or (
                self.prefill_chunk and len(req.prompt_ids) > self.prefill_chunk
            ):
                if reuse:
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += reuse
                with self._cond:
                    self._claimed.add(slot)
                    # During the admission the rows beyond the reused prefix
                    # are in flux; advertise only what is already valid.
                    self._resident[slot] = req.prompt_ids[:reuse]
                    self._admitting.append(
                        self._open_admission(req, slot, offset=reuse))
            else:
                with self._cond:
                    self._resident[slot] = []
                try:
                    self._admit(req, slot)
                except Exception as e:
                    # This request's own prefill failed: doom it alone
                    # (escalating only if the shared device state went with
                    # it) and keep admitting the rest of the queue. The
                    # slot never activated, so its page claim unwinds here.
                    with self._cond:
                        self._paged_release_row(slot)
                    self._contain_admission_failure([req], e)

    def _common_free_row(self, members) -> int | None:
        """The slot row that is free for EVERY given member, preferring the
        row with the LEAST resident content across them — same tie-break as
        ``_pick_slot``: a fresh admission should land on an empty row, not
        evict another conversation's reusable prefix history. Caller holds
        ``_cond``."""
        best, best_load = None, None
        for s in range(self.n_slots):
            if not all(
                self._slots[m * self.n_slots + s] is None
                and (m * self.n_slots + s) not in self._claimed
                for m in members
            ):
                continue
            load = sum(len(self._resident[m * self.n_slots + s])
                       for m in members)
            if best_load is None or load < best_load:
                best, best_load = s, load
        return best

    def _reuse_len(self, lcp: int, n_prompt: int) -> int:
        """Usable prefix-reuse length: capped at n_prompt−1, aligned DOWN to
        a prefill_chunk multiple, zero below MIN_PREFIX_REUSE (the same
        invariants as the single-engine admission route — see
        ``_start_admissions``)."""
        reuse = min(lcp, n_prompt - 1)
        if self.prefill_chunk:
            reuse -= reuse % self.prefill_chunk
        return reuse if reuse >= MIN_PREFIX_REUSE else 0

    def _start_admissions_members(self) -> None:
        """Admission for stacked-members engines. Two routes, decided per
        member queue head (FIFO per member — only heads are candidates):

        - **Chunked / prefix-reuse**: a head that is longer than
          prefill_chunk, or whose prefix is resident in one of its member's
          free rows, becomes an :class:`_Admission` on that member's own
          best row; in-flight admissions advance member-coalesced — one
          vmapped segment program per (bucket, history) group per iteration
          (``_segment_round_members``).
        - **Single-shot**: remaining short heads coalesce into one
          member-vmapped prefill sharing a common free slot row
          (``_admit_fn_members``); anchoring on every head in FIFO order
          keeps one busy member's full slots from starving idle members."""
        while True:
            admit_chunked: _Admission | None = None
            group: dict[int, _Request] = {}
            row = None
            with self._cond:
                if not self._pending:
                    return
                heads: list[_Request] = []
                seen: set[int] = set()
                # Per-member heads follow the policy order under qos (WFQ
                # across classes, headroom within) and FIFO otherwise.
                src = (self._policy.order(self._pending, time.monotonic())
                       if self.qos else self._pending)
                for r in src:
                    if r.member not in seen:
                        seen.add(r.member)
                        heads.append(r)
                for r in heads:
                    slot, lcp = self._pick_slot(r.prompt_ids, r.member)
                    if slot is None:
                        continue
                    reuse = self._reuse_len(lcp, len(r.prompt_ids))
                    if reuse or r.grammar is not None or self.staged or (
                            self.prefill_chunk
                            and len(r.prompt_ids) > self.prefill_chunk):
                        if self.kv_pages:
                            claim = self._paged_claim(slot, r, reuse)
                            if claim is None:
                                continue  # this member waits for pages
                            reuse = claim[0]  # forced 0 on stacked engines
                        if reuse:
                            self.prefix_hits += 1
                            self.prefix_tokens_saved += reuse
                        self._pending.remove(r)
                        if self.qos:
                            self._policy.charge(r)
                        self._note_admitted(r)
                        self._claimed.add(slot)
                        self._resident[slot] = r.prompt_ids[:reuse]
                        admit_chunked = self._open_admission(
                            r, slot, offset=reuse)
                        self._admitting.append(admit_chunked)
                        break
                if admit_chunked is None:
                    for anchor in heads:
                        bucket = prefill_bucket(
                            len(anchor.prompt_ids), self.spec.max_seq)
                        group = {
                            h.member: h for h in heads
                            if prefill_bucket(
                                len(h.prompt_ids), self.spec.max_seq
                            ) == bucket
                        }
                        row = self._common_free_row(group)
                        if row is None and len(group) > 1:
                            group = {anchor.member: anchor}
                            row = self._common_free_row(group)
                        if row is not None:
                            break
                    if row is None:
                        # No member head has a usable row: with QoS on,
                        # each head may flag a lower-class victim within
                        # its OWN member's row range (member-local parks
                        # keep stacked weight sets independent).
                        for h in heads:
                            self._maybe_flag_preemption_locked(h)
                        return  # no head has a usable row this iteration
                    if self.kv_pages:
                        # One claim per group member: the slot group's chain
                        # is shared (page ids index each member's own pool
                        # copy), sized by the largest need, released when
                        # the last member's claim drops.
                        n_claimed = 0
                        for r in group.values():
                            if self._paged_claim(row, r, 0) is None:
                                break
                            n_claimed += 1
                        if n_claimed < len(group):
                            for _ in range(n_claimed):
                                self._paged_release_row(row)
                            return  # the group waits for pages
                    for r in group.values():
                        self._pending.remove(r)
                        if self.qos:
                            self._policy.charge(r)
            if self.kv_pages and not self.staged:
                # Fresh claims above dirtied the table mirror; upload it
                # before the admission's first cache write (this thread
                # owns the decode cache; reuse is 0 so there is no COW).
                # Staged engines defer the upload to the decode loop
                # (_drain_handoffs), which owns the decode cache there.
                self._paged_sync_table()
            if (admit_chunked is not None
                    and admit_chunked.req.grammar is not None
                    and not self.staged):
                # (Under disagg/zero_drain grammar placement is decode-
                # side state — placed at register time in _drain_handoffs
                # instead.)
                # Arena placement outside _cond (a grammar's first table
                # upload must not run under the scheduler lock); the
                # admission's register turn — the only reader of g_start —
                # happens strictly after this point in the turn order.
                try:
                    admit_chunked.req.g_start = self._ensure_grammar(
                        admit_chunked.req.grammar)
                except Exception as e:
                    self._contain_admission_failure(
                        [admit_chunked.req], e, admissions=[admit_chunked])
                    continue
                self.n_constrained += 1
            if admit_chunked is None:
                try:
                    self._admit_members(group, row, bucket)
                except Exception as e:
                    # The coalesced group's own prefill failed: doom only
                    # its members (other members' active streams continue
                    # unless the shared state was consumed). No slot went
                    # live, so the group's page claims unwind here.
                    if self.kv_pages:
                        with self._cond:
                            for _ in group:
                                self._paged_release_row(row)
                    self._contain_admission_failure(list(group.values()), e)
            # chunked admissions advance in _segment_round_members; loop
            # to route any further heads

    def _admit_members(self, group: dict[int, _Request], row: int,
                       bucket: int) -> None:
        """Run one coalesced member-vmapped admission (see
        ``_start_admissions_members``)."""
        mem, n_s = self.members, self.n_slots
        spec = self.spec
        tokens = np.zeros((mem, 1, bucket), np.int32)
        lengths = np.ones((mem, 1), np.int32)  # ≥1 keeps the last-token gather valid
        enables = np.zeros((mem,), bool)
        seeds = np.zeros((mem,), np.int32)
        temps = np.ones((mem,), np.float32)
        topps = np.ones((mem,), np.float32)
        topks = np.zeros((mem,), np.int32)
        pps = np.zeros((mem,), np.float32)
        fps = np.zeros((mem,), np.float32)
        budgets = np.ones((mem,), np.int32)
        eoss = np.full((mem,), -1, np.int32)
        bias_rows = self._zero_bias_mem  # copy-on-write below
        live: dict[int, _Request] = {}
        for m, req in group.items():
            if req.cancel.is_set():
                self.n_cancelled += 1
                req.out.put(("end", None))
                if self.kv_pages:
                    # The coalesced claim in _start_admissions_members took
                    # one claim per group member; a member skipped here never
                    # reaches _release_slot, so drop its claim now.
                    with self._cond:
                        self._paged_release_row(m * n_s + row)
                continue
            self._note_admitted(req)
            n = len(req.prompt_ids)
            tokens[m, 0, :n] = req.prompt_ids
            lengths[m, 0] = n
            enables[m] = True
            seeds[m] = req.seed
            temps[m] = req.temperature
            topps[m] = req.top_p
            topks[m] = req.top_k
            pps[m] = req.pp
            fps[m] = req.fp
            budgets[m] = req.budget
            eoss[m] = req.eos_id if req.eos_id is not None else -1
            if req.bias_row is not None:
                if bias_rows is self._zero_bias_mem:
                    bias_rows = bias_rows.copy()
                bias_rows[m] = req.bias_row
            live[m] = req
        if not live:
            return
        # Shared-prefix dedup (docs/quorum.md): when the group is a FULL
        # quorum (every member live) carrying one identical prompt on a
        # shared-weights stack, prefill once and broadcast — the prompt's
        # K/V is member-invariant, so (M-1)·n prefill tokens never run.
        # Partial groups, cancels, and per-member prompt edits fall back
        # to the M-prefill program; outputs are token-for-token identical
        # either way (the pin tests assert it).
        use_dedup = (self.quorum_dedup and len(live) == mem
                     and len({tuple(r.prompt_ids)
                              for r in live.values()}) == 1)
        faults.fire("engine.admit")
        t0 = time.perf_counter()
        # The dedup program prefills the one shared prompt once; the
        # member-vmapped one computes a bucket per member, absent ones too.
        n_asked = (len(next(iter(live.values())).prompt_ids) if use_dedup
                   else sum(len(r.prompt_ids) for r in live.values()))
        family, rows = ("dedup", 1) if use_dedup else ("single_shot", mem)
        acct = self._led().open(t0)  # one span for all: same programs
        with contextlib.closing(acct), self._prefill_dispatch(
                family, bucket, n_asked, rows):
            (firsts, s_lp, top_ix, top_lp,
             self._ck, self._cv, self._token, self._lengths, self._keys,
             self._temp, self._topp, self._topk,
             self._pp, self._fp, self._counts, self._bias,
             self._live, self._budget, self._eos,
             ) = (self._dedup_admit_fn(bucket) if use_dedup
                  else self._admit_fn_members(bucket))(
                self.weights, tokens, lengths, np.int32(row), enables, seeds,
                temps, topps, topks, pps, fps, bias_rows, budgets, eoss,
                self._ck, self._cv, self._token, self._lengths, self._keys,
                self._temp, self._topp, self._topk,
                self._pp, self._fp, self._counts, self._bias,
                self._live, self._budget, self._eos,
            )
            prog = self._sent(PREFILL, family, bucket, rows * bucket, [acct])
            with self._phase("reap_block"):  # the admit blocks on its token
                firsts, s_lp, top_ix, top_lp = self._fetch_landing(
                    prog, firsts, s_lp, top_ix, top_lp)
                t1 = time.perf_counter()
                acct.close(t1)  # whole once the phase's end passes it
        obs.PREFILL.observe(t1 - t0)
        if use_dedup:
            saved = (mem - 1) * len(next(iter(live.values())).prompt_ids)
            self.quorum_dedup_tokens += saved
            self.quorum_dedup_prefills += 1
            obs.QUORUM_DEDUP_TOKENS.inc(saved)
        self.breaker.record_success()
        for m, req in live.items():
            # reused/restored are structurally 0 here like the
            # single-engine single-shot path (member reuse routes
            # through a chunked admission); recorded so every
            # admission span carries the cache-effectiveness attrs.
            req.span("prefill", t0, t1, tokens=len(req.prompt_ids),
                     bucket=bucket, slot=row, coalesced=len(live),
                     reused=0, restored=0, dedup=int(use_dedup),
                     **acct.parts_ms())
        for m, req in live.items():
            flat = m * n_s + row
            self._resident[flat] = list(req.prompt_ids)
            if req.want_lp >= 0:
                req.lp.append((float(s_lp[m]),
                               np.asarray(top_ix[m]), np.asarray(top_lp[m])))
            if not self._emit(req, int(firsts[m])):
                with self._cond:
                    self._slots[flat] = req
            elif self.kv_pages:
                # Done on the first token: the slot never activates, so
                # _release_slot will not run for this member — drop the
                # page claim taken at coalesced-admission time.
                with self._cond:
                    self._paged_release_row(flat)

    @_program("_admit_cache",
              lambda self, bucket, history: ("mseg", bucket, history),
              kept=True)
    def _seg_fn_members(self, bucket: int, history: int):
        """Jitted member-coalesced prompt segment: each member advances its
        own in-flight admission (own tokens/offset/slot row) in one vmapped
        program; ``enables[m]`` gates absent members' cache writes."""
        spec = self.spec

        def seg(params, tokens, offsets, n_valids, slots, enables, ck, cv):
            # tokens [M, 1, bucket]; offsets/n_valids/slots [M] int32;
            # enables [M] bool
            def one(p, tok, off, nv, slot, en, k, v):
                return prefill_segment(p, spec, tok, off, nv, k, v, slot,
                                       history=history, write_gate=en)

            return _member_vmap(
                one, params, tokens, offsets, n_valids, slots, enables, ck, cv)

        return jax.jit(seg, donate_argnames=("ck", "cv"))

    def _segment_round_members(self, room: _SegmentRoom,
                               floor: bool) -> bool:
        """:meth:`_segment_round` on a stacked engine: admissions sharing a
        (segment bucket, history bucket) — the lockstep fan-out case —
        coalesce into ONE vmapped segment program, at most one admission
        per member per program; a later round runs the one program that
        holds the oldest admission."""
        groups: dict[tuple[int, int], list[_Admission]] = {}
        for adm in list(self._admitting):
            req = adm.req
            if req.cancel.is_set():
                with self._cond:  # races the decode loop's final branch
                    if adm.dead:
                        continue
                    adm.dead = True
                if not req.expired:  # deadline expiry already delivered err
                    self.n_cancelled += 1
                    req.out.put(("end", None))
                self._release_admission(adm)
                continue
            if adm.final_sent:
                continue  # staged (disagg/zero_drain); awaiting register
            seg = req.prompt_ids[adm.offset: adm.offset + self.prefill_chunk]
            bucket = prefill_bucket(len(seg), self.prefill_chunk)
            history = prefill_bucket(adm.offset + len(seg), self.spec.max_seq)
            groups.setdefault((bucket, history), []).append(adm)
        for (bucket, history), adms in groups.items():
            while adms:
                batch: dict[int, _Admission] = {}
                rest: list[_Admission] = []
                for adm in adms:
                    m = adm.slot // self.n_slots
                    if m in batch:
                        rest.append(adm)
                    else:
                        batch[m] = adm
                adms = rest
                if not room.take(self.members * bucket, floor):
                    return False
                try:
                    self._run_member_segments(batch, bucket, history)
                except Exception as e:
                    if self.staged:
                        self._contain_prefill_failure(
                            [adm.req for adm in batch.values()], e,
                            admissions=list(batch.values()))
                    else:
                        self._contain_admission_failure(
                            [adm.req for adm in batch.values()], e,
                            admissions=list(batch.values()))
                if not floor:
                    return True
        return floor

    def _run_member_segments(
        self, batch: dict[int, _Admission], bucket: int, history: int
    ) -> None:
        mem, n_s = self.members, self.n_slots
        tokens = np.zeros((mem, 1, bucket), np.int32)
        offsets = np.zeros((mem,), np.int32)
        n_valids = np.zeros((mem,), np.int32)
        slots = np.zeros((mem,), np.int32)
        enables = np.zeros((mem,), bool)
        for m, adm in batch.items():
            req = adm.req
            seg = req.prompt_ids[adm.offset: adm.offset + self.prefill_chunk]
            tokens[m, 0, : len(seg)] = seg
            offsets[m] = adm.offset
            n_valids[m] = len(seg)
            slots[m] = adm.slot % n_s
            enables[m] = True
        if self.staged:
            faults.fire("engine.prefill_segment")
            # Same overlap discipline as the single-engine path: slices of
            # the completed rows dispatch BEFORE the member-vmapped segment
            # donates the staging buffers; the transfers then proceed while
            # the prefill group computes the next segment.
            disps = {m: self._handoff_dispatch(adm, adm.offset)
                     for m, adm in batch.items()}
            with self._segment_dispatch(
                    batch.values(), "mseg", bucket, int(n_valids.sum()), mem):
                self._sck, self._scv = self._seg_fn_members(bucket, history)(
                    self.prefill_params, tokens, offsets, n_valids, slots,
                    enables, self._sck, self._scv,
                )
            for m, adm in batch.items():
                adm.offset += int(n_valids[m])
                self._handoff_commit(adm, disps[m])
                if adm.offset >= len(adm.req.prompt_ids):
                    self._handoff_commit(
                        adm, self._handoff_dispatch(adm, adm.offset),
                        final=True)
            return
        with self._segment_dispatch(
                batch.values(), "mseg", bucket, int(n_valids.sum()), mem):
            self._ck, self._cv = self._seg_fn_members(bucket, history)(
                self.weights, tokens, offsets, n_valids, slots, enables,
                self._ck, self._cv,
            )
        for m, adm in batch.items():
            adm.offset += int(n_valids[m])
            self._resident[adm.slot] = adm.req.prompt_ids[: adm.offset]
            if adm.offset >= len(adm.req.prompt_ids):
                self._finish_admission(adm)

    def _finish_admission(self, adm: _Admission) -> None:
        """Install a finished chunked admission's per-slot state (flat row —
        identical for plain and stacked engines) and activate the slot."""
        req = adm.req
        prompt = req.prompt_ids
        bias = req.bias_row if req.bias_row is not None else self._zero_bias
        (self._token, self._lengths, self._keys, self._temp,
         self._topp, self._topk, self._pp, self._fp,
         self._counts, self._bias,
         self._live, self._budget, self._eos,
         self._dfa) = self._register_fn()(
            np.int32(adm.slot),
            np.int32(prompt[-1]),
            np.int32(len(prompt) - 1),
            np.int32(req.seed),
            np.float32(req.temperature),
            np.float32(req.top_p),
            np.int32(req.top_k),
            np.float32(req.pp),
            np.float32(req.fp),
            bias,
            np.int32(req.budget),
            np.int32(req.eos_id if req.eos_id is not None else -1),
            np.int32(req.g_start if req.grammar is not None else 0),
            self._token, self._lengths, self._keys,
            self._temp, self._topp, self._topk,
            self._pp, self._fp, self._counts, self._bias,
            self._live, self._budget, self._eos, self._dfa,
        )
        t1 = self._sent(OTHER, "register").t
        FLIGHT.record("register", rid=req.rid, engine=self._tag,
                      loop="decode", slot=adm.slot, tokens=len(prompt),
                      reused=adm.offset0, restored=adm.restored)
        # Wall time from slot claim to cache-complete: chunked admissions
        # include the decode turns interleaved between segments — that IS
        # the latency the admitted request experienced.
        obs.PREFILL.observe(t1 - adm.t_start)
        self.prefill_span_s += t1 - adm.t_start
        # Per-request cache effectiveness on the admission span:
        # ``reused`` is the total prefix the admission skipped
        # (offset0), ``restored`` the portion that came host→device
        # from the prefix store rather than sitting slot-resident. What
        # the span waited for (OpenSpan.parts_ms) the ledger fills in once
        # the programs the span saw dispatched have landed: with the
        # turn's decode chunk.
        span = req.span(
            "prefill", adm.t_start, t1, tokens=len(prompt),
            slot=adm.slot, chunked=True, reused=adm.offset0,
            restored=adm.restored, segments=adm.segments,
            turns=self.n_turns - adm.turn0 + 1 if adm.segments else 0,
            **self._keys_kept(len(prompt)), **self._state_carried(True))

        def settled(acct):
            self.prefill_own_s += acct.own
            self.prefill_peer_s += acct.peer
            self.prefill_decode_wait_s += acct.decode
            if span is not None:
                span.meta.update(acct.parts_ms())

        if adm.acct is not None:
            adm.acct.close(t1, settled)
        with self._cond:
            self._slots[adm.slot] = req
        self._release_admission(adm)
        self.breaker.record_success()

    def _step_admissions(self) -> None:
        """Advance the in-progress chunked admissions ahead of this turn's
        decode chunk: every one by a prompt segment (the floor round), and
        then, oldest first, by as many more as the chunk's own device time
        holds (:meth:`_segment_room`), registering each admission as its
        last segment goes out. Interleaving unit of the scheduler: after
        the turn's segments `_run_chunk` keeps active requests decoding, so
        a long admission delays an in-flight stream by at most about one
        chunk's time a turn and never stalls it (VERDICT r2 weakness 6). A
        staged engine (``zero_drain``/``disagg``) runs the floor round
        only."""
        if not self._admitting:
            return
        room = self._segment_room()
        round_ = (self._segment_round_members if self.members > 1
                  else self._segment_round)
        before, floor = self.n_prefill_segments, True
        while round_(room, floor) and not self.staged:
            floor = False
        if self.n_prefill_segments > before:
            self.n_prefill_segment_turns += 1

    def _segment_room(self) -> _SegmentRoom:
        """The room this turn's segments have: the decode chunk the turn
        will dispatch after them, at the pace of the last chunk that had
        an interval of the device to itself, against a segment token's
        pace in the last few intervals that held segments (the device
        ledger times both on its landings: ``_time_paces``). With no
        live row there is no chunk to protect and the turn does not block:
        no room, one segment a turn."""
        rows = self._active_rows()
        if not rows:
            return _SegmentRoom(0.0, 0.0)
        steps = max(1, min(r.chunk_hint or self.decode_chunk for _, r in rows))
        led = self._led()
        return _SegmentRoom(led.step_alone_s * steps, led.seg_tok_s)

    def _segment_round(self, room: _SegmentRoom, floor: bool) -> bool:
        """One round of segment dispatches. The ``floor`` round advances
        every in-progress chunked admission by ONE prompt segment, whatever
        it costs; a later round advances the oldest by one more if ``room``
        still holds it. True if another round may find work to do."""
        for adm in list(self._admitting):
            req = adm.req
            if req.cancel.is_set():
                # Atomic dead-marking: under disagg the decode loop's
                # final-marker branch can race this cancel retirement —
                # whichever side flips ``dead`` first retires the request
                # exactly once. A deadline expiry already delivered its
                # err frame (req.expired, _expire) — it is not a client
                # cancellation and gets no extra end frame.
                with self._cond:
                    if adm.dead:
                        continue
                    adm.dead = True
                if not req.expired:
                    self.n_cancelled += 1
                    req.out.put(("end", None))
                self._release_admission(adm)
                continue
            if adm.final_sent:
                continue  # fully staged; awaiting the register
            prompt = req.prompt_ids
            # A row that holds a recurrent state admits all but its last
            # token in segments: the register's decode step runs that token
            # again (for K and V a second, equal write; a state would take
            # the position twice).
            end = len(prompt) - (1 if self.spec.row_state else 0)
            seg = prompt[adm.offset : min(adm.offset + self.prefill_chunk,
                                          end)]
            bucket = prefill_bucket(len(seg), self.prefill_chunk)
            history = prefill_bucket(adm.offset + len(seg), self.spec.max_seq)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, : len(seg)] = seg
            if not room.take(bucket, floor):
                return False
            if self.staged:
                try:
                    faults.fire("engine.prefill_segment")
                    # Overlap: slice the already-complete rows off the
                    # PRE-segment staging buffers, dispatch the next
                    # segment, then transfer — handoff of chunk i runs
                    # while the prefill group computes chunk i+1. (Under
                    # zero_drain there is no transfer; the slice payload
                    # is already resident and the overlap is with the
                    # decode ring's own megachunks instead.)
                    disp = self._handoff_dispatch(adm, adm.offset)
                    with self._segment_dispatch(
                            [adm], "seg", bucket, len(seg)):
                        self._sck, self._scv = self._seg_fn(bucket, history)(
                            self.prefill_params, tokens,
                            np.int32(adm.offset), np.int32(len(seg)),
                            np.int32(adm.slot), self._sck, self._scv,
                        )
                    adm.offset += len(seg)
                    self._handoff_commit(adm, disp)
                    if adm.offset >= len(prompt):
                        # The last segment's rows hand off now; the decode
                        # loop registers once the final marker drains.
                        self._handoff_commit(
                            adm, self._handoff_dispatch(adm, adm.offset),
                            final=True)
                except Exception as e:
                    self._contain_prefill_failure([req], e, admissions=[adm])
                continue
            try:
                faults.fire("engine.prefill_segment")
                with self._segment_dispatch(
                        [adm], "seg", bucket, len(seg)):
                    self._ck, self._cv = self._seg_fn(bucket, history)(
                        self.weights, tokens, np.int32(adm.offset),
                        np.int32(len(seg)),
                        np.int32(adm.slot), self._ck, self._cv,
                    )
                adm.offset += len(seg)
                # keep the prefix-cache view in sync with the cache rows
                self._resident[adm.slot] = prompt[: adm.offset]
                if adm.offset >= end:
                    self._finish_admission(adm)
            except Exception as e:
                # One admission's segment failed: doom it alone; active
                # decodes and other admissions continue (escalation only
                # when the shared cache's donated buffers were consumed).
                self._contain_admission_failure([req], e, admissions=[adm])
            if not floor:
                return True
        return floor

    def _release_admission(self, adm: _Admission) -> None:
        if adm.acct is not None:  # a span that never ended: no account
            adm.acct.close()
        with self._cond:
            if adm in self._admitting:
                self._admitting.remove(adm)
            self._claimed.discard(adm.slot)
            if self.kv_pages and self._slots[adm.slot] is None:
                # Dead admission (cancel/deadline/failure): the claim never
                # became a live stream, so its pages unwind here — the
                # partial prefill stays retained for reuse. (On the success
                # path _finish_admission activates the slot first, so this
                # branch is skipped and the claim lives until release.)
                self._paged_release_row(adm.slot)
            if self.disagg:
                # A discarded claim is admission capacity the (possibly
                # sleeping) prefill loop can use — and either loop may be
                # the releaser here.
                self._cond.notify_all()

    def _admit(self, req: _Request, slot: int) -> None:
        faults.fire("engine.admit")
        t0 = time.perf_counter()
        n_prompt = len(req.prompt_ids)
        bucket = prefill_bucket(n_prompt, self.spec.max_seq)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n_prompt] = req.prompt_ids
        bias = req.bias_row if req.bias_row is not None else self._zero_bias
        acct = self._led().open(t0)
        with contextlib.closing(acct), self._prefill_dispatch(
                "single_shot", bucket, n_prompt):
            (first, s_lp, top_ix, top_lp,
             self._ck, self._cv, self._token, self._lengths, self._keys,
             self._temp, self._topp, self._topk,
             self._pp, self._fp, self._counts, self._bias,
             self._live, self._budget, self._eos) = self._admit_fn(bucket)(
                self.weights,
                tokens,
                np.asarray([n_prompt], np.int32),
                np.int32(slot),
                np.int32(req.seed),
                np.float32(req.temperature),
                np.float32(req.top_p),
                np.int32(req.top_k),
                np.float32(req.pp),
                np.float32(req.fp),
                bias,
                np.int32(req.budget),
                np.int32(req.eos_id if req.eos_id is not None else -1),
                self._ck, self._cv, self._token, self._lengths, self._keys,
                self._temp, self._topp, self._topk,
                self._pp, self._fp, self._counts, self._bias,
                self._live, self._budget, self._eos,
            )
            prog = self._sent(PREFILL, "single_shot", bucket, bucket, [acct])
            moe = self._moe_snapshot()
            with self._phase("reap_block"):  # the admit blocks on its token
                first, s_lp, top_ix, top_lp = self._fetch_landing(
                    prog if moe is None else moe[2],
                    first, s_lp, top_ix, top_lp)
                picks = ({} if moe is None else
                         {"picks_held": self._moe_note(moe)})
                t1 = time.perf_counter()
                acct.close(t1)  # whole once the phase's end passes it
        obs.PREFILL.observe(t1 - t0)
        self.breaker.record_success()  # a half-open probe admitted cleanly
        # reused/restored are structurally 0 on the single-shot path
        # (reuse routes through a chunked admission); recorded anyway so
        # every admission span carries the cache-effectiveness attrs.
        req.span("prefill", t0, t1, tokens=n_prompt, bucket=bucket, slot=slot,
                 reused=0, restored=0, **acct.parts_ms(), **picks,
                 **self._keys_kept(n_prompt), **self._state_carried(False))
        if req.want_lp >= 0:
            req.lp.append((float(s_lp),
                           np.asarray(top_ix), np.asarray(top_lp)))
        # The one-shot prefill wrote K/V for every prompt position.
        self._resident[slot] = list(req.prompt_ids)
        done = self._emit(req, int(first))
        if not done:
            with self._cond:
                self._slots[slot] = req
        elif self.kv_pages:
            # Finished on its first token: the slot never went live, so
            # retire the page claim here (retaining the prompt's pages as
            # a prefix-reuse donor, like any other release).
            with self._cond:
                self._paged_release_row(slot)

    def _sweep_cancelled(self) -> None:
        """Release rows whose cancel event is set (client gone, stop string
        hit): they are masked out of every not-yet-dispatched chunk; tokens
        still arriving from in-flight chunks are counted as overrun."""
        with self._cond:
            active = [(i, r) for i, r in enumerate(self._slots) if r is not None]
        for i, r in active:
            if r.cancel.is_set():
                self.n_cancelled += 1
                r.out.put(("end", None))
                with self._cond:
                    self._release_slot(i, r)

    def _active_rows(self) -> list:
        with self._cond:
            return [(i, r) for i, r in enumerate(self._slots) if r is not None]

    # ---- deadlines & failure containment ----------------------------------

    def _expire(self, req: _Request, stage: str) -> None:
        """Retire one request past its deadline: error frame first (the
        consumer must see DeadlineExceeded, not a clean end), cancel set so
        in-flight device work masks the row out at the next boundary."""
        self.n_deadline_exceeded += 1
        obs.DEADLINE_EXCEEDED.inc(stage=stage)
        now = time.perf_counter()
        req.span("deadline-exceeded", now, now, stage=stage)
        FLIGHT.record("deadline", rid=req.rid, engine=self._tag,
                      loop="decode", stage=stage)
        req.expired = True
        req.out.put(("err", DeadlineExceeded(stage)))
        req.cancel.set()

    def _sweep_deadlines(self) -> None:
        """Once per scheduler turn: shed pending requests past their deadline
        (stage ``queue`` — the engine never served them, a 503 the client can
        retry elsewhere) and cancel admitted ones (stage ``prefill`` /
        ``decode`` — a 504, the work is lost). Runs on the scheduler thread,
        so it cannot race the cancel sweep's own releases."""
        now = time.monotonic()
        # The cost model owns the ONE expiry predicate (sched/cost.py) —
        # the submit-time shed and this sweep cannot drift apart.
        expired = self.cost_model.expired

        with self._cond:
            shed = [r for r in self._pending if expired(r, now)]
            for r in shed:
                self._pending.remove(r)
            late_adm = [a for a in self._admitting if expired(a.req, now)]
            late_active = [(i, r) for i, r in enumerate(self._slots)
                           if r is not None and expired(r, now)]
            if self.qos:
                depths = self._policy.queue_depths(self._pending)
        if self.qos:
            for cls, n in depths.items():
                obs.SCHED_QUEUE_DEPTH.set(n, **{"class": cls})
        for r in shed:
            self._expire(r, "queue")
        for a in late_adm:
            self._expire(a.req, "prefill")
            if self.staged:
                # The staging path owns this admission's rows (disagg: the
                # PREFILL thread; zero_drain: this same scheduler's next
                # _step_admissions/_drain_handoffs turn); a release here
                # could re-issue the slot claim with injection pieces
                # still queued. _expire set cancel — the staged path's own
                # cancel branch retires it dead-marked, so stale pieces
                # are dropped instead of written into a new tenant.
                with self._cond:
                    self._cond.notify_all()
            else:
                self._release_admission(a)
        for i, r in late_active:
            self._expire(r, "decode")
            with self._cond:
                if self._slots[i] is r:
                    self._release_slot(i, r)

    def _maybe_flag_preemption_locked(self, head: _Request) -> None:
        """The picked admission found no usable slot: with QoS on, flag ONE
        strictly-lower-class resident row for parking. Caller holds _cond;
        the actual park happens on the decode loop's next reap boundary
        (:meth:`_sweep_preemptions` — every ``_slots`` mutation that
        touches live device state stays on that thread's turn order).

        On a stacked-member engine each member's requests live in their
        own row range (``member * n_slots .. +n_slots``), so the victim
        search is restricted to the head's member — replay bookkeeping is
        per-request, so the park/resume cycle is member-local."""
        if not self.qos or head.cancel.is_set() or head.preempt_flag:
            return
        if any(b is head for _, _, b in self._preempt_pending):
            return  # one outstanding park order per beneficiary
        lo = head.member * self.n_slots
        picked = self._preempt.pick_victim(head, self._slots, lo,
                                           lo + self.n_slots)
        if picked is None:
            return
        row, victim = picked
        victim.preempt_flag = True
        self._preempt_pending.append(  # qlint: allow-unguarded(the _locked suffix is the contract: every caller sits inside _start_admissions'/_start_admissions_members' `with self._cond:` scope — the lint's scope walker only sees the enclosing def)
            (row, victim, head))
        self._cond.notify_all()

    def _sweep_preemptions(self) -> None:
        """Execute queued park orders at this reap boundary (decode
        scheduler thread). Parking IS the ordinary release path: the
        victim's K/V prefix stays slot-resident (dense) or parked as
        retained page references (kv_pages=1), a host prefix store
        additionally snapshots it, and in-flight chunks that still carry
        the row drop its tokens as overrun (``_slots[i] is not req``) — no
        quiesce, no new device program. The victim then re-enters the
        pending queue with resume credit; ``begin_replay`` + ``_emit``'s
        replay guard make the resumed stream token-for-token identical to
        an unpreempted run (docs/scheduling.md)."""
        if not self.qos:
            return
        with self._cond:
            if not self._preempt_pending:
                return
            work = list(self._preempt_pending)
            self._preempt_pending.clear()
        for row, victim, ben in work:
            try:
                faults.fire("engine.preempt")
                with self._cond:
                    if self._slots[row] is not victim \
                            or victim.cancel.is_set():
                        # Finished/cancelled/expired since flagging: the
                        # park order is moot.
                        victim.preempt_flag = False
                        continue
                    self._release_slot(row, victim)
                    parked = victim.begin_replay()
                    victim.preempt_flag = False
                    # Head of the queue: within its class the resume
                    # credit already wins, and FIFO engines never reach
                    # here (qos gate above).
                    self._pending.insert(0, victim)
                    self.n_preemptions += 1
                    self.n_preempted_tokens += parked
                    self._cond.notify_all()
                obs.PREEMPTIONS.inc(**{"class": victim.sched_class})
                obs.PREEMPTED_TOKENS.inc(parked)
                FLIGHT.record("preempt", rid=victim.rid, engine=self._tag,
                              loop="decode", row=row,
                              victim_class=victim.sched_class,
                              beneficiary=ben.rid, parked_tokens=parked)
            except Exception as e:
                # Fault mid-park (chaos: engine.preempt): the victim alone
                # is doomed — error frame, cancel, release; the beneficiary
                # and every other stream proceed untouched, and the pool /
                # page accounting stays exact because the release path is
                # the same one a finished stream takes.
                with self._cond:
                    victim.preempt_flag = False
                    if self._slots[row] is victim:
                        self._release_slot(row, victim)
                    if victim in self._pending:
                        self._pending.remove(victim)
                    self.n_failures += 1
                victim.out.put(("err", e))
                victim.cancel.set()
                FLIGHT.record("preempt-fault", rid=victim.rid,
                              engine=self._tag, loop="decode", row=row,
                              error=f"{type(e).__name__}: {e}"[:200])

    def _sweep_drain_parks(self) -> None:
        """Drain with park=1: retire every resident stream at this reap
        boundary (decode scheduler thread). Parking IS the ordinary
        release path — the row's prefix lands in the resident map / host
        prefix store exactly as a finished stream's would, which is what
        the router-side drain migration then ships to siblings. The
        consumer sees a ``parked`` finish (never an error): the router
        proactively resumes the stream on a sibling with the delivered
        token ids as its replay journal (docs/robustness.md)."""
        if not self._draining_park:
            return
        with self._cond:
            rows = [(i, r) for i, r in enumerate(self._slots)
                    if r is not None]
        for i, req in rows:
            with self._cond:
                if self._slots[i] is not req or req.cancel.is_set():
                    continue  # finished/cancelled since listing
                self._release_slot(i, req)
                self.n_drain_parked += 1
            # `parked` BEFORE the end frame: the consumer reads it the
            # moment stream_results returns.
            req.parked = True
            req.out.put(("end", None))
            FLIGHT.record("drain-park", rid=req.rid, engine=self._tag,
                          loop="decode", row=i, emitted=req.emitted)

    def drain(self, park: bool = False) -> dict:
        """Begin a graceful drain: gate admissions shut (new submits shed
        with a retryable 503 — the router's pre-first-byte failover moves
        them to siblings) and either let residents finish (default) or
        park them (``park=True``): queued requests end ``parked``
        immediately, active rows at the decode loop's next reap boundary
        (:meth:`_sweep_drain_parks`). Idempotent; returns
        :meth:`drain_status`."""
        parked_pending: "list[_Request]" = []
        with self._cond:
            self.draining = True
            if park:
                self._draining_park = True
                # Queued requests never touched device state: retire them
                # here rather than making them wait for rows that are
                # themselves being parked.
                parked_pending = list(self._pending)
                del self._pending[:]
                self.n_drain_parked += len(parked_pending)
            self._cond.notify_all()
        for r in parked_pending:
            r.parked = True
            r.out.put(("end", None))
            FLIGHT.record("drain-park", rid=r.rid, engine=self._tag,
                          loop="decode", row=-1, emitted=r.emitted)
        return self.drain_status()

    def undrain(self) -> dict:
        """Reopen admissions (clears both drain flags); returns
        :meth:`drain_status`."""
        with self._cond:
            self.draining = False
            self._draining_park = False
            self._cond.notify_all()
        return self.drain_status()

    def drain_status(self) -> dict:
        """Drain progress for the router's drain orchestration poll:
        ``resident`` counts every stream still attached (active rows +
        in-flight admissions + queue) — zero means the replica holds no
        client state and is safe to take down."""
        with self._cond:
            busy = sum(1 for r in self._slots if r is not None)
            return {
                "draining": self.draining,
                "park": self._draining_park,
                "resident": busy + len(self._admitting)
                + len(self._pending),
                "parked_total": self.n_drain_parked,
            }

    def _device_state_ok(self) -> bool:
        """Whether the donated per-slot device state survived the last
        failed call. A jitted call that died mid-execution may have consumed
        its donated buffers — detectable as deleted arrays — in which case
        only a full rebuild (and dooming the streams whose KV lived there)
        recovers the engine."""
        try:
            leaves = jax.tree.leaves(
                (self._ck, self._cv, self._token, self._lengths, self._keys,
                 self._temp, self._topp, self._topk, self._pp, self._fp,
                 self._counts, self._bias, self._live, self._budget,
                 self._eos, self._dfa))
            return not any(x.is_deleted() for x in leaves
                           if isinstance(x, jax.Array))
        except Exception:
            return False

    def _contain_admission_failure(
        self, reqs: list[_Request], exc: Exception,
        admissions: "list[_Admission] | None" = None,
    ) -> None:
        """One admission's own dispatch failed: doom only its request(s).

        When the failed call left the shared device state intact (fault
        before dispatch, host-side error), nothing else is touched — active
        streams keep decoding and pending requests keep their place. When
        donated buffers were consumed, escalate to :meth:`_fail_all` (the
        co-batched KV went with them) — which still keeps pending requests
        queued."""
        FLIGHT.record("containment", engine=self._tag, loop="decode",
                      site="admission",
                      error=f"{type(exc).__name__}: {exc}"[:200],
                      rids=[r.rid for r in reqs])
        FLIGHT.dump("containment")
        for adm in admissions or ():
            self._release_admission(adm)
        if self._device_state_ok():
            self.n_failures += len(reqs)
            for r in reqs:
                now = time.perf_counter()
                r.span("engine-failure", now, now,
                       error=type(exc).__name__, contained=True)
                r.out.put(("err", exc))
        else:
            self._fail_all(exc, doomed=reqs)

    def _decode_guard(self):
        """The decode loop's jax.transfer_guard context (transfer_guard= /
        QUORUM_TPU_TRANSFER_GUARD) — a no-op unless the knob is set."""
        if not self.transfer_guard:
            return contextlib.nullcontext()
        return jax.transfer_guard(self.transfer_guard)

    # ---- flight recorder + per-family device-time attribution --------------

    def _next_seq(self) -> int:
        """Dispatch sequence number pairing a ring entry's dispatch and
        reap flight-recorder events (decode scheduler thread only)."""
        self._dispatch_seq += 1
        return self._dispatch_seq

    def _family_of(self, key, cache: str = "decode_cache") -> str:
        """compile_budget.json family for a program-cache key, memoized.
        Classification failures degrade to ``"unknown"`` — attribution must
        never take a serving dispatch down (the budget tests are where
        unknown keys FAIL; here they are a label)."""
        fam = self._family_cache.get(key)
        if fam is None:
            try:
                fam = (_budget.classify_decode_key(key)
                       if cache == "decode_cache"
                       else _budget.classify_admit_key(key))
            except Exception:
                fam = "unknown"
            self._family_cache[key] = fam
        return fam

    def _record_breaker_failure(self) -> None:
        """Feed the failure breaker and, on the CLOSED/HALF-OPEN → OPEN
        transition only, record the breaker event + post-mortem dump — a
        failure storm with the breaker already open must not spray one
        spurious 'transition' (and one dump file) per failure."""
        was_open = self.breaker.state == "open"
        self.breaker.record_failure()
        if not was_open and self.breaker.state == "open":
            FLIGHT.record("breaker", engine=self._tag, state="open")
            FLIGHT.dump("breaker-open")

    def _run_chunk(self) -> None:
        # The guard covers everything the token critical path does on this
        # thread: ring fill (dispatch) and blocking reap. Admission/prefill
        # stays outside — uploading the prompt is a legitimate per-request
        # transfer.
        with self._decode_guard():
            self._run_chunk_steps()

    def _run_chunk_steps(self) -> None:
        with self._phase("sweep"):
            self._sweep_cancelled()
        if not self._active_rows():
            # No rows to clamp: discard any dangling clamp stamp so the
            # idle gap until the next admission never reads as stall.
            self._note_admission_clamp(False)
            self._drain_inflight()
            return
        # Depth-K pipelined decode: top the ring up, then block on (only)
        # the oldest dispatch. The device rolls dispatch-to-dispatch while
        # the host detokenizes, SSE-emits, and schedules the next iteration.
        with self._phase("fill"):
            self._fill_inflight()
        if self._inflight:
            self._reap_oldest()
            # Incremental drain: dispatches behind the (blocking) oldest
            # whose payloads already landed are reaped without pacing the
            # device — under megachunks a long dispatch can complete
            # several successors' worth of host work, and tokens must not
            # sit in finished device buffers while the host waits on a
            # future turn's blocking reap.
            while self._inflight and self._inflight[0].ready():
                self._reap_oldest()

    def _admission_pressure(self) -> bool:
        """A chunked admission is mid-prefill, or a pending request could
        actually claim a slot right now. Pending requests with NO free
        slot are NOT pressure — they cannot admit until a row finishes
        anyway, and deep/fused dispatch is exactly what finishes rows
        sooner. Caller holds ``_cond``.

        NEVER under disagg: admissions run on their own device group, so
        the decode ring keeps its full depth (and full megachunk fusion)
        through any admission burst — the whole point of the split. Handoff
        writes/registers chain behind the in-flight ring without draining
        it.

        NEVER under zero_drain either: that is the knob's whole contract.
        Admission segments run against the staging cache (an independent
        dispatch chain — they never extend the decode-state chain the ring
        blocks on), and the injection write + register are the same small
        chained programs a disagg handoff ends in, landing at a reap
        boundary. The structural C=1/K=1 coupling this predicate used to
        impose on colocated engines is retired behind the knob."""
        if self.disagg or self.zero_drain:
            return False
        if self._admitting:
            return True
        if not self._pending:
            return False
        members = {r.member for r in self._pending}
        for m in members:
            lo = m * self.n_slots
            for i in range(lo, lo + self.n_slots):
                if self._slots[i] is None and i not in self._claimed:
                    return True
        return False

    def _target_depth(self) -> int:
        """How deep the ring may run right now. Admission pressure caps it
        at 1 (dispatch-then-drain): every extra in-flight chunk would
        delay the admission by a whole chunk on device (its programs
        chain behind the ring). Under zero_drain/disagg pressure is
        structurally False and the ring keeps its configured depth."""
        with self._cond:
            clamped = not self._stop and self._admission_pressure()
        self._note_admission_clamp(clamped)
        if clamped or self._stop:
            return 1
        return self.decode_pipeline

    def _note_admission_clamp(self, clamped: bool) -> None:
        """Accumulate wall time the decode ring spends clamped to depth 1
        for an admission (quorum_tpu_admission_stall_seconds_total) —
        observed once per ring-fill turn on the scheduler thread (the
        field's single owner). Only the span between CONSECUTIVE clamped
        observations counts: a dangling stamp is discarded when the clamp
        lifts or the ring goes idle, so an idle gap can never read as
        stall (slightly under-counts the clamp's last turn; never over).
        Engines whose ring cannot clamp (K=1 and C=1 — depth 1 IS the
        configuration) record nothing; zero_drain/disagg engines record
        nothing structurally (pressure is always False there)."""
        if self.decode_pipeline <= 1 and self.decode_loop <= 1:
            return
        now = time.monotonic()
        # Effective-C/K clamp TRANSITIONS ride the flight recorder (state
        # changes only — not one event per clamped turn): the timeline
        # shows exactly when an admission pinned the ring to depth 1 and
        # when it lifted, with the accumulated stall on the lift event.
        if clamped and self._clamp_t0 is None:
            FLIGHT.record("clamp", engine=self._tag, loop="decode",
                          state="on")
        elif not clamped and self._clamp_t0 is not None:
            FLIGHT.record("clamp", engine=self._tag, loop="decode",
                          state="off",
                          stalled_s=round(self.admission_stall_s, 6))
        if clamped and self._clamp_t0 is not None:
            dt = now - self._clamp_t0
            self.admission_stall_s += dt
            obs.ADMISSION_STALL_SECONDS.inc(dt)
        self._clamp_t0 = now if clamped else None

    def _effective_loop(self, active, n_steps: int, ahead: int) -> int:
        """Chunks THIS dispatch may fuse (1..decode_loop), clamped so the
        fusion never costs what it saves:

        - **admission pressure** → 1: an admission waits for the ring to
          drain, and a C-chunk program in it would stretch that wait C×
          (the same rule that caps the ring depth);
        - **remaining budgets**: fuse no more chunks than the longest
          still-live row can fill (rounded up to a power of two so the
          clamp adds log-many program shapes, not one per tail length) —
          the on-device early exit makes over-dispatch cheap, not free;
        - **deadlines** (the PR-4 backstop interaction): one dispatch must
          not outlive the tightest deadline among active OR queued
          requests — the per-turn sweep only runs between dispatches, and
          a C-chunk program that blows through a deadline would push the
          shed/cancel past the server's 2 s DEADLINE_SLACK_S backstop. Estimated from the per-chunk
          dispatch-to-reap EWMA; halved (staying a power of two) until it
          fits.
        """
        c = self.decode_loop
        if c <= 1 or not active:
            return 1
        with self._cond:
            if self._admission_pressure():
                return 1
            # Queued requests with no free slot exert no admission
            # pressure, but their deadline SWEEP runs only between
            # dispatches — a C-chunk dispatch delays their shed by its
            # whole length, so their deadlines clamp C exactly like an
            # active row's would.
            waiting = [r.deadline for r in self._pending
                       if r.deadline is not None]
        rem = max(r.budget - r.emitted - ahead for _, r in active)
        if rem <= 0:
            return 1
        need = -(-rem // n_steps)
        cap = 1
        while cap < need:
            cap <<= 1
        c = min(c, cap)
        deadlines = waiting + [r.deadline for _, r in active
                               if r.deadline is not None]
        if deadlines and self._chunk_ewma_s > 0.0:
            slack = min(deadlines) - time.monotonic()
            while c > 1 and c * self._chunk_ewma_s > max(slack, 0.0):
                c //= 2
        return max(1, c)

    def _fill_inflight(self) -> None:
        target = self._target_depth()
        while len(self._inflight) < target:
            active = [(i, r) for i, r in self._active_rows()
                      if not r.cancel.is_set()]
            if not active:
                return
            depth = len(self._inflight)
            # Planned lengths: host-known emitted counts plus every step
            # already in flight — an upper bound on where rows can be when
            # this chunk runs (rows that finish on device stop short of it).
            ahead = sum(c.tokens_ahead for c in self._inflight)
            if depth > 0 and not any(
                    r.budget - r.emitted > ahead for _, r in active):
                # Dispatching AHEAD of the read is worth it only when some
                # row can still be decoding in this dispatch (the device
                # budget would otherwise mask the whole window off).
                return
            n_steps = max(
                1, min(r.chunk_hint or self.decode_chunk for _, r in active))
            want_lp = any(r.want_lp >= 0 for _, r in active)
            # Program-variant gating (the logprobs pattern): only a batch
            # that actually contains a grammar row pays the constrained
            # variant — its table gathers AND its operand shapes. A batch
            # with none dispatches the exact pre-constrain program.
            constrained = any(r.grammar is not None for _, r in active)
            n_chunks = self._effective_loop(active, n_steps, ahead)
            planned = max(len(r.prompt_ids) + r.emitted for _, r in active)
            planned += ahead
            history = prefill_bucket(
                min(planned + n_steps * n_chunks, self.spec.max_seq),
                self.spec.max_seq)
            key = self._decode_key(n_steps, want_lp, history, constrained,
                                   n_chunks)
            if depth > 0 and not self._program_ready(key):
                # Only dispatch ahead onto a warm program — a first-use
                # history bucket would stall the already-computed older
                # chunks behind a full XLA compile.
                return
            mask = np.zeros((self._rows,), np.int32)
            for i, _ in active:
                mask[i] = 1
            self._mark()
            t0 = time.perf_counter()
            payload = self._dispatch_chunk(mask, n_steps, want_lp, history,
                                           constrained, n_chunks)
            fam = self._family_of(key)
            prog = self._sent(DECODE, fam, history, n_steps * n_chunks,
                              witness=payload)
            self._count_kv_tiles(active, ahead, history, n_steps * n_chunks)
            seq = self._next_seq()
            self._inflight.append(
                _InflightChunk(payload, active, n_steps, t0, history, depth,
                               constrained, n_chunks, family=fam, seq=seq,
                               prog=prog, moe=self._moe_snapshot()))
            FLIGHT.record("dispatch", engine=self._tag, loop="decode", t=t0,
                          seq=seq, family=fam, depth=depth, chunks=n_chunks,
                          steps=n_steps,
                          rids=[r.rid for _, r in active])
            if depth > 0:
                self.n_overlapped += 1
            obs.PIPELINE_DEPTH.set(len(self._inflight))

    def _count_kv_tiles(self, active, ahead: int, history: int,
                        steps: int) -> None:
        """Count what a dispatched decode chunk's ``steps`` steps read of
        the dense cache's history window, in tiles, from the lengths and the
        bucket the host holds (planned lengths: rows that finish on the
        device stop short of them); and, where the spec has a mixer, the
        rows' states its steps rewrote and those that were a live row's."""
        if self.spec.layer_pattern:
            return
        if self.spec.ssm_heads:
            layers = self.spec.n_layers
            self.n_ssm_rows_stepped += layers * self._rows * steps
            self.n_ssm_rows_live += layers * sum(
                min(steps, max(r.budget - r.emitted - ahead, 0))
                for _, r in active)
        tile = decode_tile(history)
        bucket = self._rows * (history // tile) * steps
        self.n_kv_tiles_bucket += bucket
        if not self._kernel_reads(history):
            self.n_kv_tiles_read += bucket
            return
        # entries at the chunk's first step, its own token included (an
        # admitted row's first token sits at position len(prompt)), then one
        # more a step, up to the bucket
        first_step = np.fromiter(
            (len(r.prompt_ids) + r.emitted + ahead for _, r in active),
            np.int64, len(active))
        entries = np.minimum(first_step[:, None] + np.arange(steps), history)
        self.n_kv_tiles_read += int(
            live_tiles(entries, tile, self.spec.sliding_window).sum())

    def _kernel_reads(self, history: int) -> bool:
        """Whether this engine's decode chunk at ``history`` reads the cache
        through the Pallas call: ops/flash_decode's own rule, from what the
        engine holds (the device's platform, the members, the leaf)."""
        if (self.members > 1 or self.kv_pages
                or self.mesh.devices.flat[0].platform != "tpu"):
            return False
        spec = self.spec
        side = self._ck.kv if isinstance(self._ck, StateKV) else self._ck
        return not kernel_refusal((self._rows, spec.n_heads, 1, spec.head_dim),
                                  side, history, sharded=self._sharded)

    def _reap_oldest(self) -> None:
        """Block on the oldest in-flight chunk and deliver its tokens.

        Timing covers the reap interval (blocking fetch + delivery), NOT
        dispatch-to-reap: an overlapped chunk's dispatch stamp predates up
        to K−1 older chunks' device time, so measuring from it would
        inflate DECODE_CHUNK (and overlap the per-request decode spans)
        with pipeline depth. At K=1 the reap starts right after the async
        dispatch, so the interval matches the old dispatch+drain turn; the
        dispatch-to-reap latency is kept as the span's ``inflight`` attr."""
        c = self._inflight.popleft()
        with self._phase("emit"):
            self._deliver_chunk(c)

    def _deliver_chunk(self, c: "_InflightChunk") -> None:
        """The reap of one chunk, as the turn's ``emit`` phase: the blocking
        fetch inside (``reap_block``, _emit_chunk) suspends it, so ``emit``
        is the host's part — tokens to their consumers, then the turn's
        accounting (histograms, recorder, spans) and the finished rows'
        release."""
        t0 = time.perf_counter()
        done, n_exec = self._emit_chunk(c)
        t1 = time.perf_counter()
        obs.DECODE_CHUNK.observe(t1 - t0)
        # The ledger booked the chunk at its landing (_fetch_landing, or
        # the drain's probe): the interval since the landing before it,
        # with the steps dispatched; a megachunk that stopped early ran
        # fewer (one whose rows had all finished still ran a chunk).
        self._led().decode_steps -= c.n_steps * (c.n_chunks - max(1, n_exec))
        FLIGHT.record("reap", engine=self._tag, loop="decode",
                      seq=c.seq, family=c.family or "unknown",
                      depth=c.depth, t_issue=round(c.t0, 6),
                      t_start=round(c.prog.t0, 6),
                      t_ready=round(c.prog.t1, 6),
                      booked_s=round(c.prog.seconds, 6), chunks=n_exec,
                      rids=[r.rid for _, r in c.active])
        obs.PIPELINE_DEPTH.set(len(self._inflight))
        if self.disagg:
            obs.DECODE_GROUP_ACTIVE.set(len(c.active))
        self.n_decode_chunks += 1
        self.n_decode_rows += len(c.active)
        # Megachunk accounting: chunk segments this dispatch actually
        # produced tokens for (the early exit skips the all-dead tail),
        # plus the per-chunk latency EWMA the deadline clamp estimates
        # from. The divisor is the EXECUTED segment count, not the
        # dispatched C — early-exited dispatches ran only n_exec chunks,
        # and dividing by C would bias the estimate low by up to C×,
        # letting a later fused dispatch outlive a deadline the clamp
        # exists to protect. (Dispatch-to-reap still overestimates for
        # overlapped dispatches — conservative, the right direction.)
        self.n_loop_chunks += n_exec
        obs.DECODE_LOOP_CHUNKS.observe(n_exec)
        per_chunk = (t1 - c.t0) / max(1, n_exec)
        self._chunk_ewma_s = (
            per_chunk if self._chunk_ewma_s == 0.0
            else (1 - CHUNK_EWMA_ALPHA) * self._chunk_ewma_s
            + CHUNK_EWMA_ALPHA * per_chunk)
        meta = {}
        if c.moe is not None:
            # of every program since the reap before: this chunk's rows,
            # and the prefill segments dispatched ahead of it
            meta["picks_held"] = c.moe
        if c.constrained:
            meta["constrained"] = sum(
                1 for _, r in c.active if r.grammar is not None)
        if c.n_chunks > 1:
            meta["chunks"] = c.n_chunks
        if self.kv_pages:
            # Per-turn page footprint on the decode span: how many pool
            # pages this request's row actually holds (vs the dense
            # layout's implicit max_seq/page_size rectangle).
            with self._cond:
                chains = {i: len(self._page_alloc.chain(i % self.n_slots)
                                 or ()) for i, _ in c.active}
            meta_pages = chains
        else:
            meta_pages = None
        for i, req in c.active:
            if self._slots[i] is req or i in done:
                extra = (dict(pages=meta_pages[i])
                         if meta_pages is not None else {})
                self._turn_span(req, "decode", t0, t1, steps=c.n_steps,
                                occupancy=len(c.active), history=c.history,
                                depth=c.depth,
                                inflight=round(t0 - c.t0, 6),
                                **meta, **extra)
        if done:
            with self._cond:
                for i, req in c.active:
                    if i in done and self._slots[i] is req:
                        self._release_slot(i, req)

    def _drain_inflight(self) -> None:
        """Reap every in-flight chunk: the pipeline's drain point when no
        row is active and on shutdown."""
        while self._inflight:
            self._reap_oldest()

    def _release_slot(self, i: int, req: _Request) -> None:
        """Free a slot whose request finished/cancelled. Caller holds _cond.
        The cache rows hold K/V for everything but the request's last
        sampled token (never fed back) — that prefix stays reusable; with a
        host prefix store the prefix is additionally queued for a
        device→host snapshot, so it survives the slot being reclaimed."""
        self._slots[i] = None
        self._resident[i] = req.hist[:-1]
        self._paged_release_row(i)
        if req.t_admit is not None:
            # Whole-occupancy wall time feeds the cost model's service
            # EWMA (the predictive shed's drain estimate).
            self.cost_model.observe_service(time.perf_counter() - req.t_admit)
        if self.disagg:
            # A freed decode slot is what the (possibly sleeping) prefill
            # loop waits on to admit its next pending request.
            self._cond.notify_all()
        if req.grammar is not None:
            # The row's device DFA state must return to FREE before an
            # unconstrained request can activate it (a stale grammar state
            # would wrongly mask that request in a mixed constrained
            # batch). Deferred like snapshots: the caller holds _cond, and
            # the reset's first-use compile must not run under the lock.
            self._pending_dfa_resets.append(i)
        self._queue_snapshot(i)

    def _dispatch_chunk(self, mask, n_steps: int, want_lp: bool, history: int,
                        constrained: bool = False, n_chunks: int = 1):
        """Enqueue one decode chunk (non-blocking — jax arrays are futures);
        chains the per-slot device state so further dispatches can follow
        before this one is read. Returns the chunk's output arrays — with
        a leading per-chunk axis when ``n_chunks`` > 1 (megachunk).

        The constrained variant threads the grammar arena tables (read-only
        operands — never donated, shared by every in-flight chunk) and the
        per-row DFA state (donated and chained like the rest of the slot
        state, so a chunk dispatched before its predecessor is read still
        masks from the right states)."""
        faults.fire("engine.decode")
        # Explicit upload of the one host-built operand: the active-row
        # mask. Every other input is already device-resident chained state,
        # so under transfer_guard="disallow" a dispatch performs zero
        # implicit transfers.
        mask = jax.device_put(mask, self._rep)
        if constrained:
            out = self._decode_fn(n_steps, want_lp, history,
                                  tstates=self._g_bucket,
                                  n_chunks=n_chunks)(
                self.weights, mask, self._eos, self._g_trans, self._g_accept,
                self._ck, self._cv, self._token,
                self._lengths, self._keys, self._temp, self._topp, self._topk,
                self._pp, self._fp, self._counts, self._bias,
                self._live, self._budget, self._dfa,
            )
            if want_lp:
                (toks, n_valid, s_lp, top_ix, top_lp, masked, self._ck,
                 self._cv, self._token, self._lengths, self._keys,
                 self._counts, self._live, self._budget, self._dfa) = out
                return (toks, n_valid, s_lp, top_ix, top_lp, masked)
            (toks, n_valid, masked, self._ck, self._cv, self._token,
             self._lengths, self._keys, self._counts, self._live,
             self._budget, self._dfa) = out
            return (toks, n_valid, masked)
        out = self._decode_fn(n_steps, want_lp, history, n_chunks=n_chunks)(
            self.weights, mask, self._eos, self._ck, self._cv, self._token,
            self._lengths, self._keys, self._temp, self._topp, self._topk,
            self._pp, self._fp, self._counts, self._bias,
            self._live, self._budget,
        )
        if want_lp:
            (toks, n_valid, s_lp, top_ix, top_lp, self._ck, self._cv,
             self._token, self._lengths, self._keys, self._counts,
             self._live, self._budget) = out
            return (toks, n_valid, s_lp, top_ix, top_lp)
        (toks, n_valid, self._ck, self._cv, self._token, self._lengths,
         self._keys, self._counts, self._live, self._budget) = out
        return (toks, n_valid)

    def _emit_chunk(self, c: "_InflightChunk"):
        """Block on one dispatched chunk's outputs and deliver its tokens.

        ``n_valid[i]`` (computed ON DEVICE) bounds row i's delivery: a row
        that finished mid-chunk in an earlier in-flight chunk produced
        nothing here, so nothing is discarded. Tokens produced for a row
        the host has since released (cancellation, stop strings — finishes
        the device cannot see) count into ``overrun_tokens_total``.

        A megachunk dispatch (``c.n_chunks`` > 1) arrives with a leading
        per-chunk axis; its segments drain in chunk order — per-chunk
        ``n_valid`` keeps delivery exact (a row that finished in segment 0
        produced nothing in segment 1), and a host-side finish inside
        segment j counts the later segments' tokens for that row as
        overrun (the documented ≤ C−1-chunk waste for cancel/stop-string
        finishes). Plain dispatches are normalized to a 1-segment view of
        the same loop.

        Returns ``(slots that finished in THIS dispatch, segments that
        produced any token)``."""
        active, payload = c.active, c.payload
        with self._phase("reap_block"):
            # The landing: the first observation of the payload in (the
            # incremental drain's ready() probe may have been earlier).
            # The counters' copy ran right behind the chunk and is in with
            # it.
            c.prog.witness = None  # the fetch itself is the chunk's landing
            fetched = self._fetch_landing(
                c.prog if c.moe is None else c.moe[2], *payload)
            if c.moe is not None:
                c.moe = self._moe_note(c.moe)
        t_fetch = time.perf_counter()
        if c.constrained:
            # The grammar variant's trailing per-step masked-entry counts
            # ride the fetch the tokens already require — no extra sync.
            *fetched, masked = fetched
            n_masked = int(np.asarray(masked).sum())
            if n_masked:
                self.n_constrain_masked += n_masked
                obs.CONSTRAIN_MASKED_TOKENS.inc(n_masked)
        if len(fetched) == 5:
            toks, n_valid, s_lp, top_ix, top_lp = fetched
        else:
            toks, n_valid = fetched
            s_lp = top_ix = top_lp = None
        toks, n_valid = np.asarray(toks), np.asarray(n_valid)
        if c.n_chunks == 1:
            toks, n_valid = toks[None], n_valid[None]
            if s_lp is not None:
                s_lp, top_ix, top_lp = (
                    np.asarray(s_lp)[None], np.asarray(top_ix)[None],
                    np.asarray(top_lp)[None])
        done: set[int] = set()
        n_exec = 0
        for ci in range(toks.shape[0]):
            nv = n_valid[ci]
            if not int(nv.sum()):
                continue  # all-dead segment (on-device early exit)
            n_exec += 1
            for i, req in active:
                k = int(nv[i])
                if not k:
                    continue
                if self._slots[i] is not req or i in done:
                    # Released/re-admitted while in flight, or finished
                    # host-side in an earlier segment of this dispatch:
                    # every token the device still produced is overrun.
                    self.n_overrun += k
                    continue
                before = req.emitted
                for j in range(k):
                    if req.want_lp >= 0 and s_lp is not None:
                        req.lp.append((float(s_lp[ci, i, j]),
                                       top_ix[ci, i, j], top_lp[ci, i, j]))
                    if self._emit(req, int(toks[ci, i, j])):
                        done.add(i)
                        break
                self.n_overrun += k - (req.emitted - before)
        # Host-drain gap: payload-on-host to last token in consumer queues.
        self.drain_gap_s += time.perf_counter() - t_fetch
        return done, n_exec

    def _emit(self, req: _Request, tok: int) -> bool:
        """Deliver one token; returns True when the request just finished.

        Preemption replay (``req.replay`` non-None): the resumed row is
        regenerating tokens the consumer already received. Each one is
        byte-compared against the recorded expectation and swallowed —
        host state (``hist``) advances exactly as on first delivery, but
        nothing reaches ``out`` and nothing counts as a new token. A
        mismatch means the determinism contract broke
        (token sequence = f(prompt, seed, sampler)); the stream fails
        loudly rather than silently forking the delivered text."""
        if req.cancel.is_set():
            self.n_cancelled += 1
            req.out.put(("end", None))
            return True
        replaying = req.replay is not None
        if replaying:
            expect = req.replay.pop(0)
            if not req.replay:
                req.replay = None
            if tok != expect:
                req.replay = None
                req.out.put(("err", ReplayDivergence(
                    req.emitted, tok, expect)))
                req.cancel.set()
                return True
        req.emitted += 1
        req.hist.append(tok)
        if replaying:
            # Already delivered before the preemption: swallowed, not
            # re-queued, not re-counted (an EOS never appears in a replay
            # expectation — it would have ended the stream back then). A
            # cross-replica resume journal CAN cover the whole budget
            # though (the replica died on the last token): end as length.
            self.n_replayed_tokens += 1
            if req.emitted >= req.budget:
                req.out.put(("end", "length"))
                return True
            return False
        self.n_tokens += 1
        if req.t_first is None:
            req.mark_first_token()
        req.out.put(("tok", tok))
        if req.eos_id is not None and tok == req.eos_id:
            req.out.put(("end", "stop"))
            return True
        if req.emitted >= req.budget:
            req.out.put(("end", "length"))
            return True
        return False

    def _fail_all(self, exc: Exception,
                  doomed: "list[_Request] | None" = None) -> None:
        """Recover from a scheduler-turn failure with a bounded blast radius:
        only requests whose device state was entangled with the failed
        dispatch — active slots, in-flight admissions, plus any ``doomed``
        extras the caller names — fail. Requests still in ``_pending`` were
        never dispatched: they STAY queued (bounded by their deadlines) and
        admit normally once the device state is rebuilt. Each call counts
        one engine rebuild and feeds the failure breaker — a poison-pill
        retry storm trips it and new admissions shed with 503 until a
        cooldown probe admission succeeds."""
        with self._cond:
            doomed = list(doomed or [])
            doomed += [r for r in self._slots if r is not None]
            doomed += [a.req for a in self._admitting]
            for a in self._admitting:
                # Disagg: queued handoff pieces reference re-issued claims
                # after the rebuild — the drain must drop them.
                a.dead = True
                if a.acct is not None:
                    a.acct.close()
            self._handoffs.clear()
            self._slots = [None] * self._rows
            self._admitting = []
            self._claimed = set()
            self._resident = [[] for _ in range(self._rows)]
            # Deferred snapshots reference pre-failure cache rows — drop
            # them (already-dispatched slices fail harmlessly in the
            # worker). The store's existing host copies stay valid.
            self._snap_backlog = max(
                0, self._snap_backlog - len(self._pending_snaps))
            self._pending_snaps = []
            # The rebuild below re-zeroes the per-row DFA state wholesale;
            # row-level resets queued before the failure are moot.
            self._pending_dfa_resets = []
            # Freed slots are admission capacity: wake the prefill loop
            # (disagg) so queued requests admit once the rebuild lands.
            self._cond.notify_all()
        # In-flight chunk payloads reference (possibly poisoned) device
        # arrays from before the failure — drop them unread.
        self._inflight.clear()
        obs.PIPELINE_DEPTH.set(0)
        self.n_rebuilds += 1
        # The post-mortem artifact (docs/observability.md): the ring holds
        # the dispatch/admission/deadline timeline that led here — dumped
        # BEFORE the rebuild so the artifact ends at the failure.
        FLIGHT.record("fail-all", engine=self._tag, loop="decode",
                      error=f"{type(exc).__name__}: {exc}"[:200],
                      doomed=len(doomed), rids=[r.rid for r in doomed])
        FLIGHT.dump("fail-all")
        self._record_breaker_failure()
        # Wake consumers first — the state rebuild below can itself fail, and
        # doomed requests must never hang on their queues.
        self.n_failures += len(doomed)
        for r in doomed:
            now = time.perf_counter()
            r.span("engine-failure", now, now,
                   error=type(exc).__name__, contained=False)
            r.out.put(("err", exc))
        # The failed call may have consumed its donated buffers; rebuild the
        # device state so the engine survives for subsequent requests — but
        # not mid-shutdown, where a rebuild would reallocate the multi-GB
        # cache the shutdown exists to release.
        if not self._stop:
            self._init_device_state()
            if self.zero_drain and not self._stage_state_ok():
                # The zero-drain staging cache shares this scheduler's
                # turn: a failure that consumed it must not leave the next
                # admission's segments dispatching into deleted arrays.
                # (Disagg staging belongs to the prefill loop and rebuilds
                # through _contain_prefill_failure instead.)
                self._init_stage_state()


# ---- engine sharing -------------------------------------------------------
#
# N configured backends frequently reference the same model (the reference's
# shipped config points all 3 backends at one provider, config.yaml:6-20).
# Engines are cached so those backends share one set of weights on device —
# and, with continuous batching, their concurrent requests co-batch instead
# of serializing.

_ENGINES: dict[tuple, InferenceEngine] = {}
_ENGINES_LOCK = threading.Lock()
# Every live engine (cached or directly constructed) for bulk shutdown.
_ALL_ENGINES: "weakref.WeakSet[InferenceEngine]" = weakref.WeakSet()


def shutdown_all_engines(timeout: float = 30.0) -> None:
    """Shut down every live engine and clear the shared-engine cache —
    server teardown and test-suite module cleanup."""
    for eng in list(_ALL_ENGINES):
        eng.shutdown(timeout=timeout)
    with _ENGINES_LOCK:
        _ENGINES.clear()


def release_engine(engine: "InferenceEngine", timeout: float = 30.0) -> None:
    """Shut ONE engine down and evict it from the shared cache — the hot
    reload path for a backend whose edit dropped or re-specced it. Without
    the eviction the strong ``_ENGINES`` reference keeps weights, KV cache,
    and the scheduler thread resident forever (at 7B scale the next engine
    build then OOMs the device)."""
    with _ENGINES_LOCK:
        for key, eng in list(_ENGINES.items()):
            if eng is engine:
                del _ENGINES[key]
    engine.shutdown(timeout=timeout)


def get_engine(
    spec: ModelSpec,
    mesh: Mesh | None = None,
    *,
    seed: int = 0,
    decode_pipeline: int = DEFAULT_DECODE_PIPELINE,
    decode_loop: int = DEFAULT_DECODE_LOOP,
    n_slots: int = DEFAULT_SLOTS,
    prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
    max_pending: int = DEFAULT_MAX_PENDING,
    quant: str | None = None,
    prefix_cache: bool = True,
    prefix_store: str | None = None,
    prefix_store_bytes: int = DEFAULT_PREFIX_STORE_BYTES,
    prefix_store_chunk: int = 0,
    members: int = 1,
    kv_quant: str | None = None,
    sp_impl: str = "ring",
    prefill_mesh: Mesh | None = None,
    zero_drain: bool = False,
    kv_pages: bool = False,
    kv_page_size: int = 0,
    kv_pool_pages: int = 0,
    qos: bool = False,
    member_seeds: str = "distinct",
    quorum_dedup: bool = False,
    prepare: bool = False,
) -> InferenceEngine:
    """Engines are keyed by weight identity (spec, seed, mesh, quant,
    members) plus the cache representation (kv_quant) —
    dispatch knobs like decode_chunk are per-call, so two backends that differ
    only in chunking share one set of weights on device. ``n_slots``/
    ``prefill_chunk``/``max_pending``/``decode_pipeline``/``decode_loop``/
    ``prefix_store*``
    (structural properties of the preallocated cache and the scheduler)
    apply at first construction; later callers share the existing engine
    as-is. ``prefix_cache`` is NOT structural: a
    ``prefix_cache=0`` from ANY backend disables reuse on the shared engine
    (an explicit opt-out wins over a sharing default). ``qos`` is not
    structural either — the scheduler policy is pure host state, no device
    program or cache layout depends on it, so it stays OUT of the key
    (qos=0 and qos=1 URLs share one engine, and pre-QoS cache keys are
    byte-identical); an explicit ``qos=1`` from any backend enables the
    policy on the shared engine (opt-in wins, mirroring prefix_cache).
    ``prepare`` (engine/prepare.py: the stored programs loaded on a pool
    of threads, beside the weights' init) is not structural either, and
    acts where the engine is built: every serving caller passes the same."""
    mesh = mesh or single_device_mesh()
    from quorum_tpu.parallel.mesh import AXIS_SP as _SP

    # sp_impl is inert without an sp axis — normalize it out of the key so
    # equivalent configs share one engine (and one set of weights).
    sp_key = sp_impl if dict(mesh.shape).get(_SP, 1) > 1 else None
    key = (spec, seed, quant or None,
           max(1, int(members)), kv_quant or None, sp_key,
           tuple(sorted(mesh.shape.items())),
           tuple(map(str, mesh.devices.flat)),
           # disagg is structural: the prefill group carries a second
           # weight copy + staging cache, so colocated and disaggregated
           # URLs must never share one engine.
           tuple(map(str, prefill_mesh.devices.flat))
           if prefill_mesh is not None else None,
           # zero_drain is structural too: the staging cache + staged
           # admission routing exist (or not) at construction, and a
           # drain-based URL must never silently serve zero-drain (or
           # vice versa — the cache-key pin tests depend on it).
           bool(zero_drain),
           # Paged KV is structural: the cache LAYOUT (page pool + table
           # vs dense rectangle) exists at construction, so a dense URL
           # must never share a paged engine — and the page geometry is
           # part of the identity for the same reason n_slots would be if
           # it reshaped the cache.
           (bool(kv_pages), int(kv_page_size), int(kv_pool_pages))
           if kv_pages else None,
           # member_seeds is WEIGHT identity (shared vs distinct init
           # seeds change every stacked leaf), and quorum_dedup is
           # structural (the dedup admit program + counters exist at
           # construction) — a dedup URL must never share a non-dedup
           # engine or vice versa (docs/quorum.md).
           member_seeds if max(1, int(members)) > 1 else None,
           bool(quorum_dedup))
    with _ENGINES_LOCK:
        eng = _ENGINES.get(key)
        if eng is None:
            eng = InferenceEngine(
                spec, mesh, seed=seed, n_slots=n_slots,
                decode_pipeline=decode_pipeline,
                decode_loop=decode_loop,
                prefill_chunk=prefill_chunk, max_pending=max_pending,
                quant=quant,
                prefix_cache=prefix_cache, prefix_store=prefix_store,
                prefix_store_bytes=prefix_store_bytes,
                prefix_store_chunk=prefix_store_chunk,
                members=members, kv_quant=kv_quant, sp_impl=sp_impl,
                prefill_mesh=prefill_mesh, zero_drain=zero_drain,
                kv_pages=kv_pages, kv_page_size=kv_page_size,
                kv_pool_pages=kv_pool_pages, qos=qos,
                member_seeds=member_seeds, quorum_dedup=quorum_dedup,
                prepare=prepare,
            )
            _ENGINES[key] = eng
        else:
            eng.prefix_cache = eng.prefix_cache and bool(prefix_cache)
            eng.qos = eng.qos or bool(qos)  # an explicit opt-in wins
        return eng


def get_engine_from_ckpt(
    ckpt_path: str,
    mesh: Mesh | None = None,
    *,
    dtype: str | None = None,
    decode_pipeline: int = DEFAULT_DECODE_PIPELINE,
    decode_loop: int = DEFAULT_DECODE_LOOP,
    n_slots: int = DEFAULT_SLOTS,
    prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
    max_pending: int = DEFAULT_MAX_PENDING,
    quant: str | None = None,
    prefix_cache: bool = True,
    prefix_store: str | None = None,
    prefix_store_bytes: int = DEFAULT_PREFIX_STORE_BYTES,
    prefix_store_chunk: int = 0,
    kv_quant: str | None = None,
    sp_impl: str = "ring",
    prefill_mesh: Mesh | None = None,
    zero_drain: bool = False,
    kv_pages: bool = False,
    kv_page_size: int = 0,
    kv_pool_pages: int = 0,
    qos: bool = False,
    prepare: bool = False,
) -> InferenceEngine:
    """Engine over a local HF checkpoint; keyed by (resolved path, mesh)
    so N backends pointing at one checkpoint share the loaded weights on
    device."""
    import os

    from quorum_tpu.models.hf_loader import load_hf_checkpoint

    mesh = mesh or single_device_mesh()
    resolved = os.path.realpath(ckpt_path)
    # Normalize: dtype=None and an explicit dtype equal to the default must
    # hit the same cache entry (else the checkpoint sits in HBM twice).
    eff_dtype = dtype or ModelSpec().dtype
    from quorum_tpu.parallel.mesh import AXIS_SP as _SP

    sp_key = sp_impl if dict(mesh.shape).get(_SP, 1) > 1 else None
    key = ("ckpt", resolved, eff_dtype, quant or None, kv_quant or None,
           sp_key,
           tuple(sorted(mesh.shape.items())),
           tuple(map(str, mesh.devices.flat)),
           tuple(map(str, prefill_mesh.devices.flat))
           if prefill_mesh is not None else None,
           bool(zero_drain),
           (bool(kv_pages), int(kv_page_size), int(kv_pool_pages))
           if kv_pages else None)
    with _ENGINES_LOCK:
        eng = _ENGINES.get(key)
        if eng is None:
            spec, params = load_hf_checkpoint(resolved, dtype=dtype)
            eng = InferenceEngine(
                spec, mesh, params=params, n_slots=n_slots,
                decode_pipeline=decode_pipeline,
                decode_loop=decode_loop,
                prefill_chunk=prefill_chunk, max_pending=max_pending,
                quant=quant,
                prefix_cache=prefix_cache, prefix_store=prefix_store,
                prefix_store_bytes=prefix_store_bytes,
                prefix_store_chunk=prefix_store_chunk,
                kv_quant=kv_quant,
                sp_impl=sp_impl, prefill_mesh=prefill_mesh,
                zero_drain=zero_drain,
                kv_pages=kv_pages, kv_page_size=kv_page_size,
                kv_pool_pages=kv_pool_pages, qos=qos, prepare=prepare,
            )
            _ENGINES[key] = eng
        else:
            eng.prefix_cache = eng.prefix_cache and bool(prefix_cache)
            eng.qos = eng.qos or bool(qos)  # an explicit opt-in wins
        return eng
