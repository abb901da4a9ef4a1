"""TpuBackend: an in-process JAX model behind the Backend protocol.

The reference's only backend type is a remote HTTP service
(/root/reference/src/quorum/oai_proxy.py:142-259). ``tpu://`` URLs replace the
network hop with a local compiled model: requests are tokenized, run through
the engine's prefill/decode programs on the TPU mesh, and detokenized back
into OpenAI-shaped responses — with *true* incremental streaming (tokens leave
the device per decode-chunk), fixing the reference's pseudo-streaming
(SURVEY.md §2 quirk 1).

URL grammar:  ``tpu://<model-id>?<spec overrides>&<engine options>``
  spec overrides   any ModelSpec field (n_layers=2, d_model=64, ...)
  disagg=P+D       disaggregated prefill/decode serving (default off): the
                   first P local devices become the PREFILL group (second
                   weight copy + staging KV cache; every admission rides
                   chunked prefill there) and the next D the DECODE group
                   (slot cache + the decode_pipeline/decode_loop ring); a
                   completed admission's KV prefix hands off device→device
                   chunk-by-chunk into its claimed decode slot
                   (quorum_tpu/cache/kv_transfer.py), overlapping the next
                   chunk's prefill. Admission bursts stop stretching
                   streaming inter-token gaps: the decode ring keeps full
                   depth under any admission pressure. Structural; builds
                   its own per-group tp meshes, so tp=/dp=/sp= do not
                   compose; requires chunked prefill
                   (prefill_chunk >= 16). See docs/tpu_backends.md for the
                   interaction matrix
  zero_drain=0|1   zero-drain continuous batching (default 0): the disagg
                   admission split applied WITHIN one device group. Every
                   admission prefills into a same-mesh staging cache on an
                   independent dispatch chain and the new row's KV injects
                   into its claimed slot at a reap boundary — the
                   decode_pipeline=K × decode_loop=C ring keeps full depth
                   through admission bursts instead of clamping to 1
                   (quorum_tpu_admission_stall_seconds_total is
                   structurally 0). Tokens are identical to the
                   drain-based engine's for dense models (admissions ride
                   the chunked register path). Structural (part of the
                   engine cache key); requires chunked prefill
                   (prefill_chunk >= 16); does not compose with disagg=
                   (zero-drain is structural there). See
                   docs/tpu_backends.md for the interaction matrix
  kv_pages=0|1     paged KV slot memory (default 0): the dense
                   [n_slots, max_seq] cache rectangle becomes a page pool
                   + per-row page table — rows hold pages proportional to
                   their actual length, so short-stream mixes fit many
                   more concurrent rows in the same HBM, and tier-0
                   prefix reuse becomes refcounted page ALIASING
                   (copy-on-write boundary page) instead of byte copies.
                   Admission reserves a row's full span up front: pool
                   exhaustion sheds at admission (503 + Retry-After),
                   never mid-stream. Structural (part of the engine cache
                   key); composes with kv_quant=int8, members=M and tp=;
                   rejected with sp>1. See
                   docs/tpu_backends.md for the interaction matrix
  kv_page_size=    tokens per KV page (default: prefill_chunk, else
                   min(64, max_seq)); power of two dividing max_seq
  kv_pool_pages=   physical pages in the pool (default:
                   n_slots × max_seq / page_size — the dense
                   rectangle's worth; set lower to oversubscribe slots
                   against actual lengths)
  tp=, dp=, sp=    mesh shape (default: single device); sp>1 runs admission
  sp_impl=         sp>1 attention strategy: "ring" (default — O(S/sp)
                   memory, KV blocks ppermute the ICI ring) or "ulysses"
                   (head<->sequence all-to-alls, full-seq local attention;
                   supports sliding-window specs, needs head counts
                   divisible by sp)
                   prefill as ring attention with the prompt sequence
                   sharded over the sp axis (long-context serving)
  seed=            weight-init seed (distinct seeds ≈ distinct quorum members)
  decode_chunk=    tokens per device dispatch (default 8)
  decode_pipeline= decode-dispatch ring depth (default 2): the scheduler
                   keeps up to K decode chunks in flight on the device and
                   blocks only on the oldest, hiding the host turnaround
                   (device_get + detok + SSE + scheduling) behind device
                   time. 1 = fully synchronous dispatch. Safe at any depth:
                   EOS / token-budget finishes are detected ON DEVICE
                   inside the chunk, so rows never produce overrun tokens
                   (engine metric overrun_tokens_total stays 0 for them).
                   Structural: applies when this backend constructs the
                   engine; backends sharing an engine share its depth
  decode_loop=C    megachunk decode (default 1 = off; floored to a power
                   of two so the per-dispatch clamps stay within log-many
                   program shapes): ONE dispatch covers
                   up to C decode chunks fused into a device-resident loop
                   with an on-device all-rows-finished early exit — the
                   chunk-dispatch boundary itself comes off the token
                   critical path ("Kernel Looping", PAPERS.md); the host
                   drains the returned [C, batch, chunk] token buffer
                   segment by segment. decode_loop=1 compiles the exact
                   unfused programs (cache-key pinned). Composes with
                   decode_pipeline=K (C chunks per in-flight entry); the
                   effective C self-clamps under admission pressure, short
                   remaining budgets, and tight request deadlines.
                   Cancel/stop-string finishes may waste up to C-1 chunks
                   (counted in overrun_tokens_total). Structural like
                   decode_pipeline
  slots=           concurrent batch width of the engine's KV cache (default 4;
                   applies when this backend constructs the engine — backends
                   sharing an engine share its slot count)
  prefill_chunk=   chunked-prefill segment size (default 512): prompts longer
                   than this prefill in segments interleaved with decode
                   chunks, so a long admission can't stall active streams
  queue=           admission queue bound (default 128); a full queue rejects
                   with 503 instead of growing without limit
  qos=0|1          QoS scheduler (default 0 = FIFO, docs/scheduling.md):
                   weighted-fair admission across priority classes
                   (interactive/batch/background — the 'priority' body
                   knob, else derived from deadline headroom), earliest-
                   deadline-headroom-first within a class, predictive
                   infeasible-deadline shed (503 + honest Retry-After),
                   and mid-decode preemption: an interactive admission
                   with no free slot parks a lower-class resident row at
                   a reap boundary and resumes it later token-for-token
                   identical (deterministic replay — no extra device
                   programs). NOT structural: pure host policy, outside
                   the engine cache key; qos=0/qos=1 URLs share one
                   engine with opt-in winning
  quant=int8       weight-only int8 with per-channel scales (models/quant.py):
                   halves weight HBM bytes/token (decode is bandwidth-bound →
                   up to 2× decode tokens/s) and weight HBM capacity
                   (llama-3-8b fits one 16 GB v5e at ~8.1 GB)
  kv_quant=int8    int8 KV cache (per-token scales, native int8 q·K / p·V
                   decode contractions): halves cache HBM capacity (at 8B,
                   an 8k window drops 1.07 → 0.54 GB per slot) AND the
                   cache bytes each long-context decode step streams.
                   Orthogonal to quant= (compose both for the smallest
                   footprint)
  members=M        stacked fan-out (default 1 = off): backends whose URLs
  member=i         agree on ``members=M`` (and the base seed/spec) share ONE
                   engine holding M independently-seeded weight sets
                   (seed..seed+M-1) stacked on device (block leaves
                   [L, M, …], the rest [M, …]); ``member=i``
                   selects which weight set serves THIS backend. Each member
                   keeps its own slots/sampler state and produces its own
                   stream, but every decode chunk —
                   and coalesced same-bucket admissions — advance ALL
                   members in one dispatch: an N-model quorum pays N× the
                   compute, not N× the per-chunk host turnaround
  member_seeds=    ``distinct`` (default) seeds member i with seed+i;
                   ``shared`` stacks M copies of the SAME weights (all
                   members seed identically) — the diversity then comes
                   from per-member sampling streams, and the shared
                   weights are what make ``quorum_dedup=1`` sound
  quorum_dedup=1   shared-prefix member dedup (docs/quorum.md): when a
                   full member group admits the same prompt, prefill it
                   ONCE on member 0's weights and broadcast the KV into
                   all M cache rows — prefill FLOPs drop ~M×. Requires
                   ``member_seeds=shared`` (distinct weights produce
                   distinct KV) and is structural (engine-construction
                   time); counted by quorum_tpu_quorum_dedup_tokens_total
  prefix_cache=0   disable automatic prefix caching (default on): a request
                   whose prompt prefix is already resident in a free slot's
                   KV cache admits into that slot and prefills only the
                   suffix — multi-turn histories re-prefill nothing
  prefix_store=host    tiered KV prefix store (default off): released
                   slots' KV prefixes are snapshotted device→host into a
                   chunk-granular trie (byte-budget LRU), and an admission
                   whose store match beats the slot-resident LCP restores
                   the prefix host→device and prefills only the tail — a
                   conversation's history survives its slot being
                   reclaimed under churn (docs/prefix_cache.md). Holds the
                   cache's NATIVE representation, so kv_quant=int8 halves
                   host bytes too. Structural (applies when this backend
                   constructs the engine); rejected with members=/sp>1
                   and with prefill_chunk too small to chunk (the restore
                   rides chunked prefill)
  prefix_store_bytes=  host byte budget for the store (default 1g);
                   accepts a plain byte count or a k/m/g binary suffix
                   (e.g. 512m). Least-recently-used chunks evict past it
  prefix_store_chunk=  store retention granularity in tokens (default:
                   the engine's prefill_chunk). Only whole chunks are
                   stored/matched/evicted
  max_tokens=      default completion budget when the request has none

Contract parity with the dispatcher: configured model overrides the request
model (oai_proxy.py:161-176 via prepare_body); responses are tagged with
``"backend"`` (:212); failures normalize to BackendError (:231-259).

Structured output: ``response_format`` of type ``json_object`` /
``json_schema`` / ``regex`` (extension) compiles to a token-level DFA
(quorum_tpu/constrain/, cached per grammar+tokenizer) that the engine
threads through the decode chunk ON DEVICE — guaranteed-valid output with
zero extra host syncs at any decode_pipeline depth
(docs/structured_output.md). Unsupported schemas are 400s; a grammar no
token sequence can satisfy under this tokenizer is a 422 grammar_error.
"""

from __future__ import annotations

import asyncio
import logging
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, AsyncIterator

import numpy as np

from quorum_tpu import oai
from quorum_tpu.backends.base import BackendError, CompletionResult, prepare_body
from quorum_tpu.compile_cache import cache_enabled
from quorum_tpu.config import CHUNKS_ONLY, BackendSpec
from quorum_tpu.engine.engine import (
    DEFAULT_DECODE_LOOP,
    DEFAULT_DECODE_PIPELINE,
    DEFAULT_MAX_PENDING,
    DEFAULT_PREFILL_CHUNK,
    DEFAULT_SLOTS,
    _CKPT_MEMBERS_ERROR,
    DeadlineExceeded,
    EngineBreakerOpen,
    GenerationResult,
    GrammarArenaFull,
    InferenceEngine,
    QueueFullError,
    ReplayDivergence,
    get_engine,
    get_engine_from_ckpt,
)
from quorum_tpu.engine.tokenizer import get_tokenizer
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.observability import current_trace, trace_span
from quorum_tpu.telemetry.recorder import RECORDER
from quorum_tpu.ops.sampling import SamplerConfig
from quorum_tpu.parallel.mesh import MeshConfig, make_mesh, single_device_mesh

logger = logging.getLogger(__name__)

# threads in asyncio's default executor at most, whatever the host:
# ``min(32, os.cpu_count() + 4)`` (concurrent.futures.ThreadPoolExecutor)
ASYNCIO_DEFAULT_POOL_MAX = 32


def _parse_bytes_opt(name: str, raw: str) -> int:
    """Byte-count URL option: a plain integer or a k/m/g binary suffix
    (``prefix_store_bytes=512m``). Strict — a typo must fail at config
    time, not silently size a cache to zero."""
    val = str(raw).strip().lower()
    mult = 1
    if val and val[-1] in "kmg":
        mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}[val[-1]]
        val = val[:-1]
    try:
        out = int(val) * mult
    except ValueError:
        raise ValueError(
            f"invalid {name}={raw!r} (an integer byte count, optionally "
            "with a k/m/g suffix)") from None
    if out < 1:
        raise ValueError(f"invalid {name}={raw!r} (must be positive)")
    return out


# URL options that once selected a decode program and no longer exist: the
# value that still means "off", the PR that removed the option, and what to
# do instead. A URL that still sets one must fail at config time, not
# quietly serve something else.
_CHUNKS_ONLY = f"drop the option: {CHUNKS_ONLY}"
_REMOVED_OPTIONS = (
    ("ensemble", "1", 32, "use members=M backends under the concatenate or "
                          "aggregate strategy (one stacked engine, M streams)"),
    ("pp", "1", 32, "use tp= to shard a served model (pp remains the "
                    "training axis)"),
    ("spec_decode", "0", 51, _CHUNKS_ONLY),
    ("spec_model", "", 51, _CHUNKS_ONLY),
    ("spec_ckpt", "", 51, _CHUNKS_ONLY),
    ("spec_seed", "0", 51, _CHUNKS_ONLY),
)


def _parse_bool_opt(name: str, raw: str) -> bool:
    """Strict boolean URL option: a typo must not silently mean 'enabled'."""
    val = str(raw).lower()
    if val in ("1", "true", "yes"):
        return True
    if val in ("0", "false", "no"):
        return False
    raise ValueError(f"invalid {name}={raw!r} (use 0/1, true/false, yes/no)")


def _request_sampler(body: dict[str, Any]) -> SamplerConfig:
    """Map OpenAI request knobs onto the on-device sampler.

    Sampler knobs are per-slot *arrays* in one shared decode program
    (ops.sampling.sample_token_rows), so distinct values no longer compile
    distinct programs; the 2-decimal quantization is kept purely as wire
    hygiene (an output-visible contract since round 2)."""
    temperature = _request_number(body, "temperature", 1.0)
    top_p = _request_number(body, "top_p", 1.0)
    return SamplerConfig(
        temperature=round(temperature, 2),
        top_p=round(top_p, 2),
    )


def _request_number(body: dict[str, Any], key: str, default: float) -> float:
    """Client-controlled numeric knob → float, or a 400 (not a 500) on junk."""
    val = body.get(key)
    if val is None:
        return default
    try:
        out = float(val)
        if not math.isfinite(out):
            raise ValueError("must be finite")
    except (TypeError, ValueError):
        raise _invalid_request(f"Invalid value for {key!r}: {val!r}") from None
    return out


def _reject_mixed(items: list, field: str) -> None:
    """Strings and token arrays cannot mix in one request (the documented
    contract, matching OpenAI) — per-item validation alone would silently
    accept the mix."""
    if (any(isinstance(x, str) for x in items)
            and any(isinstance(x, list) for x in items)):
        raise _invalid_request(
            f"'{field}' must not mix strings and token arrays")


def _top_dict(pairs) -> dict[str, float]:
    """Legacy ``top_logprobs`` dict keyed by token TEXT: distinct ids can
    decode to the same text (byte tokens inside a multi-byte character all
    render the replacement char) — the first (highest, top_k order) logprob
    wins rather than a later one silently overwriting it."""
    out: dict[str, float] = {}
    for text, lp in pairs:
        if text not in out:
            out[text] = float(lp)
    return out


class _DrainParked(RuntimeError):
    """The engine parked this request mid-generation (drain with park=1).
    A streaming consumer surfaces it as finish_reason ``"parked"`` — the
    router's cue to resume on a sibling — but a NON-streaming consumer
    has no resume journal, so the partial text must become a retryable
    503 (the router re-places the whole request), never a truncated
    200."""


def _invalid_request(message: str) -> BackendError:
    return BackendError(
        message,
        status_code=400,
        body=oai.error_body(message, type_="invalid_request_error", code=400),
    )


def _overloaded(name: str, why: str = "admission queue full",
                retry_after: float = 1.0) -> BackendError:
    """503 with the actual saturated resource named — an operator debugging
    the error must not tune the chat queue when the scoring gate tripped.
    Every overload response carries ``Retry-After`` (docs/robustness.md):
    load balancers and SDK retry loops key their backoff on it."""
    msg = f"Backend {name} is overloaded: {why}; retry later"
    return BackendError(
        msg, status_code=503,
        body=oai.error_body(msg, type_="overloaded_error", code=503),
        headers={"Retry-After": str(max(1, math.ceil(retry_after)))},
    )


def _breaker_open(name: str, e: EngineBreakerOpen) -> BackendError:
    """503 + Retry-After while the engine's failure breaker rejects new
    admissions (repeated device-state rebuilds — docs/robustness.md)."""
    return _overloaded(
        name, f"engine circuit breaker is open ({e})",
        retry_after=e.retry_after)


def _deadline_error(name: str, e: DeadlineExceeded) -> BackendError:
    """Map an engine deadline miss onto the HTTP contract: shed from the
    queue (the engine never served it) → 503 + Retry-After, safe to retry
    elsewhere; cancelled after admission → 504, the work is lost."""
    if e.stage == "queue":
        return _overloaded(
            name, "request deadline expired before admission (shed)")
    msg = (f"Backend {name} deadline exceeded during {e.stage}; "
           "partial work discarded")
    return BackendError(
        msg, status_code=504,
        body=oai.error_body(msg, type_="timeout_error", code=504),
        headers={"Retry-After": "1"},
    )


def _timeout_error(name: str, timeout: float) -> BackendError:
    """The asyncio-side wait outlived the backend timeout (the backstop
    behind the engine-enforced deadline): 504, counted as a backend-stage
    deadline miss."""
    from quorum_tpu.observability import DEADLINE_EXCEEDED

    DEADLINE_EXCEEDED.inc(stage="backend")
    msg = f"Backend {name} timed out after {timeout}s"
    return BackendError(
        msg, status_code=504,
        body=oai.error_body(msg, type_="timeout_error", code=504),
        headers={"Retry-After": "1"},
    )


def _grammar_unsatisfiable(name: str, e: Exception) -> BackendError:
    """422 grammar_error: the response_format grammar compiled but admits
    no completion under this backend's tokenizer — every path dead-ends
    before an accept state (e.g. a required character has no producing
    token). Distinct from a 400: the request was well-formed; the
    (grammar, tokenizer) pair cannot be served (docs/structured_output.md)."""
    msg = (f"Backend {name} cannot satisfy response_format: {e}")
    return BackendError(
        msg, status_code=422,
        body=oai.error_body(msg, type_="grammar_error", code=422),
    )


def _stop_list(body: dict[str, Any]) -> list[str]:
    stop = body.get("stop")
    if stop is None:
        return []
    if isinstance(stop, str):
        return [stop]
    if isinstance(stop, list):
        return [s for s in stop if isinstance(s, str)]
    raise _invalid_request(f"Invalid value for 'stop': {stop!r}")


class _StopMatcher:
    """Incremental stop-string scanner: withholds text that could be the
    start of a stop sequence across delta boundaries."""

    def __init__(self, stops: list[str]):
        self.stops = [s for s in stops if s]
        self._tail = ""
        self.hit = False
        self._max = max((len(s) for s in self.stops), default=0)

    def feed(self, text: str) -> str:
        if not self.stops:
            return text
        if self.hit:
            return ""
        buf = self._tail + text
        # earliest occurrence across all stop strings (OpenAI semantics)
        first = min((i for i in (buf.find(s) for s in self.stops) if i >= 0), default=-1)
        if first >= 0:
            self.hit = True
            self._tail = ""
            return buf[:first]
        # emit all but the longest suffix that prefixes some stop string
        keep = 0
        for k in range(min(self._max - 1, len(buf)), 0, -1):
            if any(s.startswith(buf[-k:]) for s in self.stops):
                keep = k
                break
        self._tail = buf[len(buf) - keep :] if keep else ""
        return buf[: len(buf) - keep] if keep else buf

    def flush(self) -> str:
        out, self._tail = self._tail, ""
        return "" if self.hit else out


class TpuBackend:
    """One local model (engine + tokenizer) serving the Backend protocol."""

    requires_auth = False  # local model: no upstream credential needed

    def __init__(
        self,
        name: str,
        engine: InferenceEngine,
        *,
        model: str = "",
        model_id: str = "",
        default_max_tokens: int = 64,
        decode_chunk: int | None = None,
        tokenizer_path: str | None = None,
        rng_offset: int = 0,
        member: int = 0,
    ):
        self.name = name
        self.engine = engine
        # Stacked-members engine: which of the engine's weight sets serves
        # this backend's requests (0 on ordinary engines).
        self.member = member
        self.model_id = model_id or "tpu-model"
        self.model = model or self.model_id
        self.default_max_tokens = default_max_tokens
        self.decode_chunk = decode_chunk  # None → engine default
        # Sampling-RNG offset: ckpt backends share one set of weights, so
        # quorum diversity must come from the sampler stream, not the init
        # seed. Offset 0 for random-init backends (their weights differ).
        self.rng_offset = rng_offset
        self.tokenizer = get_tokenizer(engine.spec.vocab_size, tokenizer_path)

    @classmethod
    def from_spec(cls, bspec: BackendSpec) -> "TpuBackend":
        model_id = bspec.tpu_model_id
        opts = bspec.tpu_options
        tp = int(opts.get("tp", 1))
        dp = int(opts.get("dp", 1))
        sp = int(opts.get("sp", 1))
        for name, off, pr, instead in _REMOVED_OPTIONS:
            if opts.get(name, off).strip() != off:
                raise ValueError(
                    f"{name}={opts[name]}: the {name}= option was removed "
                    f"in PR {pr} — {instead}")
        zero_drain = _parse_bool_opt(
            "zero_drain", opts.get("zero_drain", "0"))
        if zero_drain and opts.get("disagg"):
            # Checked at config time BEFORE the disagg mesh builds (the
            # engine re-checks): the URL names two structural answers to
            # the same problem — fail with the reason, never silently
            # pick one.
            raise ValueError(
                "zero_drain=1 does not compose with disagg=P+D: "
                "disaggregated admissions already run on their own device "
                "group with the ring at full depth — zero-drain is "
                "structural there (drop one knob)")
        prefill_mesh = None
        if opts.get("disagg"):
            from quorum_tpu.parallel.mesh import disagg_meshes, parse_disagg

            # Structural split into two disjoint device groups. dp= stays
            # a contradiction (groups are data-disjoint by construction —
            # scale requests with the replica tier, docs/scaling.md);
            # tp=/sp= are the INTRA-group factorization: tp shards
            # weights+KV within both groups, sp scales the prefill group
            # (sequence-parallel staging for 100k+-token admissions).
            # group_mesh_configs rejects every non-factoring combination
            # with the reason, at config time.
            n_p, n_d = parse_disagg(opts["disagg"])
            if dp > 1:
                raise ValueError(
                    "disagg= device groups are data-disjoint by "
                    "construction; dp= does not compose with it (scale "
                    "request throughput with the replica tier instead)")
            prefill_mesh, mesh = disagg_meshes(
                n_p, n_d, tp=tp if "tp" in opts else None, sp=sp)
        elif tp * dp * sp > 1:
            mesh = make_mesh(MeshConfig(dp=dp, sp=sp, tp=tp))
        else:
            mesh = single_device_mesh()
        ckpt = opts.get("ckpt", "")
        tokenizer_path = None
        rng_offset = 0
        n_slots = int(opts.get("slots", DEFAULT_SLOTS))
        members = int(opts.get("members", 1))
        member = int(opts.get("member", 0))
        if not 0 <= member < max(1, members):
            raise ValueError(
                f"member={member} out of range for members={members}")
        eng_kw = dict(
            n_slots=n_slots,
            prefill_mesh=prefill_mesh,
            zero_drain=zero_drain,
            decode_pipeline=int(
                opts.get("decode_pipeline", DEFAULT_DECODE_PIPELINE)),
            decode_loop=int(opts.get("decode_loop", DEFAULT_DECODE_LOOP)),
            prefill_chunk=int(opts.get("prefill_chunk", DEFAULT_PREFILL_CHUNK)),
            max_pending=int(opts.get("queue", DEFAULT_MAX_PENDING)),
            quant=opts.get("quant") or None,
            kv_quant=opts.get("kv_quant") or None,
            prefix_cache=_parse_bool_opt(
                "prefix_cache", opts.get("prefix_cache", "1")),
            sp_impl=opts.get("sp_impl", "ring"),
            # Paged KV slot memory (structural: part of the engine cache
            # key — a dense URL never shares a paged engine). Geometry
            # validation (power-of-two page size dividing max_seq, pool
            # floor) lives in the engine, which knows the resolved spec.
            kv_pages=_parse_bool_opt(
                "kv_pages", opts.get("kv_pages", "0")),
            kv_page_size=int(opts.get("kv_page_size", 0)),
            kv_pool_pages=int(opts.get("kv_pool_pages", 0)),
            # QoS scheduler (docs/scheduling.md). NOT structural: pure
            # host-side policy, deliberately outside the engine cache key
            # (pre-QoS keys stay byte-identical; qos=0 and qos=1 URLs
            # share one engine, opt-in winning).
            qos=_parse_bool_opt("qos", opts.get("qos", "0")),
            # Quorum serving (docs/quorum.md): member_seeds=shared stacks
            # M copies of ONE weight set (a quorum of sampling streams);
            # quorum_dedup=1 prefills a full group's shared prompt once
            # and broadcasts the K/V. Both structural (engine cache key);
            # value/compose errors live in the engine.
            member_seeds=opts.get("member_seeds", "distinct"),
            quorum_dedup=_parse_bool_opt(
                "quorum_dedup", opts.get("quorum_dedup", "0")),
            # The serving entry keeps the engine's compiled programs
            # across starts and loads them ahead of any request
            # (engine/prepare.py), where the persistent compile cache is on.
            prepare=cache_enabled(),
        )
        store = str(opts.get("prefix_store", "")).strip().lower()
        if store in ("", "0", "none", "off"):
            store = ""
        elif store != "host":
            raise ValueError(
                f"invalid prefix_store={opts.get('prefix_store')!r} "
                "(host, or none/0/off to disable)")
        if store:
            if members > 1:
                # Checked at config time (the engine re-checks): a stacked
                # fan-out URL must fail fast with the reason, not after a
                # members engine without the store was silently shared.
                raise ValueError(
                    "prefix_store=host does not compose with members=N "
                    "(the stacked cache carries a member axis the "
                    "snapshot/restore programs do not address) — run "
                    "separate engines or drop prefix_store")
            eng_kw["prefix_store"] = store
            if "prefix_store_bytes" in opts:
                eng_kw["prefix_store_bytes"] = _parse_bytes_opt(
                    "prefix_store_bytes", opts["prefix_store_bytes"])
            eng_kw["prefix_store_chunk"] = int(
                opts.get("prefix_store_chunk", 0))
        elif "prefix_store_bytes" in opts or "prefix_store_chunk" in opts:
            raise ValueError(
                "prefix_store_bytes=/prefix_store_chunk= have no effect "
                "without prefix_store=host — a silently ignored sizing "
                "knob hides a misconfiguration")
        if ckpt and members > 1:
            # Checked here (not just in the engine): ckpt engines are keyed
            # without members, so a stacked URL would otherwise construct a
            # members=1 engine and fail per-request instead of at config.
            raise ValueError(
                f"members=N does not apply to ckpt= backends "
                f"({_CKPT_MEMBERS_ERROR}; use seed= for sampling diversity)")
        if ckpt:
            # The quorum knobs configure the stacked members=N random init,
            # which ckpt= rejects above — strip the defaults (ckpt engines
            # are keyed/built without them) and fail a non-default loudly.
            if (eng_kw.pop("member_seeds") != "distinct"
                    or eng_kw.pop("quorum_dedup")):
                raise ValueError(
                    "member_seeds=/quorum_dedup= do not apply to ckpt= "
                    "backends: they configure the stacked members=N init, "
                    "and members=N does not apply to ckpt= (one loaded "
                    "weight set; use seed= for sampling diversity)")
            # seed= still differentiates quorum members: it offsets the
            # sampling RNG (weights are shared — one checkpoint on device).
            rng_offset = int(opts.get("seed", 0))
            # Real weights from a local HF checkpoint dir; its tokenizer files
            # (tokenizer.json / tokenizer_config.json) are used when present.
            engine = get_engine_from_ckpt(
                ckpt, mesh, dtype=opts.get("dtype"), **eng_kw
            )
            import os

            if any(
                os.path.exists(os.path.join(ckpt, f))
                for f in ("tokenizer.json", "tokenizer_config.json", "vocab.json")
            ):
                tokenizer_path = ckpt
        else:
            spec = resolve_spec(model_id, opts)
            engine = get_engine(
                spec, mesh, seed=int(opts.get("seed", 0)), members=members,
                **eng_kw
            )
        return cls(
            bspec.name,
            engine,
            model=bspec.model,
            model_id=model_id,
            default_max_tokens=int(opts.get("max_tokens", 64)),
            decode_chunk=int(opts["decode_chunk"]) if "decode_chunk" in opts else None,
            tokenizer_path=tokenizer_path,
            rng_offset=rng_offset,
            member=member,
        )

    # ---- request plumbing -------------------------------------------------

    # Request fields a local model cannot honor — a documented 400, never a
    # silent ignore (docs/api.md knob table; the round-2 backend silently
    # dropped these, VERDICT r2 missing item 1).
    _UNSUPPORTED = ("tools", "tool_choice", "functions", "function_call")
    MAX_N = 8
    # Slack the asyncio-side wait keeps beyond the engine-enforced deadline:
    # the scheduler's sweep is the real enforcement (one decode chunk of
    # latency); the wait only backstops a wedged scheduler, so a deadline
    # miss still answers within deadline + this slack.
    DEADLINE_SLACK_S = 2.0

    def _note_backstop(self, timeout: float) -> None:
        """The DEADLINE_SLACK_S backstop fired: the engine's own deadline
        sweep should have answered well inside ``timeout`` — a wedged
        scheduler is exactly what the flight-recorder post-mortem exists
        for, so the ring dumps to logs/ (docs/observability.md). The dump
        (full-ring JSON serialization + disk write) runs on its own
        thread: this method is called from the asyncio event loop, and a
        blocking write there would stall every concurrent SSE stream."""
        RECORDER.record("backstop", loop="server", backend=self.name,
                        timeout=round(float(timeout), 3))
        threading.Thread(target=RECORDER.dump, args=("backstop",),
                         name="flightrec-backstop-dump",
                         daemon=True).start()

    def _acquire_score_slot(self) -> None:
        """Admit one scoring/embedding device forward or raise 503.

        The gate (``engine.score_gate``, shared per engine — stacked
        members and ckpt backends on one engine contend for the same
        chip) bounds the direct to_thread device forwards the slot queue
        does not cover (ADVICE r4)."""
        if not self.engine.score_gate.acquire(blocking=False):
            raise _overloaded(self.name, "scoring/embedding gate saturated")

    def _release_score_slot(self) -> None:
        self.engine.score_gate.release()

    async def _shielded_to_thread(self, fn, timeout: float):
        """Run ``fn`` on a thread the event loop cannot cancel mid-device-
        work: the shield guarantees fn executes exactly once even when the
        wait times out or the client drops, so fn's own finally (slot/gate
        release) always runs. Raises asyncio.TimeoutError on expiry while
        the device work continues in the background."""
        task = asyncio.create_task(asyncio.to_thread(fn))
        task.add_done_callback(lambda t: t.cancelled() or t.exception())
        return await asyncio.wait_for(asyncio.shield(task), timeout=timeout)

    async def _gated_to_thread(self, fn, timeout: float):
        """Score-gated device forward: acquire a slot (503 when
        saturated — ADVICE r4), run ``fn`` shielded, and free the slot
        when the DEVICE work ends — not when the client's wait ends, so a
        timed-out request's still-running forward keeps its slot."""
        self._acquire_score_slot()

        def gated():
            try:
                return fn()
            finally:
                self._release_score_slot()

        # No await sits between the acquire and the task creation inside
        # _shielded_to_thread, so no cancellation point can leak the slot;
        # once the task exists the shield guarantees gated() runs and
        # releases exactly once.
        return await self._shielded_to_thread(gated, timeout)

    def _plan(self, body: dict[str, Any]) -> dict[str, Any]:
        effective = prepare_body(body, self.model)
        for key in self._UNSUPPORTED:
            if body.get(key):
                raise _invalid_request(
                    f"{key!r} is not supported by tpu:// backends"
                )
        grammar = self._plan_grammar(body.get("response_format"))
        # Explicit JSON null means "unset" for every optional knob (OpenAI
        # SDKs serialize unset optionals as null).
        n = body.get("n")
        if n is None:
            n = 1
        if not isinstance(n, int) or isinstance(n, bool) or not 1 <= n <= self.MAX_N:
            raise _invalid_request(
                f"Invalid value for 'n': {n!r} (must be an integer in "
                f"[1, {self.MAX_N}])"
            )
        want_lp = body.get("logprobs")
        if want_lp is None:
            want_lp = False
        if not isinstance(want_lp, bool):
            raise _invalid_request(f"Invalid value for 'logprobs': {want_lp!r}")
        top_lp = body.get("top_logprobs", 0)
        if top_lp is None:
            top_lp = 0
        if not isinstance(top_lp, int) or isinstance(top_lp, bool) or not 0 <= top_lp <= 20:
            raise _invalid_request(
                f"Invalid value for 'top_logprobs': {top_lp!r} (must be an "
                "integer in [0, 20])"
            )
        if top_lp and not want_lp:
            raise _invalid_request(
                "'top_logprobs' requires 'logprobs' to be true"
            )
        pp = _request_number(body, "presence_penalty", 0.0)
        fp = _request_number(body, "frequency_penalty", 0.0)
        for key, val in (("presence_penalty", pp), ("frequency_penalty", fp)):
            if not -2.0 <= val <= 2.0:
                raise _invalid_request(
                    f"Invalid value for {key!r}: {val!r} (must be in [-2, 2])"
                )
        # Tokenizer-aware templating: an instruct checkpoint's own chat
        # template when present, the static fallback otherwise. The legacy
        # /completions path supplies raw prompt ids instead (no template —
        # the prompt IS the context, _raw_prompt_ids is set internally by
        # text_complete/its streaming twin and validated like any
        # pre-tokenized input).
        raw_ids = body.get("_raw_prompt_ids")
        if raw_ids is not None:
            vocab = self.engine.spec.vocab_size
            if not (isinstance(raw_ids, list) and raw_ids and all(
                    isinstance(t, int) and not isinstance(t, bool)
                    and 0 <= t < vocab for t in raw_ids)):
                raise _invalid_request(
                    "prompt token ids must be a non-empty list of in-vocab "
                    "integers")
            ids = list(raw_ids)
        else:
            prompt = self.tokenizer.render_chat(body.get("messages") or [])
            ids = self.tokenizer.encode(prompt)
        key = (
            "max_completion_tokens"
            if body.get("max_completion_tokens") is not None
            else "max_tokens"
        )
        max_new = _request_number(body, key, float(self.default_max_tokens))
        if max_new < 1:
            raise _invalid_request(f"Invalid value for {key!r}: must be >= 1")
        # Cross-replica stream resume (docs/robustness.md "Zero-loss
        # streams"): the router re-submits a broken stream with the ids it
        # already delivered; the engine's replay guard swallows their
        # regeneration. Shape-validated at the proxy edge
        # (oai.validate_request_body) — re-checked here because the knob is
        # vocabulary-dependent and internal callers can bypass the edge.
        rt = body.get("resume_tokens")
        if rt is not None:
            vocab = self.engine.spec.vocab_size
            if not (isinstance(rt, list) and rt and all(
                    isinstance(t, int) and not isinstance(t, bool)
                    and 0 <= t < vocab for t in rt)):
                raise _invalid_request(
                    "'resume_tokens' must be a non-empty list of in-vocab "
                    "token ids")
            if n != 1:
                raise _invalid_request("'resume_tokens' requires n=1")
            if want_lp:
                raise _invalid_request(
                    "'resume_tokens' cannot be combined with 'logprobs'")
            if len(rt) > int(max_new):
                raise _invalid_request(
                    f"'resume_tokens' ({len(rt)} ids) exceeds the "
                    f"completion budget ({int(max_new)})")
        rc = body.get("resume_chars")
        return {
            "model": effective["model"],
            "prompt_ids": ids,
            "max_new": int(max_new),
            "sampler": _request_sampler(body),
            "seed": int(_request_number(body, "seed", 0.0)) + self.rng_offset,
            "stops": _stop_list(body),
            "n": n,
            "logprobs": top_lp if want_lp else -1,
            "presence_penalty": pp,
            "frequency_penalty": fp,
            "logit_bias": self._bias_row(body.get("logit_bias")),
            "grammar": grammar,
            # QoS scheduling knobs (docs/scheduling.md) — validated at the
            # proxy edge (oai.validate_request_body) and re-checked by
            # engine.submit; inert unless the engine runs qos=1.
            "priority": body.get("priority"),
            "tenant": body.get("tenant"),
            "resume_tokens": list(rt) if rt else None,
            "resume_chars": int(rc) if rc is not None else None,
            # Emit per-chunk token ids (``qt_tokens``) so the router can
            # journal the stream for a possible future resume.
            "stream_token_ids": bool(body.get("stream_token_ids")),
        }

    def _plan_grammar(self, rf: Any):
        """``response_format`` → a compiled token-DFA grammar (or None for
        text). On-device constrained decoding, docs/structured_output.md:
        json_object / json_schema / regex compile once per (grammar,
        tokenizer) — cached — and the engine masks every sampled token by
        the grammar's allow-set on device. Malformed or unsupported
        grammars are 400s; a grammar no token sequence can satisfy under
        this tokenizer is a 422 ``grammar_error`` (the dead-end path)."""
        if rf is None:
            return None
        if not isinstance(rf, dict):
            raise _invalid_request(
                f"Invalid value for 'response_format': {rf!r}")
        if rf.get("type") in (None, "text"):
            return None
        from quorum_tpu.constrain import (
            GrammarError,
            GrammarUnsatisfiable,
            compile_response_format,
        )

        if self.engine.prefill_chunk <= 0:
            raise _invalid_request(
                "response_format constrained decoding requires chunked "
                "prefill (prefill_chunk >= 16), which this backend's "
                "engine disables (sp>1 or prefill_chunk=0)")
        try:
            grammar = compile_response_format(
                rf, self.tokenizer, self.engine.spec.vocab_size)
        except GrammarUnsatisfiable as e:
            raise _grammar_unsatisfiable(self.name, e) from None
        except GrammarError as e:
            raise _invalid_request(
                f"Invalid 'response_format': {e}") from None
        if grammar is not None:
            from quorum_tpu.observability import CONSTRAINED_REQUESTS

            CONSTRAINED_REQUESTS.inc()
        return grammar

    def _bias_row(self, logit_bias: Any):
        """OpenAI ``logit_bias`` ({token-id: -100..100}) → dense [V] f32 row."""
        if not logit_bias:
            return None
        if not isinstance(logit_bias, dict):
            raise _invalid_request(
                f"Invalid value for 'logit_bias': {logit_bias!r}"
            )
        import numpy as _np

        vocab = self.engine.spec.vocab_size
        row = _np.zeros((vocab,), _np.float32)
        for tok, bias in logit_bias.items():
            try:
                idx = int(tok)
                val = float(bias)
            except (TypeError, ValueError):
                raise _invalid_request(
                    f"Invalid logit_bias entry: {tok!r}: {bias!r}"
                ) from None
            if not 0 <= idx < vocab:
                raise _invalid_request(
                    f"logit_bias token id {idx} outside vocabulary [0, {vocab})"
                )
            if not -100.0 <= val <= 100.0:
                raise _invalid_request(
                    f"logit_bias value {val} outside [-100, 100]"
                )
            row[idx] = val
        return row

    def _usage(self, n_prompt: int, n_completion: int) -> dict[str, int]:
        return {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_completion,
            "total_tokens": n_prompt + n_completion,
        }

    # ---- Backend protocol -------------------------------------------------

    # Distinct sampling streams per choice when n > 1 (documented: choice i
    # uses request seed + i·CHOICE_SEED_STRIDE).
    CHOICE_SEED_STRIDE = 7919

    def _submit_choice(self, plan: dict[str, Any], idx: int,
                       cancel: threading.Event,
                       deadline: float | None = None):
        return self.engine.submit(
            plan["prompt_ids"],
            max_new_tokens=plan["max_new"],
            sampler=plan["sampler"],
            seed=plan["seed"] + idx * self.CHOICE_SEED_STRIDE,
            eos_id=self.tokenizer.eos_id,
            cancel=cancel,
            decode_chunk=self.decode_chunk,
            presence_penalty=plan["presence_penalty"],
            frequency_penalty=plan["frequency_penalty"],
            logit_bias=plan["logit_bias"],
            logprobs=plan["logprobs"],
            member=self.member,
            deadline=deadline,
            grammar=plan["grammar"],
            priority=plan.get("priority"),
            tenant=plan.get("tenant"),
            # n == 1 is enforced whenever resume_tokens is set, so only
            # choice 0 can ever carry a journal.
            resume_tokens=plan.get("resume_tokens") if idx == 0 else None,
        )

    def _lp_entry(self, tid: int, record, top_n: int) -> dict[str, Any]:
        """One OpenAI ``logprobs.content[]`` element from an engine record.

        Non-finite alternatives are dropped: under constrained decoding
        (docs/structured_output.md) the grammar masks disallowed tokens to
        −inf BEFORE the log_softmax, so a state allowing fewer tokens than
        ``top_n`` would otherwise surface ``-Infinity`` samples —
        ``json.dumps`` renders those as the non-RFC-8259 ``-Infinity``
        literal and strict clients reject the whole body. The sampled
        token itself is always allowed (finite); the clamp is belt to
        that invariant's braces."""
        def tok_obj(t, lp):
            text = self.tokenizer.decode([int(t)])
            return {
                "token": text,
                "logprob": float(lp) if math.isfinite(float(lp)) else -9999.0,
                "bytes": list(text.encode("utf-8")),
            }

        lp, top_ids, top_lps = record
        entry = tok_obj(tid, lp)
        entry["top_logprobs"] = [
            tok_obj(int(t), float(l))
            for t, l in zip(top_ids[:top_n], top_lps[:top_n])
            if math.isfinite(float(l))
        ]
        return entry

    @staticmethod
    def _take_aligned(pending: list, n_chars: int) -> list:
        """Pop pending logprob entries covering ``n_chars`` of emitted text.

        The uniform alignment rule for both complete() and stream(): an
        entry ships exactly when its token's text ships. Entries whose text
        the stop matcher still buffers (or later swallows) stay pending /
        are dropped; an entry straddling the emit boundary ships with the
        chunk that contains its first character. Zero-length token texts
        ride along with the next emission."""
        out, used = [], 0
        while pending and used < n_chars:
            e = pending.pop(0)
            out.append(e)
            used += len(e["token"])
        return out

    def _consume(self, plan: dict[str, Any], req) -> tuple:
        """Drain one submitted choice: returns (result, text, lp_content).

        Logprob entries track *emitted content* (see ``_take_aligned``):
        tokens the stop matcher swallows get no entry — OpenAI's
        logprobs.content aligns with the tokens of the returned content."""
        result = GenerationResult()
        detok = self.tokenizer.detokenizer()
        matcher = _StopMatcher(plan["stops"])
        top_n = max(0, plan["logprobs"])
        lp_content = [] if plan["logprobs"] >= 0 else None
        pending_lp: list = []
        pieces = []
        for i, t in enumerate(self.engine.stream_results(req)):
            if t == self.tokenizer.eos_id:
                result.finish_reason = "stop"
                break
            result.token_ids.append(t)
            if lp_content is not None and i < len(req.lp):
                pending_lp.append(self._lp_entry(t, req.lp[i], top_n))
            text = matcher.feed(detok.feed(t))
            if text and req.t_delta is None:
                req.mark_first_delta()  # the first token's path
            if text and lp_content is not None:
                lp_content.extend(self._take_aligned(pending_lp, len(text)))
            pieces.append(text)
            if matcher.hit:
                # stop string matched: abort decoding now, not at budget
                result.finish_reason = "stop"
                break
        if getattr(req, "parked", False):
            # Drain park (docs/robustness.md): the engine only parks
            # unfinished requests, so whatever decoded so far is a
            # truncated prefix — it must not ship as a 200.
            raise _DrainParked(
                "request parked by a draining engine before completion")
        tail = matcher.feed(detok.flush()) + matcher.flush()
        pieces.append(tail)
        if lp_content is not None:
            if matcher.hit:
                # Stop matched: entries for swallowed tokens stay dropped;
                # the tail can still ship the entries it covers.
                if tail:
                    lp_content.extend(
                        self._take_aligned(pending_lp, len(tail)))
            else:
                # No stop: every delivered token's entry ships. Character
                # alignment alone strands entries here — a token's
                # context-free decode text ('�' per byte of a split UTF-8
                # char) can be LONGER than what it contributed to the
                # incrementally-detokenized content, so the emitted chars
                # run out before the entries do (the pre-existing flaky
                # len(logprobs.content) failure in test_openai_knobs).
                lp_content.extend(pending_lp)
                pending_lp = []
        if matcher.hit:
            # A stop string can complete only in the flushed detokenizer
            # tail; the finish reason must still say "stop", not "length".
            result.finish_reason = "stop"
        return result, "".join(pieces), lp_content

    async def complete(
        self, body: dict[str, Any], headers: dict[str, str], timeout: float
    ) -> CompletionResult:
        plan = self._plan(body)
        # One cancel event PER choice: engine.stream_results sets its
        # request's event when that choice finishes (slot release), which
        # must not abort the sibling choices. Request-level aborts (timeout,
        # client disconnect) set all of them via cancel_all().
        cancels = [threading.Event() for _ in range(plan["n"])]

        def cancel_all():
            for c in cancels:
                c.set()

        # The engine-enforced deadline: queue-wait sheds before admission
        # (503), scheduler turns cancel admitted rows past it (504). The
        # asyncio wait below keeps a slack backstop in case the scheduler
        # itself is wedged.
        deadline = time.monotonic() + timeout
        try:
            reqs = [self._submit_choice(plan, i, cancels[i], deadline)
                    for i in range(plan["n"])]
        except QueueFullError as e:
            cancel_all()  # release any choices already admitted
            raise _overloaded(
                self.name, why=str(e) or "admission queue full",
                retry_after=getattr(e, "retry_after", 1.0)) from None
        except EngineBreakerOpen as e:
            cancel_all()
            raise _breaker_open(self.name, e) from None
        except DeadlineExceeded as e:
            cancel_all()
            raise _deadline_error(self.name, e) from None

        def run():
            return [self._consume(plan, r) for r in reqs]

        try:
            # Backend-tagged span over the whole generation (submit to last
            # token drained): /debug/traces then shows the engine's own
            # queue-wait/prefill/decode spans nested inside this window.
            with trace_span(current_trace(), "backend-generate",
                            backend=self.name, choices=plan["n"],
                            prompt_tokens=len(plan["prompt_ids"])):
                outs = await self._shielded_to_thread(
                    run, timeout + self.DEADLINE_SLACK_S)
        except asyncio.TimeoutError:
            # Abort the on-device loop at the next chunk boundary; don't hold
            # the request open waiting for the full generation.
            self._note_backstop(timeout)
            cancel_all()
            raise _timeout_error(self.name, timeout) from None
        except DeadlineExceeded as e:
            cancel_all()
            raise _deadline_error(self.name, e) from None
        except GrammarArenaFull as e:
            # Device grammar arena at capacity: retryable overload, not a
            # server fault (docs/structured_output.md).
            cancel_all()
            raise _overloaded(self.name, str(e)) from None
        except _DrainParked:
            # Drain park (park=1): no resume path without a stream — shed
            # as a retryable 503 so the router re-places the request on a
            # sibling instead of relaying truncated text as a 200.
            cancel_all()
            raise _overloaded(
                self.name, "replica is draining (request parked)") from None
        except BackendError:
            raise
        except Exception as e:
            cancel_all()
            logger.exception("TPU backend %s failed", self.name)
            raise BackendError(f"Backend {self.name} failed: {e}") from e
        except BaseException:
            # Request cancellation (client disconnect): abort the shielded
            # generation thread too, or it would decode to completion while
            # occupying an engine slot.
            cancel_all()
            raise

        result0, text0, lp0 = outs[0]
        completion_total = sum(r.completion_tokens for r, _, _ in outs)
        resp = oai.completion(
            content=text0,
            model=plan["model"],
            usage=self._usage(len(plan["prompt_ids"]), completion_total),
            finish_reason=result0.finish_reason,
        )
        choices = []
        for i, (result, text, lp_content) in enumerate(outs):
            choice = {
                "index": i,
                "message": {"role": "assistant", "content": text},
                "finish_reason": result.finish_reason,
            }
            if lp_content is not None:
                choice["logprobs"] = {"content": lp_content, "refusal": None}
            choices.append(choice)
        resp["choices"] = choices
        resp["backend"] = self.name
        return CompletionResult(backend_name=self.name, status_code=200, body=resp)

    async def embed(
        self, body: dict[str, Any], headers: dict[str, str], timeout: float
    ) -> CompletionResult:
        """OpenAI ``/embeddings`` from the engine's resident weights.

        ``input`` accepts a string, a list of strings, one pre-tokenized id
        list, or a list of id lists (the OpenAI schema); mixed lists, empty
        input, out-of-vocab ids, >64 items, a non-float/base64
        ``encoding_format``, or ``dimensions`` outside 1..d_model are 400s.
        Vectors are mean-pooled final-norm hidden states, L2-normalized;
        ``dimensions`` truncates then renormalizes (OpenAI matryoshka
        semantics); inputs beyond ``max_seq`` keep their head. See
        quorum_tpu/engine/embed.py for the device path.
        """
        import base64

        from quorum_tpu.engine.embed import MAX_BATCH, embed_token_batch

        effective = prepare_body(body, self.model)  # 400 when no model anywhere
        raw = body.get("input")
        if isinstance(raw, str):
            if not raw:
                raise _invalid_request("'input' must not be an empty string")
            items: list[Any] = [raw]
        elif isinstance(raw, list) and raw and all(
                isinstance(x, int) and not isinstance(x, bool) for x in raw):
            items = [raw]  # one pre-tokenized input
        elif isinstance(raw, list) and raw:
            items = raw
            _reject_mixed(items, "input")
        else:
            raise _invalid_request(
                "'input' must be a non-empty string, list of strings, or "
                "token array(s)")
        if len(items) > MAX_BATCH:
            raise _invalid_request(
                f"at most {MAX_BATCH} inputs per embeddings request")
        vocab = self.engine.spec.vocab_size
        token_lists: list[list[int]] = []
        for x in items:
            if isinstance(x, str) and x:
                token_lists.append(self.tokenizer.encode(x))
            elif isinstance(x, list) and x and all(
                    isinstance(t, int) and not isinstance(t, bool)
                    and 0 <= t < vocab for t in x):
                token_lists.append(x)
            else:
                raise _invalid_request(
                    "each 'input' item must be a string or a non-empty list "
                    "of in-vocab token ids")
        fmt = body.get("encoding_format", "float")
        if fmt not in ("float", "base64"):
            raise _invalid_request(
                "'encoding_format' must be 'float' or 'base64'")
        d_model = self.engine.spec.d_model
        dims = body.get("dimensions", d_model)
        if (not isinstance(dims, int) or isinstance(dims, bool)
                or not 1 <= dims <= d_model):
            raise _invalid_request(
                f"'dimensions' must be an integer in 1..{d_model}")

        def run():
            return embed_token_batch(self.engine, token_lists,
                                     member=self.member)

        try:
            vectors = await self._gated_to_thread(run, timeout)
        except asyncio.TimeoutError:
            raise _timeout_error(self.name, timeout) from None
        except BackendError:
            raise
        except Exception as e:
            logger.exception("TPU backend %s embeddings failed", self.name)
            raise BackendError(f"Backend {self.name} failed: {e}") from e

        if dims < d_model:
            vectors = vectors[:, :dims]
            norms = np.linalg.norm(vectors, axis=-1, keepdims=True)
            vectors = vectors / np.maximum(norms, 1e-9)
        data = []
        for i, v in enumerate(vectors):
            if fmt == "base64":
                emb: Any = base64.b64encode(
                    v.astype("<f4").tobytes()).decode("ascii")
            else:
                emb = v.tolist()
            data.append({"object": "embedding", "index": i, "embedding": emb})
        n_tokens = sum(min(len(t), self.engine.spec.max_seq)
                       for t in token_lists)
        resp = {
            "object": "list",
            "data": data,
            "model": effective.get("model") or self.model,
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
            "backend": self.name,
        }
        return CompletionResult(
            backend_name=self.name, status_code=200, body=resp)

    def _parse_prompts(self, raw: Any) -> list[tuple[str, list[int]]]:
        """The /completions ``prompt`` field → [(text, token_ids)] — same
        shape grammar as embeddings ``input`` (string / string list / one
        id list / list of id lists); pre-tokenized prompts get their text
        from the tokenizer so ``echo`` always has something to echo."""
        if isinstance(raw, str):
            if not raw:
                raise _invalid_request("'prompt' must not be an empty string")
            items: list[Any] = [raw]
        elif isinstance(raw, list) and raw and all(
                isinstance(x, int) and not isinstance(x, bool) for x in raw):
            items = [raw]
        elif isinstance(raw, list) and raw:
            items = raw
            _reject_mixed(items, "prompt")
        else:
            raise _invalid_request(
                "'prompt' must be a non-empty string, list of strings, or "
                "token array(s)")
        vocab = self.engine.spec.vocab_size
        prompts: list[tuple[str, list[int]]] = []
        for x in items:
            if isinstance(x, str) and x:
                prompts.append((x, self.tokenizer.encode(x)))
            elif isinstance(x, list) and x and all(
                    isinstance(t, int) and not isinstance(t, bool)
                    and 0 <= t < vocab for t in x):
                prompts.append((self.tokenizer.decode(x), list(x)))
            else:
                raise _invalid_request(
                    "each 'prompt' item must be a string or a non-empty "
                    "list of in-vocab token ids")
        return prompts

    @staticmethod
    def _validate_completions_common(body: dict[str, Any]) -> None:
        """/completions rules shared by the flat and streaming paths —
        one source for each rejection, so the two modes can never drift.
        best_of=1 / n=1 are the documented OpenAI defaults (no-ops)."""
        if body.get("n") not in (None, 1):
            raise _invalid_request(
                "'n' > 1 is not supported on /completions — send a list of "
                "prompts instead")
        if body.get("best_of") not in (None, 1):
            raise _invalid_request(
                "'best_of' is not supported by tpu:// backends")
        if body.get("suffix"):
            raise _invalid_request(
                "'suffix' is not supported by tpu:// backends")

    def plan_text_stream(
        self, body: dict[str, Any]
    ) -> tuple[dict[str, Any], str]:
        """Validate a streaming /completions request and build the body its
        chat-chunk stream runs on. Returns ``(stream_body, model)`` —
        ``model`` under the same config-overrides-request precedence as
        every other path. Raises the 400 family for echo/logprobs (no
        streaming analog in the legacy wire), multi-prompt, and the shared
        /completions rules."""
        effective = prepare_body(body, self.model)
        self._validate_completions_common(body)
        # logprobs=false is the serialized default, not a request for
        # logprobs — same mapping as _parse_completions_logprobs.
        if body.get("echo") or body.get("logprobs") not in (None, False):
            raise _invalid_request(
                "'echo'/'logprobs' are not supported with 'stream' on "
                "/completions")
        prompts = self._parse_prompts(body.get("prompt"))
        if len(prompts) != 1:
            raise _invalid_request(
                "streaming /completions takes exactly one prompt")
        sbody = {k: v for k, v in body.items()
                 if k not in ("prompt", "echo", "logprobs", "stream",
                              "n", "best_of", "suffix")}
        if ("max_tokens" not in sbody
                and "max_completion_tokens" not in sbody):
            # The legacy default (16): the chat plan would otherwise fall
            # back to the backend's chat default and the same request
            # would generate 4x more when streamed.
            sbody["max_tokens"] = 16
        sbody["_raw_prompt_ids"] = prompts[0][1]
        return sbody, effective["model"]

    @staticmethod
    def _parse_completions_logprobs(body: dict[str, Any]) -> "int | None":
        lp = body.get("logprobs")
        if lp is None or lp is False:
            return None
        if lp is True:  # chat-style boolean → "just the chosen token"
            return 0
        if not isinstance(lp, int) or isinstance(lp, bool) or not 0 <= lp <= 5:
            raise _invalid_request(
                f"Invalid value for 'logprobs': {lp!r} (must be an integer "
                "in [0, 5])")
        return lp

    async def text_complete(
        self, body: dict[str, Any], headers: dict[str, str], timeout: float
    ) -> CompletionResult:
        """Legacy OpenAI ``/completions``: raw-prompt generation and
        teacher-forced scoring from the same resident weights.

        The scoring contract eval harnesses rely on: ``echo=true`` with
        ``logprobs=k`` returns every PROMPT token's logprob (first token
        ``null``) computed in one forward (engine/score.py);
        ``max_tokens=0`` is allowed exactly in that mode (pure scoring).
        Generation reuses the chat engine machinery over raw prompt ids
        (no chat template), with the full sampler/stop/penalty knob set.
        Up to 8 prompts when generating (one engine slot each), 64 when
        scoring only; ``n`` > 1 is rejected (send a prompt list instead);
        ``best_of``/``suffix`` are unsupported on tpu:// backends (400).
        """
        import uuid

        from quorum_tpu.engine.embed import MAX_BATCH
        from quorum_tpu.engine.score import score_token_batch

        effective = prepare_body(body, self.model)
        self._validate_completions_common(body)
        prompts = self._parse_prompts(body.get("prompt"))
        echo = bool(body.get("echo", False))
        lp = self._parse_completions_logprobs(body)
        mt = body.get("max_tokens")
        if mt is None:
            mt = 16  # the documented OpenAI default for /completions
        if not isinstance(mt, int) or isinstance(mt, bool) or mt < 0:
            raise _invalid_request(
                f"Invalid value for 'max_tokens': {mt!r} (integer >= 0)")
        scoring = echo and lp is not None
        if mt == 0 and not scoring:
            raise _invalid_request(
                "'max_tokens': 0 requires 'echo': true with 'logprobs' set "
                "(the pure scoring mode)")
        max_seq = self.engine.spec.max_seq
        if scoring:
            too_long = max(len(ids) for _, ids in prompts)
            if too_long > max_seq:
                raise _invalid_request(
                    f"prompt of {too_long} tokens exceeds max_seq={max_seq} "
                    "— a truncated prompt cannot be scored faithfully")
            if len(prompts) > MAX_BATCH:
                raise _invalid_request(
                    f"at most {MAX_BATCH} prompts per scoring request")
        if mt >= 1 and len(prompts) > self.MAX_N:
            raise _invalid_request(
                f"at most {self.MAX_N} prompts per generation request")

        # One deadline across both phases: echo+logprobs with generation
        # runs a scoring forward AND a decode — sequential full budgets
        # would let the request take 2x the configured timeout.
        import time as _time

        deadline = _time.monotonic() + timeout

        scores = None
        if scoring:
            def run_score():
                return score_token_batch(
                    self.engine, [ids for _, ids in prompts],
                    member=self.member, top_k=lp)

            try:
                scores = await self._gated_to_thread(
                    run_score, max(0.0, deadline - _time.monotonic()))
            except asyncio.TimeoutError:
                raise _timeout_error(self.name, timeout) from None
            except BackendError:
                raise
            except Exception as e:
                logger.exception("TPU backend %s scoring failed", self.name)
                raise BackendError(
                    f"Backend {self.name} failed: {e}") from e

        outs: list = []
        if mt >= 1:
            plan_body = {k: v for k, v in body.items()
                         if k not in ("prompt", "echo", "logprobs",
                                      "stream", "max_tokens",
                                      "max_completion_tokens")}
            plan_body["max_tokens"] = mt
            if lp is not None:
                plan_body["logprobs"] = True
                plan_body["top_logprobs"] = lp
            plans = []
            for _, ids in prompts:
                pb = dict(plan_body)
                pb["_raw_prompt_ids"] = ids
                plans.append(self._plan(pb))
            cancels = [threading.Event() for _ in plans]

            def cancel_all():
                for c in cancels:
                    c.set()

            try:
                reqs = [self._submit_choice(plans[i], 0, cancels[i], deadline)
                        for i in range(len(plans))]
            except QueueFullError as e:
                cancel_all()
                raise _overloaded(
                    self.name, why=str(e) or "admission queue full",
                    retry_after=getattr(e, "retry_after", 1.0)) from None
            except EngineBreakerOpen as e:
                cancel_all()
                raise _breaker_open(self.name, e) from None
            except DeadlineExceeded as e:
                cancel_all()
                raise _deadline_error(self.name, e) from None

            def run():
                return [self._consume(plans[i], r)
                        for i, r in enumerate(reqs)]

            try:
                outs = await self._shielded_to_thread(
                    run, max(0.0, deadline - _time.monotonic())
                    + self.DEADLINE_SLACK_S)
            except asyncio.TimeoutError:
                self._note_backstop(timeout)
                cancel_all()
                raise _timeout_error(self.name, timeout) from None
            except DeadlineExceeded as e:
                cancel_all()
                raise _deadline_error(self.name, e) from None
            except GrammarArenaFull as e:
                cancel_all()
                raise _overloaded(self.name, str(e)) from None
            except _DrainParked:
                # See complete(): a drain-parked non-streaming request
                # sheds retryably rather than returning truncated text.
                cancel_all()
                raise _overloaded(
                    self.name,
                    "replica is draining (request parked)") from None
            except BackendError:
                raise
            except Exception as e:
                cancel_all()
                logger.exception("TPU backend %s failed", self.name)
                raise BackendError(f"Backend {self.name} failed: {e}") from e
            except BaseException:
                cancel_all()
                raise

        choices = []
        total_completion = 0
        for i, (text, ids) in enumerate(prompts):
            gen_text, finish, lp_content = "", "length", None
            if outs:
                result, gen_text, lp_content = outs[i]
                finish = result.finish_reason
                total_completion += result.completion_tokens
            choice: dict[str, Any] = {
                "index": i,
                "text": (text + gen_text) if echo else gen_text,
                "finish_reason": finish,
            }
            if lp is not None:
                tokens: list[str] = []
                token_lps: list = []
                tops: list = []
                offsets: list[int] = []
                pos = 0
                if echo:
                    score = scores[i]
                    top = score.get("top")
                    # Incremental detokenization (the streaming path's own
                    # tool): byte-level BPE tokens can split one multi-byte
                    # UTF-8 character, and per-token decode([tid]) would
                    # emit replacement chars whose lengths drift
                    # tokens/text_offset away from the echoed prompt
                    # string (ADVICE r4). feed() emits only complete
                    # characters, so every offset indexes correctly into
                    # the returned text.
                    detok = self.tokenizer.detokenizer()
                    for j, tid in enumerate(ids):
                        ttext = detok.feed(int(tid))
                        if j == len(ids) - 1:
                            ttext += detok.flush()
                        tokens.append(ttext)
                        offsets.append(pos)
                        pos += len(ttext)
                        token_lps.append(score["token_logprobs"][j])
                        if j == 0:
                            tops.append(None)  # no prefix → nothing to rank
                        elif top is not None:
                            t_ids, t_lps = top[j]
                            tops.append(_top_dict(
                                (self.tokenizer.decode([int(t)]), float(l))
                                for t, l in zip(t_ids, t_lps)))
                        else:
                            tops.append({})
                if lp_content:
                    for e in lp_content:
                        tokens.append(e["token"])
                        offsets.append(pos)
                        pos += len(e["token"])
                        token_lps.append(e["logprob"])
                        tops.append(_top_dict(
                            (t["token"], t["logprob"])
                            for t in e.get("top_logprobs", [])))
                choice["logprobs"] = {
                    "tokens": tokens,
                    "token_logprobs": token_lps,
                    "top_logprobs": tops,
                    "text_offset": offsets,
                }
            else:
                choice["logprobs"] = None
            choices.append(choice)

        resp = {
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "text_completion",
            "created": oai.now(),
            "model": effective["model"],
            "choices": choices,
            "usage": self._usage(
                sum(len(ids) for _, ids in prompts), total_completion),
            "backend": self.name,
        }
        return CompletionResult(
            backend_name=self.name, status_code=200, body=resp)

    def _stream_pool(self) -> "ThreadPoolExecutor | None":
        """Where the streams' producer threads run. A stream holds its
        thread for its whole life (it blocks on the engine's queue), so a
        pool with fewer threads than a backend has slot rows leaves rows
        whose tokens nobody fetches: 32 rows over asyncio's default pool (17
        threads on 13 cores) gave every stream its first token 11 s late
        (PERF.md section 6, PR 30). That pool has ``min(32, cores + 4)``
        threads, so on no host does it hold a backend of 32 rows and
        anything else: such a backend gets a pool of the engine's own, twice
        its rows wide, so that a full engine's next requests wait in its
        queue, where the wait is counted, and not for a thread. The rule
        reads the rows and not the host, so a configuration runs the same
        way on every machine. (Smaller backends share the default pool,
        also where their streams together outnumber it, as three stacked
        members of 8 rows do on 13 cores: covering them moves the
        benchmark's ``tpot_p50_ms`` by construction, PERF.md section 5
        item 4.)"""
        # (an engine that names no slots, a test's stand-in, has the default)
        slots = getattr(self.engine, "n_slots", 0)
        if slots < ASYNCIO_DEFAULT_POOL_MAX:
            return None
        if self.engine.stream_pool is None:
            self.engine.stream_pool = ThreadPoolExecutor(
                max_workers=2 * slots * self.engine.members,
                thread_name_prefix="tpu-stream")
        return self.engine.stream_pool

    async def stream(
        self, body: dict[str, Any], headers: dict[str, str], timeout: float
    ) -> AsyncIterator[dict[str, Any]]:
        plan = self._plan(body)
        model = plan["model"]
        n = plan["n"]
        top_n = max(0, plan["logprobs"])
        chunk_id = oai.new_request_id()
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()
        counts = [0] * n
        finishes = ["length"] * n
        # Per-choice cancel events (see complete()): a finished choice's
        # slot release must not abort its siblings; request-level aborts set
        # all of them.
        cancels = [threading.Event() for _ in range(n)]

        def cancel_all():
            for c in cancels:
                c.set()

        # Submit every choice BEFORE the first yield: a full admission queue
        # (or an open breaker, or an already-expired deadline) must surface
        # as a 503 response, not as an error chunk inside an
        # already-started 200 stream.
        engine_deadline = time.monotonic() + timeout
        try:
            reqs = [self._submit_choice(plan, i, cancels[i], engine_deadline)
                    for i in range(n)]
        except QueueFullError as e:
            cancel_all()  # release any choices already admitted
            raise _overloaded(
                self.name, why=str(e) or "admission queue full",
                retry_after=getattr(e, "retry_after", 1.0)) from None
        except EngineBreakerOpen as e:
            cancel_all()
            raise _breaker_open(self.name, e) from None
        except DeadlineExceeded as e:
            cancel_all()
            raise _deadline_error(self.name, e) from None
        except ValueError as e:
            # Engine-side resume validation (journal vs budget) — a bad
            # journal is the caller's error, not a server fault.
            cancel_all()
            raise _invalid_request(str(e)) from None

        def produce(idx: int, req):
            """Drain one choice; events are (kind, choice_index, payload)."""
            detok = self.tokenizer.detokenizer()
            matcher = _StopMatcher(plan["stops"])
            pending_lp: list = []
            # Token ids consumed since the last emitted text — shipped as
            # ``qt_tokens`` on the chunk that carries their text, so the
            # router's journal only ever names ids whose text the client
            # actually received (ids with still-buffered bytes wait).
            pending_ids: list = []

            def emit(text: str):
                # Same alignment rule as _consume: entries ship only with
                # the text that contains their token (stop-swallowed or
                # still-buffered text keeps its entries pending).
                lp = self._take_aligned(pending_lp, len(text))
                ids, pending_ids[:] = list(pending_ids), []
                loop.call_soon_threadsafe(
                    queue.put_nowait, ("text", idx, (text, lp, ids)))

            try:
                resume = plan["resume_tokens"] if idx == 0 else None
                if resume:
                    # Rebuild the delivered prefix through a FRESH
                    # detokenizer + stop matcher — the continuation then
                    # renders byte-exactly where the dead replica's stream
                    # stopped. The engine swallows the regenerated journal
                    # tokens, so the loop below only ever sees NEW tokens.
                    prefix = ""
                    for tok in resume:
                        prefix += matcher.feed(detok.feed(tok))
                    want = plan["resume_chars"]
                    if matcher.hit or (want is not None
                                       and len(prefix) != want):
                        why = (", stop string inside the journal"
                               if matcher.hit else "")
                        raise ReplayDivergence(
                            len(resume), message=(
                                "resume replay diverged before admission: "
                                f"journal renders {len(prefix)} chars "
                                f"(client received {want}{why})"))
                for i, tok in enumerate(self.engine.stream_results(req)):
                    if tok == self.tokenizer.eos_id:
                        finishes[idx] = "stop"
                        break
                    counts[idx] += 1
                    pending_ids.append(tok)
                    if plan["logprobs"] >= 0 and i < len(req.lp):
                        pending_lp.append(
                            self._lp_entry(tok, req.lp[i], top_n))
                    text = matcher.feed(detok.feed(tok))
                    # Logprob entries ride only with emitted content (see
                    # _consume): text the matcher swallows drops its pending
                    # entries, keeping streamed logprobs aligned with the
                    # streamed content.
                    if matcher.hit:
                        finishes[idx] = "stop"
                        if text:
                            emit(text)
                        break
                    if text:
                        emit(text)
                if getattr(req, "parked", False):
                    # Drain park (docs/robustness.md): the router resumes
                    # this stream on a sibling from the delivered prefix.
                    # Flushing the detok tail here would deliver text the
                    # resumed stream re-renders (duplicate bytes) — hold
                    # it back; the finish tells the router to resume, the
                    # client never sees it.
                    finishes[idx] = "parked"
                else:
                    tail = matcher.feed(detok.flush()) + matcher.flush()
                    if matcher.hit:
                        # Stop string completed in the flushed tail (see
                        # complete()).
                        finishes[idx] = "stop"
                    if tail:
                        emit(tail)
                    if pending_lp and not matcher.hit:
                        # Same stranding fix as _consume: without a stop
                        # hit, every delivered token's entry ships — in a
                        # final (possibly empty-content) delta when
                        # byte-level decode lengths outran the incremental
                        # text.
                        rest, pending_lp = list(pending_lp), []
                        loop.call_soon_threadsafe(
                            queue.put_nowait, ("text", idx, ("", rest, [])))
                loop.call_soon_threadsafe(queue.put_nowait, ("end", idx, None))
            except Exception as e:  # normalized below on the consumer side
                loop.call_soon_threadsafe(queue.put_nowait, ("err", idx, e))

        pool = self._stream_pool()
        producers = [loop.run_in_executor(pool, produce, i, r)
                     for i, r in enumerate(reqs)]
        # End-to-end deadline, matching complete()'s semantics: the engine
        # sweep is the enforcement (it delivers the DeadlineExceeded error
        # event within one decode chunk); each queue wait keeps a slack
        # backstop for a wedged scheduler.
        deadline = loop.time() + timeout + self.DEADLINE_SLACK_S
        ended = 0
        try:
            # inside the try: a disconnect at this first yield must still
            # cancel the producer threads (they already occupy engine slots)
            for i in range(n):
                yield oai.chunk(id=chunk_id, model=model,
                                delta={"role": "assistant"}, index=i)
            while ended < n:
                # Batch the drain: one decode chunk delivers its k tokens
                # to the queue within microseconds of each other, so after
                # the (possibly blocking) first get, everything already
                # queued rides the same batch. Every event but the batch's
                # last is marked MoreChunk — the SSE writer then emits k
                # events with ONE socket flush (sse-coalescing contract;
                # the per-flush trace marks count the frames inside).
                events = [await asyncio.wait_for(
                    queue.get(), timeout=max(0.0, deadline - loop.time())
                )]
                while True:
                    try:
                        events.append(queue.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                for pos, (kind, idx, val) in enumerate(events):
                    more = pos < len(events) - 1
                    if kind == "text":
                        text, lp, ids = val
                        if text and reqs[idx].t_delta is None:
                            # the first token's path: past the producer
                            # thread's detokenizer and the hop onto the loop
                            reqs[idx].mark_first_delta()
                        out = oai.chunk(id=chunk_id, model=model,
                                        delta={"content": text}, index=idx)
                        if plan["logprobs"] >= 0:
                            out["choices"][0]["logprobs"] = {
                                "content": lp, "refusal": None}
                        if plan["stream_token_ids"] and ids:
                            # Resume journal metadata: the ids whose text
                            # this chunk carries (stripped by the router
                            # unless the client opted in).
                            out["qt_tokens"] = ids
                        yield oai.more(out) if more else out
                    elif kind == "end":
                        ended += 1
                        out = oai.chunk(id=chunk_id, model=model, delta={},
                                        finish_reason=finishes[idx], index=idx)
                        yield oai.more(out) if more else out
                    else:
                        if isinstance(val, DeadlineExceeded):
                            raise _deadline_error(self.name, val) from val
                        if isinstance(val, GrammarArenaFull):
                            raise _overloaded(self.name, str(val)) from val
                        if isinstance(val, ReplayDivergence):
                            # Structured failure class: the router's
                            # resume path keys its degrade-don't-retry
                            # decision on ``code``, not message text.
                            raise BackendError(
                                f"Backend {self.name} failed: {val}",
                                code="resume_diverged") from val
                        raise BackendError(
                            f"Backend {self.name} failed: {val}") from val
        except asyncio.TimeoutError:
            self._note_backstop(timeout)
            cancel_all()  # abort the device loops at the next chunk boundary
            raise _timeout_error(self.name, timeout) from None
        except BaseException:
            # Client disconnect (GeneratorExit) or cancellation: release the
            # engine within one decode chunk; the producer threads exit on
            # their own — an async generator being closed must not await.
            cancel_all()
            raise
        cancel_all()
        for p in producers:
            await p  # producers already sent "end" — returns immediately
        if (body.get("stream_options") or {}).get("include_usage"):
            # OpenAI stream_options.include_usage: one extra chunk with empty
            # choices carrying the token counts (a real count — the local
            # engine generated the tokens, api_reference/chat_completions.yaml
            # stream_options schema).
            usage_chunk = oai.chunk(id=chunk_id, model=model, delta={})
            usage_chunk["choices"] = []
            usage_chunk["usage"] = self._usage(len(plan["prompt_ids"]), sum(counts))
            yield usage_chunk

    async def aclose(self) -> None:
        return None
