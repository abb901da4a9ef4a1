"""Parallel streaming aggregator: N backend streams → one SSE stream.

Re-design of the reference's ``progress_streaming_aggregator``
(/root/reference/src/quorum/oai_proxy.py:489-885) around a merge queue: each
backend stream runs as its own task pushing deltas into one queue, so chunks
from different backends interleave **live**. The reference instead polled task
completion every 0.1 s and replayed fully-buffered responses one backend at a
time (quirks 1+3, oai_proxy.py:554, 747).

SSE contract preserved (asserted by the reference test suite and ours):
  - initial role chunk   id "chatcmpl-parallel",  model "parallel-proxy";
  - per-backend deltas   id "chatcmpl-parallel-{i}" (i = backend index);
  - final combined chunk id "chatcmpl-parallel-final", finish_reason "stop";
  - all-failed error chunk id "error", content
    "Error: All backends failed to provide content", finish_reason "error";
  - terminating "data: [DONE]".

Deliberate fixes over the reference (SURVEY.md §2 quirk list):
  - quirk 4: ``source_backends`` is honored — in aggregate strategy only the
    configured sources are fanned out to;
  - quirk 5: ``suppress_individual_responses`` suppresses per-backend deltas;
  - quirk 7: final fallback join uses ``separator.join`` (the reference used
    ``f"\\n{separator}".join`` in streaming but ``separator.join`` elsewhere);
  - quirk 8: ``created`` is epoch time, not the event-loop clock;
  - quirk 9: the aggregation hop runs only when the *selected* strategy is
    ``aggregate`` (the reference triggered it whenever an aggregator was
    configured, regardless of strategy);
  - ``strip_intermediate_thinking`` / ``hide_aggregator_thinking`` are honored
    in aggregate strategy (documented in docs/aggregate_behaviour.md:113-151
    but never read by the reference).
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass
from typing import Any, AsyncIterator

from quorum_tpu import oai, sse
from quorum_tpu.backends.base import Backend
from quorum_tpu.backends.registry import BackendRegistry
from quorum_tpu.config import AggregateParams, Config
from quorum_tpu.filtering import strip_thinking_tags
from quorum_tpu.native import make_thinking_filter
from quorum_tpu.observability import trace_span, use_trace
from quorum_tpu.strategies.aggregate import (
    AggregateOutcome,
    aggregate_with_status,
    stream_aggregate_deltas,
)

logger = logging.getLogger(__name__)
aggregation_logger = logging.getLogger("aggregation")

PROXY_MODEL_NAME = "parallel-proxy"

_DONE = object()


@dataclass
class StreamPlan:
    """Fan-out parameters resolved from config + request body."""

    backends: list[Backend]
    strategy_name: str
    separator: str
    hide_intermediate: bool
    hide_final: bool
    thinking_tags: list[str]
    skip_final: bool
    suppress_individual: bool
    aggregator: Backend | None
    aggregate_params: AggregateParams | None
    user_query: str

    @classmethod
    def from_config(
        cls,
        cfg: Config,
        registry: BackendRegistry,
        body: dict[str, Any],
    ) -> "StreamPlan":
        strategy = cfg.strategy_name
        user_query = oai.first_user_message(body)
        if strategy == "aggregate":
            p = cfg.aggregate
            suppress = p.suppress_individual_responses
            if "suppress_individual_responses" in body:  # per-request override
                suppress = bool(body["suppress_individual_responses"])
            return cls(
                backends=registry.select(p.source_backends),
                strategy_name=strategy,
                separator=p.intermediate_separator,
                hide_intermediate=p.strip_intermediate_thinking,
                hide_final=p.hide_aggregator_thinking,
                thinking_tags=p.thinking_tags,
                skip_final=False,
                suppress_individual=suppress,
                aggregator=registry.get(p.aggregator_backend) if p.aggregator_backend else None,
                aggregate_params=p,
                user_query=user_query,
            )
        p = cfg.concatenate
        return cls(
            backends=registry.backends,
            strategy_name=strategy,
            separator=p.separator,
            hide_intermediate=p.hide_intermediate_think,
            hide_final=p.hide_final_think,
            thinking_tags=p.thinking_tags,
            skip_final=p.skip_final_aggregation,
            suppress_individual=bool(body.get("suppress_individual_responses", False)),
            aggregator=None,
            aggregate_params=None,
            user_query=user_query,
        )


async def _pump(
    index: int,
    backend: Backend,
    body: dict[str, Any],
    headers: dict[str, str],
    timeout: float,
    queue: asyncio.Queue,
    trace=None,
) -> None:
    """Drive one backend stream, pushing (index, text | _DONE) into the queue.

    The request trace is re-bound inside this task (``use_trace``) so a
    ``tpu://`` backend's engine submission — which happens at the stream's
    first ``__anext__``, on THIS task, after the server handler already
    returned — still attaches its queue-wait/prefill/decode spans; the
    fan-out hop itself is recorded as a backend-tagged span."""
    try:
        with use_trace(trace), trace_span(trace, "fanout-stream",
                                          backend=backend.name, index=index):
            async for chunk in backend.stream(body, headers, timeout):
                text = oai.extract_delta_content(chunk)
                if text:
                    await queue.put((index, text))
    except Exception as e:
        logger.warning("Backend %s (%d) stream failed: %s", backend.name, index, e)
        aggregation_logger.error("Error processing backend %d: %s", index, e)
    finally:
        await queue.put((index, _DONE))


async def parallel_stream(
    plan: StreamPlan,
    body: dict[str, Any],
    headers: dict[str, str],
    timeout: float,
    aggregator_timeout: float | None = None,
    trace=None,
) -> AsyncIterator[bytes]:
    """Merge N backend streams into one OpenAI-compatible SSE byte stream."""
    aggregation_logger.info("Starting streaming aggregation process")
    yield sse.encode_event(oai.role_chunk(PROXY_MODEL_NAME))

    def content_frame(chunk: dict[str, Any]) -> bytes:
        # The first frame with content handed to the SSE writer is the
        # trace's strategy_first_delta_s: behind it lie the pump's queue
        # hop, the thinking filter and this encoding.
        if trace is not None:
            trace.mark_strategy_delta()
        return sse.encode_event(chunk)

    n = len(plan.backends)
    # Python filter by default; the native C++ twin is opt-in via
    # QUORUM_TPU_NATIVE=1 (measured slower for typical delta sizes — see
    # quorum_tpu/native/__init__.py).
    filters = {i: make_thinking_filter(plan.thinking_tags) for i in range(n)}
    collected = ["" for _ in range(n)]
    queue: asyncio.Queue = asyncio.Queue()
    tasks = [
        asyncio.create_task(_pump(i, b, body, headers, timeout, queue, trace))
        for i, b in enumerate(plan.backends)
    ]

    try:
        finished = 0
        while finished < n:
            index, item = await queue.get()
            if item is _DONE:
                finished += 1
                text = filters[index].flush() if plan.hide_intermediate else ""
            else:
                text = filters[index].feed(item) if plan.hide_intermediate else item
            if not text:
                continue
            collected[index] += text
            if not plan.suppress_individual:
                yield content_frame(
                    oai.content_chunk(text, model=PROXY_MODEL_NAME, backend_index=index)
                )
    finally:
        for t in tasks:
            t.cancel()

    for i, content in enumerate(collected):
        aggregation_logger.info(
            "Backend %d content: %s", i, content or "No content received"
        )

    if not plan.skip_final:
        # Aggregate strategy: sources were already live-filtered per
        # strip_intermediate_thinking; hide_aggregator_thinking applies only to
        # the aggregator's own output below (matches combine.py's split).
        # Concatenate strategy: final join is stripped per hide_final_think
        # (reference quirk 6 semantics).
        if plan.strategy_name == "aggregate":
            labeled = [
                (plan.backends[i].name, text)
                for i, text in enumerate(collected)
                if text
            ]
        else:
            labeled = [
                (plan.backends[i].name, strip_thinking_tags(text, plan.thinking_tags, hide=plan.hide_final))
                for i, text in enumerate(collected)
                if text
            ]
        if labeled:
            if plan.strategy_name == "aggregate" and plan.aggregator is not None and plan.aggregate_params:
                if plan.aggregate_params.stream_aggregate:
                    # In-engine aggregation hop, live (docs/quorum.md): the
                    # aggregator's tokens ARE the client response — each
                    # delta rides out under the final-chunk id as it
                    # decodes, so aggregate TTFT is the aggregator's real
                    # TTFT instead of its full generation time. A closing
                    # zero-delta chunk carries finish_reason "stop" (the
                    # buffered path folds both into one chunk).
                    final_filter = make_thinking_filter(plan.thinking_tags)
                    with use_trace(trace), trace_span(
                            trace, "aggregate", strategy=plan.strategy_name,
                            aggregator=plan.aggregator.name, streamed=1):
                        agen = stream_aggregate_deltas(
                            labeled, plan.aggregator, plan.aggregate_params,
                            plan.user_query, headers,
                            aggregator_timeout or timeout)
                        async for item in agen:
                            if isinstance(item, AggregateOutcome):
                                break
                            text = (final_filter.feed(item)
                                    if plan.hide_final else item)
                            if text:
                                yield content_frame(oai.content_chunk(
                                    text, model=PROXY_MODEL_NAME,
                                    id=oai.PARALLEL_FINAL_ID))
                        tail = final_filter.flush() if plan.hide_final else ""
                    if tail:
                        yield content_frame(oai.content_chunk(
                            tail, model=PROXY_MODEL_NAME,
                            id=oai.PARALLEL_FINAL_ID))
                    yield sse.encode_event(oai.chunk(
                        id=oai.PARALLEL_FINAL_ID, model=PROXY_MODEL_NAME,
                        delta={}, finish_reason="stop"))
                    yield sse.encode_done()
                    return
                # use_trace: this generator body runs under the ASGI server
                # (the handler's context binding is gone), so the trace must
                # be re-bound for the aggregator hop's nested spans
                # (aggregator-call, a tpu:// aggregator's engine spans) to
                # attach — the same reason _pump re-binds.
                with use_trace(trace), trace_span(
                        trace, "aggregate", strategy=plan.strategy_name,
                        aggregator=plan.aggregator.name):
                    outcome = await aggregate_with_status(
                        labeled,
                        plan.aggregator,
                        plan.aggregate_params,
                        plan.user_query,
                        headers,
                        aggregator_timeout or timeout,
                    )
                combined = outcome.content
                if plan.hide_final:
                    combined = strip_thinking_tags(combined, plan.thinking_tags, hide=True)
            else:
                with trace_span(trace, "aggregate",
                                strategy=plan.strategy_name):
                    combined = plan.separator.join(text for _, text in labeled)
            aggregation_logger.info("Final aggregated streaming content: %s", combined)
            yield content_frame(oai.final_chunk(combined, model=PROXY_MODEL_NAME))
        else:
            yield sse.encode_event(
                oai.error_chunk(
                    "Error: All backends failed to provide content",
                    model=PROXY_MODEL_NAME,
                )
            )

    yield sse.encode_done()
