"""Configuration: a typed superset of the reference's ``config.yaml`` schema.

Reference schema (/root/reference/config.yaml:1-93, consumed at
/root/reference/src/quorum/oai_proxy.py:40-85):

  settings.timeout                          request timeout (seconds)
  primary_backends[] {name, url, model}     backend registry
  iterations.aggregation.strategy           "concatenate" | "aggregate"
  strategy.concatenate {...}                concatenate parameters
  strategy.aggregate {...}                  aggregate parameters

quorum_tpu extends ``primary_backends[].url`` with a ``tpu://`` scheme:

  tpu://<model-id>?family=llama&layers=4&d_model=256&...   in-process JAX model

Query parameters configure the model (see :mod:`quorum_tpu.models.registry`)
and the serving engine (``decode_chunk=``, ``decode_pipeline=``,
``decode_loop=`` for megachunk decode, ``slots=``,
``quant=``, ``prefix_store=host``/``prefix_store_bytes=``/
``prefix_store_chunk=`` for the tiered host KV prefix store,
``disagg=P+D`` for disaggregated prefill/decode device groups with
device→device KV handoff, ``zero_drain=0|1`` for zero-drain continuous
batching on colocated engines (staged in-flight row injection — admission
bursts never clamp the decode ring) … the full grammar is
the docstring of
:mod:`quorum_tpu.backends.tpu_backend`); anything absent falls back to the
named preset for ``<model-id>`` and the engine defaults.

Loading semantics preserved from the reference (oai_proxy.py:40-63): read
``config.yaml`` from the repo/cwd root, and on *any* failure fall back to a
hardcoded single-backend default (api.openai.com, timeout 60). Unlike the
reference, loading is lazy (no import-time side effects) and the path can be
overridden with the ``QUORUM_TPU_CONFIG`` environment variable.
"""

from __future__ import annotations

import copy
import logging
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any
from urllib.parse import parse_qsl, urlparse

import yaml

from quorum_tpu.filtering import DEFAULT_THINKING_TAGS as _BASE_THINKING_TAGS

logger = logging.getLogger(__name__)

DEFAULT_CONFIG: dict[str, Any] = {
    "primary_backends": [
        {"name": "default", "url": "https://api.openai.com/v1", "model": ""}
    ],
    "settings": {"timeout": 60},
}

# Reference config.yaml:34 lists "Thought" alongside "thought"; matching is
# case-insensitive so it is redundant, but kept for config-file parity.
DEFAULT_THINKING_TAGS = list(_BASE_THINKING_TAGS) + ["Thought"]

# What every refusal of a removed speculation option says (PR 51).
CHUNKS_ONLY = ("the engine decodes by chunks only (speculative decoding "
               "left the tree; the old code is at commit 804c4df)")

DEFAULT_AGGREGATE_PROMPT = (
    "You have received the following responses regarding the user's query:\n\n"
    "{intermediate_results}\n\n"
    "Synthesize these responses into a single, comprehensive answer that captures\n"
    "the best information and insights from all sources. Resolve any contradictions\n"
    "and provide a coherent, unified response."
)


@dataclass
class BackendSpec:
    """One entry of ``primary_backends``.

    ``retries`` (opt-in, default 0) applies to ``http(s)://`` backends
    only: non-streaming calls retry up to that many extra attempts on
    connect errors / upstream 5xx with capped exponential backoff + jitter,
    never past the request deadline (docs/robustness.md)."""

    name: str
    url: str
    model: str = ""
    retries: int = 0

    @property
    def is_valid(self) -> bool:
        # Parity: the endpoint filters backends with a non-empty url
        # (oai_proxy.py:1010).
        return bool(self.url)

    @property
    def scheme(self) -> str:
        return urlparse(self.url).scheme.lower()

    @property
    def is_tpu(self) -> bool:
        return self.scheme == "tpu"

    @property
    def tpu_model_id(self) -> str:
        """``tpu://gpt2?d_model=256`` → ``gpt2``."""
        p = urlparse(self.url)
        return (p.netloc + p.path).strip("/")

    @property
    def tpu_options(self) -> dict[str, str]:
        return dict(parse_qsl(urlparse(self.url).query))

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "BackendSpec":
        try:
            retries = int(d.get("retries", 0) or 0)
        except (TypeError, ValueError):
            logger.warning("backend %r: invalid retries=%r ignored",
                           d.get("name"), d.get("retries"))
            retries = 0
        return cls(
            name=str(d.get("name", "")),
            url=str(d.get("url", "") or ""),
            model=str(d.get("model", "") or ""),
            retries=max(0, retries),
        )


@dataclass
class ConcatenateParams:
    """``strategy.concatenate`` block (config.yaml:29-40)."""

    separator: str = "\n-------------\n"
    hide_intermediate_think: bool = True
    hide_final_think: bool = False
    thinking_tags: list[str] = field(default_factory=lambda: list(DEFAULT_THINKING_TAGS))
    skip_final_aggregation: bool = False

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ConcatenateParams":
        p = cls()
        p.separator = d.get("separator", p.separator)
        p.hide_intermediate_think = bool(d.get("hide_intermediate_think", p.hide_intermediate_think))
        p.hide_final_think = bool(d.get("hide_final_think", p.hide_final_think))
        p.thinking_tags = list(d.get("thinking_tags") or p.thinking_tags)
        p.skip_final_aggregation = bool(d.get("skip_final_aggregation", p.skip_final_aggregation))
        return p


@dataclass
class AggregateParams:
    """``strategy.aggregate`` block (config.yaml:44-93).

    ``source_backends`` is honored here (the reference computed it but never
    applied it — quirk 4, oai_proxy.py:774-780, 1209-1217).
    """

    source_backends: list[str] | str = "all"
    aggregator_backend: str = ""
    intermediate_separator: str = "\n\n---\n\n"
    include_source_names: bool = False
    source_label_format: str = "Response from {backend_name}:\n"
    prompt_template: str = DEFAULT_AGGREGATE_PROMPT
    strip_intermediate_thinking: bool = True
    hide_aggregator_thinking: bool = True
    thinking_tags: list[str] = field(default_factory=lambda: list(DEFAULT_THINKING_TAGS))
    include_original_query: bool = True
    query_format: str = "Original query: {query}\n\n"
    suppress_individual_responses: bool = False
    # In-engine aggregation hop (docs/quorum.md): the synthesis request is
    # a first-class engine request — aggregator_priority pins its QoS
    # dispatch class on qos=1 engines (interactive/batch/background; ""
    # sends no knob) and stream_aggregate relays the aggregator's tokens to
    # the client AS THEY DECODE on the streaming path (instead of one
    # buffered final chunk).
    aggregator_priority: str = "interactive"
    stream_aggregate: bool = False

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "AggregateParams":
        p = cls()
        p.source_backends = d.get("source_backends", p.source_backends)
        p.aggregator_backend = d.get("aggregator_backend", p.aggregator_backend) or ""
        p.intermediate_separator = d.get("intermediate_separator", p.intermediate_separator)
        p.include_source_names = bool(d.get("include_source_names", p.include_source_names))
        p.source_label_format = d.get("source_label_format", p.source_label_format)
        p.prompt_template = d.get("prompt_template", p.prompt_template)
        p.strip_intermediate_thinking = bool(
            d.get("strip_intermediate_thinking", p.strip_intermediate_thinking)
        )
        p.hide_aggregator_thinking = bool(
            d.get("hide_aggregator_thinking", p.hide_aggregator_thinking)
        )
        p.thinking_tags = list(d.get("thinking_tags") or p.thinking_tags)
        p.include_original_query = bool(d.get("include_original_query", p.include_original_query))
        p.query_format = d.get("query_format", p.query_format)
        p.suppress_individual_responses = bool(
            d.get("suppress_individual_responses", p.suppress_individual_responses)
        )
        prio = d.get("aggregator_priority", p.aggregator_priority)
        if prio not in ("", "interactive", "batch", "background"):
            raise ValueError(
                f"invalid aggregator_priority {prio!r} (interactive, "
                "batch, background, or \"\" to send no priority knob)")
        p.aggregator_priority = prio
        p.stream_aggregate = bool(d.get("stream_aggregate", p.stream_aggregate))
        if d.get("speculative_aggregation"):
            raise ValueError(
                "speculative_aggregation: true: the key was removed in PR 51 "
                f"— drop it: {CHUNKS_ONLY}")
        return p


@dataclass
class Config:
    """Parsed configuration plus the raw dict (kept for passthrough parity)."""

    raw: dict[str, Any]
    # File the raw dict was loaded from, when it came from disk — the handle
    # dev-mode hot reload watches (None for programmatic configs).
    source_path: "Path | None" = None

    @property
    def backends(self) -> list[BackendSpec]:
        return [BackendSpec.from_dict(b) for b in self.raw.get("primary_backends", [])]

    @property
    def valid_backends(self) -> list[BackendSpec]:
        return [b for b in self.backends if b.is_valid]

    @property
    def timeout(self) -> float:
        return float((self.raw.get("settings") or {}).get("timeout", 60) or 60)

    @property
    def strategy_name(self) -> str:
        """``iterations.aggregation.strategy`` (oai_proxy.py:1049-1053)."""
        # ``or {}`` guards YAML sections present but null ("iterations:" with
        # commented-out children parses to None).
        return (
            (self.raw.get("iterations") or {}).get("aggregation") or {}
        ).get("strategy", "concatenate")

    @property
    def has_strategy_config(self) -> bool:
        return "iterations" in self.raw and "strategy" in self.raw

    def parallel_enabled(self, n_valid_backends: int | None = None) -> bool:
        """Parity with the mode select at oai_proxy.py:1043-1044."""
        n = len(self.valid_backends) if n_valid_backends is None else n_valid_backends
        return self.has_strategy_config and n > 1

    @property
    def concatenate(self) -> ConcatenateParams:
        return ConcatenateParams.from_dict(
            (self.raw.get("strategy") or {}).get("concatenate") or {}
        )

    @property
    def aggregate(self) -> AggregateParams:
        return AggregateParams.from_dict(
            (self.raw.get("strategy") or {}).get("aggregate") or {}
        )

    def copy(self) -> "Config":
        return Config(raw=copy.deepcopy(self.raw), source_path=self.source_path)


def load_config(path: str | os.PathLike | None = None) -> Config:
    """Load ``config.yaml``; fall back to :data:`DEFAULT_CONFIG` on any error.

    Search order: explicit ``path`` arg → ``$QUORUM_TPU_CONFIG`` → ``config.yaml``
    in the current working directory → ``config.yaml`` next to the installed
    package's repo root.
    """
    candidates: list[Path] = []
    if path is not None:
        candidates.append(Path(path))
    elif os.environ.get("QUORUM_TPU_CONFIG"):
        candidates.append(Path(os.environ["QUORUM_TPU_CONFIG"]))
    else:
        candidates.append(Path.cwd() / "config.yaml")
        candidates.append(Path(__file__).resolve().parent.parent / "config.yaml")

    for cand in candidates:
        try:
            raw = yaml.safe_load(cand.read_text())
            if not isinstance(raw, dict):
                raise ValueError(f"config root must be a mapping, got {type(raw)}")
            logger.info("Loaded configuration from %s", cand)
            return Config(raw=raw, source_path=cand)
        except Exception as e:  # parity: any failure → default (oai_proxy.py:52-63)
            logger.debug("Could not load config from %s: %s", cand, e)

    logger.warning("Falling back to default configuration")
    return Config(raw=copy.deepcopy(DEFAULT_CONFIG))
