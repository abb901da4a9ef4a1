"""quorum_tpu — a TPU-native LLM ensemble serving framework.

An OpenAI-compatible ``/chat/completions`` service that fans each request out to
N model backends in parallel, incrementally filters "thinking" tags out of token
streams, and combines the N answers by concatenation or by an LLM-aggregation
hop — in both SSE-streaming and non-streaming modes.

Unlike the reference design it re-imagines (andrewginns/quorum, an HTTP-only
proxy — see /root/reference/src/quorum/oai_proxy.py), quorum_tpu's backends can
be **in-process JAX models on TPU** (``tpu://`` URLs): Hugging Face-style
checkpoints loaded into sharded JAX/XLA models on a device mesh, with the decode
loop emitting tokens directly into the SSE path. HTTP backends remain supported
(with true incremental streaming, fixing the reference's buffer-then-replay
behavior at oai_proxy.py:187-203).

Package layout:
  config        typed configuration (superset of the reference config.yaml)
  devices       which device a tpu:// backend may serve from (a TPU, or the
                CPU only when asked for by name) and how it is reported
  compile_cache where the persistent XLA compile cache lives
  filtering     incremental thinking-tag filter (oai_proxy.py:262-371 parity)
  sse, oai      SSE wire format, OpenAI chat-completion object builders
  observability request traces, latency histograms, the /metrics registry
  breaker, faults   failure breaker; named fault-injection sites
  backends/     Backend protocol: http://, tpu://, fakes for tests, registry
  strategies/   fan-out, concatenate & aggregate response combination
  server/       ASGI app + h11 server (python -m quorum_tpu.server.serve)
  engine/       continuous-batching engine: slot KV cache, admission,
                decode ring, tokenizers
  sched/        QoS admission policy, cost model, preemption
  cache/        paged KV, host prefix store, device→device KV handoff
  constrain/    response_format grammars → token DFAs
  models/       ModelSpec presets, pure-JAX transformer, init, HF loader, int8
  ops/          attention, Pallas prefill/decode kernels, norms, RoPE, sampling
  parallel/     mesh, sharding rules, ring/Ulysses attention, pipeline stages
  quorum/       router-tier quorum fan-out
  router/       replica router (python -m quorum_tpu.router)
  telemetry/    flight recorder, per-family device-time models, SLO classes
  analysis/     qlint static analysis, compile budget, compile counter
  training/     loss/train step (multi-chip sharding validation)
  native/       optional C++ thinking-tag filter (off by default)
"""

__version__ = "0.1.0"
