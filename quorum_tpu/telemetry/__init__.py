"""quorum_tpu.telemetry — the engine flight-recorder subsystem (ISSUE 12).

Four load-bearing pieces plus the shared metrics plumbing:

  - :mod:`~quorum_tpu.telemetry.recorder` — the always-on bounded ring of
    structured engine events (dispatch/reap per program family, admission/
    injection/handoff/register, clamp transitions, deadline expiries,
    breaker/containment), exported as JSON and Chrome/Perfetto trace-event
    format from ``GET /debug/engine/timeline`` and auto-dumped to ``logs/``
    on failure containment.
  - :mod:`~quorum_tpu.telemetry.device_ledger` — the one account of device
    time: every program a scheduler loop dispatches, booked landing to
    landing on the loop's clock; a dry device's time booked to the phase
    of the turn that was open.
  - :mod:`~quorum_tpu.telemetry.latency` — per-program-family EWMAs/
    percentiles of the booked seconds (the generalization of the PR 6
    clamp EWMA) beside ``quorum_tpu_dispatch_device_seconds{family=...}``.
  - :mod:`~quorum_tpu.telemetry.slo` — deadline-headroom SLO classes,
    per-class/stage good-vs-breached counters, and the sliding-window burn
    rate behind the ``/health`` → ``/ready`` degradation story.
  - :mod:`~quorum_tpu.telemetry.metrics` — the Prometheus primitive types
    and exposition validator (moved out of ``observability.py``, which
    keeps the registered families and re-exports these for back-compat).

See docs/observability.md.
"""

from quorum_tpu.telemetry.latency import LatencyModel
from quorum_tpu.telemetry.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    validate_exposition,
)
from quorum_tpu.telemetry.recorder import RECORDER, FlightRecorder
from quorum_tpu.telemetry.slo import SLO, SloTracker, classify

__all__ = [
    "DEFAULT_BUCKETS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "LatencyModel",
    "MetricsRegistry",
    "RECORDER",
    "SLO",
    "SloTracker",
    "classify",
    "validate_exposition",
]
