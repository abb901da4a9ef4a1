"""Runtime backstop for the recompile budget: count XLA compiles.

jax's monitoring stream emits ``/jax/core/compile/backend_compile_duration``
once per actual backend (XLA) compilation — the event behind
``jax.log_compiles``, minus the log parsing. This module registers one
process-wide listener (idempotent, no jax backend initialization) and keeps
two readings:

- :func:`compiles_total` — every XLA compile since :func:`install`, the
  counter the test suite's conftest hook snapshots around warmed-engine
  runs ("a warmed engine compiles nothing" — any new program family fails
  loudly, replacing the per-PR cache-key pin tests' weaker coverage);
- ``quorum_tpu_recompiles_total`` (observability.RECOMPILES, on /metrics) —
  compiles observed AFTER the process served its first completed request
  (:func:`mark_warm`, called by the engine when a request's stream
  finishes). First-of-shape traffic still ticks it legitimately (the first
  constrained request, a new history bucket, a second engine); the signal
  is SUSTAINED growth under steady traffic — steady state dispatches
  cached programs, so a sustained rate means program-key drift (a shape
  family leak, an unhashable key component), exactly what the static
  ``recompile`` rules and compile_budget.json exist to prevent.

A third reading serves the engine's turn accounting:
:func:`thread_seconds`, the seconds the CALLING thread has spent inside
backend compiles (a load from the persistent cache raises the same event),
which the scheduler loop books as its turn's ``compile`` phase.

Pure stdlib + jax; safe to import before backends exist.
"""

from __future__ import annotations

import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = False
_warm = False
_total = 0
_local = threading.local()  # .seconds: this thread's compile time so far


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    global _total
    if event != COMPILE_EVENT:
        return
    # jax reports the event on the thread that compiled
    _local.seconds = thread_seconds() + duration
    with _lock:
        _total += 1
        warm = _warm
    if warm:
        from quorum_tpu import observability as obs

        obs.RECOMPILES.inc()


def install() -> None:
    """Register the monitoring listener (idempotent, process-wide)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_event_duration)


def compiles_total() -> int:
    """XLA compiles observed since install() (0 if never installed)."""
    with _lock:
        return _total


def thread_seconds() -> float:
    """Seconds the calling thread has spent in XLA backend compiles (built,
    or loaded from the persistent cache) since it started."""
    return getattr(_local, "seconds", 0.0)


def mark_warm() -> None:
    """Arm the post-warmup counter: the process has served a request, so
    every later compile lands on ``quorum_tpu_recompiles_total``."""
    global _warm
    with _lock:
        _warm = True


def is_warm() -> bool:
    with _lock:
        return _warm


def reset_for_tests() -> None:
    """Disarm + zero the readings (the listener stays registered)."""
    global _warm, _total
    with _lock:
        _warm = False
        _total = 0
