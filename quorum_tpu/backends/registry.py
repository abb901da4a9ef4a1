"""Backend registry: turn the config's ``primary_backends`` into live Backends.

The reference had no registry — the endpoint re-read the config dict on every
request (/root/reference/src/quorum/oai_proxy.py:1010-1024). Here backends are
constructed once per server (TPU models must load weights and compile exactly
once) and looked up by name. Scheme dispatch:

  http:// https://   → HttpBackend
  tpu://             → TpuBackend (lazy import; model zoo in quorum_tpu.models)
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Iterable

from quorum_tpu.backends.base import Backend
from quorum_tpu.backends.http_backend import HttpBackend
from quorum_tpu.config import BackendSpec, Config
from quorum_tpu.devices import NoAcceleratorError

logger = logging.getLogger(__name__)


class BackendRegistry:
    def __init__(self, backends: Iterable[Backend] = ()):
        self._by_name: dict[str, Backend] = {}
        self._order: list[str] = []
        # BackendSpec each backend was constructed from (when known) — the
        # identity hot reload compares to decide reuse vs reconstruction.
        self._spec_by_name: dict[str, BackendSpec] = {}
        # Configured backends that failed to construct: name → error text.
        # Requests still degrade to the survivors (the reference's
        # contract), but /health reports them and /ready stays 503 — a
        # quorum missing a member must not look whole.
        self.failed: dict[str, str] = {}
        for b in backends:
            self.add(b)

    def add(self, backend: Backend, spec: BackendSpec | None = None) -> None:
        if backend.name not in self._by_name:
            self._order.append(backend.name)
        self._by_name[backend.name] = backend
        if spec is not None:
            self._spec_by_name[backend.name] = spec

    def spec_of(self, name: str) -> BackendSpec | None:
        return self._spec_by_name.get(name)

    def get(self, name: str) -> Backend | None:
        return self._by_name.get(name)

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __len__(self) -> int:
        return len(self._by_name)

    @property
    def backends(self) -> list[Backend]:
        """Backends in config order."""
        return [self._by_name[n] for n in self._order]

    def select(self, names: list[str] | str | None) -> list[Backend]:
        """Resolve a ``source_backends`` setting: ``"all"``/None → everything,
        else the named subset (unknown names are skipped with a warning).

        If *no* name resolves the result is empty — callers surface a
        configuration error rather than silently fanning out to backends the
        operator excluded."""
        if names is None or names == "all" or names == []:
            return self.backends
        if isinstance(names, str):  # a single backend name, not a list
            names = [names]
        out = []
        for n in names:
            b = self.get(n)
            if b is None:
                logger.warning("source_backends entry %r is not a configured backend", n)
            else:
                out.append(b)
        return out

    async def aclose(self) -> None:
        for b in self.backends:
            close = getattr(b, "aclose", None)
            if close is not None:
                await close()


def _build_tpu_backend(spec: BackendSpec) -> Backend:
    from quorum_tpu.backends.tpu_backend import TpuBackend  # lazy: pulls in jax

    return TpuBackend.from_spec(spec)


SCHEME_FACTORIES: dict[str, Callable[[BackendSpec], Backend]] = {
    "http": lambda s: HttpBackend(s.name, s.url, s.model, retries=s.retries),
    "https": lambda s: HttpBackend(s.name, s.url, s.model, retries=s.retries),
    "tpu": _build_tpu_backend,
}


def _construct(reg: BackendRegistry, spec: BackendSpec) -> None:
    """Construct ``spec``'s backend into ``reg``, or record why it could not
    be: a backend that fails to construct (bad tpu:// model id, missing
    weights, ...) must not take the whole server down with it — except for
    :class:`NoAcceleratorError`, which no survivor can make up for."""
    factory = SCHEME_FACTORIES.get(spec.scheme)
    if factory is None:
        logger.warning(
            "Backend %s has unsupported URL scheme %r — skipped",
            spec.name, spec.scheme)
        reg.failed[spec.name] = f"unsupported URL scheme {spec.scheme!r}"
        return
    try:
        reg.add(factory(spec), spec=spec)
    except NoAcceleratorError:
        raise
    except Exception as e:
        logger.exception(
            "Failed to construct backend %s (%s) — skipped", spec.name, spec.url)
        reg.failed[spec.name] = f"{type(e).__name__}: {e}"


def build_registry(config: Config, **overrides: Any) -> BackendRegistry:
    """Construct backends for every *valid* (non-empty-url) configured backend.

    ``overrides`` maps backend name → pre-built Backend instance (tests inject
    FakeBackends this way instead of monkeypatching a transport).
    """
    reg = BackendRegistry()
    for spec in config.valid_backends:
        if spec.name in overrides:
            reg.add(overrides[spec.name])
            continue
        _construct(reg, spec)
    for name, backend in overrides.items():
        if name not in reg:
            reg.add(backend)
    return reg


def rebuild_registry(
    config: Config, old: BackendRegistry, overrides: dict[str, Backend]
) -> tuple[BackendRegistry, list[Backend]]:
    """Registry for a *changed* config, reusing live backends where identity
    (name + url + model) is unchanged — a dev-mode config edit must never
    tear down a serving ``tpu://`` engine that the edit didn't touch.
    (Unchanged-URL backends that DO reconstruct still re-attach to their
    weights via the engine cache — ``get_engine`` keys on weight identity —
    but instance reuse also preserves per-backend dispatch state.)

    Returns ``(new_registry, dropped)`` — ``dropped`` are the old backends
    no longer referenced, for the caller to close.
    """
    reg = BackendRegistry()
    for spec in config.valid_backends:
        if spec.name in overrides:
            reg.add(overrides[spec.name])
            continue
        prev_spec = old.spec_of(spec.name)
        prev = old.get(spec.name)
        if (prev is not None and prev_spec is not None
                and prev_spec.url == spec.url
                and prev_spec.model == spec.model
                and prev_spec.retries == spec.retries):
            reg.add(prev, spec=spec)
            continue
        _construct(reg, spec)
    for name, backend in overrides.items():
        if name not in reg:
            reg.add(backend)
    kept = {id(b) for b in reg.backends}
    dropped = [b for b in old.backends if id(b) not in kept]
    return reg, dropped
