"""Which device this process may serve from — and saying so.

With ``JAX_PLATFORMS`` unset and no chip attached, ``jax.devices()`` prints
libtpu noise on stderr and returns ``[CpuDevice(id=0)]``: a ``tpu://``
backend would then serve from the CPU, every endpoint would answer 200, and
nothing would name the platform. This module is the one place that rule
lives: a TPU, or the CPU only when it was asked for by name. Importing it
does not import jax (the registry and the compile-cache placement need the
rule before any backend exists).
"""

from __future__ import annotations

import os
from collections.abc import Mapping


class NoAcceleratorError(RuntimeError):
    """jax came up without a TPU and the CPU was not asked for by name.
    Deliberately not swallowed by the backend registry: a ``tpu://`` server
    that would silently serve from the CPU must not start at all."""


def cpu_requested(environ: Mapping[str, str] = os.environ) -> bool:
    """True iff the CPU was asked for by name: ``JAX_PLATFORMS`` starts
    with ``cpu`` (as the test suite and every CPU script set)."""
    return environ.get("JAX_PLATFORMS", "").strip().lower().startswith("cpu")


def serving_devices() -> list:
    """The devices a mesh may be built on: the TPU's — or the CPU's, only
    when :func:`cpu_requested`."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not cpu_requested():
        raise NoAcceleratorError(
            f"jax found no TPU (platform {platform!r}, "
            f"{len(devices)} device(s)) and the CPU was not asked for: set "
            "JAX_PLATFORMS=cpu to run on the CPU deliberately")
    return devices


def device_report(mesh) -> dict:
    """Where an engine runs, as jax reports it: what the construction log
    line and ``/health``'s per-backend check row carry."""
    import jax

    dev = mesh.devices.flat[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mesh": {axis: int(n) for axis, n in mesh.shape.items()},
    }


def device_memory(mesh) -> list[dict]:
    """Allocator readings for every local device, where the backend reports
    them (TPU; the CPU client reports none), each marked with whether it is
    in ``mesh``: how /health shows that a tensor-parallel engine's weights
    and KV are spread over its chips, how full each one is, and which chips
    of the host an engine leaves idle. A client call, not a device sync."""
    import jax

    in_mesh = {dev.id for dev in mesh.devices.flat}
    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            out.append({"id": dev.id, "in_mesh": dev.id in in_mesh,
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
    return out
