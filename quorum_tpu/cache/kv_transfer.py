"""Shared chunk-granular KV transfer: device↔host and device↔device.

The PR-3 prefix store moved KV in exactly one direction pair — slot cache
device→host on release, host→device on restore — with the slice/write
programs living inline in the engine. Disaggregated serving (``disagg=P+D``,
docs/tpu_backends.md) needs the same chunk-granular movement between TWO
device groups: a completed admission's staged KV prefix on the prefill mesh
hands off into the claimed slot of the decode mesh's cache. This module is
the generalization both paths share:

  - :func:`slice_rows` / :func:`write_rows` — the pure (jit-able) cache
    slice/update bodies, generic over the cache pytree (bf16 arrays or int8
    ``(values, scales)`` pairs) and over member-stacked caches (``[M, …]``
    leaves addressed by flat row ``m·n_slots + s``);
  - :func:`fetch_to_host` — the blocking device→host fetch the prefix-store
    snapshot worker runs (host arrays in the cache's native representation);
  - :func:`transfer` — move a sliced chunk pytree onto a target sharding:
    the DIRECT device→device route (``jax.device_put`` onto the target
    mesh — ICI/DCN where the runtime supports it) with a host-bounce
    fallback when the direct put is rejected, recording bytes and seconds
    on the ``quorum_tpu_kv_handoff_*`` families either way.

Layout convention (matches the engine's dense slot cache, models/transformer.py
``init_cache``): non-stacked leaves are ``[L, S, T, K·hd]`` values and, for an
int8 side, ``[L, S, T, K]`` scales (slot axis 1, position axis 2, a position's
heads one line); stacked leaves carry a leading member axis ``[M, L, S, T, …]``.
Sliced chunks drop the slot (and member) axis and are K-major: ``[L, K, n, hd]``
values, ``[L, K, n]`` scales — the one wire format snapshot, restore, and
handoff all speak (host stores and peers hold it), transposed at the wire.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quorum_tpu import observability as obs
from quorum_tpu.cache.paging import (
    kv_is_paged,
    paged_slice_rows,
    paged_write_rows,
)

logger = logging.getLogger(__name__)


def _any_paged(cache) -> bool:
    return (kv_is_paged(cache)
            or (isinstance(cache, tuple)
                and any(kv_is_paged(c) for c in cache)))


def slice_rows(cache, row, start, n: int, *, stacked: bool, n_slots: int,
               n_kv_heads: int):
    """Slice ``n`` cache positions of flat row ``row`` starting at ``start``
    out of a cache pytree (pure; call under jit). Returns the chunk pytree
    in the ``[L, K, n, …]`` wire layout (``n_kv_heads`` splits a dense
    side's lines into their heads). Non-donating by design — snapshot
    and handoff both READ a live cache. Paged caches (``PagedKV`` sides)
    gather through the page table into the SAME wire layout, so every
    consumer — snapshot, restore, handoff — is layout-blind."""
    if _any_paged(cache):
        def take_paged(c):
            return paged_slice_rows(c, row, start, n,
                                    stacked=stacked, n_slots=n_slots)
        if kv_is_paged(cache):
            return take_paged(cache)
        return tuple(take_paged(c) for c in cache)

    def take(a):
        if stacked:
            m, s = row // n_slots, row % n_slots
            lines = lax.dynamic_slice(
                a, (m, 0, s, start, 0), (1, a.shape[1], 1, n, a.shape[4])
            )[0][:, 0]
        else:
            lines = lax.dynamic_slice(
                a, (0, row, start, 0), (a.shape[0], 1, n, a.shape[3]))[:, 0]
        if lines.shape[2] == n_kv_heads:  # an int8 side's scales [L, n, K]
            return lines.transpose(0, 2, 1)
        # [L, n, K·hd] → [L, K, n, hd]
        return lines.reshape(lines.shape[:2] + (n_kv_heads, -1)).transpose(
            0, 2, 1, 3)

    return jax.tree.map(take, cache)


def write_rows(cache, chunk, row, start, *, stacked: bool, n_slots: int):
    """Write a ``[L, K, n, …]`` chunk pytree into positions
    [start, start+n) of flat row ``row`` (pure; call under jit with the
    cache donated — the restore/handoff write is a cache mutation like any
    other). Paged caches scatter through the page table (the row's pages
    must be reserved — admission pre-reserves the full span)."""
    if _any_paged(cache):
        def put_paged(c, h):
            return paged_write_rows(c, h, row, start,
                                    stacked=stacked, n_slots=n_slots)
        if kv_is_paged(cache):
            return put_paged(cache, chunk)
        return tuple(put_paged(c, h) for c, h in zip(cache, chunk))

    def put(a, h):
        # [L, K, n, hd] → [L, n, K·hd]; scales [L, K, n] → [L, n, K]
        lines = jnp.moveaxis(h, 1, 2).reshape(
            h.shape[0], h.shape[2], -1).astype(a.dtype)
        if stacked:
            m, s = row // n_slots, row % n_slots
            return lax.dynamic_update_slice(
                a, lines[None, :, None], (m, 0, s, start, 0))
        return lax.dynamic_update_slice(a, lines[:, None], (0, row, start, 0))

    return jax.tree.map(put, cache, chunk)


def fetch_to_host(payload) -> list[np.ndarray]:
    """Blocking device→host fetch of a sliced chunk pytree's leaves, in
    ``jax.tree.leaves`` order — the prefix-store snapshot worker's half of
    the device↔host route (host arrays stay in the cache's NATIVE
    representation, so ``kv_quant=int8`` halves host bytes)."""
    # qlint: allow-sync(snapshot-worker thread: the fetch blocks OFF the scheduler's hot turn by design)
    leaves = jax.device_get(jax.tree.leaves(payload))
    return [np.asarray(x) for x in leaves]


def _is_replicated(sharding) -> bool:
    """Fully-replicated check that degrades to True (— "an ordinary copy")
    on shardings/objects that don't expose the property."""
    try:
        return bool(sharding.is_fully_replicated)
    except Exception:
        return True


def transfer(chunk, sharding, *, record: bool = True):
    """Move a sliced chunk pytree onto ``sharding`` (typically the target
    group's replicated sharding) and block until it lands.

    The direct device→device route first: ``jax.device_put`` of the
    committed source arrays onto the target mesh — no host copy in the
    dataflow the runtime has to honor. When either side is PARTITIONED
    (per-group ``tp=`` sharding, an ``sp``-sharded staging cache) the same
    put additionally reshards on the fly between the two groups' layouts —
    labelled ``reshard`` so a deployment can see which handoffs pay the
    re-layout. When the runtime rejects the direct put (platforms without
    a cross-group transfer path, or a cross-mesh reshard it cannot
    express), fall back to an explicit host bounce — same bytes, one extra
    hop, never a failure mode.
    Returns ``(moved_pytree, n_bytes, seconds, route)`` with ``route`` one
    of ``"direct"`` / ``"reshard"`` / ``"host-bounce"`` (the engine adds
    the fourth, ``"resident"``, for zero-drain same-mesh injection);
    bytes/seconds land on the route-labelled
    ``quorum_tpu_kv_handoff_{bytes,seconds}`` families when ``record``.
    """
    leaves, treedef = jax.tree.flatten(chunk)
    n_bytes = int(sum(x.nbytes for x in leaves))
    t0 = time.perf_counter()
    route = "direct"
    if not _is_replicated(sharding) or any(
            not _is_replicated(getattr(x, "sharding", None))
            for x in leaves):
        route = "reshard"
    try:
        moved = [jax.device_put(x, sharding) for x in leaves]
        # qlint: allow-sync(handoff commit: the blocking wait IS the measured kv_handoff_seconds latency)
        jax.block_until_ready(moved)
    except Exception:
        # Host bounce: fetch then re-place. Logged once per call — a
        # deployment silently bouncing every handoff through host RAM is a
        # perf bug someone must be able to see.
        logger.warning(
            "direct device->device KV transfer rejected; bouncing %d bytes "
            "via host", n_bytes, exc_info=True)
        route = "host-bounce"
        # qlint: allow-sync(host-bounce fallback: an explicit d2h+h2d copy, logged loudly above)
        moved = [jax.device_put(np.asarray(x), sharding) for x in leaves]
        # qlint: allow-sync(handoff commit: the blocking wait IS the measured kv_handoff_seconds latency)
        jax.block_until_ready(moved)
    dt = time.perf_counter() - t0
    if record:
        obs.KV_HANDOFF_BYTES.inc(n_bytes, route=route)
        obs.KV_HANDOFF_SECONDS.observe(dt, route=route)
    return jax.tree.unflatten(treedef, moved), n_bytes, dt, route
