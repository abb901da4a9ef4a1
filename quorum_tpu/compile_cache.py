"""Persistent XLA compilation cache for every jax-touching entry point.

First compilation of the serving programs on a TPU costs tens of seconds
each (prefill buckets, decode chunk variants, segment programs); a process
restart — a new bench child, a redeployed server, a crash-recovered engine —
pays all of it again even though nothing changed. jax's persistent
compilation cache keys compiled executables by (program, compiler options,
backend/topology) and reloads them across processes, turning restart
compile time into a disk read.

Where the cache lives (the directory is part of the cache key, so it must
not move between runs — never a temp name, pid or time in the path):

- ``JAX_COMPILATION_CACHE_DIR`` set: jax itself uses that directory and this
  module sets none — not even when ``QUORUM_TPU_COMPILE_CACHE=<dir>`` names
  another one. The cache can always be placed from outside.
- unset: ``QUORUM_TPU_COMPILE_CACHE=<dir>`` if given, else one fixed
  directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored).

When it is on: by default everywhere except where the CPU was asked for by
name (``devices.cpu_requested`` — decided from the environment, never by
initializing a backend). XLA:CPU executables are
AOT-compiled against exact host CPU features and reload with SIGILL-risk
warnings even on the same machine, so CPU runs are opt-in:
``QUORUM_TPU_COMPILE_CACHE=1`` (or ``=<dir>``) forces the cache anywhere,
``=0`` disables it everywhere, ``JAX_COMPILATION_CACHE_DIR`` included.

**CPU determinism caveat** (why the test suite runs with the cache OFF —
tests/conftest.py): on XLA:CPU, one logical program can legitimately
compile to several numerically different executables (e.g. a
layout-specialized variant for donated-buffer steady state vs the first
call's fresh arrays). In-process, jax compiles each variant fresh and the
results are repeatable; with the persistent cache, a variant DESERIALIZED
from an entry another process/engine instance wrote can differ in float
reassociation from the in-process compile — and a near-tie sample then
flips between two otherwise-identical generations. Harmless for serving
throughput, fatal for bit-exact determinism tests.

**Its first reader is the program store** (``engine/prepare.py``): where
the cache is on, a serving engine keeps every program it compiles whole in
``programs/`` inside the cache's directory (``prepare.store_root``), and a
later start loads what is there on a pool of threads, before it makes its
weights, without even tracing. That preparation loads only: what the store
lacks is compiled by its first dispatch, reading this cache as before, and
then stored. Emptying the cache's directory empties the store with it.

No reference equivalent: the reference proxy compiles nothing
(/root/reference/src/quorum/oai_proxy.py is pure HTTP dispatch); this is
TPU-runtime surface the reference never needed.
"""

from __future__ import annotations

import os
from collections.abc import Mapping

from quorum_tpu.devices import cpu_requested

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

_DONE = False


def cache_enabled(environ: Mapping[str, str] = os.environ) -> bool:
    knob = environ.get("QUORUM_TPU_COMPILE_CACHE", "")
    if knob == "0":
        return False
    return bool(knob) or not cpu_requested(environ)


def cache_dir_to_set(environ: Mapping[str, str] = os.environ) -> str | None:
    """The directory this module must hand to jax, or None when it must set
    none: the cache is off, or ``JAX_COMPILATION_CACHE_DIR`` already places
    it from outside."""
    if not cache_enabled(environ) or environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    knob = environ.get("QUORUM_TPU_COMPILE_CACHE", "")
    return knob if knob not in ("", "1") else REPO_CACHE_DIR


def enable_persistent_compile_cache() -> None:
    """Idempotently point jax at the on-disk compilation cache."""
    global _DONE
    if _DONE:
        return
    _DONE = True

    import jax

    if not cache_enabled():
        if os.environ.get("QUORUM_TPU_COMPILE_CACHE") == "0":
            jax.config.update("jax_enable_compilation_cache", False)
        return
    cache_dir = cache_dir_to_set()
    if cache_dir is not None:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every program the serving stack compiles: the default
    # 1 s / 0-byte floors would skip the small-but-many decode/sampler
    # variants whose compiles still add up on a restart.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
