"""The program store: what an engine compiled, kept whole across starts and
loaded ahead of any request on a pool of threads.

Tracing and lowering a serving program takes about a second of the
interpreter lock whatever the persistent compile cache holds, reading the
cache another 0.2 to 3.7 s, a configuration's traffic reaches 12 to 19
programs, and a lazy start does all of that one program after another on the
thread that serves the request. So a compiled program is kept whole
(``jax.experimental.serialize_executable``: the executable with its calling
convention) in

    <compile cache directory>/programs/<build>/<engine>/<builder>=<key>

and an engine that was asked to (``InferenceEngine(prepare=True)``, as
``TpuBackend`` asks where the persistent compile cache is on) loads what its
directory holds on a pool of threads before it makes its weights, without
tracing anything. ``<build>`` is a digest of what every program follows from:
every source file of this package, the versions of jax, jaxlib and the
device's runtime, the device kind and the environment variables that reach
tracing or the compiler (``PROGRAM_ENV``). ``<engine>`` is a digest of the
engine's configuration (``InferenceEngine._program_config``: the model spec,
slots, members, quantisation, the prefill and decode chunks). The file's name
is the builder's and the key its memo holds the program under
(``engine.py::_program``), so a start lists its directory and knows which
entry of which memo each file is.

**This module only loads.** A program the directory does not hold is the first
dispatch's, as every program was before: it compiles from its real arguments
(``fn.lower(*args).compile()``: the text, and so the persistent cache's entry,
that a lazy ``jax.jit`` call makes), keeps the compiled program in the memo
and stores it (``Preparation.keep``), so the next start loads what this
one's traffic needed. Compiling a family ahead was built and measured and
does not pay on the chip's machine (PERF.md section 6, PR 44: the v5e compiler
fills the machine's cores by itself).

**The store's lifetime.** Any edit to the package makes a new ``<build>``. A
start keeps its own build's directory and the one other build used last (a
comparison of two commits on one machine alternates between two builds) and
removes every other. Where the persistent cache has a size limit
(``jax_compilation_cache_max_size``), the whole of ``programs/`` keeps under
the same limit, on its own account beside jax's entries: the least recently
loaded files go first. Emptying the cache's directory empties the store.

The memo holds a ``Future`` under a key while its program is being loaded: a
dispatch that comes early waits for that one program
(``InferenceEngine._memo``). A file that does not load is logged and removed,
and its key left to the first dispatch.
"""

from __future__ import annotations

import ast
import functools
import hashlib
import logging
import os
import pickle
import queue
import shutil
import threading
import time
from concurrent.futures import Future

logger = logging.getLogger(__name__)

# Environment variables that reach tracing, lowering or the compiler: part of
# the build's digest. A new switch that changes a program's text belongs here
# (tests/test_program_prepare.py holds the package to it).
PROGRAM_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "JAX_ENABLE_X64",
               "JAX_DEFAULT_MATMUL_PRECISION", "JAX_DEFAULT_PRNG_IMPL",
               "JAX_THREEFRY_PARTITIONABLE", "QUORUM_TPU_FLASH",
               "QUORUM_TPU_QEINSUM_INT8")
BUILDS_KEPT = 2  # this build's directory and the one other used last


def pool_width() -> int:
    """Threads that load stored programs: most of the machine's cores, at
    most 8, at least 2."""
    return max(2, min(8, (os.cpu_count() or 2) - 2))


def store_root() -> str | None:
    """``programs/`` inside the persistent compile cache's directory; None
    where that cache is off."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, "programs") if root else None


@functools.cache
def build_digest(device) -> str:
    """What every program of this process on ``device`` follows from: the
    package's sources, the versions, the device and the environment."""
    import jax
    import jaxlib

    h = hashlib.sha256()
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for root, dirs, files in sorted(os.walk(package)):
        dirs.sort()
        for name in sorted(files):
            if name.endswith((".py", ".json")):
                path = os.path.join(root, name)
                h.update(os.path.relpath(path, package).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    h.update(repr((jax.__version__, jaxlib.__version__,
                   device.client.platform_version, device.device_kind,
                   [(k, os.environ.get(k)) for k in PROGRAM_ENV])).encode())
    return h.hexdigest()[:32]


def _codec():
    """(compress, decompress): zstandard where it is installed, as jax's own
    cache entries are, else zlib."""
    try:
        import zstandard

        return (zstandard.ZstdCompressor(level=3).compress,
                zstandard.ZstdDecompressor().decompress)
    except ImportError:
        import zlib

        return functools.partial(zlib.compress, level=1), zlib.decompress


def _load(path: str, device):
    """The stored program, loaded onto ``device``."""
    from jax.experimental import serialize_executable

    with open(path, "rb") as f:
        blob = f.read()
    os.utime(path)  # the size limit goes by the last load
    payload, in_tree, out_tree = pickle.loads(_codec()[1](blob))
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree, backend=device.client,
        execution_devices=[device])


def _files(root: str) -> list:
    """(mtime, size, path) of every file under ``root`` that is still
    there: another process may be removing files too."""
    out = []
    for base, _, names in os.walk(root):
        for name in names:
            try:
                st = os.stat(os.path.join(base, name))
            except FileNotFoundError:
                continue
            out.append((st.st_mtime, st.st_size, os.path.join(base, name)))
    return out


def _store(path: str, prog, root: str) -> None:
    """Write ``prog`` to ``path`` (whole, or not at all), then hold the
    store under ``root`` to the persistent cache's size limit."""
    from jax.experimental import serialize_executable

    import jax

    t0 = time.perf_counter()
    try:
        blob = _codec()[0](pickle.dumps(serialize_executable.serialize(prog)))
        tmp = os.path.join(os.path.dirname(path),
                           f".{os.getpid()}.{threading.get_ident()}.tmp")
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except Exception:  # a program that does not serialise is compiled anew
        logger.warning("program not stored at %s", path, exc_info=True)
        return
    logger.info("program stored: %s, %d bytes in %.1f s",
                os.path.basename(path), len(blob), time.perf_counter() - t0)
    limit = jax.config.jax_compilation_cache_max_size
    if limit > 0:
        kept = 0
        for _, size, other in sorted(_files(root), reverse=True):
            kept += size
            if kept > limit and other != path:
                try:
                    os.remove(other)
                except OSError:  # a peer's eviction took it first
                    pass


def _prune(root: str, build: str) -> None:
    """Keep this build's directory and the other build used last; remove
    the rest of ``root`` (an edit to the package orphans a build's files)."""
    os.utime(os.path.join(root, build))
    others = []
    for e in os.scandir(root):
        if e.name != build:
            try:
                others.append((e.stat().st_mtime, e.path))
            except FileNotFoundError:
                pass
    for _, path in sorted(others, reverse=True)[BUILDS_KEPT - 1:]:
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        else:
            try:
                os.remove(path)
            except OSError:
                pass


class Preparation:
    """One engine's directory of the store: the pool that loads from it,
    the count of programs still out, the writers of what a first dispatch
    built, and the record that ends in one INFO line."""

    @classmethod
    def open(cls, tag: str, device, config) -> "Preparation | None":
        """The preparation of an engine of ``config`` on ``device``; None
        where there is no store (the persistent cache is off)."""
        root = store_root()
        if root is None:
            return None
        build = build_digest(device)
        directory = os.path.join(
            root, build, hashlib.sha256(repr(config).encode()).hexdigest()[:32])
        try:
            os.makedirs(directory, exist_ok=True)
            _prune(root, build)
        except OSError:
            logger.warning("no program store at %s", directory, exc_info=True)
            return None
        return cls(tag, root, directory, device)

    def __init__(self, tag: str, root: str, directory: str, device):
        self.tag = tag
        self.root = root
        self.dir = directory
        self.device = device  # the engine's one device
        self.width = pool_width()
        self.t0 = time.perf_counter()
        self.seconds = 0.0  # wall clock of the whole preparation, at its end
        self.loaded = 0
        self.failed = 0
        self._out = 0
        self._sealed = False
        self._closed = False
        self._rows: list[tuple[float, str]] = []
        self._lock = threading.Lock()
        self._writers: list[threading.Thread] = []
        self._tasks: queue.SimpleQueue = queue.SimpleQueue()
        # daemon threads: a process that ends (the benchmark's reference
        # check builds an engine for its weights alone) waits for no load
        self._threads = [threading.Thread(
            target=self._work, name=f"prepare-{tag}-{i}", daemon=True)
            for i in range(self.width)]
        for t in self._threads:
            t.start()

    @property
    def pending(self) -> int:
        """Programs being loaded and not yet in (or given up)."""
        with self._lock:
            return self._out

    def stored(self) -> list[tuple[str, object]]:
        """(builder's name, key) of every program the directory holds."""
        out = []
        for name in sorted(os.listdir(self.dir)):
            builder, eq, key = name.partition("=")
            if eq and not name.startswith("."):
                try:
                    out.append((builder, ast.literal_eval(key)))
                except (ValueError, SyntaxError):
                    pass
        return out

    def _path(self, name: str, key) -> str:
        return os.path.join(self.dir, f"{name}={key!r}")

    def load(self, memo: dict, name: str, key) -> None:
        """Load ``name``'s program for ``key`` on the pool into
        ``memo[key]``, which holds a Future until then."""
        fut: Future = Future()
        memo[key] = fut
        with self._lock:
            self._out += 1
        self._tasks.put((memo, name, key, fut))

    def _work(self) -> None:
        while (task := self._tasks.get()) is not None:
            self._one(*task)

    def _one(self, memo, name, key, fut) -> None:
        t0 = time.perf_counter()
        path = self._path(name, key)
        prog = None
        if not self._closed:
            try:
                prog = _load(path, self.device)
            except Exception:  # another runtime's, a torn write, evicted
                logger.warning("stored program %s does not load; left to "
                               "its first dispatch", path, exc_info=True)
                try:
                    os.remove(path)
                except OSError:
                    pass
        seconds = time.perf_counter() - t0
        if prog is None:
            memo.pop(key, None)
        else:
            memo[key] = prog
        with self._lock:
            self._out -= 1
            if prog is not None:
                self.loaded += 1
                self._rows.append((seconds, f"{name}={key!r} {seconds:.1f}s"))
            else:
                self.failed += 1
            last = self._sealed and self._out == 0
        fut.set_result(prog)
        if last:
            self._finish()

    def seal(self) -> None:
        """No more loads: the program that comes in last ends the
        preparation (an empty directory ends it here)."""
        with self._lock:
            self._sealed = True
            last = self._out == 0
        if last:
            self._finish()

    def keep(self, memo: dict, name: str, key, fn):
        """A first dispatch builds a program: ``fn`` (jitted) wrapped so
        that its first call compiles from the call's own arguments, as a
        lazy ``jax.jit`` call would (the same text, the same entry of the
        persistent cache), keeps the compiled program in the memo and
        stores it for the next start."""
        def first(*args):
            prog = fn.lower(*args).compile()
            memo[key] = prog
            # off the dispatching thread (a second to serialise 40 MB);
            # close() waits for it
            writer = threading.Thread(
                target=_store, args=(self._path(name, key), prog, self.root),
                name=f"store-{self.tag}")
            with self._lock:
                self._writers.append(writer)
            writer.start()
            return prog(*args)

        return first

    def close(self) -> None:
        """The engine shuts down: nothing more is loaded, and what its
        first dispatches built is on disk before this returns."""
        self._closed = True
        with self._lock:
            writers, self._writers = self._writers, []
        for w in writers:
            w.join()

    def _finish(self) -> None:
        self.seconds = time.perf_counter() - self.t0
        for _ in self._threads:
            self._tasks.put(None)
        slowest = sorted(self._rows, reverse=True)[:3]
        logger.info(
            "programs prepared: %d from the program store (%d did not load) "
            "on %d threads in %.1f s; slowest: %s",
            self.loaded, self.failed, self.width, self.seconds,
            "; ".join(text for _, text in slowest) or "none")
