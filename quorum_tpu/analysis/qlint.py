"""qlint — hot-path static analysis for the serving tree (pure stdlib ast).

Every perf/robustness PR so far hand-fought the same three hazard classes;
qlint makes them machine-checked properties of the tree instead of reviewer
folklore:

**sync** (device-sync taboo) — in the hot-path modules (``engine/``,
``models/transformer.py``, ``ops/``, ``cache/kv_transfer.py``), flag
implicit device→host transfers on the token critical path: ``.item()`` /
``.tolist()`` calls, ``np.asarray``/``np.array``/``np.copy`` over values not
provably host-resident, ``float()``/``int()``/``bool()`` over device-tracked
values, truthiness tests on device arrays, and every ``jax.device_get`` /
``block_until_ready`` site (those are *deliberate* sync points and must say
why). Each blocking d2h read stalls the dispatch pipeline the engine exists
to keep full ("Kernel Looping", PAPERS.md); the tree's budget is one
annotated fetch per dispatch. Suppress with ``# qlint: allow-sync(<reason>)``
on the line (or the line above). The static pass is backed at runtime by the
engine's ``transfer_guard`` knob (``jax.transfer_guard`` around the decode
loop — tests/conftest.py defaults it to ``disallow`` for the whole suite).

**recompile** (recompile budget) — flag jit-boundary hazards that mint
program-cache families per *call* instead of per *shape family*:
``jax.jit(f)(x)`` immediate-invoke (a fresh wrapper each call → a fresh
compile each call), ``jax.jit`` inside a loop body, and non-power-of-two
literals bound to the shape-family knobs (``decode_chunk`` & co. — the
per-dispatch clamps halve, so a non-pow2 value doubles the family count).
Suppress with ``# qlint: allow-recompile(<reason>)``. The program-key
contract itself lives in ``analysis/compile_budget.json`` (consumed by the
cache-key tests) and is backed at runtime by ``analysis/compile_watch.py``
(the ``quorum_tpu_recompiles_total`` counter + the suite's warmed-engine
zero-recompile sentinel).

**guarded** (lock discipline) — a module that declares ``_GUARDED_BY``
(engine/engine.py) promises that every mutation of the listed ``self.``
fields happens lexically inside ``with self._cond:`` (``{"lock": "_cond"}``
entries, plus documented caller-holds-the-lock ``holders``) or inside a
single-owner thread's allowlisted methods (``{"owner": [...]}`` entries).
qlint verifies every mutation site: plain/aug/ann assignment, subscript and
slice stores, ``del``, and mutating method calls (``append``/``pop``/
``clear``/``add``/``update``/…). This is exactly the class of race fixed
four separate times in the PR 3/4/7 reviews. Suppress with
``# qlint: allow-unguarded(<reason>)``.

Findings not fixed in-tree must carry a reasoned suppression; anything else
lands in ``analysis/qlint_baseline.json`` — whose entry count may only
shrink: the file records ``max_count`` and ``--baseline-update`` refuses to
grow it (burn-down is deliberate, regressions fail loudly).

CLI::

    python -m quorum_tpu.analysis.qlint              # lint the package
    python -m quorum_tpu.analysis.qlint --baseline-update
    python -m quorum_tpu.analysis.qlint path.py ...  # explicit files
                                                     # (treated as hot-path)

Exit status: 0 clean (baseline-suppressed findings allowed), 1 on any new
finding, 2 on usage/IO errors. See docs/static_analysis.md.
"""

from __future__ import annotations

import argparse
import ast
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

PKG_DIR = Path(__file__).resolve().parents[1]        # quorum_tpu/
REPO_DIR = PKG_DIR.parent
BASELINE_PATH = Path(__file__).resolve().parent / "qlint_baseline.json"

# Hot-path modules (package-relative): the token critical path. The sync and
# recompile families apply here; guarded applies wherever _GUARDED_BY is
# declared.
HOT_PATHS = (
    "engine/",
    "models/transformer.py",
    "ops/",
    "cache/kv_transfer.py",
)

# Rule family -> suppression tag.
ALLOW_TAGS = {
    "sync": "allow-sync",
    "recompile": "allow-recompile",
    "guarded": "allow-unguarded",
}

_ALLOW_RE = re.compile(r"#\s*qlint:\s*(allow-[a-z-]+)\(([^)]*)\)")

# Container-mutating method names (list/deque/set/dict).
MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "popleft", "appendleft",
    "clear", "add", "discard", "update", "setdefault", "sort", "reverse",
}

# Shape-family knobs whose literal values must be powers of two (the
# per-dispatch clamps halve; a non-pow2 value doubles the program-shape
# family count — see compile_budget.json).
SHAPE_KNOBS = {"decode_chunk", "prefill_chunk", "decode_loop",
               "decode_pipeline"}

# Names whose call RESULT is a host (numpy/python) value.
HOST_FETCHERS = {"_host_fetch", "fetch_to_host", "_fetch_landing"}
HOST_BUILTINS = {"len", "min", "max", "sum", "sorted", "list", "tuple",
                 "dict", "set", "range", "enumerate", "zip", "abs", "round",
                 "str", "repr", "any", "all", "int", "float", "bool", "id",
                 "isinstance", "getattr", "hash"}
NP_MODS = {"np", "numpy"}
DEVICE_MODS = {"jnp", "lax"}          # jax.numpy / jax.lax aliases
DEVICE_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.nn.")
DEVICE_CALLS = {"jax.device_put"}

HOST = "host"
DEVICE = "device"


@dataclass(frozen=True)
class Finding:
    rule: str       # sync | recompile | guarded
    kind: str       # short machine code, e.g. "item-call"
    path: str       # repo-relative
    line: int
    scope: str      # enclosing Class.func qualname ("<module>" at top level)
    message: str
    occurrence: int = 1  # nth identical (rule, path, scope, kind) finding

    @property
    def fingerprint(self) -> str:
        suffix = f"#{self.occurrence}" if self.occurrence > 1 else ""
        return f"{self.rule}:{self.path}:{self.scope}:{self.kind}{suffix}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}/{self.kind}] "
                f"{self.scope}: {self.message}")


def _dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_self_attr(node: ast.AST) -> str | None:
    """'x' for ``self.x``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _root_self_attr(node: ast.AST) -> str | None:
    """'x' when node is self.x possibly wrapped in subscripts/attrs
    (``self.x[i]``, ``self.x[i].y``)."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        name = _is_self_attr(node)
        if name is not None:
            return name
        node = node.value
    return None


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


# --------------------------------------------------------------------------
# host/device value classification (intra-function, heuristic)
# --------------------------------------------------------------------------


class _Classifier:
    """Classifies expressions as HOST (numpy/python, safe to convert),
    DEVICE (jax array / jit output, converting is a sync), or unknown
    (None). Deliberately heuristic: precision comes from the narrow set of
    flagged patterns, not from full type inference."""

    def __init__(self, device_attrs: set[str]):
        self.device_attrs = device_attrs

    def classify(self, node: ast.AST, env: dict[str, str]) -> str | None:
        c = self.classify
        if isinstance(node, (ast.Constant, ast.JoinedStr)):
            return HOST
        if isinstance(node, (ast.List, ast.Tuple, ast.Dict, ast.Set)):
            return HOST
        if isinstance(node, ast.Starred):
            return c(node.value, env)
        if isinstance(node, ast.Name):
            return env.get(node.id)
        if isinstance(node, ast.Attribute):
            if node.attr in ("ndim", "shape", "dtype", "size", "nbytes",
                             "sharding"):
                return HOST  # array metadata lives on host
            name = _is_self_attr(node)
            if name is not None:
                return DEVICE if name in self.device_attrs else None
            dotted = _dotted(node)
            if dotted:
                root = dotted.split(".", 1)[0]
                if root in NP_MODS:
                    return HOST
                if root in DEVICE_MODS:
                    return DEVICE
            return c(node.value, env)
        if isinstance(node, ast.Subscript):
            return c(node.value, env)
        if isinstance(node, ast.Call):
            return self._classify_call(node, env)
        if isinstance(node, (ast.BinOp,)):
            return self._combine(c(node.left, env), c(node.right, env))
        if isinstance(node, ast.UnaryOp):
            return c(node.operand, env)
        if isinstance(node, ast.Compare):
            vals = [c(node.left, env)] + [c(x, env) for x in node.comparators]
            return self._combine(*vals)
        if isinstance(node, ast.BoolOp):
            return self._combine(*[c(v, env) for v in node.values])
        if isinstance(node, ast.IfExp):
            return self._combine(c(node.body, env), c(node.orelse, env))
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            inner = dict(env)
            for gen in node.generators:
                tgt_cls = c(gen.iter, inner)
                for tname in self._target_names(gen.target):
                    if tgt_cls is not None:
                        inner[tname] = tgt_cls
            return c(node.elt, inner)
        return None

    @staticmethod
    def _combine(*classes: str | None) -> str | None:
        if any(x == DEVICE for x in classes):
            return DEVICE
        if classes and all(x == HOST for x in classes):
            return HOST
        return None

    @staticmethod
    def _target_names(target: ast.AST) -> list[str]:
        names: list[str] = []
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                names.append(n.id)
        return names

    def _classify_call(self, node: ast.Call, env: dict[str, str]) -> str | None:
        func = node.func
        # the self._xxx_fn(bucket)(args) pattern: calling a jitted callable
        if isinstance(func, ast.Call):
            return DEVICE
        dotted = _dotted(func)
        if dotted:
            root = dotted.split(".", 1)[0]
            leaf = dotted.rsplit(".", 1)[-1]
            if dotted == "jax.device_get" or leaf in HOST_FETCHERS:
                return HOST
            if root in NP_MODS:
                return HOST
            if dotted in DEVICE_CALLS or root in DEVICE_MODS \
                    or dotted.startswith(DEVICE_PREFIXES):
                return DEVICE
            if dotted in HOST_BUILTINS or root == "time":
                return HOST
        # method call: result follows the receiver (host.sum() -> host,
        # device.astype(...) -> device)
        if isinstance(func, ast.Attribute):
            return self.classify(func.value, env)
        return None


def _collect_device_attrs(tree: ast.AST) -> set[str]:
    """``self.X`` attributes assigned (anywhere in the file) from a
    device-classified expression — jit-call outputs, jax.device_put, jnp
    ops. Two passes so tuple-unpack chains settle."""
    attrs: set[str] = set()
    clf = _Classifier(attrs)
    for _ in range(2):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            cls = clf.classify(node.value, {})
            if cls != DEVICE:
                continue
            for tgt in node.targets:
                elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                for el in elts:
                    if isinstance(el, ast.Starred):
                        el = el.value
                    name = _is_self_attr(el)
                    if name is not None:
                        attrs.add(name)
    return attrs


# --------------------------------------------------------------------------
# per-function walks
# --------------------------------------------------------------------------


def _build_env(fn: ast.AST, clf: _Classifier) -> dict[str, str]:
    """Forward passes over a function body propagating host/device through
    simple assignments, tuple unpacking and for-targets."""
    env: dict[str, str] = {}
    for _ in range(3):
        changed = False

        def note(name: str, cls: str | None) -> None:
            nonlocal changed
            if cls is not None and env.get(name) != cls:
                env[name] = cls
                changed = True

        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                cls = clf.classify(node.value, env)
                for tgt in node.targets:
                    elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                    for el in elts:
                        if isinstance(el, ast.Starred):
                            el = el.value
                        if isinstance(el, ast.Name):
                            note(el.id, cls)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if isinstance(node.target, ast.Name):
                    note(node.target.id, clf.classify(node.value, env))
            elif isinstance(node, ast.For):
                cls = clf.classify(node.iter, env)
                for name in _Classifier._target_names(node.target):
                    note(name, cls)
            elif isinstance(node, ast.withitem) and node.optional_vars:
                for name in _Classifier._target_names(node.optional_vars):
                    note(name, clf.classify(node.context_expr, env))
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp, ast.DictComp)):
                # comprehension targets leak into the walk-order env so the
                # element expression classifies with them bound
                for gen in node.generators:
                    cls = clf.classify(gen.iter, env)
                    for name in _Classifier._target_names(gen.target):
                        note(name, cls)
        if not changed:
            break
    return env


class _FileLinter:
    def __init__(self, path: Path, rel: str, source: str, *, hot: bool):
        self.path = path
        self.rel = rel
        self.hot = hot
        self.tree = ast.parse(source, filename=str(path))
        self.lines = source.splitlines()
        self.suppressions = self._scan_suppressions()
        self.findings: list[Finding] = []
        self.suppressed: list[tuple[Finding, str]] = []
        self.bad_suppressions: list[Finding] = []
        self._counts: dict[tuple, int] = {}

    # -- suppression bookkeeping ------------------------------------------

    def _scan_suppressions(self) -> dict[int, tuple[str, str]]:
        out: dict[int, tuple[str, str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _ALLOW_RE.search(line)
            if m:
                out[i] = (m.group(1), m.group(2).strip())
        return out

    def emit(self, rule: str, kind: str, node: ast.AST, scope: str,
             message: str) -> None:
        line = getattr(node, "lineno", 0)
        key = (rule, self.rel, scope, kind)
        self._counts[key] = self._counts.get(key, 0) + 1
        f = Finding(rule, kind, self.rel, line, scope, message,
                    occurrence=self._counts[key])
        tag = ALLOW_TAGS[rule]
        for ln in (line, line - 1):
            sup = self.suppressions.get(ln)
            if sup and sup[0] == tag:
                if not sup[1]:
                    self.bad_suppressions.append(Finding(
                        rule, "empty-suppression-reason", self.rel, ln,
                        scope, f"{tag}() needs a reason: {message}"))
                else:
                    self.suppressed.append((f, sup[1]))
                return
        self.findings.append(f)

    # -- drive ------------------------------------------------------------

    def run(self) -> None:
        if self.hot:
            self._run_sync_and_recompile()
        self._run_guarded()

    def _functions(self):
        """Yield (scope_name, function_node) for every def in the file."""
        def walk(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    name = f"{prefix}{child.name}"
                    yield name, child
                    yield from walk(child, f"{name}.")
                elif isinstance(child, ast.ClassDef):
                    yield from walk(child, f"{prefix}{child.name}.")
                else:
                    yield from walk(child, prefix)
        yield from walk(self.tree, "")

    # -- sync + recompile --------------------------------------------------

    def _run_sync_and_recompile(self) -> None:
        device_attrs = _collect_device_attrs(self.tree)
        clf = _Classifier(device_attrs)
        seen: set[int] = set()
        for scope, fn in self._functions():
            env = _build_env(fn, clf)
            for node in ast.walk(fn):
                if id(node) in seen:
                    continue
                seen.add(id(node))
                self._check_sync_node(node, scope, env, clf)
                self._check_recompile_node(node, scope, fn)
        # module level (rare, but e.g. warm-up calls)
        env0: dict[str, str] = {}
        for node in ast.walk(self.tree):
            if id(node) in seen or isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            self._check_sync_node(node, "<module>", env0, clf)
            self._check_recompile_node(node, "<module>", self.tree)

    def _check_sync_node(self, node: ast.AST, scope: str,
                         env: dict[str, str], clf: _Classifier) -> None:
        if isinstance(node, ast.Call):
            func = node.func
            dotted = _dotted(func)
            if dotted == "jax.device_get":
                self.emit("sync", "device-get", node, scope,
                          "jax.device_get is a blocking device->host fetch; "
                          "hot-path sync points must be annotated")
                return
            if (dotted == "jax.block_until_ready"
                    or (isinstance(func, ast.Attribute)
                        and func.attr == "block_until_ready")):
                self.emit("sync", "block-until-ready", node, scope,
                          "block_until_ready stalls the dispatch pipeline; "
                          "annotate why this sync is deliberate")
                return
            if isinstance(func, ast.Attribute) and func.attr in (
                    "item", "tolist") and not node.args:
                if clf.classify(func.value, env) != HOST:
                    self.emit("sync", f"{func.attr}-call", node, scope,
                              f".{func.attr}() forces a device->host "
                              "transfer unless the value is already on "
                              "host")
                return
            if dotted and dotted.split(".", 1)[0] in NP_MODS \
                    and dotted.rsplit(".", 1)[-1] in ("asarray", "array",
                                                      "copy") and node.args:
                if clf.classify(node.args[0], env) != HOST:
                    self.emit("sync", "np-asarray", node, scope,
                              f"{dotted}(...) over a possibly device-"
                              "resident value is an implicit device->host "
                              "transfer")
                return
            if isinstance(func, ast.Name) and func.id in (
                    "float", "int", "bool") and len(node.args) == 1:
                if clf.classify(node.args[0], env) == DEVICE:
                    self.emit("sync", "host-scalar-cast", node, scope,
                              f"{func.id}() on a device value blocks on "
                              "the transfer (and the computation feeding "
                              "it)")
                return
        # truthiness on device arrays
        test = None
        if isinstance(node, (ast.If, ast.While)):
            test = node.test
        elif isinstance(node, ast.Assert):
            test = node.test
        if test is not None and clf.classify(test, env) == DEVICE:
            self.emit("sync", "array-truthiness", test, scope,
                      "truth-testing a device array forces a blocking "
                      "device->host read")

    def _check_recompile_node(self, node: ast.AST, scope: str,
                              fn: ast.AST) -> None:
        if not isinstance(node, ast.Call):
            return
        if self._is_jit_call(node.func):
            # jax.jit(f)(x): a fresh wrapper (and compile) every evaluation
            self.emit("recompile", "jit-immediate-call", node, scope,
                      "jax.jit(...)(...) builds a fresh jitted wrapper per "
                      "call — each evaluation recompiles; cache the wrapper")
            return
        if self._is_jit_call(node):
            for parent in self._loop_ancestors(fn, node):
                self.emit("recompile", "jit-in-loop", node, scope,
                          "jax.jit inside a loop mints a program per "
                          "iteration; hoist and cache the wrapper")
                break
        for kw in node.keywords:
            if kw.arg in SHAPE_KNOBS and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int) \
                    and kw.value.value > 1 and not _is_pow2(kw.value.value):
                self.emit("recompile", "non-pow2-shape-knob", kw.value, scope,
                          f"{kw.arg}={kw.value.value} is not a power of "
                          "two: the per-dispatch clamps halve, so this "
                          "doubles the program-shape family count")

    @staticmethod
    def _is_jit_call(node: ast.AST) -> bool:
        """True for ``jax.jit(...)`` and ``functools.partial(jax.jit, ...)``
        call nodes."""
        if not isinstance(node, ast.Call):
            return False
        dotted = _dotted(node.func)
        if dotted in ("jax.jit", "jit"):
            return True
        if dotted in ("functools.partial", "partial") and node.args:
            return _dotted(node.args[0]) in ("jax.jit", "jit")
        return False

    @staticmethod
    def _loop_ancestors(fn: ast.AST, target: ast.AST):
        """Yield loop nodes lexically enclosing ``target`` within ``fn``."""
        path: list[ast.AST] = []
        found: list[list[ast.AST]] = []

        def visit(node):
            path.append(node)
            if node is target:
                found.append([p for p in path
                              if isinstance(p, (ast.For, ast.While))])
            for child in ast.iter_child_nodes(node):
                visit(child)
            path.pop()

        visit(fn)
        return found[0] if found else []

    # -- guarded-by --------------------------------------------------------

    def _run_guarded(self) -> None:
        spec = self._load_guarded_map()
        if not spec:
            return
        for scope, fn in self._functions():
            method = scope.rsplit(".", 1)[-1]
            if method == "__init__":
                continue  # construction precedes publication
            self._check_guarded_fn(fn, scope, method, spec)

    def _load_guarded_map(self) -> dict:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == "_GUARDED_BY":
                        try:
                            return ast.literal_eval(node.value)
                        except ValueError:
                            self.findings.append(Finding(
                                "guarded", "bad-guarded-map", self.rel,
                                node.lineno, "<module>",
                                "_GUARDED_BY must be a literal dict"))
                            return {}
            if isinstance(node, ast.AnnAssign) and node.value is not None:
                if isinstance(node.target, ast.Name) \
                        and node.target.id == "_GUARDED_BY":
                    try:
                        return ast.literal_eval(node.value)
                    except ValueError:
                        return {}
        return {}

    def _check_guarded_fn(self, fn: ast.AST, scope: str, method: str,
                          spec: dict) -> None:
        """Walk one function tracking the lexical with-lock stack."""
        linter = self

        def mutation_ok(field: str, under_lock: bool) -> bool:
            rule = spec[field]
            lock = rule.get("lock")
            if lock and under_lock:
                return True
            if method in rule.get("holders", ()):  # caller holds the lock
                return True
            if method in rule.get("owner", ()):
                return True
            return False

        def check_target(node: ast.AST, tgt: ast.AST,
                         under_lock: bool, verb: str) -> None:
            elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
            for el in elts:
                if isinstance(el, ast.Starred):
                    el = el.value
                field = _root_self_attr(el)
                if field is not None and field in spec:
                    if not mutation_ok(field, under_lock):
                        linter.emit(
                            "guarded", f"unguarded-{verb}-{field}", node,
                            scope,
                            f"self.{field} {verb} outside `with "
                            f"self._cond:` (guarded-by contract: "
                            f"{spec[field]})")

        def is_lock_ctx(item: ast.withitem) -> bool:
            name = _is_self_attr(item.context_expr)
            return name is not None and any(
                r.get("lock") == name for r in spec.values())

        def visit(node: ast.AST, under_lock: bool) -> None:
            if isinstance(node, ast.With):
                entered = under_lock or any(
                    is_lock_ctx(i) for i in node.items)
                for child in node.body:
                    visit(child, entered)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                return  # nested defs get their own scope walk
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in targets:
                    check_target(node, tgt, under_lock, "write")
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    check_target(node, tgt, under_lock, "del")
            elif isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute) and func.attr in MUTATORS:
                    field = _root_self_attr(func.value)
                    if field is not None and field in spec:
                        if not mutation_ok(field, under_lock):
                            linter.emit(
                                "guarded",
                                f"unguarded-{func.attr}-{field}", node,
                                scope,
                                f"self.{field}.{func.attr}(...) outside "
                                f"`with self._cond:` (guarded-by contract: "
                                f"{spec[field]})")
            for child in ast.iter_child_nodes(node):
                visit(child, under_lock)

        for stmt in fn.body:
            visit(stmt, False)


# --------------------------------------------------------------------------
# baseline + CLI
# --------------------------------------------------------------------------


def load_baseline(path: Path = BASELINE_PATH) -> dict:
    if not path.exists():
        return {"max_count": 0, "findings": []}
    with open(path) as f:
        data = json.load(f)
    data.setdefault("max_count", len(data.get("findings", [])))
    data.setdefault("findings", [])
    return data


def _iter_package_files() -> list[Path]:
    return sorted(p for p in PKG_DIR.rglob("*.py")
                  if "__pycache__" not in p.parts)


def _is_hot(rel_to_pkg: str) -> bool:
    return any(rel_to_pkg == h or (h.endswith("/") and rel_to_pkg.startswith(h))
               for h in HOT_PATHS)


def run_qlint(paths: list[Path] | None = None, *,
              baseline: dict | None = None):
    """Lint ``paths`` (package files when None). Returns
    ``(new_findings, suppressed, stale_fingerprints, all_findings)`` where
    *new* excludes baseline-listed fingerprints and *suppressed* carries
    (finding, reason) for annotation-silenced sites. Explicit ``paths`` are
    treated as hot-path files (fixture mode) and skip the baseline."""
    fixture_mode = paths is not None
    files: list[tuple[Path, bool]] = []
    if fixture_mode:
        files = [(Path(p), True) for p in paths]
    else:
        for p in _iter_package_files():
            rel = p.relative_to(PKG_DIR).as_posix()
            files.append((p, _is_hot(rel)))

    findings: list[Finding] = []
    suppressed: list[tuple[Finding, str]] = []
    for path, hot in files:
        try:
            source = path.read_text()
        except OSError as e:
            raise SystemExit(f"qlint: cannot read {path}: {e}")
        rel = (path.relative_to(REPO_DIR).as_posix()
               if not fixture_mode and path.is_relative_to(REPO_DIR)
               else path.name)
        lint = _FileLinter(path, rel, source, hot=hot)
        lint.run()
        findings.extend(lint.findings + lint.bad_suppressions)
        suppressed.extend(lint.suppressed)

    if fixture_mode:
        return findings, suppressed, [], findings

    base = baseline if baseline is not None else load_baseline()
    known = set(base.get("findings", []))
    new = [f for f in findings if f.fingerprint not in known]
    present = {f.fingerprint for f in findings}
    stale = sorted(known - present)
    return new, suppressed, stale, findings


def update_baseline(findings: list[Finding],
                    path: Path = BASELINE_PATH) -> dict:
    """Regenerate the baseline; the entry count may only shrink."""
    old = load_baseline(path)
    fingerprints = sorted({f.fingerprint for f in findings})
    if path.exists() and len(fingerprints) > old["max_count"]:
        raise SystemExit(
            f"qlint: refusing to grow the baseline "
            f"({len(fingerprints)} findings > max_count="
            f"{old['max_count']}); fix or annotate the new findings")
    data = {
        "comment": ("qlint suppression baseline — burn-down only: "
                    "max_count never grows (see docs/static_analysis.md)"),
        "max_count": (len(fingerprints) if old["max_count"] == 0
                      else min(old["max_count"], len(fingerprints))
                      or len(fingerprints)),
        "findings": fingerprints,
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return data


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="qlint", description=__doc__.split("\n", 1)[0])
    ap.add_argument("paths", nargs="*",
                    help="explicit files (fixture mode: all treated as "
                         "hot-path, baseline skipped)")
    ap.add_argument("--baseline-update", action="store_true",
                    help="regenerate the suppression baseline "
                         "(shrink-only)")
    ap.add_argument("--verbose", action="store_true",
                    help="also list annotation-suppressed findings")
    args = ap.parse_args(argv)

    paths = [Path(p) for p in args.paths] or None
    new, suppressed, stale, all_findings = run_qlint(paths)

    if args.baseline_update:
        if paths is not None:
            print("qlint: --baseline-update ignores explicit paths",
                  file=sys.stderr)
            return 2
        data = update_baseline(all_findings)
        print(f"qlint: baseline updated — {len(data['findings'])} "
              f"entr{'y' if len(data['findings']) == 1 else 'ies'} "
              f"(max_count={data['max_count']})")
        return 0

    base = load_baseline() if paths is None else {"findings": []}
    n_base = len([f for f in all_findings
                  if f.fingerprint in set(base["findings"])])
    if args.verbose and suppressed:
        print("annotation-suppressed findings:")
        for f, reason in suppressed:
            print(f"  {f.render()}  [{reason}]")
    if stale:
        print(f"qlint: {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} (fixed findings) — "
              "run --baseline-update to burn them down:")
        for fp in stale:
            print(f"  {fp}")
    if new:
        print(f"qlint: {len(new)} new finding{'s' if len(new) != 1 else ''}:")
        for f in sorted(new, key=lambda f: (f.path, f.line)):
            print(f"  {f.render()}")
        print("\nfix the code, annotate with "
              "# qlint: allow-sync|allow-recompile|allow-unguarded"
              "(<reason>), or (deliberately) --baseline-update.")
        return 1
    print(f"qlint: clean — {len(suppressed)} annotated suppression"
          f"{'s' if len(suppressed) != 1 else ''}, {n_base} baseline-"
          f"suppressed, {len(stale)} stale")
    return 0


if __name__ == "__main__":
    sys.exit(main())
