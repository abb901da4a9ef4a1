"""From a profiler trace (``.xplane.pb``) to device numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` (nothing but jax is
needed; importing it touches no device) into plain lists; ``reduce`` is pure
Python over those lists, so it is checked on a small recorded trace
(``fixtures/``) and every PR computes the same numbers the same way.

What a TPU trace holds (seen in PR 23's and PR 24's chip runs): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per executed
program (``jit_chunk(…)``, ``jit_admit(…)``, ``jit_seg(…)``,
``jit_register(…)``) and whose line ``XLA Ops`` has one event per HLO
operation, nested where an operation (a ``while`` loop) contains others.

Programs are classed by the name the engine's jitted functions carry today:
``chunk`` is the decode program, ``admit`` and ``seg`` are prefill; anything
else is ``other``. PERF.md asks the tracing PR for stable names.

How many tokens a prefill execution computed is read off the trace itself,
not off the client's clocks: a TPU trace names an operation by its HLO text
(``%fusion.264 = f32[3,8,14336]{...} fusion(...), kind=kOutput, ...``), and
the feed-forward products are the matrix products whose result's last
dimension is the configuration's ``intermediate_size``. The other dimensions
multiplied are the rows the program pushed through the model: members x
padded prompt tokens for ``admit`` and ``seg``, members x slot rows for
``chunk``. So tokens and device time come from the same events of the same
profile (``rows_of``, ``reduce``'s ``prefill_executions``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
CUT_PLANE = "/host:cut"  # written by cut(): the interval a fixture was cut to
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")
# "%name = type[dims]{layout} opcode(" -- a tuple result does not match
OP_TEXT = re.compile(r"^%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
MATRIX_PRODUCTS = ("convolution", "dot")


def find_xplane(profile_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def rows_of(text: str, ffn: int) -> int | None:
    """The rows of a feed-forward product, from an operation's HLO text: the
    result's dimensions but the last multiplied, where the operation is a
    matrix product (a ``convolution`` or ``dot``, or a fusion around one,
    ``kind=kOutput``) and the last dimension is ``ffn`` (or twice it, where
    gate and up are one product). None for every other operation."""
    m = OP_TEXT.match(text)
    if not m:
        return None
    dims = [int(d) for d in m.group(1).split(",") if d]
    if len(dims) < 2 or dims[-1] not in (ffn, 2 * ffn):
        return None
    if m.group(2) not in MATRIX_PRODUCTS and not (
            m.group(2) == "fusion" and "kind=kOutput" in text):
        return None
    rows = 1
    for d in dims[:-1]:
        rows *= d
    return rows


def load(path: str, ffn: int = 0, keep_text: bool = False) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}], "ffn_rows": [[start_ns, rows], ...]}]} for the
    device planes, plus the span of their events as ``t_min``/``t_max``: the
    traced window. (The host planes also hold the profiler's own start and
    stop, seconds in which no device event is recorded at all; counting those
    would read as idle time.) ``ffn_rows`` lists the feed-forward products
    (``rows_of``) where ``ffn``, the model's intermediate size, is given.
    ``keep_text`` (for ``cut``) leaves such a product's whole HLO text on its
    event as a fourth entry."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, t_min, t_max = [], None, None
    rows_by_text: dict = {}
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != CUT_PLANE:
            continue
        lines, ffn_rows = [], []
        for line in plane.lines:
            events = []
            for e in line.events:
                start, dur = float(e.start_ns), float(e.duration_ns)
                t_min = start if t_min is None else min(t_min, start)
                t_max = start + dur if t_max is None else max(t_max,
                                                              start + dur)
                if not is_device:
                    continue
                text = e.name
                events.append([short_name(text), start, dur])
                if ffn and line.name == OPS_LINE:
                    if text not in rows_by_text:
                        rows_by_text[text] = rows_of(text, ffn)
                    if rows_by_text[text]:
                        ffn_rows.append([start, rows_by_text[text]])
                        if keep_text:
                            events[-1].append(text)
            if is_device:
                lines.append({"name": line.name, "events": events})
        if is_device:
            planes.append({"name": plane.name, "lines": lines,
                           "ffn_rows": ffn_rows})
    return {"planes": planes, "t_min": t_min or 0.0, "t_max": t_max or 0.0}


def short_name(name: str) -> str:
    """An event's name without the HLO text the TPU trace appends to it:
    ``%fusion.264 = f32[3,8,14336]{...} fusion(...)`` -> ``fusion.264``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def program_class(name: str) -> str:
    n = name.lower()
    if "chunk" in n or "decode" in n:
        return "decode"
    if "admit" in n or "seg" in n or "prefill" in n:
        return "prefill"
    if "register" in n:
        return "register"
    return "other"


def union_ns(intervals: list) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events: list) -> dict:
    """Per operation name, the time its events ran minus the time of the
    events nested inside them (a ``while`` holds its body's operations)."""
    out: dict = {}
    stack: list = []  # [name, end, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def subtract_ns(intervals: list, cover: list) -> float:
    """Length of the part of ``intervals``' union that no interval of
    ``cover`` overlaps."""
    both = union_ns(list(intervals) + list(cover))
    return both - union_ns(cover)


def reduce(trace: dict) -> dict | None:
    """Device numbers of a loaded trace, averaged over its device planes.
    None where no operation ran on any device."""
    per_plane = []
    window_ns = max(0.0, trace["t_max"] - trace["t_min"])
    for plane in trace["planes"]:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE, [])
        modules = sorted(lines.get(MODULE_LINE, []), key=lambda e: e[1])
        if not ops and not modules:
            continue
        busy_src = ops or modules
        busy = union_ns([(s, s + d) for _, s, d in busy_src])
        by_class: dict = {}
        for name, _, dur in modules:
            c = by_class.setdefault(program_class(name), [0, 0.0])
            c[0] += 1
            c[1] += dur
        gaps: dict = {}
        for (n0, s0, d0), (n1, s1, _) in zip(modules, modules[1:]):
            gap = s1 - (s0 + d0)
            if gap > 0:
                key = f"{program_class(n0)}_to_{program_class(n1)}"
                gaps[key] = gaps.get(key, 0.0) + gap
        # per prefill execution: the widest feed-forward product inside it
        marks = sorted(plane.get("ffn_rows") or [])
        starts = [m[0] for m in marks]
        executions: dict = {}
        for name, s0, d0 in modules:
            if program_class(name) != "prefill":
                continue
            inside = marks[bisect.bisect_left(starts, s0):
                           bisect.bisect_right(starts, s0 + d0)]
            e = executions.setdefault(
                max((r for _, r in inside), default=0), [0, 0.0])
            e[0] += 1
            e[1] += d0
        coll = [(s, s + d) for n, s, d in ops if COLLECTIVE.search(n)]
        compute = [(s, s + d) for n, s, d in ops
                   if not COLLECTIVE.search(n)
                   and not n.lstrip("%").startswith(("while", "conditional",
                                                     "call"))]
        per_plane.append({
            "busy_ns": busy, "classes": by_class, "gaps": gaps,
            "executions": executions,
            "ops": self_times(ops),
            "collective_ns": union_ns(coll),
            "collective_exposed_ns": subtract_ns(coll, compute)})
    if not per_plane or window_ns <= 0:
        return None
    n = len(per_plane)

    def mean_class(cls: str, i: int) -> float:
        return sum(p["classes"].get(cls, [0, 0.0])[i] for p in per_plane) / n

    ops: dict = {}
    gaps: dict = {}
    for p in per_plane:
        for k, v in p["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / n
        for k, v in p["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / n
    executions: dict = {}
    for p in per_plane:
        for rows, (count, ns) in p["executions"].items():
            e = executions.setdefault(rows, [0.0, 0.0])
            e[0] += count / n
            e[1] += ns / n / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(p["busy_ns"] for p in per_plane) / n / 1e9,
        "programs": {cls: {"count": mean_class(cls, 0),
                           "seconds": mean_class(cls, 1) / 1e9}
                     for cls in ("decode", "prefill", "register", "other")},
        # [[rows, executions, seconds], ...]: rows 0 where a prefill program
        # held no feed-forward product the trace could name
        "prefill_executions": [[rows, c, sec] for rows, (c, sec)
                               in sorted(executions.items())],
        "collective_s": sum(p["collective_ns"] for p in per_plane) / n / 1e9,
        "collective_exposed_s": sum(p["collective_exposed_ns"]
                                    for p in per_plane) / n / 1e9,
        "device_ops": [[k.replace("%", "_"), v / 1e9] for k, v in top],
        "idle_gaps": [[k, v / 1e9] for k, v in top_gaps],
    }


def cut(trace: dict, start_s: float, seconds: float) -> bytes:
    """A serialized XSpace holding only the device events that lie wholly
    inside ``[start_s, start_s + seconds)`` of the trace, and one host event
    spanning that interval: how ``fixtures/`` got its small recorded trace
    from a chip run's large one."""
    from jax.profiler import ProfileData

    lo = trace["t_min"] + start_s * 1e9
    hi = lo + seconds * 1e9
    out = []
    for pi, plane in enumerate(trace["planes"]):
        meta: dict = {}
        lines = []
        for li, line in enumerate(plane["lines"]):
            events = []
            for name, start, dur, *text in line["events"]:
                if start < lo or start + dur > hi:
                    continue
                name = (text[0] if text else name).replace(
                    "\\", "\\\\").replace('"', '\\"')
                mid = meta.setdefault(name, len(meta) + 1)
                events.append(
                    f"events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((start - lo) * 1000))} duration_ps: "
                    f"{int(round(dur * 1000))} }}")
            lines.append(f'lines {{ id: {li + 1} name: "{line["name"]}" '
                         f"timestamp_ns: 0 {' '.join(events)} }}")
        metas = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"{name}" }} }}' for name, i in meta.items())
        out.append(f'planes {{ id: {pi + 1} name: "{plane["name"]}" '
                   f"{' '.join(lines)} {metas} }}")
    out.append(
        f'planes {{ id: 99 name: "{CUT_PLANE}" lines {{ id: 1 name: "cut" '
        f"timestamp_ns: 0 events {{ metadata_id: 1 offset_ps: 0 "
        f"duration_ps: {int(round((hi - lo) * 1000))} }} }} "
        'event_metadata { key: 1 value { id: 1 name: "cut" } } }')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def main() -> int:
    """``python trace_reduce.py <profile dir or .xplane.pb> [--ffn <n>]
    [--dump]``: the reduced numbers as one JSON line; ``--ffn`` is the
    model's intermediate size (``rows_of``); ``--dump`` first lists planes,
    lines and a few event names (how the structure above was read off a chip
    run). ``--cut <start_s> <seconds> <out.pb>`` writes a small recorded
    trace."""
    import json
    import sys

    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    if not path:
        print(json.dumps(None))
        return 0
    ffn = int(sys.argv[sys.argv.index("--ffn") + 1]) \
        if "--ffn" in sys.argv else 0
    trace = load(path, ffn)
    if "--dump" in sys.argv:
        for plane in trace["planes"]:
            for line in plane["lines"]:
                names = [e[0] for e in line["events"][:6]]
                print(f"# {plane['name']} | {line['name']} | "
                      f"{len(line['events'])} events | {names}",
                      file=sys.stderr)
    if "--cut" in sys.argv:
        i = sys.argv.index("--cut")
        with open(sys.argv[i + 3], "wb") as f:
            f.write(cut(load(path, ffn, keep_text=True),
                        float(sys.argv[i + 1]), float(sys.argv[i + 2])))
    print(json.dumps(reduce(trace)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
