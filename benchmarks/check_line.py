#!/usr/bin/env python3
"""A result line held strictly to the benchmark's contract.

    python3 benchmarks/check_line.py --workload <name> --trace <0|1> < run.out

Reads a run's standard output, takes its last line and lists everything in
it the contract does not allow: a missing or extra key, a value of the wrong
type, a metric the cell does not have or lacks, a unit that is not
BENCHMARK.json's, a share of a roofline above 105 %. Exit 0 where nothing is
listed. The tests hold every rehearsal to it, and the builder's proof on the
chip every run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TOP = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


def number(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v))


def problems(line: str, bench: dict, workload: str, trace: int,
             on_chip: bool = True) -> list[str]:
    """Every way ``line`` departs from the contract for one run of
    ``workload``. ``on_chip`` false is the CPU rehearsal: no device memory,
    no device trace, so what only a chip gives is not asked for."""
    try:
        r = json.loads(line)
    except ValueError as e:
        return [f"the last line is not JSON: {e}"]
    if not isinstance(r, dict):
        return ["the last line is not a JSON object"]
    out = []
    allowed = TOP | ({"breakdown"} if trace else set())
    if set(r) - allowed or TOP - set(r):
        out.append(f"keys {sorted(r)}: extra {sorted(set(r) - allowed)}, "
                   f"missing {sorted(TOP - set(r))}")
        return out
    if not isinstance(r["correct"], bool):
        out.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(r[key], int) or isinstance(r[key], bool) \
                or r[key] < 0:
            out.append(f"{key} is not a count: {r[key]!r}")
    if r["attempted"] == 0:
        out.append("attempted is 0")
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"]: m["unit"] for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]}
    got = r["metrics"]
    if not isinstance(got, dict):
        return out + ["metrics is not an object"]
    for name, m in got.items():
        if name not in want:
            out.append(f"metric {name} is not a {kind} metric of {workload}")
            continue
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            out.append(f"metric {name} has keys other than value and unit")
            continue
        if not number(m["value"]):
            out.append(f"metric {name} has no finite number: {m['value']!r}")
        elif m["unit"] != want[name]:
            out.append(f"metric {name} has unit {m['unit']!r}, BENCHMARK.json "
                       f"says {want[name]!r}")
        elif ("roofline" in name or "mfu" in name) and m["value"] > 105:
            out.append(f"{name} reads {m['value']} %: above 105 %")
    missing = sorted(set(want) - set(got))
    if missing and (not trace or on_chip):
        out.append(f"{kind} metrics missing: {missing}")
    if trace and not got:
        out.append("a traced run reports no per-layer metric")
    d = r["device"]
    if not isinstance(d, dict):
        return out + ["device is not an object"]
    allowed_d = DEVICE | ({"busy_s", "window_s"} if trace else set())
    if set(d) - allowed_d or DEVICE - set(d):
        out.append(f"device keys {sorted(d)}")
    else:
        if not isinstance(d["platform"], str) or not isinstance(d["kind"], str):
            out.append("device platform and kind are not strings")
        if not isinstance(d["count"], int) or d["count"] < 1:
            out.append(f"device count {d['count']!r}")
        if not isinstance(d["memory_peak_bytes"], int) or (
                on_chip and d["memory_peak_bytes"] <= 0):
            out.append(f"memory_peak_bytes {d['memory_peak_bytes']!r}")
        if on_chip and d["platform"] != "tpu":
            out.append(f"platform {d['platform']!r} is not tpu")
        if trace and on_chip:
            if not (number(d.get("busy_s")) and number(d.get("window_s"))
                    and 0 < d["busy_s"] <= d["window_s"] * 1.001):
                out.append(f"busy_s {d.get('busy_s')!r} and window_s "
                           f"{d.get('window_s')!r} are not 0 < busy <= window")
    if "breakdown" in r:
        b = r["breakdown"]
        if not isinstance(b, dict) or set(b) != {"device_ops", "idle_gaps"}:
            out.append("breakdown has keys other than device_ops, idle_gaps")
        else:
            for key, rows in b.items():
                if not isinstance(rows, list) or len(rows) > 10 or not all(
                        isinstance(x, list) and len(x) == 2
                        and isinstance(x[0], str) and number(x[1])
                        for x in rows):
                    out.append(f"breakdown.{key} is not at most 10 "
                               "[name, seconds] pairs")
    elif trace and on_chip:
        out.append("a traced run on the chip carries no breakdown")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    lines = sys.stdin.read().strip().splitlines()
    found = problems(lines[-1] if lines else "", bench, args.workload,
                     args.trace)
    for p in found:
        print(p)
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
