"""Everything ``--trace 1`` adds to a run, each part under a guard and a limit
of its own: the profile call, the span fetches, the reduction of the profile
in a child, and the per-layer readers.

The rule: a traced run cannot fail where an untraced run of the same cell
would pass. So nothing here raises into ``run.py`` and nothing here waits
without a limit. A part that cannot be read leaves its metrics out of the
result line and says which and why on stderr (``traced run: <part> left
out: <why>``); a number that could not be read is never made up.

The profile is posted so that it ends with the window. The profiler's stop
holds the server up for tens of seconds (PERF.md), which then falls into the
cool-down; counters, spans and client clocks are read over
``[0, read_until_s)``, the part of the window before the profile starts.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

# The profile's length is what its stop costs: after 4 s the call returned
# 48 s (quorum), 68 s (longprompt) and 109 s (chat) after the window's end
# (PR 24's chip runs; PERF.md), too close to what a run may take. 2 s still
# holds some ten decode chunks and as many admissions.
TRACE_SECONDS = 2.0
TRACE_LEAD_S = 0.5            # read_until_s lies this long before the profile
POST_AFTER_READ_S = 0.1       # so the last scrape is in before the call
PROFILE_RETURN_LIMIT_S = 150.0  # after the window's end
SPAN_FETCH_TIMEOUT_S = 5.0
SPAN_FETCH_BUDGET_S = 30.0
REDUCE_LIMIT_S = 60.0         # seen: 6 to 10 s after 4 s of trace


def left_out(part: str, why) -> None:
    print(f"traced run: {part} left out: {why}", file=sys.stderr, flush=True)


def load_reader(name: str):
    """A per-layer metric's reader, found by the metric's name:
    ``layer_metrics/<name>.py`` with a ``read(artifacts)``. A name split by
    cell (``queue_wait_ms.serve``) is a file of its own like any other."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def queue_wait_ms(m0: dict, m1: dict):
    """The queue-wait reader between any two scrapes (``run.py`` prints it by
    thirds of the window, where a growing backlog shows)."""
    try:
        return load_reader("queue_wait_ms").read({"m0": m0, "m1": m1})
    except Exception:
        return None


def strip_first_token(rec: dict) -> None:
    """Rehearsal fault: the record of a stream that ended with ``stop`` and
    no content (its first sampled token was the end-of-sequence id)."""
    rec["first"] = None
    for s in rec["streams"].values():
        s.update(first=None, last=None, deltas=0, chars=0, tokens=0,
                 finish="stop")


class Tracing:
    def __init__(self, server, window_s: float, out_dir: str, faults=(),
                 ffn: int = 0, keep_profile: bool = False):
        self.server, self.out_dir, self.faults = server, out_dir, set(faults)
        self.keep_profile = keep_profile  # builder's: to cut a fixture from
        self.ffn = ffn  # the model's intermediate size: trace_reduce.rows_of
        self.trace_len = min(TRACE_SECONDS, window_s / 2.0)
        self.read_until_s = window_s - self.trace_len - TRACE_LEAD_S
        self.profile: dict = {}
        self.spans: dict = {}
        self.trace = None
        self._thread = None
        self._reducer = None
        self._reduce_started = 0.0

    # -- the profile call ---------------------------------------------------

    def start_profile(self, t0: float) -> None:
        seconds = "x" if "profile" in self.faults else self.trace_len

        def take() -> None:
            time.sleep(max(0.0, t0 + self.read_until_s + POST_AFTER_READ_S
                           - time.monotonic()))
            self.profile["posted_at_s"] = time.monotonic() - t0
            try:
                status, text = self.server.call(
                    "POST", f"/debug/profile?seconds={seconds}",
                    timeout=self.trace_len + TRACE_LEAD_S
                    + PROFILE_RETURN_LIMIT_S)
                self.profile.update(status=status, body=text[:300])
                if status == 200:
                    self.profile["dir"] = json.loads(text)["profile_dir"]
            except Exception as e:
                self.profile.update(status=0, body=repr(e)[:300])
            self.profile["returned_at_s"] = time.monotonic() - t0

        self._thread = threading.Thread(target=take, daemon=True)
        self._thread.start()

    def wait_for_profile(self, t_window_end: float) -> None:
        """Until the call returns, or its limit after the window's end."""
        self._thread.join(timeout=max(
            0.0, t_window_end + PROFILE_RETURN_LIMIT_S - time.monotonic()))
        if self._thread.is_alive():
            left_out("device trace", "the profile call had not returned "
                     f"{PROFILE_RETURN_LIMIT_S:.0f} s after the window")
        elif self.profile.get("status") != 200:
            left_out("device trace", f"/debug/profile answered "
                     f"{self.profile.get('status')}: "
                     f"{self.profile.get('body')}")

    # -- the program's spans ------------------------------------------------

    def fetch_spans(self, window: list) -> None:
        deadline = time.monotonic() + SPAN_FETCH_BUDGET_S
        wanted = [r for r in window
                  if r.get("rid") and r["due"] < self.read_until_s]
        missing = 0
        for i, r in enumerate(wanted):
            if time.monotonic() > deadline:
                left_out("spans", f"{len(wanted) - i} of {len(wanted)} not "
                         f"fetched in {SPAN_FETCH_BUDGET_S:.0f} s")
                break
            rid = r["rid"] + ("-gone" if "span" in self.faults and i == 0
                              else "")
            try:
                status, text = self.server.call(
                    "GET", f"/debug/traces/{rid}",
                    timeout=SPAN_FETCH_TIMEOUT_S)
                if status == 200:
                    self.spans[r["rid"]] = json.loads(text)
                else:
                    missing += 1
            except Exception:
                missing += 1
        if missing:
            left_out("spans", f"{missing} of {len(wanted)} requests have no "
                     "trace on /debug/traces")

    # -- from the profile to device numbers, in a child on the CPU ------------

    def reduce_profile(self, child_cls, env: dict) -> None:
        """Start the reduction; ``report`` collects it. The profile directory
        the server named is relative to its working directory, out_dir."""
        if self.profile.get("status") != 200 or not self.profile.get("dir"):
            return
        try:
            profile_dir = os.path.join(self.out_dir, self.profile["dir"])
            if "reduce" in self.faults:
                with open(trace_reduce.find_xplane(profile_dir), "wb") as f:
                    f.write(b"not an xplane")
            self._reducer = child_cls(
                [sys.executable, os.path.join(HERE, "trace_reduce.py"),
                 profile_dir, "--ffn", str(self.ffn)],
                dict(env, JAX_PLATFORMS="cpu"))
            self._reduce_started = time.monotonic()
        except Exception as e:
            left_out("device trace", f"the reduction did not start: {e!r}")

    def _collect_trace(self) -> None:
        if self._reducer is None:
            return
        rc, out, err = self._reducer.result(max(
            1.0, self._reduce_started + REDUCE_LIMIT_S - time.monotonic()))
        # profiles are large: deleted once reduced, so the tree stays small
        if not self.keep_profile:
            shutil.rmtree(os.path.join(self.out_dir, "profiles"),
                          ignore_errors=True)
        if rc != 0:
            left_out("device trace", f"the reduction exited {rc}: "
                     f"{err.strip().splitlines()[-1:] or ''}")
            return
        try:
            self.trace = json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError) as e:
            left_out("device trace", f"the reduction printed no JSON: {e!r}")
            return
        if self.trace is None:
            left_out("device trace", "the profile holds no operation on a "
                     "TPU device plane")

    # -- the result line's traced part ----------------------------------------

    def report(self, art: dict, per_layer: list, result: dict) -> dict:
        """Fill ``result`` with what could be read: per-layer metrics, the
        device's busy time, the breakdown. Returns what the parts gave, for
        an earlier line."""
        self._collect_trace()
        art.update(spans=self.spans, trace=self.trace)
        for m in per_layer:
            try:
                value = load_reader(m["name"]).read(art)
            except Exception as e:
                left_out(m["name"], repr(e))
                continue
            if value is None or not math.isfinite(value):
                left_out(m["name"], "its reader found nothing to read")
                continue
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if self.trace:
            result["device"]["busy_s"] = self.trace["busy_s"]
            result["device"]["window_s"] = self.trace["window_s"]
            result["breakdown"] = {"device_ops": self.trace["device_ops"],
                                   "idle_gaps": self.trace["idle_gaps"]}
        return {"profile": self.profile, "spans": len(self.spans),
                "trace": self.trace is not None,
                "programs": (self.trace or {}).get("programs"),
                "prefill_executions": (self.trace or {}).get(
                    "prefill_executions")}
