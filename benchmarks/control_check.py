"""Builder's check, never part of a benchmark run: a probe compared with a
CONTROL in the reference's place.

    python3 benchmarks/control_check.py <out>/probe.json '{"mixer": false}'
    python3 benchmarks/control_check.py <out>/probe.json '{"reset_at": 512}' along

``probe.json`` is what a run of ``run.py`` left in its ``--out`` directory:
the served probe and the job ``reference_check.py`` was given. This runs that
same comparison, its search and its two limits untouched, with the named
reference's ``forward_for`` handed the ``changes`` of the second argument
(the reference's own ``CHANGES``: each turns the model into what one fault of
the served path would compute). The line it prints is ``reference_check``'s:
a control has to come out ``"ok": false``, by one of the limits. If it reads
``true``, the cell's ``correct`` would pass a program with that fault.

The search stops at the first position no id fits, so a control that fails
says how far it was out at that one position. ``along`` shows the rest: the
sound reference is searched as in a run, and ``reference_check``'s own
``"control"`` pass (at the ids found, a second forward against the reference
itself, every position, the same limits) is handed the changed reference in
the place of its lowered weights. The line's ``control`` then has the
fault's size at all the probe's positions, without the served path's noise.

``reference_check.py``'s ``"control"`` key lowers the weights' precision and
knows two names; a fault of the mathematics is this file's.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys
import tempfile
import types

import named
import reference_check


def main() -> int:
    probe_path, changes = sys.argv[1], json.loads(sys.argv[2])
    along = sys.argv[3:] == ["along"]
    with open(probe_path) as f:
        job = json.load(f)
    plain = named.load("references", job["reference"]).forward_for
    changes = {k: tuple(v) if isinstance(v, list) else v
               for k, v in changes.items()}
    sound: dict = {}

    def forward_for(backend, f32, take):
        if not along:
            return plain(backend, f32, take, changes)
        if f32.__name__ == "to_f32":  # reference_check's lowered weights
            return plain(backend, sound["f32"], take, changes)
        sound["f32"] = f32
        return plain(backend, f32, take)

    named.load = lambda directory, wanted: types.SimpleNamespace(
        forward_for=forward_for)
    if not along:
        sys.argv = [sys.argv[0], probe_path]
        return reference_check.main()
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(dict(job, control="int8"), f)
        f.flush()
        sys.argv = [sys.argv[0], f.name]
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            rc = reference_check.main()
    line = json.loads(said.getvalue().strip().splitlines()[-1])
    line["control"]["precision"] = changes  # the slot's name, not an int8
    for probe in line["detail"]:
        probe.pop("abs_err", None)
    print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
