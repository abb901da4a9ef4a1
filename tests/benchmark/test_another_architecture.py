"""A configuration of another architecture is an addition of files.

Over a temporary copy of the benchmark, the files under
``another_architecture/`` (an expert model with a dense first layer, of whose
experts and vocabulary one chip holds a share) and two entries in
BENCHMARK.json are added; nothing that is there is edited. Every lookup then
finds the new files by the names the configuration file gives: the reference,
the cost model, the prefill-rows dimension, the published values. And the
accepted configurations name none of them, so what is measured is the code
that was there."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import BENCH, REPO, child_env, load, rehearse  # noqa: E402

import published_widths  # noqa: E402

ADDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "another_architecture")
ENTRIES = load(os.path.join(ADDED, "entries.json"))
CELL = ENTRIES["workload"]["name"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark with the configuration and its cell added: (root of the
    copy, its BENCHMARK.json, the files that were there with their bytes)."""
    root = tmp_path_factory.mktemp("another")
    shutil.copytree(os.path.join(REPO, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    os.symlink(os.path.join(REPO, "quorum_tpu"), root / "quorum_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    for name, where in ENTRIES["files"].items():
        os.makedirs((root / where).parent, exist_ok=True)
        shutil.copy(os.path.join(ADDED, name), root / where)
    bench = json.loads(json.dumps(BENCH))
    if ENTRIES["config"] not in bench["configs"]:  # a tree that has them
        bench["configs"].append(ENTRIES["config"])
        bench["workloads"].append(ENTRIES["workload"])
    for m in bench["end_to_end"]:  # an open loop, as mistral-7b.chat
        cells = m.get("workloads", [])
        if "mistral-7b.chat" in cells and CELL not in cells:
            cells.append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench, before


def in_copy(root, script: str) -> dict:
    """A script's last line of JSON, run with the copy's benchmarks/ first
    on the path (the modules find their files beside themselves)."""
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, "
         f"{str(root / 'benchmarks')!r})\n" + script],
        capture_output=True, text=True, timeout=120, cwd=root,
        env=child_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_run_finds_reference_and_rows_dimension_by_name(copy):
    """The whole command in rehearsal, traced: the names reach the probe's
    job, the reference's child and ``Tracing``, and the run is correct."""
    root, bench, before = copy
    run = rehearse(CELL, 1, str(root / "out"), repo=str(root), bench=bench)
    said = run["steps"]["configuration"]
    assert (said["reference"], said["cost_model"]) == ("later_moe",) * 2
    assert run["steps"]["traced parts"]["prefill_rows_dim"] == 1408
    assert load(root / "out" / "probe.json")["reference"] == "later_moe"
    assert run["result"]["correct"] is True
    ref = run["steps"]["reference compared"]
    assert ref["ok"] is True and ref["compared"] >= 6
    assert "decode_step_roofline" not in run["result"]["metrics"]  # a CPU
    assert all(p.read_bytes() == data for p, data in before.items())


def test_the_roofline_readers_use_the_named_cost_model(copy):
    root, _, _ = copy
    got = in_copy(root, '''
import json, cost_model, traced
cfg = json.load(open("benchmarks/configs/later-moe.json"))
model = cost_model.for_config(cfg)
peaks = json.load(open("benchmarks/peaks.json"))["TPU v5 lite"]
traffic = json.load(open("benchmarks/traffic/chat.json"))
art = {"config": cfg, "peaks": peaks, "traffic": traffic,
       "m0": {"quorum_tpu_engine_decode_chunks_total": 10,
              "quorum_tpu_engine_decode_busy_rows_total": 50},
       "m1": {"quorum_tpu_engine_decode_chunks_total": 30,
              "quorum_tpu_engine_decode_busy_rows_total": 250},
       "trace": {"window_s": 4.0, "busy_s": 3.9, "programs": {
           "decode": {"count": 20.0, "seconds": 3.2},
           "prefill": {"count": 5.0, "seconds": 0.5}},
           "prefill_executions": [[512, 5.0, 0.5]]}}
grid = traffic["grid"]
context = sum(p + c / 2.0 for p, c in grid) / len(grid)
prompt = sum(p for p, _ in grid) / len(grid)
want = {}
for name, m in (("named", model), ("default", cost_model)):
    cfg_m = cfg if m is model else dict(cfg, serve={"backends": [1]})
    d = m.least_seconds(*m.decode_step(cfg_m, 10.0, context), cfg_m, peaks)
    p = m.least_seconds(*m.prefill(cfg_m, 512, prompt, 1), cfg_m, peaks)
    want[name] = [100.0 * d * 1000.0 / 20.0, 100.0 * 5 * p / 0.5]
print(json.dumps({
    "module": model.__name__, "file": model.__file__,
    "default": cost_model.for_config({}).__name__,
    "read": [traced.load_reader(n).read(art)
             for n in ("decode_step_roofline", "prefill_roofline")],
    "want": want}))
''')
    assert got["file"] == str(root / "benchmarks" / "cost_models"
                              / "later_moe.py")
    assert got["default"] == "cost_model" != got["module"]
    assert got["read"] == pytest.approx(got["want"]["named"])
    assert got["read"][0] != pytest.approx(got["want"]["default"][0], rel=0.05)
    assert all(0 < v < 100 for v in got["read"])


def _added(copy):
    root, _, _ = copy
    return (ENTRIES["config"], load(root / ENTRIES["config"]["file"]),
            str(root / "benchmarks" / "configs" / "published"))


def test_the_added_configuration_keeps_its_published_widths(copy, tmp_path):
    entry, data, published = _added(copy)
    assert {"num_experts", "vocab_size"} <= set(entry["reduced"])
    assert published_widths.problems(entry, data, published) == []
    # a source no file has: nothing stands in for it
    assert "no file" in published_widths.problems(entry, data,
                                                  str(tmp_path))[0]


CHANGES = [
    ({"moe_intermediate_size": 704}, [], "moe_intermediate_size is 704"),
    ({"hidden_size": 1024}, [], "hidden_size is 1024"),
    ({"moe_intermediate_size": 704}, ["moe_intermediate_size"],
     "moe_intermediate_size is a width"),
    ({"num_experts_per_tok": 2}, ["num_experts_per_tok"],
     "num_experts_per_tok is a width"),
    ({"sliding_window": 128}, ["sliding_window"], "sliding_window is a width"),
    ({"head_dim": 64}, ["head_dim"], "head_dim is a width"),
    ({"rope_parameters": {"rope_theta": 1, "rope_type": "default"}}, [],
     "rope_parameters is"),
    ({"deployment": ""}, [], "num_experts is a share"),
    ({"reduced_why": {"num_hidden_layers": "27", "num_experts": "a few",
                      "max_position_embeddings": "163840",
                      "vocab_size": "102400"}}, [], "num_experts is a share"),
    ({"num_experts": 64}, [], "num_experts is in reduced")]


@pytest.mark.parametrize("change,reduced,said", CHANGES,
                         ids=[f"{i}-{'-'.join(c)}"
                              for i, (c, _, _) in enumerate(CHANGES)])
def test_a_changed_width_or_an_unstated_share_is_named(copy, change, reduced,
                                                       said):
    entry, data, published = _added(copy)
    entry = dict(entry, reduced=entry["reduced"] + reduced)
    found = published_widths.problems(entry, dict(data, **change), published)
    assert any(said in p for p in found), found


@pytest.mark.parametrize("key,step", [("reference", "reference"),
                                      ("cost_model", "cost model")])
def test_a_name_with_no_file_stops_the_run_and_is_never_a_default(
        copy, tmp_path, key, step):
    root, bench, _ = copy
    cfg = load(root / ENTRIES["config"]["file"])
    cfg[key] = "not_there"
    (root / "benchmarks" / "configs" / f"no-{key}.json").write_text(
        json.dumps(cfg))
    broken = json.loads(json.dumps(bench))
    broken["configs"][-1]["file"] = f"benchmarks/configs/no-{key}.json"
    top = tmp_path / "top"
    os.makedirs(top)
    for name in ("benchmarks", "quorum_tpu"):
        os.symlink(root / name, top / name)
    (top / "BENCHMARK.json").write_text(json.dumps(broken))
    proc = subprocess.run(
        [sys.executable, str(top / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1", "--trace", "1", "--rehearsal",
         "--out", str(tmp_path / "out")], capture_output=True, text=True,
        timeout=120, cwd=top, env=child_env())
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert proc.stderr.startswith(f"benchmark FAILED: {step}: the "
                                  "configuration names 'not_there'")


@pytest.mark.parametrize("cfg", BENCH["configs"][:3], ids=lambda c: c["name"])
def test_an_accepted_configuration_names_no_lookup(cfg):
    """PR 24's three files: the reference, the cost model and the rows'
    dimension they are measured with are ``reference.py``, ``cost_model.py``
    and ``intermediate_size``, as before the lookups were there."""
    data = load(os.path.join(REPO, cfg["file"]))
    assert not {"reference", "cost_model", "prefill_rows_dim"} & set(data)
    assert cfg["source"].endswith("mistralai/Mistral-7B-v0.1/blob/main/"
                                  "config.json")
