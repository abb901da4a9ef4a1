"""``mistral-7b-2k.longprompt`` in rehearsal, untraced and traced, and the
proof that the harness is driven by data: a cell, a configuration, a traffic
mix and a per-layer metric added to a temporary copy as new files and entries
only, and run there."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import (BENCH, CHIP_ONLY, REPO, cell_metrics, load,  # noqa: E402
                      rehearse)

CELL = "mistral-7b-2k.longprompt"


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("long_u")), seed=5)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("long_t")),
                    seed=2147483658)


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced):
    result = untraced["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(cell_metrics("end_to_end", CELL))
    assert {"ttft_mean_ms", "tokens_per_s"} <= set(result["metrics"])
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_long_prompts_take_the_chunked_prefill_path(untraced):
    """The rehearsal preset cuts prefill_chunk with the prompts, so the
    segment programs run here too."""
    cfg = load(os.path.join(REPO, "benchmarks", "configs",
                            "mistral-7b-2k.json"))
    url = cfg["rehearsal"]["backends"][0]["url"]
    chunk = int(url.split("prefill_chunk=")[1].split("&")[0])
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    assert window and all(r["prompt_tokens"] > chunk for r in window)


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want
    assert {"ttft_max3_ms", "queue_wait_ms"} <= want


def test_a_cell_a_configuration_a_mix_and_a_metric_are_added_as_files_only(
        tmp_path):
    """A temporary copy of the benchmark; nothing that is there is edited
    but BENCHMARK.json, which gains entries."""
    for path in BENCH["paths"][:1]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__", "out"))
    os.symlink(os.path.join(REPO, "quorum_tpu"), tmp_path / "quorum_tpu")
    bdir = tmp_path / "benchmarks"
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}

    cfg = load(bdir / "configs" / "mistral-7b.json")
    cfg["deployment"] = "a later PR's configuration"
    (bdir / "configs" / "later-7b.json").write_text(json.dumps(cfg))
    mix = load(bdir / "traffic" / "quorum.json")
    mix.update(clients=2, why="a later PR's mix")
    (bdir / "traffic" / "pairs.json").write_text(json.dumps(mix))
    (bdir / "layer_metrics" / "chunks_in_window.py").write_text(
        '"""A later PR\'s reader: decode chunks between the scrapes."""\n\n\n'
        "def read(art):\n"
        '    key = "quorum_tpu_engine_decode_chunks_total"\n'
        '    return art["m1"][key] - art["m0"][key]\n')
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "later-7b", "source": cfg["source"],
        "file": "benchmarks/configs/later-7b.json",
        "reduced": cfg["reduced"], "why": "added by a test"})
    bench["workloads"].append({
        "name": "later-7b.pairs", "config": "later-7b", "traffic": "pairs",
        "chips": 1, "why": "added by a test"})
    bench["per_layer"].append({
        "name": "chunks_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler and admission",
        "moves": "tokens_per_s", "workloads": ["later-7b.pairs"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "mistral-7b-x3.quorum" in m["workloads"]:
            m["workloads"].append("later-7b.pairs")  # a closed-loop cell
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = rehearse("later-7b.pairs", 1, str(tmp_path / "out"),
                   repo=str(tmp_path), bench=bench)
    assert run["result"]["correct"] is True
    assert run["result"]["metrics"]["chunks_in_window"]["value"] > 0
    assert {"ttft_max3_ms", "queue_wait_ms"} <= set(
        run["result"]["metrics"])
    untraced = rehearse("later-7b.pairs", 0, str(tmp_path / "out"),
                        repo=str(tmp_path), bench=bench)
    assert "tokens_per_s" in untraced["result"]["metrics"]
    assert all(p.read_bytes() == data for p, data in before.items())
