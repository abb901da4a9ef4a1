"""``mistral-7b.saturate``: data only. The accepted ``mistral-7b``
configuration under a closed loop of twice its rows: the cell in one untraced
rehearsal, its traffic file held to the generator and to ``chat.json``'s
grid, its entries appended."""

from __future__ import annotations

import os
import sys
import urllib.parse

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import (BENCH, BENCH_DIR, cell_metrics, load, rehearse,  # noqa: E402,E501
                      traffic_file)

import loadgen  # noqa: E402
import published_widths  # noqa: E402

CELL = "mistral-7b.saturate"
CFG = load(os.path.join(BENCH_DIR, "configs", "mistral-7b.json"))


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("saturate_u")),
                    seed=3000000019)


def test_untraced_line_has_the_cell_s_end_to_end_metrics(untraced):
    result = untraced["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tpot_p50_ms", "tokens_per_s",
                                      "setup_s"}
    assert set(result["metrics"]) == set(cell_metrics("end_to_end", CELL))
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert untraced["steps"]["token accounting"]["ok"]


def test_the_queue_is_never_empty(untraced):
    """Twice the rows in clients: whenever a row frees, a request waits."""
    traffic = traffic_file("saturate")
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    assert len(window) > traffic["clients"]
    assert untraced["steps"]["window ends"]["pending_at_read_end"] > 0


def test_the_traffic_is_chat_s_grid_under_a_closed_loop():
    traffic, chat = traffic_file("saturate"), traffic_file("chat")
    loadgen.check_traffic(traffic)
    assert traffic["loop"] == "closed" and traffic["ramp_s"] == 16
    for key in ("grid", "warmup", "probe", "source"):
        assert traffic[key] == chat[key]
    url = urllib.parse.urlparse(CFG["serve"]["backends"][0]["url"])
    opts = dict(urllib.parse.parse_qsl(url.query))
    assert traffic["clients"] == 24 == 2 * int(opts["slots"])
    assert max(p + c + 16 for p, c in traffic["grid"]) <= int(opts["max_seq"])


def test_the_cell_is_data_on_an_accepted_configuration():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mistral-7b", "saturate", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == "mistral-7b")
    assert published_widths.problems(entry, CFG) == []
    assert BENCH["configs"].index(entry) == 1  # where it was: nothing moved
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    # after the cells that were there (a later cell goes after this one)
    for name in ("tokens_per_s", "loop_host_ms_per_chunk"):
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) > cells.index("k-exaone-ep8.reason")
    # one service time of queue is in its first-token time: not reported
    assert CELL not in by_name["ttft_mean_ms"]["workloads"]
    assert CELL not in by_name["latency_p50_ms"]["workloads"]
    assert cell_metrics("per_layer", CELL)
