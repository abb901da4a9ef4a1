"""``mistral-7b-x3.quorum`` in rehearsal: the closed-loop quorum cell end to
end on the CPU at a tiny preset, untraced and traced, and a traced run whose
profile cannot be reduced."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import CHIP_ONLY, cell_metrics, rehearse  # noqa: E402

import e2e  # noqa: E402

CELL = "mistral-7b-x3.quorum"


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("quorum_u")),
                    seed=2147483650)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("quorum_t")),
                    seed=4000000009)


@pytest.fixture(scope="module")
def unreducible(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("quorum_b")),
                    seed=7, faults=("reduce",))


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced):
    result = untraced["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(cell_metrics("end_to_end", CELL))
    assert "tokens_per_s" in result["metrics"]  # the loop is closed
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_quorum_cell_accounts_for_every_member_stream(untraced):
    """Three member streams a request, and the engine's token count held
    against what they asked for: the merge forwards text only."""
    accounting = untraced["steps"]["token accounting"]
    assert accounting["ok"], accounting
    assert (accounting["engine_tokens_ramp_to_idle"]
            < 1.2 * accounting["asked_by_finished_requests"]), accounting
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    assert window and all(len(e2e.member_streams(r)) == 3 for r in window)
    assert all("final" in r["streams"] for r in window)


def test_closed_loop_keeps_its_clients_busy_through_the_window(untraced):
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    assert all(0.0 <= r["due"] < 4.0 and r["end"] is not None
               for r in window)
    assert any(r["phase"] == "ramp" for r in untraced["records"])
    view = untraced["steps"]["window client view"]
    assert view["requests"] == len(window) and view["ttft_p90_ms"] > 0


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(
        cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert result["metrics"]["rows_per_chunk"]["value"] > 1
    assert traced_run["steps"]["traced parts"]["spans"] >= 1


def test_profile_that_cannot_be_reduced_still_ends_in_a_valid_line(
        unreducible):
    """rehearse() has held the run to exit 0 and the contract's line."""
    err = unreducible["stderr"]
    assert "traced run: device trace left out: the reduction exited 1" in err
    assert "benchmark FAILED" not in err
    parts = unreducible["steps"]["traced parts"]
    assert parts["profile"]["status"] == 200 and parts["trace"] is False
    assert unreducible["result"]["correct"] is True
    assert "queue_wait_ms" in unreducible["result"]["metrics"]
    assert not os.path.exists(os.path.join(unreducible["out"], "profiles"))
