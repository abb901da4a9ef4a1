"""``decode_kv_read_share`` (ISSUE 35): the reader on hand-made scrapes, and
the entry that lists it. The engine counts the two counters it reads
(``tests/test_decode_kv_tiles.py`` has the counts); a scrape without them,
as the parent commit's, reads nothing and never raises."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import BENCH, cell_metrics  # noqa: E402

import traced  # noqa: E402

NAME = "decode_kv_read_share"
READ = "quorum_tpu_engine_decode_kv_tiles_read_total"
BUCKET = "quorum_tpu_engine_decode_kv_tiles_bucket_total"
DENSE_INT8_CELLS = ["mistral-7b.chat", "mistral-7b-2k.longprompt",
                    "mistral-7b.saturate"]


def read(m0: dict, m1: dict):
    return traced.load_reader(NAME).read({"m0": m0, "m1": m1})


def test_reader_returns_the_ratio_of_the_counters_rises():
    # 3.3 live rows of 12 at one tile of two: 1,320 of 9,600 tiles a window
    m0 = {READ: 500.0, BUCKET: 2400.0}
    m1 = {READ: 1820.0, BUCKET: 12000.0}
    assert read(m0, m1) == pytest.approx(100.0 * 1320 / 9600)
    # the einsum path counts what the bucket counts
    assert read({READ: 0.0, BUCKET: 0.0}, {READ: 64.0, BUCKET: 64.0}) == 100.0


@pytest.mark.parametrize("m0,m1", [
    ({}, {}),                                        # the parent's scrapes
    ({BUCKET: 1.0}, {READ: 2.0, BUCKET: 3.0}),       # a family missing once
    ({READ: 5.0, BUCKET: 9.0}, {READ: 5.0, BUCKET: 9.0}),  # no chunk went out
], ids=["absent", "half", "at-rest"])
def test_reader_reads_nothing_where_there_is_nothing_to_read(m0, m1):
    assert read(m0, m1) is None


def test_the_entry_lists_the_dense_int8_cells_and_moves_their_step():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms", "workloads": DENSE_INT8_CELLS}
    for cell in DENSE_INT8_CELLS:
        assert NAME in cell_metrics("per_layer", cell)
        assert "tpot_p50_ms" in cell_metrics("end_to_end", cell)
    assert os.path.isfile(os.path.join(
        os.path.dirname(traced.__file__), "layer_metrics", NAME + ".py"))
