"""The per-layer readers of the program's own first-token stages, prefill
counters and turn phases (ISSUE 25), on hand-made scrapes: what each reads,
and that a family missing from either scrape reads nothing and never raises
(the parent commit has none of these families, and the traced run lays this
PR's readers over it)."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import BENCH, CELLS, cell_metrics  # noqa: E402

import traced  # noqa: E402

E = "quorum_tpu_engine_"
M0 = {
    "quorum_tpu_queue_wait_seconds_sum": 1.0,
    "quorum_tpu_queue_wait_seconds_count": 10,
    "quorum_tpu_first_token_prefill_seconds_sum": 2.0,
    "quorum_tpu_first_token_prefill_seconds_count": 10,
    "quorum_tpu_first_token_backend_seconds_sum": 0.5,
    "quorum_tpu_first_token_backend_seconds_count": 10,
    "quorum_tpu_first_token_strategy_seconds_sum": 0.1,
    "quorum_tpu_first_token_strategy_seconds_count": 4,
    "quorum_tpu_first_token_wire_seconds_sum": 0.1,
    "quorum_tpu_first_token_wire_seconds_count": 4,
    E + "prefill_span_seconds_total": 3.0,
    E + "prefill_decode_wait_seconds_total": 2.0,
    E + "prefill_tokens_total": 1000, E + "prefill_padded_tokens_total": 1500,
    E + "decode_chunks_total": 100,
    E + "turn_idle_seconds_total": 50.0,
    E + "turn_reap_block_seconds_total": 40.0,
    E + "turn_sweep_seconds_total": 0.1, E + "turn_admit_seconds_total": 1.0,
    E + "turn_fill_seconds_total": 0.5, E + "turn_emit_seconds_total": 0.3,
    E + "turn_compile_seconds_total": 2.0,
}
RISE = {  # what each sample rose by between the two scrapes
    "quorum_tpu_queue_wait_seconds_sum": 3.0,        # 30 members: 100 ms
    "quorum_tpu_queue_wait_seconds_count": 30,
    "quorum_tpu_first_token_prefill_seconds_sum": 6.0,   # 200 ms
    "quorum_tpu_first_token_prefill_seconds_count": 30,
    "quorum_tpu_first_token_backend_seconds_sum": 1.5,   # 50 ms
    "quorum_tpu_first_token_backend_seconds_count": 30,
    "quorum_tpu_first_token_strategy_seconds_sum": 0.02,  # 10 requests: 2 ms
    "quorum_tpu_first_token_strategy_seconds_count": 10,
    "quorum_tpu_first_token_wire_seconds_sum": 0.01,      # 1 ms
    "quorum_tpu_first_token_wire_seconds_count": 10,
    E + "prefill_span_seconds_total": 8.0,
    E + "prefill_decode_wait_seconds_total": 6.0,
    E + "prefill_tokens_total": 3000, E + "prefill_padded_tokens_total": 4000,
    E + "decode_chunks_total": 200,
    E + "turn_idle_seconds_total": 5.0,
    E + "turn_reap_block_seconds_total": 38.0,
    E + "turn_sweep_seconds_total": 0.02, E + "turn_admit_seconds_total": 0.6,
    E + "turn_fill_seconds_total": 0.2, E + "turn_emit_seconds_total": 0.18,
    E + "turn_compile_seconds_total": 0.0,
}
WANT = {"ttft_engine_ms": 300.0, "ttft_engine_ms.open": 300.0,
        "backend_first_delta_ms": 50.0, "merge_hold_ms": 3.0,
        "prefill_decode_wait_share": 75.0, "prefill_pad_share": 25.0,
        "loop_host_ms_per_chunk": 5.0}
NEEDS = {  # one sample of each family a reader needs
    "ttft_engine_ms": ["quorum_tpu_queue_wait_seconds_sum",
                       "quorum_tpu_first_token_prefill_seconds_count"],
    "backend_first_delta_ms": ["quorum_tpu_first_token_backend_seconds_sum"],
    "merge_hold_ms": ["quorum_tpu_first_token_strategy_seconds_count",
                      "quorum_tpu_first_token_wire_seconds_sum"],
    "prefill_decode_wait_share": [E + "prefill_span_seconds_total",
                                  E + "prefill_decode_wait_seconds_total"],
    "prefill_pad_share": [E + "prefill_tokens_total",
                          E + "prefill_padded_tokens_total"],
    "loop_host_ms_per_chunk": [E + "turn_emit_seconds_total",
                               E + "turn_compile_seconds_total",
                               E + "decode_chunks_total"],
}
NEEDS["ttft_engine_ms.open"] = NEEDS["ttft_engine_ms"]


def _art(m0=None, m1=None):
    return {"m0": dict(M0) if m0 is None else m0,
            "m1": ({k: M0[k] + RISE[k] for k in M0} if m1 is None else m1),
            "spans": {}, "records": [], "config": {}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_rise_between_the_scrapes(name):
    assert traced.load_reader(name).read(_art()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name,sample,scrape", [
    (name, sample, scrape) for name in sorted(NEEDS)
    for sample in NEEDS[name] for scrape in ("m0", "m1")])
def test_reader_with_a_family_missing_from_one_scrape_reads_nothing(
        name, sample, scrape):
    art = _art()
    del art[scrape][sample]
    assert traced.load_reader(name).read(art) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_nothing_observed_between_the_scrapes_reads_nothing(name):
    assert traced.load_reader(name).read(_art(m1=dict(M0))) is None
    assert traced.load_reader(name).read(_art(m0={}, m1={})) is None


def test_new_metrics_are_listed_in_the_cells_the_issue_names():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    quorum, chat, longprompt = CELLS[:3]
    assert set(WANT) <= set(by_name)
    assert all(by_name[n]["source"] == "program_counter" for n in WANT)

    def lists(name, cells):  # a later PR's cell appends itself after these
        return by_name[name]["workloads"][:len(cells)] == cells

    assert lists("loop_host_ms_per_chunk", CELLS[:3])
    assert lists("prefill_decode_wait_share", [longprompt])
    assert lists("ttft_engine_ms.open", [chat])
    for name in ("ttft_engine_ms", "backend_first_delta_ms", "merge_hold_ms",
                 "prefill_pad_share"):
        assert lists(name, [quorum, longprompt])
    # appended: what the benchmark had keeps its place at the head of the list
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:16][-1] == "device_idle_share" and names[16:23] == [
        "ttft_engine_ms", "ttft_engine_ms.open", "backend_first_delta_ms",
        "merge_hold_ms", "prefill_decode_wait_share", "prefill_pad_share",
        "loop_host_ms_per_chunk"]
    assert "ttft_engine_ms.open" in cell_metrics("per_layer", chat)
