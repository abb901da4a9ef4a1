"""The six per-layer readers of the engine's device ledger (ISSUE 40), on
hand-made scrapes: what each reads, that the parent's scrape (none of the
ledger's families) reads nothing and never raises, and that the six entries
are appended to ``per_layer`` with the cells the issue names."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import BENCH, CELLS, cell_metrics  # noqa: E402

import serving  # noqa: E402
import traced  # noqa: E402

E = "quorum_tpu_engine_"
# the parent's scrape: the families that were there before the ledger
PARENT0 = {E + "prefill_span_seconds_total": 3.0,
           E + "prefill_decode_wait_seconds_total": 2.0,
           E + "prefill_padded_tokens_total": 1500.0,
           E + "decode_chunks_total": 100.0}
PARENT1 = {E + "prefill_span_seconds_total": 11.0,
           E + "prefill_decode_wait_seconds_total": 8.0,
           E + "prefill_padded_tokens_total": 81500.0,
           E + "decode_chunks_total": 300.0}
M0 = dict(PARENT0, **{
    E + "device_decode_seconds_total": 10.0,
    E + "device_decode_steps_total": 1000.0,
    E + "device_prefill_seconds_total": 4.0,
    E + "device_other_seconds_total": 0.1,
    E + "device_starved_seconds_total": 6.0,
    E + "prefill_peer_seconds_total": 1.0,
    E + "stalls_total": 1.0,
})
RISE = {
    E + "device_decode_seconds_total": 24.0,   # 2000 steps: 12 ms a step
    E + "device_decode_steps_total": 2000.0,
    E + "device_prefill_seconds_total": 8.0,   # 80,000 tokens: 100 ms a ktok
    E + "device_other_seconds_total": 0.0,
    E + "device_starved_seconds_total": 8.0,   # of 40 s: 20 %
    E + "prefill_peer_seconds_total": 2.0,     # of 8 s of spans: 25 %
    E + "stalls_total": 0.0,
}
M1 = dict(PARENT1, **{k: M0[k] + v for k, v in RISE.items()})
WANT = {
    "engine_decode_step_ms": 12.0,
    "engine_prefill_ms_per_ktok": 100.0,
    "device_prefill_share": 20.0,
    "device_starved_share": 20.0,
    "first_token_peer_prefill_share": 25.0,
    "window_stalls": 0.0,
}
NEEDS = {  # the samples whose absence from a scrape must silence the reader
    "engine_decode_step_ms": ["device_decode_seconds_total",
                              "device_decode_steps_total"],
    "engine_prefill_ms_per_ktok": ["device_prefill_seconds_total",
                                   "prefill_padded_tokens_total"],
    "device_prefill_share": ["device_decode_seconds_total",
                             "device_prefill_seconds_total",
                             "device_other_seconds_total",
                             "device_starved_seconds_total"],
    "device_starved_share": ["device_decode_seconds_total",
                             "device_prefill_seconds_total",
                             "device_other_seconds_total",
                             "device_starved_seconds_total"],
    "first_token_peer_prefill_share": ["prefill_peer_seconds_total",
                                       "prefill_span_seconds_total"],
    "window_stalls": ["stalls_total"],
}


def _art(m0=M0, m1=M1):
    return {"m0": m0, "m1": m1, "spans": {}, "trace": None,
            "log_compiles0": 0, "log_compiles1": 0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_rise_between_the_scrapes(name):
    assert traced.load_reader(name).read(_art()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_on_the_parents_scrape(name):
    """The traced run lays these readers over the parent commit, whose
    ``/metrics`` has none of the ledger's families: None, never a raise."""
    reader = traced.load_reader(name)
    assert reader.read(_art(PARENT0, PARENT1)) is None
    assert reader.read(_art({}, {})) is None


@pytest.mark.parametrize("name, sample", [
    (name, sample) for name in sorted(NEEDS) for sample in NEEDS[name]])
@pytest.mark.parametrize("scrape", ["m0", "m1"])
def test_reader_needs_every_family_in_both_scrapes(name, sample, scrape):
    art = _art(dict(M0), dict(M1))
    del art[scrape][E + sample]
    assert traced.load_reader(name).read(art) is None


def test_a_shares_base_is_the_four_accounts_together():
    """Both shares divide by the rise of decode + prefill + other + starved:
    the scheduler loop's wall clock between the scrapes."""
    art = _art(dict(M0), dict(M1, **{
        E + "device_other_seconds_total": M0[E + "device_other_seconds_total"]
        + 10.0}))  # 50 s now
    assert traced.load_reader("device_prefill_share").read(art) == \
        pytest.approx(16.0)
    assert traced.load_reader("device_starved_share").read(art) == \
        pytest.approx(16.0)


def test_the_scrape_sums_a_familys_label_sets():
    """``serving.parse_metrics`` hands a reader each family summed over its
    label sets: the starved phases and the prefill buckets arrive as one
    number each, the backend label with them."""
    text = "\n".join([
        "# TYPE quorum_tpu_engine_device_starved_seconds_total counter",
        'quorum_tpu_engine_device_starved_seconds_total'
        '{backend="m",phase="admit"} 1.5',
        'quorum_tpu_engine_device_starved_seconds_total'
        '{backend="m",phase="emit"} 0.25',
        "# TYPE quorum_tpu_engine_device_prefill_seconds_total counter",
        'quorum_tpu_engine_device_prefill_seconds_total'
        '{backend="m",family="seg",bucket="512"} 3.0',
        'quorum_tpu_engine_device_prefill_seconds_total'
        '{backend="m",family="single_shot",bucket="64"} 0.5',
        "# TYPE quorum_tpu_engine_stalls_total counter",
        'quorum_tpu_engine_stalls_total{backend="m"} 0', ""])
    m = serving.parse_metrics(text)
    assert m[E + "device_starved_seconds_total"] == pytest.approx(1.75)
    assert m[E + "device_prefill_seconds_total"] == pytest.approx(3.5)
    assert m[E + "stalls_total"] == 0.0


def test_the_six_entries_are_appended_with_the_cells_the_issue_names():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    quorum, chat, longprompt, reason, longdoc, saturate = CELLS[:6]
    every = [quorum, chat, longprompt, reason, longdoc, saturate]
    cells = {
        "engine_decode_step_ms": every,
        "engine_prefill_ms_per_ktok": [quorum, longprompt, reason, longdoc],
        "device_prefill_share": [quorum, longprompt, reason, longdoc,
                                 saturate],
        "device_starved_share": every,
        "first_token_peer_prefill_share": [longprompt, reason, longdoc],
        "window_stalls": every,
    }
    moves = {"engine_prefill_ms_per_ktok": "ttft_mean_ms",
             "first_token_peer_prefill_share": "ttft_mean_ms"}
    for name, want in cells.items():
        entry = by_name[name]
        # a later PR's cell appends itself after these
        assert entry["workloads"][:len(want)] == want
        assert entry["source"] == "program_counter"
        assert entry["moves"] == moves.get(name, "tpot_p50_ms")
        # all six are the loop's own account, on the host's clock: the
        # device's idle time is the profile's (device_idle_share)
        assert entry["layer"] == "scheduler and admission"
        assert all(name in cell_metrics("per_layer", c) for c in want)
    # appended: what the benchmark had keeps its place ahead of them
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("engine_decode_step_ms")
    assert names[first:first + 6] == list(cells)
    assert names[first - 1] == "decode_kv_read_share"
    # every cell still reports an end-to-end metric each new metric moves
    by_e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for name, want in cells.items():
        e2e = by_e2e[by_name[name]["moves"]]
        assert set(want) <= set(e2e.get("workloads", CELLS))
