"""``k-exaone-ep8.reason``: the cell in rehearsal at the family's tiny preset,
untraced and traced; its configuration held to the program's preset and to
the source; its cost model counted by hand at the published widths; and its
four readers on scrapes that have, lack and zero their counters."""

from __future__ import annotations

import importlib.util
import os
import sys
import urllib.parse

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _helpers import (BENCH, BENCH_DIR, CHIP_ONLY, REPO, cell_metrics,  # noqa: E402,E501
                      load, rehearse, traffic_file)

import cost_model  # noqa: E402
import traced  # noqa: E402

CELL = "k-exaone-ep8.reason"
CFG = load(os.path.join(BENCH_DIR, "configs", "k-exaone-ep8.json"))
PUBLISHED = load(os.path.join(BENCH_DIR, "configs", "published",
                              "k-exaone-236b-a23b.json"))["config"]
PEAKS = load(os.path.join(BENCH_DIR, "peaks.json"))["TPU v5 lite"]
NEW = ("expert_picks_held_share", "expert_load_max_over_mean",
       "moe_dropped_picks", "kv_window_share")
E = "quorum_tpu_engine_"


def options(side: str) -> tuple[str, dict]:
    url = urllib.parse.urlparse(CFG[side]["backends"][0]["url"])
    return url.netloc, dict(urllib.parse.parse_qsl(url.query))


def model_config():
    """``models/model_config.py`` by its path: dataclasses only, no jax."""
    spec = importlib.util.spec_from_file_location(
        "model_config_alone", os.path.join(REPO, "quorum_tpu", "models",
                                           "model_config.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


# ---- the cell, whole, on the CPU ------------------------------------------------


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("reason_u")),
                    seed=3000000019)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("reason_t")),
                    seed=2147483903)


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced):
    result = untraced["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tpot_p50_ms", "ttft_mean_ms",
                                      "tokens_per_s", "setup_s"}
    assert set(result["metrics"]) == set(cell_metrics("end_to_end", CELL))
    assert all(m["value"] > 0 for m in result["metrics"].values())
    said = untraced["steps"]["configuration"]
    assert (said["reference"], said["cost_model"]) == ("k_exaone",) * 2
    ref = untraced["steps"]["reference compared"]
    assert ref["ok"] is True and ref["compared"] == 12


def test_the_window_takes_both_admission_paths_and_wraps_the_ring(untraced):
    """The tiny preset cuts ``prefill_chunk`` and the window with the prompts,
    so single-shot admits, segments and wrapped rings all run here too."""
    _, opts = options("rehearsal")
    chunk = int(opts["prefill_chunk"])
    window = [r for r in untraced["records"] if r["phase"] == "window"]
    prompts = {r["prompt_tokens"] for r in window}
    assert min(prompts) <= chunk < max(prompts)
    tiny = model_config().MODEL_PRESETS["k-exaone-tiny"]
    assert min(prompts) > tiny.ring  # every row's ring wraps
    assert untraced["steps"]["token accounting"]["ok"]


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want and set(NEW) <= want
    assert {"prefill_segments_per_turn", "prefill_pad_share"} <= want


def test_traced_line_reads_the_preset_s_share_and_cache(traced_run):
    got = {n: traced_run["result"]["metrics"][n]["value"] for n in NEW}
    tiny = model_config().MODEL_PRESETS["k-exaone-tiny"]
    even = 100.0 * tiny.experts_held / tiny.n_experts
    assert 0.6 * even < got["expert_picks_held_share"] < 1.6 * even
    assert 1.0 <= got["expert_load_max_over_mean"] < tiny.experts_held
    assert got["moe_dropped_picks"] == 0.0
    _, opts = options("rehearsal")
    rings = len(tiny.layers_of("L")) * tiny.ring
    full = len(tiny.layers_of("G")) * int(opts["max_seq"])
    assert got["kv_window_share"] == pytest.approx(
        100.0 * rings / (rings + full))


# ---- the configuration ----------------------------------------------------------


def test_reduced_is_depth_experts_vocabulary_positions_and_the_mtp_layer():
    entry = next(c for c in BENCH["configs"] if c["name"] == "k-exaone-ep8")
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers", "layer_types",
        "mlp_layer_types", "sliding_windows"]
    n = CFG["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert CFG[key] == PUBLISHED[key][:n]  # the layers that are here
    assert CFG["layer_types"].count("full_attention") * 3 == CFG[
        "layer_types"].count("sliding_attention")  # whole periods
    assert CFG["num_experts"] * CFG["layer_chips"] == PUBLISHED["num_experts"]
    assert CFG["vocab_size"] * CFG["layer_chips"] == PUBLISHED["vocab_size"]
    assert CFG["num_nextn_predict_layers"] == 0
    assert {"a_rotary", "b_qk_norm", "c_post_norm", "d_router_bias",
            "weights", "residual_stream"} <= set(CFG["assumed"])


@pytest.mark.parametrize("key,field", [
    ("hidden_size", "d_model"), ("head_dim", "head_dim"),
    ("num_attention_heads", "n_heads"), ("num_key_value_heads", "n_kv_heads"),
    ("intermediate_size", "d_ff"), ("moe_intermediate_size", "d_ff_expert"),
    ("num_experts_per_tok", "experts_per_token"),
    ("num_shared_experts", "n_shared_experts"),
    ("first_k_dense_replace", "first_dense"),
    ("sliding_window", "sliding_window"),
    ("sliding_window_pattern", "layer_pattern"),
    ("routed_scaling_factor", "router_scale"), ("rms_norm_eps", "norm_eps"),
    ("num_hidden_layers", "n_layers"), ("num_experts", "experts_held"),
    ("vocab_size", "vocab_size"), ("max_position_embeddings", "max_seq")])
def test_the_served_spec_is_the_configuration_file_s(key, field):
    """The preset under the URL's options, against the file: every width as
    published, every cut as the file states it."""
    model, opts = options("serve")
    spec = model_config().resolve_spec(model, opts)
    assert getattr(spec, field) == CFG[key]
    assert spec.n_experts == PUBLISHED["num_experts"]  # the router's outputs
    assert spec.rope_theta == CFG["rope_parameters"]["rope_theta"]
    assert int(opts["slots"]) == traffic_file("reason")["clients"]


def test_the_cell_fits_its_rows_and_names_no_opt_in():
    traffic = traffic_file("reason")
    _, opts = options("serve")
    assert max(p + c + 16 for p, c in traffic["grid"]) <= int(opts["max_seq"])
    assert traffic["probe"] == [768, 12] and traffic["ramp_s"] == 40
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone-ep8", "reason", 1)
    assert set(opts) == {"n_layers", "experts_held", "vocab_size", "max_seq",
                         "slots", "seed"}


# ---- the cost model, counted by hand at the published widths ------------------


def test_cost_model_counts_the_share_held_here():
    model = cost_model.for_config(CFG)
    assert model.__file__.endswith(os.path.join("cost_models", "k_exaone.py"))
    s = model.shapes(CFG)
    assert s["attn"] == 113_246_208 and s["expert"] == 37_748_736
    assert s["dense_mlp"] == 339_738_624 and s["head"] == 117_964_800
    assert (s["window_layers"], s["full_layers"], s["sparse"]) == (6, 2, 7)
    assert (s["held"], s["routed"], s["router"]) == (16, 128, 786_432)
    # two full layers' keys and values of a position, bf16
    assert model.kv_bytes_per_token(CFG) == 2 * 2 * 8 * 128 * 2
    # 32 rows pick 13.97 of the 16 held experts a layer: 10.64 GB of weights
    rows, context = 32.0, 1314.5
    picked = 16 * (1 - (120 / 128) ** 32)
    params = (8 * s["attn"] + s["dense_mlp"] + s["head"] + 7 * (
        s["router"] + (1 + picked) * s["expert"]))
    cache = rows * (2 * context + 6 * 128) * 4096
    ops, byts = model.decode_step(CFG, rows, context)
    assert byts == pytest.approx(2 * params + cache)
    assert 10.6e9 < 2 * params < 10.7e9 and 0.43e9 < cache < 0.46e9
    # a row multiplies one expert's worth of the held ones beside the shared
    active = (8 * s["attn"] + s["dense_mlp"] + s["head"]
              + 7 * (s["router"] + 2 * s["expert"]))
    assert ops == pytest.approx(
        2 * active * rows + 4 * 64 * 128 * (2 * context + 6 * 128) * rows)
    least = model.least_seconds(ops, byts, CFG, PEAKS)
    assert least == pytest.approx(byts / 819e9)  # bound by the bytes
    assert 0.0130 < least < 0.0140


@pytest.mark.parametrize("rows,held_read", [(1, 1.0), (8, 6.45), (32, 13.97),
                                            (512, 16.0)])
def test_an_expert_is_read_only_if_a_row_picked_it(rows, held_read):
    model = cost_model.for_config(CFG)
    s = model.shapes(CFG)
    _, byts = model.prefill(CFG, rows, 892.0, 1)
    rest = 8 * s["attn"] + s["dense_mlp"] + 7 * (s["router"] + s["expert"])
    assert (byts / 2 - rest) / (7 * s["expert"]) == pytest.approx(
        held_read, abs=0.01)


def test_a_window_layer_reads_no_more_than_its_window():
    model = cost_model.for_config(CFG)
    short = model.decode_step(CFG, 1.0, 100.0)[1]
    at_window = model.decode_step(CFG, 1.0, 128.0)[1]
    far = model.decode_step(CFG, 1.0, 4000.0)[1]
    assert at_window - short == pytest.approx(8 * 28 * 4096)  # all 8 layers
    assert far - at_window == pytest.approx(2 * 3872 * 4096)  # the 2 full
    # and a prefill segment of 512 rows is bound by the weights it reads
    ops, byts = model.prefill(CFG, 512, 892.0, 1)
    assert byts / PEAKS["hbm_bytes_per_s"] > ops / PEAKS["bf16_flops"]
    assert model.prefill(CFG, 1024, 892.0, 2)[1] == pytest.approx(2 * byts)


# ---- the four readers -------------------------------------------------------------


def scrape(picks, held, busiest, dropped, experts=16, full=1_073_741_824,
           window=100_663_296) -> dict:
    return {E + "moe_picks_total": picks, E + "moe_picks_held_total": held,
            E + "moe_busiest_expert_picks_total": busiest,
            E + "moe_dropped_picks_total": dropped,
            E + "moe_experts_held": experts,
            E + "kv_cache_full_bytes": full,
            E + "kv_cache_window_bytes": window}


SEEN = {"m0": scrape(56_000, 7_000, 1_000, 0),
        "m1": scrape(168_000, 22_400, 3_500, 3)}
# the parent's scrape has none of the families; a spec without a pattern has
# them all at 0 and its whole cache under "full"
ABSENT = {"m0": {E + "decode_chunks_total": 10.0},
          "m1": {E + "decode_chunks_total": 30.0}}
ZERO = {"m0": scrape(0, 0, 0, 0, experts=0, window=0),
        "m1": scrape(0, 0, 0, 0, experts=0, window=0)}


@pytest.mark.parametrize("name,want", [
    ("expert_picks_held_share", 100.0 * 15_400 / 112_000),
    ("expert_load_max_over_mean", 3_500 * 16 / 22_400),
    ("moe_dropped_picks", 3.0),
    ("kv_window_share", 100.0 * 100_663_296 / 1_174_405_120)])
def test_reader_reads_the_engine_s_counters(name, want):
    assert traced.load_reader(name).read(dict(SEEN)) == pytest.approx(want)


@pytest.mark.parametrize("art", [ABSENT, ZERO, {"m0": {}, "m1": {}}],
                         ids=["parent", "unpatterned", "lost"])
@pytest.mark.parametrize("name", NEW)
def test_reader_with_nothing_observed_reads_nothing(name, art):
    """Laid over a program that lacks the counters, or one whose model has
    no expert layer: the metric is left out, nothing raises."""
    assert traced.load_reader(name).read(dict(art)) is None


def test_new_metrics_are_the_cell_s_own_and_appended():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-4:] == list(NEW)
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        assert m["source"] == "program_counter"
    assert by_name["kv_window_share"]["layer"] == by_name["hbm_peak_gb"]["layer"]
    assert BENCH["workloads"][-1]["name"] == CELL
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW:
            assert m["workloads"][-1] == CELL  # after the names that were there
