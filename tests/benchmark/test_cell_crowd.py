"""``lfm2-8b-a1b-l14.crowd``: the cell in one untraced and one traced
rehearsal at the family's tiny preset; its configuration held to the
program's preset and to the source; the cut's bytes counted from the spec;
its traffic to the generator and to ``manychat.json``; its cost model counted
by hand at the published widths; and its new reader on scrapes that have,
lack and zero their counters. Pins are written relative to the file's other
entries, so that a later cell appended to a list moves none of them."""

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
import loadgen  # noqa: E402
import published_widths  # noqa: E402
import traced  # noqa: E402

CELL = "lfm2-8b-a1b-l14.crowd"
CFG = load(os.path.join(BENCH_DIR, "configs", "lfm2-8b-a1b-l14.json"))
PUBLISHED = load(os.path.join(BENCH_DIR, "configs", "published",
                              "lfm2-8b-a1b.json"))["config"]
PEAKS = load(os.path.join(BENCH_DIR, "peaks.json"))["TPU v5 lite"]
NEW = "moe_tile_fill_share"
END_TO_END = {"tpot_p50_ms", "ttft_mean_ms", "tokens_per_s", "setup_s"}
E = "quorum_tpu_engine_"


def options(side: str) -> tuple[str, dict]:
    url = urllib.parse.urlparse(CFG[side]["backends"][0]["url"])
    return url.netloc, dict(urllib.parse.parse_qsl(url.query))


def model_config():
    """``models/model_config.py`` by its path: dataclasses only, no jax."""
    spec = importlib.util.spec_from_file_location(
        "model_config_alone_lfm2", os.path.join(
            REPO, "quorum_tpu", "models", "model_config.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def served_spec():
    model, opts = options("serve")
    return model_config().resolve_spec(model, opts), opts


# ---- the cell, whole, on the CPU: one run untraced, one traced ---------------------


@pytest.fixture(scope="module")
def untraced_run(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("crowd_u")),
                    seed=2147483941)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("crowd_t")),
                    seed=2147483929)


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced_run):
    result = untraced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == END_TO_END
    assert set(cell_metrics("end_to_end", CELL)) == END_TO_END
    window = [r for r in untraced_run["records"] if r["phase"] == "window"]
    assert window and result["attempted"] == len(window)
    assert all(r["end"] is not None and not r["error"] for r in window)
    assert untraced_run["steps"]["token accounting"]["ok"] is True


def test_traced_line_has_every_per_layer_metric_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want
    assert {NEW, "expert_picks_held_share", "expert_load_max_over_mean",
            "moe_dropped_picks", "prefill_segments_per_turn"} <= want
    said = traced_run["steps"]["configuration"]
    assert (said["reference"], said["cost_model"]) == ("lfm2_moe",) * 2
    ref = traced_run["steps"]["reference compared"]
    assert ref["ok"] is True and ref["compared"] == 12


def test_traced_line_reads_what_the_expert_products_multiplied(traced_run):
    """Every expert is held, so every pick is a held one and none is
    dropped; the tiny preset's expert layers are whole periods, whose picks
    are grouped into tiles of 128 rows whatever the rows: at the rehearsal's
    8 rows and short prompts no tile fills, so the share is a few percent."""
    got = {n: m["value"] for n, m in traced_run["result"]["metrics"].items()}
    assert got["expert_picks_held_share"] == 100.0
    assert got["moe_dropped_picks"] == 0.0
    assert 0.0 < got[NEW] < 25.0
    assert got["expert_load_max_over_mean"] >= 1.0
    assert got["prefill_segments_per_turn"] > 0


# ---- the configuration and the traffic ----------------------------------------------


def test_the_configuration_keeps_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b-l14")
    assert published_widths.problems(entry, CFG) == []
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert entry["source"] == CFG["source"]
    assert CFG["num_hidden_layers"] == 14 and PUBLISHED[
        "num_hidden_layers"] == 24
    assert CFG["layer_types"] == PUBLISHED["layer_types"][:14]
    for key, value in PUBLISHED.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert {"a_parts", "b_taps", "c_head_dim", "d_qk_norm_then_rotary",
            "e_tied", "f_router", "g_block", "stream_dtype", "tail_dtype",
            "weights", "tokenizer"} <= set(CFG["assumed"])
    assert CFG["deployment"].startswith("the first of two pipeline stages")
    assert "prefill_rows_dim" not in CFG  # intermediate_size finds the rows


@pytest.mark.parametrize("key,field", [
    ("hidden_size", "d_model"), ("num_attention_heads", "n_heads"),
    ("num_key_value_heads", "n_kv_heads"), ("intermediate_size", "d_ff"),
    ("moe_intermediate_size", "d_ff_expert"), ("vocab_size", "vocab_size"),
    ("norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
    ("num_experts", "n_experts"), ("num_experts", "held"),
    ("num_experts_per_tok", "experts_per_token"),
    ("num_dense_layers", "first_dense"), ("conv_L_cache", "conv_taps"),
    ("routed_scaling_factor", "router_scale"),
    ("num_hidden_layers", "n_layers"), ("max_position_embeddings", "max_seq")])
def test_the_served_spec_is_the_configuration_file_s(key, field):
    """The preset under the URL's options, against the file: every width as
    published, the cuts as the file states them, no option but the cut's."""
    spec, opts = served_spec()
    assert getattr(spec, field) == CFG[key]
    kinds = {"conv": "C", "full_attention": "G"}
    assert [spec.attn_kind(i) for i in range(spec.n_layers)] == [
        kinds[k] for k in CFG["layer_types"]]
    assert spec.head_dim * spec.n_heads == CFG["hidden_size"]
    assert int(opts["slots"]) == traffic_file("crowd")["clients"]
    assert set(opts) == {"n_layers", "max_seq", "slots", "seed"}


def test_the_cut_s_bytes_are_the_issue_s_from_the_spec():
    """9.33 GB of weights; at the 64 rows served (ISSUE 52's fallback from
    128) 0.81 GB of K and V and 5.8 MB of tails, half of the issue's 1.61 GB
    and 11.5 MB: counted from the served spec, two bytes a parameter."""
    spec, opts = served_spec()
    d, rows = spec.d_model, int(opts["slots"])
    conv = d * 3 * d + d * d + spec.conv_taps * d
    attn = 2 * d * spec.n_heads * spec.head_dim \
        + 2 * d * spec.n_kv_heads * spec.head_dim
    n_conv, n_attn = len(spec.layers_of("C")), len(spec.layers_of("G"))
    assert (n_conv, n_attn, spec.first_dense) == (11, 3, 2)
    experts = spec.held * 3 * d * spec.d_ff_expert + d * spec.n_experts
    params = (spec.vocab_size * d + n_conv * conv + n_attn * attn
              + spec.first_dense * 3 * d * spec.d_ff
              + (spec.n_layers - spec.first_dense) * experts)
    assert params * CFG["weight_bytes_per_param"] / 1e9 == pytest.approx(
        9.33, abs=0.01)
    kv = n_attn * 2 * rows * spec.max_seq * spec.n_kv_heads * spec.head_dim * 2
    tails = n_conv * rows * (spec.conv_taps - 1) * d * 2
    assert rows == 64
    assert kv / 1e9 == pytest.approx(1.61 / 2, abs=0.005)
    assert tails / 1e6 == pytest.approx(11.5 / 2, abs=0.05)
    model = cost_model.for_config(CFG)
    assert model.kv_bytes_per_token(CFG) * rows * spec.max_seq == kv
    assert model.state_bytes_per_row(CFG) * rows == tails
    # kv_cache_state_bytes over state + K and V: what kv_state_share reads
    assert 100.0 * tails / (tails + kv) == pytest.approx(0.71, abs=0.01)


def test_the_traffic_is_manychat_s_grid_and_clients():
    traffic, many = traffic_file("crowd"), traffic_file("manychat")
    loadgen.check_traffic(traffic)
    _, opts = options("serve")
    assert traffic["grid"] == many["grid"] and len(traffic["grid"]) == 32
    assert max(p + c + 16 for p, c in traffic["grid"]) <= int(opts["max_seq"])
    assert (traffic["loop"], traffic["clients"], traffic["ramp_s"]) == (
        "closed", many["clients"], many["ramp_s"])
    # set-up reaches every admit bucket, both long prompts' segments and the
    # probe's
    assert [p for p, _ in traffic["warmup"]] == [32, 64, 128, 256, 512, 514,
                                                 768, 1024]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b-l14", "crowd", 1)
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("side", ["serve", "rehearsal"])
def test_the_probe_decodes_inside_the_reach_of_each_hand_over(side):
    """``correct`` has to see a tail lost between two programs: the probe's
    prompt goes through one whole segment, a padded one of ONE real token
    (which reads the tail the whole segment left) and the register (which
    reads the tail the padded one left), and decodes from the next position
    on: the first decoded positions lie inside the taps' reach of both."""
    traffic = traffic_file("crowd")
    _, opts = options(side)
    chunk = int(opts.get("prefill_chunk", 512))
    div = CFG["rehearsal"] if side == "rehearsal" else None
    prompt = loadgen.scale_pair(traffic["probe"], div)[0]
    whole, rest = divmod(prompt - 1, chunk)  # the register runs the last one
    assert whole == 1 and 1 <= rest < CFG["conv_L_cache"]
    assert traffic["probe"][0] in [p for p, _ in traffic["warmup"]]
    assert traffic["probe"][1] == 12


# ---- the cost model, counted by hand at the published widths ------------------


def test_cost_model_counts_a_decode_step_s_weights_kv_and_tails():
    model = cost_model.for_config(CFG)
    assert model.__file__.endswith(os.path.join("cost_models", "lfm2_moe.py"))
    s = model.shapes(CFG)
    assert (s["attn"], s["conv"]) == (10_485_760, 16_783_360)
    assert (s["dense_mlp"], s["expert"]) == (44_040_192, 11_010_048)
    assert (s["attn_layers"], s["conv_layers"], s["sparse"]) == (3, 11, 12)
    rows, context = 128.0, 310.0
    every = 3 * s["attn"] + 11 * s["conv"] + 2 * s["dense_mlp"] \
        + 12 * s["router"]
    read = every + 12 * model.experts_read(CFG, rows) * s["expert"]
    ops, byts = model.decode_step(CFG, rows, context)
    assert byts == pytest.approx(
        2 * (read + s["head"]) + rows * context * 6144
        + 2 * rows * 11 * 2 * 2048 * 2)
    assert 12 * 32 * s["expert"] * 2 / byts == pytest.approx(0.88, abs=0.01)
    least = model.least_seconds(ops, byts, CFG, PEAKS)
    assert least == pytest.approx(byts / 819e9)   # bound by the bytes
    assert 0.0115 < least < 0.0120                # ISSUE 52's 11.7 ms
    # in the ramp few rows are live: the floor counts the experts they are
    # expected to pick, so a program that reads only those cannot read over
    # 100 % of it
    few = model.decode_step(CFG, 4.0, context)[1]
    assert few < 0.5 * byts
    assert model.experts_read(CFG, 4.0) == pytest.approx(
        32 * (1 - 0.875 ** 4))


def test_a_prefill_execution_is_bound_by_the_experts_it_reads():
    model = cost_model.for_config(CFG)
    ops, byts = model.prefill(CFG, 185.0, 185.0, 1)
    assert byts / 819e9 == pytest.approx(0.0114, abs=0.0004)
    assert ops / PEAKS["bf16_flops"] < 0.25 * byts / PEAKS["hbm_bytes_per_s"]
    # a whole 512-token segment too: 4 of 32 experts a token
    ops, byts = model.prefill(CFG, 512.0, 185.0, 1)
    assert ops / PEAKS["bf16_flops"] < byts / PEAKS["hbm_bytes_per_s"]


# ---- the new reader -----------------------------------------------------------------


def scrape(held, dropped, rows) -> dict:
    return {E + "moe_picks_total": held, E + "moe_picks_held_total": held,
            E + "moe_dropped_picks_total": dropped,
            E + "moe_tile_rows_total": rows}


SEEN = {"m0": scrape(1_000, 0, 8_000), "m1": scrape(17_000, 0, 136_000)}
DROPS = {"m0": scrape(0, 0, 0), "m1": scrape(1_000, 200, 4_000)}
# the parent's scrape has the picks and not the rows; an engine without
# expert layers has neither
PARENT = {"m0": {E + "moe_picks_held_total": 10.0,
                 E + "moe_dropped_picks_total": 0.0},
          "m1": {E + "moe_picks_held_total": 30.0,
                 E + "moe_dropped_picks_total": 0.0}}
AT_REST = {"m0": scrape(7, 0, 56), "m1": scrape(7, 0, 56)}


@pytest.mark.parametrize("art,want", [(SEEN, 12.5), (DROPS, 20.0)],
                         ids=["sixteen_picks_a_tile", "less_what_was_dropped"])
def test_reader_reads_the_share_of_the_multiplied_rows_that_were_picks(
        art, want):
    assert traced.load_reader(NEW).read(dict(art)) == pytest.approx(want)


@pytest.mark.parametrize("art", [PARENT, AT_REST, {"m0": {}, "m1": {}}],
                         ids=["parent", "at_rest", "lost"])
def test_reader_finds_nothing_where_there_is_nothing(art):
    """On the parent commit, whose program has no such counter, the reader
    returns None and does not raise: the line leaves the metric out."""
    assert traced.load_reader(NEW).read(dict(art)) is None


def test_the_new_metric_is_appended_and_lists_the_expert_cells():
    names = [m["name"] for m in BENCH["per_layer"]]
    mine = BENCH["per_layer"][names.index(NEW)]
    assert names.index(NEW) > names.index("kv_state_share")
    assert (mine["layer"], mine["moves"], mine["source"], mine["unit"],
            mine["better"]) == ("model step", "tpot_p50_ms",
                                "program_counter", "%", "higher")
    held = BENCH["per_layer"][names.index("expert_picks_held_share")]
    assert set(mine["workloads"]) == set(held["workloads"])
    assert mine["workloads"][0] == CELL
    assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics",
                                       NEW + ".py"))
    assert BENCH["workloads"][-1]["name"] == CELL or CELL in [
        w["name"] for w in BENCH["workloads"]]
    assert all(w["chips"] == 1 for w in BENCH["workloads"]
               if w["name"] == CELL)
