"""``dots3-ep8.longdoc``: the cell in one traced rehearsal at the family's tiny
preset; its configuration held to the program's preset and to the source; its
traffic to the generator; its cost model counted by hand at the published
widths; and its two readers on scrapes that have, lack and zero their
counters."""

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

CELL = "dots3-ep8.longdoc"
CFG = load(os.path.join(BENCH_DIR, "configs", "dots3-ep8.json"))
PUBLISHED = load(os.path.join(BENCH_DIR, "configs", "published",
                              "dots3-note-prev.json"))["config"]
PEAKS = load(os.path.join(BENCH_DIR, "peaks.json"))["TPU v5 lite"]
NEW = ("attn_keys_kept_share", "kv_index_share")
SHARED = ("expert_picks_held_share", "expert_load_max_over_mean",
          "moe_dropped_picks", "kv_window_share")
E = "quorum_tpu_engine_"


def options(side: str) -> tuple[str, dict]:
    url = urllib.parse.urlparse(CFG[side]["backends"][0]["url"])
    return url.netloc, dict(urllib.parse.parse_qsl(url.query))


def model_config():
    """``models/model_config.py`` by its path: dataclasses only, no jax."""
    spec = importlib.util.spec_from_file_location(
        "model_config_alone_dots", os.path.join(
            REPO, "quorum_tpu", "models", "model_config.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


# ---- the cell, whole, on the CPU: one run, traced ---------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("longdoc_t")),
                    seed=2147483903)


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want
    assert set(NEW + SHARED) <= want
    said = traced_run["steps"]["configuration"]
    assert (said["reference"], said["cost_model"]) == ("dots3",) * 2
    ref = traced_run["steps"]["reference compared"]
    assert ref["ok"] is True and ref["compared"] == 8


def test_traced_line_reads_the_selection_and_the_cache_by_kind(traced_run):
    got = {n: traced_run["result"]["metrics"][n]["value"]
           for n in NEW + SHARED}
    tiny = model_config().MODEL_PRESETS["dots3-tiny"]
    _, opts = options("rehearsal")
    # every prompt of the window is past index_topk: the full layers dropped
    # more than half of what their queries could have attended
    prompts = {r["prompt_tokens"] for r in traced_run["records"]
               if r["phase"] == "window"}
    assert min(prompts) > 2 * tiny.index_topk and min(prompts) > tiny.ring
    assert 5.0 < got["attn_keys_kept_share"] < 50.0
    full = 3 * int(opts["max_seq"])
    rows = {"full": full * 128, "index": full * tiny.index_head_dim,
            "window": 3 * tiny.ring * 128}     # rows are padded to 128 lanes
    assert got["kv_index_share"] == pytest.approx(
        100.0 * rows["index"] / sum(rows.values()))
    assert got["kv_window_share"] == pytest.approx(
        100.0 * rows["window"] / (rows["window"] + rows["full"]))
    even = 100.0 * tiny.experts_held / tiny.n_experts
    assert 0.6 * even < got["expert_picks_held_share"] < 1.6 * even
    assert got["moe_dropped_picks"] == 0.0


# ---- the configuration and the traffic ----------------------------------------------


def test_the_configuration_keeps_the_published_widths():
    entry = next(c for c in BENCH["configs"] if c["name"] == "dots3-ep8")
    assert published_widths.problems(entry, CFG) == []
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "layer_types"]
    n = CFG["num_hidden_layers"]
    assert CFG["layer_types"] == PUBLISHED["layer_types"][:n]
    # a dense layer, five expert layers, one whole window-window-window-full
    # period among them; half full here against 13 of 46 published
    assert CFG["layer_types"][2:] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert CFG["layer_types"].count("full_attention") == 3
    assert PUBLISHED["layer_types"].count("full_attention") == 13
    assert "13 of 46" in CFG["reduced_why"]["num_hidden_layers"]
    assert CFG["n_routed_experts"] * CFG["layer_chips"] == PUBLISHED[
        "n_routed_experts"]
    assert CFG["vocab_size"] * CFG["layer_chips"] == PUBLISHED["vocab_size"]
    assert {"a_pre_norm", "b_rescale", "c_window", "d_gate", "e_indexer",
            "f_router", "index_precision", "weights"} <= set(CFG["assumed"])
    assert "not_loaded" in CFG and CFG["deployment"]


@pytest.mark.parametrize("key,field", [
    ("hidden_size", "d_model"), ("num_attention_heads", "n_heads"),
    ("intermediate_size", "d_ff"), ("moe_intermediate_size", "d_ff_expert"),
    ("num_experts_per_tok", "experts_per_token"),
    ("n_shared_experts", "n_shared_experts"),
    ("first_k_dense_replace", "first_dense"),
    ("sliding_window_size", "sliding_window"),
    ("routed_scaling_factor", "router_scale"), ("rms_norm_eps", "norm_eps"),
    ("rope_theta", "rope_theta"), ("swa_rope_theta", "swa_rope_theta"),
    ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
    ("qk_nope_head_dim", "qk_nope_head_dim"),
    ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
    ("swa_num_attention_heads", "swa_n_heads"),
    ("swa_q_lora_rank", "swa_q_lora_rank"),
    ("swa_kv_lora_rank", "swa_kv_lora_rank"),
    ("swa_qk_nope_head_dim", "swa_qk_nope_head_dim"),
    ("swa_qk_rope_head_dim", "swa_qk_rope_head_dim"),
    ("swa_v_head_dim", "swa_v_head_dim"),
    ("index_n_heads", "index_n_heads"), ("index_head_dim", "index_head_dim"),
    ("index_topk", "index_topk"),
    ("num_hidden_layers", "n_layers"), ("n_routed_experts", "experts_held"),
    ("vocab_size", "vocab_size"), ("max_position_embeddings", "max_seq")])
def test_the_served_spec_is_the_configuration_file_s(key, field):
    """The preset under the URL's options, against the file: every width as
    published, every cut as the file states it."""
    model, opts = options("serve")
    spec = model_config().resolve_spec(model, opts)
    assert getattr(spec, field) == CFG[key]
    assert spec.n_experts == PUBLISHED["n_routed_experts"]  # router outputs
    kinds = {"full_attention": "G", "sliding_attention": "L"}
    assert [spec.attn_kind(i) for i in range(spec.n_layers)] == [
        kinds[k] for k in CFG["layer_types"]]
    assert int(opts["slots"]) == traffic_file("longdoc")["clients"]


def test_the_traffic_is_the_generator_s_and_fits_its_rows():
    traffic = traffic_file("longdoc")
    loadgen.check_traffic(traffic)
    _, opts = options("serve")
    assert max(p + c + 16 for p, c in traffic["grid"]) <= int(opts["max_seq"])
    prompts = [p for p, _ in traffic["grid"]]
    completions = [c for _, c in traffic["grid"]]
    assert (min(prompts), max(prompts)) == (2560, 15360)
    assert all(p % 512 == 0 for p in prompts)
    # inside the cut of 32..512: the spread the file assumed is narrower
    assert (min(completions), max(completions)) == (55, 472)
    # the published means, through the cut: 7,590 in and 182 out
    assert 7000 < sum(prompts) / 32 < 8000
    assert 160 < sum(completions) / 32 < 200
    assert traffic["probe"] == [4096, 8]  # half the history is dropped
    assert traffic["probe"][0] == 2 * CFG["index_topk"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-ep8", "longdoc", 1)
    assert set(opts) == {"n_layers", "experts_held", "vocab_size", "max_seq",
                         "slots", "seed"}


# ---- the cost model, counted by hand at the published widths ------------------


def test_cost_model_counts_the_share_held_here():
    model = cost_model.for_config(CFG)
    assert model.__file__.endswith(os.path.join("cost_models", "dots3.py"))
    s = model.shapes(CFG)
    assert s["full"]["params"] == 144_048_128  # 8,388,608 + 983,040 indexer
    assert s["swa"]["params"] == 90_832_896
    assert s["expert"] == 23_592_960 and s["dense_mlp"] == 212_336_640
    assert s["head"] == 97_320_960 and s["router"] == 1_310_720
    assert (s["window_layers"], s["full_layers"], s["sparse"]) == (3, 3, 5)
    assert (s["held"], s["routed"]) == (32, 256)
    # a position grows the cache by a latent row and an index key a full layer
    assert model.kv_bytes_per_token(CFG) == 3 * (576 + 128) * 2
    # 16 rows pick 12.8 of the 32 held experts a layer
    rows, context = 16.0, 7576.0
    picked = 32 * (1 - (248 / 256) ** 16)
    params = (3 * s["full"]["params"] + 3 * s["swa"]["params"]
              + s["dense_mlp"] + s["head"]
              + 5 * (s["router"] + (1 + picked) * s["expert"]))
    cache = rows * 2 * (3 * (128 * context + 576 * 2048) + 3 * 1088 * 513)
    ops, byts = model.decode_step(CFG, rows, context)
    assert byts == pytest.approx(2 * params + cache)
    assert 5.2e9 < 2 * params < 5.4e9 and 0.25e9 < cache < 0.27e9
    # a row multiplies one expert's worth of the held ones beside the shared
    active = (3 * s["full"]["params"] + 3 * s["swa"]["params"]
              + s["dense_mlp"] + s["head"]
              + 5 * (s["router"] + 2 * s["expert"]))
    attention = (3 * (2 * 64 * 128 * context + 128 * 2 * 320 * 2048)
                 + 3 * 64 * 2 * 384 * 513)
    assert ops == pytest.approx((2 * active + attention) * rows)
    least = model.least_seconds(ops, byts, CFG, PEAKS)
    assert least == pytest.approx(byts / 819e9)  # bound by the bytes
    assert 0.0065 < least < 0.0070


def test_a_query_attends_no_more_than_the_indexer_keeps():
    model = cost_model.for_config(CFG)
    short = model.decode_step(CFG, 1.0, 2000.0)
    at_topk = model.decode_step(CFG, 1.0, 2048.0)
    far = model.decode_step(CFG, 1.0, 16000.0)
    # up to index_topk a position more is an index key and a row to read,
    # beyond it an index key alone; the rings are full from 513 on
    assert at_topk[1] - short[1] == pytest.approx(48 * 3 * (128 + 576) * 2)
    assert far[1] - at_topk[1] == pytest.approx(13952 * 3 * 128 * 2)
    assert far[0] - at_topk[0] == pytest.approx(13952 * 3 * 2 * 64 * 128)
    assert model.mean_attended(1000.0, 2048) == 500.0
    assert model.mean_attended(8192.0, 2048) == 2048 - 256
    # a 512-token segment reads every held expert (9.6 GB, 11.8 ms) and is
    # bound by that, not by its 1.5 TFLOP (7.8 ms): 3.0 GFLOP a prompt token
    # (ISSUE 34 reckoned 3.3)
    ops, byts = model.prefill(CFG, 512, 7488.0, 1)
    assert ops / PEAKS["bf16_flops"] < byts / PEAKS["hbm_bytes_per_s"]
    assert 2.8e9 < ops / 512 < 3.3e9 and 9.5e9 < byts < 9.8e9


# ---- the two readers ---------------------------------------------------------------


def scrape(attended, history, full=1_006_632_960, window=113_246_208,
           index=201_326_592) -> dict:
    return {E + "dsa_keys_attended_total": attended,
            E + "dsa_keys_in_history_total": history,
            E + "kv_cache_full_bytes": full,
            E + "kv_cache_window_bytes": window,
            E + "kv_cache_index_bytes": index}


SEEN = {"m0": scrape(1_000_000, 2_000_000),
        "m1": scrape(5_000_000, 12_000_000)}
# the parent's scrape has none of the families; a spec that selects nothing
# has no dsa counters and no index keys
ABSENT = {"m0": {E + "decode_chunks_total": 10.0},
          "m1": {E + "decode_chunks_total": 30.0}}
NOTHING = {"m0": {E + "kv_cache_full_bytes": 5, E + "kv_cache_index_bytes": 0},
           "m1": {E + "kv_cache_full_bytes": 5, E + "kv_cache_index_bytes": 0}}
AT_REST = {"m0": scrape(7, 9), "m1": scrape(7, 9, index=0)}


@pytest.mark.parametrize("name,want", [
    ("attn_keys_kept_share", 40.0),
    ("kv_index_share", 100.0 * 201_326_592 / 1_321_205_760)])
def test_reader_reads_the_engine_s_counters(name, want):
    assert traced.load_reader(name).read(dict(SEEN)) == pytest.approx(want)


@pytest.mark.parametrize("art", [ABSENT, NOTHING, AT_REST,
                                 {"m0": {}, "m1": {}}],
                         ids=["parent", "unselecting", "at_rest", "lost"])
@pytest.mark.parametrize("name", NEW)
def test_reader_with_nothing_observed_reads_nothing(name, art):
    """Laid over a program that lacks the counters, or one whose model
    selects nothing: the metric is left out, nothing raises."""
    assert traced.load_reader(name).read(dict(art)) is None


def test_new_entries_are_appended_and_the_cell_joins_the_shared_lists():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # after what was there; what a later PR appends goes after these
    assert names[names.index("kv_window_share") + 1:][:2] == list(NEW)
    for name, moves in zip(NEW, ("ttft_mean_ms", "tpot_p50_ms")):
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == moves
        assert m["source"] == "program_counter"
    assert by_name["kv_index_share"]["layer"] == by_name["hbm_peak_gb"]["layer"]
    assert by_name["attn_keys_kept_share"]["layer"] == by_name[
        "decode_step_ms"]["layer"]
    # every list the other patterned cell is on: the shared code's counters
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if "k-exaone-ep8.reason" in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    assert set(cell_metrics("end_to_end", CELL)) == {
        "tpot_p50_ms", "ttft_mean_ms", "tokens_per_s", "setup_s"}
    cells = [w["name"] for w in BENCH["workloads"]]
    at = cells.index("k-exaone-ep8.reason")
    assert cells[at + 1:at + 3] == [CELL, "mistral-7b.saturate"]
