"""``falcon-h1-34b-l6.manychat``: the cell in one untraced and one traced
rehearsal at the family's tiny preset; its configuration held to the
program's preset and to the source; its traffic to the generator and to
``chat.json``; its cost model counted by hand at the published widths; and
its two readers on scrapes that have, lack and zero their counters."""

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

CELL = "falcon-h1-34b-l6.manychat"
CFG = load(os.path.join(BENCH_DIR, "configs", "falcon-h1-34b-l6.json"))
PUBLISHED = load(os.path.join(BENCH_DIR, "configs", "published",
                              "falcon-h1-34b-instruct.json"))["config"]
PEAKS = load(os.path.join(BENCH_DIR, "peaks.json"))["TPU v5 lite"]
NEW = ("kv_state_share", "ssm_state_live_share")
END_TO_END = {"tpot_p50_ms", "ttft_mean_ms", "tokens_per_s", "setup_s"}
E = "quorum_tpu_engine_"


def options(side: str) -> tuple[str, dict]:
    url = urllib.parse.urlparse(CFG[side]["backends"][0]["url"])
    return url.netloc, dict(urllib.parse.parse_qsl(url.query))


def model_config():
    """``models/model_config.py`` by its path: dataclasses only, no jax."""
    spec = importlib.util.spec_from_file_location(
        "model_config_alone_falcon", os.path.join(
            REPO, "quorum_tpu", "models", "model_config.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


# ---- the cell, whole, on the CPU: one run untraced, one traced ---------------------


@pytest.fixture(scope="module")
def untraced_run(tmp_path_factory):
    return rehearse(CELL, 0, str(tmp_path_factory.mktemp("manychat_u")),
                    seed=2147483913)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return rehearse(CELL, 1, str(tmp_path_factory.mktemp("manychat_t")),
                    seed=2147483903)


def test_untraced_line_has_the_cells_end_to_end_metrics(untraced_run):
    result = untraced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == END_TO_END
    assert set(cell_metrics("end_to_end", CELL)) == END_TO_END


def test_the_windows_requests_all_finish_and_two_take_segments(untraced_run):
    """Every request of the window finished with the tokens it asked for; the
    grid's two long prompts, divided like the others, are still over the
    rehearsal's prefill chunk and went through segments."""
    window = [r for r in untraced_run["records"] if r["phase"] == "window"]
    assert window and untraced_run["result"]["attempted"] == len(window)
    assert all(r["end"] is not None and not r["error"] for r in window)
    assert untraced_run["steps"]["token accounting"]["ok"] is True
    _, opts = options("rehearsal")
    chunk = int(opts["prefill_chunk"])
    div = {"prompt_div": CFG["rehearsal"]["prompt_div"],
           "completion_div": CFG["rehearsal"]["completion_div"]}
    scaled = [loadgen.scale_pair(p, div)[0]
              for p in traffic_file("manychat")["grid"]]
    assert sorted(p for p in scaled if p > chunk) == [96, 128]
    assert {r["prompt_tokens"] for r in window} >= {96, 128}


def test_traced_line_has_the_per_layer_metrics_a_cpu_can_read(traced_run):
    result = traced_run["result"]
    assert result["correct"] is True and result["failed"] == 0
    want = set(cell_metrics("per_layer", CELL)) - CHIP_ONLY
    assert set(result["metrics"]) == want
    assert set(NEW) <= want
    said = traced_run["steps"]["configuration"]
    assert (said["reference"], said["cost_model"]) == ("falcon_h1",) * 2
    ref = traced_run["steps"]["reference compared"]
    assert ref["ok"] is True and ref["compared"] == 12


def test_traced_line_reads_the_state_s_share_and_what_moved_it(traced_run):
    got = {n: traced_run["result"]["metrics"][n]["value"] for n in NEW}
    tiny = model_config().MODEL_PRESETS["falcon-h1-tiny"]
    _, opts = options("rehearsal")
    state = (tiny.ssm_heads * tiny.ssm_head_dim * tiny.ssm_state * 4
             + (tiny.ssm_conv - 1) * tiny.ssm_conv_width * 2)
    kv = 2 * int(opts["max_seq"]) * tiny.n_kv_heads * tiny.head_dim * 2
    assert got["kv_state_share"] == pytest.approx(
        100.0 * state / (state + kv))
    assert 0.0 < got["ssm_state_live_share"] <= 100.0
    # the scan runs over what the prefill programs run over: its pad share
    # is prefill_pad_share, and the line has no second name for it
    assert "prefill_pad_share" in traced_run["result"]["metrics"]
    assert "ssm_scan_pad_share" not in traced_run["result"]["metrics"]
    assert traced_run["result"]["metrics"][
        "prefill_segments_per_turn"]["value"] > 0


# ---- the configuration and the traffic ----------------------------------------------


def test_the_configuration_keeps_the_published_widths():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "falcon-h1-34b-l6")
    assert published_widths.problems(entry, CFG) == []
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert entry["source"] == CFG["source"]
    assert CFG["num_hidden_layers"] == 6 and PUBLISHED[
        "num_hidden_layers"] == 72
    for key, value in PUBLISHED.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert {"a_parts", "b_mlp", "c_gate_norm", "d_rotary_pairing", "e_conv",
            "f_block", "state_dtype", "tail_dtype", "weights"} <= set(
        CFG["assumed"])
    assert CFG["deployment"] and CFG["prefill_rows_dim"] == CFG[
        "intermediate_size"]


@pytest.mark.parametrize("key,field", [
    ("hidden_size", "d_model"), ("num_attention_heads", "n_heads"),
    ("num_key_value_heads", "n_kv_heads"), ("head_dim", "head_dim"),
    ("intermediate_size", "d_ff"), ("vocab_size", "vocab_size"),
    ("rms_norm_eps", "norm_eps"), ("rope_theta", "rope_theta"),
    ("mamba_n_heads", "ssm_heads"), ("mamba_d_head", "ssm_head_dim"),
    ("mamba_d_state", "ssm_state"), ("mamba_n_groups", "ssm_groups"),
    ("mamba_d_conv", "ssm_conv"), ("mamba_chunk_size", "ssm_chunk"),
    ("mamba_d_ssm", "ssm_width"),
    ("embedding_multiplier", "emb_scale"),
    ("attention_in_multiplier", "attn_in_mult"),
    ("attention_out_multiplier", "attn_out_mult"),
    ("key_multiplier", "key_mult"), ("ssm_in_multiplier", "ssm_in_mult"),
    ("ssm_out_multiplier", "ssm_out_mult"),
    ("lm_head_multiplier", "lm_head_mult"),
    ("num_hidden_layers", "n_layers"), ("max_position_embeddings", "max_seq")])
def test_the_served_spec_is_the_configuration_file_s(key, field):
    """The preset under the URL's options, against the file: every width and
    multiplier as published, both cuts as the file states them."""
    model, opts = options("serve")
    spec = model_config().resolve_spec(model, opts)
    assert getattr(spec, field) == CFG[key]
    assert list(spec.ssm_mults) == CFG["ssm_multipliers"]
    assert [spec.mlp_gate_mult, spec.mlp_down_mult] == CFG["mlp_multipliers"]
    assert int(opts["slots"]) == traffic_file("manychat")["clients"]
    assert set(opts) == {"n_layers", "max_seq", "slots", "seed"}


def test_the_traffic_is_chat_s_grid_with_its_two_long_prompts_uncut():
    traffic, chat = traffic_file("manychat"), traffic_file("chat")
    loadgen.check_traffic(traffic)
    _, opts = options("serve")
    assert max(p + c + 16 for p, c in traffic["grid"]) <= int(opts["max_seq"])
    assert (traffic["loop"], traffic["clients"], traffic["ramp_s"]) == (
        "closed", 64, 16)
    changed = [(mine, theirs) for mine, theirs in zip(
        traffic["grid"], chat["grid"]) if mine != theirs]
    assert changed == [([768, 110], [480, 110]), ([1024, 250], [480, 250])]
    assert len(traffic["grid"]) == len(chat["grid"]) == 32
    assert sum(c for _, c in traffic["grid"]) / 32 == pytest.approx(249.75)
    # set-up reaches every admit bucket, both long prompts' segments and the
    # probe's
    assert [p for p, _ in traffic["warmup"]] == [32, 64, 128, 256, 512, 530,
                                                 768, 1024]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-l6", "manychat", 1)


@pytest.mark.parametrize("side", ["serve", "rehearsal"])
def test_the_probe_decodes_just_behind_a_boundary_the_state_is_carried_over(
        side):
    """``correct`` has to see a state lost between two segments: the probe's
    prompt goes through a whole segment and a padded one, and what it decodes
    lies a few tens of positions behind the boundary, where a state zeroed
    there still shows (PERF.md section 2a: 256 behind it did not)."""
    traffic = traffic_file("manychat")
    _, opts = options(side)
    chunk = int(opts.get("prefill_chunk", 512))
    div = CFG["rehearsal"] if side == "rehearsal" else None
    prompt, generated = loadgen.scale_pair(traffic["probe"], div)[0], \
        traffic["probe"][1]
    whole, rest = divmod(prompt - 1, chunk)  # the register runs the last one
    assert whole == 1 and 0 < rest < chunk // 8
    assert prompt + generated - whole * chunk <= 32
    assert traffic["probe"][0] in [p for p, _ in traffic["warmup"]]


# ---- the cost model, counted by hand at the published widths ------------------


def test_cost_model_counts_the_state_a_live_row_moves():
    model = cost_model.for_config(CFG)
    assert model.__file__.endswith(os.path.join("cost_models", "falcon_h1.py"))
    s = model.shapes(CFG)
    attention = 5120 * 2560 * 2 + 5120 * 512 * 2
    mixer = 5120 * (4096 + 4096 + 512 + 512 + 32) + 4096 * 5120
    assert s["layer_params"] == attention + mixer + 3 * 5120 * 21504
    assert (attention, mixer) == (31_457_280, 68_321_280)
    assert s["head_params"] == 5120 * 261120
    assert model.kv_bytes_per_token(CFG) == 6 * 2 * 512 * 2
    # a row's state: 32 x 128 x 256 float32 and a tail of 3 x 5120 bfloat16,
    # six layers: as much as 2,063 positions of its K and V
    assert model.state_bytes_per_row(CFG) == 6 * (4_194_304 + 30_720)
    rows, context = 64.0, 300.0
    params = 6 * s["layer_params"] + s["head_params"]
    ops, byts = model.decode_step(CFG, rows, context)
    state = 2 * rows * model.state_bytes_per_row(CFG)
    assert byts == pytest.approx(2 * params + rows * context * 12288 + state)
    assert 3.2e9 < state < 3.3e9 and 7.8e9 < 2 * params < 7.9e9
    assert 0.27 < state / byts < 0.30            # the state's share of a step
    assert ops == pytest.approx(
        (2 * params + 4 * 6 * 20 * 128 * context + 5 * 6 * 1_048_576) * rows)
    least = model.least_seconds(ops, byts, CFG, PEAKS)
    assert least == pytest.approx(byts / 819e9)  # bound by the bytes
    assert 0.0135 < least < 0.0142


def test_a_prefill_execution_counts_its_products_and_the_chunked_scan():
    model = cost_model.for_config(CFG)
    s = model.shapes(CFG)
    ops, byts = model.prefill(CFG, 512, 185.0, 1)
    scan = 6 * 512 * (128 * (512 + 4096) + 4 * 1_048_576)
    assert ops == pytest.approx(2 * 6 * s["layer_params"] * 512
                                + 2 * 6 * 20 * 128 * 512 * 185 + scan)
    assert scan / ops < 0.01  # the chunked form, beside the matrix products
    assert byts == pytest.approx(2 * (6 * s["layer_params"] + s["head_params"])
                                 + 2 * model.state_bytes_per_row(CFG))
    # 512 rows are bound by their operations, 128 by the weights' bytes
    assert ops / PEAKS["bf16_flops"] > byts / PEAKS["hbm_bytes_per_s"]
    ops, byts = model.prefill(CFG, 128, 185.0, 1)
    assert ops / PEAKS["bf16_flops"] < byts / PEAKS["hbm_bytes_per_s"]


# ---- the two readers ----------------------------------------------------------------


def scrape(live, stepped, state=1_622_999_040, full=1_610_612_736) -> dict:
    return {E + "ssm_state_rows_live_total": live,
            E + "ssm_state_rows_stepped_total": stepped,
            E + "kv_cache_state_bytes": state,
            E + "kv_cache_full_bytes": full,
            E + "kv_cache_window_bytes": 0, E + "kv_cache_index_bytes": 0}


SEEN = {"m0": scrape(1_000, 2_000), "m1": scrape(10_000, 12_000)}
# the parent's scrape has none of the families; an engine whose spec has no
# mixer has neither the counters nor the gauge
ABSENT = {"m0": {E + "decode_chunks_total": 10.0,
                 E + "kv_cache_full_bytes": 5},
          "m1": {E + "decode_chunks_total": 30.0,
                 E + "kv_cache_full_bytes": 5}}
AT_REST = {"m0": scrape(7, 9), "m1": scrape(7, 9, state=0)}


@pytest.mark.parametrize("name,want", [
    ("kv_state_share", 100.0 * 1_622_999_040 / 3_233_611_776),
    ("ssm_state_live_share", 90.0)])
def test_reader_reads_the_engine_s_counters(name, want):
    assert traced.load_reader(name).read(dict(SEEN)) == pytest.approx(want)


@pytest.mark.parametrize("art", [ABSENT, AT_REST, {"m0": {}, "m1": {}}],
                         ids=["parent", "at_rest", "lost"])
@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_there_is_nothing(name, art):
    """On the parent commit, whose program has no such counter, a reader
    returns None and does not raise: the line leaves the metric out."""
    assert traced.load_reader(name).read(dict(art)) is None


def test_the_new_metrics_are_the_cells_own():
    mine = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in mine)
    assert {m["name"]: (m["layer"], m["moves"]) for m in mine} == {
        "kv_state_share": ("KV manager", "tpot_p50_ms"),
        "ssm_state_live_share": ("model step", "tpot_p50_ms")}
    assert all(os.path.isfile(os.path.join(
        BENCH_DIR, "layer_metrics", name + ".py")) for name in NEW)
