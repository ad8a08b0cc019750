"""work/smallthinker.py's counts at the published sizes of
configs/smallthinker-21b-a3b-int8.json, on values small enough to check by
hand, and the six metric files this configuration brought on the readers they
name (readers/labelled.py is new with them: a series picked by two labels)."""

import json
import os
import sys
import types

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

from readers import labelled  # noqa: E402
from work import smallthinker as work  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "smallthinker-21b-a3b-int8.json")
CELL = load("workloads", "smallthinker-longqa-mixed.json")
BENCH = load("..", "BENCHMARK.json")
NEW = ("swa_step_attn_ms", "swa_step_attn_roofline", "swa_chunk_attn_ms", "gqa_decode_attn_ms",
       "swa_rows_read_share", "kv_window_pool_live")


def series(name, value, **labels):
    # the exposition sorts a series' labels by name
    inner = ",".join(f'{k}="{v}"' for k, v in sorted({"deployment_name": "", **labels}.items()))
    return f"{name}{{{inner}}} {value}"


def scrape(calls, window_pages=None, labelled_series=True):
    """`calls` decode steps of 24 live rows at a mean context of 8,192: a full
    layer reads 8,192 rows a slot, a window layer 4,096, its kernel 5,120."""
    kind = {"kind": "full"} if labelled_series else {}
    lines = [series("seldon_llm_attn_calls_total", calls, program="decode", **kind),
             series("seldon_llm_attn_context_tokens_total", calls * 24 * 8192, program="decode", **kind),
             series("seldon_llm_moe_calls_total", calls, program="decode"),
             series("seldon_llm_moe_live_rows_total", calls * 24, program="decode"),
             series("seldon_llm_moe_routed_pairs_total", calls * 24 * 6 * 16, program="decode"),
             series("seldon_llm_moe_experts_touched_total", calls * 16 * 58, program="decode")]
    if labelled_series:
        lines += [
            series("seldon_llm_attn_calls_total", calls, program="decode", kind="window"),
            series("seldon_llm_attn_context_tokens_total", calls * 24 * 4096, program="decode", kind="window"),
            series("seldon_llm_attn_rows_read_total", calls * 24 * 5120, program="decode", kind="window"),
            series("seldon_llm_attn_context_tokens_unwindowed_total", calls * 24 * 8192,
                   program="decode", kind="window"),
            series("seldon_llm_attn_rows_read_total", calls * 24 * 9216, program="chunk", kind="window")]
    if window_pages is not None:
        lines += [series("seldon_llm_kv_pages_in_use", 3000, **{"class": "full"}),
                  series("seldon_llm_kv_pages_total", 6146, **{"class": "full"}),
                  series("seldon_llm_kv_pages_in_use", window_pages, **{"class": "window"}),
                  series("seldon_llm_kv_pages_total", 1778, **{"class": "window"})]
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(params=None, **kw):
    first, last = scrape(0, **kw), scrape(100, **kw)
    return types.SimpleNamespace(
        config=CONFIG, cell=CELL, params=params or {}, scrapes=[(0.0, first), (1.0, last)],
        window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0), records=[],
        run=types.SimpleNamespace(note=lambda *_: None, hop_identity_noted=True))


def test_the_cut_is_the_issues_arithmetic():
    lin, n = work.linear_params(CONFIG), work.kinds(CONFIG)
    assert n == {"window": 9, "full": 3}        # three whole periods of [0, 1, 1, 1]
    # attention 2 x 2560 x 3584 + 2 x 2560 x 512 and the router's 2560 x 64
    assert lin["layer"] == 2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64 == 21_135_360
    assert work.expert_params(CONFIG) == 3 * 2560 * 768 == 5_898_240
    layer = lin["layer"] + 64 * work.expert_params(CONFIG)
    assert round(layer / 1e6, 1) == 398.6
    # int8, a byte a weight: ISSUE 49's 7.16 GB at 16 layers, 5.56 GB at the 12 served
    assert round((16 * layer + 2 * lin["head"]) / 1e9, 2) == 7.16
    assert round((CONFIG["num_hidden_layers"] * layer + 2 * lin["head"]) / 1e9, 2) == 5.56
    assert work.kv_row_bytes(CONFIG) == 2048                            # K + V, 4 x 128 bf16 each


def test_the_window_reads_bytes_are_the_rows_inside_the_windows_once():
    seen = work.swa_step_attn(ctx())
    # 9 window layers x (24 slots x 4,096 rows read + 24 rows written) x 2,048 B
    assert seen["bytes"] == 9 * (24 * 4096 + 24) * 2048
    assert seen["flops"] == 9 * 4.0 * 28 * 128 * 24 * 4096
    step = work.decode_step(ctx())
    experts = work.expert_ffn_cost(CONFIG, 16 * 58, 24 * 6 * 16)
    kv = (3 * 24 * 8192 + 9 * 24 * 4096) * 2048
    assert step["bytes"] == work.linear_bytes(CONFIG) + experts["bytes"] + 24 * 2560 + kv
    # a program without the kind label (the parent): nothing to read, no raise
    assert work.swa_step_attn(ctx(labelled_series=False)) is None
    assert work.decode_step(ctx(labelled_series=False)) is None


def test_the_new_metric_files_read_what_they_say_and_nothing_on_the_parent():
    share = load("layer_metrics", "swa_rows_read_share.json")
    assert share["reader"] == "labelled:ratio"
    assert labelled.ratio(ctx(share["params"])) == 5120 / 8192      # decode alone, not the chunks'
    assert labelled.ratio(ctx(share["params"], labelled_series=False)) is None
    live = load("layer_metrics", "kv_window_pool_live.json")
    assert live["reader"] == "labelled:gauge_share"
    assert labelled.gauge_share(ctx(live["params"], window_pages=889)) == 50.0
    assert labelled.gauge_share(ctx(live["params"])) is None
    for name, program, scope in (("swa_step_attn_ms", "decode_step", "attn.window"),
                                 ("swa_chunk_attn_ms", "prefill_chunk", "attn.window"),
                                 ("gqa_decode_attn_ms", "decode_step", "attn.gqa")):
        spec = load("layer_metrics", name + ".json")
        assert spec["reader"] == "hlo_scopes:per_call_ms"
        assert spec["params"] == {"program": program, "scopes": [scope]}
    roofline = load("layer_metrics", "swa_step_attn_roofline.json")
    assert roofline["reader"] == "hlo_scopes:roofline" and roofline["params"]["work"] == "swa_step_attn"
    assert hasattr(work, roofline["params"]["work"]) and roofline["params"]["bound"] == "hbm"


def test_the_cell_and_the_benchmarks_entries_agree():
    assert set(NEW) <= set(CELL["metrics"]["per_layer"])
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in CELL["metrics"]["per_layer"]:
        assert CELL["name"] in listed[name]["workloads"], name
        assert listed[name]["moves"] == "throughput"
        spec = load("layer_metrics", name + ".json")
        assert (spec["unit"], spec["layer"]) == (listed[name]["unit"], listed[name]["layer"]), name
    for name in NEW:
        assert listed[name]["workloads"] == [CELL["name"]]
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL["name"]]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG["name"], CELL["traffic_mix"], 1)
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]]
    assert config["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout"]
    # every width as published; the layouts cut with the depth, whole periods
    for key, value in (("hidden_size", 2560), ("moe_ffn_hidden_size", 768), ("head_dim", 128),
                       ("num_attention_heads", 28), ("num_key_value_heads", 4),
                       ("moe_num_primary_experts", 64), ("moe_num_active_primary_experts", 6),
                       ("sliding_window_size", 4096), ("max_position_embeddings", 16384),
                       ("vocab_size", 151936)):
        assert CONFIG[key] == value, key
    assert CONFIG["sliding_window_layout"] == CONFIG["rope_layout"] == [0, 1, 1, 1] * 3
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 12
