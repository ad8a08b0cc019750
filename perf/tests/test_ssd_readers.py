"""work/granite_hybrid.py's counts at the published sizes of
configs/granite-4.0-h-micro-int8.json, on values small enough to check by hand,
and the four metric files this configuration brought (`ssd_step_ms`,
`ssd_chunk_ms`, `ssd_chunk_roofline`, `ssd_state_roofline`) on the accepted
readers (`hlo_scopes:per_call_ms`, `hlo_scopes:roofline`)."""

import json
import os
import sys
import types

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
sys.path.insert(0, PERF)

from readers import hlo_scopes  # noqa: E402
from work import granite_hybrid as work  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "granite-4.0-h-micro-int8.json")
CELL = load("workloads", "granite4h-sessions-decode.json")
NEW = ("ssd_step_ms", "ssd_chunk_ms", "ssd_chunk_roofline", "ssd_state_roofline")


def scrape(calls, rows, layers=36, pages=None):
    lines = [f'seldon_llm_ssd_rows_total{{model="m",program="decode"}} {rows}',
             f'seldon_llm_ssd_layer_calls_total{{model="m",program="decode"}} {calls * layers}',
             f'seldon_llm_ssd_rows_total{{model="m",program="chunk"}} {rows * 2}',
             f'seldon_llm_ssd_layer_calls_total{{model="m",program="chunk"}} {calls * layers}']
    if pages is not None:
        lines.append(f'seldon_llm_kv_pages_in_use{{model="m"}} {pages}')
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(**gauges):
    # 100 decode steps of 96 live rows between the window's two end scrapes
    first, last = scrape(0, 0, **gauges), scrape(100, 9600, **gauges)
    return types.SimpleNamespace(
        config=CONFIG, cell=CELL, params={}, scrapes=[(0.0, first), (1.0, last)],
        window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0), records=[])


def test_the_file_holds_every_number_of_the_catalogs_config_under_its_own_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip(f"no catalog at {catalog}: the file's keys were NOT compared with it")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "granite-4.0-h-micro")
    assert CONFIG["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if CONFIG.get(k, "absent") != v}
    assert differ == set(CONFIG["reduced"]) == {"max_position_embeddings"}
    assert CONFIG["max_position_embeddings"] == CELL["server"]["continuous_batching_max_len"] == 2048
    # the program's words for two of the catalog's: the kinds of layer, and no position
    assert CONFIG["layer_kinds"] == ["mamba" if k == "mamba" else "full_attention"
                                     for k in CONFIG["layer_types"]]
    assert CONFIG["position_embedding_type"] == "nope" and CONFIG["position_rope_theta"] is None


def test_the_model_is_three_billion_parameters_as_published_and_the_table_is_held_once():
    lin, n = work.linear_params(CONFIG), work.kinds(CONFIG)
    assert n == {"ssd": 36, "attention": 4, "ffn": 40}
    # W_in [2048, 4096 + 4352 + 64] and W_out [4096, 2048]
    assert lin["ssd"] == 2048 * 8512 + 4096 * 2048 == 25_821_184
    # W_q, W_o [2048, 2048]; W_k, W_v [2048, 512]
    assert lin["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert lin["ffn"] == 2048 * 16384 + 8192 * 2048 == 50_331_648
    assert lin["head"] == 2048 * 100352 == 205_520_896
    total = work.params_total(CONFIG)
    assert total == 36 * (25_821_184 + 50_331_648) + 4 * (10_485_760 + 50_331_648) + 205_520_896
    assert round(total / 1e9, 2) == 3.19
    # int8 + a float32 scale a channel + the float32 small leaves: 3.2 GB
    assert total < work.linear_bytes(CONFIG) < total + 1e7


def test_the_state_is_two_megabytes_a_slot_a_layer_and_76_megabytes_a_slot():
    dims = work.ssd_dims(CONFIG)
    assert dims == {"inner": 4096, "channels": 4352, "state": 64 * 64 * 128}
    assert dims["state"] * 4 == 2_097_152
    # a slot a layer, once each way
    assert work.matrix_state_bytes(CONFIG, 1) == 2 * 2_097_152
    assert work.state_bytes(CONFIG, 1) - work.matrix_state_bytes(CONFIG, 1) == 2 * 3 * 4352 * 2
    # what a slot keeps over the 36 layers, whatever its length: 76.4 MB
    held = 36 * work.state_bytes(CONFIG, 1) / 2
    assert held == 36 * (2_097_152 + 3 * 4352 * 2) == 76_437_504
    seen = work.ssd_state(ctx())
    assert seen["bytes"] == 2 * 96 * 36 * 2_097_152          # 96 live rows a step, 36 layers: 14.5 GB
    assert round(seen["bytes"] / 1e9, 1) == 14.5
    assert seen["flops"] == 36 * 96 * 6 * 64 * 64 * 128
    chunk = work.ssd_chunk(ctx())
    assert chunk["bytes"] > 36 * (25_821_184 + 2 * 2_097_152)   # the weights and one slot's h a layer
    assert chunk["flops"] > 36 * 192 * 2 * 25_821_184           # 192 live rows a chunk here


def test_the_whole_step_counts_weights_cache_and_state_once():
    got = work.decode_step(ctx(pages=1500))
    weights = work.linear_bytes(CONFIG)
    kv = 4 * 1500 * 64 * 2 * 8 * 64 * 2                  # 4 attention layers, 1,500 live pages
    assert got["bytes"] == weights + 96 * 2048 + 36 * work.state_bytes(CONFIG, 96) + kv
    # four fifths of it are the state
    assert 0.76 < work.ssd_state(ctx())["bytes"] / got["bytes"] < 0.82
    assert work.decode_step(ctx()) is None               # no page gauge: nothing to read
    empty = types.SimpleNamespace(config=CONFIG, cell=CELL, params={}, scrapes=[], records=[],
                                  window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0))
    assert work.ssd_state(empty) is None and work.ssd_chunk(empty) is None


def test_the_four_metric_files_stand_on_the_accepted_readers_and_the_cell_lists_them():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec = load("layer_metrics", name + ".json")
        module, function = spec["reader"].split(":")
        assert module == "hlo_scopes" and callable(getattr(hlo_scopes, function))
        assert spec["moves"] == "throughput" == listed[name]["moves"]
        assert listed[name]["workloads"] == [CELL["name"]] and listed[name]["unit"] == spec["unit"]
        assert all(scope.startswith("mix.ssd") for scope in spec["params"]["scopes"])
        if function == "roofline":
            assert callable(getattr(work, spec["params"]["work"]))
    assert load("layer_metrics", "ssd_state_roofline.json")["params"] == {
        "program": "decode_step", "scopes": ["mix.ssd.rule"], "work": "ssd_state", "bound": "hbm"}
    # a program without the scope, or a run without a trace: nothing to read, nothing raised
    bare = ctx()
    bare.trace, bare.params = None, load("layer_metrics", "ssd_step_ms.json")["params"]
    assert hlo_scopes.per_call_ms(bare) is None
    # every per-layer metric of the cell's list that BENCHMARK.json knows names the cell
    for name in CELL["metrics"]["per_layer"]:
        assert CELL["name"] in listed[name]["workloads"], name
    assert CELL["name"] in next(m for m in bench["end_to_end"] if m["name"] == "throughput")["workloads"]


def test_the_cells_traffic_is_the_issues_to_the_letter():
    traffic = CELL["traffic"]
    assert (traffic["generator"], traffic["clients"], traffic["requests"], traffic["stream"]) == (
        "closed_loop", 96, 384, False)
    assert traffic["request"] == {"prompt_tokens": {"dist": "uniform", "min": 64, "max": 512},
                                  "output_tokens": {"dist": "uniform", "min": 256, "max": 768}}
    assert (CELL["ramp_s"], CELL["tail_s"], CELL["drain_s"], CELL["trace_s"]) == (10, 0, 30, 2)
    assert CELL["probe"] == {"prompt_tokens": 258, "output_tokens": 8}
    assert CELL["server"] == {"continuous_batching": 96, "continuous_batching_max_len": 2048}
    assert CELL["metrics"]["end_to_end"] == ["throughput", "setup_s"]


def test_every_limit_of_the_file_is_one_the_plane_judges_by_and_the_states_probe_is_long():
    assert CONFIG["plane"] == "llm_rest_state_reference"
    assert set(CONFIG["reference_tolerance"]) == {"atol_over_scale", "state_rtol", "why"}
    # the state's probe: past a chunk's edge with padded rows behind it, then
    # steps of the kernel for over twice as long as the judged heads remember at least
    probe = CELL["state_probe"]
    assert probe["prompt_tokens"] % 256 and probe["prompt_tokens"] > 256
    assert probe["output_tokens"] >= 3 * probe["carried_tokens"] >= 300
    assert probe["prompt_tokens"] <= max(CONFIG["server"]["len_buckets"])
    assert probe["output_tokens"] <= CONFIG["server"]["max_new_tokens"]


def test_a_heads_distance_is_a_share_of_that_heads_own_size():
    import numpy as np

    from planes.llm_rest_state_reference import by_head

    want = np.stack([np.full((4, 8), 2.0), np.full((4, 8), 100.0), np.eye(4, 8)])
    got = want.copy()
    got[0] += 0.02          # 1 % of every value of a small head
    got[1, 0, 0] += 1.0     # one value of a large one: 1 / (100 sqrt(32))
    np.testing.assert_allclose(by_head(got, want), [0.01, 1 / (100 * 32 ** 0.5), 0.0], rtol=1e-12)
