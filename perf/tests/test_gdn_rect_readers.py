"""work/olmo_hybrid.py's counts at the published sizes of
configs/olmo-hybrid-7b-int8.json, on values small enough to check by hand, and
the two metric files this configuration brought (`gdn_state_fill`,
`decode_step_roofline_throughput`) on the accepted readers."""

import json
import os
import sys
import types

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

from readers import scrape as scrape_reader  # noqa: E402
from work import olmo_hybrid as work  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "olmo-hybrid-7b-int8.json")
CELL = load("workloads", "olmohybrid-evalgen-decode.json")


def scrape(calls, rows, layers=24, pages=None, matrix=None, tiled=None):
    lines = [f'seldon_llm_gdn_rows_total{{model="m",program="decode"}} {rows}',
             f'seldon_llm_gdn_layer_calls_total{{model="m",program="decode"}} {calls * layers}',
             f'seldon_llm_gdn_rows_total{{model="m",program="chunk"}} {rows * 8}',
             f'seldon_llm_gdn_layer_calls_total{{model="m",program="chunk"}} {calls * layers}']
    if pages is not None:
        lines.append(f'seldon_llm_kv_pages_in_use{{model="m"}} {pages}')
    if matrix is not None:
        lines += [f'seldon_llm_state_matrix_bytes{{model="m"}} {matrix}',
                  f'seldon_llm_state_matrix_tiled_bytes{{model="m"}} {tiled}']
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(**gauges):
    # 100 decode steps of 32 live rows between the window's two end scrapes
    first, last = scrape(0, 0, **gauges), scrape(100, 3200, **gauges)
    return types.SimpleNamespace(
        config=CONFIG, cell=CELL, params={}, scrapes=[(0.0, first), (1.0, last)],
        window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0), records=[])


def test_the_model_is_seven_and_a_half_billion_parameters_as_published():
    lin, n = work.linear_params(CONFIG), work.kinds(CONFIG)
    assert n == {"gdn": 24, "attention": 8, "ffn": 32}
    # W_q, W_k [3840, 2880], W_v, W_g [3840, 5760], W_b, W_a [3840, 30], W_o [5760, 3840]
    assert lin["gdn"] == 3840 * (2 * 2880 + 2 * 5760) + 3840 * 60 + 5760 * 3840 == 88_704_000
    assert lin["attention"] == 4 * 3840 * 3840 == 58_982_400
    assert lin["ffn"] == 3 * 3840 * 11008 == 126_812_160
    assert lin["head"] == 3840 * 100352 == 385_351_680
    total = work.params_total(CONFIG)
    assert total == 24 * (88_704_000 + 126_812_160) + 8 * (58_982_400 + 126_812_160) + 2 * 385_351_680
    assert round(total / 1e9, 2) == 7.43
    # the catalog's "208 M a layer and 771 M": the mean layer and the table + head
    assert round((total - 2 * lin["head"]) / 32 / 1e6) == 208 and round(2 * lin["head"] / 1e6) == 771


def test_the_matrix_state_is_counted_as_the_model_holds_it_whatever_the_layout():
    # a slot a layer, once each way: 2 x 30 x 96 x 192 x 4 B
    assert work.matrix_state_bytes(CONFIG, 1) == 2 * 30 * 96 * 192 * 4 == 4_423_680
    # 24 layers a slot, held once: 53.1 MB of float32 S, and the conv rows' 1.66 MB
    assert 24 * work.gdn_dims(CONFIG)["state"] * 4 == 53_084_160
    assert work.state_bytes(CONFIG, 1) - work.matrix_state_bytes(CONFIG, 1) == 2 * 3 * 11520 * 2
    seen = work.gdn_state(ctx())
    assert seen["bytes"] == 24 * 32 * 4_423_680          # 32 live rows a step, 24 layers
    assert seen["flops"] == 24 * 32 * 6 * 30 * 96 * 192
    chunk = work.gdn_chunk(ctx())
    assert chunk["bytes"] > 24 * (88_704_000 + 4_423_680)    # the weights and one slot's S a layer


def test_the_whole_step_counts_weights_cache_and_state_once():
    got = work.decode_step(ctx(pages=300))
    weights = work.linear_bytes(CONFIG)
    # int8 + scales + taps; of the table a step reads its 32 rows alone (385 MB less)
    assert 7.43e9 - 385_351_680 < weights < 7.43e9 - 385_351_680 + 2e7
    kv = 8 * 300 * 64 * 2 * 30 * 128 * 2                 # 8 attention layers, 300 live pages
    assert got["bytes"] == weights + 32 * 3840 + 24 * work.state_bytes(CONFIG, 32) + kv
    assert work.decode_step(ctx()) is None               # no page gauge: nothing to read


def test_the_fill_reads_the_two_gauges_and_nothing_on_a_program_without_them():
    spec = load("layer_metrics", "gdn_state_fill.json")
    assert spec["reader"] == "scrape:gauge_share" and spec["params"]["scale"] == 1.0
    own = 24 * 32 * 30 * 96 * 192 * 4
    full = ctx(matrix=own, tiled=own)
    full.params = spec["params"]
    assert scrape_reader.gauge_share(full) == 1.0
    padded = ctx(matrix=own, tiled=own * 4 // 3)         # [.., 96, 192] held a head a row
    padded.params = spec["params"]
    assert scrape_reader.gauge_share(padded) == 0.75
    parent = ctx()                                       # the parent has no such gauge
    parent.params = spec["params"]
    assert scrape_reader.gauge_share(parent) is None
    twin = load("layer_metrics", "decode_step_roofline_throughput.json")
    accepted = load("layer_metrics", "decode_step_roofline.json")
    assert twin["reader"] == accepted["reader"] and twin["params"] == accepted["params"]
    assert twin["moves"] == "throughput" and "olmohybrid-evalgen-decode" == CELL["name"]
    assert {"gdn_state_fill", "decode_step_roofline_throughput"} <= set(CELL["metrics"]["per_layer"])
