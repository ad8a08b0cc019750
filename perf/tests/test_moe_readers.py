"""The MoE cell's readers and work functions, on values small enough to check by hand."""

import json
import os
import sys
import types

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

import scope_times  # noqa: E402
from readers import counters  # noqa: E402
from work import olmoe  # noqa: E402

with open(os.path.join(PERF, "configs", "olmoe-1b-7b-int8.json")) as f:
    CONFIG = json.load(f)


def scrape(calls, rows, pairs, touched, biggest, pages=10.0):
    lines = [f'seldon_llm_moe_{k}_total{{model="m",program="decode"}} {v}' for k, v in (
        ("calls", calls), ("layer_calls", calls * 16), ("live_rows", rows),
        ("routed_pairs", pairs), ("experts_touched", touched), ("max_group", biggest))]
    lines += ['seldon_llm_moe_calls_total{model="m",program="chunk"} 0',
              f"seldon_llm_kv_pages_in_use {pages}"]
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(params=None):
    # 100 decode steps of 24 live rows between the scrapes: 8 experts a row, 16 layers
    scrapes = [(0.0, scrape(10, 240, 30720, 9000, 1000)),
               (1.0, scrape(110, 2640, 337920, 105000, 11000))]
    return types.SimpleNamespace(scrapes=scrapes, params=params or {}, config=CONFIG)


def test_counter_ratio_reads_the_window_and_the_label():
    touched = counters.ratio(ctx({"over": "seldon_llm_moe_experts_touched_total",
                                  "under": "seldon_llm_moe_layer_calls_total",
                                  "label": 'program="decode"'}))
    assert touched == (105000 - 9000) / (100 * 16) == 60.0
    skew = counters.ratio(ctx({"over": "seldon_llm_moe_max_group_total",
                               "under": "seldon_llm_moe_routed_pairs_total",
                               "label": 'program="decode"', "scale_by": "num_experts"}))
    assert abs(skew - 10000 * 64 / 307200) < 1e-12
    # a program older than the counter: nothing to read, and no raise
    assert counters.ratio(ctx({"over": "seldon_llm_nope_total",
                               "under": "seldon_llm_moe_layer_calls_total"})) is None
    assert counters.ratio(ctx({"over": "seldon_llm_moe_experts_touched_total",
                               "under": "seldon_llm_moe_layer_calls_total",
                               "label": 'program="chunk"'})) is None


def test_expert_bytes_follow_the_experts_touched_not_the_stack():
    one = olmoe.expert_bytes(CONFIG)
    assert olmoe.expert_params(CONFIG) == 3 * 2048 * 1024
    assert one == 3 * 2048 * 1024 + 4 * (1024 + 1024 + 2048)
    need = olmoe.moe_ffn_decode(ctx())
    # per call: 960 experts touched over the 16 layers (60 a layer), 3072 pairs
    assert need["bytes"] == 960 * one + 3072 * 2 * 2 * 2048
    assert need["flops"] == 2.0 * 3072 * 3 * 2048 * 1024
    assert need["bytes"] < 16 * 64 * one            # never 64 experts always
    step = olmoe.decode_step(ctx())
    assert step["bytes"] > need["bytes"] + 640 * 131072   # + attention, head, 640 live KV tokens
    assert olmoe.moe_ffn_chunk(ctx()) is None        # no chunk ran between the scrapes


def test_marks_and_self_time():
    marks = [scope_times.fold(m) for m in ("moe.experts", "ragged_dot")]
    assert scope_times.marked("%ragged_dot_none.3 = f32[256,1024] custom-call(...)", [], marks)
    assert scope_times.marked("%fusion.7 = bf16[256,2048] fusion(...)",
                              ["jit(decode_step)/layer_3/moe/moe.experts/mul"], marks)
    assert not scope_times.marked("%fusion.9 = f32[32,64] fusion(...)",
                                  ["jit(decode_step)/layer_3/moe/moe.route/top_k"], marks)
    # a marked loop [0, 10) holding an unmarked op [2, 5) and a marked one [6, 9):
    # own times 4, 3, 3, and the marked ones sum to 7 of the 10
    own = scope_times.self_seconds([(0.0, 10.0, True), (2.0, 5.0, False), (6.0, 9.0, True)])
    assert sorted(own) == [(0.0, 4.0, True), (2.0, 3.0, False), (6.0, 3.0, True)]


def test_the_cell_schedule_is_a_pure_function_of_the_seed():
    """test_harness.py's check of the same name, for this cell: that file maps
    plane names through a closed dict (`plan_of`) which a PR may not edit, so
    its parametrised case for this cell cannot find `llm_rest_reference`
    (PERF.md section 7).  The plane's requests are llm_rest's own."""
    import numpy as np
    from planes import llm_rest, llm_rest_reference
    from traffic import draw, open_loop

    assert issubclass(llm_rest_reference.Plane, llm_rest.Plane)
    assert llm_rest_reference.Plane.make_request is llm_rest.Plane.make_request
    with open(os.path.join(PERF, "workloads", "olmoe-chat-steady.json")) as f:
        cell = json.load(f)

    def plan(seed):
        run = types.SimpleNamespace(cell=cell, config=CONFIG, seed=seed, free_port=lambda: 1,
                                    rngs=draw.streams(seed), out_dir="/nonexistent")
        plane = llm_rest_reference.Plane(run)
        return open_loop.build(cell["traffic"], plane.make_request, run.rngs, [2.0, 8.0, 2.0])

    a, b, other = plan(7), plan(7), plan(8)
    assert len(a) == len(b) > 0
    assert [x["request"]["body"] for x in a] == [x["request"]["body"] for x in b]
    assert [x["due"] for x in a] == [x["due"] for x in b]
    assert [x["request"]["body"] for x in a] != [x["request"]["body"] for x in other][:len(a)]
    sizes = np.array([x["sizes"]["prompt_tokens"] for x in a])
    assert sizes.min() >= 32 and sizes.max() <= 512
