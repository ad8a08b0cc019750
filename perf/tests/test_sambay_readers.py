"""work/sambay.py's counts at the published sizes of
configs/phi-4-mini-flash-reasoning-int8.json, on values small enough to check by
hand, and the six metric files this configuration brought (`xattn_step_roofline`,
`xattn_step_ms`, `s6_chunk_roofline`, `s6_chunk_ms`, `s6_step_ms`,
`cross_rows_share`) on the accepted readers (`hlo_scopes:per_call_ms`,
`hlo_scopes:roofline`, `labelled:ratio`)."""

import json
import os
import sys
import types

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
sys.path.insert(0, PERF)

from readers import hlo_scopes, labelled  # noqa: E402
from work import sambay as work  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "phi-4-mini-flash-reasoning-int8.json")
CELL = load("workloads", "phi4flash-longtrace-decode.json")
NEW = ("xattn_step_roofline", "xattn_step_ms", "s6_chunk_roofline", "s6_chunk_ms", "s6_step_ms",
       "cross_rows_share")
ROW = 5120          # a cached token of the ONE pool: 2 x 20 x 64 x 2 B


def scrape(calls, live=32, context=8600, chunk_rows=1024, lasts=0.125):
    """What the counters read after `calls` decode steps of `live` slots at a mean
    context of `context` rows each, and as many chunks of `chunk_rows` rows of which
    the share `lasts` were a prompt's last."""
    def line(name, program, value, **labels):
        more = "".join(f',{k}="{v}"' for k, v in labels.items())
        return f'{name}{{model="m",program="{program}"{more}}} {value}'

    rows = calls * live
    lines = [line("seldon_llm_s6_rows_total", "decode", rows),
             line("seldon_llm_s6_layer_calls_total", "decode", calls * 9),
             line("seldon_llm_s6_rows_total", "chunk", calls * chunk_rows),
             line("seldon_llm_s6_layer_calls_total", "chunk", calls * 9),
             line("seldon_llm_self_decoder_rows_total", "decode", rows),
             line("seldon_llm_cross_decoder_rows_total", "decode", rows),
             line("seldon_llm_self_decoder_rows_total", "chunk", calls * chunk_rows),
             line("seldon_llm_cross_decoder_rows_total", "chunk", calls * lasts)]
    for kind, seen in (("full", context), ("shared", context), ("window", 512)):
        lines.append(line("seldon_llm_attn_context_tokens_total", "decode", rows * seen,
                          kind=kind, form="absorbed"))
        lines.append(line("seldon_llm_attn_calls_total", "decode", calls, kind=kind,
                          form="absorbed"))
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(**seen):
    first, last = scrape(0, **seen), scrape(100, **seen)
    return types.SimpleNamespace(
        config=CONFIG, cell=CELL, params={}, scrapes=[(0.0, first), (1.0, last)],
        window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0), records=[])


def test_the_file_holds_every_number_of_the_catalogs_config_under_its_own_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip(f"no catalog at {catalog}: the file's keys were NOT compared with it")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if CONFIG.get(k, "absent") != v}
    assert differ == set(CONFIG["reduced"]) == {"max_position_embeddings"}
    assert CONFIG["max_position_embeddings"] == CELL["server"]["continuous_batching_max_len"] == 16384
    # the plan, spelled out: 32 entries in the program's words
    kinds = CONFIG["layer_types"]
    assert len(kinds) == CONFIG["num_hidden_layers"] == 32
    assert [kinds[i] for i in (0, 1, 16, 17, 18, 19)] == [
        "s6", "sliding_attention", "s6", "full_attention", "gmu", "cross_attention"]
    assert (CONFIG["memory_source"], CONFIG["kv_source"]) == (16, 17)
    assert CONFIG["position_rope_theta"] is None and CONFIG["state_dtype"] == "float32"
    assert CONFIG["mamba_d_inner"] == CONFIG["mamba_expand"] * CONFIG["hidden_size"] == 5120
    assert CONFIG["mamba_dt_rank"] == -(-CONFIG["hidden_size"] // 16) == 160


def test_the_model_is_3_85_billion_parameters_and_the_table_is_held_once():
    lin, n = work.linear_params(CONFIG), work.kinds(CONFIG)
    assert n == {"s6": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7, "ffn": 32}
    assert lin["s6"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 == 41_123_840
    assert lin["window"] == lin["full"] == 2 * 2560 * 2560 + 2 * 2560 * 1280 == 19_660_800
    assert lin["cross"] == 2 * 2560 * 2560 == 13_107_200          # queries and output only
    assert lin["gmu"] == 2 * 2560 * 5120 == 26_214_400
    assert lin["ffn"] == 2560 * 20480 + 10240 * 2560 == 78_643_200
    assert lin["head"] == 2560 * 200064 == 512_163_840
    total = work.params_total(CONFIG)
    assert total == (32 * 78_643_200 + 9 * 41_123_840 + 9 * 19_660_800 + 7 * 13_107_200
                     + 7 * 26_214_400 + 512_163_840)
    assert round(total / 1e9, 2) == 3.85
    every = work.SELF_DECODER + work.CROSS_DECODER
    held = work.linear_bytes(CONFIG, every) + work.head_bytes(CONFIG)
    assert total < held < total + 2e7       # int8 + a float32 scale a channel + the small leaves
    # a prompt's row: 18 layers; all 32 would cost 1.7 x as much
    assert round(2 * work.per_token_linear(CONFIG, work.SELF_DECODER) / 1e9, 1) == 3.9
    assert round(2 * work.per_token_linear(CONFIG, every) / 1e9, 1) == 6.7


def test_a_token_is_5120_bytes_in_one_pool_and_a_slot_3_23_megabytes_of_state():
    from work.lfm2 import kv_row_bytes

    assert kv_row_bytes(CONFIG) == ROW
    dims = work.s6_dims(CONFIG)
    assert dims == {"inner": 5120, "rank": 160, "states": 16, "state": 5120 * 16}
    assert work.matrix_state_bytes(CONFIG, 1) == 2 * 5120 * 16 * 4
    # what a slot keeps over the 9 layers, whatever its length: 3.23 MB
    held = 9 * work.state_bytes(CONFIG, 1) / 2
    assert held == 9 * (5120 * 16 * 4 + 3 * 5120 * 2) == 3_225_600


def test_the_shared_pool_is_read_eight_times_a_step_and_is_seven_tenths_of_its_bytes():
    shared = work.shared_kv_step(ctx())
    # 32 live rows x 8,600 rows of context x 5,120 B x (the full layer + 7 cross layers)
    # + the step's 32 rows written once
    assert shared["bytes"] == (32 * 8600 * 8 + 32) * ROW
    assert round(shared["bytes"] / 1e9, 1) == 11.3
    assert shared["flops"] == 4 * 40 * 64 * 32 * 8600 * 8      # the model's pairs, not the padded form's
    step = work.decode_step(ctx())
    assert 0.68 < shared["bytes"] / step["bytes"] < 0.72
    # (the window layers' read, for the accepted `swa_step_attn_roofline` once a
    # `benchmark` PR appends this cell to its list)
    window = work.swa_step_attn(ctx())
    assert window["bytes"] == 8 * (32 * 512 + 32) * ROW
    state = work.s6_state(ctx())
    assert state["bytes"] == 9 * 2 * 32 * 5120 * 16 * 4
    assert step["bytes"] == (work.linear_bytes(CONFIG, work.SELF_DECODER + work.CROSS_DECODER)
                             + work.head_bytes(CONFIG) + 32 * 2560 + 9 * work.state_bytes(CONFIG, 32)
                             + shared["bytes"] + window["bytes"])
    # 19-20 ms a step at 819 GB/s
    assert 19.0 < step["bytes"] / 819e9 * 1e3 < 20.5


def test_a_chunk_runs_the_scan_over_its_rows_and_one_slots_state():
    chunk = work.s6_chunk(ctx())
    rows = 1024
    assert chunk["flops"] == 9 * (rows * (2 * 41_123_840 + 2 * 4 * 5120) + rows * 6 * 5120 * 16)
    assert chunk["bytes"] == 9 * (41_123_840 + 4 * (10240 + 192 + 5120 + 2560)
                                  + 4 * (5120 * 7 + 5120 * 16) + rows * 2 * 2560 * 2
                                  + 2 * (3 * 5120 * 2 + 5120 * 16 * 4))
    empty = types.SimpleNamespace(config=CONFIG, cell=CELL, params={}, scrapes=[], records=[],
                                  window=types.SimpleNamespace(open=0.0, close=1.0, seconds=1.0))
    for need in (work.s6_chunk, work.s6_state, work.shared_kv_step, work.swa_step_attn,
                 work.decode_step, work.prefill_chunk):
        assert need(empty) is None


def test_a_program_without_the_counters_gives_nothing_and_raises_nothing():
    """The parent's program has no `seldon_llm_s6_*`, no decoder-row counters and no
    kind="shared": every function returns None and the line leaves the metric out."""
    bare = ctx()
    for i, (t, s) in enumerate(bare.scrapes):
        kept = [ln for ln in s["metrics"].splitlines()
                if "_s6_" not in ln and "decoder_rows" not in ln and 'kind="shared"' not in ln]
        bare.scrapes[i] = (t, {"metrics": "\n".join(kept) + "\n"})
    for need in (work.s6_chunk, work.s6_state, work.shared_kv_step, work.swa_step_attn,
                 work.decode_step, work.prefill_chunk):
        assert need(bare) is None
    bare.params = load("layer_metrics", "cross_rows_share.json")["params"]
    assert labelled.ratio(bare) is None


def test_the_skip_is_a_number():
    seen = ctx()
    seen.params = load("layer_metrics", "cross_rows_share.json")["params"]
    assert labelled.ratio(seen) == pytest.approx(0.125 / 1024)
    halves = work.decoder_rows(seen, "chunk")
    assert halves == {"self": 1024.0, "cross": 0.125}


def test_the_six_metric_files_stand_on_the_accepted_readers_and_the_cell_lists_them():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    readers = {"hlo_scopes": hlo_scopes, "labelled": labelled}
    for name in NEW:
        spec = load("layer_metrics", name + ".json")
        module, function = spec["reader"].split(":")
        assert callable(getattr(readers[module], function))
        assert spec["moves"] == "throughput" == listed[name]["moves"]
        assert listed[name]["workloads"] == [CELL["name"]] and listed[name]["unit"] == spec["unit"]
        assert listed[name]["layer"] == spec["layer"] == "models / kernels"
        if function == "roofline":
            assert callable(getattr(work, spec["params"]["work"]))
    assert load("layer_metrics", "xattn_step_roofline.json")["params"] == {
        "program": "decode_step", "scopes": ["attn.gqa", "attn.cross"], "work": "shared_kv_step",
        "bound": "hbm"}
    assert load("layer_metrics", "s6_chunk_roofline.json")["params"] == {
        "program": "prefill_chunk", "scopes": ["mix.s6"], "work": "s6_chunk", "bound": "max"}
    # a run without a trace: nothing to read, nothing raised
    bare = ctx()
    bare.trace, bare.params = None, load("layer_metrics", "s6_step_ms.json")["params"]
    assert hlo_scopes.per_call_ms(bare) is None
    # every per-layer metric of the cell's list that BENCHMARK.json knows names the cell,
    # last of its list (appended), and the accepted ones resolve their work in this file
    for name in CELL["metrics"]["per_layer"]:
        assert listed[name]["workloads"][-1] == CELL["name"], name
        params = load("layer_metrics", name + ".json").get("params", {})
        if "work" in params:
            assert callable(getattr(work, params["work"])), (name, params["work"])
    assert next(m for m in bench["end_to_end"] if m["name"] == "throughput")["workloads"][-1] == \
        CELL["name"]
    assert bench["workloads"][-1]["name"] == CELL["name"] and bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == CONFIG["name"]
    assert bench["configs"][-1]["reduced"] == CONFIG["reduced"]


def test_the_cells_traffic_is_the_issues_to_the_letter():
    traffic = CELL["traffic"]
    assert (traffic["generator"], traffic["clients"], traffic["requests"], traffic["stream"]) == (
        "closed_loop", 32, 128, False)
    assert traffic["request"] == {"prompt_tokens": {"dist": "uniform", "min": 4096, "max": 12288},
                                  "output_tokens": {"dist": "uniform", "min": 512, "max": 1024}}
    assert (CELL["ramp_s"], CELL["tail_s"], CELL["drain_s"], CELL["trace_s"]) == (20, 0, 90, 2)
    assert CELL["warmup"] == [{"prompt_tokens": 310, "output_tokens": 16},
                              {"prompt_tokens": 4800, "output_tokens": 8},
                              {"prompt_tokens": 12000, "output_tokens": 4}]
    assert CELL["probe"] == {"prompt_tokens": 770, "output_tokens": 8}
    assert CELL["server"] == {"continuous_batching": 32, "continuous_batching_max_len": 16384}
    assert CELL["metrics"]["end_to_end"] == ["throughput", "setup_s"] and CELL["unit"] == "tokens"


def test_every_limit_of_the_file_is_one_the_plane_judges_by():
    assert CONFIG["plane"] == "llm_rest_state_reference" and CONFIG["work"] == "sambay"
    assert set(CONFIG["reference_tolerance"]) == {"atol_over_scale", "state_rtol", "why"}
    probe = CELL["state_probe"]
    assert (probe["prompt_tokens"], probe["output_tokens"]) == (500, 384)
    assert probe["prompt_tokens"] <= max(CONFIG["server"]["len_buckets"])
    assert probe["output_tokens"] <= CONFIG["server"]["max_new_tokens"] == 1024
    # the probe is past the window and a chunk boundary
    assert CELL["probe"]["prompt_tokens"] > CONFIG["sliding_window"] + 256


def test_the_state_goes_out_a_block_of_128_channels_at_a_time():
    import numpy as np

    sys.path.insert(0, os.path.join(PERF, "reference"))
    from sambay import blocks

    h = np.arange(5120 * 16, dtype=np.float32).reshape(5120, 16)
    out = blocks(h)
    assert out.shape == (40, 128, 16) and (out[3, 5] == h[3 * 128 + 5]).all()
    assert blocks(np.zeros((96, 8), np.float32)).shape == (1, 96, 8)
