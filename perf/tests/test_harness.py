"""CPU tests of the harness itself (not part of the repo's tier-1 tests):

    python -m pytest perf/tests -q
"""

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
sys.path.insert(0, PERF)

import stats  # noqa: E402
from planes import graph_edge, llm_rest, wire  # noqa: E402
from traffic import closed_loop, draw, open_loop  # noqa: E402
from work import decoder, resnet50  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
# every cell file, the ones BENCHMARK.json does not list (PERF.md section 7) too
CELL_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(PERF, "workloads")))


def fake_run(cell_name: str, seed: int):
    cell = load("workloads", cell_name + ".json")
    config = load("configs", cell["config"] + ".json")
    return types.SimpleNamespace(cell=cell, config=config, seed=seed,
                                 free_port=lambda: 1, rngs=draw.streams(seed),
                                 repo=REPO, perf_dir=PERF, out_dir="/nonexistent")


def plan_of(cell_name: str, seed: int):
    run = fake_run(cell_name, seed)
    if run.config["plane"] == "graph_edge":          # 224x224 rows are slow to draw
        run.config["server"]["input_shape"] = [8, 8, 3]
    plane = {"llm_rest": llm_rest, "graph_edge": graph_edge}[run.config["plane"]].Plane(run)
    generator = {"open_loop": open_loop, "closed_loop": closed_loop}[
        run.cell["traffic"]["generator"]]
    return generator.build(run.cell["traffic"], plane.make_request, run.rngs, [2.0, 8.0, 2.0])


@pytest.mark.parametrize("cell", CELL_FILES)
def test_schedule_is_a_pure_function_of_the_seed(cell):
    a, b, other = plan_of(cell, 7), plan_of(cell, 7), plan_of(cell, 8)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.get("due") == y.get("due")
        assert x["sizes"] == y["sizes"]
        assert x["request"]["body"] == y["request"]["body"]
    assert [x["request"]["body"] for x in a] != [x["request"]["body"] for x in other][:len(a)]


def test_arrivals_are_poisson_at_the_rate_asked_for_with_a_fixed_count():
    rng = np.random.default_rng(0)
    segments = [5.0, 200.0, 5.0]
    due = np.concatenate(draw.arrivals(rng, {"process": "poisson", "rate": 50.0}, segments))
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 210.0
    # a fixed amount of work: every segment holds exactly rate x length
    assert [int(((due >= a) & (due < b)).sum()) for a, b in ((0, 5), (5, 205), (205, 210))] \
        == [250, 10000, 250]
    gaps = np.diff(due[(due >= 5) & (due < 205)])
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15
    with pytest.raises(ValueError, match="unknown arrival process"):
        draw.arrivals(rng, {"process": "bursts", "rate": 50.0}, segments)


def test_lengths_are_the_same_multiset_for_every_seed_clipped_and_centred():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 32, "max": 512}
    a = draw.draw(np.random.default_rng(1), spec, 108)
    b = draw.draw(np.random.default_rng(2), spec, 108)
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 32 and a.max() <= 512 and abs(np.median(a) - 192) < 4
    # quantile midpoints: the 97th of 108 sits at u = 96.5 / 108, z = 1.24546
    assert sorted(a)[96] == round(192 * np.exp(0.6 * 1.24546))
    u = draw.draw(np.random.default_rng(1), {"dist": "uniform", "min": 1, "max": 4}, 8)
    assert sorted(u) == [1, 1, 2, 2, 3, 3, 4, 4]


def test_llm_prompt_has_the_token_count_drawn():
    run = fake_run("mistral7b-chat-steady", 3)
    plane = llm_rest.Plane(run)
    sizes = {"prompt_tokens": 300, "output_tokens": 9}
    a = json.loads(plane.make_request(sizes, np.random.default_rng(1))["body"])
    b = json.loads(plane.make_request(sizes, np.random.default_rng(2))["body"])
    # the program's byte tokenizer: one token per ASCII byte, nothing added
    assert len(a["prompt"].encode()) == 300 and a["max_new_tokens"] == 9
    assert a["prompt"] != b["prompt"]


def test_wire_codec_round_trips_and_reports_a_failure_status():
    x = np.random.default_rng(0).random((2, 3, 4)).round(3)
    assert (wire.decode_tensor_message(wire.encode_tensor_message(x)) == x).all()
    status = wire._field(1, wire._field(2, b"ring full"))
    with pytest.raises(ValueError, match="ring full"):
        wire.decode_tensor_message(status)


def test_percentile_refuses_fewer_than_ten_samples_beyond_it():
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(range(99), 90)      # 9.9 beyond
    assert stats.percentile(range(100), 90) == pytest.approx(89.1)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(range(999), 99)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile([], 50)


# -- trace reduction, against perf/tests/make_fixture.py's table, by hand ------

@pytest.fixture(scope="module")
def reduced():
    """perf/trace.py is named as the issue names it, which is also a module
    of the standard library, so it is run as the helper child it is."""
    out = os.path.join(os.path.dirname(__file__), "small.reduced.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "trace.py"), os.path.dirname(__file__), out],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    try:
        with open(out) as f:
            return json.load(f)
    finally:
        os.remove(out)


def test_trace_busy_union_window_and_idle(reduced):
    # ops cover [0,100) (loop with two fusions inside), [200,300) (two ops
    # overlapping by 10) and [400,450): 250 us busy of a 450 us window
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(250e-6)
    assert reduced["window_s"] == pytest.approx(450e-6)


def test_trace_gaps_are_labelled_by_the_host_event_covering_most(reduced):
    assert reduced["gaps"] == pytest.approx([100e-6, 100e-6])
    # [100,200): dispatch [90,210) covers all of it.  [300,400): wait
    # [295,395) covers 95, tiny [300,301) covers 1
    assert dict(reduced["gap_labels"]) == pytest.approx({"dispatch": 100e-6, "wait": 100e-6})


def test_trace_per_program_and_per_op_self_time(reduced):
    assert reduced["programs"] == {"jit_step": {"calls": 2, "seconds": pytest.approx(200e-6)},
                                   "jit_other": {"calls": 1, "seconds": pytest.approx(50e-6)}}
    ops = dict(reduced["ops"])
    # loop 100 - 30 - 40 inside it; fusion.1 30 + (60 - 10 overlapped by copy.3) + 50
    assert ops == pytest.approx({"loop": 30e-6, "fusion.1": 130e-6, "fusion.2": 40e-6,
                                 "copy.3": 50e-6})
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])


# -- operations and bytes from shapes, against hand arithmetic -----------------

def test_decoder_work_matches_hand_arithmetic():
    cfg = load("configs", "mistral-7b-int8.json")
    lin = decoder.linear_params(cfg)
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert lin["layer"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 == 218103808
    assert lin["head"] == 4096 * 32000
    assert decoder.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2 * 32 == 131072
    step = decoder.decode_step_cost(cfg, 32, 10000)
    weights = 32 * (218103808 + 4 * (4096 + 2048 + 4096 + 28672 + 4096)) + 131072000 + 4 * 32000
    assert step["bytes"] == weights + 32 * 4096 + 10000 * 131072
    assert weights == pytest.approx(7.116e9, rel=1e-3)
    chunk = decoder.prefill_chunk_cost(cfg, 256, 1024, head_positions=1)
    assert chunk["flops"] == (2 * 256 * 32 * 218103808
                              + 4 * 32 * 128 * (256 * 1024 + 256 * 257 // 2) * 32
                              + 2 * 131072000)
    assert chunk["flops"] == pytest.approx(3.73e12, rel=2e-3)


def test_resnet50_work_matches_hand_arithmetic():
    assert resnet50.conv_flops(112, 7, 3, 64) == 2 * 112 * 112 * 49 * 3 * 64
    one = resnet50.forward_cost(224, 3, 1000)
    # the textbook figures: 4.09 G multiply-adds, 25.5 M weights outside batch norm
    assert one["flops"] / 2 == pytest.approx(4.089e9, rel=1e-3)
    assert one["weights"] == pytest.approx(25.50e6, rel=1e-3)
    # first bottleneck at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 64->256
    first = 2 * 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    stem = 2 * 112 * 112 * 49 * 3 * 64
    assert resnet50.forward_cost(224, 3, 1000)["flops"] > stem + first


# -- the benchmark's own files agree with each other ---------------------------

def test_every_name_in_benchmark_json_has_its_file():
    for cfg in BENCH["configs"]:
        data = load("configs", cfg["name"] + ".json")
        assert cfg["file"] == f"perf/configs/{cfg['name']}.json"
        assert data["source"] == cfg["source"] and data["reduced"] == cfg["reduced"]
    for cell in BENCH["workloads"]:
        data = load("workloads", cell["name"] + ".json")
        assert (data["config"], data["traffic_mix"]) == (cell["config"], cell["traffic"])
    reports = {c: load("workloads", c + ".json")["metrics"] for c in CELLS}
    for group, directory in (("end_to_end", "metrics"), ("per_layer", "layer_metrics")):
        for m in BENCH[group]:
            assert load(directory, m["name"] + ".json")["unit"] == m["unit"]
            assert sorted(m.get("workloads", CELLS)) == sorted(
                c for c in CELLS if m["name"] in reports[c][group]), m["name"]
    for m in BENCH["per_layer"]:
        spec = load("layer_metrics", m["name"] + ".json")
        assert (spec["layer"], spec["moves"]) == (m["layer"], m["moves"])
        assert all(m["moves"] in reports[c]["end_to_end"] for c in m["workloads"])
        module, _, function = spec["reader"].partition(":")
        assert re.search(rf"^def {function}\(ctx\)", open(
            os.path.join(PERF, "readers", module + ".py")).read(), re.M)


def test_the_general_code_names_no_cell_configuration_or_metric():
    names = [x["name"] for group in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[group]]
    files = ["run.py", "trace.py", "sweep.py", "server.py", "stats.py", "launch_traced.py"] + [
        os.path.join("traffic", f) for f in os.listdir(os.path.join(PERF, "traffic"))
        if f.endswith(".py")]
    for rel in files:
        text = open(os.path.join(PERF, rel)).read()
        for name in names:
            assert not re.search(rf"(?<![\w-]){re.escape(name)}(?![\w-])", text), (rel, name)
