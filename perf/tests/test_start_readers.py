"""readers/start.py on synthetic scrapes of the start ledger's three series
(values small enough to check by hand), the seven metric files that name its
readers, and the cell that lists them (mistral7b-chat-short-overload)."""

import json
import os
import sys
import types

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

from readers import start  # noqa: E402
from traffic import open_loop, open_loop_completed  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CELL = load("workloads", "mistral7b-chat-short-overload.json")
TWIN = load("workloads", "mistral7b-chat-short.json")
BENCH = load("..", "BENCHMARK.json")
NEW = ("start_to_ready_s", "start_weights_s", "build_trace_s", "build_lower_s", "build_load_s",
       "build_cache_hit_share", "builds_in_window")
STAGES = {"import": 4.0, "construct": 0.5, "load.weights": 12.0, "load.rest": 0.25, "listen": 1.0,
          "batcher.build": 2.0}


def series(name, value, **labels):
    # the exposition sorts a series' labels by name
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(
        {"deployment_name": "", "predictor_name": "", **labels}.items()))
    return f"{name}{{{inner}}} {value}"


def scrape(hits=0, misses=0, later=0, stages=STAGES, ledger=True):
    """A server whose start built three programs and the weight draws; `later`
    more executables were loaded after this scrape's predecessor."""
    lines = [series("seldon_llm_loop_turns_total", 100 + later)]
    if ledger:
        lines += [series(start.STAGE, v, stage=k) for k, v in stages.items()]
        for program, trace, lower, load_ in (("decode_step", 3.0, 1.0, 6.0),
                                             ("prefill_chunk", 2.0, 0.5, 4.0),
                                             ("weights", 7.0, 9.0, 11.0), ("other", 0.25, 0.25, 0.5)):
            lines += [series(start.BUILD_SECONDS, trace, program=program, leg="trace", nested="0"),
                      series(start.BUILD_SECONDS, lower, program=program, leg="lower", nested="0"),
                      # a warm cache books the load, a cold one the compile
                      series(start.BUILD_SECONDS, load_, program=program, nested="0",
                             leg="cache_load" if hits else "compile")]
        # inside decode_step's trace: in no sum
        lines += [series(start.BUILD_SECONDS, 1.5, program="paged_live_read", leg="trace", nested="1"),
                  series(start.BUILD_SECONDS + "_created", 1.79e9, program="other", leg="trace", nested="0")]
        if hits:
            lines.append(series(start.BUILDS, hits + later, program="decode_step", cache="hit"))
        if misses:
            lines.append(series(start.BUILDS, misses, program="other", cache="miss"))
        lines.append(series(start.BUILDS, 2, program="other", cache="off"))
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(params=None, later=0, **kw):
    scrapes = [(0.0, scrape(**kw)), (1.0, scrape(later=later, **kw))]
    return types.SimpleNamespace(
        cell=CELL, params=params or {}, scrapes=scrapes, records=[],
        run=types.SimpleNamespace(note=lambda *_: None))


def read(name, **kw):
    spec = load("layer_metrics", name + ".json")
    module, _, function = spec["reader"].partition(":")
    assert module == "start"
    return getattr(start, function)(ctx(spec.get("params"), **kw))


def test_the_stages_to_ready_are_summed_and_batcher_build_is_not_among_them():
    assert read("start_to_ready_s", hits=9) == 4.0 + 0.5 + 12.0 + 0.25 + 1.0
    assert read("start_weights_s", hits=9) == 12.0
    # a program that reports four of the five: no sum of fewer
    four = {k: v for k, v in STAGES.items() if k != "load.rest"}
    assert read("start_to_ready_s", hits=9, stages=four) is None
    assert read("start_weights_s", hits=9, stages=four) == 12.0


def test_build_seconds_leave_out_the_weight_draws_and_every_nested_leg():
    assert read("build_trace_s", hits=9) == 3.0 + 2.0 + 0.25
    assert read("build_lower_s", hits=9) == 1.0 + 0.5 + 0.25
    # a warm start books cache_load, a cold one compile: build_load_s is either
    assert read("build_load_s", hits=9) == read("build_load_s", misses=9) == 6.0 + 4.0 + 0.5


def test_the_cache_share_counts_hits_over_hits_and_misses():
    assert read("build_cache_hit_share", hits=9, misses=3) == 75.0
    assert read("build_cache_hit_share", hits=9) == 100.0
    assert read("build_cache_hit_share", misses=4) == 0.0
    assert read("build_cache_hit_share") is None    # built with the cache off: no share


def test_builds_in_window_is_what_the_end_scrapes_differ_by():
    assert read("builds_in_window", hits=9, misses=3) == 0.0
    assert read("builds_in_window", hits=9, later=2) == 2.0


def test_a_program_without_the_ledger_gives_nothing_and_does_not_raise():
    for name in NEW:
        assert read(name, ledger=False) is None, name
    empty = types.SimpleNamespace(params={"stages": ["listen"], "legs": ["trace"]}, scrapes=[])
    for reader in (start.stage, start.built, start.hit_share, start.in_window):
        assert reader(empty) is None


def test_the_cell_is_chat_shorts_traffic_faster_and_the_benchmarks_entries_agree():
    for key in ("config", "unit", "ramp_s", "tail_s", "warmup", "probe", "server", "rehearse"):
        assert CELL[key] == TWIN[key], key
    # the same draws, sent the same way; credited where they END (the generator's share)
    assert {**CELL["traffic"], "arrivals": None, "generator": None} == {
        **TWIN["traffic"], "arrivals": None, "generator": None}
    assert (CELL["traffic"]["generator"], TWIN["traffic"]["generator"]) == (
        "open_loop_completed", "open_loop")
    knee = CELL["knee"]["knee"]
    assert CELL["traffic"]["arrivals"] == {"process": "poisson", "rate": 33.5}
    assert 33.5 == int(1.3 * knee * 2) / 2      # 1.3 x the knee, rounded down to 0.5 req/s
    assert (CELL["drain_s"], CELL["trace_s"], CELL["limits"]) == (60, 2, {})
    assert CELL["metrics"]["end_to_end"] == ["throughput", "setup_s"]
    assert CELL["metrics"]["per_layer"] == TWIN["metrics"]["per_layer"] + list(NEW)
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in CELL["metrics"]["per_layer"]:
        assert CELL["name"] in listed[name]["workloads"], name
        spec = load("layer_metrics", name + ".json")
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            listed[name]["unit"], listed[name]["layer"], listed[name]["moves"]), name
    for name in NEW:
        assert listed[name]["workloads"] == [CELL["name"]]
        assert listed[name]["layer"] == "start-up"
        assert listed[name]["moves"] == ("throughput" if name == "builds_in_window" else "setup_s")
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL["name"]]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CELL["config"], CELL["traffic_mix"], 1)
    (throughput,) = [m for m in BENCH["end_to_end"] if m["name"] == "throughput"]
    assert CELL["name"] in throughput["workloads"]


def test_a_request_belongs_to_the_window_its_reply_ended_in():
    window = types.SimpleNamespace(open=10.0, close=20.0)
    assert (open_loop_completed.build, open_loop_completed.run) == (open_loop.build, open_loop.run)
    for due, done, completed, offered in ((5.0, 12.0, 1.0, 0.0),     # the ramp's, finished inside
                                          (12.0, 19.9, 1.0, 1.0),
                                          (19.0, 31.0, 0.0, 1.0),    # the drain's: the next window's
                                          (12.0, 20.0, 0.0, 1.0),
                                          (5.0, 9.0, 0.0, 0.0)):
        rec = {"due": due, "done": done, "ok": True}
        assert open_loop_completed.share(rec, window) == completed, rec
        assert open_loop.share(rec, window) == offered, rec
    # never answered: it counts, as failed, unless it was the tail's
    assert open_loop_completed.share({"due": 19.0}, window) == 1.0
    assert open_loop_completed.share({"due": 22.0}, window) == 0.0
