"""CPU tests of the host-turn readers (perf/turn_idle.py, readers/turn.py),
against perf/tests/make_turn_fixture.py's table, by hand:

    python -m pytest perf/tests -q
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
REPO = os.path.dirname(PERF)
sys.path.insert(0, PERF)

from readers import counters, turn  # noqa: E402

FIXTURE = os.path.join(HERE, "host_turn.xplane.pb")
OLD = os.path.join(HERE, "loop_phases.xplane.pb")
US = 1e-6


def helper(script: str, fixture: str, out) -> dict:
    proc = subprocess.run([sys.executable, os.path.join(PERF, script), fixture, str(out)],
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    return json.loads(out.read_text())


def test_the_fixture_is_what_its_script_writes():
    before = open(FIXTURE, "rb").read()
    subprocess.run([sys.executable, os.path.join(HERE, "make_turn_fixture.py")], check=True)
    assert open(FIXTURE, "rb").read() == before


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    return helper("turn_idle.py", FIXTURE, tmp_path_factory.mktemp("turn") / "idle_by_part.json")


def test_idle_seconds_go_to_the_innermost_part(table):
    # idle [100,200): the wake out to the worker until 110, dispatch's own head 5,
    #   pages 10, the call 70 (JAX's PjitFunction inside it is not the program's),
    #   book 5 of its 10.
    # idle [300,400): wake_worker 10, emit's own 2 + 2 + 2, drain_wait 28 outside its
    #   asides' 10, emit.slots 16 less the finish's 6 inside it, the wake back 6,
    #   hop.loop 4, [380,390) lies between two turns, the next turn's hop.loop 10.
    # idle [500,700): wake_worker 20, prefill's own 2 + 2, build 8, call 60,
    #   activate 8, the wake back 90, hop.loop 10.
    assert table["spans"] == 26 and table["http_spans"] == 4
    assert table["idle_s"] == pytest.approx(400 * US)
    assert table["window_s"] == pytest.approx(800 * US)
    assert table["by_part"] == pytest.approx({
        "hop.wake_worker": 40 * US, "hop.wake_loop": 96 * US, "hop.loop": 24 * US,
        "dispatch": 5 * US, "dispatch.pages": 10 * US, "dispatch.call": 70 * US,
        "dispatch.book": 5 * US, "emit": 6 * US, "drain_wait": 28 * US,
        "drain_wait.asides": 10 * US, "emit.slots": 10 * US, "emit.finish": 6 * US,
        "prefill": 4 * US, "prefill.build": 8 * US, "prefill.call": 60 * US,
        "prefill.activate": 8 * US, "outside llm spans": 10 * US})
    assert sum(table["by_part"].values()) == pytest.approx(table["idle_s"])
    # llm.turn alone is [210,212): the device was busy then
    assert "hop" not in table["by_part"]
    assert table["part_s"]["hop"] == pytest.approx(2 * US)
    assert table["part_s"]["hop.loop"] == pytest.approx(194 * US)
    assert table["part_s"]["hop.wake_worker"] == pytest.approx(150 * US)
    assert table["part_s"]["hop.wake_loop"] == pytest.approx(114 * US)
    assert table["part_s"]["dispatch"] == pytest.approx(10 * US)     # 100 less its parts' 90
    assert table["host_events_per_s"] == pytest.approx(31 / (800 * US))


def test_idle_under_an_open_http_span_is_cut_by_part_and_by_what(table):
    # sse_write [120,150): pages [120,125), the call [125,150); scrape [430,560)
    # meets idle from 500 on: wake_worker 20, prefill 2, build 8, call 30; parse
    # and reply lie inside the wake back [600,690)
    assert {k: pytest.approx(v) for k, v in table["idle_http"].items()} == {
        "dispatch.pages": {"sse_write": 5 * US}, "dispatch.call": {"sse_write": 25 * US},
        "hop.wake_worker": {"scrape": 20 * US}, "prefill": {"scrape": 2 * US},
        "prefill.build": {"scrape": 8 * US}, "prefill.call": {"scrape": 30 * US},
        "hop.wake_loop": {"parse": 30 * US, "reply": 5 * US}}
    assert table["http_s"] == pytest.approx({
        "sse_write": 30 * US, "scrape": 130 * US, "parse": 30 * US, "reply": 5 * US})


def test_an_older_programs_trace_reads_as_spans_py_reads_it(tmp_path):
    # loop_phases.xplane.pb is the trace of a program without parts: the table's
    # old keys are spans.py's, to the digit, and nothing is under an http span
    new = helper("turn_idle.py", OLD, tmp_path / "by_part.json")
    old = helper("spans.py", OLD, tmp_path / "by_phase.json")
    assert new["by_part"] == old["by_phase"] and new["part_s"] == old["phase_s"]
    assert (new["idle_s"], new["window_s"], new["spans"]) == (
        old["idle_s"], old["window_s"], old["spans"])
    assert new["idle_http"] == {} and new["http_s"] == {} and new["http_spans"] == 0


def test_spans_py_reads_the_new_trace_with_its_old_keys_intact(tmp_path):
    # the phases the benchmark reads by name hold no part, so spans.py's rows for
    # them are what they were; a part stands beside its phase under its own key
    old = helper("spans.py", FIXTURE, tmp_path / "by_phase.json")["by_phase"]
    assert old["drain_wait"] == pytest.approx(28 * US) and old["emit"] == pytest.approx(6 * US)
    assert not {"admit", "first_token", "first_token_wait"} & set(old)
    assert sum(old.values()) == pytest.approx(400 * US)


def fake_ctx(tmp_path, trace, params, scrapes=()):
    run = types.SimpleNamespace(out_dir=str(tmp_path), perf_dir=PERF, repo=REPO,
                                notes=[], note=lambda text: run.notes.append(text))
    return types.SimpleNamespace(run=run, trace=trace, params=params, scrapes=list(scrapes),
                                 config={})


def test_idle_in_reads_parts_and_the_seconds_under_an_http_span(tmp_path):
    ctx = fake_ctx(tmp_path, {"file": FIXTURE, "devices": 1},
                   {"parts": ["hop", "hop.loop", "hop.wake_worker", "hop.wake_loop"]})
    assert turn.idle_in(ctx) == pytest.approx(100.0 * (24 + 40 + 96) / 800)
    assert os.path.exists(tmp_path / "idle_by_part.json")
    assert len(ctx.run.notes) == 1 and "idle by part" in ctx.run.notes[0]
    ctx.params = {"parts": ["hop", "hop.wake_worker", "hop.wake_loop"], "http": True}
    assert turn.idle_in(ctx) == pytest.approx(100.0 * (20 + 35) / 800)
    ctx.params = {"parts": ["dispatch", "dispatch.pages", "dispatch.call", "dispatch.book"]}
    assert turn.idle_in(ctx) == pytest.approx(100.0 * 90 / 800)
    assert len(ctx.run.notes) == 1          # the table is computed once a run


def test_idle_in_returns_nothing_where_the_program_wrote_no_such_span(tmp_path):
    params = {"parts": ["hop"], "http": True}
    # the parent's kind of trace: llm.* phases, no http.* span
    assert turn.idle_in(fake_ctx(tmp_path, {"file": OLD, "devices": 1}, params)) is None
    # ... whose bare turn is all of hop, as spans.py reads it
    assert turn.idle_in(fake_ctx(tmp_path, {"file": OLD, "devices": 1},
                                 {"parts": ["hop"]})) == pytest.approx(100.0 * 135 / 700)
    small = {"file": os.path.join(HERE, "small.xplane.pb"), "devices": 1}
    assert turn.idle_in(fake_ctx(tmp_path, small, {"parts": ["hop"]})) is None
    assert turn.idle_in(fake_ctx(tmp_path, None, params)) is None
    assert turn.idle_in(fake_ctx(tmp_path, {"file": None, "devices": 0}, params)) is None


def scrape(parts: dict, counts: dict, hop: float, turns: int, extra: str = "") -> dict:
    lines = [f'seldon_llm_loop_part_seconds_total{{deployment_name="",part="{p}"}} {v}'
             for p, v in parts.items()]
    lines += [f'seldon_llm_loop_part_total{{deployment_name="",part="{p}"}} {v}'
              for p, v in counts.items()]
    lines.append(f'seldon_llm_loop_seconds_total{{deployment_name="",phase="hop"}} {hop}')
    lines.append(f'seldon_llm_loop_turns_total{{deployment_name=""}} {turns}')
    return {"metrics": "\n".join(lines) + "\n" + extra}


def test_per_sums_series_between_the_windows_end_scrapes_and_notes_the_identity(tmp_path):
    first = scrape({"hop.wake_worker": 1.0, "hop.wake_loop": 2.0, "hop.worker": 0.1,
                    "hop.loop": 0.5, "dispatch.call": 4.0}, {"dispatch.call": 100}, 3.7, 100,
                   'seldon_http_busy_seconds_total{what="scrape"} 1.0\n')
    middle = scrape({"hop.wake_worker": 9.0}, {}, 9.0, 9)           # not an end: not read
    last = scrape({"hop.wake_worker": 1.6, "hop.wake_loop": 2.9, "hop.worker": 0.2,
                   "hop.loop": 1.4, "dispatch.call": 6.0}, {"dispatch.call": 600}, 6.2, 600,
                  'seldon_http_busy_seconds_total{what="scrape"} 1.3\n'
                  'seldon_http_busy_seconds_total{what="sse_write"} 0.2\n')
    scrapes = [(0.0, first), (1.0, middle), (4.0, last)]
    sec = "seldon_llm_loop_part_seconds_total"
    ctx = fake_ctx(tmp_path, None, {
        "over": [[sec, 'part="hop.wake_worker"'], [sec, 'part="hop.wake_loop"']],
        "under": [["seldon_llm_loop_turns_total", ""]], "scale": 1e3}, scrapes)
    assert turn.per(ctx) == pytest.approx(1e3 * (0.6 + 0.9) / 500)
    # the check that no piece of a turn is unnamed, once a run: 0.6 + 0.9 + 0.1 + 0.9 of 2.5
    assert len(ctx.run.notes) == 1 and "2.5000 s of the phase's 2.5000 s (100.00 %)" in ctx.run.notes[0]
    ctx.params = {"over": [["seldon_http_busy_seconds_total", ""]], "under": "seconds",
                  "scale": 100.0}
    # a series that appears during the window counts from its first value... which the
    # first scrape lacks: sums are of what both ends hold, 1.5 - 1.0 over 4 s
    assert turn.per(ctx) == pytest.approx(100.0 * 0.5 / 4.0)
    assert len(ctx.run.notes) == 1
    # the data-only metrics go through counters:ratio with one label on both sides
    ctx.params = {"over": sec, "under": "seldon_llm_loop_part_total",
                  "label": 'part="dispatch.call"', "scale": 1e3}
    assert counters.ratio(ctx) == pytest.approx(1e3 * 2.0 / 500)


def test_per_returns_nothing_on_the_parents_scrapes(tmp_path):
    old = {"metrics": 'seldon_llm_loop_turns_total{deployment_name=""} 3\n'}
    later = {"metrics": 'seldon_llm_loop_turns_total{deployment_name=""} 9\n'}
    ctx = fake_ctx(tmp_path, None, {
        "over": [["seldon_llm_loop_part_seconds_total", 'part="hop.loop"']],
        "under": [["seldon_llm_loop_turns_total", ""]]}, [(0.0, old), (1.0, later)])
    assert turn.per(ctx) is None and ctx.run.notes == []
    ctx.scrapes = ctx.scrapes[:1]
    assert turn.per(ctx) is None


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(PERF, "layer_metrics"))))
def test_every_metric_file_names_a_reader_that_is_there(name):
    import importlib

    with open(os.path.join(PERF, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    module, _, function = spec["reader"].partition(":")
    assert callable(getattr(importlib.import_module(f"readers.{module}"), function))
    assert set(spec) >= {"layer", "unit", "moves", "reader", "params", "what"}
