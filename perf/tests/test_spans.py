"""CPU tests of the loop-phase readers (perf/spans.py, readers/spans.py,
readers/loop.py), against perf/tests/make_spans_fixture.py's table, by hand:

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

from readers import loop, spans  # noqa: E402

FIXTURE = os.path.join(HERE, "loop_phases.xplane.pb")
US = 1e-6


def test_the_fixture_is_what_its_script_writes(tmp_path):
    before = open(FIXTURE, "rb").read()
    subprocess.run([sys.executable, os.path.join(HERE, "make_spans_fixture.py")], check=True)
    assert open(FIXTURE, "rb").read() == before


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    out = tmp_path_factory.mktemp("spans") / "idle_by_phase.json"
    proc = subprocess.run([sys.executable, os.path.join(PERF, "spans.py"), FIXTURE, str(out)],
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    return json.loads(out.read_text())


def test_idle_seconds_go_to_the_innermost_phase_that_covers_them(table):
    # idle [100,200): drain_wait [95,190) takes 90, the emit around it the last 10.
    # idle [300,400): first_token_wait 20, first_token 20, prefill's own tail
    #   [340,345) 5, the turn outside every phase [345,350) = hop 5, then
    #   [350,360) lies between two recorded turns in no llm span at all, and the
    #   next turn's [360,400) = hop 40.
    # idle [450,600): hop [450,460) 10, admit 40, hop [500,580) 80, and [580,600)
    #   comes after the last recorded turn: the capture's edge, not the program's hole.
    assert table["spans"] == 9          # np.asarray is JAX's event, not read
    assert table["idle_s"] == pytest.approx(350 * US)
    assert table["window_s"] == pytest.approx(700 * US)
    assert table["by_phase"] == pytest.approx({
        "drain_wait": 90 * US, "emit": 10 * US, "first_token_wait": 20 * US,
        "first_token": 20 * US, "prefill": 5 * US, "hop": 135 * US, "admit": 40 * US,
        "outside llm spans": 10 * US, "edge of the capture": 20 * US})
    assert sum(table["by_phase"].values()) == pytest.approx(table["idle_s"])


def test_phase_seconds_are_self_times_and_fill_the_turns(table):
    # turn [50,350): emit 120 - 95 inside it, prefill 55 - 25 - 20, hop the rest
    # (300 - 120 - 55 = 125); turn [360,580): admit 40, hop 180; idle stands in
    # the capture's edge, which is no phase and is left out like the holes
    assert table["phase_s"] == pytest.approx({
        "hop": 305 * US, "emit": 25 * US, "drain_wait": 95 * US, "prefill": 10 * US,
        "first_token_wait": 25 * US, "first_token": 20 * US, "admit": 40 * US,
        "idle": 20 * US})


def fake_ctx(tmp_path, trace, params, scrapes=()):
    run = types.SimpleNamespace(out_dir=str(tmp_path), perf_dir=PERF, repo=REPO,
                                notes=[], note=lambda text: run.notes.append(text))
    return types.SimpleNamespace(run=run, trace=trace, params=params, scrapes=list(scrapes))


def test_idle_in_reads_the_named_phases_as_a_share_of_the_traced_interval(tmp_path):
    ctx = fake_ctx(tmp_path, {"file": FIXTURE, "devices": 1},
                   {"phases": ["first_token_wait", "first_token", "admit"]})
    assert spans.idle_in(ctx) == pytest.approx(100.0 * (20 + 20 + 40) / 700)
    assert os.path.exists(tmp_path / "idle_by_phase.json")
    assert len(ctx.run.notes) == 1 and "idle by phase" in ctx.run.notes[0]
    ctx.params = {"phases": ["drain_wait"]}     # the table is computed once a run
    assert spans.idle_in(ctx) == pytest.approx(100.0 * 90 / 700)
    assert len(ctx.run.notes) == 1


def test_idle_in_returns_nothing_where_the_program_wrote_no_llm_span(tmp_path):
    # small.xplane.pb is the parent's kind of trace: host events, none of them llm.*
    ctx = fake_ctx(tmp_path, {"file": os.path.join(HERE, "small.xplane.pb"), "devices": 1},
                   {"phases": ["admit"]})
    assert spans.idle_in(ctx) is None
    assert spans.idle_in(fake_ctx(tmp_path, None, {"phases": ["admit"]})) is None
    assert spans.idle_in(fake_ctx(tmp_path, {"file": None, "devices": 0},
                                  {"phases": ["admit"]})) is None


def scrape(seconds: dict, counts: dict, turns: int) -> dict:
    lines = [f'seldon_llm_loop_seconds_total{{deployment_name="",phase="{p}"}} {v}'
             for p, v in seconds.items()]
    lines += [f'seldon_llm_loop_phase_total{{deployment_name="",phase="{p}"}} {v}'
              for p, v in counts.items()]
    lines.append(f'seldon_llm_loop_turns_total{{deployment_name=""}} {turns}')
    return {"metrics": "\n".join(lines) + "\n"}


def test_loop_share_and_ms_per_are_differences_between_the_windows_end_scrapes(tmp_path):
    first = scrape({"dispatch": 1.0, "drain_wait": 10.0, "hop": 0.5, "first_token_wait": 0.2,
                    "first_token": 0.1}, {"first_token": 10}, 100)
    middle = scrape({"dispatch": 9.0, "drain_wait": 9.0, "hop": 9.0, "first_token_wait": 9.0,
                     "first_token": 9.0}, {"first_token": 9}, 9)     # not an end: not read
    last = scrape({"dispatch": 3.0, "drain_wait": 16.0, "hop": 1.5, "first_token_wait": 0.6,
                   "first_token": 0.4}, {"first_token": 30}, 600)
    scrapes = [(0.0, first), (1.0, middle), (2.0, last)]
    ctx = fake_ctx(tmp_path, None, {"phases": ["dispatch", "hop"]}, scrapes)
    # all phases: 2 + 6 + 1 + 0.4 + 0.3 = 9.7 s; dispatch + hop = 3
    assert loop.share(ctx) == pytest.approx(100.0 * 3.0 / 9.7)
    # the partition, checked in every traced run: 9.7 s of phases in 2 s of wall
    assert "9.700 s of the 2.000 s" in ctx.run.notes[-1]
    ctx.params = {"phases": ["hop"], "per": {"metric": "seldon_llm_loop_turns_total"}}
    assert loop.ms_per(ctx) == pytest.approx(1e3 * 1.0 / 500)
    ctx.params = {"phases": ["first_token_wait", "first_token"],
                  "per": {"metric": "seldon_llm_loop_phase_total",
                          "label": 'phase="first_token"'}}
    assert loop.ms_per(ctx) == pytest.approx(1e3 * 0.7 / 20)


def test_loop_readers_return_nothing_on_the_parents_scrapes(tmp_path):
    old = {"metrics": 'seldon_llm_kv_pages_in_use{deployment_name=""} 3\n'}
    ctx = fake_ctx(tmp_path, None, {"phases": ["hop"],
                                    "per": {"metric": "seldon_llm_loop_turns_total"}},
                   [(0.0, old), (1.0, old)])
    assert loop.share(ctx) is None and loop.ms_per(ctx) is None
    ctx.scrapes = ctx.scrapes[:1]
    assert loop.share(ctx) is None and loop.ms_per(ctx) is None
