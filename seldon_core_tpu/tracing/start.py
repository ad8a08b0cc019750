"""The start ledger: where a server's start goes, and what every program's
build cost, from inside the process.

Two books, always on, one object a process (:func:`get_ledger`):

**Stages.** ``STAGES`` is a closed tuple. The first five partition the wall
from the process's creation (as the kernel has it) to the first ``/ready``
that answered 200: each is opened by :meth:`StartLedger.advance`, which closes
the one before it on the same clock reading, so no second lies between two of
them or in both. What happens after the app listens (``batcher.build``) is a
:meth:`StartLedger.stage` of its own. A stage is a :class:`~seldon_core_tpu.
tracing.Span` under the one root ``server.start`` (exported like any other
span when ``TRACING=1``), a ``jax.profiler.TraceAnnotation`` named
``start.<stage>`` once JAX is imported (so a profile taken over it shows it
on the device trace's clock), and seconds in ``stage_seconds``.

**Builds.** JAX reports every trace, lowering and backend compile of a jitted
function through ``jax.monitoring``: a scalar event when the leg begins, a
duration event when it ends, both on the thread that does the work, and
between the two of a backend compile whether the persistent cache had the
executable. :meth:`StartLedger.listen` registers for them once
(transport/cli.py ``_start_serving``); a listener runs when something is
built and never on a cached call. A leg that begins while another is open on
its thread is traced INSIDE it and booked ``nested="1"``: the ``nested="0"``
seconds of a thread are wall it really spent. They are thread-seconds: two
threads that build side by side book both.

docs/observability.md "Start-up" has the tables.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import secrets
import sys
import threading
import time
from typing import Any, Dict, Iterable, Optional, Tuple

from seldon_core_tpu import tracing

logger = logging.getLogger(__name__)

#: process creation -> first ``/ready`` 200, in order, then what follows it
TO_READY = ("import", "construct", "load.weights", "load.rest", "listen")
STAGES = TO_READY + ("batcher.build",)

#: jax.monitoring's name of each leg of a build (the scalar event that opens
#: it and the duration event that closes it carry the same name)
LEGS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: ... and of the persistent cache's verdict on the backend compile under way
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


#: jitted function -> the program its builds are booked under (`other` for a
#: function in no row): the process's one table, filled by :func:`name_programs`
_programs: Dict[str, str] = {}


def name_programs(table: Iterable[Tuple[str, Iterable[str]]]) -> None:
    """``(program, jitted function names)`` rows, from the module that defines
    the functions (servers/llmserver.py ``BUILD_PROGRAMS``)."""
    for program, functions in table:
        for function in functions:
            _programs[function] = program


def process_age_s() -> float:
    """Seconds since the kernel created this process: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against CLOCK_BOOTTIME. 0.0
    where that cannot be read: the start then counts from this module's import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0
    return max(age, 0.0)


class _Open:
    """One open stage: its span and, where JAX is there, its annotation."""

    __slots__ = ("name", "span", "annotation")

    def __init__(self, name: str, span: tracing.Span, annotation: Any):
        self.name, self.span, self.annotation = name, span, annotation


class StartLedger:
    def __init__(self, age_s: Optional[float] = None):
        now = tracing.now()
        self.born = now - (process_age_s() if age_s is None else age_s)
        self._lock = threading.Lock()
        self.stage_seconds: Dict[str, float] = {}
        self.ready_s: Optional[float] = None    # born -> the first /ready 200
        self._root = tracing.Span(
            name="server.start", trace_id=secrets.token_hex(16),
            span_id=secrets.token_hex(8), parent_id=None, start=self.born)
        # the partition's open stage: `import` runs from the process's creation
        self._at: Optional[_Open] = self._open("import", self.born)
        # builds: (program, leg, nested) -> [seconds, legs];
        # (program, cache) -> backend compiles
        self.build_seconds: Dict[Tuple[str, str, str], list] = {}
        self.builds: Dict[Tuple[str, str], int] = {}
        self._threads = threading.local()
        self.listener_s = 0.0
        self.listener_calls = 0
        self._listening = False

    # -- stages ------------------------------------------------------------
    def _open(self, name: str, start: float) -> _Open:
        span = tracing.Span(name="start." + name, trace_id=self._root.trace_id,
                            span_id=secrets.token_hex(8), parent_id=self._root.span_id,
                            start=start)
        annotation = None
        if "jax" in sys.modules:
            from jax.profiler import TraceAnnotation

            annotation = TraceAnnotation("start." + name)
            annotation.__enter__()
        return _Open(name, span, annotation)

    def _close(self, stage: _Open, end: float) -> list:
        """Book the stage; its span, for the tracer (outside the lock)."""
        if stage.annotation is not None:
            stage.annotation.__exit__(None, None, None)
        stage.span.end = end
        with self._lock:
            self.stage_seconds[stage.name] = (
                self.stage_seconds.get(stage.name, 0.0) + end - stage.span.start)
        return [stage.span]

    def advance(self, name: str) -> None:
        """The partition moves on to ``name``: the open stage closes and
        ``name`` opens on ONE clock reading. Forward only, and only before the
        first ``/ready``: a second component loaded by this process, or a
        reload, moves nothing."""
        at = self._at
        if at is None or TO_READY.index(name) <= TO_READY.index(at.name):
            return
        now = tracing.now()
        self._at = None
        spans = self._close(at, now)
        self._at = self._open(name, now)
        tracing.get_tracer().record_spans(spans)

    def ready(self) -> None:
        """The first ``/ready`` that answers 200 closes the partition and the
        root span, and says where the start went in one INFO line."""
        at = self._at
        if at is None:
            return
        self._at = None
        now = tracing.now()
        spans = self._close(at, now)
        self._root.end = now
        self.ready_s = now - self.born
        tracing.get_tracer().record_spans(spans + [self._root])
        start, built = self.snapshot()
        logger.info("start to ready %.3f s by stage: %s; built so far: %s", self.ready_s,
                    json.dumps(start["stages"]), json.dumps(built["build_seconds"]))

    @contextlib.contextmanager
    def stage(self, name: str):
        """A stage outside the partition (``batcher.build``): span, annotation
        and seconds like the five, opened and closed by its ``with``."""
        if name not in STAGES:
            raise ValueError(f"unknown start stage {name!r}: one of {STAGES}")
        opened = self._open(name, tracing.now())
        try:
            yield
        finally:
            tracing.get_tracer().record_spans(self._close(opened, tracing.now()))

    # -- builds ------------------------------------------------------------
    def listen(self) -> None:
        """Register with ``jax.monitoring`` (public API), once."""
        if self._listening:
            return
        from jax import monitoring

        self._listening = True
        monitoring.register_scalar_listener(self._leg_begins)
        monitoring.register_event_listener(self._cache_event)
        monitoring.register_event_duration_secs_listener(self._leg_ends)

    def close(self) -> None:
        """Unregister (tests: a process has one ledger for its whole life)."""
        if not self._listening:
            return
        from jax import monitoring

        self._listening = False
        monitoring.unregister_scalar_listener(self._leg_begins)
        monitoring.unregister_event_listener(self._cache_event)
        monitoring.unregister_event_duration_listener(self._leg_ends)

    def _state(self):
        state = self._threads
        if not hasattr(state, "open"):
            state.open = []         # [leg, cache verdict] of the legs open on this thread
            state.legs = 0          # legs this thread has ended that lay in no other
            state.named = (0, None)  # ... the last of them of a named program: (at, program)
        return state

    def _leg_begins(self, event: str, _value: float, **_kw: Any) -> None:
        if event in LEGS:
            t0 = time.perf_counter()
            self._state().open.append([event, "off"])
            self._listened(t0)

    def _cache_event(self, event: str, **_kw: Any) -> None:
        verdict = CACHE_EVENTS.get(event)
        if verdict is not None:
            t0 = time.perf_counter()
            open_ = self._state().open
            if open_:
                open_[-1][1] = verdict
            self._listened(t0)

    def _leg_ends(self, event: str, seconds: float, fun_name: str = "", **_kw: Any) -> None:
        leg = LEGS.get(event)
        if leg is None:
            return
        t0 = time.perf_counter()
        state = self._state()
        cache = "off"
        if state.open:
            cache = state.open.pop()[1]
        nested = "1" if state.open else "0"
        # jit(f) is how JAX names f once it is lowered
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        program = _programs.get(fun_name, "other")
        if nested == "0":
            state.legs += 1
            if program != "other":
                state.named = (state.legs, program)
        compiled = leg == "compile"
        if compiled and cache == "hit":     # the executable came out of the cache
            leg = "cache_load"
        with self._lock:
            cell = self.build_seconds.setdefault((program, leg, nested), [0.0, 0])
            cell[0] += seconds
            cell[1] += 1
            if compiled:
                self.builds[(program, cache)] = self.builds.get((program, cache), 0) + 1
        self._listened(t0)

    def _listened(self, t0: float) -> None:
        # (unlocked: a lost update here loses microseconds of a diagnostic)
        self.listener_s += time.perf_counter() - t0
        self.listener_calls += 1

    def thread_builds(self) -> int:
        """Legs of a build the calling thread has ended so far: read before a
        call, and :meth:`built_since` after it says what the call had to build."""
        return self._state().legs

    def built_since(self, mark: int) -> Optional[str]:
        """The program the calling thread traced, lowered, compiled or loaded
        since ``mark`` (the last named one, else ``other``); None where it
        built nothing, as every call but a program's first does."""
        state = self._state()
        if state.legs == mark:
            return None
        at, program = state.named
        return program if at > mark else "other"

    # -- what /metrics and the log get ---------------------------------------
    def snapshot(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """The two books as the log lines carry them: (the stages and what the
        listeners themselves cost, the builds by program)."""
        with self._lock:
            seconds: Dict[str, Any] = {}
            for (program, leg, nested), (s, n) in sorted(self.build_seconds.items()):
                seconds.setdefault(program, {}).setdefault(leg, {})[nested] = [round(s, 6), n]
            builds: Dict[str, Any] = {}
            for (program, cache), n in sorted(self.builds.items()):
                builds.setdefault(program, {})[cache] = n
            start = {"stages": {k: round(v, 6) for k, v in self.stage_seconds.items()},
                     "ready_s": self.ready_s, "listener_s": round(self.listener_s, 6),
                     "listener_calls": self.listener_calls}
            return start, {"builds": builds, "build_seconds": seconds}

    def series(self) -> Tuple[Dict[str, float], Dict[tuple, float], Dict[tuple, int]]:
        """(stage -> seconds, (program, leg, nested) -> seconds, (program,
        cache) -> builds) for MetricsRegistry.sync_start."""
        with self._lock:
            return (dict(self.stage_seconds),
                    {k: v[0] for k, v in self.build_seconds.items()},
                    dict(self.builds))


_ledger: Optional[StartLedger] = None


def get_ledger() -> StartLedger:
    global _ledger
    if _ledger is None:
        _ledger = StartLedger()
    return _ledger


def set_ledger(ledger: Optional[StartLedger]) -> None:
    global _ledger
    _ledger = ledger
