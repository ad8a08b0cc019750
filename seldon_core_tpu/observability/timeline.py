"""/debug/timeline: recent per-request flight-recorder timelines plus the
aggregated scaling-signal snapshot.

The REST route (transport/rest.py, on both the component and engine apps)
and its gRPC mirror (``Model/DebugTimeline``, transport/grpc_server.py)
both render through :func:`timeline_report`, so the two transports can
never drift. Schema: docs/observability.md "The /debug/timeline schema".

The scaling block is the per-request-derived half of what ROADMAP item 4
(elastic control plane) consumes: queue depth, slot occupancy, page
pressure and shed totals say how loaded the replica IS; the flight
recorder's TTFT / queue-wait / worst-gap quantiles say what that load is
DOING to requests — the pair a scale controller steers by.

Deliberately read-only and drain-free: unlike ``llm_stats`` (which drains
its observation deques into the /metrics histograms), everything here is a
snapshot — hitting /debug/timeline in a loop never starves the Prometheus
scrape.
"""

from __future__ import annotations

from typing import Any, Optional


def parse_n(raw: Any, default: int = 32) -> int:
    """The shared ``?n=`` / jsonData ``n`` parse for every timeline
    surface (REST component app, REST engine app, gRPC DebugTimeline):
    one clamp, one default — three hand-kept copies would drift."""
    try:
        return int(raw)
    except (TypeError, ValueError):
        return default


def _batcher(component: Any):
    svc = getattr(component, "_batcher_service", None)
    return None if svc is None else svc.batcher


def _recorder(component: Any, batcher: Any):
    fn = getattr(component, "flight_recorder", None)
    if fn is not None:
        return fn()
    return getattr(batcher, "_flight", None) if batcher is not None else None


def timeline_report(component: Any, n: int = 32) -> dict:
    """The /debug/timeline payload for one component. Components without a
    batcher (or with tracing disabled) report ``tracing: false`` with an
    empty timeline list — the endpoint never 500s on configuration."""
    from seldon_core_tpu.tracing import get_tracer

    batcher = _batcher(component)
    recorder = _recorder(component, batcher)
    out: dict = {
        "tracing": recorder is not None,
        "tracer_enabled": get_tracer().enabled,
        "timelines": [],
        "scaling": scaling_snapshot(component, batcher, recorder),
    }
    if recorder is not None:
        out["timelines"] = recorder.timelines(n)
    return out


def scaling_snapshot(component: Any, batcher: Any = None,
                     recorder: Optional[Any] = None) -> dict:
    """The aggregated scaling-signal snapshot (load state + request-latency
    quantiles). Safe on a bare component: absent layers report zeros."""
    if batcher is None:
        batcher = _batcher(component)
    if recorder is None:
        recorder = _recorder(component, batcher)
    snap: dict = {
        "active_slots": 0,
        "total_slots": 0,
        "queue_depth": 0,
        "steps_in_flight": 0,
        "page_pressure": 0.0,
        "page_sheds_total": 0,
        "handoff_queue_depth": 0,
        "draining": False,
        # fleet health (runtime/engine.py ReplicaSet): True when the fleet
        # quarantined this replica after an unplanned death — the
        # autoscaler reads it as a replace signal (docs/control-plane.md);
        # a solo component is never ejected
        "ejected": False,
        "prefill_devices": 0,
        "decode_devices": 0,
        # multi-tenant: queued admissions per SLO class (the weighted-fair
        # scheduler's split of queue_depth — runtime/scheduler.py)
        "queue_by_class": {},
    }
    if batcher is not None:
        snap["active_slots"] = sum(1 for s in batcher._slots if s.active)
        snap["total_slots"] = batcher.S
        sched = batcher._pending
        if hasattr(sched, "depths"):
            # ONE scheduler-lock read: queue_depth derives from the same
            # snapshot as its per-class split, so the two can never
            # disagree within one scaling snapshot
            by_class = sched.depths()
            snap["queue_by_class"] = by_class
            snap["queue_depth"] = sum(by_class.values())
        else:
            snap["queue_depth"] = len(sched)
        snap["steps_in_flight"] = batcher.steps_in_flight()
        snap["draining"] = bool(getattr(batcher, "draining", False))
        pages = batcher.page_stats()
        total = max(pages["kv_pages_total"], 1)
        snap["page_pressure"] = pages["kv_pages_in_use"] / total
        snap["page_sheds_total"] = pages["kv_page_sheds"]
        if getattr(batcher, "_remote", None) is not None:
            snap["handoff_queue_depth"] = (
                batcher.handoff_stats()["handoff_queue_depth"])
            mesh = getattr(batcher, "disagg_mesh", None)
            if mesh is not None:
                # the prefill:decode split the autoscaler's rebalance
                # actuator steers (controlplane/autoscaler.py)
                snap["prefill_devices"] = len(mesh.prefill_devices)
                snap["decode_devices"] = len(mesh.decode_devices)
    if recorder is not None:
        snap["requests"] = recorder.snapshot()
    return snap


def retry_after_hint(component: Any, default_s: float = 1.0) -> float:
    """The transport-side dynamic ``Retry-After`` for shed responses
    (docs/resilience.md "Dynamic backoff"): components with a batcher
    delegate to its backlog-derived hint
    (``ContinuousBatcher.retry_after_hint`` — base x the full drain waves
    queued ahead, doubled near page exhaustion); everything else keeps
    the configured constant.  Wired into
    ``AdmissionController.retry_after_fn`` by the REST/gRPC apps, and
    called OUTSIDE any admission lock."""
    batcher = _batcher(component)
    hint = getattr(batcher, "retry_after_hint", None)
    if hint is None:
        return float(default_s)
    # ``default_s`` is the admission controller's CONFIGURED constant
    # (annotation/env): it stays the floor — the batcher hint (based on
    # its own shed_retry_after_s knob) may only raise backoff above it,
    # never silently undercut an operator's explicit setting
    return max(float(hint()), float(default_s))


def engine_retry_after_hint(engine: Any, default_s: float = 1.0) -> float:
    """The engine-edge variant: the WORST (largest) backlog-derived hint
    among the graph's in-process components, so a shed at the engine edge
    reflects the busiest batcher behind it."""
    comps = getattr(engine, "_components", {}) or {}
    return max((retry_after_hint(c, default_s) for c in comps.values()),
               default=float(default_s))


def wire_retry_after(admission: Any, component: Any = None,
                     engine: Any = None) -> Any:
    """THE one place dynamic shed backoff is wired (docs/resilience.md
    "Dynamic backoff"): installs ``retry_after_fn`` on an
    AdmissionController unless one is already set.  All four transport
    apps (REST/gRPC x component/engine) call this — hand-kept copies of
    the closure were exactly the drift :func:`parse_n` exists to
    prevent.  The fn runs outside the admission lock by the controller's
    contract."""
    if admission.retry_after_fn is not None:
        return admission
    if engine is not None:
        admission.retry_after_fn = (
            lambda: engine_retry_after_hint(engine, admission.retry_after_s))
    elif component is not None:
        admission.retry_after_fn = (
            lambda: retry_after_hint(component, admission.retry_after_s))
    return admission
