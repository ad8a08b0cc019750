"""Readers of the reduced device trace (perf/trace.py)."""

from __future__ import annotations


def program(ctx, needle: str) -> dict | None:
    """Calls and device seconds of the traced programs whose name holds `needle`."""
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    hits = [p for name, p in ctx.trace["programs"].items() if needle in name]
    if not hits:
        return None
    return {"calls": sum(p["calls"] for p in hits),
            "seconds": sum(p["seconds"] for p in hits)}


def idle(ctx) -> float | None:
    """100 x (1 - union of device-op intervals / traced interval)."""
    if not ctx.trace or not ctx.trace["devices"] or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def work_per_call(ctx) -> float | None:
    """Client work a second in the window over program calls a second in the
    trace.  params: program."""
    prog = program(ctx, ctx.params["program"])
    if prog is None or not prog["calls"]:
        return None
    work_rate = sum(r["work"] for r in ctx.records) / ctx.window.seconds
    return work_rate / (prog["calls"] / ctx.trace["window_s"])


def roofline(ctx) -> float | None:
    """Share of its roofline a program reached: the least time the chip could
    take for what one call needs (operations over peak FLOP/s, or bytes over
    peak bytes/s, as `bound` says) over the device time one call took.
    params: program, work (a function of perf/work/<config's work>.py giving
    {"flops", "bytes"} needed per call), bound ("mxu" or "hbm")."""
    prog = program(ctx, ctx.params["program"])
    need = getattr(ctx.work, ctx.params["work"])(ctx)
    if prog is None or need is None or not prog["seconds"]:
        return None
    least = (need["flops"] / ctx.peaks["bf16_flops_per_s"] if ctx.params["bound"] == "mxu"
             else need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (prog["seconds"] / prog["calls"])
