#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's file (perf/workloads/<cell>.json) names its configuration
(perf/configs/<config>.json), which names its plane (perf/planes/<plane>.py);
the traffic generator (perf/traffic/<generator>.py), the end-to-end metrics
(perf/metrics/<name>.json) and the per-layer metrics
(perf/layer_metrics/<name>.json, each with a reader in perf/readers/) are found
by name too, and the cell's file says which of them it reports.  This file
knows none of them: a later PR adds cells, configurations and metrics by adding
files, and entries in BENCHMARK.json for the driver (which this file never reads).

This process never imports JAX.  The server is a child started through the
program's normal entry point with JAX_PLATFORMS=tpu; platform, device kind,
count and peak memory are what the child wrote in its own log.  The last line
of standard output is the result; a run that is not on a TPU prints none and
exits non-zero.  --rehearse-cpu runs the same files and code path at the toy
sizes their `rehearse` blocks give, prints `platform: cpu`, and exits 4.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

T_PROCESS_START = time.monotonic()
PERF = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(PERF)
sys.path.insert(0, PERF)

import numpy as np  # noqa: E402

import server as srv  # noqa: E402
import stats  # noqa: E402
from traffic import draw  # noqa: E402

READY_DEADLINE_S = 900.0
EXIT_REHEARSAL, EXIT_FAILED = 4, 1


def load_json(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        data = json.load(f)
    toy = data.pop("rehearse", {})
    return merge(data, toy) if rehearse else data


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Window:
    """The schedule of one run on the event loop's clock: traffic from `start`,
    measured from `open` to `close`, kept up `tail_s` longer so that the last
    measured requests see the same load as the first, then `drain_s` to finish."""

    def __init__(self, start: float, ramp_s: float, seconds: float, tail_s: float,
                 drain_s: float):
        self.start, self.open = start, start + ramp_s
        self.close = self.open + seconds
        self.seconds = seconds
        self.horizon_s = ramp_s + seconds + tail_s
        self.deadline = start + self.horizon_s + drain_s

    async def drain(self, tasks: list) -> None:
        loop = asyncio.get_running_loop()
        if tasks:
            await asyncio.wait(tasks, timeout=max(0.0, self.deadline - loop.time()))
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


class Run:
    def __init__(self, args):
        self.args, self.repo, self.perf_dir = args, REPO, PERF
        self.rehearse = args.rehearse_cpu
        self.platform = "cpu" if self.rehearse else "tpu"
        self.seed, self.traced = args.seed, bool(args.trace)
        self.cell = load_json(
            os.path.join(PERF, "workloads", args.workload + ".json"), self.rehearse)
        self.config = load_json(
            os.path.join(PERF, "configs", self.cell["config"] + ".json"), self.rehearse)
        # the deployment's geometry (slots, cache length) is sized to the traffic
        self.config["server"] = merge(self.config["server"], self.cell.get("server", {}))
        self.out_dir = os.path.join(PERF, "out", args.workload)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.rngs = draw.streams(self.seed)

    @staticmethod
    def note(text: str) -> None:
        print(f"[perf {time.strftime('%H:%M:%S')}] {text}", flush=True)

    @staticmethod
    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def metric_specs(self, group: str, directory: str) -> dict:
        """name -> definition file, for the cell's metrics of one group."""
        out = {}
        for name in self.cell["metrics"][group]:
            with open(os.path.join(PERF, directory, name + ".json")) as f:
                out[name] = json.load(f)
        return out


def by_name(package: str, name: str):
    return importlib.import_module(f"{package}.{name}")


# ---------------------------------------------------------------------------
# the measured part
# ---------------------------------------------------------------------------

async def scraper(plane, scrapes: list, stop: asyncio.Event) -> None:
    """Once a second: the program's histograms are fed from bounded deques
    that only a scrape drains, so a slower scrape loses samples."""
    loop = asyncio.get_running_loop()
    while not stop.is_set():
        t = loop.time()
        try:
            scrapes.append((t, await plane.scrape()))
        except Exception as e:  # noqa: BLE001 - a failed scrape is a hole, not a failed run
            scrapes.append((t, {"error": repr(e)}))
        try:
            await asyncio.wait_for(stop.wait(), timeout=max(0.0, t + 1.0 - loop.time()))
        except asyncio.TimeoutError:
            pass


class Heartbeat:
    """How long this process could not run: a task on the event loop and a
    plain thread each sleep 20 ms at a time and keep the worst overshoot.  A
    late generator reads as a slow server, so both are printed with the
    lateness: a stall of the loop alone is the harness's own code in its way,
    one of both is the machine taking the CPU away."""

    STEP = 0.02

    NOTABLE = 0.25

    def __init__(self):
        self.loop_worst = self.thread_worst = 0.0
        self.loop_at = self.thread_at = None
        self.notable: list = []    # (who, monotonic time, seconds)
        self._stop = threading.Event()

    def _thread(self) -> None:
        while not self._stop.is_set():
            t0 = time.monotonic()
            time.sleep(self.STEP)
            over = time.monotonic() - t0 - self.STEP
            if over > self.NOTABLE:
                self.notable.append(("thread", t0, over))
            if over > self.thread_worst:
                self.thread_worst, self.thread_at = over, t0

    async def run(self) -> None:
        thread = threading.Thread(target=self._thread, daemon=True)
        thread.start()
        loop = asyncio.get_running_loop()
        try:
            while True:
                t0 = loop.time()
                await asyncio.sleep(self.STEP)
                over = loop.time() - t0 - self.STEP
                if over > self.NOTABLE:
                    self.notable.append(("loop", t0, over))
                if over > self.loop_worst:
                    self.loop_worst, self.loop_at = over, t0
        finally:
            self._stop.set()
            thread.join()


async def tracer(run: Run, window: Window, trace_dir: str) -> dict:
    """Ask the traced child for `trace_s` seconds in the middle of the window,
    and wait for it to have written the trace."""
    loop = asyncio.get_running_loop()
    trace_s = min(float(run.cell.get("trace_s", 4.0)), window.seconds)
    await asyncio.sleep(max(0.0, window.open + (window.seconds - trace_s) / 2 - loop.time()))
    with open(os.path.join(trace_dir, "start.tmp"), "w") as f:
        f.write(str(trace_s))
    asked = loop.time()
    os.replace(os.path.join(trace_dir, "start.tmp"), os.path.join(trace_dir, "start"))
    done = os.path.join(trace_dir, "done")
    while not os.path.exists(done):
        if loop.time() > asked + trace_s + 240:
            raise RuntimeError("the traced child did not finish its trace")
        await asyncio.sleep(0.2)
    with open(done) as f:
        return {"asked": asked, **json.load(f)}


async def one_window(run: Run, plane, server: srv.Server, seconds: float) -> dict:
    """Traffic through one ramp, window, tail and drain against a warm server."""
    loop = asyncio.get_running_loop()
    cell = run.cell
    generator = by_name("traffic", cell["traffic"]["generator"])
    out: dict = {"scrapes": [], "trace": None}
    segments = [float(cell["ramp_s"]), seconds, float(cell.get("tail_s", 0.0))]
    plan = generator.build(cell["traffic"], plane.make_request, run.rngs, segments)
    window = Window(loop.time() + 0.5, float(cell["ramp_s"]), seconds,
                    float(cell.get("tail_s", 0.0)), float(cell["drain_s"]))
    out["set_up"] = time.monotonic() - T_PROCESS_START
    out["window"] = window
    run.note(f"set-up {out['set_up']:.1f}s; traffic for {window.horizon_s:.0f}s, "
             f"window {seconds:.0f}s after a ramp of {cell['ramp_s']}s")
    stop = asyncio.Event()
    side = []
    if run.traced:
        side.append(asyncio.ensure_future(scraper(plane, out["scrapes"], stop)))
        trace_task = asyncio.ensure_future(
            tracer(run, window, os.path.join(run.out_dir, "trace")))

    async def mark(at: float, key: str) -> None:
        await asyncio.sleep(max(0.0, at - loop.time()))
        out[key] = server.log_size()

    marks = [asyncio.ensure_future(mark(window.open, "log_open")),
             asyncio.ensure_future(mark(window.close, "log_close"))]
    heartbeat = Heartbeat()
    beating = asyncio.ensure_future(heartbeat.run())
    out["records"] = await generator.run(plan, cell["traffic"], plane.send, window)
    beating.cancel()
    await asyncio.gather(beating, return_exceptions=True)
    out["stalls"] = {
        "loop_worst_ms": heartbeat.loop_worst * 1e3, "thread_worst_ms": heartbeat.thread_worst * 1e3,
        "loop_at_s": heartbeat.loop_at and heartbeat.loop_at - window.open,
        "thread_at_s": heartbeat.thread_at and heartbeat.thread_at - window.open,
        "over_250ms": [(who, round(t - window.open, 2), round(over, 2))
                       for who, t, over in heartbeat.notable]}
    await asyncio.gather(*marks)
    stop.set()
    await asyncio.gather(*side)
    if run.traced:
        out["trace"] = await trace_task
    server.alive_or_die()
    for r in out["records"]:
        r["samples"] = plane.samples(r) if r.get("ok") else {}
        r["share"] = generator.share(r, window)
        r["work"] = plane.work(r["sizes"]) * r["share"]
    out["in_window"] = [r for r in out["records"] if r["share"] > 0]
    out["compiles"] = server.compiles_between(out["log_open"], out["log_close"])
    return out


async def measure(run: Run, plane, server: srv.Server, seconds: float) -> dict:
    """Warm-up and the first half of the correctness probes, one window, the
    second half of the probes."""
    await plane.connect()
    try:
        await plane.probe("before", run.rngs["probe"])
        server.alive_or_die()
        out = await one_window(run, plane, server, seconds)
        await plane.probe("after", run.rngs["probe"])
    finally:
        await plane.close()
    return out


# ---------------------------------------------------------------------------
# from records to metrics
# ---------------------------------------------------------------------------

def end_to_end(name: str, spec: dict, records: list, seconds: float, set_up: float) -> float:
    """One end-to-end metric, as perf/metrics/<name>.json defines it."""
    done = [r for r in records if r.get("ok")]
    if spec["kind"] == "percentile":      # of one sample a request
        values = [r["samples"][spec["sample"]] for r in done if spec["sample"] in r["samples"]]
        return stats.percentile(values, spec["q"]) * spec.get("scale", 1.0)
    if spec["kind"] == "pooled_percentile":   # of a list a request, all requests together
        values = [v for r in done for v in r.get(spec["list"], [])]
        return stats.percentile(values, spec["q"]) * spec.get("scale", 1.0)
    if spec["kind"] == "rate":
        return sum(r["work"] for r in done) / seconds
    if spec["kind"] == "setup":
        return set_up
    raise ValueError(f"metric {name}: unknown kind {spec['kind']!r}")


def describe(run: Run, records: list, in_window: list, window: Window) -> dict:
    """What was drawn and what came back, for the earlier lines and the out dir."""
    def pct(values, q):
        return float(np.percentile(values, q)) if len(values) else None

    late = [(r["sent"] - r["due"]) * 1e3 for r in records if "sent" in r]
    ok = [r for r in in_window if r.get("ok")]
    info = {
        "sent": len(records), "in_window": len(in_window), "succeeded": len(ok),
        "failed": len(in_window) - len(ok),
        "errors": sorted({r.get("error", "unfinished") for r in in_window if not r.get("ok")})[:5],
        "lateness_p50_ms": pct(late, 50), "lateness_p99_ms": pct(late, 99),
        "offered_per_s": len([r for r in records if window.open <= r["due"] < window.close])
        / window.seconds,
        "backlog_at_close": len([r for r in records if r.get("sent", window.close) < window.close
                                 and r.get("done", window.close + 1) >= window.close]),
    }
    for key in sorted({k for r in records for k in r["sizes"]}):
        col = [r["sizes"][key] for r in records]
        info[f"drawn_{key}"] = {"p50": pct(col, 50), "p90": pct(col, 90),
                                "min": min(col), "max": max(col)}
    for key in sorted({k for r in ok for k in r["samples"]}):
        col = [r["samples"][key] for r in ok if key in r["samples"]]
        info[f"{key}"] = {"n": len(col), "p50": pct(col, 50), "p90": pct(col, 90),
                          "p99": pct(col, 99), "mean": float(np.mean(col))}
    gaps = [g for r in ok for g in r.get("gaps", [])]
    if gaps:
        info["token_gap_s"] = {"n": len(gaps), "p50": pct(gaps, 50), "p99": pct(gaps, 99)}
    limits = run.cell.get("limits", {})
    if limits.get("samples") and in_window:
        inside = [r for r in ok if all(r["samples"].get(k, float("inf")) <= v
                                       for k, v in limits["samples"].items())]
        info["share_inside_limits"] = len(inside) / len(in_window)
    return info


class ReaderContext:
    """What a per-layer reader may read: the window's client records, the
    once-a-second scrapes, the reduced device trace, the shapes and the peaks."""

    def __init__(self, run: Run, measured: dict, trace: dict | None, device: dict):
        self.cell, self.config, self.run = run.cell, run.config, run
        self.records = [r for r in measured["in_window"] if r.get("ok")]
        self.window = measured["window"]
        self.scrapes = [(t, s) for t, s in measured["scrapes"]
                        if "error" not in s and self.window.open <= t <= self.window.close]
        self.trace = trace
        with open(os.path.join(PERF, "peaks.json")) as f:
            peaks = json.load(f)
        if device["kind"] not in peaks and not run.rehearse:
            raise KeyError(f"no peaks for device kind {device['kind']!r} in perf/peaks.json")
        self.peaks = peaks.get(device["kind"])
        self.work = by_name("work", run.config["work"])
        self.params: dict = {}


def per_layer(run: Run, specs: dict, ctx: ReaderContext) -> dict:
    out = {}
    for name, spec in specs.items():
        module, _, function = spec["reader"].partition(":")
        ctx.params = spec.get("params", {})
        value = getattr(by_name("readers", module), function)(ctx)
        if value is None:
            run.note(f"{name}: nothing to read")
        else:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def reduce_trace(run: Run) -> dict | None:
    """The trace is read by a helper child with JAX on the CPU."""
    out_path = os.path.join(run.out_dir, "trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "trace.py"),
         os.path.join(run.out_dir, "trace", "profile"), out_path],
        env=srv.child_env("cpu", False, {}), cwd=REPO, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{proc.stderr[-2000:]}")
    with open(out_path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------

def launch(run: Run):
    """The cell's plane and its server, ready and on the platform asked for."""
    plane = by_name("planes", run.config["plane"]).Plane(run)
    plane.prepare()
    cmd = plane.command()
    env = dict(cmd["env"])
    if run.traced:
        os.makedirs(os.path.join(run.out_dir, "trace"))
        env["PERF_TRACE_DIR"] = os.path.join(run.out_dir, "trace")
    argv = (srv.TRACED if run.traced else srv.CLI) + cmd["argv"]
    server = srv.Server(argv, srv.child_env(run.platform, run.traced, env), cmd["cwd"],
                        os.path.join(run.out_dir, "server.log"))
    try:
        server.wait_http(cmd["ready"], READY_DEADLINE_S)
        found = srv.early_platform(server)
        if found != run.platform:
            raise srv.ServerError(f"the server is on platform {found!r}, not {run.platform!r}")
    except BaseException:
        server.kill()
        raise
    run.note(f"server ready {time.monotonic() - T_PROCESS_START:.1f}s after process start")
    return plane, server


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    run = Run(args)
    seconds = float(run.cell.get("seconds", args.seconds)) if run.rehearse else args.seconds
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    server = None
    try:
        plane, server = launch(run)
        measured = asyncio.run(measure(run, plane, server, seconds))
        text = server.stop()
    except (srv.ServerError, RuntimeError) as e:
        run.note(f"FAILED: {e}")
        return EXIT_FAILED
    finally:
        if server is not None:
            server.kill()

    device = srv.device_report(text)
    window, in_window = measured["window"], measured["in_window"]
    info = describe(run, measured["records"], in_window, window)
    compiles = info["compiles_in_window"] = measured["compiles"]
    info["violations"] = plane.violations[:10]
    info["generator_stalls"] = measured["stalls"]
    with open(os.path.join(run.out_dir, "records.json"), "w") as f:
        json.dump([{k: (v - window.open if k in ("due", "sent", "done", "first", "last") and v else v)
                    for k, v in r.items() if k not in ("reply_tokens", "gaps")}
                   for r in measured["records"]], f)
    correct = not plane.violations and compiles == 0
    for k, v in info.items():
        run.note(f"{k}: {json.dumps(v)}")
    run.note(f"device: {json.dumps(device)}")

    trace = reduce_trace(run) if run.traced else None
    if measured["scrapes"]:  # the last scrape, for whoever writes the next reader
        with open(os.path.join(run.out_dir, "last_scrape.json"), "w") as f:
            json.dump(measured["scrapes"][-1][1], f)
    metrics: dict = {}
    try:
        if run.traced:
            ctx = ReaderContext(run, measured, trace, device)
            metrics = per_layer(run, run.metric_specs("per_layer", "layer_metrics"), ctx)
        else:
            for name, spec in run.metric_specs("end_to_end", "metrics").items():
                value = end_to_end(name, spec, in_window, window.seconds, measured["set_up"])
                metrics[name] = {"value": value, "unit": spec["unit"]}
    except stats.TooFewSamples as e:
        run.note(f"FAILED: {e}")
        if not run.rehearse:
            return EXIT_FAILED

    result = {
        "correct": correct, "attempted": info["in_window"], "failed": info["failed"],
        "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]},
    }
    if trace is not None and trace["devices"]:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["ops"], "idle_gaps": trace["gap_labels"]}
    with open(os.path.join(run.out_dir, "result.json"), "w") as f:
        json.dump({"result": result, "info": info, "device": device,
                   "traced": measured["trace"], "args": vars(args)}, f, indent=1)
    if "jax" in sys.modules:
        run.note("FAILED: the benchmark's parent process imported JAX")
        return EXIT_FAILED
    if run.rehearse:
        print(f"REHEARSAL on platform: {device['platform']} - toy sizes, not a result: "
              f"correct={correct} metrics={json.dumps(metrics)}")
        return EXIT_REHEARSAL
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
