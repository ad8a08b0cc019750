#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two product planes once, at full width, through the commands a
user would type, and checks what comes back:

  LLM    ``python -m seldon_core_tpu.transport.cli microservice Llama7BInt8
         REST`` on examples/llm_7b_int8/Llama7BInt8.py (Llama-2-7B width,
         int8 weights, random from a seed, continuous batching, every other
         option at its default), then /v1/generate over HTTP: one request,
         a seeded pair that must match, an SSE stream, eight concurrent
         requests, a prompt longer than one prefill chunk; then /metrics.
  graph  ``make -C native clean all``, a seeded ResNet-50 checkpoint,
         ``... cli edge --spec <JAX_SERVER leaf>``: REST tensor predictions
         of batch 1 and 64 and one gRPC Seldon/Predict, on the DEVICE_MODEL
         plane (native edge -> ring -> ModelExecutor).

THIS process never imports JAX: a chip belongs to one process, so every
server is a child, one at a time, started with JAX_PLATFORMS=tpu (JAX then
refuses to start rather than fall back) and killed by process group before
the next. What is reported about the device — platform, device_kind, count,
memory, compile cache — is what the SERVING process wrote in its own log.

    python chip_smoke.py                 one chip, both phases
    python chip_smoke.py --tp 4          four chips: the LLM phase with
                                         tensor_parallel=4, the per-device
                                         memory spread checked, then a
                                         remote-prefill arm (recorded, does
                                         not gate)
    python chip_smoke.py --rehearse-cpu  toy dims on the CPU, to debug this
                                         script; prints platform: cpu and
                                         exits 4 — never a chip result

Last line of stdout on success: {"ok": true, "device": {...}}. Any failed
check, any child not on a TPU, any child that dies, exits non-zero with no
such line. Speeds are NOT measured here: the timings printed are start-up
and compile costs, for PERF.md's "cold vs warm" record.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chip_smoke_out")
CLI = [sys.executable, "-m", "seldon_core_tpu.transport.cli"]

READY_DEADLINE_S = 600.0   # 7B: streamed init + first compiles
REQUEST_TIMEOUT_S = 600.0  # the first request compiles the step programs


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"CHECK FAILED: {what}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str, **extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = platform
    env.update(extra)
    return env


def tail(path: str, n: int = 4000) -> str:
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - n))
        return f.read().decode(errors="replace")


class Server:
    """One serving child in its own process group, its output in a log."""

    def __init__(self, name: str, argv: list, env: dict, cwd: str):
        self.name = name
        self.log_path = os.path.join(OUT, f"{name}.log")
        self.t_start = time.monotonic()
        with open(self.log_path, "wb") as f:
            self.proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=f, stderr=subprocess.STDOUT,
                start_new_session=True)
        log(f"{name}: started pid {self.proc.pid}: {' '.join(argv[2:])}")

    def alive_or_die(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SystemExit(
                f"{self.name} exited rc={rc} while in use; its log ends:\n"
                f"{tail(self.log_path)}")

    def wait_http(self, port: int, path: str) -> float:
        """Seconds from process start until ``path`` answers 200."""
        deadline = self.t_start + READY_DEADLINE_S
        while time.monotonic() < deadline:
            self.alive_or_die()
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - self.t_start
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
        raise SystemExit(
            f"{self.name} not ready on {path} after {READY_DEADLINE_S:.0f}s; "
            f"its log ends:\n{tail(self.log_path)}")

    def stop(self) -> str:
        """Interrupt the leader (the commands log their device footprint on
        the way out), wait, and return the whole log."""
        self.alive_or_die()
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise SystemExit(f"{self.name} did not exit within 90s of SIGINT")
        if rc != 0:
            raise SystemExit(f"{self.name} exited rc={rc} on SIGINT; its log "
                             f"ends:\n{tail(self.log_path)}")
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def kill(self) -> None:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()


@contextlib.contextmanager
def serving(name: str, argv: list, env: dict, cwd: str = REPO):
    srv = Server(name, argv, env, cwd)
    try:
        yield srv
    finally:
        srv.kill()  # whole group, whatever happened above


def post_json(url: str, body: dict, timeout: float = REQUEST_TIMEOUT_S):
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=timeout) as r:
        check(r.status == 200, f"POST {url} -> {r.status}")
        payload = r.read()
    return json.loads(payload), time.monotonic() - t0


# ---------------------------------------------------------------------------
# what the serving process said about itself
# ---------------------------------------------------------------------------

TOPO_RE = re.compile(
    r"detected Topology\(platform=(\w+), device_kind='([^']*)', devices=(\d+)")
MEM_RE = re.compile(
    r"device (\d+) \(([^)]*)\) memory: bytes_in_use=(\w+) "
    r"peak_bytes_in_use=(\w+) bytes_limit=(\w+)")
CACHE_RE = re.compile(r"compile cache at (\S+)")


def device_report(text: str, want_platform: str, name: str) -> dict:
    m = TOPO_RE.search(text)
    check(m is not None, f"{name}: no 'detected Topology(...)' line in its log")
    device = {"platform": m.group(1), "kind": m.group(2),
              "count": int(m.group(3))}
    check(device["platform"] == want_platform,
          f"{name} served on platform {device['platform']!r}, "
          f"not {want_platform!r}")
    def num(field: str):  # CPU devices keep no stats: the log says None
        return None if field == "None" else int(field)

    memory = [
        {"device": int(d), "bytes_in_use": num(u), "peak_bytes_in_use": num(p),
         "bytes_limit": num(lim)}
        for d, _kind, u, p, lim in MEM_RE.findall(text)]
    check(len(memory) == device["count"],
          f"{name}: {len(memory)} device-memory lines for "
          f"{device['count']} devices")
    cache = CACHE_RE.search(text)
    check(cache is not None, f"{name}: no 'compile cache at' line in its log")
    return {"device": device, "memory": memory, "cache_dir": cache.group(1)}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def gb(n) -> str:
    return "n/a" if n is None else f"{n / 1e9:.2f} GB"


def machine_limits() -> str:
    """What this machine lets a child create: the edge's rings are mapped
    files on /dev/shm, and a refusal there is read against these."""
    import resource

    def soft(which: int) -> str:
        n = resource.getrlimit(which)[0]
        return "unlimited" if n == resource.RLIM_INFINITY else f"{n} bytes"

    shm = "absent"
    if os.path.isdir("/dev/shm"):
        st = os.statvfs("/dev/shm")
        shm = f"{gb(st.f_bavail * st.f_frsize)} free"
    return (f"file size limit {soft(resource.RLIMIT_FSIZE)}, address space "
            f"limit {soft(resource.RLIMIT_AS)}, /dev/shm {shm}")


# ---------------------------------------------------------------------------
# phase LLM
# ---------------------------------------------------------------------------

def typed(name: str, value, kind: str) -> dict:
    return {"name": name, "value": str(value), "type": kind}


def phase_llm(name: str, platform: str, rehearse: bool, params: list,
              xla_devices: int = 0) -> dict:
    """Serve Llama7BInt8 through ``cli microservice ... REST`` and drive
    /v1/generate. ``params`` are typed unit parameters (constructor
    overrides), the way a deployment passes them."""
    vocab, n_new, long_len = 32000, 32, 300  # long prompt > prefill_chunk 256
    if rehearse:
        # toy dims: same code path, can never pass for a chip
        params = params + [
            typed("model", "llama-tiny", "STRING"), typed("quantize", "", "STRING"),
            typed("kv_page_size", 8, "INT"), typed("prefill_chunk", 16, "INT"),
            typed("max_new_tokens", 8, "INT")]
        vocab, n_new, long_len = 256, 8, 40
    port = free_port()
    extra = {"PREDICTIVE_UNIT_PARAMETERS": json.dumps(params)}
    if xla_devices:
        extra["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={xla_devices}")
    url = f"http://127.0.0.1:{port}/v1/generate"
    out: dict = {"params": {p["name"]: p["value"] for p in params}}

    def generate(prompt: str, **kw):
        body, dt = post_json(url, {"prompt": prompt, "max_new_tokens": n_new, **kw})
        toks = body["tokens"]
        check(len(toks) == n_new, f"{name}: {len(toks)} tokens, wanted {n_new}")
        check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
              f"{name}: token id outside [0, {vocab}): {toks}")
        return body, dt

    argv = CLI + ["microservice", "Llama7BInt8", "REST", "--port", str(port),
                  "--host", "127.0.0.1"]
    with serving(name, argv, child_env(platform, **extra),
                 cwd=os.path.join(REPO, "examples", "llm_7b_int8")) as srv:
        out["start_to_ready_s"] = round(srv.wait_http(port, "/ready"), 1)
        log(f"{name}: ready after {out['start_to_ready_s']}s")

        _, dt = generate("The first request builds the slot pool and compiles.")
        out["first_request_s"] = round(dt, 1)
        log(f"{name}: first request {out['first_request_s']}s")
        srv.alive_or_die()

        later = []
        a, dt = generate("Seeded requests must repeat exactly.", seed=1234)
        later.append(dt)
        b, dt = generate("Seeded requests must repeat exactly.", seed=1234)
        later.append(dt)
        check((a["tokens"], a["text"]) == (b["tokens"], b["text"]),
              f"{name}: same prompt + seed gave {a['tokens']} then {b['tokens']}")

        # SSE: one event per token, then the done event with all of them
        req = urllib.request.Request(
            url, data=json.dumps({"prompt": "Stream this.", "stream": True,
                                  "max_new_tokens": n_new}).encode())
        t0 = time.monotonic()
        events = []
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
            check(r.status == 200, f"{name}: SSE -> {r.status}")
            for line in r:
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
        later.append(time.monotonic() - t0)
        streamed = [e["token"] for e in events if "token" in e]
        check(events and events[-1].get("done") is True,
              f"{name}: SSE stream did not end with a done event: {events[-1:]}")
        check(streamed == events[-1]["tokens"] and len(streamed) == n_new,
              f"{name}: SSE streamed {len(streamed)} tokens, done event "
              f"carries {len(events[-1]['tokens'])}, wanted {n_new}")

        # eight at once: they share the decode batch (a failed check in a
        # worker re-raises here when its result is read)
        t0 = time.monotonic()
        with ThreadPoolExecutor(8) as pool:
            list(pool.map(
                lambda i: generate(f"Concurrent request number {i}."), range(8)))
        out["eight_concurrent_s"] = round(time.monotonic() - t0, 2)

        # longer than one prefill chunk: admission prefills piecewise
        long_prompt = ("chunked prefill " * 40)[:long_len]
        body, dt = generate(long_prompt)
        out["long_prompt_first_s"] = round(dt, 1)  # compiles the 256 chunk
        check("truncated_prompt" not in body or rehearse,
              f"{name}: the {long_len}-token prompt was truncated: {body}")
        _, dt = generate(long_prompt)
        later.append(dt)
        out["later_requests_s"] = [round(x, 2) for x in later]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            metrics = r.read().decode()
        in_use = re.search(r"^seldon_llm_kv_pages_in_use(?:\{[^}]*\})? (\S+)$",
                           metrics, re.M)
        check(in_use is not None and float(in_use.group(1)) == 0.0,
              f"{name}: seldon_llm_kv_pages_in_use not back at 0 when idle: "
              f"{in_use and in_use.group(1)}")
        lag = {m.group(1): float(m.group(2)) for m in re.finditer(
            r'^seldon_llm_decode_host_lag_steps_bucket\{[^}]*le="([^"]+)"[^}]*\} (\S+)$',
            metrics, re.M)}
        check("+Inf" in lag and lag["+Inf"] - lag.get("1.0", 0.0) > 0,
              f"{name}: no decode drain saw the host >= 2 steps behind the "
              f"device (pipeline never ran ahead): {lag}")
        out["host_lag_ge2_drains"] = int(lag["+Inf"] - lag["1.0"])
        text = srv.stop()

    out.update(device_report(text, platform, name))
    read = re.search(r"decode read: (\w+)", text)
    check(read is not None, f"{name}: no 'decode read:' line in its log")
    out["paged_decode_read"] = read.group(1)
    return out


# ---------------------------------------------------------------------------
# phase graph
# ---------------------------------------------------------------------------

EXPORT = """
import jax, numpy as np
from seldon_core_tpu.models import get_model
from seldon_core_tpu.servers.jaxserver import export_checkpoint
side, buckets, out = {side}, {buckets}, {out!r}
model = get_model("resnet50")
variables = jax.jit(lambda k, x: model.init(k, x, train=False))(
    jax.random.PRNGKey(0), np.zeros((1, side, side, 3), np.float32))
export_checkpoint(out, "resnet50", variables, input_shape=[side, side, 3],
                  apply_kwargs={{"train": False}}, batch_buckets=buckets,
                  use_orbax=False)
"""


def phase_graph(platform: str, rehearse: bool) -> dict:
    """ResNet-50 behind ``cli edge`` on the DEVICE_MODEL plane, binaries
    built here from what git would commit."""
    import numpy as np  # the parent may use numpy; it may not use JAX

    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.transport import grpc_client

    side, buckets, big = (96, [1, 8], 8) if rehearse else (224, [1, 8, 64], 64)
    out: dict = {"input": [side, side, 3], "batch_buckets": buckets}

    t0 = time.monotonic()
    subprocess.run(["make", "-C", os.path.join(REPO, "native"), "clean", "all"],
                   check=True, stdout=subprocess.DEVNULL)
    out["make_clean_all_s"] = round(time.monotonic() - t0, 1)
    log(f"graph: make -C native clean all took {out['make_clean_all_s']}s")

    # weights from a seed, made on the CPU by a tool step of its own: the
    # chip is for the server
    ckpt = os.path.join(OUT, "resnet50_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    subprocess.run(
        [sys.executable, "-c",
         EXPORT.format(side=side, buckets=buckets, out=ckpt)],
        check=True, env=child_env("cpu"), cwd=REPO)
    spec_path = os.path.join(OUT, "graph.json")
    with open(spec_path, "w") as f:
        json.dump({"name": "smoke", "graph": {
            "name": "resnet50", "type": "MODEL",
            "implementation": "JAX_SERVER", "modelUri": ckpt}}, f)

    port, gport = free_port(), free_port()
    argv = CLI + ["edge", "--spec", spec_path, "--port", str(port),
                  "--grpc-port", str(gport)]
    rng = np.random.default_rng(0)
    images = rng.random((big, side, side, 3)).round(3)

    def tensor(batch) -> dict:
        return {"data": {"tensor": {"shape": list(batch.shape),
                                    "values": batch.ravel().tolist()}}}

    def logits(body: dict, n: int) -> "np.ndarray":
        t = body["data"]["tensor"]
        check(t["shape"] == [n, 1000], f"graph: output shape {t['shape']}, "
              f"wanted [{n}, 1000]")
        arr = np.asarray(t["values"], np.float64).reshape(n, 1000)
        check(bool(np.isfinite(arr).all()), "graph: non-finite logits")
        return arr

    try:
        with serving("graph", argv, child_env(platform)) as srv:
            out["start_to_ready_s"] = round(srv.wait_http(port, "/ready"), 1)
            log(f"graph: ready after {out['start_to_ready_s']}s "
                f"(load + every bucket warmed)")
            url = f"http://127.0.0.1:{port}/api/v0.1/predictions"
            body, dt = post_json(url, tensor(images[:1]))
            out["first_request_s"] = round(dt, 2)
            one = logits(body, 1)
            later = []
            body, dt = post_json(url, tensor(images))
            later.append(dt)
            many = logits(body, big)
            body, dt = post_json(url, tensor(images[:1]))
            later.append(dt)
            check(bool((logits(body, 1) == one).all()),
                  "graph: the same batch-1 request gave different logits")
            t0 = time.monotonic()
            reply = grpc_client.call_sync(
                f"127.0.0.1:{gport}", "Predict",
                SeldonMessage.from_dict(tensor(images[:1])), service="Seldon",
                timeout_s=120.0)
            later.append(time.monotonic() - t0)
            via_grpc = logits(reply.to_dict(), 1)
            out["later_requests_s"] = [round(x, 2) for x in later]
            # REST and gRPC hit the same batch-1 program; the batch-`big`
            # program is another compile of the same bf16 math
            scale = float(np.abs(one).max())
            check(bool(np.allclose(via_grpc, one, rtol=0, atol=1e-6 * scale)),
                  "graph: gRPC and REST disagree on the same image")
            err = float(np.abs(many[:1] - one).max())
            check(err <= 0.05 * scale,
                  f"graph: row 0 of the batch-{big} reply is {err:.4g} from "
                  f"the batch-1 reply (logit scale {scale:.4g}); bf16 allows "
                  f"{0.05 * scale:.4g}")
            check(float(np.abs(many[0] - many[1]).max()) > 0,
                  "graph: two different images gave identical logits")
            out["batch_row_vs_single_max_abs"] = err
            out["logit_scale"] = scale
            text = srv.stop()
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)  # ~100 MB: not left in the tree

    native = re.search(r"graph compiled natively with (\d+) device model", text)
    check(native is not None and native.group(1) == "1",
          "graph: the server's log does not say the graph compiled natively "
          "with one device model — this was the ring fallback, another plane")
    out.update(device_report(text, platform, "graph"))
    return out


# ---------------------------------------------------------------------------

def memory_spread(memory: list, name: str) -> None:
    """Every device holds a share of the params and the KV pool."""
    used = [m["bytes_in_use"] for m in memory]
    check(all(u is not None for u in used),
          f"{name}: a device reports no bytes_in_use: {memory}")
    check(min(used) > 0.5e9 and max(used) <= 2 * min(used),
          f"{name}: per-device bytes_in_use {[gb(u) for u in used]} — not "
          f"every device holds a comparable share (everything on device 0 "
          f"is what a batcher that ignores the mesh looks like)")


def summarize(name: str, r: dict) -> None:
    d = r["device"]
    log(f"{name}: platform: {d['platform']}  device_kind: {d['kind']}  "
        f"devices: {d['count']}   (from the serving process's log)")
    for k in ("params", "paged_decode_read", "make_clean_all_s",
              "start_to_ready_s", "first_request_s", "long_prompt_first_s",
              "eight_concurrent_s", "later_requests_s", "host_lag_ge2_drains",
              "batch_row_vs_single_max_abs"):
        if k in r:
            log(f"{name}:   {k}: {r[k]}")
    for m in r["memory"]:
        log(f"{name}:   device {m['device']}: in use {gb(m['bytes_in_use'])}, "
            f"peak {gb(m['peak_bytes_in_use'])} of {gb(m['bytes_limit'])}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="LLM phase with tensor_parallel=N (N chips)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy dims on the CPU; never a chip result")
    args = ap.parse_args()
    platform = "cpu" if args.rehearse_cpu else "tpu"
    os.makedirs(OUT, exist_ok=True)
    # a terminated smoke still unwinds through serving()'s finally, which
    # kills the server's process group
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    # where the children will keep compiled programs (the rule of
    # seldon_core_tpu.utils.configure_compile_cache; each child's log
    # confirms the directory it used)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    entries_before = cache_entries(cache)
    log(f"machine: {machine_limits()}")
    t_all = time.monotonic()

    phases: dict = {}
    if args.tp:
        phases["llm_tp"] = phase_llm(
            "llm_tp", platform, args.rehearse_cpu,
            [typed("tensor_parallel", args.tp, "INT")],
            xla_devices=args.tp if args.rehearse_cpu else 0)
        check(phases["llm_tp"]["device"]["count"] == args.tp,
              f"--tp {args.tp} ran on {phases['llm_tp']['device']['count']} devices")
        if not args.rehearse_cpu:  # CPU devices keep no memory stats
            memory_spread(phases["llm_tp"]["memory"], "llm_tp")
    else:
        phases["llm"] = phase_llm("llm", platform, args.rehearse_cpu, [])
        phases["graph"] = phase_graph(platform, args.rehearse_cpu)

    for name, r in phases.items():
        check(r["cache_dir"] == cache,
              f"{name} kept its compile cache at {r['cache_dir']}, not {cache}")
        summarize(name, r)
    log(f"compile cache: {cache}  entries before: {entries_before}  "
        f"after: {cache_entries(cache)}")
    log(f"total {time.monotonic() - t_all:.0f}s")

    if args.tp:
        # Second arm, recorded, never gating: real device-to-device KV
        # handoff. Its failure is an outcome, so it alone is caught.
        try:
            r = phase_llm(
                "llm_remote_prefill", platform, args.rehearse_cpu,
                [typed("disaggregation", "remote_prefill", "STRING"),
                 typed("prefill_devices", 1, "INT")],
                xla_devices=args.tp if args.rehearse_cpu else 0)
            summarize("llm_remote_prefill", r)
            log("remote_prefill arm: PASSED")
        except (SystemExit, Exception) as e:  # noqa: BLE001 — outcome, not gate
            log(f"remote_prefill arm: FAILED (recorded, not gating): "
                f"{str(e)[:3000]}")

    check("jax" not in sys.modules, "the smoke's parent process imported JAX")
    device = next(iter(phases.values()))["device"]
    if args.rehearse_cpu:
        print("REHEARSAL PASSED on platform: cpu — toy dims, not a chip result")
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        raise SystemExit(4)
    with open(os.path.join(OUT, "result.json"), "w") as f:
        json.dump(phases, f, indent=1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
