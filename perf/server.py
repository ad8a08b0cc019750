"""The serving child: started through the program's normal entry point, in its
own process group, its output in a log that is the only source of what is said
about the device.  This process never imports JAX, so the chip is the child's.
The pattern is chip_smoke.py's (PR 21), copied so that the program cannot move it.
"""

from __future__ import annotations

import contextlib
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = [sys.executable, "-m", "seldon_core_tpu.transport.cli"]
TRACED = [sys.executable, os.path.join(REPO, "perf", "launch_traced.py")]

TOPO_RE = re.compile(
    r"detected Topology\(platform=(\w+), device_kind='([^']*)', devices=(\d+)")
MEM_RE = re.compile(
    r"device (\d+) \(([^)]*)\) memory: bytes_in_use=(\w+) "
    r"peak_bytes_in_use=(\w+) bytes_limit=(\w+)")
CACHE_RE = re.compile(r"compile cache at (\S+)")
COMPILE_RE = re.compile(rb"^\d{4}-\d\d-\d\d[^\n]*Compiling \S+ with global shapes", re.M)


class ServerError(RuntimeError):
    pass


def child_env(platform: str, traced: bool, extra: dict) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = platform
    env["JAX_LOG_COMPILES"] = "1"
    # every program goes into the persistent cache, the sub-second ones too,
    # so that a second run finds all of them there
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["TRACING"] = "1" if traced else "0"
    env.update(extra)
    return env


class Server:
    def __init__(self, argv: list, env: dict, cwd: str, log_path: str):
        self.log_path = log_path
        self.t_start = time.monotonic()
        with open(log_path, "wb") as f:
            self.proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=f, stderr=subprocess.STDOUT,
                start_new_session=True)

    def tail(self, n: int = 3000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def alive_or_die(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise ServerError(f"server exited rc={rc}; its log ends:\n{self.tail()}")

    def wait_http(self, url: str, deadline_s: float) -> None:
        deadline = self.t_start + deadline_s
        while time.monotonic() < deadline:
            self.alive_or_die()
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
        raise ServerError(f"server not ready at {url} after {deadline_s:.0f}s; "
                          f"its log ends:\n{self.tail()}")

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def compiles_between(self, begin: int, end: int) -> int:
        """JAX_LOG_COMPILES lines the child wrote between two log offsets."""
        with open(self.log_path, "rb") as f:
            f.seek(begin)
            return len(COMPILE_RE.findall(f.read(end - begin)))

    def stop(self) -> str:
        """SIGINT: the serving commands log each device's memory on the way
        out.  Returns the whole log."""
        self.alive_or_die()
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise ServerError("server did not exit within 90s of SIGINT")
        if rc != 0:
            raise ServerError(f"server exited rc={rc} on SIGINT; its log ends:\n"
                              f"{self.tail()}")
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def kill(self) -> None:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()


def device_report(text: str) -> dict:
    """platform, kind, count and peak memory as the serving process logged them."""
    m = TOPO_RE.search(text)
    if m is None:
        raise ServerError("no 'detected Topology(...)' line in the server's log")
    device = {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}
    rows = MEM_RE.findall(text)
    if len(rows) != device["count"]:
        raise ServerError(f"{len(rows)} device-memory lines for {device['count']} devices")
    peaks = [int(p) for _d, _k, _u, p, _l in rows if p != "None"]
    limits = [int(lim) for _d, _k, _u, _p, lim in rows if lim != "None"]
    device["memory_peak_bytes"] = max(peaks) if peaks else 0
    device["bytes_limit"] = max(limits) if limits else 0
    cache = CACHE_RE.search(text)
    device["cache_dir"] = cache.group(1) if cache else None
    return device


def early_platform(server: Server) -> str | None:
    with open(server.log_path, errors="replace") as f:
        m = TOPO_RE.search(f.read())
    return m.group(1) if m else None
