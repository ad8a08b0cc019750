"""One start of an LLM server, itemised from its log: what each step program
cost before its first result, and each warm-up request's wall.

    python benchmarks/warm_start.py perf/out/<cell>/server.log [more logs ...]

The benchmark's server runs under ``JAX_LOG_COMPILES=1`` (perf/server.py), so
its log has, for every jitted function, the seconds of "Finished tracing +
transforming", of the jaxpr-to-MLIR conversion and of the XLA compilation
(with the persistent cache warm, that is the look-up and the load of the
executable). Prints, a log: when the first line, ``/ready`` and each
``/v1/generate`` reply were written (seconds from the log's first line), and a
row a program of ``PROGRAMS`` in the order they started, with the functions
traced on its behalf (the paged read and the page walk are jitted functions of
their own: models/transformer.py ``paged_live_read``, ops/page_walk.py) listed
beside it: their seconds are INSIDE the program's that called them first. Reads
text only: no JAX, any machine.
"""

import re
import sys
from datetime import datetime

NESTED = ("paged_live_read", "_walk_pages")    # traced inside the program that calls them first
PROGRAMS = ("decode_step", "prefill_chunk", "first_token") + NESTED
STAMP = r"(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d,\d{3})"
TRACED = re.compile(rf"^{STAMP} WARNING jax\._src\.dispatch: Finished tracing \+ transforming "
                    r"(\S+) for pjit in ([\d.e-]+) sec")
LOWERED = re.compile(rf"^{STAMP} WARNING jax\._src\.dispatch: Finished jaxpr to MLIR module "
                     r"conversion jit\((\S+)\) in ([\d.e-]+) sec")
COMPILED = re.compile(rf"^{STAMP} WARNING jax\._src\.dispatch: Finished XLA compilation of "
                      r"jit\((\S+)\) in ([\d.e-]+) sec")
REPLIED = re.compile(rf'^{STAMP} INFO aiohttp\.access: .*"(GET /ready|POST /v1/generate) HTTP')


def seconds(stamp: str) -> float:
    return datetime.strptime(stamp, "%Y-%m-%d %H:%M:%S,%f").timestamp()


def itemise(path: str) -> dict:
    """{"replies": [(what, at_s)], "programs": [{name, at_s, trace_s, lower_s,
    compile_s}], "others": {"trace_s", "lower_s", "compile_s"}} of one log."""
    start, replies, programs = None, [], []
    others = dict(trace_s=0.0, lower_s=0.0, compile_s=0.0)
    open_rows = {}
    with open(path, errors="replace") as f:
        for line in f:
            if start is None and (m := re.match(STAMP, line)):
                start = seconds(m.group(1))
            if m := REPLIED.match(line):
                if m.group(2) != "GET /ready" or not any(w == "GET /ready" for w, _ in replies):
                    replies.append((m.group(2), seconds(m.group(1)) - start))
                continue
            for pattern, key in ((TRACED, "trace_s"), (LOWERED, "lower_s"), (COMPILED, "compile_s")):
                if not (m := pattern.match(line)):
                    continue
                name, took = m.group(2), float(m.group(3))
                if name not in PROGRAMS:
                    others[key] += took
                elif key == "trace_s":
                    row = dict(name=name, at_s=seconds(m.group(1)) - took - start,
                               trace_s=took, lower_s=0.0, compile_s=0.0)
                    programs.append(row)
                    open_rows.setdefault(name, []).append(row)
                elif open_rows.get(name):
                    row = open_rows[name][0]
                    row[key] = took
                    if key == "compile_s":
                        open_rows[name].pop(0)
    return dict(replies=replies, programs=programs, others=others)


def main() -> int:
    for path in sys.argv[1:]:
        found = itemise(path)
        print(path)
        print("  " + "  ".join(f"{what.split('/')[-1]} {at:.1f}s" for what, at in found["replies"][:8]))
        for row in found["programs"]:
            if row["name"] in NESTED and row["trace_s"] < 0.005:
                continue    # a later layer's call: the trace was there
            print(f"  {row['name']:14s} from {row['at_s']:6.1f}s  trace {row['trace_s']:6.2f}  "
                  f"to MLIR {row['lower_s']:6.2f}  compile or load {row['compile_s']:6.2f}")
        o = found["others"]
        print(f"  every other function: trace {o['trace_s']:.2f}  to MLIR {o['lower_s']:.2f}  "
              f"compile or load {o['compile_s']:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
