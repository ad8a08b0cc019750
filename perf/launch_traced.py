"""The traced child: the program's own `cli.main(argv)` in this process, plus a
control thread that starts and stops `jax.profiler` when the benchmark asks.
Only the process that holds the chip can trace it, and neither benchmarked
server has a /profile route (PERF.md section 7), so the benchmark wraps the
normal entry point without editing the program.

The benchmark writes `<PERF_TRACE_DIR>/start` holding the seconds to trace; this
thread writes the trace under `<PERF_TRACE_DIR>/profile` and then
`<PERF_TRACE_DIR>/done` holding the seconds start_trace..stop_trace took.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def control(trace_dir: str) -> None:
    start = os.path.join(trace_dir, "start")
    while not os.path.exists(start):
        time.sleep(0.05)
    with open(start) as f:
        seconds = float(f.read())
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host it measures
    options.host_tracer_level = 2
    t0 = time.monotonic()
    jax.profiler.start_trace(os.path.join(trace_dir, "profile"),
                             profiler_options=options)
    t1 = time.monotonic()
    time.sleep(seconds)
    t2 = time.monotonic()
    jax.profiler.stop_trace()
    t3 = time.monotonic()
    with open(os.path.join(trace_dir, "done.tmp"), "w") as f:
        json.dump({"start_s": t1 - t0, "traced_s": t2 - t1, "stop_s": t3 - t2}, f)
    os.replace(os.path.join(trace_dir, "done.tmp"), os.path.join(trace_dir, "done"))


def main() -> None:
    threading.Thread(target=control, args=(os.environ["PERF_TRACE_DIR"],),
                     daemon=True).start()
    from seldon_core_tpu.transport import cli

    cli.main(sys.argv[1:])


if __name__ == "__main__":
    main()
