"""Writes perf/tests/loop_phases.xplane.pb: a trace whose host plane carries the
program's `llm.*` loop phases, small enough to cut the idle time by hand.

The encoder is make_fixture.py's (imported, not copied).  The file's name must
sort before small.xplane.pb: trace.py's find_trace takes the last trace under a
directory, and test_harness.py points it at this one.  Times are microseconds;
test_spans.py's expectations are computed from this table by hand.

  device /device:TPU:0
    XLA Ops   op.1 [0, 100)   op.2 [200, 300)   op.3 [400, 450)   op.4 [600, 700)
              -> idle [100, 200), [300, 400), [450, 600)
  host /host:CPU
    loop      llm.turn [50, 350)   llm.turn [360, 580)   llm.idle [610, 630)
    worker    llm.emit [90, 210) > llm.drain_wait [95, 190)
              llm.prefill [290, 345) > llm.first_token_wait [295, 320), llm.first_token [320, 340)
              llm.admit [460, 500)
              np.asarray [95, 190)   (JAX's own event: not the program's, not read)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from make_fixture import f_bytes, plane  # noqa: E402

DEVICE = {"XLA Ops": [("op.1", 0, 100), ("op.2", 200, 300), ("op.3", 400, 450),
                      ("op.4", 600, 700)]}
HOST = {
    "loop": [("llm.turn", 50, 350), ("llm.turn", 360, 580), ("llm.idle", 610, 630)],
    "worker": [("llm.emit", 90, 210), ("llm.drain_wait", 95, 190), ("np.asarray", 95, 190),
               ("llm.prefill", 290, 345), ("llm.first_token_wait", 295, 320),
               ("llm.first_token", 320, 340), ("llm.admit", 460, 500)],
}


def main() -> None:
    space = f_bytes(1, plane(1, "/device:TPU:0", DEVICE)) + f_bytes(1, plane(2, "/host:CPU", HOST))
    with open(os.path.join(HERE, "loop_phases.xplane.pb"), "wb") as f:
        f.write(space)


if __name__ == "__main__":
    main()
