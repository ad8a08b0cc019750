"""Writes perf/tests/host_turn.xplane.pb: a trace whose host plane carries what
the program writes since PR 33, small enough to cut by hand: the loop's phases
with their parts nested in them, `hop` measured (`llm.hop.loop`, and the two
wake legs of each hand-off), and the transport thread's `http.*` spans.

The encoder is make_fixture.py's (imported, not copied); the file's name sorts
before small.xplane.pb, as loop_phases.xplane.pb's must (make_spans_fixture.py).
Times are microseconds; test_turn.py's expectations are computed from this
table by hand.

  device /device:TPU:0
    XLA Ops   op.1 [0, 100)   op.2 [200, 300)   op.3 [400, 500)   op.4 [700, 800)
              -> idle [100, 200), [300, 400), [500, 700)
  host /host:CPU
    loop      llm.turn [50, 380):  hop.loop [50, 90)  wake_worker [90, 110)  wake_loop [212, 230)
                                   hop.loop [230, 290)  wake_worker [290, 310)  wake_loop [370, 376)
                                   hop.loop [376, 380)        ([210, 212) is llm.turn alone)
              llm.turn [390, 760): hop.loop [390, 410)  wake_worker [410, 520)  wake_loop [600, 690)
                                   hop.loop [690, 760)
    worker    llm.dispatch [110, 210) > .pages [115, 125), .call [125, 195), .book [195, 205)
              PjitFunction(decode_step) [126, 190)      (JAX's own event: not read)
              llm.emit [310, 370) > llm.drain_wait [312, 350) > .asides [340, 350)
                                  > llm.emit.slots [352, 368) > llm.emit.finish [360, 366)
              llm.prefill [520, 600) > .build [522, 530), .call [530, 590), .activate [590, 598)
    transport http.sse_write [120, 150)   http.scrape [430, 560)   http.parse [610, 640)
              http.reply [650, 655)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from make_fixture import f_bytes, plane  # noqa: E402

DEVICE = {"XLA Ops": [("op.1", 0, 100), ("op.2", 200, 300), ("op.3", 400, 500),
                      ("op.4", 700, 800)]}
HOST = {
    "loop": [("llm.turn", 50, 380), ("llm.hop.loop", 50, 90), ("llm.hop.wake_worker", 90, 110),
             ("llm.hop.wake_loop", 212, 230), ("llm.hop.loop", 230, 290),
             ("llm.hop.wake_worker", 290, 310), ("llm.hop.wake_loop", 370, 376),
             ("llm.hop.loop", 376, 380), ("llm.turn", 390, 760), ("llm.hop.loop", 390, 410),
             ("llm.hop.wake_worker", 410, 520), ("llm.hop.wake_loop", 600, 690),
             ("llm.hop.loop", 690, 760)],
    "worker": [("llm.dispatch", 110, 210), ("llm.dispatch.pages", 115, 125),
               ("llm.dispatch.call", 125, 195), ("PjitFunction(decode_step)", 126, 190),
               ("llm.dispatch.book", 195, 205), ("llm.emit", 310, 370),
               ("llm.drain_wait", 312, 350), ("llm.drain_wait.asides", 340, 350),
               ("llm.emit.slots", 352, 368), ("llm.emit.finish", 360, 366),
               ("llm.prefill", 520, 600), ("llm.prefill.build", 522, 530),
               ("llm.prefill.call", 530, 590), ("llm.prefill.activate", 590, 598)],
    "transport": [("http.sse_write", 120, 150), ("http.scrape", 430, 560),
                  ("http.parse", 610, 640), ("http.reply", 650, 655)],
}


def main() -> None:
    space = f_bytes(1, plane(1, "/device:TPU:0", DEVICE)) + f_bytes(1, plane(2, "/host:CPU", HOST))
    with open(os.path.join(HERE, "host_turn.xplane.pb"), "wb") as f:
        f.write(space)


if __name__ == "__main__":
    main()
