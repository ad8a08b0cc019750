"""Writes perf/tests/small.xplane.pb: a trace small enough to reduce by hand.

An XSpace (tsl/profiler/protobuf/xplane.proto) with one device plane and one
host plane, encoded field by field so that the fixture needs nothing but this
file.  Times below are microseconds; the test's expectations are computed from
the same table by hand (see test_harness.py).

  device /device:TPU:0
    XLA Modules  jit_step(1) [0, 100)      jit_step(1) [200, 300)   jit_other(2) [400, 450)
    XLA Ops      loop [0, 100) > fusion.1 [10, 40), fusion.2 [50, 90)
                 fusion.1 [200, 260)  copy.3 [250, 300)  (overlapping)
                 fusion.1 [400, 450)
  host /host:CPU
    main         dispatch [90, 210)   wait [295, 395)   tiny [300, 301)
"""

import os

US = 1_000_000  # picoseconds


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def f_int(number: int, value: int) -> bytes:
    return varint(number << 3) + varint(value)


def f_bytes(number: int, payload: bytes) -> bytes:
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def plane(plane_id: int, name: str, lines: dict) -> bytes:
    """lines: {line name: [(event name, start_us, end_us)]}."""
    names = sorted({e[0] for events in lines.values() for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = f_int(1, plane_id) + f_bytes(2, name.encode())
    for line_id, (line_name, events) in enumerate(lines.items(), 1):
        line = f_int(1, line_id) + f_bytes(2, line_name.encode()) + f_int(3, 0)
        for ev_name, start, end in events:
            line += f_bytes(4, f_int(1, ids[ev_name]) + f_int(2, start * US)
                            + f_int(3, (end - start) * US))
        body += f_bytes(3, line)
    for n, i in ids.items():
        meta = f_int(1, i) + f_bytes(2, n.encode())
        body += f_bytes(4, f_int(1, i) + f_bytes(2, meta))   # map<int64, XEventMetadata>
    return body


DEVICE = {
    "XLA Modules": [("jit_step(1)", 0, 100), ("jit_step(1)", 200, 300), ("jit_other(2)", 400, 450)],
    "XLA Ops": [("loop", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 90),
                ("fusion.1", 200, 260), ("copy.3", 250, 300), ("fusion.1", 400, 450)],
}
HOST = {"main": [("dispatch", 90, 210), ("wait", 295, 395), ("tiny", 300, 301)]}


def main() -> None:
    space = f_bytes(1, plane(1, "/device:TPU:0", DEVICE)) + f_bytes(1, plane(2, "/host:CPU", HOST))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "small.xplane.pb"), "wb") as f:
        f.write(space)


if __name__ == "__main__":
    main()
