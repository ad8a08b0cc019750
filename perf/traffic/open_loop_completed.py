"""Open loop, judged on what is COMPLETED inside the window (PR 51): `open_loop`'s
schedule and sending to the letter, so the same seed offers the same requests
at the same times; only the crediting differs.  `open_loop` credits a request
whole if it was DUE in the window, however late it came back, so a cell in
which every request is answered in the end reads its offered load whatever
the server does.  Above capacity the queue grows all through the run and what
the server FINISHES a second is its capacity: a request belongs to the window
if its reply ended inside it.  One that was due before the window closed and
never came back belongs to it too, as failed: an overloaded server that drops
requests must not read as a fast one."""

from __future__ import annotations

from traffic.open_loop import build, run  # noqa: F401  (the generator's other two thirds)


def share(rec: dict, window) -> float:
    done = rec.get("done")
    if done is None:
        return 1.0 if rec["due"] < window.close else 0.0
    return 1.0 if window.open <= done < window.close else 0.0
