"""Shared bench-side formatter for the decode-pipeline fields of
``LLMServer.llm_stats()`` (llm_batch_bench).

llm_stats() destructively DRAINS the dispatch/sync/lag deques (the same
contract /metrics scraping relies on), so call this once per measurement
window and reuse the dict — never read the private deques directly next to
a live metrics endpoint."""

from __future__ import annotations

import numpy as np


def pipeline_report(server) -> dict:
    st = server.llm_stats()

    def med_ms(xs):
        return round(1e3 * float(np.median(xs)), 3) if xs else None

    return {
        "depth_config": st.get("decode_pipeline_depth"),
        "fuse_steps": st.get("decode_fuse_steps"),
        "inflight_hwm": st.get("decode_inflight_hwm", 0),
        "dispatch_ms_median": med_ms(st.get("decode_dispatch_times_s")),
        "sync_ms_median": med_ms(st.get("decode_sync_times_s")),
    }
