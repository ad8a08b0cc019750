"""Concurrent-vs-sequential LLM serving throughput (VERDICT r3 item 3).

Measures tokens/s for N clients served (a) sequentially — each waits for the
previous, the per-request ``generate()`` world — versus (b) concurrently
through the shared ContinuousBatcher (one in-flight decode batch, requests
join/leave between steps). Writes benchmarks/report_llm_concurrent.json.

Run with --tpu for the 0.7B bench config on the real chip; the default is a
small CPU config — a rehearsal of counts and ratios, never a device number
(ROADMAP A1 replaces this script).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def _remote_hop_phase(wire_format, array, reps=64):
    """One arm of the wire-format A/B (ISSUE 18): ``reps`` sequential
    predict_raw round trips over a loopback REST hop — RemoteComponent on
    one end, ``make_component_app`` over an echo component on the other —
    with the tensor body encoded per ``wire_format``. Both ends live in
    this process, so for frames the codec's own timers hold all four
    serialization legs (client+server, encode+decode); for JSON the same
    four legs are microbenched outside the hop (they run inside aiohttp
    handlers where they can't be isolated)."""
    import asyncio
    import socket

    from aiohttp import web

    from seldon_core_tpu.codec import framing
    from seldon_core_tpu.contracts.graph import Endpoint
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.runtime.remote import RemoteComponent
    from seldon_core_tpu.transport.rest import make_component_app

    class _Echo:
        def predict(self, X, names, meta=None):
            return X

    msg = SeldonMessage.from_array(array)
    body_bytes = (len(framing.encode_message(msg)) if wire_format == "frame"
                  else len(json.dumps(msg.to_dict()).encode()))

    async def go():
        app = make_component_app(_Echo())
        runner = web.AppRunner(app)
        await runner.setup()
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        site = web.SockSite(runner, sock)
        await site.start()
        comp = RemoteComponent(
            Endpoint(service_host="127.0.0.1", service_port=port,
                     type="REST"), wire_format=wire_format)
        try:
            await comp.predict_raw(msg)  # warm: connection + frame probe
            framing.frame_stats()        # the timed window owns its samples
            t0 = time.perf_counter()
            for _ in range(reps):
                await comp.predict_raw(msg)
            return time.perf_counter() - t0
        finally:
            await comp.close()
            await runner.cleanup()

    wall = asyncio.run(go())
    if wire_format == "frame":
        st = framing.frame_stats()
        ser_s = (sum(st["frame_encode_times_s"]) +
                 sum(st["frame_decode_times_s"]))
    else:
        t0 = time.perf_counter()
        for _ in range(reps):
            SeldonMessage.from_dict(json.loads(json.dumps(msg.to_dict())))
        ser_s = 2.0 * (time.perf_counter() - t0)  # request + response legs
    return {
        "wire_format": wire_format,
        "requests": reps,
        "body_bytes": body_bytes,
        "ms_per_request": round(1e3 * wall / reps, 3),
        "req_per_s": round(reps / wall, 1),
        "serialization_ms_per_request": round(1e3 * ser_s / reps, 3),
        "serialization_share_pct": round(100.0 * ser_s / wall, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tpu", action="store_true")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-cache-dtype", default="", choices=("", "bf16", "int8"),
                    help="KV-cache storage format (default bf16)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="decode steps kept dispatched ahead of the host")
    ap.add_argument("--fuse-steps", type=int, default=0,
                    help="K fused device-side decode steps per host sync "
                         "when the admit queue is empty (0 = off)")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="tokens per KV page (0 = default 64)")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="total pages in the global pool (0 = fully "
                         "provisioned; smaller oversubscribes)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked admission prefill size (0 = default 256)")
    ap.add_argument("--spec-mode", default="off",
                    choices=("off", "ngram", "draft"),
                    help="speculative decoding: ngram = zero-weight "
                         "prompt-lookup self-draft, draft = small draft "
                         "model verified by the target (PR 8)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens per verify step (0 = default 4)")
    ap.add_argument("--prompt-style", default="random",
                    choices=("random", "repetitive"),
                    help="repetitive = cyclic token prompts, the n-gram "
                         "drafter's home turf (the acceptance-rate "
                         "headline scenario); random = un-draftable "
                         "worst case")
    ap.add_argument("--wire-format", default="", choices=("", "json", "frame"),
                    help="remote-hop A/B arm (ISSUE 18): after the serving "
                         "phases, drive tensor bodies through a loopback "
                         "REST hop (RemoteComponent -> component app) with "
                         "the chosen encoding; reports per-request latency, "
                         "bytes on the wire, and the serialization share — "
                         "run once per format and diff the report entries")
    ap.add_argument("--tracing", action="store_true",
                    help="tracing-overhead guard arm: rerun the concurrent "
                         "phase with the flight recorder enabled and "
                         "assert throughput stays within "
                         "TRACING_MAX_OVERHEAD_PCT (default 2%%) of "
                         "disabled — the recorder's no-new-syncs claim, "
                         "enforced (docs/observability.md)")
    args = ap.parse_args()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from seldon_core_tpu.runtime.batcher import BatcherService
    from seldon_core_tpu.servers.llmserver import LLMServer

    on_tpu = args.tpu
    kwargs = (
        dict(vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
             n_kv_heads=16, ffn_dim=5504, max_seq_len=2048)
        if on_tpu
        else dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_dim=128, max_seq_len=512)
    )
    max_new = 64 if on_tpu else 32
    plen = 128 if on_tpu else 24
    if args.prompt_style == "repetitive":
        # the speculation headline needs the generated text's repeating
        # orbit to dominate the pre-orbit warmup (the first ~10 tokens
        # before greedy decode settles into a cycle accept almost
        # nothing); 64 new tokens puts ~80% of the decode inside the
        # orbit where the prompt-lookup proposer runs at acceptance ~1
        max_new = max(max_new, 64)
    spec_kwargs = {}
    if args.spec_mode != "off":
        spec_kwargs = dict(spec_mode=args.spec_mode, spec_k=args.spec_k)
        if args.spec_mode == "draft":
            # half-width draft over the target's vocab: cheap forwards,
            # real (imperfect) drafting quality
            dkw = dict(kwargs)
            dkw["dim"] = max(kwargs["dim"] // 2, 16)
            dkw["ffn_dim"] = max(kwargs["ffn_dim"] // 2, 32)
            spec_kwargs.update(draft_model="transformer",
                               draft_model_kwargs=dkw)
    server = LLMServer(model="transformer", model_kwargs=kwargs,
                       init_random=True, max_new_tokens=max_new,
                       len_buckets=(plen,), batch_buckets=(1, args.clients),
                       temperature=0.0, eos_id=-1,
                       kv_cache_dtype=args.kv_cache_dtype,
                       kv_page_size=args.kv_page_size,
                       kv_pool_pages=args.kv_pool_pages,
                       prefill_chunk=args.prefill_chunk,
                       decode_pipeline_depth=args.pipeline_depth,
                       decode_fuse_steps=args.fuse_steps,
                       **spec_kwargs)
    server.load()
    rng = np.random.default_rng(0)
    if args.prompt_style == "repetitive":
        # short cycles: greedy decode of a random-init model falls into a
        # repeating orbit the prompt-lookup proposer then predicts, so
        # acceptance approaches 1 — the accepted-tokens-per-read headline
        cycles = [rng.integers(1, kwargs["vocab_size"] - 1, size=3).tolist()
                  for _ in range(args.clients)]
        prompts = [(c * ((plen + 2) // 3))[:plen] for c in cycles]
    else:
        prompts = [rng.integers(1, kwargs["vocab_size"] - 1,
                                size=plen).tolist()
                   for _ in range(args.clients)]

    svc = BatcherService(server, max_slots=args.slots)
    # warm both paths at FULL length (the decode scan compiles per static
    # n_steps and the batcher's fused-K program only compiles once a
    # request has >= K tokens of budget — a short warm call would leave
    # compiles inside the timed windows)
    svc.submit_sync(prompts[0], max_new)
    server.generate([prompts[0]], max_new_tokens=max_new)

    # (a) sequential: one request at a time, per-request generate()
    t0 = time.perf_counter()
    seq_tokens = 0
    for p in prompts:
        out = server.generate([p], max_new_tokens=max_new)
        seq_tokens += len(out["tokens"][0])
    seq_s = time.perf_counter() - t0

    # (a') direct: every prompt in ONE batched generate() — the raw
    # device-side decode ceiling the served path is measured against
    # (VERDICT weak #1 put the pre-pipelining batcher at 11% of this).
    # Warm at the FULL max_new: the decode scan compiles per static
    # n_steps, so a shorter warm call leaves the timed call paying compile
    server.generate(prompts, max_new_tokens=max_new)
    t0 = time.perf_counter()
    out = server.generate(prompts, max_new_tokens=max_new)
    direct_s = time.perf_counter() - t0
    direct_tokens = sum(len(t) for t in out["tokens"])

    # (b) concurrent: all clients at once through the shared batch. ONE
    # harness serves both the headline phase and the --tracing A/B arm —
    # the overhead arm must difference the exact workload the headline
    # measures, not a hand-kept copy that can drift.
    import threading

    def concurrent_phase(s, reps=1):
        """(total tokens, wall) for ``reps`` back-to-back waves of all
        clients; gc runs OUTSIDE the window so one arm's garbage cannot
        bill the next."""
        import gc

        gc.collect()
        total = 0
        t0 = time.perf_counter()
        for _ in range(reps):
            results = [0] * args.clients

            def work(i):
                results[i] = len(s.submit_sync(prompts[i], max_new))

            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(args.clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            total += sum(results)
        return total, time.perf_counter() - t0

    conc_tokens, conc_s = concurrent_phase(svc)
    # pipeline instrumentation BEFORE close(): dispatch-ahead depth actually
    # reached, and the dispatch/sync split the tentpole is about
    from benchmarks._pipeline_stats import pipeline_report

    server._batcher_service = svc  # llm_stats reads the hwm through it
    pipeline = pipeline_report(server)
    spec = svc.batcher.spec_stats()
    # close BEFORE the tracing arm builds its own service: two live
    # services means two device-resident slot-cache/KV pools at once — a
    # config whose single pool fits the chip would OOM inside the arm
    svc.close()

    # --tracing: the overhead guard arm. Two halves:
    #
    # 1. REPORTED: an interleaved on/off throughput A/B on ONE
    #    recorder-armed service (same event loop, same slot caches, same
    #    compiled programs — the recorder toggled while idle). On real
    #    chips this differenced pair is the headline; on the CPU rehearsal
    #    it is BIMODAL (a measurement window that eats one batcher
    #    0.5s idle-wait edge swings the arm +-50%), so it is reported,
    #    never gated on.
    # 2. ENFORCED: the deterministic decomposition of the same quantity —
    #    the recorder's measured host work per token (per-event append +
    #    per-request materialization, microbenched on the real class with
    #    realistic segments) over the measured serving wall per token at
    #    this batch. The numerator is syscall-free pure Python (stable to
    #    a few percent); the denominator's noise only scales a number an
    #    order of magnitude under the limit. The recorder claims
    #    "appends, never synchronization" on the decode path; this is
    #    where that claim is a number instead of a comment.
    tracing_entry = None
    if args.tracing:
        from seldon_core_tpu.tracing import Tracer, set_tracer

        def run_concurrent(s):
            # reps=2 lengthens the timed window so thread-spawn and
            # scheduler noise amortize; same harness as the headline phase
            tokens, wall = concurrent_phase(s, reps=2)
            return tokens / wall

        set_tracer(Tracer(enabled=True))
        svc_ab = BatcherService(server, max_slots=args.slots)
        recorder = svc_ab.batcher._flight
        assert recorder is not None, "recorder never armed"
        svc_ab.submit_sync(prompts[0], max_new)  # warm (compiles shared)
        # paired rounds, MEDIAN of per-round on/off ratios: adjacent
        # off/on runs see the same machine state, so slow drift cancels,
        # and the median shrugs off one scheduler hiccup that a best-of
        # or a single pair would bake into the verdict
        import statistics

        rounds = 6
        ratios = []
        offs, ons = [], []
        run_concurrent(svc_ab)  # shake out thread-pool cold start
        for r in range(rounds):
            # alternate which arm runs first: any within-pair drift
            # (allocator growth, cache churn) biases both directions
            # equally instead of always billing the second arm.
            # toggled only while the batcher is idle (all submits joined)
            order = ("off", "on") if r % 2 == 0 else ("on", "off")
            vals = {}
            for arm in order:
                svc_ab.batcher._flight = recorder if arm == "on" else None
                vals[arm] = run_concurrent(svc_ab)
            svc_ab.batcher._flight = recorder
            offs.append(vals["off"])
            ons.append(vals["on"])
            ratios.append(vals["on"] / vals["off"])
        svc_ab.close()
        set_tracer(Tracer(enabled=False))
        ab_overhead_pct = (1.0 - statistics.median(ratios)) * 100.0

        # the enforced half: microbench the recorder's two cost centers on
        # the real class — the per-event append (what every drained step
        # pays per active slot) and the per-request begin+materialize
        # (ring -> timeline dict + span tree + tracer buffer append)
        from seldon_core_tpu.runtime.flight import (
            EV_FIRST_TOKEN, EV_STEP, FlightRecorder)
        from seldon_core_tpu.tracing import Tracer as _Tracer

        bench_fr = FlightRecorder(1)
        bench_tr = _Tracer(enabled=True, max_buffer=1 << 30)
        bench_fr.begin(0, None, time.perf_counter(), plen)
        n_rec = 50_000
        t0 = time.perf_counter()
        for _ in range(n_rec):
            bench_fr.record(0, EV_STEP, tokens=1, t_dispatch=0.0)
        per_record_s = (time.perf_counter() - t0) / n_rec
        n_req = 500
        t0 = time.perf_counter()
        for _ in range(n_req):
            bench_fr.begin(0, None, time.perf_counter(), plen)
            bench_fr.record(0, EV_FIRST_TOKEN, tokens=1)
            for _ in range(max_new - 1):
                bench_fr.record(0, EV_STEP, tokens=1, t_dispatch=0.0)
            bench_fr.complete(0, "done", max_new, bench_tr)
        per_request_s = (time.perf_counter() - t0) / n_req
        bench_tr.drain()

        # per_request_s covers one whole lifecycle (admission + an event
        # per token + materialization), so the recorder's cost per SERVED
        # token is simply per_request_s / tokens-per-request. Denominator:
        # the DISABLED arm's per-token wall — dividing by the enabled arm
        # would put the recorder's own cost in the denominator and make
        # the limit self-lenient as that cost grows.
        baseline_tok_per_s = statistics.median(offs)
        recorder_s_per_token = per_request_s / max(max_new, 1)
        serving_s_per_token = 1.0 / baseline_tok_per_s
        overhead_pct = 100.0 * recorder_s_per_token / serving_s_per_token
        limit = float(os.environ.get("TRACING_MAX_OVERHEAD_PCT", "2.0"))
        # TRACING_ENFORCE_AB=1 (on-chip runs, where decode steps are long
        # enough for the differenced pair to mean something) additionally
        # gates the raw A/B delta, making the literal "throughput within
        # limit of disabled" claim enforceable where it is measurable
        enforce_ab = os.environ.get("TRACING_ENFORCE_AB", "") == "1"
        if enforce_ab and ab_overhead_pct > limit:
            overhead_pct = max(overhead_pct, ab_overhead_pct)
        tracing_entry = {
            "disabled_tok_per_s": round(baseline_tok_per_s, 1),
            "enabled_tok_per_s": round(statistics.median(ons), 1),
            "ab_overhead_pct": round(ab_overhead_pct, 2),
            "ab_enforced": enforce_ab,
            "recorder_us_per_event": round(per_record_s * 1e6, 3),
            "recorder_us_per_request": round(per_request_s * 1e6, 1),
            "overhead_pct": round(overhead_pct, 2),
            "limit_pct": limit,
        }
        # the violation verdict is ENFORCED at the very end, AFTER the
        # report JSON is written — a failing CI run must leave the
        # numbers it failed on in the artifact, not just a stdout line

    # --wire-format: the remote-hop A/B (ISSUE 18). Both arms run so one
    # invocation carries the comparison; the flag picks the headline the
    # summary line reports.
    remote_hop = None
    if args.wire_format:
        hop_array = np.random.default_rng(1).standard_normal(
            (args.clients, plen, kwargs["dim"]), dtype=np.float32)
        remote_hop = {
            fmt: _remote_hop_phase(fmt, hop_array)
            for fmt in ("json", "frame")}
        remote_hop["frame_vs_json_speedup"] = round(
            remote_hop["json"]["ms_per_request"] /
            remote_hop["frame"]["ms_per_request"], 2)
        remote_hop["headline"] = args.wire_format

    platform = jax.devices()[0].platform
    # per-token KV bytes alongside tok/s so BENCH rounds can attribute
    # bandwidth regressions (decode attention streams the whole static
    # cache each step: bytes/step ~= slots * cache_len * bytes_per_token)
    from seldon_core_tpu.models.cache import kv_cache_bytes_per_token

    kv_per_tok = kv_cache_bytes_per_token(server._cfg, server.kv_cache_dtype)
    entry = {
        "config": {"clients": args.clients, "slots": args.slots,
                   "max_new_tokens": max_new, "prompt_len": plen,
                   "model": kwargs},
        "kv_cache": {"dtype": server.kv_cache_dtype,
                     "bytes_per_token": kv_per_tok,
                     # page pool accounting: resident
                     # HBM is pool pages, not slots x max_len
                     "pages": {k: v for k, v in server.llm_stats().items()
                               if k.startswith("kv_page")}},
        "sequential": {"tok_per_s": round(seq_tokens / seq_s, 1),
                       "wall_s": round(seq_s, 2), "tokens": seq_tokens},
        "direct": {"tok_per_s": round(direct_tokens / direct_s, 1),
                   "wall_s": round(direct_s, 2), "tokens": direct_tokens},
        "concurrent": {"tok_per_s": round(conc_tokens / conc_s, 1),
                       "wall_s": round(conc_s, 2), "tokens": conc_tokens},
        "speedup": round((conc_tokens / conc_s) / (seq_tokens / seq_s), 2),
        # the tentpole ratio: served (batcher) vs raw batched decode — the
        # number VERDICT weak #1 measured at 0.11 before pipelining
        "served_vs_direct": round(
            (conc_tokens / conc_s) / (direct_tokens / direct_s), 3),
        "pipeline": pipeline,
        # speculation (PR 8): tokens_per_forward is the >1-accepted-token-
        # per-KV-cache-read multiplier; accept_rate is why it moves. The
        # per-slot EMA list is dropped from the report (scrape /metrics
        # for it) — the aggregates are the bench claim.
        "speculation": {k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in spec.items()
                        if k != "spec_accept_rate_per_slot"},
    }
    if tracing_entry is not None:
        # the --tracing guard arm: enabled-vs-disabled flight-recorder
        # throughput at this batch (CI enforces the limit via exit code)
        entry["tracing"] = tracing_entry
    if remote_hop is not None:
        entry["remote_hop"] = remote_hop
    if platform == "tpu":
        entry["note"] = (
            "the batcher keeps pipeline_depth decode steps dispatched ahead "
            "of the host (one sync per drained step, overlapped with device "
            "compute); served_vs_direct is the architecture claim")
    out_path = os.path.join(HERE, "report_llm_concurrent.json")
    report = {"metric": "LLM serving throughput, N concurrent clients vs "
                        "sequential (shared ContinuousBatcher vs per-request "
                        "generate)"}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                report.update(json.load(f))
        except Exception:
            pass
    report.pop("platform", None)  # pre-merge format
    for k in ("config", "sequential", "concurrent", "speedup", "note"):
        report.pop(k, None)
    report[platform] = entry
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    summary = {"sequential_tok_s": entry["sequential"]["tok_per_s"],
               "concurrent_tok_s": entry["concurrent"]["tok_per_s"],
               "direct_tok_s": entry["direct"]["tok_per_s"],
               "served_vs_direct": entry["served_vs_direct"],
               "inflight_hwm": pipeline["inflight_hwm"],
               "speedup": entry["speedup"], "platform": platform}
    if tracing_entry is not None:
        summary["tracing_overhead_pct"] = tracing_entry["overhead_pct"]
        if tracing_entry["overhead_pct"] > tracing_entry["limit_pct"]:
            print(json.dumps({"tracing_overhead_violation": tracing_entry}))
            sys.exit(1)
    if remote_hop is not None:
        head = remote_hop[args.wire_format]
        summary["remote_hop_ms"] = head["ms_per_request"]
        summary["remote_hop_serialization_share_pct"] = head[
            "serialization_share_pct"]
        summary["remote_hop_frame_vs_json_x"] = remote_hop[
            "frame_vs_json_speedup"]
    if spec.get("spec_mode", "off") != "off":
        summary["spec_mode"] = spec["spec_mode"]
        summary["spec_k"] = spec["spec_k"]
        summary["spec_accept_rate"] = round(spec["spec_accept_rate"], 3)
        summary["spec_tokens_per_forward"] = round(
            spec["spec_tokens_per_forward"], 3)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
