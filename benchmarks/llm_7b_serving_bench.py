"""Llama-2-7B-dims int8 through the PRODUCT serving stack (VERDICT r4 #2/#8).

Round 4 measured 7B as a raw decode loop; this runs the same weights through
the real serving path in one chip session (one 39 s streamed init amortized
across phases):

  A. direct generate() decode at b8/b1 — in-session re-confirmation of the
     r4-llm7b rows, and the step-time basis for phase D's attribution.
  B. REST transport end-to-end: aiohttp `make_component_app` server, N in
     {1, 4, 8} concurrent HTTP clients on /v1/generate-style jsonData
     prompts joining the shared ContinuousBatcher. The batcher now keeps
     `decode_pipeline_depth` steps dispatched ahead of the host (PR 3);
     the report carries the dispatch-ahead depth actually reached, the
     dispatch-vs-sync split, and served_vs_direct (vs phase A's b8 row) —
     the ratio VERDICT weak #1 measured at 0.11 pre-pipelining (earlier
     harness, ~75 ms RTT to the chip). DECODE_FUSE_STEPS=K runs K tokens
     per host sync.
  C. prefix-cached multi-turn: turn-2 prompt = turn-1 prompt + answer +
     follow-up; prefill latency cold (cleared cache) vs cached (turn-1
     prefix KV reused, suffix-only extend). Median of repeats; the pair is
     the VERDICT #8 deliverable.
  D. b8-vs-b1 step-time attribution: jax.profiler traces of the decode
     step at both batches, categorized with tpu_profile's parser — why
     does b8 cost 17.8 ms/step when b1 costs 12.5 on a weights-bound
     decode (r4 question).
  E. LONG-prefix prefix-cache pair (VERDICT #7): a 1.5-2k-token shared
     system prefix + short per-request suffix, cold full prefill vs
     cached suffix-only extend, device-isolated (jitted-call medians
     minus a measured dispatch floor — the round-5 methodology) so the
     cache is measured where it actually matters.
  S. speculative decoding arm (ISSUE 8): SPEC_MODE=off|ngram|draft picks
     the proposer, SPEC_K the max draft depth; sweeps K over the
     repetitive-text scenario (the n-gram drafter's home turf) plus a
     random un-draftable control, reporting tok/s, draft acceptance and
     accepted tokens per verify forward — the >1-token-per-KV-read
     multiplier — vs K.
  M. radix prefix-cache arm (ISSUE 12): multi-turn chat through the
     token-block trie — prefill tokens (∝ FLOPs) per served token under
     three policies on one transcript (cold / the old exact-match cache
     simulated / radix measured), bit-exactness radix-vs-cold enforced,
     plus a ReplicaSet prefix-routing vs least-loaded A/B on two
     replicas (CPU rehearsal; on-chip needs a slice per replica).
  L. multi-tenant arm (ISSUE 15): batched-LoRA + SLO scheduling through
     one continuous batch (ADAPTERS = pool size, SLO_MIX =
     "interactive:batch" request counts, TENANT_QUOTA = the flooding
     tenant's queue bound). Reports adapted-vs-base tokens/s (the
     near-base-throughput claim), per-class TTFT p95 unloaded vs under a
     batch-tenant flood (the isolation ratio the 2x acceptance bar
     gates; MULTITENANT_ENFORCE=1 makes the bar exit-code-enforced),
     SLO attainment at 2x-unloaded, batch tokens under flood (no
     starvation), and the per-tenant quota sheds with their
     seldon_tenant_shed_total visibility. Builds its OWN lora-enabled
     server — on chip run this phase alone (7B weights twice won't
     co-fit).
  D (DISAGG set). disaggregated prefill/decode arm (ISSUE 9): DISAGG=
     remote_prefill splits the mesh (PREFILL_DEVICES / DECODE_DEVICES /
     PREFILL_WORKERS envs) and reruns phase P's long-prefill adversary
     with admission prefill on the prefill slice — the decode-slice
     victim's worst inter-token gap vs the PR 7 chunked-interleaved
     number — plus TTFT / inter-token-gap histogram summaries and the
     handoff counters. Needs >= 2 visible devices (CPU rehearsal:
     XLA_FLAGS=--xla_force_host_platform_device_count=8).
  N (NETWORK_HANDOFF set). cross-host KV handoff arm (ISSUE 18): reruns
     the disaggregated batch with the prefill->decode handoff streamed
     as length-prefixed frames over a real socket instead of
     jax.device_put, at batch-8 concurrent streaming. Reports the
     device-vs-network tok/s pair (when does device_put beat the
     socket), wire bytes per handoff, the handoff-seconds histogram, and
     the serialization share of end-to-end latency — the <5% acceptance
     bar of the framing tentpole, reported by the bench. Same >= 2
     visible devices requirement as the DISAGG arm.

Writes benchmarks/report_llm_7b_serving.json and appends the attribution
to DECODE_NOTES.md (by hand, from the printed table).

At 7B the phases do NOT co-fit in one process's HBM (weights 6.7 GB +
generate b8/b1 KV + the batcher's slot caches exhaust the chip when the
earlier phases' executables are still resident), so each invocation runs
the phases named in argv ("A", "BC", "D"; default all — the CPU rehearsal
fits in one) and MERGES its keys into the existing report.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

REPORT = os.path.join(HERE, "report_llm_7b_serving.json")
PORT = 8731


def log(key, value):
    print(json.dumps({key: value}), flush=True)


def main() -> None:
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    # phase L builds its OWN lora-enabled server, which does not co-fit
    # with the headline 7B server on chip — on TPU run it alone ("L")
    phases = "".join(sys.argv[1:]).upper() or (
        "ABCDEPSMN" if on_tpu else "ABCDEPSMLN")
    report = {}
    if os.path.exists(REPORT):
        with open(REPORT) as f:
            report = json.load(f)
    report["platform"] = jax.devices()[0].platform
    if not on_tpu:
        # CPU rehearsal config: same code path, toy dims
        model_kwargs = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=128, max_seq_len=1024)
        model_name = "transformer"
        quantize = None
        max_new, plen = 8, 16
        len_buckets = (16, 32, 64)
    else:
        model_kwargs = None
        model_name = "llama2-7b"
        quantize = "int8"
        max_new, plen = 64, 128
        len_buckets = (128, 256, 512)

    from seldon_core_tpu.servers.llmserver import LLMServer

    t0 = time.perf_counter()
    kwargs = dict(model=model_name, init_random=True, seed=0,
                  max_new_tokens=max_new, len_buckets=len_buckets,
                  batch_buckets=(1, 8), temperature=0.0, eos_id=-1,
                  continuous_batching=8, prefix_cache_size=8,
                  kv_cache_dtype=os.environ.get("KV_CACHE_DTYPE", ""),
                  kv_page_size=int(os.environ.get("KV_PAGE_SIZE", "0")),
                  kv_pool_pages=int(os.environ.get("KV_POOL_PAGES", "0")),
                  prefill_chunk=int(os.environ.get("PREFILL_CHUNK", "0")),
                  decode_pipeline_depth=int(
                      os.environ.get("DECODE_PIPELINE_DEPTH", "2")),
                  decode_fuse_steps=int(
                      os.environ.get("DECODE_FUSE_STEPS", "0")))
    if model_kwargs is not None:
        kwargs["model_kwargs"] = model_kwargs
    if quantize:
        kwargs["quantize"] = quantize
    server = LLMServer(**kwargs)
    server.load()
    report["load_s"] = round(time.perf_counter() - t0, 1)
    log("load_s", report["load_s"])

    # per-token KV bytes alongside tok/s (ISSUE 2 satellite): bytes/step of
    # KV read = batch * cache_len * bytes_per_token, the term DECODE_NOTES
    # round 5 measured growing 2.71x from b1 to b8
    from seldon_core_tpu.models.cache import kv_cache_bytes_per_token

    kv_per_tok = kv_cache_bytes_per_token(server._cfg, server.kv_cache_dtype)
    report["kv_cache"] = {
        "dtype": server.kv_cache_dtype,
        "bytes_per_token": kv_per_tok,
    }
    log("kv_cache", report["kv_cache"])

    rng = np.random.default_rng(0)
    vocab = 31999 if on_tpu else 255

    # ---- A. direct decode (in-session basis for the attribution) -------
    decode = {}
    for b in (8, 1) if "A" in phases else ():
        prompts = [rng.integers(1, vocab, size=plen).tolist() for _ in range(b)]
        t0 = time.perf_counter()
        server.generate(prompts, max_new_tokens=max_new)  # compile + warm
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = server.generate(prompts, max_new_tokens=max_new)
            times.append(time.perf_counter() - t0)
        med = float(np.median(times))
        n_tokens = sum(len(t) for t in out["tokens"])
        decode[f"b{b}"] = {
            "tok_per_s": round(n_tokens / med, 1),
            "ms_per_step": round(1e3 * med / max_new, 3),
            "compile_s": round(compile_s, 1),
            "kv_bytes_per_token": kv_per_tok,
            "kv_read_gb_per_step": round(
                b * (plen + max_new) * kv_per_tok / 1e9, 3),
        }
        log(f"decode_b{b}", decode[f"b{b}"])
    if "A" in phases:
        report["direct_decode"] = decode
        _write(report)

    # ---- B. REST + ContinuousBatcher, N concurrent clients -------------
    if "B" in phases:
        _rest_batching(server, report, plen, max_new)

    # ---- C. prefix-cached multi-turn prefill: cold vs cached -----------
    if "C" in phases:
        _prefix_multi_turn(server, report, rng, vocab, plen, max_new)

    # ---- E. long-prefix pair: 1.5-2k shared system prefix --------------
    if "E" in phases:
        _prefix_long_system(server, report, rng, vocab, on_tpu)

    # ---- P. paged KV arm: capacity at fixed HBM + prefill adversary ----
    if "P" in phases:
        _paged_arm(server, report, rng, vocab, plen, max_new, on_tpu)

    # ---- S. speculative decoding arm: acceptance + tok/s vs K ----------
    if "S" in phases:
        _spec_arm(server, report, rng, vocab, plen, max_new, on_tpu)

    # ---- M. radix prefix cache: multi-turn chat FLOPs + routing A/B ----
    if "M" in phases:
        _radix_arm(server, report, rng, vocab, plen, max_new, on_tpu)

    # ---- L. multi-tenant arm: batched LoRA + SLO-aware scheduling ------
    if "L" in phases:
        _multitenant_arm(server, report, rng, vocab, plen, max_new, on_tpu)

    # ---- D (DISAGG env). disaggregated prefill/decode arm (ISSUE 9) ----
    if "D" in phases and os.environ.get("DISAGG", ""):
        _disagg_arm(server, report, rng, vocab, plen, max_new, on_tpu)

    # ---- N (NETWORK_HANDOFF env). framed cross-host handoff (ISSUE 18) -
    if "N" in phases and os.environ.get("NETWORK_HANDOFF", ""):
        _network_handoff_arm(server, report, rng, vocab, plen, max_new,
                             on_tpu)

    # ---- D. b8 vs b1 decode-step attribution ---------------------------
    if on_tpu and "D" in phases:
        _attribution(server, report, rng, vocab, plen, on_tpu)

    _write(report)


def _paged_arm(server, report, rng, vocab, plen, max_new, on_tpu) -> None:
    """Phase P (ISSUE 7): the paged-KV claims, measured.

    (1) concurrent-slots-at-fixed-HBM: a paged pool holding the SAME KV
        bytes as a 4-slot dense cache serves 8 concurrent mixed-length
        requests (short-heavy mix — dense bills every slot at max_len, the
        pool bills pages written), zero sheds = the 2x capacity claim.
    (2) time-to-first-token under a long-prefill adversary: a steady
        decode stream is running when a top-bucket prompt admits; chunked
        prefill (PREFILL_CHUNK env) vs one-shot (chunk = whole bucket),
        reporting the victim's worst inter-token gap and the adversary's
        TTFT for both. KV_PAGE_SIZE env sets the page size.
    """
    import asyncio

    from seldon_core_tpu.models.cache import kv_cache_bytes_per_token
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    page_size = int(os.environ.get("KV_PAGE_SIZE", "0")) or (64 if on_tpu else 8)
    chunk = int(os.environ.get("PREFILL_CHUNK", "0")) or (256 if on_tpu else 8)
    kv_per_tok = kv_cache_bytes_per_token(server._cfg, server.kv_cache_dtype)

    # -- (1) capacity at fixed HBM --------------------------------------
    slots_dense = 4
    max_len = 2 * plen + max_new
    n_pages_slot = -(-max_len // page_size)
    # pool holding exactly the dense cache's bytes, serving 2x the slots
    pool_pages = slots_dense * n_pages_slot + 2
    dense_bytes = slots_dense * max_len * kv_per_tok
    lens = [plen // 4] * 5 + [plen // 2] * 2 + [plen]  # short-heavy mix

    async def capacity_run():
        b = ContinuousBatcher(server, max_slots=2 * slots_dense,
                              max_len=max_len, page_size=page_size, pool_pages=pool_pages,
                              prefill_chunk=chunk)
        prompts = [rng.integers(1, vocab, size=max(L, 1)).tolist()
                   for L in lens]
        t0 = time.perf_counter()
        outs = await asyncio.gather(
            *[b.submit(p, max_new_tokens=max_new) for p in prompts],
            return_exceptions=True)
        wall = time.perf_counter() - t0
        stats = b.page_stats()
        await b.close()
        ok = sum(1 for o in outs if isinstance(o, list))
        return ok, wall, stats

    ok, wall, stats = asyncio.run(capacity_run())
    capacity = {
        "dense_slots_at_budget": slots_dense,
        "paged_slots_at_budget": 2 * slots_dense,
        "hbm_budget_bytes": dense_bytes,
        "pool_pages": pool_pages, "page_size": page_size,
        "mixed_lens": lens, "completed": ok, "requests": len(lens),
        "sheds": stats["kv_page_sheds"], "wall_s": round(wall, 2),
        "capacity_x_at_fixed_hbm": round(
            (2 * slots_dense) / slots_dense, 2) if ok == len(lens) else None,
    }
    report["paged_capacity"] = capacity
    log("paged_capacity", capacity)

    # -- (2) long-prefill adversary: chunked vs one-shot -----------------
    long_len = server.len_buckets[-1]

    def adversary_run(chunk_size):
        async def go():
            b = ContinuousBatcher(server, max_slots=2, max_len=long_len + max_new,
                                  page_size=page_size,
                                  prefill_chunk=chunk_size)
            gaps, last = [], [None]

            def on_tok(t):
                now = time.perf_counter()
                if t is not None and last[0] is not None:
                    gaps.append(now - last[0])
                last[0] = now

            victim_p = rng.integers(1, vocab, size=plen // 2).tolist()
            steady = asyncio.ensure_future(
                b.submit(victim_p, max_new_tokens=4 * max_new,
                         on_token=on_tok))
            while not any(s.active for s in b._slots):
                await asyncio.sleep(0.002)
            warm_gaps = len(gaps)
            adv_p = rng.integers(1, vocab, size=long_len).tolist()
            t0 = time.perf_counter()
            ttft = [None]

            def first_tok(t):
                if t is not None and ttft[0] is None:
                    ttft[0] = time.perf_counter() - t0
            await asyncio.sleep(0)
            adv = asyncio.ensure_future(
                b.submit(adv_p, max_new_tokens=4, on_token=first_tok))
            await asyncio.gather(steady, adv)
            await b.close()
            during = gaps[warm_gaps:] or [0.0]
            # a drained step surfaces its tokens in a burst, so intra-drain
            # gaps are ~0; the steady-state baseline is the positive
            # (drain-to-drain) gaps only
            base = [g for g in gaps[:warm_gaps] if g > 1e-6] or [0.0]
            return (float(np.median(base)), float(np.max(during)),
                    ttft[0])

        return asyncio.run(go())

    # warm pass first: the chunk/decode programs compile per static shape,
    # and a compile inside the timed window would masquerade as a stall
    adversary_run(chunk_size=chunk)
    adversary_run(chunk_size=long_len)
    base_g, worst_chunked, ttft_chunked = adversary_run(chunk_size=chunk)
    _, worst_oneshot, ttft_oneshot = adversary_run(chunk_size=long_len)
    adversary = {
        "adversary_prompt_tokens": long_len, "prefill_chunk": chunk,
        "victim_median_gap_ms": round(1e3 * base_g, 2),
        "victim_worst_gap_ms": {
            "chunked": round(1e3 * worst_chunked, 2),
            "oneshot": round(1e3 * worst_oneshot, 2),
        },
        "adversary_ttft_ms": {
            "chunked": round(1e3 * (ttft_chunked or 0), 2),
            "oneshot": round(1e3 * (ttft_oneshot or 0), 2),
        },
        "gap_inflation_x": {
            "chunked": round(worst_chunked / base_g, 2) if base_g else None,
            "oneshot": round(worst_oneshot / base_g, 2) if base_g else None,
        },
    }
    report["paged_prefill_adversary"] = adversary
    log("paged_prefill_adversary", adversary)
    _write(report)


def _spec_arm(server, report, rng, vocab, plen, max_new, on_tpu) -> None:
    """Phase S (ISSUE 8): speculative decoding through the serving path.

    SPEC_MODE=off|ngram|draft picks the proposer (default ngram — the
    zero-extra-weights prompt-lookup self-draft; draft needs a draft
    model: auto half-width rehearsal model on CPU, DRAFT_MODEL_URI on
    TPU), SPEC_K the max draft depth per verify step (default 4). The
    arm runs an off baseline plus a K sweep over the REPETITIVE-text
    scenario — short cyclic prompts, where greedy decode falls into the
    cycle and the proposer predicts it, so acceptance approaches 1 —
    and a random-prompt un-draftable control at the top K, where the
    per-slot controller must step the offered depth down to the 1-probe
    floor. tokens_per_forward is the claim: accepted tokens per target
    forward = tokens per KV-cache read (ROADMAP item 2's multiplier).
    """
    import asyncio

    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.runtime.spec import normalize_spec_mode

    mode = normalize_spec_mode(os.environ.get("SPEC_MODE", "ngram"))
    if mode == "off":
        report["speculation"] = {
            "mode": "off", "note": "SPEC_MODE=off: arm skipped"}
        _write(report)
        return
    k_top = int(os.environ.get("SPEC_K", "0")) or 4
    clients = 8
    if not on_tpu:
        # the rehearsal's global max_new (8) cannot exercise an orbit:
        # greedy decode needs ~10 tokens to settle into the repeating
        # cycle the prompt-lookup proposer predicts, so the speculation
        # arm decodes longer than the other phases
        max_new = max(max_new, 64)

    spec_server = server
    if mode == "draft" and getattr(server, "_draft_module", None) is None:
        if on_tpu:
            # a second 7B-scale load belongs to its own invocation; tell
            # the operator what to set instead of silently downgrading
            report["speculation"] = {
                "mode": "draft",
                "skipped": "target server has no draft model loaded — "
                           "run phase S with DRAFT_MODEL_URI (or a "
                           "draft-configured server)"}
            _write(report)
            return
        from seldon_core_tpu.servers.llmserver import LLMServer

        tkw = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, ffn_dim=128, max_seq_len=1024)
        dkw = dict(tkw)
        dkw["dim"], dkw["ffn_dim"] = 32, 64  # half-width rehearsal draft
        spec_server = LLMServer(
            model="transformer", model_kwargs=tkw, init_random=True,
            seed=0, max_new_tokens=max_new, len_buckets=server.len_buckets,
            batch_buckets=(1, clients), temperature=0.0, eos_id=-1,
            continuous_batching=clients,
            draft_model="transformer", draft_model_kwargs=dkw)
        spec_server.load()

    # repetitive scenario: per-client 3-token cycles tiled to plen
    cycles = [rng.integers(1, vocab, size=3).tolist() for _ in range(clients)]
    rep_prompts = [(c * ((plen + 2) // 3))[:plen] for c in cycles]
    rand_prompts = [rng.integers(1, vocab, size=plen).tolist()
                    for _ in range(clients)]

    def run_arm(prompts, spec_mode, k):
        async def go():
            b = ContinuousBatcher(spec_server, max_slots=clients,
                                  spec_mode=spec_mode, spec_k=k or None)
            # warm: the spec/decode programs compile per static shape —
            # a compile inside the timed window is not the claim
            await asyncio.gather(*[
                b.submit(p, max_new_tokens=2) for p in prompts[:1]])
            t0 = time.perf_counter()
            outs = await asyncio.gather(*[
                b.submit(p, max_new_tokens=max_new) for p in prompts])
            wall = time.perf_counter() - t0
            stats = b.spec_stats()
            await b.close()
            toks = sum(len(o) for o in outs)
            return toks, wall, stats

        return asyncio.run(go())

    arms = {}
    toks, wall, _ = run_arm(rep_prompts, "off", 0)
    arms["off"] = {"tok_per_s": round(toks / wall, 1),
                   "wall_s": round(wall, 3)}
    log("spec_off", arms["off"])
    for k in sorted({1, 2, k_top}):
        toks, wall, st = run_arm(rep_prompts, mode, k)
        arms[f"k{k}"] = {
            "tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "accept_rate": round(st["spec_accept_rate"], 3),
            "tokens_per_forward": round(st["spec_tokens_per_forward"], 3),
            "draft_overhead_fraction": round(
                st["spec_draft_overhead_fraction"], 3),
            "slot_verify_steps": st["spec_slot_steps_total"],
        }
        log(f"spec_k{k}", arms[f"k{k}"])
    toks, wall, st = run_arm(rand_prompts, mode, k_top)
    control = {
        "tok_per_s": round(toks / wall, 1),
        "accept_rate": round(st["spec_accept_rate"], 3),
        "tokens_per_forward": round(st["spec_tokens_per_forward"], 3),
        "draft_overhead_fraction": round(
            st["spec_draft_overhead_fraction"], 3),
    }
    log("spec_random_control", control)

    report["speculation"] = {
        "mode": mode, "spec_k": k_top, "clients": clients,
        "scenario": "repetitive (3-token cycles tiled to prompt length)",
        "arms": arms,
        "random_control": control,
        "note": "tokens_per_forward = accepted tokens per target verify "
                "forward = tokens per KV-cache read; CPU-rehearsal tok/s "
                "is dispatch-bound (each verify forward is K+1 columns "
                "wide but the rehearsal model is compute-trivial) — the "
                "bandwidth win needs the chip, the acceptance numbers "
                "do not",
    }
    _write(report)


def _radix_arm(server, report, rng, vocab, plen, max_new, on_tpu) -> None:
    """Phase M (ISSUE 12): the radix-trie claims, measured on a multi-turn
    chat scenario (each turn's prompt = previous prompt + answer + new
    user tokens — the traffic shape fleet prefix reuse exists for).

    (1) prefill FLOPs per served token, three policies over the SAME
        transcript: cold (no reuse — every turn prefills its whole
        prompt), the OLD exact-match cache (simulated on the token
        stream: only previously-stored whole PROMPTS serve as prefixes,
        so each turn still recomputes the previous turn's ANSWER), and
        the radix trie (measured live: generated blocks re-enter the
        trie, so only the new user tokens + one partial block prefill).
        Prefill FLOPs scale with tokens prefilled (reported directly);
        the acceptance bar is radix <= 0.5x the exact-match policy.
    (2) bit-exactness: the radix arm's outputs must equal the cold arm's
        token-for-token.
    (3) ReplicaSet routing A/B (CPU rehearsal: two toy replicas in one
        process): prefix-aware dispatch keeps a session on the replica
        that caches it, least-loaded bounces sessions between replicas —
        compared on total radix hit tokens. On-chip this needs one
        replica per slice/host (ROADMAP 3); rehearsed here.
    """
    import asyncio

    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    n_turns = 6
    user_len = max(2, plen // 16)
    gen = max_new

    def transcript(b):
        """Drive the chat through ONE batcher; returns (outputs,
        prompt lengths, hit tokens from the trie if present)."""

        async def go():
            outs, lens = [], []
            prompt = rng_local.integers(1, vocab, size=plen).tolist()
            for t in range(n_turns):
                if t > 0:
                    user = rng_local.integers(
                        1, vocab, size=user_len).tolist()
                    prompt = prompt + outs[-1] + user
                outs.append(await b.submit(prompt, max_new_tokens=gen))
                lens.append(len(prompt))
            hits = (b._radix.stats()["prefix_hit_tokens"]
                    if b._radix is not None else 0)
            await b.close()
            return outs, lens, hits

        return asyncio.run(go())

    mlen = plen + n_turns * (user_len + gen) + gen
    pool = 0  # fully provisioned: the A/B measures FLOPs, not shedding
    import numpy as np_mod

    # cold arm: same server, prefix caching off for this batcher only
    rng_local = np_mod.random.default_rng(1234)
    saved = server.prefix_cache_size
    server.prefix_cache_size = 0
    try:
        cold_b = ContinuousBatcher(server, max_slots=2, max_len=mlen,
                                   pool_pages=pool)
        cold_outs, lens, _ = transcript(cold_b)
    finally:
        server.prefix_cache_size = saved
    # radix arm: identical transcript (same local rng seed)
    rng_local = np_mod.random.default_rng(1234)
    radix_b = ContinuousBatcher(server, max_slots=2, max_len=mlen,
                                pool_pages=pool)
    radix_outs, lens2, hit_tokens = transcript(radix_b)

    served = n_turns * gen
    prefilled_cold = sum(lens)
    prefilled_radix = sum(lens2) - hit_tokens
    # the OLD exact-match cache, simulated on the same token stream: it
    # stored whole PROMPTS only (never generated continuations), and an
    # entry served only as an exact stored prefix
    stored = []
    prefilled_exact = 0
    for L in lens:
        hit = max((s for s in stored if s <= L), default=0)
        prefilled_exact += L - hit
        stored.append(L)

    arm = {
        "turns": n_turns,
        "served_tokens": served,
        "prefill_tokens_per_served_token": {
            "cold": round(prefilled_cold / served, 2),
            "exact_match_cache": round(prefilled_exact / served, 2),
            "radix": round(prefilled_radix / served, 2),
        },
        "radix_vs_exact_reduction": round(
            prefilled_exact / max(prefilled_radix, 1), 2),
        "radix_vs_cold_reduction": round(
            prefilled_cold / max(prefilled_radix, 1), 2),
        "bit_exact_vs_cold": radix_outs == cold_outs,
        "note": (
            "prefill FLOPs scale with tokens prefilled (causal attention "
            "makes the saving slightly SUPER-linear: skipped tokens were "
            "the expensive late positions); exact_match_cache is the "
            "pre-PR 12 policy replayed on the same transcript — it "
            "recomputes every turn's generated answer, the radix trie "
            "does not"),
    }
    arm["radix_stats"] = {
        k: v for k, v in radix_b._radix.stats().items()} if \
        radix_b._radix is not None else {}
    assert arm["bit_exact_vs_cold"], "radix outputs diverged from cold"
    # the ISSUE 12 acceptance bar, on a deterministic transcript: token
    # counts (∝ FLOPs) are exact arithmetic, so this cannot flake
    assert arm["radix_vs_exact_reduction"] >= 2.0, arm
    log("radix_multi_turn", arm)
    report["radix_multi_turn"] = arm
    _write(report)

    # --- ReplicaSet prefix-routing vs least-loaded A/B (rehearsal) ------
    if on_tpu:
        report["radix_routing_ab"] = {
            "note": "skipped on-chip: two 7B replicas need one slice "
                    "each (ROADMAP 3); rehearsed on CPU"}
        _write(report)
        return
    from seldon_core_tpu.runtime.batcher import BatcherService
    from seldon_core_tpu.runtime.engine import ReplicaSet
    from seldon_core_tpu.servers.llmserver import LLMServer

    def mk_replica():
        r = LLMServer(model="transformer",
                      model_kwargs=dict(vocab_size=256, dim=64, n_layers=2,
                                        n_heads=4, n_kv_heads=2,
                                        ffn_dim=128, max_seq_len=1024),
                      init_random=True, seed=0, max_new_tokens=gen,
                      len_buckets=(16, 32, 64), batch_buckets=(1, 8),
                      temperature=0.0, eos_id=-1, continuous_batching=4,
                      continuous_batching_max_len=mlen,
                      prefix_cache_size=8)
        r.load()
        r._batcher_service = BatcherService(r, max_slots=4)
        return r

    def run_policy(prefix_aware: bool) -> int:
        replicas = [mk_replica(), mk_replica()]
        rs = ReplicaSet(replicas)
        try:
            sessions = {}
            rngp = np_mod.random.default_rng(7)
            for turn in range(n_turns):
                for sid in range(4):
                    prompt = sessions.get(sid)
                    if prompt is None:
                        prompt = rngp.integers(1, 255, size=plen).tolist()
                    target = (rs.pick_for(prompt) if prefix_aware
                              else rs.pick())
                    out = target._batcher_service.submit_sync(prompt, gen)
                    sessions[sid] = prompt + out + rngp.integers(
                        1, 255, size=user_len).tolist()
            return sum(r.llm_stats()["prefix_hit_tokens"]
                       for r in replicas)
        finally:
            for r in replicas:
                r._batcher_service.close()

    hits_prefix = run_policy(True)
    hits_least = run_policy(False)
    ab = {
        "sessions": 4, "turns": n_turns, "replicas": 2,
        "prefix_hit_tokens": {"prefix_routing": hits_prefix,
                              "least_loaded": hits_least},
        "note": ("prefix routing keeps each chat session on the replica "
                 "whose trie caches it; least-loaded bounces sessions "
                 "between replicas, so every bounce re-prefills the "
                 "whole history cold"),
    }
    log("radix_routing_ab", ab)
    report["radix_routing_ab"] = ab
    _write(report)


def _rest_batching(server, report, plen, max_new) -> None:
    from aiohttp import web

    from seldon_core_tpu.transport.rest import make_component_app

    app = make_component_app(server)
    loop_holder = {}

    def run_server():
        import asyncio

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        loop_holder["loop"] = loop
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", PORT)
        loop.run_until_complete(site.start())
        loop.run_forever()

    th = threading.Thread(target=run_server, daemon=True)
    th.start()
    time.sleep(2)

    import requests

    url = f"http://127.0.0.1:{PORT}/api/v0.1/predictions"

    def client_request(i: int):
        # 1-byte-per-token ByteTokenizer: a plen-char string is a
        # plen-token prompt; vary it per client so the prefix cache is
        # not the thing being measured here
        prompt = chr(65 + i % 26) * plen
        body = {"jsonData": {"prompt": prompt, "max_new_tokens": max_new}}
        r = requests.post(url, json=body, timeout=600)
        r.raise_for_status()
        out = r.json()
        toks = out.get("jsonData", {}).get("tokens", [[]])[0]
        return len(toks)

    client_request(0)  # warm the transport + batcher compile
    serving = {}
    for n_clients in (1, 4, 8):
        results = [0] * n_clients
        threads = []

        def work(i):
            results[i] = client_request(i)

        t0 = time.perf_counter()
        for i in range(n_clients):
            t = threading.Thread(target=work, args=(i,))
            threads.append(t)
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        toks = sum(results)
        serving[f"clients_{n_clients}"] = {
            "tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 2),
            "new_tokens": toks,
        }
        log(f"serving_n{n_clients}", serving[f"clients_{n_clients}"])
    base = serving["clients_1"]["tok_per_s"]
    serving["scaling_8_over_1"] = round(
        serving["clients_8"]["tok_per_s"] / base, 2) if base else None
    # dispatch-ahead instrumentation (PR 3): proves the pipeline actually
    # ran ahead of the host under transport load, plus the dispatch-vs-sync
    # split so a TPU session can see where the step wall lives (one
    # llm_stats() snapshot — it drains the same deques /metrics consumes)
    if getattr(server, "_batcher_service", None) is not None:
        from benchmarks._pipeline_stats import pipeline_report

        serving["pipeline"] = pipeline_report(server)
    # served-vs-direct: the VERDICT weak-#1 ratio (0.11 pre-pipelining),
    # against the same-session phase-A b8 direct-decode row when present
    direct = report.get("direct_decode", {}).get("b8", {}).get("tok_per_s")
    if direct:
        serving["served_vs_direct_b8"] = round(
            serving["clients_8"]["tok_per_s"] / direct, 3)
    serving["note"] = (
        "the batcher keeps pipeline_depth decode steps dispatched ahead of "
        "the host (PR 3); DECODE_FUSE_STEPS=K runs K tokens per host "
        "sync; served_vs_direct_b8 is the architecture claim (VERDICT "
        "weak #1: 0.11 before pipelining, earlier harness)")
    report["rest_continuous_batching"] = serving
    _write(report)


def _prefix_multi_turn(server, report, rng, vocab, plen, max_new) -> None:
    import numpy as np

    turn1 = rng.integers(1, vocab, size=plen).tolist()
    ans = server.generate([turn1], max_new_tokens=max_new)["tokens"][0]
    follow = rng.integers(1, vocab, size=max_new).tolist()
    turn2 = turn1 + ans + follow

    def prefill_time(clear: bool, repeats: int = 7) -> float:
        times = []
        for _ in range(repeats):
            if clear:
                server.clear_prefix_cache()
            else:
                server.clear_prefix_cache()
                server.generate([turn1], max_new_tokens=1)  # re-prime prefix
            t0 = time.perf_counter()
            server.generate([turn2], max_new_tokens=1)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    cold = prefill_time(clear=True)
    cached = prefill_time(clear=False)

    # Request wall time includes dispatch, which can dwarf the compute
    # saved, so ALSO time the raw jitted calls the two paths
    # dispatch — full-prompt prefill vs suffix-only extend — minus a
    # measured trivial-dispatch floor, which isolates device time.
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import PAD_POS

    def med_call(fn, *a, repeats=15):
        fn(*a)  # warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    noop = jax.jit(lambda x: x + 1)
    floor = med_call(noop, jnp.zeros((8,), jnp.float32))

    buckets = sorted(server.len_buckets)
    plen2 = len(turn2)
    bucket2 = next((b for b in buckets if b >= plen2), plen2)
    mlen = max(plen2, buckets[-1]) + max_new
    toks = np.zeros((1, bucket2), np.int32)
    poss = np.full((1, bucket2), PAD_POS, np.int32)
    toks[0, :plen2] = turn2
    poss[0, :plen2] = np.arange(plen2)
    prefill = server._get_prefill(1, bucket2, mlen)
    cold_call = med_call(prefill, server._params, jnp.asarray(toks), jnp.asarray(poss))

    server.clear_prefix_cache()
    server.generate([turn1], max_new_tokens=1)  # prime turn1 prefix
    hit = server._prefix_lookup(turn2, mlen)
    assert hit is not None, "prefix lookup must hit after priming"
    p0, _, caches, _ = hit
    suffix = turn2[p0:]
    sbucket = next((b for b in buckets if b >= len(suffix)), len(suffix))
    stoks = np.zeros((1, sbucket), np.int32)
    spos = np.full((1, sbucket), PAD_POS, np.int32)
    stoks[0, :len(suffix)] = suffix
    spos[0, :len(suffix)] = np.arange(p0, p0 + len(suffix))
    extend = server._get_extend(1, sbucket, mlen)
    cached_call = med_call(extend, server._params, caches, jnp.asarray(stoks),
                           jnp.asarray(spos), jnp.asarray(p0, jnp.int32))

    report["prefix_multi_turn"] = {
        "turn2_prompt_tokens": len(turn2),
        "cold_prefill_s": round(cold, 4),
        "cached_prefill_s": round(cached, 4),
        "cached_speedup_wall": round(cold / cached, 2) if cached else None,
        "prefix_hits_total": server._prefix_hits,
        "device_isolated": {
            "dispatch_floor_s": round(floor, 4),
            "cold_prefill_call_s": round(cold_call, 4),
            "cached_extend_call_s": round(cached_call, 4),
            "cold_minus_floor_s": round(cold_call - floor, 4),
            "cached_minus_floor_s": round(cached_call - floor, 4),
            "device_speedup": round(
                (cold_call - floor) / max(cached_call - floor, 1e-9), 2),
            "note": "request wall includes dispatch; "
                    "the floor-subtracted pair isolates the device-side "
                    "cost of full-prompt prefill vs suffix-only extend",
        },
    }
    log("prefix_multi_turn", report["prefix_multi_turn"])
    _write(report)


def _prefix_long_system(server, report, rng, vocab, on_tpu) -> None:
    """VERDICT #7: measure the prefix cache where it matters — a 1.5-2k
    token shared system prefix with a short per-request suffix. Cold arm
    prefills the full (prefix + suffix) prompt; cached arm runs only the
    suffix extend against the stored prefix KV. Device-isolated via the
    round-5 methodology: median jitted-call walls minus a measured
    trivial-dispatch floor (request wall includes dispatch and would hide
    the device-side ratio)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import PAD_POS
    from seldon_core_tpu.utils import bucket as _bucket_fn

    # the long-prefix shape: past the top len_bucket on purpose (that is
    # the point — short-bucket pairs were already phase C)
    prefix_len = 1536 if on_tpu else 192
    suffix_len = 64 if on_tpu else 16
    if prefix_len + suffix_len + 8 > server._cfg.max_seq_len:
        report["prefix_long_system"] = {
            "skipped": f"model context {server._cfg.max_seq_len} too short "
                       f"for a {prefix_len}-token prefix"}
        _write(report)
        return
    system = rng.integers(1, vocab, size=prefix_len).tolist()
    suffix = rng.integers(1, vocab, size=suffix_len).tolist()
    full = system + suffix

    def med_call(fn, *a, repeats=7):
        fn(*a)  # warm (compile)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    noop = jax.jit(lambda x: x + 1)
    floor = med_call(noop, jnp.zeros((8,), jnp.float32))

    # a bucket snug around the full prompt, so the cold arm is not padded
    # to 2x by the round-up-past-top-bucket rule
    buckets = sorted(set(list(server.len_buckets)
                         + [prefix_len, prefix_len + 2 * suffix_len]))
    full_bucket = _bucket_fn(len(full), buckets)
    mlen = full_bucket + 8

    # cold: the whole prompt through one prefill at its bucket
    toks = np.zeros((1, full_bucket), np.int32)
    poss = np.full((1, full_bucket), PAD_POS, np.int32)
    toks[0, :len(full)] = full
    poss[0, :len(full)] = np.arange(len(full))
    prefill = server._get_prefill(1, full_bucket, mlen)
    cold_call = med_call(prefill, server._params, jnp.asarray(toks),
                         jnp.asarray(poss))

    # cached: prefill the system prefix ONCE (the shared entry), then time
    # only the suffix extend every request pays
    ptoks = np.zeros((1, prefix_len), np.int32)
    ppos = np.full((1, prefix_len), PAD_POS, np.int32)
    ptoks[0, :] = system
    ppos[0, :] = np.arange(prefix_len)
    pf = server._get_prefill(1, prefix_len, mlen)
    _, prefix_caches = pf(server._params, jnp.asarray(ptoks), jnp.asarray(ppos))
    sbucket = _bucket_fn(suffix_len, buckets)
    stoks = np.zeros((1, sbucket), np.int32)
    spos = np.full((1, sbucket), PAD_POS, np.int32)
    stoks[0, :suffix_len] = suffix
    spos[0, :suffix_len] = np.arange(prefix_len, prefix_len + suffix_len)
    extend = server._get_extend(1, sbucket, mlen)
    cached_call = med_call(extend, server._params, prefix_caches,
                           jnp.asarray(stoks), jnp.asarray(spos),
                           jnp.asarray(prefix_len, jnp.int32))

    report["prefix_long_system"] = {
        "prefix_tokens": prefix_len,
        "suffix_tokens": suffix_len,
        "dispatch_floor_s": round(floor, 4),
        "cold_prefill_call_s": round(cold_call, 4),
        "cached_extend_call_s": round(cached_call, 4),
        "cold_minus_floor_s": round(cold_call - floor, 4),
        "cached_minus_floor_s": round(cached_call - floor, 4),
        "device_speedup": round(
            (cold_call - floor) / max(cached_call - floor, 1e-9), 2),
        "note": "shared system-prompt shape: every request re-paying the "
                "full long-prefix prefill vs suffix-only extend against "
                "the cached prefix KV; medians of 7, dispatch floor "
                "subtracted (round-5 device-isolated methodology)",
    }
    log("prefix_long_system", report["prefix_long_system"])
    _write(report)


def _multitenant_arm(server, report, rng, vocab, plen, max_new,
                     on_tpu) -> None:
    """Phase L (ISSUE 15): the multi-tenant claims, measured.

    (1) adapted-vs-base tokens/s: the same request wave served all-base
        and all-adapted (ADAPTERS distinct LoRA adapters round-robin)
        through one continuous batch — the near-base-model-throughput
        claim (hlolint additionally pins the compiled cost band).
    (2) SLO isolation under a deterministic flood: interactive TTFT p95
        alone vs with a batch-class tenant saturating the queue
        (SLO_MIX interactive:batch request counts, everything submitted
        in one burst so arrival order favors the flood). The acceptance
        bar is flooded p95 <= 2x unloaded p95 WHILE the flood still
        generates tokens (no starvation either way); the deterministic
        CI twin is tests/test_scheduler.py::
        test_slo_isolation_under_deterministic_load, and
        MULTITENANT_ENFORCE=1 (or on-chip) makes the bar exit-code-
        enforced here too.
    (3) per-tenant quota sheds: the flooding tenant runs under
        TENANT_QUOTA, so part of its burst sheds 503 — counted, and the
        seldon_tenant_shed_total{tenant,slo_class} series' visibility on
        /metrics is checked from a real registry scrape."""
    import asyncio
    import types

    from seldon_core_tpu.runtime.adapters import projection_dims
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.runtime.resilience import ShedError
    from seldon_core_tpu.servers.llmserver import LLMServer

    n_adapters = int(os.environ.get("ADAPTERS", "3"))
    mix = os.environ.get("SLO_MIX", "6:24")
    n_inter, n_batch = (int(x) for x in mix.split(":"))
    quota = int(os.environ.get("TENANT_QUOTA", str(max(4, n_batch // 2))))
    rank = 8 if on_tpu else 4
    page_size = 64 if on_tpu else 8

    if on_tpu:
        kwargs = dict(model="llama2-7b", quantize="int8")
    else:
        kwargs = dict(model="transformer",
                      model_kwargs=dict(vocab_size=256, dim=64, n_layers=2,
                                        n_heads=4, n_kv_heads=2, ffn_dim=128,
                                        max_seq_len=1024))
    ls = LLMServer(init_random=True, seed=0, max_new_tokens=max_new,
                   len_buckets=(plen,), batch_buckets=(1,),
                   temperature=0.0, eos_id=-1, lora_rank=rank,
                   lora_max_adapters=n_adapters + 1,
                   tenant_quotas={"bulk": quota}, **kwargs)
    ls.load()
    cfg = ls._cfg
    arng = np.random.default_rng(7)
    names = []
    for i in range(n_adapters):
        w = {p: (arng.normal(size=(cfg.n_layers, di, rank)) * 0.05,
                 arng.normal(size=(cfg.n_layers, rank, do)) * 0.05)
             for p, (di, do) in projection_dims(cfg).items()}
        names.append(f"tenant-{i}")
        ls.adapter_registry.load(names[-1], w)

    slots = 4
    mlen = plen + max_new + page_size

    def run_wave(reqs, sync_metrics=False):
        """One burst of requests through a fresh batcher. Returns
        (per-request TTFT, outputs, quota sheds, wall, metric text)."""

        async def go():
            b = ContinuousBatcher(ls, max_slots=slots, max_len=mlen,
                                  len_buckets=(plen,), page_size=page_size)
            ttfts = [None] * len(reqs)
            outs = [None] * len(reqs)
            sheds = [0]
            t0 = time.perf_counter()

            async def one(i, r):
                t_sub = time.perf_counter()

                def first(t, i=i, t_sub=t_sub):
                    if t is not None and ttfts[i] is None:
                        ttfts[i] = time.perf_counter() - t_sub

                try:
                    outs[i] = await b.submit(
                        r["prompt"], max_new_tokens=max_new, on_token=first,
                        tenant=r["tenant"], slo_class=r["slo_class"],
                        adapter=r.get("adapter"))
                except ShedError:
                    sheds[0] += 1

            await asyncio.gather(*[one(i, r) for i, r in enumerate(reqs)])
            wall = time.perf_counter() - t0
            text = ""
            if sync_metrics:
                # the tenant tallies flow llm_stats -> sync_llm exactly as
                # in serving; a real registry scrape proves the series
                from seldon_core_tpu.metrics.registry import MetricsRegistry

                ls._batcher_service = types.SimpleNamespace(batcher=b)
                try:
                    m = MetricsRegistry(deployment="bench", predictor="L")
                    m.sync_llm(ls)
                    text = m.expose().decode()
                finally:
                    del ls._batcher_service
            await b.close()
            return ttfts, outs, sheds[0], wall, text

        return asyncio.run(go())

    def mk(n, tenant, cls, seed):
        prng = np.random.default_rng(seed)
        return [dict(prompt=prng.integers(1, vocab, size=plen).tolist(),
                     tenant=tenant, slo_class=cls) for _ in range(n)]

    # warm the adapted compiled programs (one shape serves base AND
    # adapted slots) so the wave walls below measure serving, not compile
    run_wave(mk(slots, "warm", "batch", seed=5))

    # (1) adapted-vs-base throughput, same wave shape
    base_reqs = mk(2 * slots, "base", "batch", seed=11)
    _, base_outs, _, base_wall, _ = run_wave(base_reqs)
    ad_reqs = mk(2 * slots, "acme", "batch", seed=11)
    for i, r in enumerate(ad_reqs):
        r["adapter"] = names[i % n_adapters]
    _, ad_outs, _, ad_wall, _ = run_wave(ad_reqs)
    base_tps = sum(len(t) for t in base_outs if t) / base_wall
    ad_tps = sum(len(t) for t in ad_outs if t) / ad_wall

    # (2) unloaded interactive TTFT, then the flood
    un_t, _, _, _, _ = run_wave(mk(n_inter, "chat", "interactive", seed=21))
    un_p95 = float(np.percentile([t for t in un_t if t is not None], 95))
    flood = mk(n_batch, "bulk", "batch", seed=31) + \
        mk(n_inter, "chat", "interactive", seed=41)
    fl_t, fl_outs, fl_sheds, _, text = run_wave(flood, sync_metrics=True)
    inter_t = [t for t in fl_t[n_batch:] if t is not None]
    fl_p95 = float(np.percentile(inter_t, 95)) if inter_t else float("inf")
    batch_tokens = sum(len(t) for t in fl_outs[:n_batch] if t)
    attain = (sum(1 for t in inter_t if t <= 2 * un_p95)
              / max(len(inter_t), 1))
    shed_visible = ("seldon_tenant_shed_total" in text
                    and 'tenant="bulk"' in text)

    arm = {
        "adapters": n_adapters, "rank": rank, "slo_mix": mix,
        "tenant_quota_bulk": quota,
        "tok_per_s": {"base": round(base_tps, 1),
                      "adapted": round(ad_tps, 1),
                      "adapted_vs_base": round(ad_tps / base_tps, 3)},
        "interactive_ttft_ms": {
            "unloaded_p95": round(un_p95 * 1e3, 2),
            "flooded_p95": round(fl_p95 * 1e3, 2),
            "isolation_ratio": round(fl_p95 / un_p95, 3) if un_p95 else None,
        },
        "slo_attainment_2x": round(attain, 3),
        "batch_tokens_under_flood": batch_tokens,
        "quota_sheds": fl_sheds,
        "tenant_shed_metric_visible": shed_visible,
    }
    report["multitenant"] = arm
    log("multitenant", arm)
    _write(report)
    # no starvation either way is unconditional; the latency bar is
    # enforced on chip / on request (CPU rehearsal shares cores between
    # the flood and the victim, so wall-clock there is indicative only)
    assert batch_tokens > 0, "batch class starved under the flood"
    assert fl_sheds > 0 and shed_visible, \
        "quota sheds must happen and be scrape-visible"
    if on_tpu or os.environ.get("MULTITENANT_ENFORCE", "") == "1":
        assert fl_p95 <= 2 * un_p95, (
            f"interactive TTFT p95 {fl_p95:.4f}s exceeded 2x its "
            f"unloaded value {un_p95:.4f}s under the batch flood")


def _disagg_arm(server, report, rng, vocab, plen, max_new, on_tpu) -> None:
    """Phase D with DISAGG set (ISSUE 9): disaggregation's headline claim,
    measured — the decode slice's worst victim inter-token gap under the
    SAME long-prefill adversary phase P times, with admission prefill
    moved off-slice entirely (local chunked prefill interleaves the burst;
    remote prefill removes it), plus the adversary's TTFT, the TTFT /
    inter-token-gap histogram summaries (the new
    seldon_llm_ttft_seconds / seldon_llm_inter_token_seconds series), and
    the handoff counters (count, device-to-device bytes, per-handoff
    wall)."""
    import asyncio

    import jax

    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.runtime.disagg import normalize_disaggregation

    mode = normalize_disaggregation(os.environ.get("DISAGG", ""))
    if mode == "off" or len(jax.devices()) < 2:
        note = (f"DISAGG={mode}, devices={len(jax.devices())}: arm needs "
                "remote_prefill + >= 2 devices (CPU rehearsal: XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)")
        report["disagg"] = {"note": note}
        log("disagg", report["disagg"])
        return
    pre_n = int(os.environ.get("PREFILL_DEVICES", "0")) or 1
    dec_n = int(os.environ.get("DECODE_DEVICES", "0"))
    workers = int(os.environ.get("PREFILL_WORKERS", "0"))
    page_size = int(os.environ.get("KV_PAGE_SIZE", "0")) or (
        64 if on_tpu else 8)
    chunk = int(os.environ.get("PREFILL_CHUNK", "0")) or (
        256 if on_tpu else 8)
    long_len = server.len_buckets[-1]

    from seldon_core_tpu.parallel.mesh import disaggregated_mesh

    mesh = disaggregated_mesh(pre_n, dec_n)

    def adversary_run(disagg):
        async def go():
            kw = dict(max_slots=2, max_len=long_len + max_new,
                      page_size=page_size,
                      prefill_chunk=chunk, disaggregation=disagg)
            if disagg != "off":
                kw["disagg_mesh"] = mesh
                if workers:
                    kw["prefill_workers"] = workers
            b = ContinuousBatcher(server, **kw)
            gaps, last = [], [None]

            def on_tok(t):
                now = time.perf_counter()
                if t is not None and last[0] is not None:
                    gaps.append(now - last[0])
                last[0] = now

            victim_p = rng.integers(1, vocab, size=plen // 2).tolist()
            steady = asyncio.ensure_future(
                b.submit(victim_p, max_new_tokens=4 * max_new,
                         on_token=on_tok))
            while not any(s.active for s in b._slots):
                await asyncio.sleep(0.002)
            warm_gaps = len(gaps)
            adv_p = rng.integers(1, vocab, size=long_len).tolist()
            t0 = time.perf_counter()
            ttft = [None]

            def first_tok(t):
                if t is not None and ttft[0] is None:
                    ttft[0] = time.perf_counter() - t0
            await asyncio.sleep(0)
            adv = asyncio.ensure_future(
                b.submit(adv_p, max_new_tokens=4, on_token=first_tok))
            await asyncio.gather(steady, adv)
            handoff = b.handoff_stats()
            await b.close()
            during = gaps[warm_gaps:] or [0.0]
            base = [g for g in gaps[:warm_gaps] if g > 1e-6] or [0.0]
            return (float(np.median(base)), float(np.max(during)),
                    ttft[0], handoff)

        return asyncio.run(go())

    # warm passes: the chunk/decode/import programs (and the workers'
    # committed param copies) compile outside the timed window
    adversary_run("off")
    adversary_run(mode)
    # drain latency deques so the histograms below cover timed runs only
    server.llm_stats()
    base_g, worst_local, ttft_local, _ = adversary_run("off")
    _, worst_disagg, ttft_disagg, handoff = adversary_run(mode)
    st = server.llm_stats()

    def _hist(samples_s):
        if not samples_s:
            return None
        ms = np.asarray(samples_s) * 1e3
        return {"n": int(ms.size),
                "p50_ms": round(float(np.percentile(ms, 50)), 2),
                "p90_ms": round(float(np.percentile(ms, 90)), 2),
                "p99_ms": round(float(np.percentile(ms, 99)), 2),
                "max_ms": round(float(np.max(ms)), 2)}

    disagg = {
        "mode": mode,
        "prefill_devices": len(mesh.prefill_devices),
        "decode_devices": len(mesh.decode_devices),
        "prefill_workers": workers or len(mesh.prefill_devices),
        "adversary_prompt_tokens": long_len, "prefill_chunk": chunk,
        "victim_median_gap_ms": round(1e3 * base_g, 2),
        # local_chunked is PR 7's number on today's build; disagg is the
        # PR 9 claim — the burst leaves the decode slice entirely
        "victim_worst_gap_ms": {
            "local_chunked": round(1e3 * worst_local, 2),
            "disagg": round(1e3 * worst_disagg, 2),
        },
        "adversary_ttft_ms": {
            "local_chunked": round(1e3 * (ttft_local or 0), 2),
            "disagg": round(1e3 * (ttft_disagg or 0), 2),
        },
        "gap_inflation_x": {
            "local_chunked": round(worst_local / base_g, 2) if base_g
            else None,
            "disagg": round(worst_disagg / base_g, 2) if base_g else None,
        },
        "handoffs_total": handoff["handoffs_total"],
        "handoff_transfer_mb": round(
            handoff["handoff_transfer_bytes_total"] / 1e6, 3),
        # the new latency series, summarized the way the Prometheus
        # histograms bucket them (llm_stats -> seldon_llm_ttft_seconds /
        # seldon_llm_inter_token_seconds / seldon_llm_handoff_seconds)
        "ttft_hist": _hist(st.get("ttft_s", [])),
        "inter_token_hist": _hist(st.get("inter_token_s", [])),
        "handoff_hist": _hist(st.get("handoff_times_s", [])),
    }
    report["disagg"] = disagg
    log("disagg", disagg)
    _write(report)


def _network_handoff_arm(server, report, rng, vocab, plen, max_new,
                         on_tpu) -> None:
    """Phase N with NETWORK_HANDOFF set (ISSUE 18): the framed socket
    handoff vs jax.device_put on the SAME batch-8 concurrent streaming
    workload. The headline is the serialization share — total frame
    encode+decode seconds (the codec's own timers, the same samples
    seldon_frame_{encode,decode}_seconds scrape) over the network run's
    end-to-end wall — with the <5% acceptance bar reported alongside,
    plus wire bytes per handoff and the handoff-seconds histogram."""
    import asyncio

    import jax

    from seldon_core_tpu.codec import framing
    from seldon_core_tpu.parallel.mesh import disaggregated_mesh
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    if len(jax.devices()) < 2:
        note = (f"devices={len(jax.devices())}: arm needs >= 2 (CPU "
                "rehearsal: XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)")
        report["network_handoff"] = {"note": note}
        log("network_handoff", report["network_handoff"])
        return
    pre_n = int(os.environ.get("PREFILL_DEVICES", "0")) or 1
    page_size = int(os.environ.get("KV_PAGE_SIZE", "0")) or (
        64 if on_tpu else 8)
    clients = 8
    # the handoff (and so the codec) is paid once per request while the
    # stream pays per token: measure at the disagg arm's steady-request
    # length so the per-handoff cost amortizes the way serving does
    gen = 4 * max_new
    mesh = disaggregated_mesh(pre_n)
    prompts = [rng.integers(1, vocab, size=plen).tolist()
               for _ in range(clients)]

    def run(transport):
        async def go():
            b = ContinuousBatcher(
                server, max_slots=clients, max_len=plen + gen,
                page_size=page_size,
                disaggregation="remote_prefill", disagg_mesh=mesh,
                handoff_transport=transport)
            # a per-token callback keeps this the batch-8 CONCURRENT
            # STREAMING shape the acceptance bar names
            streamed = [0]

            def on_tok(t):
                if t is not None:
                    streamed[0] += 1

            t0 = time.perf_counter()
            outs = await asyncio.gather(*[
                b.submit(p, max_new_tokens=gen, on_token=on_tok)
                for p in prompts])
            wall = time.perf_counter() - t0
            stats = b.handoff_stats()
            await b.close()
            assert streamed[0] == sum(len(t) for t in outs)
            return outs, wall, stats

        return asyncio.run(go())

    # warm both transports: prefill/decode/import programs (and the
    # workers' committed param copies) compile outside the timed windows
    run("device")
    run("network")
    server.llm_stats()      # drain latency deques
    framing.frame_stats()   # drain codec timers: the window owns its samples
    base_outs, wall_dev, _ = run("device")
    outs, wall_net, hstats = run("network")
    fstats = framing.frame_stats()
    st = server.llm_stats()
    assert outs == base_outs, "network handoff broke bit-exactness"

    ser_s = (sum(fstats["frame_encode_times_s"]) +
             sum(fstats["frame_decode_times_s"]))
    tokens = sum(len(t) for t in outs)
    wire_bytes = hstats["handoff_network_bytes_total"]
    n_handoffs = hstats["handoffs_total"]

    def _hist(samples_s):
        if not samples_s:
            return None
        ms = np.asarray(samples_s) * 1e3
        return {"n": int(ms.size),
                "p50_ms": round(float(np.percentile(ms, 50)), 2),
                "p90_ms": round(float(np.percentile(ms, 90)), 2),
                "p99_ms": round(float(np.percentile(ms, 99)), 2),
                "max_ms": round(float(np.max(ms)), 2)}

    entry = {
        "clients": clients, "max_new_tokens": gen,
        "prompt_tokens": plen,
        "prefill_devices": len(mesh.prefill_devices),
        "tok_per_s": {"device": round(tokens / wall_dev, 1),
                      "network": round(tokens / wall_net, 1)},
        # when device_put beats the socket: the same-host rehearsal pays
        # the codec + TCP for nothing — the ratio quantifies that tax;
        # cross-host there is no device path at all (DECODE_NOTES PR 18)
        "network_vs_device": round(wall_dev / wall_net, 3),
        "handoffs_total": n_handoffs,
        "handoff_wire_mb": round(wire_bytes / 1e6, 3),
        "bytes_per_handoff": round(wire_bytes / max(n_handoffs, 1)),
        # the framing tentpole's acceptance bar, reported: codec seconds
        # over end-to-end wall at batch-8 concurrent streaming
        "serialization_s": round(ser_s, 4),
        "serialization_share_pct": round(100.0 * ser_s / wall_net, 2),
        "serialization_share_limit_pct": 5.0,
        "handoff_hist": _hist(st.get("handoff_times_s", [])),
        "ttft_hist": _hist(st.get("ttft_s", [])),
    }
    report["network_handoff"] = entry
    log("network_handoff", entry)
    _write(report)


def _attribution(server, report, rng, vocab, plen, on_tpu, max_new=16) -> None:
    import jax

    from benchmarks.tpu_profile import summarize, walk_op_profile

    if True:
        attrib = {}
        for b in (1, 8):
            prompts = [rng.integers(1, vocab, size=plen).tolist()
                       for _ in range(b)]
            server.generate(prompts, max_new_tokens=8)  # ensure compiled
            logdir = os.path.join(HERE, f"profile_llm7b_b{b}")
            os.makedirs(logdir, exist_ok=True)
            with jax.profiler.trace(logdir):
                server.generate(prompts, max_new_tokens=16)
            s = summarize(logdir)
            flat = []
            if "data" in s:
                tree = s["data"]
                root = tree.get("byCategory") or tree.get("byProgram") or tree
                walk_op_profile(root, flat)
                flat.sort(key=lambda r: -(r["time_frac"] or 0))
                attrib[f"b{b}"] = flat[:25]
            else:
                attrib[f"b{b}"] = s
            log(f"profiled_b{b}", "ok" if "data" in s else s)
        report["step_attribution_top_ops"] = attrib
    _write(report)


def _write(report) -> None:
    with open(REPORT, "w") as f:
        json.dump(report, f, indent=2)
    print("written", REPORT, flush=True)


if __name__ == "__main__":
    main()
