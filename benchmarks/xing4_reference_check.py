"""How far the SERVED logits of a benchmark configuration are from its plain
float32 reference, and how far WRONG references are: the readings a
configuration's `reference_tolerance` is set from (perf/configs/<config>.json).

    chiprun -- python benchmarks/xing4_reference_check.py \
        --workload xing4-reasoning-decode --probes 12 --wrong-probes 2 --out chiprun_out/pr31r/reference_check.json
    chiprun -- python benchmarks/xing4_reference_check.py \
        --workload lfm2-rag-mixed --probes 12 --wrong-probes 2 --out chiprun_out/pr35/reference_check.json
    chiprun -- python benchmarks/xing4_reference_check.py --workload qwen3next-longctx-mixed \
        --probes 12 --wrong-probes 2 --long 2 --long-size 6144+64 --out chiprun_out/pr38/reference_check.json
    chiprun -- python benchmarks/xing4_reference_check.py --workload qwen3next-longctx-mixed \
        --probes 2 --wrong-probes 2 --only-wrongs state_held_in_bf16 --long 2 --long-size 6144+64 \
        --long-wrongs state_held_in_bf16,weights_at_4_bits --out chiprun_out/pr38/reference_check_long.json
    chiprun -- python benchmarks/xing4_reference_check.py --workload smallthinker-longqa-mixed \
        --probes 12 --wrong-probes 2 --long 1 --long-size 14336+64 --out chiprun_out/pr49/reference_check.json
    chiprun -- python benchmarks/xing4_reference_check.py --workload granite4h-sessions-decode \
        --probes 12 --wrong-probes 2 --long 2 --long-size 512+768 \
        --long-wrongs state_held_in_bf16,weights_at_4_bits --out chiprun_out/pr53/reference_check.json
    chiprun -- python benchmarks/xing4_reference_check.py --workload phi4flash-longtrace-decode \
        --probes 12 --wrong-probes 2 --long 2 --long-size 2400+64 \
        --long-wrongs state_held_in_bf16,weights_at_4_bits --out chiprun_out/pr55/reference_check.json
    (then once more with --probes 2 --wrong-probes 2 --only-low: the 4-bit tree in a call of its
    own, because the machine's host holds 40 GiB and a 15-layer tree is 11.5 GB of it)
    chiprun -- python benchmarks/xing4_reference_check.py --workload lfm2-rag-mixed \
        --probes 1 --wrong-probes 0 --long 1 --long-size 900+8,1924+8 --long-unseeded \
        --out chiprun_out/pr58/ref_lfm2.json
    (PR 58: the one-offs come WITHOUT a seed, so their tail of 900 rows is ONE padded chunk of
    the wide program, whose row 899 the head reads; seeded, as every other call here, the same
    tail is four narrow chunks)

In one process on the chip: the server the cell's files describe (the
configuration's `weights_seed`, the cell's slots and cache length) and its
batcher serve `--probes` seeded probes of the cell's probe size (a prompt that
crosses a chunk boundary + decoded rows, logits asked, out of the step programs
that serve every request) and, with `--long N`, N one-off requests at the cell's longest
prompt and answer (or of `--long-size PROMPT+NEW`). Then the server is dropped, the int8 tree is taken to the
host, and seldon_core_tpu/models/reference.py computes each comparison on the
chip in float32 at highest matmul precision, a leaf at a time: the right
reference for every probe, FOLLOWING the experts the served path took (the
probe's `routing`; perf/planes/llm_rest_followed_reference.py says why) and,
for the first `--free-probes`, choosing for itself; and for the first
`--wrong-probes` each wrong one (`reference.WRONG`'s keywords, and the weights
rounded to 4 bits, the nearest precision below the configuration's int8), also
following. Readings are the plane's two: max |served - reference| over
max |reference|, and how far the furthest served choice lies behind the
reference's own.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WRONGS = {
    "plain_residual": {"streams": False}, "one_sinkhorn_iteration": {"sinkhorn_iters": 1},
    "no_selection_bias": {"select_bias": False}, "softmax_scores": {"router_score": "softmax"},
    "no_q_norm": {"q_norm": False}, "no_shared_expert": {"shared": False},
    "largest_expert_left_out": {"leave_out_rank": 0}, "no_mscale_squared": {"scale_mscale": False},
}


def lfm2_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of a configuration with conv layers (its `work` is
    "lfm2"), for a probe of ``prompt_tokens`` prefilled in chunks of ``chunk``:
    the two that break the state's hand-over at a chunk's edge are placed where
    the probe's chunks end."""
    padded = -(-prompt_tokens // chunk) * chunk
    return {
        "state_zeroed_at_a_chunk_start": {"conv_reset_every": chunk},
        "state_from_the_chunks_last_row": {"conv_state_pad": (prompt_tokens, padded)},
        "taps_reversed": {"taps_reversed": True},
        "gate_b_left_out": {"gate_b": False}, "gate_c_left_out": {"gate_c": False},
        "qk_norm_over_the_whole_projection": {"qk_norm": "whole"}, "qk_norm_left_out": {"qk_norm": False},
        "softmax_scores": {"router_score": "softmax"}, "largest_expert_left_out": {"leave_out_rank": 0},
        "no_selection_bias": {"select_bias": False},
    }


def qwen3_next_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of a configuration with linear-attention layers (its
    `work` is "qwen3_next"), placed like ``lfm2_wrongs``' where the probe's
    chunks end."""
    padded = -(-prompt_tokens // chunk) * chunk
    return {
        "decay_left_out": {"gdn_decay": False}, "beta_one": {"gdn_beta": False},
        "no_l2_norm_on_q_and_k": {"gdn_l2norm": False},
        "state_zeroed_at_a_chunk_start": {"gdn_reset_every": chunk},
        "state_from_the_chunks_last_row": {"conv_state_pad": (prompt_tokens, padded)},
        "taps_reversed": {"taps_reversed": True}, "no_silu_after_the_taps": {"gdn_silu": False},
        "z_gate_left_out": {"gdn_z_gate": False}, "attention_gate_left_out": {"attn_gate": False},
        "rotary_over_the_whole_head": {"rotary_all": True},
        "no_shared_expert": {"shared": False}, "shared_gate_left_out": {"shared_gate": False},
        "largest_held_expert_left_out": {"leave_out_held": True},
        "state_held_in_bf16": {"gdn_state_bf16": True},
    }


def olmo_hybrid_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of Olmo-Hybrid (its `work` is "olmo_hybrid"): a dense
    model, so nothing is followed; the two that break the state's hand-over lie
    where the probe's chunks end."""
    padded = -(-prompt_tokens // chunk) * chunk
    return {
        "beta_not_doubled": {"gdn_beta_doubled": False}, "decay_left_out": {"gdn_decay": False},
        "no_l2_norm_on_q_and_k": {"gdn_l2norm": False},
        "state_zeroed_at_a_chunk_start": {"gdn_reset_every": chunk},
        "state_from_the_chunks_last_row": {"conv_state_pad": (prompt_tokens, padded)},
        "taps_reversed": {"taps_reversed": True}, "output_gate_left_out": {"gdn_z_gate": False},
        "q_not_scaled": {"gdn_q_scale": False},
        "pre_norm_in_place_of_branch_norm": {"norm_placement": "pre"},
        "qk_norm_a_head": {"qk_norm": "head_tiled"},
        "rope_at_theta_500000": {"rope_theta_wrong": 500000.0},
        "state_held_in_bf16": {"gdn_state_bf16": True},
    }


def granite_hybrid_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of granite-4.0-h (its `work` is "granite_hybrid"): a
    dense model, so nothing is followed; the two that break the state's hand-over
    lie where the probe's chunks end."""
    padded = -(-prompt_tokens // chunk) * chunk
    return {
        "state_held_in_bf16": {"ssd_state_bf16": True},
        "gate_after_the_norm": {"ssd_gate_after_norm": True},
        "attention_scaled_by_head_dim": {"attention_multiplier_off": True},
        "residual_multiplier_one": {"residual_multiplier_off": True},
        "skip_left_out": {"ssd_skip": False}, "conv_bias_left_out": {"ssd_conv_bias": False},
        "b_and_c_not_convolved": {"ssd_conv_bc": False},
        "state_zeroed_at_a_chunk_start": {"ssd_reset_every": chunk},
        "state_from_the_chunks_last_row": {"conv_state_pad": (prompt_tokens, padded)},
    }


def sambay_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of Phi-4-mini-flash (its `work` is "sambay"): a dense
    model, so nothing is followed; each of ISSUE 55's readings of a layer taken
    the other way (the weights at 4 bits are every configuration's)."""
    return {
        "state_held_in_bf16": {"s6_state_bf16": True},
        "state_zeroed_at_a_chunk_start": {"s6_reset_every": chunk},
        "memory_taken_after_the_gate": {"gmu_memory_gated": True},
        "memory_from_an_earlier_layer": {"gmu_memory_layer": 14},
        "skip_left_out_of_the_memory": {"gmu_memory_skip": False},
        "cross_layers_read_their_own_input": {"cross_kv_own": True},
        "pairs_by_halves": {"diff_pairs": "halves"},
        "lambda_init_of_layer_0": {"lambda_init_layer0": True},
        "sub_norm_left_out": {"diff_subln": False},
        "one_minus_lambda_init_left_out": {"diff_scale": False},
        "window_4_rows_off": {"window_wrong": 508},
        "layer_norm_without_the_mean": {"layer_norm_mean": False},
        "projection_biases_left_out": {"bias_off": "attention"},
        "norm_biases_left_out": {"bias_off": "norm"},
    }


def smallthinker_wrongs(prompt_tokens: int, chunk: int) -> dict:
    """The wrong references of SmallThinker (its `work` is "smallthinker"): each
    of ISSUE 49's readings of a layer taken the other way (the weights at 4 bits
    are every configuration's)."""
    return {
        "no_window": {"window_off": True}, "window_a_page_wide_of_the_mark": {"window_wrong": 4096 + 64},
        "rope_on_the_global_layers_too": {"rope_on_global": True},
        "no_rope_on_the_window_layers": {"rope_on_window": False},
        "router_fed_the_ffn_input": {"router_input": "ffn_input"},
        "silu_for_relu": {"ffn_act": "silu"}, "top_6_not_renormalised": {"renormalize": False},
        "one_expert_a_token_left_out": {"leave_out_rank": 0},
    }


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load(kind: str, name: str, rehearse: bool) -> dict:
    """A benchmark file; with ``rehearse`` at the toy sizes of its block, as
    perf/run.py --rehearse-cpu reads it."""
    with open(os.path.join(REPO, "perf", kind, name + ".json")) as f:
        data = json.load(f)
    toy = data.pop("rehearse", {})
    return merge(data, toy) if rehearse else data


def four_bits(params):
    """The int8 tree with every quantized leaf rounded to 4 bits on its own
    scale (q in [-8, 7], the scale 127 / 7 times as coarse)."""
    import jax

    from seldon_core_tpu.ops.quantize import QuantizedTensor

    def visit(leaf):
        if not isinstance(leaf, QuantizedTensor):
            return leaf
        q = np.clip(np.rint(np.asarray(leaf.q, np.float32) * (7.0 / 127.0)), -8, 7).astype(np.int8)
        return dataclasses.replace(
            leaf, q=q, scale=np.asarray(leaf.scale) * np.float32(127.0 / 7.0))

    return jax.tree.map(visit, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--probes", type=int, default=6)
    ap.add_argument("--long", type=int, default=0)
    ap.add_argument("--long-size", default="", metavar="PROMPT+NEW[,PROMPT+NEW]",
                    help="the one-off requests' size, or sizes: --long of each "
                         "(default: the cell's longest prompt and answer)")
    ap.add_argument("--long-unseeded", action="store_true",
                    help="the one-offs come WITHOUT a seed, once the wide chunk program is there "
                         "(a first request nobody reads starts its build): a tail of 769-1,024 "
                         "rows is then ONE padded wide chunk (runtime/batcher.py _chunk_width)")
    ap.add_argument("--wrong-probes", type=int, default=1)
    ap.add_argument("--only-wrongs", default="", metavar="NAME,NAME",
                    help="of the wrong references (and weights_at_4_bits), these alone")
    ap.add_argument("--long-wrongs", default="", metavar="NAME,NAME",
                    help="wrong references (and weights_at_4_bits) read against every one-off too: "
                         "those whose fault grows with the length")
    ap.add_argument("--only-low", action="store_true",
                    help="of the wrong references, the 4-bit weights alone")
    ap.add_argument("--free-probes", type=int, default=2,
                    help="probes also compared with the reference choosing for itself")
    ap.add_argument("--seed", type=int, default=3000005900)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true", help="toy sizes, on whatever platform")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the configuration's file (e.g. num_hidden_layers=5)")
    args = ap.parse_args()

    import jax

    from seldon_core_tpu.models import reference
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.servers.llmserver import LLMServer

    cell = load("workloads", args.workload, args.rehearse)
    cfg = load("configs", cell["config"], args.rehearse)
    for item in args.set:
        key, _, value = item.partition("=")
        cfg[key] = json.loads(value)
    server_kw = merge(cfg["server"], cell.get("server", {}))
    slots, max_len = server_kw.pop("continuous_batching"), server_kw.pop("continuous_batching_max_len")
    server_kw["model_kwargs"] = {ours: cfg[theirs] for ours, theirs in cfg["model_kwargs_from"].items()}
    server_kw["seed"] = cfg["weights_seed"]
    t0 = time.monotonic()
    server = LLMServer(**server_kw)
    server.load()
    print(f"server loaded in {time.monotonic() - t0:.0f}s on {jax.devices()[0].device_kind}", flush=True)
    batcher = ContinuousBatcher(server, max_slots=slots, max_len=max_len)
    rng = np.random.default_rng(args.seed)
    probe = cell["probe"]
    sizes = [(probe["prompt_tokens"], probe["output_tokens"])] * args.probes
    if args.long:
        if args.long_size:
            long_sizes = [tuple(int(n) for n in size.split("+")) for size in args.long_size.split(",")]
        else:   # (a fixed length has a "value" and no "max")
            request = cell["traffic"]["request"]
            long_sizes = [tuple(request[key].get("max", request[key].get("value"))
                                for key in ("prompt_tokens", "output_tokens"))]
        sizes.extend(size for size in long_sizes for _ in range(args.long))
    asks = [(rng.integers(97, 123, size=n).tolist(), new) for n, new in sizes]

    async def serve():
        served = []
        for i, (prompt, new) in enumerate(asks):
            seed = 1234 + i
            if i >= args.probes and args.long_unseeded:
                seed = None
                if batcher._wide_build is None:   # no request has finished yet
                    await batcher.submit(rng.integers(97, 123, size=64).tolist(), 2)
                if batcher._wide_build is not None:   # (None: slots too short for a wide chunk)
                    await asyncio.to_thread(batcher._wide_build.join)
            info = {"logits": []}
            t1 = time.monotonic()
            out = await batcher.submit(prompt, new, info=info, seed=seed)
            print(f"served {len(prompt)} + {len(out)} in {time.monotonic() - t1:.1f}s "
                  f"(seed {seed}); chunks so far by head and width: "
                  f"{batcher._phases.stats()['chunk_head']}", flush=True)
            # a dense model routes nothing: there is nothing to follow
            assert info.get("routing_start", 0) == 0
            took = np.stack(info["routing"]) if info.get("routing") else None
            served.append((prompt, out, np.stack(info["logits"]), took))
        await batcher.close()
        return served

    served = asyncio.run(serve())
    stats = jax.devices()[0].memory_stats() or {}
    loop = batcher._phases.stats()   # which chunk programs, and which form of the latent read, served them
    print(f"prompt rows by chunk width: {loop['chunk_rows']}; attention calls {loop['attn_calls']}, "
          f"of them expanded once {loop.get('attn_expanded_calls')}", flush=True)
    model_cfg = server._cfg
    params = jax.device_get(server._params)
    del server, batcher
    gc.collect()
    jax.clear_caches()
    if args.only_low:
        # the 4-bit tree in the int8 one's place, before anything else is on
        # the host: both, beside what the readings leave there, pass 40 GiB
        params = four_bits(params)
        gc.collect()

    def reading(tree, prompt, out, got, took, sound=None, **wrong) -> tuple:
        """``took`` [tokens, MoE layers, k]: the served experts, which the
        reference follows (None = it chooses for itself, "free"). ``sound``: the
        right reference's logits for the same rows; a wrong one then says how
        far it lies from THEM too (`from_sound`: the fault's own size, with no
        served arithmetic in it). -> (the numbers, the reference's logits)."""
        first = len(prompt) - 1
        t1 = time.monotonic()
        ref, routing = reference.forward(tree, model_cfg, prompt + out,
                                         rows=slice(first, first + len(out)), follow=took, **wrong)
        ref = np.asarray(ref)
        scale = float(np.abs(ref).max())
        per_row = np.abs(got - ref).max(axis=1) / scale
        if routing:
            margins = np.stack([np.asarray(layer["margin"]) for layer in routing])
            behind = np.stack([np.asarray(layer["behind"]) for layer in routing])[:, :first + len(out)]
        else:       # a dense model
            margins, behind = np.ones((0,)), np.zeros((1,))
        apart = {} if sound is None else {
            "from_sound": float(np.abs(ref - sound).max() / np.abs(sound).max())}
        return {"over_scale": float(per_row.max()), "scale": scale, **apart,
                "first_row": float(per_row[0]), "rows_mean": float(per_row.mean()),
                "margins_under_1e-3": int((margins < 1e-3).sum()), "margins": int(margins.size),
                "behind_max": float(behind.max()), "fell_the_other_way": int((behind > 0).sum()),
                "seconds": time.monotonic() - t1}, ref

    result = {"workload": args.workload, "layers": model_cfg.n_layers, "set": args.set,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"), "probes": [], "wrong": {}, "long": None,
              "long_unseeded": args.long_unseeded, "chunk_rows": loop["chunk_rows"],
              "chunk_head": loop["chunk_head"]}

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)

    for i, (prompt, out, got, took) in enumerate(served[:0 if args.only_low else args.probes]):
        right, _ = reading(params, prompt, out, got, took)
        if i < args.free_probes:
            right["free"] = reading(params, prompt, out, got, None)[0]["over_scale"]
        print(f"probe {i}: {len(prompt)} + {len(out)}: {json.dumps(right)}", flush=True)
        result["probes"].append(right)
        save()
    def against(name: str, tree, i: int, **wrong) -> None:
        prompt, out, got, took = served[i]
        r, _ = reading(tree, prompt, out, got, took, **wrong)
        result["wrong"].setdefault(name, []).append([r["over_scale"], r["behind_max"]])
        print(f"probe {i} against {name}: logits {r['over_scale']:.4f}, furthest choice behind "
              f"{r['behind_max']:.4f}", flush=True)
        save()

    wrongs = WRONGS
    placed = {"lfm2": lfm2_wrongs, "qwen3_next": qwen3_next_wrongs,
              "olmo_hybrid": olmo_hybrid_wrongs, "granite_hybrid": granite_hybrid_wrongs,
              "smallthinker": smallthinker_wrongs, "sambay": sambay_wrongs}.get(cfg.get("work"))
    if placed:   # a configuration with state layers: some wrongs lie where the probe's chunks end
        wrongs = placed(probe["prompt_tokens"], server_kw.get("prefill_chunk") or 256)
    only = [name for name in args.only_wrongs.split(",") if name]
    for i in range(0 if args.only_low else min(args.wrong_probes, args.probes)):
        for name, wrong in wrongs.items():
            if not only or name in only:
                against(name, params, i, **wrong)
    if args.wrong_probes and (not only or "weights_at_4_bits" in only):
        # a second tree on the host: made late and dropped before the one-off,
        # whose 2,048 rows of logits are 1 GB a copy (the machine has 40 GiB)
        low = params if args.only_low else four_bits(params)
        for i in range(min(args.wrong_probes, args.probes)):
            against("weights_at_4_bits", low, i)
        del low
        gc.collect()
    long_wrongs = [name for name in args.long_wrongs.split(",") if name]
    low = four_bits(params) if "weights_at_4_bits" in long_wrongs else None
    for prompt, out, got, took in served[args.probes:]:
        right, sound = reading(params, prompt, out, got, took)
        for name in long_wrongs:
            tree, wrong = (low, {}) if name == "weights_at_4_bits" else (params, wrongs[name])
            r, _ = reading(tree, prompt, out, got, took, sound=sound, **wrong)
            right.setdefault("wrong", {})[name] = [r["over_scale"], r["behind_max"], r["from_sound"]]
        print(f"one-off: {len(prompt)} + {len(out)}: {json.dumps(right)}", flush=True)
        result["long"] = (result["long"] or []) + [
            {"prompt": len(prompt), "decoded": len(out), **right}]
        save()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
