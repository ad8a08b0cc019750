"""The stream-mixing metrics of `xing4-reasoning-decode` (`hc_step_ms`,
`hc_step_roofline`): their definition files on the accepted readers
(readers/hlo_scopes.py) and work/xing4.py's counts, on values small enough to
check by hand."""

import json
import os
import sys
import types

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

from readers import hlo_scopes as reader  # noqa: E402
from work import deepseek_v2, xing4 as work  # noqa: E402


def load(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "xing4.0-29b-a4b-int8.json")
CELL = load("workloads", "xing4-reasoning-decode.json")
LAYERS = CONFIG["num_hidden_layers"]
US = 1_000_000  # picoseconds


def write_trace(path: str, mixing: bool = True) -> None:
    """jit_decode_step [0, 100) us twice. Ops of a step: fusion.1 [5, 25) under
    resid.hc.pre, fusion.2 [30, 40) under resid.hc.post, fusion.3 [45, 85) under
    attn.latent.q. Without `mixing` (the parent's program) the first two are
    under the plain residual's add."""
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    proto = hlo_pb2.HloProto()
    proto.hlo_module.name = "decode_step"
    entry = proto.hlo_module.computations.add(name="main", id=1, root_id=10)
    base = "jit(decode_step)/Transformer/layer_1"
    paths = ((f"{base}/attention_hc/resid.hc.pre/div", f"{base}/resid.hc.post/add",
              f"{base}/attn/attention/attn.latent.q/dot_general") if mixing
             else (f"{base}/add", f"{base}/add", f"{base}/attn/attention/dot_general"))
    for i, path_ in enumerate(paths, 1):
        entry.instructions.add(name=f"fusion.{i}", id=10 + i, opcode="fusion").metadata.op_name = path_
    space = xplane_pb2.XSpace()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    em = meta.event_metadata[1]
    em.name = "jit_decode_step(1)"
    em.stats.add(metadata_id=1).bytes_value = proto.SerializeToString()
    device = space.planes.add(name="/device:TPU:0")
    for i, name in enumerate(["jit_decode_step(1)", "%fusion.1 = f32[4,32,1] fusion(%a)",
                              "%fusion.2 = bf16[32,1,4,3584] fusion(%b)",
                              "%fusion.3 = bf16[32,1,32,192] fusion(%c)"], 1):
        device.event_metadata[i].name = name

    def line(name: str, events: list) -> None:
        ln = device.lines.add(name=name, timestamp_ns=0)
        for meta_id, start, end in events:
            ln.events.add(metadata_id=meta_id, offset_ps=start * US, duration_ps=(end - start) * US)

    line("XLA Modules", [(1, 0, 100), (1, 200, 300)])
    step = [(2, 5, 25), (3, 30, 40), (4, 45, 85)]
    line("XLA Ops", step + [(m, s + 200, e + 200) for m, s, e in step])
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def scrape(calls, rows, pairs, touched, context):
    lines = [f'seldon_llm_moe_{k}_total{{model="m",program="decode"}} {v}' for k, v in (
        ("calls", calls), ("live_rows", rows), ("routed_pairs", pairs), ("experts_touched", touched))]
    lines += [f'seldon_llm_attn_calls_total{{model="m",program="decode"}} {calls}',
              f'seldon_llm_attn_context_tokens_total{{model="m",program="decode"}} {context}']
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(tmp_path, params=None, trace=None):
    # 100 decode steps of 32 live rows at 2,000 tokens of context each
    scrapes = [(0.0, scrape(10, 320, 12800, 3500, 640_000)),
               (1.0, scrape(110, 3520, 140800, 38500, 7_040_000))]
    run = types.SimpleNamespace(out_dir=str(tmp_path), perf_dir=PERF, repo=os.path.dirname(PERF),
                                note=lambda _t: None)
    return types.SimpleNamespace(
        scrapes=scrapes, params=params or {}, config=CONFIG, run=run, trace=trace, work=work,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_metric_files_name_the_accepted_readers_and_this_cells_work():
    ms, share = load("layer_metrics", "hc_step_ms.json"), load("layer_metrics", "hc_step_roofline.json")
    assert ms["reader"] == "hlo_scopes:per_call_ms" and share["reader"] == "hlo_scopes:roofline"
    for spec in (ms, share):
        assert spec["params"]["program"] == "decode_step" and spec["params"]["scopes"] == ["resid.hc"]
        assert spec["moves"] == "throughput" and spec["layer"] == "models / kernels"
    assert share["unit"] == "%" and share["params"]["bound"] == "hbm"
    assert callable(getattr(work, share["params"]["work"]))
    assert {"hc_step_ms", "hc_step_roofline"} <= set(CELL["metrics"]["per_layer"])
    # every roofline metric of the cell finds its work function in this cell's module
    for name in CELL["metrics"]["per_layer"]:
        spec = load("layer_metrics", name + ".json")
        if "work" in spec.get("params", {}):
            assert callable(getattr(work, spec["params"]["work"])), name


def test_the_mixings_bytes_are_streams_once_each_way_and_phi(tmp_path):
    need = work.hc_step(ctx(tmp_path))
    # a sub-layer: 32 rows x (4 streams read + 4 written + the output read) x 3584 x 2 B,
    # Phi [14336, 24] in float32; two sub-layers a layer
    sub_layer = 32 * 9 * 3584 * 2 + 14336 * 24 * 4
    assert need["bytes"] == 2 * LAYERS * sub_layer
    assert need["bytes"] / 819e9 < 2e-4          # a hundred-odd microseconds a step at the roofline
    whole = work.decode_step(ctx(tmp_path))
    assert whole["bytes"] > need["bytes"] + 350 * deepseek_v2.expert_params(CONFIG)


def test_compressed_queries_are_counted_in_wqs_place(tmp_path):
    lin, plain = work.linear_params(CONFIG), deepseek_v2.linear_params(CONFIG)
    q = 32 * (128 + 64)
    assert plain["attention"] - lin["attention"] == 3584 * q - 768 * (3584 + q) > 0
    attn = work.mla_decode_attn(ctx(tmp_path))
    without = deepseek_v2.mla_decode_attn(ctx(tmp_path))
    assert attn["bytes"] - without["bytes"] == LAYERS * 768 * (3584 + q)


def test_readers_give_the_mixings_time_and_share_and_nothing_for_the_parent(tmp_path):
    params = load("layer_metrics", "hc_step_roofline.json")["params"]
    assert reader.roofline(ctx(tmp_path, params)) is None           # no traced run
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path)
    traced = ctx(tmp_path, params, {"devices": 1, "file": path})
    assert reader.per_call_ms(traced) == pytest.approx(0.03)        # 20 + 10 us, not the 40 of attn.latent.q
    need = work.hc_step(traced)
    assert reader.roofline(traced) == pytest.approx(100.0 * need["bytes"] / 819e9 / 30e-6)
    # the parent's program has no op under resid.hc: nothing to read, no raise
    parent_dir = tmp_path / "parent"
    parent_dir.mkdir()
    parent_path = str(parent_dir / "t.xplane.pb")
    write_trace(parent_path, mixing=False)
    parent = ctx(parent_dir, params, {"devices": 1, "file": parent_path})
    assert reader.per_call_ms(parent) is None and reader.roofline(parent) is None


def test_the_followed_plane_judges_logits_and_choices(tmp_path):
    """planes/llm_rest_followed_reference.py: the probe's routing goes into
    the reference's ask; `correct` falls if the logits are over
    `atol_over_scale`, if a followed choice is over `choice_behind` behind the
    reference's own, or if the reply has no routing to follow; the plane's
    requests are llm_rest's own."""
    import asyncio
    import base64

    import numpy as np
    from planes import llm_rest, llm_rest_followed_reference as followed

    assert issubclass(followed.Plane, llm_rest.Plane)
    assert followed.Plane.make_request is llm_rest.Plane.make_request
    assert CONFIG["plane"] == "llm_rest_followed_reference"
    limits = CONFIG["reference_tolerance"]
    assert 0 < limits["atol_over_scale"] < 0.2 and 0 < limits["choice_behind"] < 0.2

    def pack(a, dtype):
        a = np.asarray(a, dtype)
        return {"shape": list(a.shape), "base64": base64.b64encode(a.tobytes()).decode()}

    def judge(served, ref, behind, routing=True):
        notes = []
        run = types.SimpleNamespace(
            cell=CELL, config=CONFIG, seed=1, free_port=lambda: 1, out_dir=str(tmp_path),
            note=notes.append)
        plane = followed.Plane(run)
        plane._probe_prompt, plane._probe = "ab", [5, 6]
        plane.ask_path = str(tmp_path / "ask.json")
        took = np.zeros((3, 14, 4), np.int32)
        reply = {"tokens": [5, 6], "logits": pack(served, "<f4")}
        if routing:
            reply["routing"] = {"first_token": 0, **pack(took, "<i4")}

        class Resp:
            status = 200

            async def json(self, content_type=None):
                return reply

            async def __aenter__(self):
                return self

            async def __aexit__(self, *exc):
                return False

        plane.session = types.SimpleNamespace(post=lambda url, data: Resp())
        plane._wait_for_answer = lambda: {
            "logits": np.asarray(ref, np.float32), "seconds": np.asarray([1.0, 2.0]),
            "margins": np.full((14, 3), 0.05), "behind": np.asarray(behind, np.float64)}
        asyncio.run(plane._against_reference())
        with open(plane.ask_path) as f:
            ask = json.load(f)
        assert ask["tokens"] == [97, 98, 5, 6] and ask["rows"] == [1, 3]
        assert ("follow" in ask) == routing and (not routing or np.shape(ask["follow"]) == (3, 14, 4))
        return plane.violations

    ref = np.asarray([[4.0, -2.0, 1.0], [0.5, 3.0, -4.0]])
    quiet = np.zeros((14, 3))
    assert judge(ref + 0.5 * limits["atol_over_scale"] * 4.0, ref, quiet) == []
    far = judge(ref + 1.5 * limits["atol_over_scale"] * 4.0, ref, quiet)
    assert len(far) == 1 and "from the float32 reference" in far[0]
    behind = quiet.copy()
    behind[3, 1] = 2 * limits["choice_behind"]
    other_rule = judge(ref, ref, behind)
    assert len(other_rule) == 1 and "another rule" in other_rule[0]
    assert any("no routing" in v for v in judge(ref, ref, quiet, routing=False))
