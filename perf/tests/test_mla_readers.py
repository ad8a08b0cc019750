"""The latent-attention cell's reader (ops under a named scope, found through
the HLO the trace carries) and work functions, on values small enough to check
by hand."""

import json
import os
import sys
import types

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERF)

import hlo_scopes  # noqa: E402
from readers import hlo_scopes as reader  # noqa: E402
from work import deepseek_v2 as work  # noqa: E402

with open(os.path.join(PERF, "configs", "deepseek-v2-lite-int8.json")) as f:
    CONFIG = json.load(f)
LAYERS = CONFIG["num_hidden_layers"]
US = 1_000_000  # picoseconds


def write_trace(path: str, with_hlo: bool = True) -> None:
    """One device plane: jit_decode_step [0, 100) us twice and jit_other once.
    Ops of a step: while.1 [0, 100) > fusion.7 [10, 40) (under
    attn.latent.read), fusion.8 [50, 90) (no op_name of its own; its fused
    root is under attn.latent.write), copy.3 [92, 98) (under attn alone).
    jit_other runs a fusion.7 of its own, under no scope."""
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

    def module(name: str, scoped: bool) -> bytes:
        proto = hlo_pb2.HloProto()
        proto.hlo_module.name = name
        fused = proto.hlo_module.computations.add(name="fused_computation.8", id=2, root_id=21)
        fused.instructions.add(name="scatter.2", id=21, opcode="scatter").metadata.op_name = (
            f"jit({name})/Transformer/layer_0/attn/attn.latent.write/scatter" if scoped else "")
        entry = proto.hlo_module.computations.add(name="main", id=1, root_id=10)
        entry.instructions.add(name="while.1", id=10, opcode="while").metadata.op_name = f"jit({name})/while"
        entry.instructions.add(name="fusion.7", id=11, opcode="fusion").metadata.op_name = (
            f"jit({name})/Transformer/layer_0/attn/attn.latent.read/dot_general" if scoped
            else f"jit({name})/Transformer/layer_0/moe.experts/dot")
        entry.instructions.add(name="fusion.8", id=12, opcode="fusion",
                               called_computation_ids=[2])
        entry.instructions.add(name="copy.3", id=13, opcode="copy").metadata.op_name = (
            f"jit({name})/Transformer/layer_0/attn/reshape")
        return proto.SerializeToString()

    space = xplane_pb2.XSpace()
    if with_hlo:
        meta = space.planes.add(name="/host:metadata")
        meta.stat_metadata[1].name = "Hlo Proto"
        for i, (name, scoped) in enumerate((("decode_step", True), ("other", False)), 1):
            em = meta.event_metadata[i]
            em.name = f"jit_{name}({i})"
            em.stats.add(metadata_id=1).bytes_value = module(name, scoped)
    device = space.planes.add(name="/device:TPU:0")
    names = ["jit_decode_step(1)", "jit_other(2)", "%while.1 = (s32[]) while(%t)",
             "%fusion.7 = bf16[8,16384,16]{1,2,0} fusion(%a, %b)", "%fusion.8 = bf16[2050,64,576] fusion(%c)",
             "%copy.3 = bf16[8,16,576] copy(%d)"]
    for i, name in enumerate(names, 1):
        device.event_metadata[i].name = name

    def line(name: str, events: list) -> None:
        ln = device.lines.add(name=name, timestamp_ns=0)
        for meta_id, start, end in events:
            ln.events.add(metadata_id=meta_id, offset_ps=start * US, duration_ps=(end - start) * US)

    line("XLA Modules", [(1, 0, 100), (1, 200, 300), (2, 400, 450)])
    step = [(3, 0, 100), (4, 10, 40), (5, 50, 90), (6, 92, 98)]
    line("XLA Ops", step + [(m, s + 200, e + 200) for m, s, e in step] + [(4, 400, 450)])
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_ops_under_a_scope_are_found_through_the_traces_own_hlo(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path)
    out = hlo_scopes.reduce(path, ["attn.latent"])
    assert out["modules_with_hlo"] == 2
    step = out["programs"]["jit_decode_step"]
    assert step["calls"] == 2 and abs(step["seconds"] - 200e-6) < 1e-12
    # fusion.7 (30 us) + fusion.8 through its fused root (40 us), twice; not copy.3, not the loop
    assert abs(step["scoped_s"] - 140e-6) < 1e-12
    assert [label.split(" ")[0] for label, _s in step["ops"]] == ["%fusion.8", "%fusion.7"]
    # the same instruction name in another module is that module's: under no scope there
    assert out["programs"]["jit_other"]["scoped_s"] == 0.0
    assert hlo_scopes.reduce(path, ["attn"])["programs"]["jit_decode_step"]["scoped_s"] \
        == pytest.approx(152e-6)


def test_a_trace_without_hlo_gives_nothing_and_does_not_raise(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, with_hlo=False)
    out = hlo_scopes.reduce(path, ["attn.latent"])
    assert out["modules_with_hlo"] == 0 and out["programs"] == {}


def scrape(calls, rows, pairs, touched, context):
    lines = [f'seldon_llm_moe_{k}_total{{model="m",program="decode"}} {v}' for k, v in (
        ("calls", calls), ("live_rows", rows), ("routed_pairs", pairs), ("experts_touched", touched))]
    lines += [f'seldon_llm_attn_calls_total{{model="m",program="decode"}} {calls}',
              f'seldon_llm_attn_context_tokens_total{{model="m",program="decode"}} {context}']
    return {"metrics": "\n".join(lines) + "\n"}


def ctx(tmp_path=None, params=None, trace=None):
    # 100 decode steps of 2 live rows at 10,000 tokens of context each
    scrapes = [(0.0, scrape(10, 20, 2160, 2000, 200_000)),
               (1.0, scrape(110, 220, 23760, 22000, 2_200_000))]
    run = types.SimpleNamespace(out_dir=str(tmp_path), perf_dir=PERF, repo=os.path.dirname(PERF),
                                note=lambda _t: None)
    return types.SimpleNamespace(
        scrapes=scrapes, params=params or {}, config=CONFIG, run=run, trace=trace, work=work,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_attention_is_credited_the_cheaper_formulation():
    """A chunk of 256 rows at offset 12,032 needs the EXPANDED count (K/V of
    the 12,288 context rows made once); a decode step needs the ABSORBED one."""
    pairs = 256 * 12032 + 256 * 257 / 2
    move = 2.0 * 16 * 512 * (128 + 128)
    expanded = pairs * 2 * 16 * (128 + 64 + 128) + 12288 * move
    absorbed = pairs * 2 * 16 * (2 * 512 + 64) + 256 * move
    assert work.latent_attention_flops(CONFIG, pairs, 256, 12288) == expanded < absorbed
    step = work.latent_attention_flops(CONFIG, 20000.0, 2, 20000.0)
    assert step == 20000.0 * 2 * 16 * (2 * 512 + 64) + 2 * move
    assert work.row_bytes(CONFIG) == 1152


def test_decode_bytes_are_the_live_rows_not_the_view(tmp_path):
    need = work.mla_decode_attn(ctx(tmp_path))
    # per call: 20,000 live rows and the 2 written, a layer; wkv_a, W_UK and W_UV once
    weights = 2048 * 576 + 16 * 512 * 256
    assert need["bytes"] == LAYERS * (20002 * 1152 + weights)
    assert need["bytes"] < LAYERS * 8 * 16384 * 1152 / 5      # a fifth of the 8 x 16,384-row view
    whole = work.decode_step(ctx(tmp_path))
    assert whole["bytes"] > need["bytes"] - LAYERS * 2048 * 576 + 200 * work.expert_params(CONFIG)


def test_reader_gives_none_without_a_trace_and_a_share_with_one(tmp_path):
    params = {"program": "decode_step", "scopes": ["attn.latent"], "work": "mla_decode_attn",
              "bound": "hbm"}
    assert reader.roofline(ctx(tmp_path, params)) is None           # no traced run
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path)
    traced = ctx(tmp_path, params, {"devices": 1, "file": path})
    assert reader.per_call_ms(traced) == pytest.approx(0.07)
    need = work.mla_decode_attn(traced)
    assert reader.roofline(traced) == pytest.approx(100.0 * need["bytes"] / 819e9 / 70e-6)
    # a program without the scope (the parent's): nothing to read, no raise
    other = ctx(tmp_path, {**params, "program": "other"}, {"devices": 1, "file": path})
    assert reader.per_call_ms(other) is None
