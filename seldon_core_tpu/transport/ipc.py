"""IPC serving: multi-process frontends over the native staging ring.

The reference scales its Python wrapper with gunicorn workers, each paying
full JSON->proto->ndarray codec plus a socket hop to the engine pod
(SURVEY.md §3.1). Here transport workers (REST/gRPC frontends, or any client
process) stage requests into the shared-memory ring (native/ring.cc) and the
single device-owning engine process drains them in batches — the TPU-native
layout, since exactly one process should own the TPU chip while N CPU-bound
frontends decode payloads.

Frame format (bytes, little-endian):
    u16 worker_id | u32 request_id | u8 kind | payload
kind: 0 = predict(SeldonMessage JSON), 1 = feedback(Feedback JSON),
      2 = device-model call (binary tensor, no JSON):
          u16 model_id | u8 method (0=predict, 1=transform_input)
          | u8 n_chain_extra | n_chain_extra x (u16 model, u8 method)
          | u8 ndim | u32 dims[ndim] | f64 data
          (chained stages run sequentially in one round-trip; the response
          fragment is then a JSON array, one fragment per stage).
Responses travel back on a per-worker ring as
    u32 request_id | u8 status | body
status 0 JSON kinds: JSON payload. status 0 model kind:
    u8 dtype (0=f32,1=f64 — the model's output dtype, data itself is f64)
    | u8 ndim | u32 dims[ndim] | u32 json_len
    | json ({"names": [...], "tags": {...}, "metrics": [...]}) | f64 data.
status 1 (any kind): JSON Status body.

The kind-2 path is how the native edge serves graphs with real models at
native speed (runtime/edgeprogram.py DEVICE_MODEL): the edge executes the
graph — routing, combining, meta — in C++ and ships ONLY the tensor here;
this process owns the device and micro-batches concurrent requests into one
jitted call (requests for the same model with the same feature shape are
stacked along axis 0 — the serving-side continuous batching the reference's
replica fan-out can't do).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import struct
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from seldon_core_tpu.contracts.payload import Feedback, SeldonError, SeldonMessage
from seldon_core_tpu.native import PayloadTooLarge, RingFull, SharedRing

logger = logging.getLogger(__name__)

_REQ_HEADER = struct.Struct("<HIB")
_RESP_HEADER = struct.Struct("<IB")
# model_id, method, n_chain_extra, then n_chain_extra x (u16 model, u8
# method) chained stages, then u8 ndim + u32 dims. A chained frame runs its
# stages sequentially (stage i+1 consumes stage i's output) in ONE ring
# round-trip — the transform->model hot path costs one RTT, not one per hop.
_MODEL_REQ = struct.Struct("<HBB")
_CHAIN_STAGE = struct.Struct("<HB")

METHOD_PREDICT = 0
METHOD_TRANSFORM_INPUT = 1

KIND_PREDICT = 0
KIND_FEEDBACK = 1
KIND_MODEL = 2
# Full-proto frames from the edge's gRPC listener: payload is the raw
# SeldonMessage/Feedback proto; ok responses carry proto bytes back, error
# responses carry u8 grpc-status-code + utf8 message.
KIND_PROTO_PREDICT = 3
KIND_PROTO_FEEDBACK = 4


# what one ring's mapped file may span once its slots outgrow the default
# (the file is sparse, but RLIMIT_FSIZE and quotas count its length: a 1 GiB
# ring was refused with EFBIG on a machine whose limit was not ours to set)
RING_FILE_BYTES = 1 << 28


def ring_geometry(models) -> tuple:
    """``(capacity, slot_size)`` for rings that can carry every frame these
    device models can be sent: one request of a model's largest batch
    bucket, packed as the f64 the edge ships. The 1 MiB default slot cannot
    hold ONE 224x224x3 image (1.2 MB) — the edge answers "tensor larger
    than ring slot" — so the slot grows to that frame (64 B aligned, no
    more) and the capacity shrinks to the power of two that keeps the ring's
    file within ``RING_FILE_BYTES``, never below 2. Small-tensor models keep
    the default (1024 x 1 MiB)."""
    need = 0
    for m in models:
        shape = (getattr(m, "_config", None) or {}).get("input_shape")
        if shape:
            rows = max(getattr(m, "batch_buckets", None) or (1,))
            need = max(need, 8 * rows * int(np.prod(shape)) + 4096)
    if need <= 1 << 20:
        return 1024, 1 << 20
    slot = (need + 63) & ~63
    fit = RING_FILE_BYTES // slot
    return (1 << (fit.bit_length() - 1) if fit >= 2 else 2), slot


class ModelExecutor:
    """Executes kind-2 device-model frames for the native edge.

    Holds the graph's resolvable model components (modelId order from
    compile_edge_program). Frames arriving in one drain batch for the same
    model with the same feature shape are stacked into ONE predict call —
    the device sees large batches even when every client sends batch-1."""

    def __init__(self, models):
        self.models = list(models)
        self.batched_calls = 0
        self.batched_rows = 0
        # cap stacking at the largest compiled bucket so a burst can never
        # trigger an unseen-batch-shape XLA compile mid-traffic
        self.max_rows = [
            int(max(getattr(m, "batch_buckets", ()) or (256,))) for m in self.models
        ]
        # Response meta fragments (names/tags/metrics JSON) depend only on
        # the output shape for components that don't override tags()/
        # metrics() — cache the encoded bytes per (model, ndim, cols)
        # instead of re-deriving + json.dumps-ing on every request.
        from seldon_core_tpu.components.component import _has_impl

        self._frag_static = [
            not (_has_impl(m, "tags") or _has_impl(m, "metrics"))
            for m in self.models
        ]
        # Dynamic-fragment components that can attribute tags/metrics to a
        # row range (row_slice protocol, e.g. outlier detectors): stacked
        # into one scoring call with per-frame row attribution instead of
        # running solo per request.
        self._row_sliceable = [
            callable(getattr(m, "row_slice", None)) for m in self.models
        ]
        self._frag_cache: Dict[tuple, bytes] = {}

    def warm(self) -> None:
        """Compile every (bucket, feature-shape) pair up front. Without this
        a load burst walks the bucket ladder one compile at a time while
        requests queue behind each compile (measured: a 10s load window
        collapsed to ~94 rps from compile storms). A bucket that cannot
        compile or run (e.g. one too large for the device's memory) raises
        here, at start-up: a server that came up without it would compile
        — and fail — under traffic. Size ``batch_buckets`` to the device."""
        for i, component in enumerate(self.models):
            shape = None
            cfg = getattr(component, "_config", None)
            if isinstance(cfg, dict):
                shape = cfg.get("input_shape")
            if shape is None:
                continue
            dtype = np.dtype(getattr(component, "input_dtype", "float32"))
            for b in sorted(set(getattr(component, "batch_buckets", ()) or (1,))):
                if b > self.max_rows[i]:
                    continue
                logger.info("warming model %d bucket %d", i, b)
                component.predict(np.zeros((b, *shape), dtype), [], meta={})

    # ---- frame codecs -------------------------------------------------
    @staticmethod
    def parse_frame(payload: bytes):
        """Returns (stages, arr): stages = ((model_id, method), ...) — one
        entry for plain frames, several for fused chains."""
        model_id, method, n_extra = _MODEL_REQ.unpack_from(payload)
        off = _MODEL_REQ.size
        stages = [(model_id, method)]
        for _ in range(n_extra):
            m, meth = _CHAIN_STAGE.unpack_from(payload, off)
            stages.append((m, meth))
            off += _CHAIN_STAGE.size
        ndim = payload[off]
        off += 1
        dims = struct.unpack_from(f"<{ndim}I", payload, off)
        off += 4 * ndim
        n = 1
        for d in dims:
            n *= d
        arr = np.frombuffer(payload, dtype="<f8", count=n, offset=off).reshape(dims)
        return tuple(stages), arr

    @staticmethod
    def _ok_response(req_id: int, arr: np.ndarray, frag: bytes) -> bytes:
        dtype_code = 1 if arr.dtype == np.float64 else 0
        out = arr.astype("<f8", copy=False)
        head = _RESP_HEADER.pack(req_id, 0) + bytes([dtype_code, out.ndim])
        head += struct.pack(f"<{out.ndim}I", *out.shape)
        head += struct.pack("<I", len(frag)) + frag
        return head + out.tobytes()

    def _fragment_for(self, model_id: int, method: int, component,
                      result: np.ndarray) -> bytes:
        key = (model_id, method, result.ndim,
               int(result.shape[1]) if result.ndim > 1 else -1)
        if self._frag_static[model_id]:
            cached = self._frag_cache.get(key)
            if cached is not None:
                return cached
        from seldon_core_tpu.components.component import (
            client_class_names,
            client_custom_metrics,
            client_custom_tags,
            client_feature_names,
        )

        fragment: Dict[str, Any] = {}
        if method == METHOD_TRANSFORM_INPUT:
            # request-flow response: engine construct_response(is_request=True)
            names = client_feature_names(component, [])
        else:
            names = client_class_names(component, result)
        if names:
            fragment["names"] = list(names)
        tags = client_custom_tags(component)
        if tags:
            fragment["tags"] = tags
        metrics = client_custom_metrics(component)
        if metrics:
            fragment["metrics"] = metrics
        frag = json.dumps(fragment).encode() if fragment else b""
        if self._frag_static[model_id]:
            self._frag_cache[key] = frag
        return frag

    def _row_fragment(self, method: int, component, result: np.ndarray,
                      lo: int, hi: int) -> bytes:
        """Fragment for rows [lo, hi) of a stacked call on a row-sliceable
        dynamic component — same encoded shape as _fragment_for, but tags/
        metrics come from the component's per-row attribution."""
        from seldon_core_tpu.components.component import (
            client_class_names,
            client_feature_names,
        )

        fragment: Dict[str, Any] = {}
        if method == METHOD_TRANSFORM_INPUT:
            names = client_feature_names(component, [])
        else:
            names = client_class_names(component, result)
        if names:
            fragment["names"] = list(names)
        tags, mets = component.row_slice(lo, hi)
        if tags:
            fragment["tags"] = tags
        if mets:
            fragment["metrics"] = mets
        return json.dumps(fragment).encode() if fragment else b""

    @staticmethod
    def _err_response(req_id: int, info: str, reason: str, code: int = 500) -> bytes:
        return _RESP_HEADER.pack(req_id, 1) + _error_body(info, reason, code)

    # ---- execution ----------------------------------------------------
    def _call_stacked(self, call, items, max_rows, finish, fail, finish_chunk=None,
                      set_segments=None):
        """Shared micro-batch machinery: ``items`` = [(key, arr)] with equal
        trailing shapes; concatenates into chunks of <= max_rows rows, one
        call per chunk, splits results back per key. Both the plain frame
        path and the fused-chain path use THIS loop so stacking policy,
        the row-split guard, and accounting can never diverge.

        ``finish_chunk(chunk, result)``, when given, may consume a whole
        stacked chunk at once (the C bulk-response path); returning False
        falls back to per-frame ``finish``, and returning a set of keys
        marks those frames as already answered (partial bulk push) so only
        the REMAINING frames take the per-frame path.

        ``set_segments(counts)``, when given, is told each chunk's
        per-frame row counts right before the stacked call — the windowed
        components' stack_segments protocol (window framing must not
        straddle request boundaries; analytics/outliers.py Seq2Seq)."""
        idx = 0
        while idx < len(items):
            chunk = []
            rows = 0
            while idx < len(items):
                _, a = items[idx]
                if chunk and rows + a.shape[0] > max_rows:
                    break
                chunk.append(items[idx])
                rows += a.shape[0]
                idx += 1
            answered: set = set()  # keys already responded to — a late
            # exception must not fail() these (duplicate responses)
            try:
                if len(chunk) == 1:
                    key, arr = chunk[0]
                    finish(key, np.asarray(call(arr)))
                    continue
                stacked = np.concatenate([a for _, a in chunk], axis=0)
                if set_segments is not None:
                    set_segments([a.shape[0] for _, a in chunk])
                result = np.asarray(call(stacked))
                if result.shape[:1] != stacked.shape[:1]:
                    raise SeldonError(
                        "device model output rows do not match stacked "
                        "input rows; cannot split a micro-batch")
                self.batched_calls += 1
                self.batched_rows += stacked.shape[0]
                handled = finish_chunk(chunk, result) if finish_chunk else False
                if handled is True:
                    continue
                if isinstance(handled, set):
                    answered |= handled
                offset = 0
                for key, a in chunk:
                    if key not in answered:
                        finish(key, result[offset:offset + a.shape[0]])
                        answered.add(key)
                    offset += a.shape[0]
            except Exception as e:
                for key, _ in chunk:
                    if key not in answered:
                        fail(key, e)

    def _chunk_pusher(self, model_id: int, method: int, component, rings):
        """finish_chunk callback for _call_stacked: pushes a whole stacked
        chunk's responses through scr_push_model_resps — the C side frames
        each response directly into its ring slot, replacing per-frame
        struct packs + bytes concats + one FFI push per frame. Returns None
        when the bulk path doesn't apply (no rings / dynamic fragment)."""
        if not rings or not self._frag_static[model_id]:
            return None

        def finish_chunk(chunk, result) -> bool:
            if result.ndim < 2 or not (
                np.issubdtype(result.dtype, np.number) or result.dtype == np.bool_
            ):
                return False  # per-frame path handles odd shapes/dtypes
            workers = {key[0] for key, _ in chunk}
            if any(w not in rings for w in workers):
                return False
            frag = self._fragment_for(model_id, method, component, result)
            dtype_code = 1 if result.dtype == np.float64 else 0
            data = np.ascontiguousarray(result, dtype="<f8")
            row_nvals = int(np.prod(result.shape[1:], dtype=np.int64))
            tail = result.shape[1:]
            by_worker: Dict[int, list] = {}
            off = 0
            for (worker_id, req_id), a in chunk:
                by_worker.setdefault(worker_id, []).append(
                    (req_id, off, a.shape[0]))
                off += a.shape[0]
            pushed: set = set()  # worker_ids whose batch fully pushed
            for worker_id, entries in by_worker.items():
                try:
                    rings[worker_id].push_model_resps(
                        [e[0] for e in entries], [e[1] for e in entries],
                        [e[2] for e in entries], data, row_nvals, tail, frag,
                        dtype_code)
                    pushed.add(worker_id)
                except PayloadTooLarge:
                    # Rings can have differing slot sizes, so one worker of
                    # a multi-worker chunk can overflow while the rest fit.
                    # push_model_resps pre-checks sizes per call, so the
                    # failing worker pushed NOTHING — its frames are safe to
                    # re-answer via the per-frame fallback, as are those of
                    # workers not yet attempted. Report only the
                    # already-pushed workers' frames as handled.
                    if not pushed:
                        return False  # nothing pushed: plain per-frame path
                    logger.warning(
                        "bulk response overflow on worker %d after partial "
                        "multi-worker push; remaining frames take the "
                        "per-frame fallback", worker_id)
                    return {key for key, _ in chunk if key[0] in pushed}
                except RingFull:
                    # Worker %d's ring jammed for the full timeout — a
                    # partial per-WORKER push is possible here, so answering
                    # its frames again would enqueue duplicates into the
                    # same jammed ring; its frames 504 at the edge. Other
                    # workers' rings are healthy: pushed ones are done,
                    # unattempted ones take the per-frame fallback.
                    logger.error(
                        "response ring full during bulk push to worker %d; "
                        "its frames will time out at the edge", worker_id)
                    return {key for key, _ in chunk
                            if key[0] in pushed or key[0] == worker_id}
            return True

        return finish_chunk

    def _predict_frames(self, model_id: int, method: int, frames,
                        rings=None) -> Dict[tuple, bytes]:
        """frames: [((worker_id, req_id), arr)]; one stacked call when shapes
        allow. Keys are (worker, req) pairs throughout: req_ids are
        per-edge-worker counters, so with multiple edge workers the bare
        req_id collides across workers."""
        out: Dict[tuple, bytes] = {}
        if model_id >= len(self.models):
            for key, _ in frames:
                out[key] = self._err_response(
                    key[1], f"unknown device model {model_id}", "BAD_GRAPH")
            return out
        component = self.models[model_id]
        if method == METHOD_TRANSFORM_INPUT:
            def call(arr):
                return component.transform_input(arr, [], meta={})
        elif method == METHOD_PREDICT:
            def call(arr):
                return component.predict(arr, [], meta={})
        else:
            for key, _ in frames:
                out[key] = self._err_response(
                    key[1], f"unknown device method {method}", "BAD_GRAPH")
            return out

        def finish(key: tuple, result: np.ndarray) -> None:
            if not (isinstance(result, np.ndarray)
                    and (np.issubdtype(result.dtype, np.number)
                         or result.dtype == np.bool_)):
                out[key] = self._err_response(
                    key[1],
                    "device model returned a non-numeric payload",
                    "ENGINE_ERROR")
                return
            out[key] = self._ok_response(
                key[1], result,
                self._fragment_for(model_id, method, component, result))

        # stack 2-D frames with equal feature shape into one call, chunked at
        # the largest compiled bucket (stacking must never out-shape the
        # warmed compile cache). Components with DYNAMIC tags/metrics (e.g.
        # outlier detectors scoring each request) must run solo: a stacked
        # call would compute one tags() for the whole batch and misattribute
        # per-request scores — UNLESS the component implements the row_slice
        # protocol, in which case the stacked call's tags/metrics are sliced
        # per frame from its own rows.
        max_rows = self.max_rows[model_id]
        row_sliced = self._row_sliceable[model_id] and not self._frag_static[model_id]
        if self._frag_static[model_id] or row_sliced:
            stackable = [(r, a) for r, a in frames if a.ndim >= 2]
            solo = [(r, a) for r, a in frames if a.ndim < 2]
        else:
            stackable = []
            solo = list(frames)
        by_shape: Dict[tuple, list] = {}
        for r, a in stackable:
            by_shape.setdefault(a.shape[1:], []).append((r, a))
        def fail(key, e):
            out[key] = self._err_response(
                key[1], str(e),
                getattr(e, "reason", "ENGINE_ERROR"),
                int(getattr(e, "status_code", 500)))

        if row_sliced:
            def finish_chunk(chunk, result):
                if not (np.issubdtype(result.dtype, np.number)
                        or result.dtype == np.bool_):
                    return False  # finish() errors each frame (non-numeric)
                if result.ndim < 2:
                    # falling to finish() would attach whole-batch tags to
                    # every frame — misattribution; fail the chunk instead
                    raise SeldonError(
                        "row-sliceable component returned <2-D output "
                        "from a stacked call")
                off = 0
                for key, a in chunk:
                    rows = a.shape[0]
                    out[key] = self._ok_response(
                        key[1], result[off:off + rows],
                        self._row_fragment(method, component,
                                           result[off:off + rows],
                                           off, off + rows))
                    off += rows
                return True
        else:
            finish_chunk = self._chunk_pusher(model_id, method, component, rings)
        seg_hook = (getattr(component, "stack_segments", None)
                    if row_sliced else None)
        for shape, group in by_shape.items():
            self._call_stacked(call, group, max_rows, finish, fail, finish_chunk,
                               set_segments=seg_hook)
        for key, arr in solo:
            try:
                # graftlint: allow-host-sync-in-hot-path(IPC worker must materialize the result to copy it into the shared-memory ring — the sync IS the response write)
                finish(key, np.asarray(call(arr)))
            except Exception as e:
                fail(key, e)
        return out

    def execute(self, frames, rings=None) -> Dict[int, Dict[int, bytes]]:
        """frames: [(worker_id, req_id, payload_bytes)] →
        {worker_id: {req_id: response_bytes}}.

        With ``rings`` ({worker_id: SharedRing}), stacked chunks with static
        fragments push their responses directly through the C bulk path and
        do NOT appear in the returned dict — only solo frames, errors, and
        fallback cases come back as bytes for the caller to push."""
        parsed: Dict[tuple, list] = {}
        responses: Dict[int, Dict[int, bytes]] = {}
        for worker_id, req_id, payload in frames:
            try:
                stages, arr = self.parse_frame(payload)
            except Exception:
                responses.setdefault(worker_id, {})[req_id] = self._err_response(
                    req_id, "malformed device-model frame", "MICROSERVICE_BAD_DATA", 400)
                continue
            if len(stages) > 1:
                parsed.setdefault(stages, []).append(((worker_id, req_id), arr))
                continue
            model_id, method = stages[0]
            parsed.setdefault((model_id, method), []).append(((worker_id, req_id), arr))
        for gkey, group in parsed.items():
            if isinstance(gkey[0], tuple):  # fused chain group
                results = self._run_chains(gkey, group)
            else:
                model_id, method = gkey
                results = self._predict_frames(model_id, method, group, rings)
            for (worker_id, req_id), resp in results.items():
                responses.setdefault(worker_id, {})[req_id] = resp
        return responses

    def _run_chains(self, stages, group) -> Dict[tuple, bytes]:
        """Fused chains, executed STAGE-WISE across all frames sharing the
        stage tuple: a dynamic-tags stage (outlier detector) runs solo per
        frame (per-request score attribution), while a static stage (the
        model) stacks every frame's rows into one jitted call — the chain
        costs one ring RTT and the model stage still micro-batches. The
        response fragment is a JSON array, one fragment per stage."""
        current: Dict[tuple, np.ndarray] = {key: arr for key, arr in group}
        frags: Dict[tuple, list] = {key: [] for key, _ in group}
        out: Dict[tuple, bytes] = {}

        def fail(key, e):
            out[key] = self._err_response(
                key[1], str(e), getattr(e, "reason", "ENGINE_ERROR"),
                int(getattr(e, "status_code", 500)))
            current.pop(key, None)

        for model_id, method in stages:
            if not current:
                break
            if model_id >= len(self.models):
                for key in list(current):
                    fail(key, SeldonError(f"unknown device model {model_id}",
                                          reason="BAD_GRAPH"))
                break
            component = self.models[model_id]
            if method == METHOD_TRANSFORM_INPUT:
                def call(a, _c=component):
                    return _c.transform_input(a, [], meta={})
            elif method == METHOD_PREDICT:
                def call(a, _c=component):
                    return _c.predict(a, [], meta={})
            else:
                for key in list(current):
                    fail(key, SeldonError(f"unknown device method {method}",
                                          reason="BAD_GRAPH"))
                break

            def finish_stage(key, result):
                result = np.asarray(result)
                if not (np.issubdtype(result.dtype, np.number)
                        or result.dtype == np.bool_):
                    fail(key, SeldonError(
                        "device model returned a non-numeric payload"))
                    return
                frags[key].append(self._fragment_for(
                    model_id, method, component, result).decode() or "{}")
                current[key] = result

            keys = list(current)
            row_sliced = (self._row_sliceable[model_id]
                          and not self._frag_static[model_id])
            if self._frag_static[model_id] or row_sliced:
                by_shape: Dict[tuple, list] = {}
                solo = []
                for k in keys:
                    a = current[k]
                    if a.ndim >= 2:
                        by_shape.setdefault(a.shape[1:], []).append((k, a))
                    else:
                        solo.append(k)
                finish_chunk = None
                if row_sliced:
                    # one scoring call for the whole chunk; each frame's
                    # stage fragment is sliced from its own rows
                    def finish_chunk(chunk, result,
                                     _m=model_id, _meth=method, _c=component):
                        if not (np.issubdtype(result.dtype, np.number)
                                or result.dtype == np.bool_):
                            return False  # finish_stage errors per frame
                        if result.ndim < 2:
                            raise SeldonError(
                                "row-sliceable component returned <2-D "
                                "output from a stacked call")
                        off = 0
                        for k, a in chunk:
                            rows = a.shape[0]
                            frag = self._row_fragment(
                                _meth, _c, result[off:off + rows],
                                off, off + rows)
                            frags[k].append(frag.decode() or "{}")
                            current[k] = result[off:off + rows]
                            off += rows
                        return True
                seg_hook = (getattr(component, "stack_segments", None)
                            if row_sliced else None)
                for shape, items in by_shape.items():
                    self._call_stacked(call, items, self.max_rows[model_id],
                                       finish_stage, fail, finish_chunk,
                                       set_segments=seg_hook)
                for k in solo:
                    try:
                        finish_stage(k, np.asarray(call(current[k])))
                    except Exception as e:
                        fail(k, e)
            else:
                # dynamic tags/metrics without row attribution: solo per
                # frame (per-request scores)
                for k in keys:
                    try:
                        finish_stage(k, call(current[k]))
                    except Exception as e:
                        fail(k, e)

        for key, arr in current.items():
            frag = ("[" + ",".join(frags[key]) + "]").encode()
            out[key] = self._ok_response(key[1], arr, frag)
        return out


def _error_body(info: str, reason: str, code: int = 500) -> bytes:
    """Error frame body (Status contract shape, contracts/payload.py Status):
    clients parse status.info/status.reason; HTTP frontends use status.code."""
    return json.dumps(
        {"status": {"code": code, "info": info, "reason": reason, "status": "FAILURE"}}
    ).encode()


def default_ring_dir(prefix: str = "seldon-ring-") -> str:
    """Ring files MUST live on tmpfs: a MAP_SHARED mapping over a disk-backed
    file re-faults through the filesystem (journal block allocation) every
    time writeback cleans a dirtied page — measured 8.8ms ping-pong RTT on
    /tmp (ext4) vs 0.45ms on /dev/shm for the identical ring."""
    import tempfile

    shm = "/dev/shm"
    if os.path.isdir(shm) and os.access(shm, os.W_OK):
        return tempfile.mkdtemp(prefix=prefix, dir=shm)
    return tempfile.mkdtemp(prefix=prefix)


def request_ring_path(base: str) -> str:
    return base + ".req"


def response_ring_path(base: str, worker_id: int) -> str:
    return f"{base}.resp.{worker_id}"


class IPCEngineServer:
    """Drains the request ring into the in-process GraphEngine."""

    def __init__(
        self,
        engine: Any,
        base_path: str,
        n_workers: int,
        capacity: int = 1024,
        slot_size: int = 1 << 20,
        batch: int = 64,
        model_executor: Optional[ModelExecutor] = None,
    ):
        self.engine = engine
        self.base_path = base_path
        self.batch = batch
        self.model_executor = model_executor
        # sweep temp files orphaned by a previous creator killed mid-create;
        # glob per exact ring path so a sibling base sharing this prefix
        # (e.g. "<base>2") is never touched mid-create
        import glob

        ring_paths = [request_ring_path(base_path)] + [
            response_ring_path(base_path, w) for w in range(n_workers)
        ]
        for stale in (t for p in ring_paths for t in glob.glob(p + ".tmp.*")):
            try:
                os.unlink(stale)
            except OSError:
                pass
        self.req_ring = SharedRing(
            request_ring_path(base_path), capacity=capacity, slot_size=slot_size, create=True
        )
        self.resp_rings = {
            w: SharedRing(
                response_ring_path(base_path, w), capacity=capacity, slot_size=slot_size,
                create=True,
            )
            for w in range(n_workers)
        }
        self._stop = False

    async def serve_forever(self, poll_wait_s: float = 0.05) -> None:
        """Drain loop. The hot path (kind-2 model frames) runs entirely on a
        dedicated thread — pop, stacked predict, response push — with zero
        event-loop hops; only JSON graph frames (kind 0/1) cross into the
        asyncio engine. (asyncio.to_thread cost ~1ms of scheduling per hop at
        exactly the moment throughput mattered.)"""
        loop = asyncio.get_running_loop()
        done = asyncio.Event()
        trace = bool(os.environ.get("SELDON_IPC_TRACE"))

        from collections import deque

        # Backpressure for JSON graph frames: cap in-flight engine coroutines
        # so a burst fills the ring (edge answers 503 ENGINE_BUSY) instead of
        # growing the event-loop queue without bound.
        inflight: Any = deque()
        max_inflight = max(4 * self.batch, 64)

        # Fully-local graph: plane-3 frames execute inline on the drain
        # thread (engine coroutines never suspend), skipping the
        # run_coroutine_threadsafe hop + to_thread response push that
        # dominated the old per-request cost. Async graphs (remote nodes,
        # async user components) keep the event-loop path.
        inline_plane3 = not getattr(self.engine, "has_async_nodes", True)

        def drain() -> None:
            try:
                while not self._stop:
                    t0 = time.perf_counter()
                    # one FFI call per drain; frames are zero-copy views into
                    # the ring's pop buffer, consumed before the next drain
                    # (model frames synchronously below; JSON frames copied
                    # into bytes before crossing to the event loop)
                    frames = self.req_ring.pop_many(self.batch, poll_wait_s)
                    if not frames:
                        continue
                    t1 = time.perf_counter()
                    model_frames = []
                    for f in frames:
                        try:
                            worker_id, req_id, kind = _REQ_HEADER.unpack_from(f)
                        except struct.error:
                            logger.error(
                                "dropping malformed IPC frame (%d bytes)", len(f))
                            continue
                        if kind == KIND_MODEL and self.model_executor is not None:
                            model_frames.append(
                                (worker_id, req_id, f[_REQ_HEADER.size:]))
                        elif inline_plane3:
                            self._handle_sync(f)
                        else:
                            f = bytes(f)
                            while inflight and inflight[0].done():
                                inflight.popleft()
                            if len(inflight) >= max_inflight:
                                inflight.popleft().result()  # block: backpressure
                            inflight.append(
                                asyncio.run_coroutine_threadsafe(self._handle(f), loop))
                    if model_frames:
                        self._handle_models_sync(model_frames)
                    if trace:
                        print(
                            f"ipc cycle: pop={1e3*(t1-t0):.2f}ms "
                            f"n={len(frames)} "
                            f"handle={1e3*(time.perf_counter()-t1):.2f}ms",
                            file=__import__('sys').stderr, flush=True)
            finally:
                loop.call_soon_threadsafe(done.set)

        threading.Thread(target=drain, name="ipc-drain", daemon=True).start()
        await done.wait()

    def _handle_models_sync(self, model_frames) -> None:
        try:
            responses = self.model_executor.execute(model_frames, rings=self.resp_rings)
        except Exception:
            logger.exception("model executor batch failed")
            responses = {}
            for w, r, _ in model_frames:
                responses.setdefault(w, {})[r] = ModelExecutor._err_response(
                    r, "model executor crashed", "ENGINE_ERROR")
        for worker_id, by_req in responses.items():
            ring = self.resp_rings.get(worker_id)
            if ring is None:
                logger.error("device responses for unknown worker %d dropped",
                             worker_id)
                continue
            for resp in by_req.values():
                try:
                    ring.push_wait(resp, 5.0)
                except PayloadTooLarge:
                    req_id = _RESP_HEADER.unpack_from(resp)[0]
                    err = ModelExecutor._err_response(
                        req_id,
                        f"device response too large for IPC slot "
                        f"({len(resp)} bytes)",
                        "RESPONSE_TOO_LARGE")
                    try:
                        ring.push_wait(err, 5.0)
                    except Exception:
                        logger.exception("dropping oversized device response")
                except Exception:
                    logger.exception(
                        "dropping device response for stalled worker %d",
                        worker_id)

    def stop(self) -> None:
        self._stop = True

    def _handle_sync(self, frame) -> None:
        """Plane-3 frame (JSON kind 0/1 or proto kind 3/4) executed INLINE on
        the drain thread — no event-loop hop, no to_thread push. Only valid
        when the graph has no async nodes (engine.has_async_nodes False), in
        which case predict()/send_feedback() never suspend; the serve loop
        picks between this and the coroutine path once at startup."""
        try:
            worker_id, req_id, kind = _REQ_HEADER.unpack_from(frame)
        except struct.error:
            logger.error("dropping malformed IPC frame (%d bytes)", len(frame))
            return
        try:
            if kind in (KIND_PROTO_PREDICT, KIND_PROTO_FEEDBACK):
                from seldon_core_tpu.transport import proto_convert as pc
                from seldon_core_tpu.transport.proto import prediction_pb2 as pb

                raw = bytes(frame[_REQ_HEADER.size:])
                if kind == KIND_PROTO_PREDICT:
                    out = self.engine.predict_sync(
                        pc.message_from_proto(pb.SeldonMessage.FromString(raw)))
                else:
                    out = self.engine.send_feedback_sync(
                        pc.feedback_from_proto(pb.Feedback.FromString(raw)))
                body = pc.message_to_proto(out).SerializeToString()
            else:
                payload = json.loads(bytes(frame[_REQ_HEADER.size:]))
                if kind == KIND_PREDICT:
                    out = self.engine.predict_sync(SeldonMessage.from_dict(payload))
                elif kind == KIND_FEEDBACK:
                    out = self.engine.send_feedback_sync(Feedback.from_dict(payload))
                else:
                    raise SeldonError(f"unknown IPC kind {kind}")
                body = json.dumps(out.to_dict()).encode()
            status = 0
        except Exception as e:
            if kind in (KIND_PROTO_PREDICT, KIND_PROTO_FEEDBACK):
                http = int(getattr(e, "status_code", 500))
                code = {400: 3, 503: 14, 504: 4}.get(http, 13)
                body = bytes([code]) + str(e).encode()
            else:
                body = _error_body(
                    str(e),
                    getattr(e, "reason", "ENGINE_ERROR"),
                    int(getattr(e, "status_code", 500)),
                )
            status = 1
        ring = self.resp_rings.get(worker_id)
        if ring is None:
            logger.error("response for unknown worker %d dropped", worker_id)
            return
        try:
            ring.push_wait(_RESP_HEADER.pack(req_id, status) + body, 5.0)
        except PayloadTooLarge:
            err = _error_body(
                f"response too large for IPC slot "
                f"({len(body)} bytes > {ring.slot_size - _RESP_HEADER.size})",
                "RESPONSE_TOO_LARGE",
                500,
            )
            try:
                ring.push_wait(_RESP_HEADER.pack(req_id, 1) + err, 5.0)
            except Exception:
                logger.exception(
                    "dropping oversized response %d for worker %d", req_id, worker_id)
        except RingFull:
            # jammed for the full timeout; the edge's deadline 504s this
            # request — do not kill the drain thread
            logger.error("response ring full; dropping response %d for worker %d",
                         req_id, worker_id)

    async def _handle(self, frame: bytes) -> None:
        # No failure below may escape: serve_forever gathers these, so one bad
        # frame / oversized body / stalled worker would kill serving for all
        # workers.
        try:
            worker_id, req_id, kind = _REQ_HEADER.unpack_from(frame)
        except struct.error:
            logger.error("dropping malformed IPC frame (%d bytes)", len(frame))
            return
        try:
            if kind in (KIND_PROTO_PREDICT, KIND_PROTO_FEEDBACK):
                from seldon_core_tpu.transport import proto_convert as pc
                from seldon_core_tpu.transport.proto import prediction_pb2 as pb

                raw = bytes(frame[_REQ_HEADER.size:])
                if kind == KIND_PROTO_PREDICT:
                    req = pb.SeldonMessage.FromString(raw)
                    out = await self.engine.predict(pc.message_from_proto(req))
                else:
                    req = pb.Feedback.FromString(raw)
                    out = await self.engine.send_feedback(pc.feedback_from_proto(req))
                body = pc.message_to_proto(out).SerializeToString()
                status = 0
            else:
                payload = json.loads(frame[_REQ_HEADER.size:])
                if kind == KIND_PREDICT:
                    out = await self.engine.predict(SeldonMessage.from_dict(payload))
                elif kind == KIND_FEEDBACK:
                    out = await self.engine.send_feedback(Feedback.from_dict(payload))
                else:
                    raise SeldonError(f"unknown IPC kind {kind}")
                body = json.dumps(out.to_dict()).encode()
                status = 0
        except Exception as e:
            if kind in (KIND_PROTO_PREDICT, KIND_PROTO_FEEDBACK):
                # edge expects u8 grpc-status + message for proto frames;
                # mapping mirrors edge.cc grpc_code_from_http
                http = int(getattr(e, "status_code", 500))
                code = {400: 3, 503: 14, 504: 4}.get(http, 13)
                body = bytes([code]) + str(e).encode()
            else:
                body = _error_body(
                    str(e),
                    getattr(e, "reason", "ENGINE_ERROR"),
                    int(getattr(e, "status_code", 500)),
                )
            status = 1
        ring = self.resp_rings.get(worker_id)
        if ring is None:
            logger.error("response for unknown worker %d dropped", worker_id)
            return
        try:
            await asyncio.to_thread(
                ring.push_wait, _RESP_HEADER.pack(req_id, status) + body, 5.0
            )
        except PayloadTooLarge:
            err = _error_body(
                f"response too large for IPC slot "
                f"({len(body)} bytes > {ring.slot_size - _RESP_HEADER.size})",
                "RESPONSE_TOO_LARGE",
                500,
            )
            try:
                await asyncio.to_thread(ring.push_wait, _RESP_HEADER.pack(req_id, 1) + err, 5.0)
            except Exception:
                logger.exception("dropping oversized response %d for worker %d", req_id, worker_id)
        except Exception:
            logger.exception("dropping response %d for stalled worker %d", req_id, worker_id)


class IPCClient:
    """Worker-side handle: send a request frame, wait for the matching
    response (out-of-order safe — responses for other requests from this
    worker are parked)."""

    _PARKED_MAX = 1024

    def __init__(self, base_path: str, worker_id: int, timeout_s: float = 30.0):
        self.worker_id = int(worker_id)
        self.timeout_s = timeout_s
        self.req_ring = SharedRing(request_ring_path(base_path), create=False)
        self.resp_ring = SharedRing(response_ring_path(base_path, worker_id), create=False)
        self._next_id = 0
        # rid -> (arrival time, frame). Bounded: late responses to requests
        # that already timed out would otherwise accumulate forever, and after
        # u32 request-id wraparound a stale frame could match a live request.
        self._parked: Dict[int, tuple] = {}

    def _prune_parked(self) -> None:
        now = time.monotonic()
        stale = [rid for rid, (t, _) in self._parked.items() if now - t > self.timeout_s]
        for rid in stale:
            del self._parked[rid]
        while len(self._parked) > self._PARKED_MAX:
            oldest = min(self._parked, key=lambda rid: self._parked[rid][0])
            del self._parked[oldest]

    def _call(self, kind: int, payload: Dict[str, Any]) -> Dict[str, Any]:
        req_id = self._next_id
        self._next_id = (self._next_id + 1) & 0xFFFFFFFF
        frame = _REQ_HEADER.pack(self.worker_id, req_id, kind) + json.dumps(payload).encode()
        self.req_ring.push_wait(frame, timeout_s=self.timeout_s)

        deadline = time.monotonic() + self.timeout_s
        while True:
            if req_id in self._parked:
                raw = self._parked.pop(req_id)[1]
            else:
                raw = self.resp_ring.pop()
                if raw is None:
                    if time.monotonic() > deadline:
                        self._prune_parked()
                        raise TimeoutError(f"IPC response {req_id} timed out")
                    time.sleep(0.0002)
                    continue
            rid, status = _RESP_HEADER.unpack_from(raw)
            body = json.loads(raw[_RESP_HEADER.size:])
            if rid != req_id:
                self._parked[rid] = (time.monotonic(), raw)
                self._prune_parked()
                continue
            if status != 0:
                raise SeldonError(
                    body.get("status", {}).get("info", "IPC engine error"),
                    reason=body.get("status", {}).get("reason", "ENGINE_ERROR"),
                    status_code=500,
                )
            return body

    def predict(self, message: SeldonMessage) -> SeldonMessage:
        return SeldonMessage.from_dict(self._call(KIND_PREDICT, message.to_dict()))

    def send_feedback(self, feedback: Feedback) -> SeldonMessage:
        return SeldonMessage.from_dict(self._call(KIND_FEEDBACK, feedback.to_dict()))

    def close(self) -> None:
        self.req_ring.close()
        self.resp_ring.close()


def cleanup_rings(base_path: str, n_workers: int) -> None:
    import glob

    paths = [request_ring_path(base_path)] + [
        response_ring_path(base_path, w) for w in range(n_workers)
    ]
    # stale .tmp.<pid> files left by a creator killed between open and rename
    paths += [t for p in paths for t in glob.glob(p + ".tmp.*")]
    for p in paths:
        try:
            os.unlink(p)
        except OSError:
            pass
