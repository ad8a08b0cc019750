"""Disaggregated prefill/decode serving: the host-side coordination layer.

The compute story (docs/performance.md "Disaggregated serving"): prefill is
a compute-bound burst, decode is a bandwidth-bound trickle, and running both
on one mesh slice makes every admission a latency spike for every in-flight
stream — PR 7's chunked prefill only *interleaves* the burst. Splitting the
serving mesh (parallel/mesh.py ``disaggregated_mesh``) runs admission
prefill on a **prefill slice** and the pipelined decode batch on a
**decode slice**, with the prefilled KV moved device-to-device (DistServe,
Zhong et al. OSDI 2024; Splitwise, Patel et al. ISCA 2024).

This module is the host half of that split:

- ``TransferQueue`` — the lock-guarded handoff channel between prefill
  workers and the decode batcher. A handoff is registered at admission,
  becomes READY when the worker finishes, and is consumed by the batcher
  loop — or cancelled by a shed. Every transition is atomic under one
  lock, so a handoff is delivered exactly once and its decode-side pages
  are freed exactly once even when a shed races the worker's put (the
  interleavings tests/test_schedules.py explores).
- ``PrefillWorker`` — one worker thread per prefill-slice device: it keeps
  a committed copy of the params and a single-sequence staging page pool
  on its device, runs the server's own compiled chunk program there
  (``_get_prefill_chunk`` — the SAME program local admission compiles, so
  the written KV is bit-identical), then moves the result onto the decode device with
  ``jax.device_put`` — a direct device-to-device copy, no host round trip
  for the KV — and publishes the handoff.
- ``PrefillWorkerPool`` — M workers behind least-backlog dispatch.

The decode side (runtime/batcher.py ``disaggregation="remote_prefill"``)
imports a ready handoff into its slot pool with one donated jitted scatter
(``ContinuousBatcher._get_handoff_import``), pinned by the ``disagg.import_pages`` hlolint
contract: zero infeed/outfeed, donation aliasing intact, bytes within the
committed budget.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

DISAGGREGATION_MODES = ("off", "remote_prefill")

# How a finished prefill's KV reaches the decode slice: "device" is the
# direct jax.device_put fast path (shared topology); "network" frames the
# page bucket header+raw and streams it over a socket to the decode host's
# HandoffReceiver (no shared topology — ROADMAP multi-host decode).
HANDOFF_TRANSPORTS = ("device", "network")

# Outer wire format of one network handoff: an 8-byte little-endian length
# prefix, then that many frame bytes (codec/framing.py layout). The length
# is bounded before ANY allocation — a corrupt prefix must not let the
# receiver allocate attacker-controlled gigabytes.
MAX_HANDOFF_FRAME_BYTES = 1 << 33  # 8 GiB: > any pow2 bucket we ship

# TransferQueue record states (values are only compared for identity)
_STAGED = "staged"        # registered; the worker has not finished yet
_READY = "ready"          # handoff published, waiting for the batcher
_CANCELLED = "cancelled"  # shed before the worker finished


def normalize_disaggregation(value) -> str:
    """Canonical disaggregation mode ("off" or "remote_prefill"); raises
    ValueError on anything else so misconfiguration fails at load() time,
    not inside the batcher's admission path."""
    v = str(value or "off").strip().lower()
    if v in ("off", "none", "no", "0", ""):
        return "off"
    if v in ("remote_prefill", "remote-prefill", "prefill", "disagg",
             "disaggregated"):
        return "remote_prefill"
    raise ValueError(
        f"unknown disaggregation {value!r}: expected one of "
        f"{DISAGGREGATION_MODES}")


class PrefillRequest:
    """What a worker needs to prefill one admission: the (already
    truncated) prompt, its length bucket (caps the chunk width), and the
    page count the decode side allocated for it. ``record_events`` asks
    the worker to stamp flight-recorder stage events into the Handoff
    (set when the decode side's recorder is running).

    Prefix reuse (radix trie, runtime/radix.py): when the decode side
    already caches the prompt's leading ``prefix_len`` tokens
    (``prefix_pages`` whole blocks), ``prefix_staged`` carries their KV
    as an exported page bucket — the worker imports it into its staging
    pool and computes ONLY positions ``prefix_len..``, then hands back
    only the suffix pages. The prefix ships forward as a D2D copy (bytes,
    not FLOPs); the prefill compute saved is the point."""

    __slots__ = ("job_id", "ids", "plen", "n_pages", "record_events",
                 "prefix_len", "prefix_pages", "prefix_staged")

    def __init__(self, job_id: int, ids: List[int], plen: int,
                 n_pages: int = 0, record_events: bool = False,
                 prefix_len: int = 0, prefix_pages: int = 0,
                 prefix_staged: Any = None):
        self.job_id = job_id
        self.ids = list(ids)
        self.plen = int(plen)
        self.n_pages = int(n_pages)
        self.record_events = bool(record_events)
        self.prefix_len = int(prefix_len)
        self.prefix_pages = int(prefix_pages)
        self.prefix_staged = prefix_staged


class Handoff:
    """One finished prefill, published by a worker: the staged KV already
    resident on the DECODE device (``jax.device_put`` moved it
    device-to-device; the host never materialized it), the last-position
    logits the first sampled token draws from (a small [vocab] host array
    — admission-time, once per request), and timing/bytes for the
    handoff metrics. ``error`` carries a worker-side failure instead of
    a payload — the batcher resolves the request with it.

    ``events`` carries the worker's flight-recorder stage stamps
    ((perf_counter t, kind, fields) tuples — runtime/flight.py): written by
    the WORKER thread before ``put`` publishes the handoff, read by the
    batcher after ``pop`` — ownership transfers through the TransferQueue's
    lock, so the single-writer-per-slot ring discipline holds without the
    worker ever touching a slot ring."""

    __slots__ = ("job_id", "staged", "first_logits", "error", "prefill_s",
                 "transfer_bytes", "events")

    def __init__(self, job_id: int, staged: Any = None,
                 first_logits: Optional[np.ndarray] = None,
                 error: Optional[BaseException] = None,
                 prefill_s: float = 0.0, transfer_bytes: int = 0,
                 events: Optional[list] = None):
        self.job_id = job_id
        self.staged = staged
        self.first_logits = first_logits
        self.error = error
        self.prefill_s = prefill_s
        self.transfer_bytes = transfer_bytes
        self.events = events or []


class TransferQueue:
    """Lock-guarded handoff channel between prefill workers and the decode
    batcher, with exactly-once delivery/cancellation semantics.

    Protocol (all transitions atomic under ``self._lock``):

    - ``register(job_id)`` (batcher, at admission): the job exists, STAGED.
    - ``put(handoff)`` (worker thread): STAGED -> READY, or returns False
      when the job was cancelled meanwhile — the worker just drops the
      payload (the decode-side pages were freed by the canceller).
    - ``pop()`` (batcher loop): oldest READY handoff, removed — the
      batcher now owns the import and the slot owns the pages.
    - ``cancel(job_id)`` (batcher shed paths): READY -> returns the
      handoff (the CALLER frees the pages, exactly once); STAGED ->
      marked cancelled and returns None (the caller frees the pages NOW;
      the worker's later put is refused). Unknown/already-popped ->
      None and the caller must NOT free (the slot owns them).

    An unlocked reconstruction of this state machine double-delivers a
    handoff (pop vs pop) or frees pages twice (pop vs cancel) under
    interleavings the deterministic-schedule suite finds
    (tests/test_schedules.py); the real class survives the same
    exploration."""

    def __init__(self):
        self._lock = threading.Lock()
        self._state: Dict[int, str] = {}
        self._ready: deque = deque()  # Handoff records, arrival order
        self.handoffs_total = 0
        self.transfer_bytes_total = 0
        # optional ready-notification hook (the batcher points this at a
        # loop-threadsafe wakeup); read under the lock, invoked outside it
        # so the callback can never deadlock against queue users
        self.on_ready: Optional[Any] = None

    def register(self, job_id: int) -> None:
        with self._lock:
            self._state[job_id] = _STAGED

    def put(self, handoff: Handoff) -> bool:
        """Publish a finished prefill. False = the job was cancelled while
        the worker ran (payload dropped — the canceller already freed the
        decode-side pages), OR the job is unknown / already READY. Only a
        STAGED job can become READY: with the network transport a frame
        replayed over a reconnected socket must not double-deliver."""
        with self._lock:
            st = self._state.get(handoff.job_id)
            if st is not _STAGED:
                if st is _CANCELLED:
                    del self._state[handoff.job_id]
                return False
            self._state[handoff.job_id] = _READY
            self._ready.append(handoff)
            self.handoffs_total += 1
            self.transfer_bytes_total += int(handoff.transfer_bytes)
            cb = self.on_ready
        if cb is not None:
            try:
                cb()
            except Exception:  # a wakeup hook must never kill a worker
                logger.exception("transfer-queue on_ready hook failed")
        return True

    def pop(self) -> Optional[Handoff]:
        """Oldest READY handoff, or None. The caller owns the import; the
        job's pages now belong to its slot."""
        with self._lock:
            if not self._ready:
                return None
            h = self._ready.popleft()
            self._state.pop(h.job_id, None)
            return h

    def cancel(self, job_id: int) -> Optional[Handoff]:
        """Shed a job. Returns the handoff if it was READY (caller frees
        its decode-side pages); None if it was still STAGED (caller frees
        the pages now — the worker's put will be refused) or already
        popped (caller must NOT free: the slot owns them)."""
        with self._lock:
            st = self._state.get(job_id)
            if st is _READY:
                found = None
                for i, h in enumerate(self._ready):
                    if h.job_id == job_id:
                        found = h
                        del self._ready[i]
                        break
                del self._state[job_id]
                return found
            if st is _STAGED:
                self._state[job_id] = _CANCELLED
            return None

    def ready_depth(self) -> int:
        with self._lock:
            return len(self._ready)

    def depth(self) -> int:
        """Jobs registered and not yet consumed (staged + ready)."""
        with self._lock:
            return len(self._state)

    def stats(self):
        """(handoffs_total, transfer_bytes_total, staged+ready depth) —
        one consistent snapshot for the /metrics scrape."""
        with self._lock:
            return (self.handoffs_total, self.transfer_bytes_total,
                    len(self._state))


class PrefillWorker:
    """One prefill-slice worker: a dedicated thread that runs the server's
    compiled prefill programs on its own device and hands the written KV
    to the decode device.

    The worker keeps a committed copy of the params on its device
    (``LLMServer._params_on``) and a
    single-sequence staging page pool (``RESERVED_PAGES + n_pages`` pages
    — pages 2.. back the sequence; the batcher's block-row width is
    reused so the chunk program has the batcher's exact shape contract).
    Prefill itself is the SAME compiled program local admission runs
    (``_get_prefill_chunk``), just dispatched on the
    prefill device — which is what makes remote-prefill serving
    bit-exact against single-slice serving (tests/test_disagg.py).

    All cross-thread state (the backlog, the closing flag) lives under
    ``self._cond``; the staging pool and params copy are touched only by
    the worker thread after ``__init__``."""

    def __init__(self, server: Any, queue: TransferQueue, device: Any,
                 decode_device: Any, *, max_len: int, page_size: int,
                 n_pages: int, prefill_chunk: int,
                 name: str = "prefill-worker",
                 transport: str = "device",
                 receiver_addr: Optional[tuple] = None):
        if transport not in HANDOFF_TRANSPORTS:
            raise ValueError(
                f"unknown handoff transport {transport!r}: expected one of "
                f"{HANDOFF_TRANSPORTS}")
        if transport == "network" and receiver_addr is None:
            raise ValueError("network handoff transport needs the decode "
                             "side's HandoffReceiver address")
        self.server = server
        self.queue = queue
        self.device = device
        self.decode_device = decode_device
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.prefill_chunk = int(prefill_chunk)
        self.name = name
        self.transport = transport
        self.receiver_addr = receiver_addr
        self._sock = None  # persistent frame socket, worker thread only
        self._cond = threading.Condition()
        self._backlog: deque = deque()
        self._closing = False
        self._params = None    # committed copy, built on first job
        self._staging = None   # paged staging pool, built on first job
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    # -- caller side ---------------------------------------------------
    def submit(self, req: PrefillRequest) -> None:
        with self._cond:
            if self._closing:
                raise RuntimeError(f"{self.name} is closed")
            self._backlog.append(req)
            self._cond.notify()

    def backlog_depth(self) -> int:
        with self._cond:
            return len(self._backlog)

    def close(self, timeout_s: float = 30.0) -> None:
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        # bounded: a wedged device dispatch must not hang server shutdown
        self._thread.join(timeout=timeout_s)
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # -- worker side ---------------------------------------------------
    def _next_job(self) -> Optional[PrefillRequest]:
        with self._cond:
            while not self._backlog and not self._closing:
                self._cond.wait(timeout=0.5)
            if self._backlog:
                return self._backlog.popleft()
            return None  # closing and drained

    def _run(self) -> None:
        while True:
            req = self._next_job()
            if req is None:
                return
            try:
                handoff = self._prefill_one(req)
            except BaseException as e:  # noqa: BLE001 — worker must not die
                logger.exception("prefill worker %s failed job %d",
                                 self.name, req.job_id)
                handoff = Handoff(req.job_id, error=e)
            self._publish(handoff)

    def _ensure_state(self):
        import jax

        if self._params is None:
            self._params = self.server._params_on(self.device)
        if self._staging is None:
            from seldon_core_tpu.models.cache import RESERVED_PAGES

            # server-cached compile: M workers share one staging-init
            # program; each executes it once onto its own device
            pool = self.server._get_staging_pool_init(
                RESERVED_PAGES + self.n_pages, self.page_size)()
            self._staging = jax.device_put(pool, self.device)

    def _prefill_one(self, req: PrefillRequest) -> Handoff:
        import time

        t0 = time.perf_counter()
        self._ensure_state()
        staged, first_logits = self._prefill_paged(req)
        import jax

        t1 = time.perf_counter()
        from seldon_core_tpu.runtime.flight import (
            EV_HANDOFF_COMPUTE, EV_HANDOFF_TRANSFER)

        if self.transport == "network":
            # cross-host: no shared topology for a device-to-device put.
            # The KV stays on the prefill device here; ``_frame_handoff``
            # pulls it to host in ONE bulk transfer and ships it as a
            # frame. The transfer event is stamped by the RECEIVER (it
            # owns the wire-bytes count and the decode-side import time).
            events = []
            if req.record_events:
                events = [(t1, EV_HANDOFF_COMPUTE,
                           {"worker": self.name, "dur_s": t1 - t0})]
            return Handoff(req.job_id, staged=staged,
                           first_logits=first_logits, prefill_s=t1 - t0,
                           events=events)
        # THE handoff: a direct device-to-device copy onto the decode
        # slice — the KV never rounds through host memory (the jitted
        # decode-side import is hlolint-checked for zero infeed/outfeed)
        moved = jax.device_put(staged, self.decode_device)
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree.leaves(moved))
        t2 = time.perf_counter()
        events = []
        if req.record_events:
            events = [
                (t1, EV_HANDOFF_COMPUTE,
                 {"worker": self.name, "dur_s": t1 - t0}),
                (t2, EV_HANDOFF_TRANSFER,
                 {"bytes": nbytes, "dur_s": t2 - t1}),
            ]
        return Handoff(req.job_id, staged=moved, first_logits=first_logits,
                       prefill_s=t2 - t0,
                       transfer_bytes=nbytes, events=events)

    # -- network transport (worker side) -------------------------------
    def _publish(self, handoff: Handoff) -> None:
        """Deliver a finished handoff. Device transport (and every error
        handoff) goes straight into the TransferQueue; network transport
        frames the staged KV and streams it to the decode host's
        ``HandoffReceiver``, which runs the SAME ``queue.put`` there — so
        the exactly-once staged/cancel protocol is identical on both
        transports."""
        if self.transport != "network" or handoff.error is not None:
            self.queue.put(handoff)
            return
        try:
            import jax

            # the worker thread pays this wait either way (the encoder's
            # bulk device_get blocks on the async prefill values); taking
            # it BEFORE the codec keeps seldon_frame_encode_seconds a
            # serialization number instead of a compute-tail number; the
            # decode side never waits here — this is the worker's thread
            jax.block_until_ready(handoff.staged)
            payload = self._frame_handoff(handoff)
            self._send_frame(payload)
        except BaseException as e:  # noqa: BLE001 — worker must not die
            logger.exception("prefill worker %s could not ship job %d over "
                             "the network handoff", self.name,
                             handoff.job_id)
            self.queue.put(Handoff(handoff.job_id, error=e))

    def _frame_handoff(self, handoff: Handoff) -> bytes:
        """Serialize one handoff as a frame: tree skeleton + job metadata
        in the JSON section, KV pages and first-token logits as raw
        tensor buffers. ``encode_frame`` pulls every device leaf to host
        in one bulk ``jax.device_get`` — the framing contract graftlint
        enforces on this path."""
        from seldon_core_tpu.codec import framing

        skel, leaves = framing.tree_skeleton(handoff.staged)
        tensors = list(leaves)
        fl_ref = None
        if handoff.first_logits is not None:
            fl_ref = len(tensors)
            tensors.append(handoff.first_logits)
        meta = {
            "kind": "KVHandoff",
            "job_id": handoff.job_id,
            "prefill_s": handoff.prefill_s,
            "skeleton": skel,
            "first_logits_ref": fl_ref,
            "record_events": bool(handoff.events),
            "events": [[t, kind, fields]
                       for (t, kind, fields) in handoff.events],
        }
        return framing.encode_frame(meta, tensors, path="handoff")

    def _send_frame(self, payload: bytes) -> None:
        """Ship one length-prefixed frame over the persistent socket,
        reconnecting once on a broken pipe (the receiver tolerates
        reconnects; the TransferQueue refuses replayed job_ids)."""
        import socket
        import struct

        wire = struct.pack("<Q", len(payload)) + payload
        for attempt in (0, 1):
            try:
                if self._sock is None:
                    self._sock = socket.create_connection(
                        self.receiver_addr, timeout=30.0)
                    self._sock.setsockopt(socket.IPPROTO_TCP,
                                          socket.TCP_NODELAY, 1)
                self._sock.sendall(wire)
                return
            except OSError:
                if self._sock is not None:
                    try:
                        self._sock.close()
                    finally:
                        self._sock = None
                if attempt:
                    raise

    def _prefill_paged(self, req: PrefillRequest):
        """Chunked prefill into the staging pool through a staging block
        row — the same compiled chunk program type as local paged
        admission (``_prefill_step``), on the prefill device. The staging
        pool is reused across jobs: its pages are position-reset before
        each prompt so no previous occupant's positions survive.

        Prefix reuse: when the request carries a decode-side radix hit
        (``prefix_pages`` exported blocks), the bucket imports into the
        staging pool's leading sequence pages and the chunk loop starts
        at ``prefix_len`` — the suffix chunks ATTEND over the imported
        prefix through the same staging row, so the written suffix KV is
        bit-identical to a cold full prefill, at suffix-only FLOPs."""
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, RESERVED_PAGES, TRASH_PAGE
        from seldon_core_tpu.runtime.batcher import _page_table_ops

        reset_pages = _page_table_ops()[2]
        n0 = req.n_pages or -(-len(req.ids) // self.page_size)
        n_pre = min(req.prefix_pages, n0) if req.prefix_staged is not None \
            else 0
        ids_np = np.full((self.n_pages,), TRASH_PAGE, np.int32)
        ids_np[:n0] = np.arange(RESERVED_PAGES, RESERVED_PAGES + n0)
        self._staging = reset_pages(self._staging, jnp.asarray(ids_np))
        row = np.full((self.n_pages,), NULL_PAGE, np.int32)
        row[:n0] = np.arange(RESERVED_PAGES, RESERVED_PAGES + n0)
        bt_row = jnp.asarray(row[None, :])
        if n_pre:
            # decode-side cached prefix: D2D the exported bucket onto this
            # device and scatter it into the sequence's leading staging
            # pages (the same jitted import program the decode side runs)
            bucket = jax.device_put(req.prefix_staged, self.device)
            staged_pages = (jax.tree.leaves(bucket)[0].shape[0]
                            - RESERVED_PAGES)
            imp = self.server._get_handoff_import(self.n_pages, staged_pages)
            pre_row = np.full((self.n_pages,), NULL_PAGE, np.int32)
            pre_row[:n_pre] = np.arange(RESERVED_PAGES,
                                        RESERVED_PAGES + n_pre)
            self._staging = imp(self._staging, bucket, jnp.asarray(pre_row),
                                jnp.asarray(n_pre, jnp.int32))

        C = min(self.prefill_chunk, req.plen) or req.plen
        fn = self.server._get_prefill_chunk(C, self.n_pages)
        L = len(req.ids)
        logits = None
        start = n_pre * self.page_size if n_pre else 0
        while start < L:
            part = req.ids[start:start + C]
            n = len(part)
            toks = np.zeros((1, C), np.int32)
            pos = np.full((1, C), PAD_POS, np.int32)
            toks[0, :n] = part
            pos[0, :n] = np.arange(start, start + n)
            start += n
            # the head runs for the prompt's last row alone, in its last chunk
            head_row = np.int32(n - 1 if start >= L else -1)
            logits, self._staging, _ = fn(self._params, self._staging, bt_row,
                                          jnp.asarray(toks), jnp.asarray(pos), head_row)
        # graftlint: allow-host-sync-in-hot-path(admission-time sync on the PREFILL worker thread, once per request: the LAST chunk's logits seed the first sampled token; the decode slice never blocks on it)
        first_logits = np.asarray(logits[0, 0]).astype(np.float32)
        # Ship only a power-of-two page bucket covering the pages THIS
        # worker wrote (the suffix — imported prefix pages never travel
        # back: the decode side still holds their originals), not the
        # whole max_len staging pool: interconnect bytes track the
        # uncached suffix length (DECODE_NOTES.md "interconnect math")
        # and the decode-side import stays at O(log n_pages) compiles.
        # The slice runs on the prefill device; the import masks rows
        # past the valid count to TRASH_PAGE so bucket padding never
        # lands in a live page.
        from seldon_core_tpu.runtime.batcher import pow2_bucket

        n_suffix = n0 - n_pre
        b = pow2_bucket(n_suffix, self.n_pages - n_pre)
        staged = jax.tree.map(
            lambda p: p[n_pre:n_pre + RESERVED_PAGES + b], self._staging)
        return staged, first_logits


class TruncatedStream(ConnectionError):
    """Mid-message EOF. Carries the bytes read so far: the frame layout
    puts the metadata section (and so the job_id) ahead of the tensor
    payload, so the receiver can usually still resolve the victim job
    with an error handoff instead of leaking its staged decode-side
    slot (the PR 19 leak sweep's truncated-frame finding)."""

    def __init__(self, msg: str, partial: bytes = b""):
        super().__init__(msg)
        self.partial = partial


# Bounded read for frames the receiver refuses to take fully (declared
# length over MAX_HANDOFF_FRAME_BYTES): enough for header + tensor table
# + metadata JSON on any real handoff, so the job_id is recoverable
# without trusting the hostile length prefix.
HANDOFF_META_PROBE_BYTES = 1 << 20


def _recv_exact(conn, n: int) -> Optional[bytes]:
    """Read exactly ``n`` bytes from a socket, or None on clean EOF.
    A mid-message EOF raises — a half-frame must never decode."""
    buf = bytearray()
    while len(buf) < n:
        chunk = conn.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            if buf:
                raise TruncatedStream(
                    f"handoff stream truncated: wanted {n} bytes, "
                    f"got {len(buf)}", partial=bytes(buf))
            return None
        buf.extend(chunk)
    return bytes(buf)


class HandoffReceiver:
    """Decode-host side of the network KV handoff: a TCP listener whose
    reader threads decode incoming frames, land the KV on the decode
    device with one ``jax.device_put``, and publish through the SAME
    ``TransferQueue.put`` the device transport uses — cancel/shed and
    exactly-once semantics are transport-independent by construction.

    A malformed frame never kills the receiver: the frame layout puts
    the metadata section before the payload, so a corrupt tensor region
    still yields the ``job_id`` (``decode_frame(meta_only=True)``) and
    the job is resolved with an error handoff — one request fails, the
    batch survives (the chaos-harness poison contract). A frame whose
    metadata is unreadable is logged and dropped; the outer length
    prefix is bounds-checked before ANY allocation."""

    def __init__(self, queue: TransferQueue, device: Any,
                 host: str = "127.0.0.1"):
        import socket

        self.queue = queue
        self.device = device
        self._lock = threading.Lock()
        self.network_bytes_total = 0  # wire payload bytes, under _lock
        self._closing = False
        self._conns: List[Any] = []
        self._threads: List[threading.Thread] = []
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.bind((host, 0))
        self._listener.listen(16)
        self.addr = self._listener.getsockname()
        t = threading.Thread(target=self._accept_loop,
                             name="handoff-receiver", daemon=True)
        self._threads.append(t)
        t.start()

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed by close()
            with self._lock:
                if self._closing:
                    conn.close()
                    return
                self._conns.append(conn)
                t = threading.Thread(target=self._read_loop, args=(conn,),
                                     name="handoff-reader", daemon=True)
                self._threads.append(t)
            t.start()

    def _read_loop(self, conn) -> None:
        import struct

        try:
            while True:
                head = _recv_exact(conn, 8)
                if head is None:
                    return
                (n,) = struct.unpack("<Q", head)
                if n > MAX_HANDOFF_FRAME_BYTES:
                    # refusing the frame must not leak the job: read a
                    # BOUNDED probe (never the hostile declared length) —
                    # the leading metadata section usually survives, and
                    # resolving the job with an error handoff frees its
                    # staged decode-side slot instead of hanging it
                    probe = b""
                    try:
                        probe = _recv_exact(
                            conn, min(n, HANDOFF_META_PROBE_BYTES)) or b""
                    except TruncatedStream as te:
                        probe = te.partial
                    except (OSError, ConnectionError):
                        pass
                    self._refuse(
                        probe,
                        f"frame declares {n} bytes "
                        f"(cap {MAX_HANDOFF_FRAME_BYTES})")
                    return
                try:
                    payload = _recv_exact(conn, n)
                except TruncatedStream as te:
                    self._refuse(te.partial, str(te))
                    return
                if payload is None:
                    return
                handoff = self._materialize(payload)
                if handoff is not None:
                    self.queue.put(handoff)
        except (OSError, ConnectionError) as e:
            with self._lock:
                closing = self._closing
            if not closing:
                logger.warning("handoff connection dropped: %s", e)
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _materialize(self, payload: bytes) -> Optional[Handoff]:
        """One received frame -> one Handoff with the KV resident on the
        decode device. Decode failures become error handoffs when the
        metadata (and so the job_id) survives, else None (drop)."""
        import time

        import jax

        from seldon_core_tpu.codec import framing
        from seldon_core_tpu.runtime.flight import EV_HANDOFF_TRANSFER

        t0 = time.perf_counter()
        try:
            meta, tensors = framing.decode_frame(payload, path="handoff")
            if meta.get("kind") != "KVHandoff":
                raise framing.FrameError(
                    f"expected a KVHandoff frame, got {meta.get('kind')!r}")
            skel = meta["skeleton"]
            fl_ref = meta.get("first_logits_ref")
            first_logits = None
            if fl_ref is not None:
                # .copy() releases the frame buffer once the tree's leaves
                # are device-resident — the [vocab] logits are the only
                # host-side survivor of the payload
                first_logits = tensors[fl_ref].copy()
            staged = framing.tree_unskeleton(skel, tensors)
            staged = jax.device_put(staged, self.device)
            t1 = time.perf_counter()
            events = [(e[0], e[1], e[2]) for e in meta.get("events", ())]
            if meta.get("record_events"):
                events.append((t1, EV_HANDOFF_TRANSFER,
                               {"bytes": len(payload), "dur_s": t1 - t0}))
            with self._lock:
                self.network_bytes_total += len(payload)
            return Handoff(meta["job_id"], staged=staged,
                           first_logits=first_logits,
                           prefill_s=meta.get("prefill_s", 0.0),
                           transfer_bytes=len(payload), events=events)
        except Exception as e:  # noqa: BLE001 — receiver must not die
            job_id = None
            try:
                meta, _ = framing.decode_frame(payload, meta_only=True,
                                               path="handoff")
                job_id = meta.get("job_id")
            except Exception:  # noqa: BLE001
                pass
            if job_id is None:
                logger.exception("dropping undecodable handoff frame "
                                 "(no recoverable job_id)")
                return None
            logger.exception("handoff frame for job %s failed to decode; "
                             "resolving with error", job_id)
            return Handoff(job_id, error=e)

    def _refuse(self, prefix: bytes, why: str) -> None:
        """Last-ditch resolution for a frame the receiver will never
        fully read (oversized declared length, mid-frame truncation).
        The metadata section leads the frame, so the prefix usually
        still decodes with ``meta_only=True`` — publishing an error
        handoff then releases the job's staged decode-side slot (pages,
        prefix pins, the client future) through the same exactly-once
        queue path a poisoned-but-complete frame takes. Without a
        recoverable job_id the frame is logged and dropped: the slot
        leak is then the sender's bug to surface, not silently ours."""
        from seldon_core_tpu.codec import framing

        job_id = None
        try:
            meta, _ = framing.decode_frame(prefix, meta_only=True,
                                           path="handoff")
            if meta.get("kind") == "KVHandoff":
                job_id = meta.get("job_id")
        except Exception:  # noqa: BLE001 — the prefix is hostile input
            pass
        if job_id is None:
            logger.error("dropping unresolvable handoff frame (%s; "
                         "no recoverable job_id in %d probe bytes)",
                         why, len(prefix))
            return
        logger.error("handoff frame for job %s refused (%s); "
                     "resolving with error", job_id, why)
        self.queue.put(Handoff(job_id, error=ConnectionError(why)))

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"handoff_network_bytes_total": self.network_bytes_total}

    def close(self, timeout_s: float = 5.0) -> None:
        import socket

        with self._lock:
            self._closing = True
            conns = list(self._conns)
        for c in conns:
            # close() from another thread does not interrupt a blocked
            # recv(); shutdown() does — the reader sees EOF and exits
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # likewise a blocked accept() survives listener.close(); a
        # zero-byte self-connect wakes it so it can observe _closing
        try:
            with socket.create_connection(self.addr, timeout=1.0):
                pass
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=timeout_s)


class PrefillWorkerPool:
    """M prefill workers behind least-backlog dispatch, publishing into
    one shared TransferQueue. One worker per prefill-slice device is the
    natural shape (each worker's programs are committed to its device);
    more devices than workers just leaves slices idle."""

    def __init__(self, server: Any, devices: Sequence, decode_device: Any,
                 *, max_len: int, page_size: int, n_pages: int,
                 prefill_chunk: int,
                 queue: Optional[TransferQueue] = None,
                 transport: str = "device",
                 receiver_addr: Optional[tuple] = None):
        # ``queue``: adopt an EXISTING TransferQueue instead of creating
        # one — the disagg-rebalance actuator builds the replacement pool
        # on the batcher's live queue so jobs staged on the outgoing pool
        # keep their exactly-once delivery path (runtime/batcher.py
        # ``rebalance_disagg``).
        self.queue = queue if queue is not None else TransferQueue()
        self.transport = transport
        self.receiver_addr = receiver_addr
        self.workers = [
            PrefillWorker(server, self.queue, dev, decode_device,
                          max_len=max_len,
                          page_size=page_size, n_pages=n_pages,
                          prefill_chunk=prefill_chunk,
                          name=f"prefill-worker-{i}",
                          transport=transport, receiver_addr=receiver_addr)
            for i, dev in enumerate(devices)
        ]

    def submit(self, req: PrefillRequest) -> None:
        self.queue.register(req.job_id)
        # least-backlog, lowest index breaks ties: deterministic placement
        # keeps parity tests and schedule replays reproducible
        _, w = min(enumerate(self.workers),
                   key=lambda iw: (iw[1].backlog_depth(), iw[0]))
        w.submit(req)

    def backlog_depth(self) -> int:
        return sum(w.backlog_depth() for w in self.workers)

    def close(self, timeout_s: float = 30.0) -> None:
        for w in self.workers:
            w.close(timeout_s=timeout_s)
