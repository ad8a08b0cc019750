"""ctypes binding for the shared-memory staging ring (native/ring.cc).

``SharedRing`` is the IPC data plane between transport worker processes and
the device-owning engine process: lock-free MPMC, payloads are raw bytes (the
codec's packed tensors), one memcpy per side. The .so builds lazily via make
with the baked-in g++ (pybind11 is unavailable in this environment; ctypes
keeps the binding dependency-free).
"""

from __future__ import annotations

import ctypes
import logging
import os
import shutil
import struct
import subprocess
import threading
import time
from typing import Optional

_U32 = struct.Struct("<I")

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libseldon_staging.so")

_lib = None
_lib_lock = threading.Lock()


class ToolchainMissing(RuntimeError):
    """No ``make`` / C++ compiler here and nothing built: the native plane
    cannot exist on this machine (tests skip on it; a failed compile is a
    different thing and raises ``NativeBuildError``)."""


class NativeBuildError(RuntimeError):
    """``make`` ran and failed; the message carries its stderr."""


def build_native() -> str:
    """Bring ``native/build`` up to date and return the .so path.

    ``make`` is the arbiter of staleness: it compares every target with
    every prerequisite the Makefile lists (headers included), so a stale
    binary left in a checkout is rebuilt and a fresh one costs a few
    milliseconds. On a machine with no toolchain, what is already built is
    used as it is."""
    cxx = os.environ.get("CXX", "g++").split()[0]  # the Makefile's default
    if shutil.which("make") is None or shutil.which(cxx) is None:
        if os.path.exists(_SO_PATH):
            return _SO_PATH
        raise ToolchainMissing(
            "no make/C++ compiler on PATH and native/build is empty")
    proc = subprocess.run(["make", "-C", _NATIVE_DIR], capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            f"make -C {_NATIVE_DIR} failed ({proc.returncode}):\n"
            f"{proc.stderr.strip()}")
    return _SO_PATH


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = build_native()
        lib = ctypes.CDLL(path, use_errno=True)
        lib.scr_create.restype = ctypes.c_void_p
        lib.scr_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.scr_attach.restype = ctypes.c_void_p
        lib.scr_attach.argtypes = [ctypes.c_char_p]
        lib.scr_detach.argtypes = [ctypes.c_void_p]
        lib.scr_capacity.restype = ctypes.c_uint64
        lib.scr_capacity.argtypes = [ctypes.c_void_p]
        lib.scr_slot_size.restype = ctypes.c_uint64
        lib.scr_slot_size.argtypes = [ctypes.c_void_p]
        lib.scr_size.restype = ctypes.c_uint64
        lib.scr_size.argtypes = [ctypes.c_void_p]
        lib.scr_push.restype = ctypes.c_int
        lib.scr_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        lib.scr_pop.restype = ctypes.c_int
        lib.scr_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
        lib.scr_pop_many.restype = ctypes.c_int
        lib.scr_pop_many.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.scr_push_model_resps.restype = ctypes.c_int
        lib.scr_push_model_resps.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,  # req_ids u32*
            ctypes.c_void_p,  # row_offsets u64*
            ctypes.c_void_p,  # row_counts u32*
            ctypes.c_uint32,  # n
            ctypes.c_void_p,  # data f8*
            ctypes.c_uint64,  # row_nvals
            ctypes.c_void_p,  # tail_dims u32*
            ctypes.c_uint32,  # n_tail
            ctypes.c_char_p,  # frag
            ctypes.c_uint32,  # frag_len
            ctypes.c_uint32,  # dtype_code
        ]
        _lib = lib
        return lib


def native_available() -> bool:
    try:
        _load()
        return True
    except ToolchainMissing as e:
        logger.warning("native staging unavailable: %s", e)
        return False


class RingFull(RuntimeError):
    pass


class PayloadTooLarge(ValueError):
    pass


class SharedRing:
    """MPMC shared-memory byte queue over a mapped file.

    create=True initialises the file (the engine side does this); workers
    attach to the same path. Capacity must be a power of two.
    """

    def __init__(self, path: str, capacity: int = 1024, slot_size: int = 1 << 20,
                 create: bool = False):
        self._lib = _load()
        self.path = path
        if create:
            self._h = self._lib.scr_create(path.encode(), capacity, slot_size)
            if not self._h:
                # the ring file is sparse, so this is a limit on the file or
                # the mapping (EFBIG: RLIMIT_FSIZE), not memory in use
                err = ctypes.get_errno()
                raise OSError(
                    err, f"could not create ring ({capacity} slots x "
                    f"{slot_size} bytes): {os.strerror(err)}", path)
        else:
            self._h = self._lib.scr_attach(path.encode())
            if not self._h:
                raise RuntimeError(f"could not attach ring at {path}")
        self.capacity = int(self._lib.scr_capacity(self._h))
        self.slot_size = int(self._lib.scr_slot_size(self._h))
        self._popbuf = ctypes.create_string_buffer(self.slot_size)
        self._manybuf = None  # lazy (pop_many only; engine-side)

    # ------------------------------------------------------------------
    def push(self, payload: bytes) -> bool:
        """True on success, False when full; raises PayloadTooLarge."""
        rc = self._lib.scr_push(self._h, payload, len(payload))
        if rc == 0:
            return True
        if rc == -1:
            return False
        raise PayloadTooLarge(f"{len(payload)} bytes > slot_size {self.slot_size}")

    def push_wait(self, payload: bytes, timeout_s: float = 1.0, spin_s: float = 0.0002) -> None:
        deadline = time.monotonic() + timeout_s
        while not self.push(payload):
            if time.monotonic() > deadline:
                raise RingFull(f"ring {self.path} full for {timeout_s}s")
            time.sleep(spin_s)

    def pop(self) -> Optional[bytes]:
        """One payload or None when empty."""
        rc = self._lib.scr_pop(self._h, self._popbuf, self.slot_size)
        if rc >= 0:
            # string_at copies exactly rc bytes; _popbuf.raw[:rc] would
            # materialise the full slot (1MB) per pop — measured as ~2/3 of
            # the engine's CPU at 7k rps
            return ctypes.string_at(self._popbuf, rc)
        if rc == -1:
            return None
        raise RuntimeError(f"ring pop error {rc}")

    def pop_batch(self, max_items: int, wait_s: float = 0.0, spin_s: float = 0.0002):
        """Drain up to max_items; optionally wait up to wait_s for the first."""
        out = []
        deadline = time.monotonic() + wait_s
        while len(out) < max_items:
            item = self.pop()
            if item is None:
                if out or time.monotonic() > deadline:
                    break
                time.sleep(spin_s)
                continue
            out.append(item)
        return out

    def pop_many(self, max_items: int, wait_s: float = 0.0, spin_s: float = 0.0002):
        """Batched drain: ONE FFI call pops up to max_items frames into the
        reusable pop buffer and returns zero-copy memoryview slices into it.

        The views are valid only until the next pop/pop_many on this ring —
        callers must finish with (or copy) each frame within the drain
        cycle. Falls back timing-wise like pop_batch: waits up to wait_s for
        the first frame."""
        if self._manybuf is None:
            # slot_size + 4 guarantees the largest possible frame always
            # fits (progress), the extra room batches typical small frames
            self._manybuf = ctypes.create_string_buffer(self.slot_size + 4 + (256 << 10))
        used = ctypes.c_uint32(0)
        deadline = time.monotonic() + wait_s
        while True:
            n = self._lib.scr_pop_many(
                self._h, self._manybuf, len(self._manybuf), max_items,
                ctypes.byref(used))
            if n > 0:
                break
            if n == -3:
                # non-empty ring whose first frame exceeds our buffer: the
                # sizing above makes this impossible (slot_size + 4 always
                # fits), so spinning would loop forever on a real bug
                raise RuntimeError(
                    "scr_pop_many: pending frame larger than drain buffer "
                    f"({len(self._manybuf)} bytes) — ring slot_size mismatch")
            if time.monotonic() > deadline:
                return []
            time.sleep(spin_s)
        # ctypes buffers expose format 'c' memoryviews, whose item access
        # returns 1-byte bytes (and struct/int indexing raises); cast to 'B'.
        # Read-only: np.frombuffer over these views must yield read-only
        # arrays so an in-place-mutating component fails fast (as it did
        # with pop_batch's bytes) instead of scribbling over the shared
        # drain buffer under other frames.
        mv = memoryview(self._manybuf).cast("B").toreadonly()
        out = []
        off = 0
        for _ in range(n):
            (length,) = _U32.unpack_from(mv, off)
            out.append(mv[off + 4:off + 4 + length])
            off += 4 + length
        return out

    def push_model_resps(self, req_ids, row_offsets, row_counts, data,
                         row_nvals: int, tail_dims, frag: bytes,
                         dtype_code: int, timeout_s: float = 5.0,
                         spin_s: float = 0.0002) -> None:
        """Bulk kind-2 OK response push: the C side builds each response
        frame directly in its ring slot (ModelExecutor._ok_response layout)
        from one stacked f8 row buffer. Retries the unpushed tail when the
        ring is momentarily full; raises RingFull past timeout_s and
        PayloadTooLarge when a response exceeds the slot."""
        import numpy as np

        req_ids = np.ascontiguousarray(req_ids, dtype=np.uint32)
        row_offsets = np.ascontiguousarray(row_offsets, dtype=np.uint64)
        row_counts = np.ascontiguousarray(row_counts, dtype=np.uint32)
        tail = np.ascontiguousarray(tail_dims, dtype=np.uint32)
        if data.dtype != np.float64 or not data.flags.c_contiguous:
            raise ValueError("push_model_resps needs C-contiguous float64 rows")
        # pre-check EVERY response against the slot size so the C call can
        # never commit a partial batch and then fail (-2 after i pushes
        # would leave pushed frames to be answered AGAIN by the fallback)
        head = 7 + 4 * (1 + len(tail)) + 4 + len(frag)
        if int(row_counts.max(initial=0)) * row_nvals * 8 + head > self.slot_size:
            raise PayloadTooLarge(
                f"model response exceeds slot_size {self.slot_size}")
        deadline = time.monotonic() + timeout_s
        start = 0
        n = len(req_ids)
        while start < n:
            rc = self._lib.scr_push_model_resps(
                self._h,
                req_ids[start:].ctypes.data, row_offsets[start:].ctypes.data,
                row_counts[start:].ctypes.data, n - start,
                data.ctypes.data, row_nvals,
                tail.ctypes.data, len(tail), frag, len(frag), dtype_code)
            if rc == -2:
                raise PayloadTooLarge(
                    f"model response exceeds slot_size {self.slot_size}")
            start += rc
            if start < n:
                if time.monotonic() > deadline:
                    raise RingFull(f"ring {self.path} full for {timeout_s}s")
                time.sleep(spin_s)

    def __len__(self) -> int:
        return int(self._lib.scr_size(self._h))

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.scr_detach(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
