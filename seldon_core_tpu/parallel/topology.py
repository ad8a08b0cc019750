"""First-class topology: the one place device/mesh/host facts live.

Every other layer — servers, batcher, disagg workers, autoscaler — used to
re-derive the device world (`jax.devices()`, ad-hoc ``Mesh`` construction,
``devices[0]`` defaults, ``slice_index`` probes) at its own call sites,
which is exactly the single-mesh assumption ROADMAP item 1 names as the
scale-out blocker: facts derived twice can disagree, and a slice handed to
a worker has no way to say "this is your world now".

``Topology`` is the declared object those layers consume instead:

* the **axis-name registry** (:data:`DECLARED_AXES`) — the only legal mesh
  axis names; ``tools/shardlint`` statically checks every
  ``PartitionSpec``/collective ``axis_name`` literal against it, and
  :meth:`Topology.mesh` re-checks at runtime, so a typo'd axis fails in
  lint and in the first mesh build rather than as a silent replication.
* the **device world** plus host/process layout (process index/count,
  local devices, physical slice map) — derived ONCE in
  :meth:`Topology.detect` and injected everywhere else.
* **slice views**: :meth:`Topology.sub_topology` hands a disaggregated
  slice a Topology of its own devices, so a prefill or decode slice can
  itself be tensor-parallel sharded (``slice_topo.serving_mesh(tp)``) —
  the pre-work for TP × disaggregation.

Host/slice assumptions (``devices[0]`` defaults, ``process_index == 0``
gating, ``slice_index`` probes) are only legal inside the functions
declared in :data:`SINGLE_HOST_GUARDS`; shardlint's ``host-assumption``
rule enforces that, which is why the registries below are plain literals —
the linter reads them with ``ast`` without importing anything.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field, replace
from typing import Dict, Mapping, Optional, Sequence, Tuple

from seldon_core_tpu.parallel import mesh as _mesh

logger = logging.getLogger(__name__)

# ----------------------------------------------------------------------
# declared registries (read statically by tools/shardlint — keep literal)
# ----------------------------------------------------------------------

#: The only legal mesh axis names. Every ``PartitionSpec`` / ``shard_map``
#: / collective ``axis_name`` literal anywhere in the tree must come from
#: this table (shardlint rule ``axis-name-discipline``); Topology.mesh()
#: raises on anything else at runtime.
DECLARED_AXES: Dict[str, str] = {
    "data": "data-parallel replicas; DCN-tolerant (one sync per step)",
    "model": "tensor parallelism (GSPMD); ICI-only, innermost",
    "seq": "sequence parallelism for long context; ICI-only",
    "expert": "expert parallelism for MoE layers",
    "pipe": "pipeline stages; DCN-tolerant point-to-point handoff",
}

#: Functions allowed to touch raw host/process/slice facts
#: (``devices[0]``, ``process_index`` comparisons, ``slice_index``
#: probes). Everything else must consume the Topology predicates
#: (``single_host`` / ``is_primary_process`` / ``default_device``) or
#: carry a reasoned ``# shardlint: allow-host-assumption(...)``.
SINGLE_HOST_GUARDS: Dict[str, str] = {
    "Topology.detect": "the one derivation site for the device world",
    "Topology.default_device": "placement default = first LOCAL device; "
                               "the declared form of devices[0]",
    "Topology.is_primary_process": "process_index == 0 IS this predicate; "
                                   "callers gate on it, not on the index",
    "physical_slice_map": "slice_index probing is the topology layer's "
                          "job; consumers branch on the returned map",
}

#: Constructors/functions that guarantee prefill/decode slice
#: disjointness at runtime, so call sites passing statically-opaque
#: device sets are contract-covered (shardlint rule
#: ``slice-disjointness`` still reports PROVABLE overlaps at any site —
#: a certain overlap is a bug even when the contract turns it into a
#: clean crash).
SLICE_CONTRACTS: Dict[str, str] = {
    "DisaggregatedMesh": "constructor raises ValueError on any "
                         "prefill/decode device overlap",
    "disaggregated_mesh": "delegates to DisaggregatedMesh after "
                          "complement/tail splits of one device list",
    "partition_for_disaggregation": "returns complementary partitions "
                                    "(whole physical slices or "
                                    "tail/head) of a single list",
    "Topology.disaggregated": "delegates counts to disaggregated_mesh, "
                              "which splits one device list into "
                              "complementary halves",
}


def physical_slice_map(devices: Sequence) -> Optional[Dict[int, list]]:
    """``{slice_index: [devices]}`` when every device exposes a physical
    slice id (real multi-slice platforms), else None (CPU test meshes,
    single-slice platforms). The ONE place the ``slice_index`` attribute
    is probed; consumers branch on the returned map, which makes their
    single-slice fallback a declared fact instead of an implicit one."""
    if not devices or not all(hasattr(d, "slice_index") for d in devices):
        return None
    by_slice: Dict[int, list] = {}
    for d in devices:
        by_slice.setdefault(d.slice_index, []).append(d)
    return by_slice


@dataclass(frozen=True)
class Topology:
    """Immutable snapshot of the device world one process serves from.

    ``devices`` is the (sub)world in enumeration order — for the process
    topology that is ``jax.devices()``; for a slice view it is the
    slice's devices. Meshes, disaggregated splits, and placement
    defaults are all derived from here so every consumer agrees."""

    devices: Tuple
    local_devices: Tuple
    process_index: int = 0
    process_count: int = 1
    slice_map: Optional[Mapping[int, tuple]] = field(default=None)

    # -- device identity -------------------------------------------------

    @property
    def platform(self) -> str:
        """What JAX says the devices are ("tpu", "cpu", ...): from
        outside, a server on the CPU and one on the chip otherwise look
        the same."""
        return self.default_device.platform

    @property
    def device_kind(self) -> str:
        return self.default_device.device_kind

    # -- derivation ----------------------------------------------------

    @classmethod
    def detect(cls) -> "Topology":
        """Derive the process topology from the JAX runtime. The only
        place outside tests that asks JAX for the device world; call
        ``multihost.initialize()`` first on multi-host pods."""
        import jax

        devices = tuple(jax.devices())
        sm = physical_slice_map(devices)
        topo = cls(
            devices=devices,
            local_devices=tuple(jax.local_devices()),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            slice_map=None if sm is None else {
                k: tuple(v) for k, v in sm.items()},
        )
        logger.info("detected %r", topo)
        return topo

    def sub_topology(self, devices: Sequence) -> "Topology":
        """A view of this topology restricted to ``devices`` (a disagg
        slice, a replica's shard, ...). Host/process layout carries
        over; the slice map is re-derived for the subset, so a slice can
        build its own meshes — including TP within the slice."""
        devices = tuple(devices)
        unknown = set(map(id, devices)) - set(map(id, self.devices))
        if unknown:
            raise ValueError(
                f"sub_topology devices not in this topology's world "
                f"({len(unknown)} of {len(devices)} unknown)")
        local = set(map(id, self.local_devices))
        sm = physical_slice_map(devices)
        return replace(
            self,
            devices=devices,
            local_devices=tuple(d for d in devices if id(d) in local),
            slice_map=None if sm is None else {
                k: tuple(v) for k, v in sm.items()},
        )

    # -- host/process predicates (the declared guards) -----------------

    @property
    def device_count(self) -> int:
        return len(self.devices)

    @property
    def local_device_count(self) -> int:
        return len(self.local_devices)

    @property
    def single_host(self) -> bool:
        return self.process_count == 1

    @property
    def is_primary_process(self) -> bool:
        return self.process_index == 0

    @property
    def default_device(self):
        """Placement default: the first device this process can address
        (falls back to the world's first device for pure slice views
        with no local member)."""
        pool = self.local_devices or self.devices
        return pool[0]

    @property
    def num_slices(self) -> int:
        return len(self.slice_map) if self.slice_map else 1

    # -- mesh builders (axis names validated against DECLARED_AXES) ----

    def _check_axes(self, names) -> None:
        unknown = [a for a in names if a not in DECLARED_AXES]
        if unknown:
            raise ValueError(
                f"undeclared mesh axis name(s) {unknown!r}: every axis "
                f"must be registered in parallel/topology.py "
                f"DECLARED_AXES (have: {', '.join(DECLARED_AXES)})")

    def mesh(self, axes: Dict[str, int]):
        """``make_mesh`` over this topology's devices, axis names
        checked against the declared registry."""
        self._check_axes(axes)
        return _mesh.make_mesh(axes, self.devices)

    def serving_mesh(self, model_parallel: int = 1):
        return self.mesh({"data": -1, "model": model_parallel})

    def hybrid_mesh(self, ici_axes: Dict[str, int],
                    dcn_axes: Optional[Dict[str, int]] = None):
        from seldon_core_tpu.parallel.multihost import hybrid_mesh

        self._check_axes(dict(dcn_axes or {}))
        self._check_axes(ici_axes)
        return hybrid_mesh(ici_axes, dcn_axes, self.devices)

    def disaggregated(self, prefill_devices=1, decode_devices=0):
        """Disaggregated prefill/decode split of this topology's world.
        The returned ``DisaggregatedMesh`` carries ``prefill_topology``
        / ``decode_topology`` sub-views so each slice can build further
        meshes (TP inside a slice) without re-deriving anything."""
        dm = _mesh.disaggregated_mesh(
            prefill_devices, decode_devices, devices=self.devices)
        dm.attach_topology(self)
        return dm

    def __repr__(self) -> str:  # keep logs short: devices can be many
        return (f"Topology(platform={self.platform}, "
                f"device_kind={self.device_kind!r}, "
                f"devices={self.device_count}, "
                f"process={self.process_index}/{self.process_count}, "
                f"slices={self.num_slices})")


# ----------------------------------------------------------------------
# process singleton (injectable for tests / virtual meshes)
# ----------------------------------------------------------------------

_TOPO_LOCK = threading.Lock()
_PROCESS_TOPOLOGY: Optional[Topology] = None


def get_topology() -> Topology:
    """The process topology, detecting it on first use. Tests and
    virtual-mesh harnesses inject their own via :func:`set_topology`."""
    global _PROCESS_TOPOLOGY
    with _TOPO_LOCK:
        if _PROCESS_TOPOLOGY is None:
            _PROCESS_TOPOLOGY = Topology.detect()
        return _PROCESS_TOPOLOGY


def log_device_memory() -> None:
    """One INFO line per local device of the process topology: what its
    allocator holds now and its high-water mark. Serving entry points call
    this on the way out, so a run's footprint is in the server's own log
    (chip_smoke.py reads it there). Silent when this process never
    detected a topology — no device work happened."""
    with _TOPO_LOCK:
        topo = _PROCESS_TOPOLOGY
    if topo is None:
        return
    for d in topo.local_devices:
        stats = d.memory_stats() or {}  # None on backends that keep none (CPU)
        logger.info(
            "device %d (%s) memory: bytes_in_use=%s peak_bytes_in_use=%s "
            "bytes_limit=%s", d.id, d.device_kind,
            stats.get("bytes_in_use"), stats.get("peak_bytes_in_use"),
            stats.get("bytes_limit"))


def set_topology(topo: Optional[Topology]) -> Optional[Topology]:
    """Install (or with None, reset) the process topology; returns the
    previous value so callers can restore it."""
    global _PROCESS_TOPOLOGY
    with _TOPO_LOCK:
        prev = _PROCESS_TOPOLOGY
        _PROCESS_TOPOLOGY = topo
        return prev
