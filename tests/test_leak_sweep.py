"""Exhaustive error-path leak sweep (ISSUE 19 — the dynamic half).

``tools/leaklint`` statically proves every registered acquire site pairs
with a release on every CFG path; this suite makes those paths EXECUTE.
``testing/faults.py LeakSweep`` arms a deterministic one-shot fault at
each registered acquire/commit boundary (adapter pin, page allocation,
radix copy-on-write funding, prefill staging, handoff import, resume
journal), a request is driven through it, and the residue probe then
asserts every refcount the unwind owns is back to zero: pages held by
slots, elevated trie pins, adapter pins, staged remote jobs, undelivered
handoffs, journal entries.

Coverage crosses configurations the way the burned-down leaks did: the local
sweep replays the PR 7 / PR 12 / PR 15 shapes (prefix-pin drop on
exhaustion, cow-source-pin drop-and-retry, adapter-pin on the 400 path),
the disaggregated sweeps replay the staging/import containment, and the
stub-fleet sweep replays the PR 16 journal-entry lifetime — plus a
negative control proving the harness actually detects a planted leak.

Tier-1 runs the local sweep, the disaggregated sweep, and
the millisecond stub tests.
"""

from __future__ import annotations

import types

import numpy as np
import pytest

from seldon_core_tpu.contracts.payload import SeldonError
from seldon_core_tpu.runtime.batcher import ensure_stream_service
from seldon_core_tpu.runtime.engine import ReplicaSet
from seldon_core_tpu.runtime.resilience import ShedError
from seldon_core_tpu.servers.llmserver import LLMServer
from seldon_core_tpu.testing.faults import LeakSweep

pytestmark = pytest.mark.leakcheck

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)
RANK = 4

# 16 tokens = two full 8-token pages once cached; the cow probe extends
# the first block and half the second, forcing a partial-block match
WARM = list(range(1, 17))
COW_PROBE = WARM[:12] + [77]
# full-block prefix reuse + an uncached tail: exhaustion here must drop
# the two prefix pins on the unwind (the PR 7 / PR 15 leak class)
PINNED_TAIL = WARM + [88, 89]
FRESH = [50, 51, 52, 53]


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=4, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3,
                continuous_batching=3, continuous_batching_max_len=40)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def local_server():
    # one server covers three boundaries: LoRA registry (adapter-pin),
    # page pool (page-alloc), radix trie (radix-cow)
    return make_server(kv_page_size=8,
                       prefix_cache_size=8, lora_rank=RANK,
                       lora_max_adapters=4)


@pytest.fixture(scope="module")
def disagg_server():
    return make_server(disaggregation="remote_prefill", prefill_devices=2,
                       kv_page_size=8)


def load_one_adapter(server) -> str:
    reg = server.adapter_registry
    if "tenant-0" in reg.names():
        return "tenant-0"
    rng = np.random.default_rng(7)
    cfg = server._cfg
    dims = {"wq": (cfg.dim, cfg.n_heads * cfg.head_dim),
            "wo": (cfg.n_heads * cfg.head_dim, cfg.dim),
            "w1": (cfg.dim, cfg.ffn_dim),
            "w2": (cfg.ffn_dim, cfg.dim),
            "w3": (cfg.dim, cfg.ffn_dim)}
    w = {proj: (rng.normal(size=(cfg.n_layers, din, RANK)) * 0.25,
                rng.normal(size=(cfg.n_layers, RANK, dout)) * 0.25)
         for proj, (din, dout) in dims.items()}
    reg.load("tenant-0", w, alpha=2 * RANK)
    return "tenant-0"


# ---------------------------------------------------------------------------
# local paged serving: adapter-pin, page-alloc, radix-cow
# ---------------------------------------------------------------------------

def test_leak_sweep_local_paged(local_server):
    """The three local admission boundaries, swept on one live batcher.
    Each drive states its expected containment outcome explicitly —
    error vs success is part of the contract under test, not noise."""
    svc = ensure_stream_service(local_server)
    b = svc.batcher
    sweep = LeakSweep(b)
    assert set(sweep.boundaries()) == {"adapter-pin", "page-alloc",
                                       "radix-cow"}
    name = load_one_adapter(local_server)

    # warm the trie: WARM's two full blocks are cached after release
    assert svc.submit_sync(WARM, 4)
    sweep.assert_clean("warmup")

    def drive(boundary):
        if boundary == "adapter-pin":
            # the injected KeyError is the unknown-adapter 400 path: the
            # request fails before any pin exists, nothing to unwind
            with pytest.raises(Exception):
                svc.submit_sync(FRESH, 4, adapter=name)
        elif boundary == "page-alloc":
            # exhaustion with two prefix pins held: the unwind must free
            # them before shedding (PR 7 / PR 15 class) — with nothing
            # in flight the admission sheds 503 rather than parking
            with pytest.raises(ShedError):
                svc.submit_sync(PINNED_TAIL, 4)
        else:  # radix-cow
            # the first (cow-funded) allocation fails; the cow pin drops
            # and the retry succeeds — SUCCESS proves the drop-and-retry
            # path ran (a cow-less admission would have shed instead),
            # and a double-drop of the pin would raise in the allocator
            # (PR 12 class)
            assert svc.submit_sync(COW_PROBE, 4)

    assert sweep.sweep(drive) == sweep.boundaries()
    assert sweep.fired == 3

    # the batch still serves after the whole sweep — containment, not
    # survival-by-restart
    assert svc.submit_sync(FRESH, 4)
    sweep.assert_clean("post-sweep serving")


def test_leak_sweep_detects_a_planted_leak(local_server):
    """Negative control: a pin the unwind forgets MUST fail the sweep —
    otherwise a zero-residue pass proves nothing. Plant an adapter pin
    with no owner and check both the probe and assert_clean see it."""
    svc = ensure_stream_service(local_server)
    b = svc.batcher
    name = load_one_adapter(local_server)
    sweep = LeakSweep(b)
    sweep.assert_clean("baseline")
    aid = b._adapters.resolve_and_pin(name)  # the planted leak
    try:
        assert sweep.residue()["adapter_pins"] == 1
        with pytest.raises(AssertionError, match="leak residue"):
            sweep.assert_clean("planted leak")
    finally:
        b._adapters.unpin(aid)
    sweep.assert_clean("after repair")


def test_leak_sweep_never_fired_is_an_error(local_server):
    """A sweep whose fault never fires is a silently-skipped layer: the
    harness must refuse it rather than report the boundary covered."""
    svc = ensure_stream_service(local_server)
    sweep = LeakSweep(svc.batcher)
    with pytest.raises(AssertionError, match="never fired"):
        sweep.sweep(lambda boundary: None, boundaries=["page-alloc"])
    sweep.disarm()
    with pytest.raises(ValueError, match="not applicable"):
        sweep.arm("prefill-stage")  # no remote pool on this batcher


# ---------------------------------------------------------------------------
# disaggregated serving: staging + import boundaries
# ---------------------------------------------------------------------------

def _sweep_disagg(server):
    svc = ensure_stream_service(server)
    b = svc.batcher
    sweep = LeakSweep(b)
    want = {"prefill-stage", "handoff-import", "page-alloc"}
    assert set(sweep.boundaries()) == want

    assert svc.submit_sync(WARM, 4)  # compile + prove the happy path
    sweep.assert_clean("warmup")

    def drive(boundary):
        if boundary == "page-alloc":
            with pytest.raises(ShedError):
                svc.submit_sync(PINNED_TAIL, 4)
        elif boundary == "prefill-stage":
            # the worker raises; _publish turns it into an error handoff
            # and the decode side releases the staged slot + pages
            with pytest.raises(SeldonError):
                svc.submit_sync(FRESH, 4)
        else:  # handoff-import
            # the staged payload is poisoned; the import containment
            # releases slot, suffix pages, and prefix pins — the client
            # sees the import's own exception, whatever type it is
            with pytest.raises(Exception):
                svc.submit_sync(FRESH, 4)

    swept = sweep.sweep(drive)
    assert set(swept) == want
    assert svc.submit_sync(FRESH, 4)  # still serving
    sweep.assert_clean("post-sweep serving")


def test_leak_sweep_disagg_paged(disagg_server):
    _sweep_disagg(disagg_server)


# ---------------------------------------------------------------------------
# resume journal boundary on a stub fleet (no jax, milliseconds)
# ---------------------------------------------------------------------------

class _StubBatcher:
    def __init__(self):
        self._pending = []
        self._slots = []
        # what LeakSweep and replica_load read of the pool: empty, no trie
        self._allocator = types.SimpleNamespace(stats=lambda: (0, 0, 0))
        self._radix = None
        self.crashed = None
        self._task = None
        self.heartbeat = 0.0

    def accommodates(self, prompt, max_new_tokens=None):
        return True


class _StubService:
    def __init__(self):
        self.batcher = _StubBatcher()
        self.calls = 0

    def submit_sync(self, prompt, max_new_tokens=None, on_token=None,
                    **kw):
        self.calls += 1
        out = list(range(10, 10 + (max_new_tokens or 4)))
        for t in out:
            if on_token is not None:
                on_token(t)
        return out


class _StubReplica:
    def __init__(self):
        self._batcher_service = _StubService()


def test_leak_sweep_journal_record(monkeypatch):
    """The PR 16 boundary: ``ResumeJournal.record`` raising must fail
    the fleet submit BEFORE any entry exists — depth stays zero and the
    fleet keeps dispatching afterwards."""
    fleet = ReplicaSet([_StubReplica(), _StubReplica()])
    sweep = LeakSweep(_StubBatcher(), engine=fleet)
    assert sweep.boundaries() == ["page-alloc", "journal-record"]

    def drive(boundary):
        with pytest.raises(SeldonError):
            fleet.submit_sync([1, 2, 3], 4, seed=5)

    assert sweep.sweep(drive, ["journal-record"]) == ["journal-record"]
    assert fleet.submit_sync([1, 2, 3], 4, seed=5) == [10, 11, 12, 13]
    sweep.assert_clean("post-sweep fleet submit")


def test_leak_sweep_detects_undischarged_journal_entry():
    """Negative control for the journal probe: a discard that never runs
    (the PR 16 leak shape) leaves depth > 0 and fails assert_clean."""
    fleet = ReplicaSet([_StubReplica()])
    sweep = LeakSweep(_StubBatcher(), engine=fleet)
    # plant the leak: disable discard for one submit
    real_discard = fleet._journal.discard
    fleet._journal.discard = lambda jid: None
    try:
        assert fleet.submit_sync([1, 2, 3], 4, seed=5)
        assert sweep.residue()["journal_depth"] == 1
        with pytest.raises(AssertionError, match="journal_depth"):
            sweep.assert_clean("planted journal leak")
    finally:
        fleet._journal.discard = real_discard
        for jid in list(fleet._journal._entries):
            fleet._journal.discard(jid)
    sweep.assert_clean("after repair")
