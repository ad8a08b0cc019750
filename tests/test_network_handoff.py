"""Cross-host KV handoff over the wire (ISSUE 18 tentpole, network half).

The contract: swapping the prefill->decode transport from ``jax.device_put``
to a framed TCP stream changes NOTHING about tokens — network-handoff
serving is bit-exact against device-handoff serving for greedy and seeded
sampling — while the receiver publishes through
the SAME TransferQueue, so cancel/shed/poison and exactly-once semantics
are transport-independent: a replayed frame cannot double-deliver, a
corrupt frame fails ONE request (the metadata section rides ahead of the
payload, so the job_id survives truncation), and an oversized declared
length costs a comparison, never an allocation.

Both hosts live in this process (prefill worker thread -> loopback TCP ->
receiver thread) on the virtual 8-device CPU mesh; the wire path is the
real one."""

from __future__ import annotations

import asyncio
import socket
import struct
import time

import numpy as np
import pytest

from seldon_core_tpu.codec import framing
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.runtime.disagg import (
    MAX_HANDOFF_FRAME_BYTES,
    Handoff,
    HandoffReceiver,
    TransferQueue,
)
from seldon_core_tpu.runtime.flight import EV_HANDOFF_TRANSFER
from seldon_core_tpu.servers.llmserver import LLMServer
from seldon_core_tpu.testing.faults import HandoffPoisoner

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server(disaggregation="remote_prefill", prefill_devices=2)


@pytest.fixture(scope="module")
def sampled_server():
    return make_server(disaggregation="remote_prefill", prefill_devices=2,
                       temperature=0.8, top_k=20, seed=5)


def run_batch(server, prompts, *, n=8, seeds=None, transport="device",
              **batcher_kw):
    """One batch through a fresh ContinuousBatcher; ``transport`` selects
    the handoff path on the SAME server object (identical params, identical
    rng chain — any token difference is the wire's fault)."""
    batcher_kw.setdefault("page_size", 8)

    async def go():
        b = ContinuousBatcher(server, handoff_transport=transport,
                              **batcher_kw)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=n,
                     seed=None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)])
        stats = {"handoff": b.handoff_stats(),
                 "pages": b.page_stats()}
        await b.close()
        return outs, stats

    return asyncio.run(go())


PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13],
           [7], [60, 61, 62, 63, 64, 65]]


# ---------------------------------------------------------------- parity
# tier-1 870s budget: tier-1 keeps the seeded parity below (the denser
# cell — page accounting + rng chain over the wire); the pinned
# network-handoff CI step runs this file unfiltered
@pytest.mark.slow
def test_network_handoff_greedy_parity(server):
    """The acceptance bar: KV streamed header+raw over a socket decodes
    into the exact tokens the device-to-device copy produces — and the
    bytes really crossed the wire (the device path reports zero)."""
    base, dstats = run_batch(server, PROMPTS,
                             max_slots=3, max_len=40, len_buckets=(8,))
    net, nstats = run_batch(server, PROMPTS, transport="network",
                            max_slots=3, max_len=40, len_buckets=(8,))
    assert net == base
    assert nstats["handoff"]["handoffs_total"] == len(PROMPTS)
    assert nstats["handoff"]["handoff_queue_depth"] == 0
    assert nstats["handoff"]["handoff_network_bytes_total"] > 0
    assert dstats["handoff"]["handoff_network_bytes_total"] == 0
    assert nstats["pages"]["kv_pages_in_use"] == 0


def test_network_handoff_seeded_parity(sampled_server):
    """Seeded sampling across the socket: the first token samples from the
    worker's logits AFTER an encode/decode/device_put round trip, on the
    same per-request key — bf16/f32 buffers must survive bit-for-bit."""
    prompts = [[5, 9, 17, 2], [40, 3, 22], [7, 7, 7, 7, 7]]
    seeds = [42, 1234, 7]
    base, _ = run_batch(sampled_server, prompts, seeds=seeds,
                        max_slots=3, max_len=40, len_buckets=(8,))
    net, _ = run_batch(sampled_server, prompts, seeds=seeds,
                       transport="network",
                       max_slots=3, max_len=40, len_buckets=(8,))
    assert net == base


@pytest.mark.slow  # tier-1 870s budget: network bit-exactness is proven by the
# parity cells above; the pinned CI step runs this file unfiltered
def test_server_level_transport_config():
    """handoff_transport configured on the SERVER (the deployment-spec
    path) reaches the batcher and serves bit-exact."""
    s = make_server(disaggregation="remote_prefill", prefill_devices=2,
                    handoff_transport="network")
    expected = [s.generate([p], max_new_tokens=4)["tokens"][0]
                for p in PROMPTS[:2]]

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=32, len_buckets=(8,),
                              page_size=8)
        assert b.handoff_transport == "network"
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=4) for p in PROMPTS[:2]])
        stats = b.handoff_stats()
        await b.close()
        return outs, stats

    outs, stats = asyncio.run(go())
    assert outs == expected
    assert stats["handoff_network_bytes_total"] > 0
    st = s.llm_stats()
    assert "handoff_network_bytes_total" in st


# ------------------------------------------------------- poison / chaos
@pytest.mark.slow  # tier-1 870s budget: network bit-exactness is proven by the
# parity cells above; the pinned CI step runs this file unfiltered
def test_poisoned_network_handoff_fails_one_request_not_the_batch():
    """The chaos contract holds on the wire: a frame truncated in flight
    (HandoffPoisoner's network mode) resolves with an error for ITS
    request only — the metadata section decoded before the payload hole,
    so the job_id routed the failure; the batch survives and the next
    request serves bit-exact."""
    s = make_server(disaggregation="remote_prefill", prefill_devices=2,
                    max_new_tokens=4)
    expected = s.generate([[5, 9, 17]], max_new_tokens=4)["tokens"][0]

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=32, len_buckets=(8,),
                              page_size=8,
                              handoff_transport="network")
        HandoffPoisoner(b, first_n=1)
        with pytest.raises(Exception):
            await b.submit([40, 3, 22, 8], max_new_tokens=4)
        assert b.crashed is None
        ok = await b.submit([5, 9, 17], max_new_tokens=4)
        pages = b.page_stats()["kv_pages_in_use"]
        await b.close()
        return ok, pages

    ok, pages = asyncio.run(go())
    assert ok == expected
    assert pages == 0


def test_transfer_queue_refuses_replayed_put():
    """Exactly-once under reconnects: put() only transitions STAGED ->
    READY. A duplicate frame for an already-delivered job and a frame for
    a job this queue never staged are both refused — a replaying socket
    cannot double-deliver."""
    q = TransferQueue()
    q.register(1)
    assert q.put(Handoff(1, staged="kv", transfer_bytes=5))
    assert not q.put(Handoff(1, staged="kv-replay", transfer_bytes=5))
    assert not q.put(Handoff(99, staged="never-registered"))
    h = q.pop()
    assert h.job_id == 1 and h.staged == "kv"
    assert not q.put(Handoff(1, staged="kv-after-pop"))
    assert q.pop() is None
    assert q.stats()[0] == 1  # one delivery, ever


# -------------------------------------------- receiver wire protocol
# protocol-level tests on a live receiver + raw sockets (no model, ms)

def _kv_frame(job_id, *, record_events=True, events=()):
    staged = {"k": np.arange(6, dtype=np.float32).reshape(2, 3),
              "v": [np.arange(4, dtype=np.int32)]}
    skel, leaves = framing.tree_skeleton(staged)
    tensors = list(leaves)
    fl_ref = len(tensors)
    tensors.append(np.linspace(0, 1, 8, dtype=np.float32))
    meta = {"kind": "KVHandoff", "job_id": job_id, "prefill_s": 0.25,
            "skeleton": skel, "first_logits_ref": fl_ref,
            "record_events": record_events,
            "events": [list(e) for e in events]}
    return staged, framing.encode_frame(meta, tensors, path="handoff")


def _send(addr, payload, *, declared=None):
    n = len(payload) if declared is None else declared
    with socket.create_connection(addr, timeout=5.0) as s:
        try:
            s.sendall(struct.pack("<Q", n) + payload)
            s.shutdown(socket.SHUT_WR)
            # wait for the receiver to finish with this connection before
            # the test asserts (EOF on our side == reader done)
            s.settimeout(5.0)
            s.recv(1)
        except OSError:
            pass  # receiver may RST mid-send (the oversized-prefix drop)


def _wait_pop(q, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        h = q.pop()
        if h is not None:
            return h
        time.sleep(0.01)
    raise AssertionError("no handoff delivered within timeout")


@pytest.fixture()
def receiver():
    import jax

    q = TransferQueue()
    r = HandoffReceiver(q, jax.devices()[0])
    yield q, r
    r.close()


def test_receiver_roundtrip_device_put_and_events(receiver):
    q, r = receiver
    q.register(11)
    staged, payload = _kv_frame(
        11, events=[(0.5, "prefill_compute", {"dur_s": 0.1})])
    _send(r.addr, payload)
    h = _wait_pop(q)
    assert h.job_id == 11 and h.error is None
    assert h.prefill_s == 0.25
    assert h.transfer_bytes == len(payload)
    # the tree came back with containers AND values intact, device-resident
    assert np.array_equal(np.asarray(h.staged["k"]), staged["k"])
    assert np.array_equal(np.asarray(h.staged["v"][0]), staged["v"][0])
    assert np.array_equal(np.asarray(h.first_logits),
                          np.linspace(0, 1, 8, dtype=np.float32))
    # carried events survive, and the receiver stamped the transfer leg
    kinds = [e[1] for e in h.events]
    assert kinds[0] == "prefill_compute"
    assert kinds[-1] == EV_HANDOFF_TRANSFER
    assert h.events[-1][2]["bytes"] == len(payload)
    assert r.stats()["handoff_network_bytes_total"] == len(payload)


def test_receiver_truncated_frame_resolves_job_with_error(receiver):
    """Corrupt payload, intact metadata: the job fails cleanly instead of
    vanishing — this is what lets the batcher fail ONE request."""
    q, r = receiver
    q.register(21)
    _, payload = _kv_frame(21)
    _send(r.addr, payload[:-16])
    h = _wait_pop(q)
    assert h.job_id == 21
    assert h.error is not None and h.staged is None
    assert r.stats()["handoff_network_bytes_total"] == 0  # not a delivery


def test_receiver_survives_undecodable_garbage(receiver):
    """No recoverable job_id -> logged and dropped; the receiver (and its
    listener) stay up for the next good frame on a NEW connection."""
    q, r = receiver
    _send(r.addr, b"\x00" * 64)
    q.register(31)
    _, payload = _kv_frame(31)
    _send(r.addr, payload)
    h = _wait_pop(q)
    assert h.job_id == 31 and h.error is None


def test_receiver_oversized_length_prefix_drops_without_allocating(receiver):
    """An attacker-declared oversized frame never allocates the declared
    size: the receiver reads at most a bounded metadata probe, finds no
    recoverable job_id in the garbage, drops the connection, and the
    listener keeps serving."""
    q, r = receiver
    _send(r.addr, b"x" * 32, declared=MAX_HANDOFF_FRAME_BYTES + 1)
    q.register(41)
    _, payload = _kv_frame(41)
    _send(r.addr, payload)
    assert _wait_pop(q).job_id == 41


def test_receiver_wire_truncation_resolves_job_with_error(receiver):
    """Connection dies mid-payload (declared > delivered): the metadata
    leads the frame, so the partial buffer still yields the job_id and
    the job resolves with an error handoff instead of vanishing.  Before
    PR 19 the partial bytes were discarded, leaking the prefill-side
    staged pages and the decode-side future forever."""
    q, r = receiver
    q.register(61)
    _, payload = _kv_frame(61)
    _send(r.addr, payload[:-16], declared=len(payload))
    h = _wait_pop(q)
    assert h.job_id == 61
    assert h.error is not None and h.staged is None
    assert r.stats()["handoff_network_bytes_total"] == 0  # not a delivery


def test_receiver_oversized_frame_with_recoverable_meta_resolves_job(receiver):
    """A frame declaring more than MAX_HANDOFF_FRAME_BYTES but whose
    header+metadata fit in the bounded probe: the receiver refuses the
    payload yet still publishes an error handoff for the job it names.
    Before PR 19 this branch dropped the connection without resolving the
    job — the registered future and its slot pages leaked."""
    q, r = receiver
    q.register(71)
    _, payload = _kv_frame(71)
    _send(r.addr, payload, declared=MAX_HANDOFF_FRAME_BYTES + 1)
    h = _wait_pop(q)
    assert h.job_id == 71
    assert h.error is not None and h.staged is None
    # the listener survives the refusal and serves the next good frame
    q.register(72)
    _, good = _kv_frame(72)
    _send(r.addr, good)
    assert _wait_pop(q).job_id == 72


def test_receiver_replayed_frame_cannot_double_deliver(receiver):
    """The same frame arriving twice (socket replay after a reconnect):
    the first lands, the second is refused by the queue's STAGED->READY
    gate — stats count ONE delivery."""
    q, r = receiver
    q.register(51)
    _, payload = _kv_frame(51)
    _send(r.addr, payload)
    assert _wait_pop(q).job_id == 51
    _send(r.addr, payload)
    time.sleep(0.2)  # give the reader thread time to (wrongly) deliver
    assert q.pop() is None
    assert q.stats()[0] == 1


# ------------------------------------------------------------- validation
def test_load_validates_handoff_transport():
    with pytest.raises(ValueError, match="unknown handoff_transport"):
        make_server(disaggregation="remote_prefill", prefill_devices=2,
                    handoff_transport="banana")
    with pytest.raises(ValueError, match="remote_prefill"):
        make_server(handoff_transport="network")


def test_batcher_validates_handoff_transport(server):
    with pytest.raises(ValueError, match="unknown handoff_transport"):
        ContinuousBatcher(server, max_slots=2, max_len=32, len_buckets=(8,),
                          page_size=8, handoff_transport="banana")


@pytest.mark.slow  # tier-1 870s budget: network bit-exactness is proven by the
# parity cells above; the pinned CI step runs this file unfiltered
def test_rebalance_preserves_network_transport(server):
    """Autoscaler-driven prefill resizing rebuilds the worker pool — the
    new pool must keep streaming to the SAME receiver."""

    async def go():
        b = ContinuousBatcher(server, max_slots=2, max_len=32,
                              len_buckets=(8,), page_size=8,
                              handoff_transport="network")
        addr_before = b._remote.receiver_addr
        assert b.rebalance_disagg(3)
        assert b._remote.transport == "network"
        assert b._remote.receiver_addr == addr_before
        out = await b.submit([5, 9, 17], max_new_tokens=4)
        stats = b.handoff_stats()
        await b.close()
        return out, stats

    out, stats = asyncio.run(go())
    assert len(out) == 4
    assert stats["handoff_network_bytes_total"] > 0
