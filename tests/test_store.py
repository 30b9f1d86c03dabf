"""Per-host object store: the by-reference task data plane
(fiber_tpu/store, docs/objectstore.md).

Coverage map:
* serialization: protocol-5 out-of-band envelope roundtrip + legacy
  payload compat + framing's preallocated recv path;
* LocalStore: put/get roundtrip inline AND through the disk tier
  (spill/eviction), pin/ref-count semantics;
* wire plane: chunked get/put, digest verification, miss handling;
* pool integration — the acceptance criteria: an 8 MB broadcast arg
  over >= 32 tasks crosses the wire ONCE (store counters prove it), and
  chaos-injected fetch failure under a fixed seed degrades to inline
  payloads without losing a single task;
* host agent store ops (the cluster cache tier).

Soak variants are marked ``slow`` (run via `make chaos` / full tiers).
"""

import os
import socket

import numpy as np
import pytest

import fiber_tpu
from fiber_tpu import serialization
from fiber_tpu.store import LocalStore, ObjectRef, StoreClient, StoreServer
from fiber_tpu.store.core import digest_of
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))


def unique_array(mbytes: float = 8.0) -> np.ndarray:
    """Content-unique payload: the host cache directory outlives one
    test (it IS the cross-process dedup under test), so every test must
    broadcast bytes nobody has cached yet."""
    rng = np.random.default_rng(int.from_bytes(os.urandom(8), "big"))
    return rng.standard_normal(int(mbytes * (1 << 20) / 4)).astype(
        np.float32)


# ---------------------------------------------------------------------------
# serialization + framing satellites
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("session", ["script", "interactive"])
def test_oob_envelope_roundtrip_and_legacy_compat(monkeypatch, session):
    # An interactive session (a notebook, `python -c`, an xdist worker:
    # no `__main__.__file__`) pickles by value with cloudpickle; it
    # must produce the same envelope as the stdlib pickler.
    monkeypatch.setattr(serialization, "is_in_interactive_console",
                        lambda: session == "interactive")
    arr = np.arange(200_000, dtype=np.float32)
    blob = serialization.dumps(arr)
    # Out-of-band: the envelope costs bytes(header) over raw, never the
    # old in-band pickling's extra full copy of the array.
    assert serialization.is_envelope(blob)
    assert len(blob) < arr.nbytes + 4096
    back = serialization.loads(blob)
    assert np.array_equal(back, arr)
    assert back.flags.writeable  # loads must not hand out frozen views
    # A graph the stdlib pickler refuses (a lambda) goes by value, and
    # its array out-of-band all the same.
    by_value = serialization.dumps((lambda: 7, arr))
    assert serialization.is_envelope(by_value)
    assert len(by_value) < arr.nbytes + 4096
    fn, back = serialization.loads(by_value)
    assert fn() == 7 and np.array_equal(back, arr)
    # Small payloads stay plain pickles; plain pickles keep loading.
    small = serialization.dumps({"k": [1, 2, 3]})
    assert not serialization.is_envelope(small)
    assert serialization.loads(small) == {"k": [1, 2, 3]}
    # Frames arrive as bytearrays (framing.recv_frame); both formats
    # must load from them.
    assert np.array_equal(serialization.loads(bytearray(blob)), arr)
    assert serialization.loads(bytearray(small)) == {"k": [1, 2, 3]}


def test_oob_envelope_mixed_graph():
    """Buffers inside containers go out-of-band individually; the
    structure and small leaves stay in the pickle stream."""
    obj = {
        "params": np.full(100_000, 3.0, np.float64),
        "meta": {"gen": 7, "name": "es"},
        "pair": (np.arange(50_000, dtype=np.int64), b"tag"),
    }
    back = serialization.loads(serialization.dumps(obj))
    assert back["meta"] == {"gen": 7, "name": "es"}
    assert np.array_equal(back["params"], obj["params"])
    assert np.array_equal(back["pair"][0], obj["pair"][0])
    assert back["pair"][1] == b"tag"


def test_recv_frame_preallocated_large():
    """framing.recv_frame fills one preallocated bytearray via
    recv_into — a multi-MB frame round-trips exactly."""
    import threading

    from fiber_tpu.framing import recv_frame, send_frame

    a, b = socket.socketpair()
    try:
        payload = os.urandom(3 << 20)

        def send() -> None:
            # Off-thread: a multi-MB sendall blocks until the reader
            # drains the socketpair buffer.
            send_frame(a, payload)
            send_frame(a, memoryview(payload)[: 1 << 10])  # bytes-like

        t = threading.Thread(target=send, daemon=True)
        t.start()
        got = recv_frame(b)
        assert isinstance(got, bytearray) and bytes(got) == payload
        assert bytes(recv_frame(b)) == payload[: 1 << 10]
        t.join(10)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# LocalStore
# ---------------------------------------------------------------------------


def test_local_store_put_get_roundtrip_inline():
    st = LocalStore(capacity_bytes=64 << 20)
    obj = {"theta": np.arange(100_000, dtype=np.float32), "gen": 3}
    ref = st.put(obj)
    assert isinstance(ref, ObjectRef) and ref.size > 0
    found, back = st.get(ref.digest)
    assert found
    assert back["gen"] == 3
    assert np.array_equal(back["theta"], obj["theta"])
    # content-addressed dedup
    ref2 = st.put({"theta": obj["theta"], "gen": 3})
    assert ref2.digest == ref.digest
    assert st.stats()["put_dedup_hits"] == 1


def test_local_store_spill_and_reload(tmp_path):
    """Capacity pressure spills LRU entries to disk; gets transparently
    reload them (the spilled-roundtrip acceptance case)."""
    st = LocalStore(capacity_bytes=1 << 20, root=str(tmp_path))
    refs = [st.put(np.full(100_000, i, np.float32)) for i in range(8)]
    stats = st.stats()
    assert stats["evictions"] > 0 and stats["spills"] > 0
    assert stats["ram_bytes"] <= 1 << 20
    for i, ref in enumerate(refs):
        found, back = st.get(ref.digest)
        assert found, i
        assert back[0] == i
    assert st.stats()["disk_hits"] > 0


def test_local_store_refs_and_pins(tmp_path):
    """Pinned entries are unevictable; ref-held entries survive via
    spill; released entries can be dropped entirely."""
    st = LocalStore(capacity_bytes=1 << 20, root=str(tmp_path))
    pinned = st.put(np.zeros(100_000, np.float32))
    assert st.get_bytes(pinned.digest, pin=True) is not None
    held = st.put(np.ones(100_000, np.float32), refs=1)
    # flood to force eviction pressure
    for i in range(8):
        st.put(np.full(100_000, 2.0 + i, np.float32))
    assert pinned.digest in st.ram_digests()  # pin held it in RAM
    found, back = st.get(held.digest)  # ref'd: spilled, not lost
    assert found and back[0] == 1.0
    st.unpin(pinned.digest)
    st.release(held.digest)
    for i in range(8):
        st.put(np.full(100_000, 50.0 + i, np.float32))
    assert pinned.digest not in st.ram_digests()  # unpinned -> evictable


def test_local_store_memory_only_keeps_refs():
    """Without a disk tier, ref-held entries must never be evicted (no
    spill target exists)."""
    st = LocalStore(capacity_bytes=1 << 20, root=None)
    held = st.put(np.ones(100_000, np.float32), refs=1)
    for i in range(8):
        st.put(np.full(100_000, float(i), np.float32))
    found, back = st.get(held.digest)
    assert found and back[0] == 1.0


# ---------------------------------------------------------------------------
# wire plane
# ---------------------------------------------------------------------------


@pytest.fixture
def wire(tmp_path):
    server_store = LocalStore(capacity_bytes=64 << 20)
    server = StoreServer(server_store, "127.0.0.1")
    client_store = LocalStore(capacity_bytes=64 << 20,
                              root=str(tmp_path / "client"))
    client = StoreClient(client_store)
    yield server_store, server, client
    client.close()
    server.close()


def test_wire_chunked_get_put_and_miss(wire):
    server_store, server, client = wire
    big = unique_array(4.0)  # 4 MB -> several STORE_CHUNK frames
    ref = server_store.put(big, refs=1, owner=server.addr)
    got = client.resolve(ref)
    assert np.array_equal(got, big)
    assert client.resolve(ref) is got  # per-process resolution cache
    stats = server.stats()
    assert stats["gets"] == 1
    assert stats["bytes_served"] >= big.nbytes
    # chunked put (client -> server)
    blob = serialization.dumps(unique_array(2.0))
    pref = client.push(blob, server.addr)
    found, back = server_store.get(pref.digest)
    assert found and isinstance(back, np.ndarray)
    assert server.stats()["puts"] == 1
    # miss: an unknown digest fails the resolve, catchably
    from fiber_tpu.store import StoreFetchError

    bogus = ObjectRef("0" * 64, 10, server.addr)
    with pytest.raises(StoreFetchError):
        client.fetch_bytes(bogus)


def test_wire_put_rejects_digest_mismatch(wire):
    _server_store, server, client = wire
    data = serialization.dumps(np.arange(100_000))
    lying_digest = digest_of(data + b"x")
    from fiber_tpu.store.plane import STORE_CHUNK
    from fiber_tpu import serialization as s
    from fiber_tpu.transport import Endpoint

    ep = Endpoint("req").connect(server.addr)
    try:
        nchunks = -(-len(data) // STORE_CHUNK)
        ep.send(s.dumps(("put", lying_digest, len(data), nchunks)))
        for off in range(0, len(data), STORE_CHUNK):
            ep.send(bytes(data[off:off + STORE_CHUNK]))
        reply = s.loads(ep.recv(timeout=30.0))
        assert reply[0] == "err" and "digest" in reply[1]
    finally:
        ep.close()
    assert client.stats()["fetch_failures"] == 0  # unrelated client ok


# ---------------------------------------------------------------------------
# pool integration (the tentpole acceptance tests)
# ---------------------------------------------------------------------------


def test_pool_broadcast_dedup_once_per_host():
    """Acceptance: Pool.map over >= 32 tasks sharing an 8 MB arg moves
    the payload over the wire ONCE for the whole (single-host) worker
    set — proven by the store server's app counters AND the transport's
    exact framing-boundary byte counters (a second transfer would land
    ~2x the payload on the wire) — and every task still computes on the
    real array."""
    arr = unique_array(8.0)
    with fiber_tpu.Pool(2) as pool:
        before = pool.store_stats()
        assert before["enabled"]
        out = pool.starmap(targets.arr_sum_plus,
                           [(arr, i) for i in range(40)], chunksize=2)
        after = pool.store_stats()
    want = float(arr.sum())
    assert [round(v - want) for v in out] == list(range(40))
    assert after["gets"] - before.get("gets", 0) == 1
    served = after["bytes_served"] - before.get("bytes_served", 0)
    assert served >= arr.nbytes
    # Exact wire volume (Endpoint.bytes_tx at the framing boundary):
    # one 8 MB transfer plus small control replies — strictly under the
    # two-transfer mark. Server-side app counters alone couldn't see a
    # hypothetical duplicate send that never reached self._bump.
    wire_tx = after["wire_bytes_tx"] - before.get("wire_bytes_tx", 0)
    assert arr.nbytes <= wire_tx < 2 * arr.nbytes
    assert after["wire_frames_tx"] > before.get("wire_frames_tx", 0)
    assert after["inline_fallbacks"] == 0


def test_pool_map_over_tuples_encodes_elements():
    """Plain map (not starmap) over (big, i) tuples still dedups the
    big element: the encoder looks one tuple level deep."""
    arr = unique_array(4.0)
    with fiber_tpu.Pool(2) as pool:
        before = pool.store_stats()
        out = pool.map(targets.arr_item,
                       [(arr, i) for i in range(32)], chunksize=2)
        after = pool.store_stats()
    want = float(arr.sum())
    assert [round(v - want) for v in out] == list(range(32))
    assert after["gets"] - before.get("gets", 0) == 1


def test_pool_put_object_explicit_broadcast():
    arr = unique_array(2.0)
    with fiber_tpu.Pool(2) as pool:
        ref = pool.put_object(arr)
        assert isinstance(ref, ObjectRef)
        out = pool.starmap(targets.arr_sum_plus,
                           [(ref, i) for i in range(8)])
    want = float(arr.sum())
    assert [round(v - want) for v in out] == list(range(8))


def test_pool_big_results_travel_by_reference():
    """Results above the threshold come back as refs the master
    resolves from its own store — values intact, server put counters
    prove the path was exercised."""
    with fiber_tpu.Pool(2) as pool:
        out = pool.map(targets.big_result, [2 << 20] * 6, chunksize=1)
        stats = pool.store_stats()
    for arr in out:
        assert isinstance(arr, np.ndarray)
        assert arr.shape == ((2 << 20) // 8,)
        assert arr[-1] == arr.shape[0] - 1
    assert stats["puts"] >= 1
    assert stats["bytes_received"] >= 2 << 20


def test_pool_store_disabled_ships_inline():
    fiber_tpu.init(store_enabled=False)
    try:
        arr = unique_array(1.0)
        with fiber_tpu.Pool(2) as pool:
            assert not pool.store_stats()["enabled"]
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(8)])
        want = float(arr.sum())
        assert [round(v - want) for v in out] == list(range(8))
    finally:
        fiber_tpu.init()


def test_pool_chaos_fetch_failure_degrades_to_inline(tmp_path):
    """Acceptance: with a seeded fetch-failure injection the affected
    chunk is re-sent inline (storemiss path) — the map loses NOTHING
    and the fallback counter records the degradation."""
    chaos.install(chaos.ChaosPlan(seed=SEED,
                                  token_dir=str(tmp_path / "tokens"),
                                  fail_store_fetch=1))
    try:
        arr = unique_array(4.0)
        with fiber_tpu.Pool(2) as pool:
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(40)],
                               chunksize=2)
            fallbacks = pool.store_stats()["inline_fallbacks"]
        want = float(arr.sum())
        assert [round(v - want) for v in out] == list(range(40))
        assert fallbacks >= 1
        assert chaos.active().spent("fail-store_fetch") == 1
    finally:
        chaos.uninstall()
        fiber_tpu.init()


# ---------------------------------------------------------------------------
# host agent cache tier
# ---------------------------------------------------------------------------


def test_host_agent_store_ops(tmp_path):
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, staging_root=str(tmp_path))
    try:
        blob = serialization.dumps(np.arange(200_000, dtype=np.float32))
        digest = digest_of(blob)
        assert not agent._dispatch("store_has", digest)
        assert agent._dispatch("store_put", digest, blob) == len(blob)
        assert agent._dispatch("store_has", digest)
        assert bytes(agent._dispatch("store_get", digest)) == blob
        stats = agent._dispatch("store_stats")
        assert stats["objects"] == 1 and stats["bytes"] == len(blob)
        # digest is used as a file name: reject anything non-sha256
        with pytest.raises(ValueError):
            agent._dispatch("store_put", "../evil", blob)
        with pytest.raises(ValueError):
            agent._dispatch("store_get", "ABC")
        # payloads must match their claimed content address
        with pytest.raises(ValueError):
            agent._dispatch("store_put", digest, blob + b"x")
        assert agent._dispatch("store_delete", digest)
        assert not agent._dispatch("store_has", digest)
    finally:
        agent.stop()


# ---------------------------------------------------------------------------
# soaks (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_soak_generations_dedup_and_eviction():
    """ES-shaped soak: 6 'generations', each broadcasting fresh 4 MB
    params over 24 tasks. Every generation costs exactly one wire
    transfer; old generations age out of the worker RAM tier without
    correctness loss."""
    with fiber_tpu.Pool(2) as pool:
        before = pool.store_stats()
        for gen in range(6):
            arr = unique_array(4.0)
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(24)],
                               chunksize=2)
            want = float(arr.sum())
            assert [round(v - want) for v in out] == list(range(24))
        after = pool.store_stats()
    assert after["gets"] - before.get("gets", 0) == 6
    assert after["inline_fallbacks"] == 0


@pytest.mark.slow
def test_soak_slow_store_does_not_lose_tasks(tmp_path):
    """Degraded-store latency (every get served late) slows fetches but
    never fails tasks — and must not trip the health plane."""
    chaos.install(chaos.ChaosPlan(seed=SEED,
                                  token_dir=str(tmp_path / "tokens"),
                                  slow_store_every=1, slow_store_s=0.5))
    try:
        arr = unique_array(4.0)
        with fiber_tpu.Pool(2) as pool:
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(24)],
                               chunksize=2)
        want = float(arr.sum())
        assert [round(v - want) for v in out] == list(range(24))
    finally:
        chaos.uninstall()
        fiber_tpu.init()
