"""Device store tier (docs/objectstore.md "Device tier"): HBM-budgeted
LRU of digest -> replicated device pytrees, honest ``ici`` transfer
accounting, the ``hbm_fill`` closed-loop demotion, and the resolution /
pool-broadcast integration — all on the 8-device CPU mesh."""

import pickle
import time

import numpy as np
import pytest

import fiber_tpu
from fiber_tpu import store as storemod
from fiber_tpu import telemetry
from fiber_tpu.store.core import digest_of
from fiber_tpu.store.device_tier import DeviceTier
from fiber_tpu.telemetry.device import DEVICE
from fiber_tpu.telemetry.flightrec import FLIGHT
from tests import targets


def _mb(n: float, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(int(n * (1 << 20) / 4)).astype(np.float32)


def _dig(tag) -> str:
    return digest_of(f"test-device-tier-{tag}".encode())


def _ici_bytes() -> int:
    site = DEVICE.snapshot()["transfers"].get("ici") or {}
    return int(site.get("bytes", 0))


@pytest.fixture(autouse=True)
def _fresh_state():
    fiber_tpu.init()
    storemod.reset()
    yield
    storemod.reset()
    fiber_tpu.init()


# ---------------------------------------------------------------------------
# LRU / pin / eviction discipline
# ---------------------------------------------------------------------------


def test_put_get_and_lru_eviction():
    tier = DeviceTier(capacity_bytes=int(2.5 * (1 << 20)))
    a, b, c = _mb(1, 1), _mb(1, 2), _mb(1, 3)
    tier.put(_dig("a"), a)
    tier.put(_dig("b"), b)
    assert tier.get(_dig("b")) is not None  # refresh: a becomes LRU victim
    tier.put(_dig("c"), c)
    st = tier.stats()
    assert st["evictions"] == 1 and st["entries"] == 2
    assert tier.get(_dig("a")) is None  # evicted; host tiers keep the bytes
    assert tier.contains(_dig("b")) and tier.contains(_dig("c"))
    np.testing.assert_array_equal(np.asarray(tier.get(_dig("c"))), c)
    st = tier.stats()
    assert st["hits"] == 2 and st["misses"] == 1


def test_pins_block_eviction_refs_do_not():
    tier = DeviceTier(capacity_bytes=int(2.5 * (1 << 20)))
    tier.put(_dig("a"), _mb(1, 1), refs=5)
    assert tier.get(_dig("a"), pin=True) is not None  # hard pin
    tier.put(_dig("b"), _mb(1, 2), refs=5)
    tier.put(_dig("c"), _mb(1, 3))
    # a is pinned: the LRU walk skips it and drops b (refs are lifecycle
    # hints only — the host tiers still hold every byte).
    assert tier.contains(_dig("a"))
    assert not tier.contains(_dig("b"))
    tier.unpin(_dig("a"))
    tier.put(_dig("d"), _mb(1, 4))
    assert not tier.contains(_dig("a"))  # unpinned: refs did not save it
    assert tier.contains(_dig("c")) and tier.contains(_dig("d"))


def test_delete_and_ref_lifecycle():
    tier = DeviceTier()
    tier.put(_dig("del"), _mb(0.25, 5), refs=1)
    tier.add_ref(_dig("del"))
    tier.release(_dig("del"), 2)
    tier.delete(_dig("del"))
    assert not tier.contains(_dig("del"))
    assert tier.stats()["bytes"] == 0


# ---------------------------------------------------------------------------
# sharding metadata + accounting
# ---------------------------------------------------------------------------


def test_sharding_metadata_roundtrip():
    tier = DeviceTier()
    arr = _mb(1, 7)
    dev = tier.put(_dig("m"), arr)
    assert dev.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(dev), arr)
    (leaf,) = tier.meta(_dig("m"))
    assert leaf["shape"] == arr.shape
    assert leaf["dtype"] == "float32"
    assert leaf["nbytes"] == arr.nbytes
    assert leaf["replicated"] is True
    assert "PartitionSpec" in leaf["sharding"]
    assert tier.meta(_dig("nope")) is None


def test_put_accounts_ici_ingest_plus_fanout():
    import jax

    tier = DeviceTier()
    arr = _mb(1, 9)
    before = _ici_bytes()
    tier.put(_dig("acct"), arr)
    # One ingest H2D + (n_dev - 1) mesh fan-out, all under site=ici.
    assert _ici_bytes() - before == arr.nbytes * len(jax.devices())
    before2 = _ici_bytes()
    assert tier.put(_dig("acct"), arr) is not None  # dedup
    assert _ici_bytes() == before2  # repeat put: zero new movement
    assert tier.stats()["put_dedup_hits"] == 1


def test_registry_twins_move():
    puts = telemetry.counter("store_device_puts")
    hits = telemetry.counter("store_device_hits")
    evics = telemetry.counter("store_device_evictions")
    p0, h0 = puts.value(), hits.value()
    e0 = evics.value(cause="delete")
    tier = DeviceTier()
    tier.put(_dig("reg"), _mb(0.25, 11))
    assert tier.get(_dig("reg")) is not None
    tier.delete(_dig("reg"))
    assert puts.value() == p0 + 1
    assert hits.value() == h0 + 1
    assert evics.value(cause="delete") == e0 + 1
    assert telemetry.gauge("store_device_bytes").value() == 0.0


# ---------------------------------------------------------------------------
# closed-loop demotion (hbm_fill remediation)
# ---------------------------------------------------------------------------


def test_demote_promote_flight_evented():
    fiber_tpu.init(flightrec_enabled=True)
    tier = DeviceTier()
    arr = _mb(1, 13)
    tier.put(_dig("dem"), arr)
    freed = tier.demote("hbm_fill")
    assert freed == arr.nbytes and tier.demoted
    assert tier.get(_dig("dem")) is None  # falls through to host tiers
    assert tier.put(_dig("dem2"), arr) is arr  # passthrough, not cached
    tier.promote()
    assert not tier.demoted
    assert tier.put(_dig("dem"), arr) is not arr  # admitting again
    acts = [e for e in FLIGHT.snapshot()
            if e["plane"] == "store" and e["kind"] == "remediate"]
    assert [e["action"] for e in acts[-2:]] == [
        "demote_device_tier", "promote_device_tier"]
    assert acts[-2]["rule"] == "hbm_fill"
    assert acts[-2]["bytes"] == arr.nbytes


def _sample(**kw):
    base = {"wall": time.time(), "mono": time.monotonic(),
            "tasks_per_s": 0.0, "inflight": 0.0, "queue_depth": 0.0,
            "heartbeat_age_s": 0.0, "tx_queue_bytes": 0.0}
    base.update(kw)
    return base


def test_watchdog_hbm_fill_demotes_and_repromotes(monkeypatch):
    """The drill: breach edge demotes the tier (flight-evented), device
    maps keep completing with ZERO lost tasks while demoted, clear edge
    re-promotes."""
    from fiber_tpu import config
    from fiber_tpu.meta import meta
    from fiber_tpu.telemetry import monitor as monitormod

    # The drill drives a watchdog of its own by hand. The process's
    # sampler thread is kept off: where the pool below outlives a tick
    # (a loaded machine), the global watchdog sees the patched 95%
    # too, raises the rule itself and becomes the only one allowed to
    # revert it.
    fiber_tpu.init(flightrec_enabled=True, monitor_enabled=False)
    tier = storemod.device_store_tier()
    assert tier is not None
    arr = _mb(0.25, 17)
    tier.put(_dig("wd"), arr)
    dog = monitormod.AnomalyWatchdog()
    dog.configure(config.get())

    monkeypatch.setattr(monitormod, "_hbm_usage",
                        lambda: (95 << 20, 100 << 20))
    dog.observe(_sample())
    assert "hbm_fill" in dog.snapshot()["active"]
    assert tier.demoted and not tier.contains(_dig("wd"))

    # Zero lost tasks while demoted: the broadcast args pass through
    # unbatched (host bytes intact) and the map completes exactly.
    fn = meta(device=True)(_dev_sum_plus)
    items = [(arr, np.float32(i)) for i in range(8)]
    with fiber_tpu.Pool(2) as pool:
        out = pool.starmap(fn, items)
    want = float(arr.sum())
    assert [round(float(v) - want) for v in out] == list(range(8))
    assert tier.stats()["entries"] == 0  # demoted tier admitted nothing

    monkeypatch.setattr(monitormod, "_hbm_usage",
                        lambda: (10 << 20, 100 << 20))
    dog.observe(_sample())
    assert "hbm_fill" not in dog.snapshot()["active"]
    assert not tier.demoted
    tier.put(_dig("wd"), arr)
    assert tier.contains(_dig("wd"))  # re-promoted tier admits again
    acts = [e.get("action") for e in FLIGHT.snapshot()
            if e["plane"] == "store" and e["kind"] == "remediate"]
    assert "demote_device_tier" in acts and "promote_device_tier" in acts


# ---------------------------------------------------------------------------
# accessor semantics
# ---------------------------------------------------------------------------


def test_accessor_live_knob_preserves_contents():
    tier = storemod.device_store_tier()
    assert tier is not None
    arr = _mb(0.25, 19)
    tier.put(_dig("knob"), arr)
    fiber_tpu.init(store_device_enabled=False)
    assert storemod.device_store_tier() is None  # withheld, not torn down
    fiber_tpu.init(store_device_enabled=True)
    again = storemod.device_store_tier()
    assert again is tier and again.contains(_dig("knob"))


def test_accessor_survives_submodule_import():
    # Regression: a package attr named like the submodule would be
    # rebound to the module object by the import machinery.
    import fiber_tpu.store.device_tier  # noqa: F401

    assert callable(storemod.device_store_tier)


# ---------------------------------------------------------------------------
# resolution integration: one host = one fetch = one replication
# ---------------------------------------------------------------------------


def test_resolve_device_shares_one_replication_per_host():
    from fiber_tpu import serialization
    from fiber_tpu.store import LocalStore
    from fiber_tpu.store.plane import StoreClient, StoreServer

    arr = _mb(1, 19)
    st = LocalStore(capacity_bytes=64 << 20)
    server = StoreServer(st, "127.0.0.1")
    try:
        ref = st.put_bytes(serialization.dumps(arr))
        wire_ref = type(ref)(ref.digest, ref.size, server.addr, True)
        assert wire_ref.device_hint is True
        before = _ici_bytes()
        c1 = StoreClient(LocalStore(capacity_bytes=64 << 20))
        out1 = c1.resolve(wire_ref, device=True)
        served_once = server.stats()["bytes_served"]
        moved_once = _ici_bytes() - before
        assert served_once >= arr.nbytes and moved_once > 0
        # A second resolver in the same process (another pool worker on
        # this host): no second wire fetch, no second H2D/fan-out — the
        # device tier hands back the SAME replicated pytree.
        c2 = StoreClient(LocalStore(capacity_bytes=64 << 20))
        out2 = c2.resolve(wire_ref, device=True)
        assert server.stats()["bytes_served"] == served_once
        assert _ici_bytes() - before == moved_once
        assert out2 is out1
        np.testing.assert_array_equal(np.asarray(out2), arr)
        c1.close()
        c2.close()
    finally:
        server.close()


def test_resolve_host_cache_never_holds_device_forms():
    """Regression (review r12 #1): the client's host object cache must
    keep the HOST form — a device=True resolution hands out the tier's
    replicated pytree, but a later device=False resolve of the same
    digest returns host arrays, and after an hbm_fill demotion nothing
    outside the tier pins the replicated jax.Arrays (the demote would
    otherwise never free the HBM it exists to shed)."""
    import jax

    from fiber_tpu import serialization
    from fiber_tpu.store import LocalStore
    from fiber_tpu.store.plane import StoreClient, StoreServer

    arr = _mb(1, 37)
    st = LocalStore(capacity_bytes=64 << 20)
    server = StoreServer(st, "127.0.0.1")
    try:
        ref = st.put_bytes(serialization.dumps(arr))
        wire_ref = type(ref)(ref.digest, ref.size, server.addr, True)
        client = StoreClient(LocalStore(capacity_bytes=64 << 20))
        dev = client.resolve(wire_ref, device=True)
        assert isinstance(dev, jax.Array)
        # Host-plane caller of the same digest: host array, not the
        # device form the tier cached.
        host = client.resolve(wire_ref, device=False)
        assert isinstance(host, np.ndarray)
        np.testing.assert_array_equal(host, arr)
        # The obj cache itself holds no device arrays to pin HBM past
        # a demotion.
        assert all(not isinstance(v, jax.Array)
                   for v in client._objs.values())
        tier = storemod.device_store_tier()
        tier.demote()
        try:
            # Demoted: both planes degrade to the host form, zero wire.
            served = server.stats()["bytes_served"]
            out = client.resolve(wire_ref, device=True)
            assert isinstance(out, np.ndarray)
            assert server.stats()["bytes_served"] == served
        finally:
            tier.promote()
        client.close()
    finally:
        server.close()


def test_objectref_device_hint_pickles_and_defaults():
    from fiber_tpu.store.core import ObjectRef

    hinted = ObjectRef("d" * 8, 128, "1.2.3.4:1", True)
    assert pickle.loads(pickle.dumps(hinted)).device_hint is True
    legacy = ObjectRef("d" * 8, 128, "1.2.3.4:1")
    assert legacy.device_hint is False
    assert pickle.loads(pickle.dumps(legacy)).device_hint is False


def test_device_hint_marks_only_shared_broadcast_refs():
    """Regression (review r12 #2): on a device-destined map only refs
    SHARED across items (the broadcast idiom) carry device_hint —
    per-item payloads must not be mesh-replicated n_dev-wide or churn
    the tier's LRU out of the actual broadcast params."""
    from fiber_tpu.store.core import ObjectRef

    shared = _mb(1, 41)
    uniq = [_mb(1, 42 + i) for i in range(3)]
    with fiber_tpu.Pool(2) as pool:
        digs = []
        enc = pool._encode_items([(shared, u) for u in uniq], digs,
                                 None, device_hint=True)
    assert all(isinstance(e, ObjectRef) for it in enc for e in it)
    shared_refs = {it[0] for it in enc}
    assert len(shared_refs) == 1  # memo: one ref instance for all items
    assert next(iter(shared_refs)).device_hint is True
    assert all(it[1].device_hint is False for it in enc)


def test_chaos_store_fetch_fails_through_device_path(tmp_path):
    """Acceptance: a chaos-injected wire failure surfaces as the same
    StoreFetchError the storemiss/inline-resend path keys on — the
    device tier neither masks it nor caches a phantom entry — and the
    retry resolves and fills the tier."""
    from fiber_tpu import serialization
    from fiber_tpu.store import LocalStore
    from fiber_tpu.store.plane import (StoreClient, StoreFetchError,
                                       StoreServer)
    from fiber_tpu.testing import chaos

    arr = _mb(1, 29)
    st = LocalStore(capacity_bytes=64 << 20)
    server = StoreServer(st, "127.0.0.1")
    chaos.install(chaos.ChaosPlan(seed=3, token_dir=str(tmp_path),
                                  fail_store_fetch=1))
    try:
        ref = st.put_bytes(serialization.dumps(arr))
        wire_ref = type(ref)(ref.digest, ref.size, server.addr, True)
        client = StoreClient(LocalStore(capacity_bytes=64 << 20))
        with pytest.raises(StoreFetchError):
            client.resolve(wire_ref, device=True)
        tier = storemod.device_store_tier()
        assert not tier.contains(ref.digest)
        out = client.resolve(wire_ref, device=True)
        np.testing.assert_array_equal(np.asarray(out), arr)
        assert tier.contains(ref.digest)
        client.close()
    finally:
        chaos.uninstall()
        server.close()


@pytest.mark.slow
def test_pool_chaos_fetch_degrades_to_inline_with_device_hint(tmp_path):
    """Pool-level drill: @meta(tpu=1) broadcast refs carry device_hint,
    workers resolve them device-side, and a chaos-injected fetch
    failure still degrades through storemiss to the inline resend — the
    map loses NOTHING."""
    from fiber_tpu.testing import chaos

    chaos.install(chaos.ChaosPlan(seed=7, token_dir=str(tmp_path),
                                  fail_store_fetch=1))
    try:
        arr = _mb(4.0, 31)
        with fiber_tpu.Pool(2) as pool:
            out = pool.starmap(targets.arr_sum_plus_accel,
                               [(arr, i) for i in range(12)],
                               chunksize=2)
            fallbacks = pool.store_stats()["inline_fallbacks"]
        want = float(arr.sum())
        assert [round(v - want) for v in out] == list(range(12))
        assert fallbacks >= 1
        assert chaos.active().spent("fail-store_fetch") == 1
    finally:
        chaos.uninstall()
        fiber_tpu.init()


# ---------------------------------------------------------------------------
# pool broadcast split (collective broadcast through the tier)
# ---------------------------------------------------------------------------


def _dev_sum_plus(arr, x):
    return arr.sum() + x


def test_pool_device_broadcast_split_and_dedup():
    """The ES idiom [(params, s) for s in seeds] on a device map: the
    shared param is lifted through the tier ONCE; the repeat generation
    is digest-dedup'd with zero new ici movement."""
    from fiber_tpu.meta import meta

    arr = _mb(0.25, 23)  # above the 64KB broadcast floor
    fn = meta(device=True)(_dev_sum_plus)
    items = [(arr, np.float32(i)) for i in range(8)]
    with fiber_tpu.Pool(2) as pool:
        out1 = pool.starmap(fn, items)
        tier = storemod.device_store_tier()
        st1 = tier.stats()
        before = _ici_bytes()
        out2 = pool.starmap(fn, items)
        st2 = tier.stats()
    want = float(arr.sum())
    for out in (out1, out2):
        assert [round(float(v) - want) for v in out] == list(range(8))
    assert st1["puts"] == 1
    assert st2["put_dedup_hits"] >= 1
    assert _ici_bytes() == before  # repeat generation: zero new movement


def test_pool_device_broadcast_below_floor_untouched():
    """Tiny shared args are not worth content-addressing: below the
    floor the split must leave the map alone."""
    from fiber_tpu.meta import meta

    arr = np.ones(16, dtype=np.float32)  # far below the 64KB floor
    fn = meta(device=True)(_dev_sum_plus)
    with fiber_tpu.Pool(2) as pool:
        out = pool.starmap(fn, [(arr, np.float32(i)) for i in range(8)])
    assert [round(float(v) - 16.0) for v in out] == list(range(8))
    tier = storemod.device_store_tier()
    assert tier is None or tier.stats()["puts"] == 0
