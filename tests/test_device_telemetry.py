"""Device telemetry plane: transfer accounting, compile observability +
recompile-storm watchdog, device gauges, unified host+device timeline,
live MFU, and the `fiber-tpu devices` / `top` surfaces
(docs/observability.md "Device telemetry")."""

import gzip
import json
import os
import threading
import time

import numpy as np
import pytest

import fiber_tpu
from fiber_tpu import config, telemetry
from fiber_tpu.telemetry import monitor as monitormod
from fiber_tpu.telemetry import tracing
from fiber_tpu.telemetry.device import DEVICE
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.telemetry.monitor import WATCHDOG, AnomalyWatchdog
from tests import targets


@pytest.fixture(autouse=True)
def _device_isolation():
    """Each test starts with clean device-plane state and ends with
    config overrides dropped (init re-syncs the plane)."""
    DEVICE.clear()
    WATCHDOG.clear()
    FLIGHT.clear()
    yield
    fiber_tpu.init()
    DEVICE.clear()
    WATCHDOG.clear()


def _sample(**kw):
    base = {"wall": time.time(), "mono": time.monotonic(),
            "tasks_per_s": 0.0, "inflight": 0.0, "queue_depth": 0.0,
            "heartbeat_age_s": 0.0, "tx_queue_bytes": 0.0}
    base.update(kw)
    return base


# ---------------------------------------------------------------------------
# transfer accounting
# ---------------------------------------------------------------------------


def test_transfer_records_metrics_flight_and_span():
    fiber_tpu.init()
    before = telemetry.histogram("device_transfer_seconds").count(
        site="unit")
    with tracing.trace_context("t-dev", None):
        with DEVICE.transfer("unit", 4096):
            time.sleep(0.005)
    snap = DEVICE.snapshot()
    agg = snap["transfers"]["unit"]
    assert agg["count"] == 1 and agg["bytes"] == 4096
    assert agg["seconds"] >= 0.004
    assert snap["transfer_bytes"] == 4096
    assert telemetry.histogram("device_transfer_seconds").count(
        site="unit") == before + 1
    assert telemetry.histogram("device_transfer_bytes").sum(
        site="unit") >= 4096
    # flight event on the device plane
    ev = [e for e in FLIGHT.snapshot()
          if e["plane"] == "device" and e["kind"] == "transfer"]
    assert ev and ev[-1]["site"] == "unit" and ev[-1]["bytes"] == 4096
    # span joined the ambient trace (explain's fallback source)
    sp = [s for s in tracing.SPANS.snapshot()
          if s["name"] == "device.transfer"]
    assert sp and sp[-1]["trace"] == "t-dev" and sp[-1]["bytes"] == 4096


def test_transfer_off_is_noop():
    fiber_tpu.init(device_telemetry_enabled=False)
    assert not DEVICE.enabled
    with DEVICE.transfer("unit", 100):
        pass
    DEVICE.note_compile("fp")
    assert DEVICE.snapshot()["transfers"] == {}
    assert DEVICE.snapshot()["compiles"] == 0
    # the telemetry master switch kills the plane too
    fiber_tpu.init(telemetry_enabled=False)
    assert not DEVICE.enabled


def test_transfer_counters_through_real_map_with_store_broadcast():
    """The acceptance path: a broadcast arg big enough to travel by
    reference is resolved once per worker through the store — that
    resolution is a host->device boundary, accounted per worker and
    shipped to the master on the result stream (("dev", ...) frames),
    where Pool.device_stats() renders it beside the master's own and
    the backend's per-host snapshots."""
    fiber_tpu.init(store_inline_max=64 * 1024)
    arr = np.ones((200_000,), dtype=np.float64)  # 1.6MB > inline max
    with fiber_tpu.Pool(2) as pool:
        out = pool.starmap(targets.arr_sum_plus,
                           [(arr, i) for i in range(8)], chunksize=1)
        assert out == [float(arr.sum()) + i for i in range(8)]
        stats = pool.device_stats()
    assert set(stats) >= {"master", "workers", "hosts"}
    assert stats["hosts"].keys() == {"local"}
    assert stats["workers"], "no worker shipped device frames"
    for snap in stats["workers"].values():
        agg = snap["transfers"]["store_resolve"]
        assert agg["bytes"] >= arr.nbytes
        assert agg["seconds"] > 0
        assert agg["count"] >= 1
        # null-safe on CPU: HBM is honestly None, never zero/raise
        assert snap["hbm"]["bytes_in_use"] is None
        assert snap["hbm"]["bytes_limit"] is None
        assert snap["compiles"] >= 0


def test_checkpoint_load_batches_device_put_through_accounting(tmp_path):
    """Satellite: load(device_put=True) transfers the whole leaf list
    as ONE batched tree transfer, routed through the `checkpoint`
    transfer site."""
    import jax

    from fiber_tpu.utils import checkpoint

    fiber_tpu.init()
    tree = {"w": np.arange(1024.0), "b": [np.ones(8), np.zeros(4)]}
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, tree)
    restored = checkpoint.load(path, device_put=True)
    assert isinstance(restored["w"], jax.Array)
    assert np.allclose(np.asarray(restored["w"]), tree["w"])
    assert np.allclose(np.asarray(restored["b"][0]), tree["b"][0])
    agg = DEVICE.snapshot()["transfers"]["checkpoint"]
    assert agg["count"] == 1  # one batched transfer, not one per leaf
    expected = sum(leaf.nbytes
                   for leaf in (tree["w"], tree["b"][0], tree["b"][1]))
    assert agg["bytes"] == expected


def test_dmap_transfer_accounted_and_fingerprinted():
    from fiber_tpu.parallel import device_map

    fiber_tpu.init()

    def triple(x):
        return x * 3

    out = device_map(triple, np.arange(16.0))
    assert float(out[5]) == 15.0
    snap = DEVICE.snapshot()
    assert snap["transfers"]["dmap"]["count"] >= 1
    assert snap["transfers"]["dmap"]["bytes"] >= 16 * 8
    assert any("triple" in fp for fp in snap["compile_fingerprints"])
    # cached second call: no new fingerprint note
    before = snap["compiles"]
    device_map(triple, np.arange(16.0))
    ours = {fp: n for fp, n in
            DEVICE.snapshot()["compile_fingerprints"].items()
            if "triple" in fp}
    assert sum(ours.values()) == 1, \
        f"cache hit re-fingerprinted: {ours} (compiles {before})"


# ---------------------------------------------------------------------------
# compile observability + recompile storm
# ---------------------------------------------------------------------------


def test_monitoring_listeners_install_directly_and_defer_without_jax(
        monkeypatch):
    """The compile listeners register with jax.monitoring itself (the
    installed jax has the surface; no shim). A process that never
    imported jax defers — telemetry must not pay the import."""
    import sys

    import jax

    from fiber_tpu.telemetry.device import DeviceTelemetry

    fresh = DeviceTelemetry()
    with monkeypatch.context() as m:
        m.delitem(sys.modules, "jax")
        assert fresh.install_listeners() is False   # deferred
    assert fresh.install_listeners() is True
    assert fresh.install_listeners() is True        # idempotent
    # a real compilation reaches the installed duration listener
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert fresh.snapshot()["compile_seconds"] > 0
    # and a compile-accounting call works with or without listeners
    fresh.note_compile("fp")
    assert fresh.snapshot()["compile_fingerprints"] == {"fp": 1}


def test_jax_event_listener_counts_compiles_not_cache_hits():
    fiber_tpu.init()
    DEVICE._on_jax_event("/jax/compilation_cache/tasks_using_cache")
    DEVICE._on_jax_event("/jax/compilation_cache/cache_hits")
    snap = DEVICE.snapshot()
    assert snap["compiles"] == 0
    assert snap["compile_cache_hits"] == 1
    # A miss is a compilation — but the event names no program, so a
    # burst of them (every program misses a cold persistent cache) must
    # not read as ONE function recompiling.
    for _ in range(DEVICE.storm_count + 1):
        DEVICE._on_jax_event("/jax/compilation_cache/cache_misses")
    DEVICE._on_jax_duration("backend_compile", 0.25)
    DEVICE._on_jax_duration("/jax/unrelated/event", 9.0)
    # time SAVED by a cache hit is not time spent compiling
    DEVICE._on_jax_duration(
        "/jax/compilation_cache/compile_time_saved_sec", 5.0)
    snap = DEVICE.snapshot()
    assert snap["compiles"] == DEVICE.storm_count + 1
    assert snap["compile_seconds"] == pytest.approx(0.25)
    assert snap["recompile"]["storm"] is False


def test_recompile_storm_synthetic_trigger_and_watchdog_edge_clear():
    """Satellite: the same fingerprint compiling repeatedly inside the
    window is a storm; the watchdog raises `recompile_storm` ONCE
    (edge), keeps it active while the storm persists, and clears when
    the window drains."""
    fiber_tpu.init(anomaly_recompile_count=3,
                   anomaly_recompile_window_s=30.0)
    dog = AnomalyWatchdog()
    dog.configure(config.get())
    assert DEVICE.storm_count == 3
    DEVICE.note_compile("shape-churn")
    DEVICE.note_compile("shape-churn")
    assert DEVICE.recompile_state()["storm"] is False
    dog.observe(_sample())
    assert "recompile_storm" not in dog.snapshot()["active"]
    DEVICE.note_compile("shape-churn")
    state = DEVICE.recompile_state()
    assert state["storm"] is True and state["count"] == 3
    assert state["fingerprint"] == "shape-churn"
    dog.observe(_sample())
    snap = dog.snapshot()
    assert "recompile_storm" in snap["active"]
    assert snap["active"]["recompile_storm"]["count"] == 3
    total = snap["total"]
    dog.observe(_sample())          # same incident: no second event
    assert dog.snapshot()["total"] == total
    # flight + registry evidence
    kinds = {(e["plane"], e["kind"]) for e in FLIGHT.snapshot()}
    assert ("monitor", "recompile_storm") in kinds
    # the window drains -> clear edge
    DEVICE._recompiles.clear()
    dog.observe(_sample())
    assert "recompile_storm" not in dog.snapshot()["active"]
    kinds = [(e["kind"], e.get("rule")) for e in FLIGHT.snapshot()
             if e["plane"] == "monitor"]
    assert ("clear", "recompile_storm") in kinds


def test_hbm_fill_rule(monkeypatch):
    fiber_tpu.init(anomaly_hbm_fill_pct=0.9)
    dog = AnomalyWatchdog()
    dog.configure(config.get())
    monkeypatch.setattr(monitormod, "_hbm_usage",
                        lambda: (95 << 20, 100 << 20))
    dog.observe(_sample())
    assert "hbm_fill" in dog.snapshot()["active"]
    monkeypatch.setattr(monitormod, "_hbm_usage",
                        lambda: (10 << 20, 100 << 20))
    dog.observe(_sample())
    assert dog.snapshot()["active"] == {}
    # CPU posture: no limit -> the rule can never breach
    monkeypatch.setattr(monitormod, "_hbm_usage", lambda: (0, 0))
    dog.observe(_sample())
    assert "hbm_fill" not in dog.snapshot()["active"]


def test_device_gauges_ride_monitor_sampler():
    from fiber_tpu.telemetry.timeseries import TIMESERIES

    fiber_tpu.init(monitor_enabled=False)  # drive ticks by hand
    TIMESERIES.clear()
    try:
        TIMESERIES.add_probe(DEVICE.update_gauges)
        TIMESERIES.sample_once()
        series = TIMESERIES.snapshot()["series"]
        # device gauges are tracked series (CPU leaves them unset -> 0;
        # the honest None lives in device_snapshot)
        assert "hbm_bytes_in_use" in series
        assert "live_array_bytes" in series
    finally:
        TIMESERIES.clear()


# ---------------------------------------------------------------------------
# null-safe snapshots
# ---------------------------------------------------------------------------


def test_device_snapshot_null_safe_on_cpu():
    fiber_tpu.init()
    DEVICE.update_gauges()
    snap = DEVICE.snapshot()
    assert snap["hbm"] == {"bytes_in_use": None, "bytes_limit": None}
    assert snap["mfu"]["mfu"] is None
    # live arrays ARE countable on CPU jax (it's a process property)
    assert snap["live_arrays"]["count"] is None \
        or snap["live_arrays"]["count"] >= 0
    json.dumps(snap)  # picklable/JSON-able agent payload


def test_hbm_probe_survives_broken_memory_stats(monkeypatch):
    from fiber_tpu.telemetry import device as devmod

    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            raise RuntimeError("PJRT says no")

    monkeypatch.setattr(devmod, "_devices", lambda: [_Dev()])
    assert devmod._hbm_stats() == {"bytes_in_use": None,
                                   "bytes_limit": None}
    # and a device that DOES report stats surfaces them
    class _Good:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 10, "bytes_limit": 100}

    monkeypatch.setattr(devmod, "_devices", lambda: [_Good()])
    assert devmod._hbm_stats() == {"bytes_in_use": 10,
                                   "bytes_limit": 100}


# ---------------------------------------------------------------------------
# live MFU
# ---------------------------------------------------------------------------


def test_live_mfu_gauge_when_peak_resolves(monkeypatch):
    fiber_tpu.init()

    @fiber_tpu.meta(device=True, flops=1000.0)
    def sq(x):
        return x * x

    # no peak (CPU): the observation records None honestly
    with fiber_tpu.Pool(2) as pool:
        out = pool.map(sq, np.arange(8.0))
        assert [float(v) for v in out] == [x * x for x in range(8)]
    mfu = DEVICE.snapshot()["mfu"]
    assert mfu["mfu"] is None
    assert mfu["items"] == 8
    assert mfu["flops_per_sec"] > 0
    # a resolved peak (a device whose kind has a row in the table of
    # utils/flops.py) populates the gauge
    class V5e:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    from fiber_tpu.telemetry import device as devicemod

    monkeypatch.setattr(devicemod, "_devices", lambda: [V5e()])
    with fiber_tpu.Pool(2) as pool:
        pool.map(sq, np.arange(8.0))
    mfu = DEVICE.snapshot()["mfu"]
    assert mfu["mfu"] is not None and 0 < mfu["mfu"] < 1
    assert mfu["peak_row"] == "v5 lite:1.97e+14"
    assert telemetry.gauge("pool_map_mfu").value() == \
        pytest.approx(mfu["mfu"])
    kinds = {(e["plane"], e["kind"]) for e in FLIGHT.snapshot()}
    assert ("device", "mfu") in kinds


# ---------------------------------------------------------------------------
# unified host+device timeline
# ---------------------------------------------------------------------------


def _write_fake_xla_capture(root, shared=None) -> str:
    """A capture shaped like jax.profiler.trace output: Chrome trace
    JSON gzipped under plugins/profile/<run>/. ``shared`` is a span of
    the host's that the capture holds too, as ``tracing.span`` writes
    it: same name, the span id in ``args``, on the capture's own clock
    (here: 150 us after its first device op)."""
    run = os.path.join(str(root), "plugins", "profile", "run1")
    os.makedirs(run)
    doc = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 1,
         "ts": 100.0, "dur": 50.0},
        {"ph": "X", "name": "copy.2", "pid": 1, "tid": 1,
         "ts": 200.0, "dur": 10.0},
    ]}
    if shared is not None:
        doc["traceEvents"].append(
            {"ph": "X", "name": shared["name"], "pid": 2, "tid": 7,
             "ts": 250.0, "dur": 5.0, "args": {"span": shared["span"]}})
    with gzip.open(os.path.join(run, "host.trace.json.gz"), "wt") as fh:
        json.dump(doc, fh)
    return str(root)


def test_trace_dump_merges_xla_capture(tmp_path):
    """The unified timeline: trace_dump writes ONE valid Chrome trace
    holding host spans AND the XLA capture's device ops, placed on the
    epoch's axis by the span both sides hold, on distinct process
    rows."""
    fiber_tpu.init()
    with tracing.span("xla.capture") as shared:
        pass
    xla_dir = _write_fake_xla_capture(tmp_path / "xla", shared)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(8))
        assert pool.map(targets.sleep_echo, xs, chunksize=2) == xs
        out = pool.trace_dump(str(tmp_path / "merged.json"),
                              xla_dir=xla_dir)
    with open(out) as fh:
        doc = json.load(fh)
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "worker.execute" in names          # host plane
    assert "fusion.1" in names                # device plane
    host_ev = next(e for e in doc["traceEvents"]
                   if e.get("name") == "worker.execute")
    dev_ev = next(e for e in doc["traceEvents"]
                  if e.get("name") == "fusion.1")
    # device events placed on the epoch's axis by the shared span: the
    # capture's fusion.1 began 150 us before the capture's copy of it
    assert abs(dev_ev["ts"] - host_ev["ts"]) < 600 * 1e6
    assert dev_ev["ts"] == pytest.approx(
        shared["start_ns"] / 1e3 - 150.0, abs=1.0)
    assert dev_ev["pid"] != host_ev["pid"]    # separate lanes
    metas = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(m.startswith("XLA ") for m in metas)


def test_trace_dump_uses_noted_capture_and_survives_missing(tmp_path):
    fiber_tpu.init()
    with tracing.span("pool.serialize", trace="t9", seq=9) as shared:
        pass
    # a noted capture directory with NO trace files: merge is a no-op,
    # the host dump still writes
    DEVICE.note_xla_trace(str(tmp_path / "empty"))
    assert DEVICE.last_xla_trace() == str(tmp_path / "empty")
    from fiber_tpu.telemetry import export

    out = export.write_chrome_trace(
        str(tmp_path / "host_only.json"), tracing.SPANS.snapshot(),
        xla_dir=str(tmp_path / "empty"))
    with open(out) as fh:
        doc = json.load(fh)
    assert any(e.get("name") == "pool.serialize"
               for e in doc["traceEvents"])
    # a capture that holds a span of the dump merges whole ...
    xla_dir = _write_fake_xla_capture(tmp_path / "xla2", shared)
    assert export.merge_xla_trace(doc, xla_dir) == 4
    # ... and one that shares none cannot be placed: nothing is guessed
    stale = _write_fake_xla_capture(tmp_path / "xla3")
    assert export.merge_xla_trace(doc, stale) == 0


def test_real_capture_holds_the_span_and_merges_on_it(tmp_path):
    """End to end with the real profiler: ``utils.profiling.trace``
    makes the whole capture one ``xla.capture`` span, which the span
    store and the capture both hold (same name, the span id beside it),
    so the merge needs no guess at the clocks."""
    import jax.numpy as jnp

    from fiber_tpu.telemetry import export
    from fiber_tpu.utils.profiling import trace

    fiber_tpu.init()
    tracing.SPANS.clear()
    out = str(tmp_path / "capture")
    with trace(out):
        jnp.arange(64.0).sum().block_until_ready()
    spans = tracing.SPANS.snapshot()
    (shared,) = [s for s in spans if s["name"] == "xla.capture"]
    assert DEVICE.last_xla_trace() == out
    xla = export.load_xla_chrome_trace(export.find_xla_chrome_trace(out))
    theirs = [e for e in xla["traceEvents"]
              if e.get("name") == "xla.capture"]
    assert [e["args"]["span"] for e in theirs] == [shared["span"]]
    doc = export.chrome_trace(spans)
    assert export.merge_xla_trace(doc, out) > 0
    # the capture's copy of the span lands where the host's own is (the
    # two clocks are read microseconds apart, and a sampler tick may be
    # a second shared span)
    placed = [e for e in doc["traceEvents"]
              if e.get("name") == "xla.capture" and e["pid"] >= 1000]
    assert placed[0]["ts"] == pytest.approx(
        shared["start_ns"] / 1e3, abs=500.0)


def _phase_spans(parent=None):
    return [s for s in tracing.SPANS.snapshot()
            if s["name"] in ("jax.trace", "jax.lower",
                             "jax.backend_compile")
            and (parent is None or s["parent"] == parent)]


def test_compile_spans_carry_fun_name_phase_and_cache():
    """Each of JAX's three compile phases becomes a span with JAX's own
    start and end, the function's name, and on the backend phase
    whether the persistent cache was hit (a cleared in-memory cache and
    the same program again) or missed."""
    import jax

    fiber_tpu.init()
    assert DEVICE.install_listeners()
    old = (jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        def unit_compile_probe(x):
            return (x * 5.0 + 2.0).sum()

        seen = []
        for _ in range(2):
            tracing.SPANS.clear()
            jax.clear_caches()
            jax.jit(unit_compile_probe)(
                np.arange(11.0)).block_until_ready()
            seen.append([s for s in _phase_spans()
                         if "unit_compile_probe" in s["fun_name"]])
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", old[0])
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", old[1])
    for spans, outcome in zip(seen, ("miss", "hit")):
        assert {s["name"] for s in spans} == {
            "jax.trace", "jax.lower", "jax.backend_compile"}
        for s in spans:
            assert isinstance(s["start_ns"], int)
            assert s["end_ns"] >= s["start_ns"] > 1e18   # the epoch's
            assert s["dur"] == pytest.approx(
                (s["end_ns"] - s["start_ns"]) / 1e9)
            if s["name"] == "jax.backend_compile":
                assert s["cache"] == outcome
            else:
                assert "cache" not in s


def test_traces_inside_a_trace_fold_into_the_outermost_span():
    """Every jitted function met while another is traced is traced in
    turn (a thousand under one train step): one ``jax.trace`` span for
    the outermost, with the count and the seconds of those inside it,
    which ``compile_seconds`` counts a second time."""
    import jax
    import jax.numpy as jnp

    fiber_tpu.init()
    assert DEVICE.install_listeners()

    @jax.jit
    def unit_inner(x):
        return jnp.sin(x) * 2.0

    def unit_outer(x):
        return unit_inner(x) + unit_inner(x + 1.0).sum()

    # jnp's own jits traced beforehand, so that the traces inside
    # unit_outer are one deep (a trace two deep is in `nested_s` twice)
    warm = jnp.sin(jnp.asarray(np.arange(5.0)) + 1.0) * 2.0
    (warm + warm.sum()).block_until_ready()
    tracing.SPANS.clear()
    before = DEVICE.snapshot()["compile_seconds"]
    jax.jit(unit_outer)(np.arange(5.0)).block_until_ready()
    spent = DEVICE.snapshot()["compile_seconds"] - before
    spans = _phase_spans()
    assert not [s for s in spans if "unit_inner" in s["fun_name"]]
    outer = [s for s in spans if s["name"] == "jax.trace"
             and s["fun_name"] == "unit_outer"]
    assert outer and all(s["nested"] >= 1 for s in outer)
    assert all(0 < s["nested_s"] < s["dur"] for s in outer)
    # per listening instance: spans + nested seconds == compile_seconds
    mine = sum(s["dur"] + s.get("nested_s", 0.0) for s in spans)
    assert mine / (len(outer) or 1) == pytest.approx(spent, rel=0.05)


def test_new_shape_compiles_under_that_calls_span():
    """Which call recompiled: the compile spans are children of the
    ambient span, so a call with a new shape shows its three phases
    under ITS ``lm.train_step`` span, and a call with a known shape
    shows none."""
    import jax
    import jax.numpy as jnp
    import optax

    from fiber_tpu.models import TinyLM, make_train_step

    fiber_tpu.init()
    assert DEVICE.install_listeners()
    model = TinyLM(vocab=16, dim=16, heads=2, layers=1, max_seq=8,
                   attention="reference")
    opt = optax.sgd(1e-2)
    step = make_train_step(model, opt, batched=True)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    tracing.SPANS.clear()
    for batch in (1, 1, 2):
        params, state, loss = step(
            params, state, jnp.zeros((batch, 8), jnp.int32))
    loss.block_until_ready()
    calls = [s for s in tracing.SPANS.snapshot()
             if s["name"] == "lm.train_step"]
    assert [c["tokens"] for c in calls] == [8, 8, 16]
    first, second, third = (_phase_spans(c["span"]) for c in calls)
    assert second == []
    for children, call in ((first, calls[0]), (third, calls[2])):
        # JAX names the function "step" while tracing it and
        # "jit(step)" while lowering and compiling it
        own = [s for s in children
               if s["fun_name"] in ("step", "jit(step)")]
        assert {s["name"] for s in own} == {
            "jax.trace", "jax.lower", "jax.backend_compile"}
        assert all(s["trace"] == call["trace"] for s in children)
        assert all(call["start_ns"] <= s["start_ns"]
                   and s["end_ns"] <= call["end_ns"] + 1_000_000
                   for s in own)


# ---------------------------------------------------------------------------
# the host's account on the call span, and the step_stall rule
# ---------------------------------------------------------------------------


def _step(fn="lm.train_step", body=None, **attrs):
    from fiber_tpu.telemetry import device

    with device.step(fn, 8, **attrs) as sp:
        if body is not None:
            body()
    return sp


def _spin(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_a_call_that_computes_reads_its_cpu_time():
    fiber_tpu.init()
    sp = _step(body=lambda: _spin(0.02))
    assert sp["cpu_ns"] >= 15_000_000
    assert sp["gc_ns"] >= 0 and sp["gc_runs"] >= 0


def test_a_call_that_sleeps_reads_as_off_the_cpu():
    fiber_tpu.init()
    sp = _step(body=lambda: time.sleep(0.02))
    assert sp["end_ns"] - sp["start_ns"] - sp["cpu_ns"] >= 15_000_000


def test_es_run_fused_spans_carry_the_account_through_the_one_site():
    fiber_tpu.init()
    _step("es.run_fused", generations=1)
    sp = _step("es.run_fused", generations=1)
    assert {"cpu_ns", "gc_runs", "since_ns", "since_cpu_ns"} <= set(sp)
    assert sp["generations"] == 1
    assert sp in tracing.SPANS.snapshot()


def test_since_fields_start_with_a_threads_second_call():
    fiber_tpu.init()
    first = _step()
    time.sleep(0.02)
    second = _step()
    assert not [k for k in first if k.startswith("since_")]
    assert second["since_ns"] >= 15_000_000
    assert second["since_cpu_ns"] < second["since_ns"]
    assert {"since_gc_ns", "since_gc_runs"} <= set(second)
    # another fn on the same thread starts its own account
    other = _step("es.run_fused")
    assert "since_ns" not in other


def test_since_fields_are_kept_apart_for_two_threads():
    """Two threads call in turn: each one's ``since`` runs from its own
    last call, across the other's call in between."""
    fiber_tpu.init()
    spans = {"a": [], "b": []}
    turn = threading.Semaphore(1), threading.Semaphore(0)

    def caller(name, mine, theirs):
        for _ in range(2):
            assert mine.acquire(timeout=10)
            spans[name].append(_step(body=lambda: time.sleep(0.02)))
            theirs.release()

    threads = [threading.Thread(target=caller, args=("a", *turn)),
               threading.Thread(target=caller, args=("b", *turn[::-1]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
        assert not t.is_alive()
    for name in ("a", "b"):
        first, second = spans[name]
        assert "since_ns" not in first
        # the other thread's 20 ms call lies inside this one's since
        assert second["since_ns"] >= 15_000_000
        assert second["since_cpu_ns"] < 10_000_000


def test_a_collection_between_two_calls_shows_in_the_seconds_since():
    import gc

    fiber_tpu.init()
    _step()
    gc.collect()
    sp = _step()
    assert sp["since_gc_runs"] >= 1 and sp["since_gc_ns"] > 0
    assert sp["gc_runs"] == 0 and sp["gc_ns"] == 0


def test_with_telemetry_off_no_source_is_read(monkeypatch):
    from fiber_tpu.telemetry import device

    fiber_tpu.init(telemetry_enabled=False)

    def fail(*a, **kw):
        raise AssertionError("read with telemetry off")

    monkeypatch.setattr(device.StepCalls, "_snapshot", fail)
    monkeypatch.setattr(device.StepCalls, "_ended", fail)
    monkeypatch.setattr(device.time, "thread_time_ns", fail)
    before = len(tracing.SPANS)
    assert _step() is None
    assert len(tracing.SPANS) == before
    assert device.CALLS.in_flight is None


class _Clock:
    """The clock the periods are judged on, moved by hand: a real sleep
    of 10 ms overshoots under load, and 50 ms over is a stall."""

    def __init__(self, monkeypatch):
        from fiber_tpu.telemetry import device

        real = device.CALLS._snapshot
        self.ns = time.perf_counter_ns()
        monkeypatch.setattr(device.CALLS, "_snapshot",
                            lambda: (self.ns,) + real()[1:])

    def paced(self, periods_s, fn="lm.train_step"):
        """Calls of ``fn`` whose starts lie ``periods_s`` apart."""
        _step(fn)
        for period in periods_s:
            self.ns += int(period * 1e9)
            _step(fn)


def test_step_stall_is_one_event_with_both_halves_of_the_account(
        monkeypatch):
    """Ten periods of 10 ms and one of 200: exactly one flight event on
    the monitor plane, one move of each counter, the rule active; the
    next 10 ms period clears it."""
    from fiber_tpu.telemetry import device

    fiber_tpu.init()
    clock = _Clock(monkeypatch)
    stalls = telemetry.counter("device_step_stalls")
    anomalies = telemetry.counter("monitor_anomalies")
    before = (stalls.value(fn="lm.train_step"),
              anomalies.value(rule="step_stall"))
    clock.paced([0.01] * 10)
    assert "step_stall" not in WATCHDOG.snapshot()["active"]
    clock.paced([0.2])
    events = [e for e in FLIGHT.snapshot()
              if (e["plane"], e["kind"]) == ("monitor", "step_stall")]
    assert len(events) == 1
    ev = events[0]
    assert ev["fn"] == "lm.train_step"
    assert ev["period_s"] == pytest.approx(0.2)
    assert ev["median_s"] == pytest.approx(0.01)
    # the call that began the period, and the time since it ended
    assert ev["call"]["ns"] < 50_000_000 and "cpu_ns" in ev["call"]
    assert ev["since"]["ns"] == 200_000_000
    assert ev["since"]["cpu_ns"] < 100_000_000
    for half in ("call", "since"):
        assert {"cpu_ns", "gc_ns", "gc_runs"} <= set(ev[half])
    assert ev["detail"].startswith(
        "lm.train_step period 0.20 s (median 0.01): in call 0.0")
    assert "since 0.200 s (cpu 0.0" in ev["detail"]
    assert "gc 0.000 / 0 runs" in ev["detail"]
    assert (stalls.value(fn="lm.train_step"),
            anomalies.value(rule="step_stall")) == (before[0] + 1,
                                                    before[1] + 1)
    active = WATCHDOG.snapshot()["active"]
    assert active["step_stall"]["fn"] == "lm.train_step"
    # the stalled period is not held: the median it is judged by stands
    assert max(device.CALLS._held["lm.train_step"].periods) == 10_000_000
    clock.ns += 10_000_000
    _step()
    assert "step_stall" not in WATCHDOG.snapshot()["active"]
    kinds = [(e["kind"], e.get("rule")) for e in FLIGHT.snapshot()
             if e["plane"] == "monitor"]
    assert kinds.count(("clear", "step_stall")) == 1
    assert len([k for k in kinds if k[0] == "step_stall"]) == 1


def test_step_stall_is_said_once_the_span_that_found_it_has_closed(
        monkeypatch):
    """The log line and the flight event are written after the call
    span that ends the stalled period is over, so that neither its
    length nor its ``cpu_ns`` holds them."""
    from fiber_tpu.telemetry import device

    fiber_tpu.init()
    clock = _Clock(monkeypatch)
    real, closed = device.CALLS._raise_stall, []

    def raise_stall(*args):
        closed.append("end_ns" in args[-1] and "cpu_ns" in args[-1])
        real(*args)

    monkeypatch.setattr(device.CALLS, "_raise_stall", raise_stall)
    clock.paced([0.01] * 9 + [0.5])
    assert closed == [True]
    assert "step_stall" in WATCHDOG.snapshot()["active"]


def test_step_stall_needs_eight_periods_and_fifty_milliseconds(
        monkeypatch):
    fiber_tpu.init()
    clock = _Clock(monkeypatch)
    # seven periods held: a long eighth is not judged
    clock.paced([0.005] * 7 + [0.15], fn="es.run_fused")
    assert "step_stall" not in WATCHDOG.snapshot()["active"]
    # held now (the long one among them): eight medians, 49 ms over
    clock.paced([0.005] * 3 + [0.054], fn="es.run_fused")
    assert "step_stall" not in WATCHDOG.snapshot()["active"]
    assert not [e for e in FLIGHT.snapshot() if e["kind"] == "step_stall"]
    # 50 ms over, and under 1.5 medians of steps of a second
    clock.paced([1.0] * 32 + [1.45], fn="lm.train_step")
    assert "step_stall" not in WATCHDOG.snapshot()["active"]
    clock.paced([0.005] * 8 + [0.056], fn="lm.other_step")
    assert WATCHDOG.snapshot()["active"]["step_stall"]["fn"] == (
        "lm.other_step")


def test_step_stall_names_where_the_sampler_saw_the_caller():
    """Ticks taken inside the stalled period give the event its ``at``:
    the innermost frame of the calling thread."""
    from fiber_tpu.telemetry import device
    from fiber_tpu.telemetry.timeseries import TIMESERIES

    fiber_tpu.init(monitor_enabled=False)
    done = threading.Event()

    def pause_between_steps():  # time.sleep has no Python frame
        deadline = time.monotonic() + 5.0
        while not done.is_set() and time.monotonic() < deadline:
            time.sleep(0.005)

    def job():
        _step()
        for _ in range(9):
            time.sleep(0.01)
            _step()
        pause_between_steps()
        _step()

    t = threading.Thread(target=job)
    t.start()
    deadline = time.monotonic() + 10
    seen = {}
    while time.monotonic() < deadline:
        time.sleep(0.02)
        seen = device.CALLS.caller_now()
        if "pause_between_steps" in seen.get("at", ""):
            break
    TIMESERIES.tick()
    time.sleep(0.5)  # the pause outlasts the rule's 50 ms, under load too
    done.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert seen["open"] is None and seen["idle_ns"] > 0
    ev = [e for e in FLIGHT.snapshot() if e["kind"] == "step_stall"][-1]
    assert any("pause_between_steps" in at for at in ev["at"])
    assert "sampler saw" in ev["detail"]
    assert "pause_between_steps" in ev["detail"]


# ---------------------------------------------------------------------------
# collection plane: agent op, backends, CLI
# ---------------------------------------------------------------------------


@pytest.fixture
def embedded_agent(tmp_path):
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1", staging_root=str(tmp_path))
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()
    yield agent
    agent.stop()


def test_agent_device_snapshot_op(embedded_agent):
    from fiber_tpu.backends.tpu import AgentClient

    fiber_tpu.init()
    with DEVICE.transfer("unit", 77):
        pass
    client = AgentClient("127.0.0.1", embedded_agent.port)
    try:
        snap = client.call("device_snapshot")
    finally:
        client.close()
    assert snap["pid"] == os.getpid()
    assert snap["transfers"]["unit"]["bytes"] == 77
    assert snap["hbm"]["bytes_in_use"] is None  # CPU: honest null


def test_local_backend_cluster_devices():
    from fiber_tpu.backends.local import LocalBackend

    fiber_tpu.init()
    out = LocalBackend().cluster_devices()
    assert set(out) == {"local"}
    assert "transfers" in out["local"] and "hbm" in out["local"]


def test_device_stats_and_cli_over_sim_pool(monkeypatch, capsys):
    """The acceptance path on a real sim:2 pod: a pool map with a
    store-resolved broadcast arg, then Pool.device_stats() returning
    per-host transfer bytes+seconds, compile count+seconds and HBM
    stats (null-safe on CPU) for every cluster host, and the
    `fiber-tpu devices` CLI rendering the same agents."""
    from fiber_tpu import cli
    from fiber_tpu.backends import get_backend, reset_backends

    monkeypatch.setenv("FIBER_BACKEND", "tpu")
    old = config.get().tpu_hosts
    config.get().update(tpu_hosts="sim:2")
    reset_backends()
    try:
        fiber_tpu.init(backend="tpu",
                       tpu_hosts="sim:2", store_inline_max=64 * 1024)
        arr = np.ones((200_000,), dtype=np.float64)
        with fiber_tpu.Pool(4) as pool:
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(12)],
                               chunksize=1)
            assert out == [float(arr.sum()) + i for i in range(12)]
            stats = pool.device_stats()
        # per-host agent snapshots, keyed like host_health
        assert len(stats["hosts"]) == 2
        for snap in stats["hosts"].values():
            assert "error" not in snap
            assert "transfer_bytes" in snap
            assert "transfer_seconds" in snap
            assert "compiles" in snap and "compile_seconds" in snap
            assert snap["hbm"]["bytes_in_use"] is None  # CPU: honest
        # the workers that resolved the broadcast shipped real numbers
        assert stats["workers"]
        assert any(
            s["transfers"].get("store_resolve", {}).get("bytes", 0)
            >= arr.nbytes for s in stats["workers"].values())
        assert all(s["transfer_seconds"] > 0
                   for s in stats["workers"].values()
                   if s["transfers"])
        # the CLI renders the same agents
        hosts = ",".join(stats["hosts"])
        assert cli.main(["devices", "--hosts", hosts]) == 0
        rendered = capsys.readouterr().out
        assert "XFER-B" in rendered
        for key in stats["hosts"]:
            assert key in rendered
    finally:
        try:
            get_backend("tpu").shutdown_sim_cluster()
        except Exception:  # noqa: BLE001
            pass
        config.get().update(tpu_hosts=old)
        reset_backends()


def test_devices_cli(embedded_agent, capsys):
    from fiber_tpu import cli

    fiber_tpu.init()
    with DEVICE.transfer("store_resolve", 1 << 20):
        pass
    hosts = f"127.0.0.1:{embedded_agent.port}"
    assert cli.main(["devices", "--hosts", hosts, "--sites"]) == 0
    out = capsys.readouterr().out
    assert "XFER-B" in out and "COMPILES" in out and "MFU" in out
    assert hosts in out
    assert "1.0MB" in out                 # the transfer we recorded
    assert "store_resolve" in out         # --sites breakdown
    # null HBM/MFU render '-', never 0
    row = next(line for line in out.splitlines() if hosts in line)
    assert " - " in row or row.rstrip().endswith("-")
    # --json ships raw snapshots
    assert cli.main(["devices", "--hosts", hosts, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc[hosts]["transfers"]["store_resolve"]["bytes"] == 1 << 20
    # unreachable host: DOWN row + rc 1
    assert cli.main(["devices", "--hosts", "127.0.0.1:1"]) == 1
    assert "DOWN" in capsys.readouterr().out


def test_top_renders_hbm_and_mfu_columns(embedded_agent, capsys):
    from fiber_tpu import cli

    fiber_tpu.init(monitor_interval_s=0.1)
    hosts = f"127.0.0.1:{embedded_agent.port}"
    assert cli.main(["top", "--hosts", hosts, "--iterations", "1",
                     "--no-clear"]) == 0
    out = capsys.readouterr().out
    assert "HBM" in out and "MFU" in out
    row = next(line for line in out.splitlines() if hosts in line)
    assert "-" in row  # CPU host: honest dashes, not zeros


def test_top_row_renders_device_numbers():
    from fiber_tpu.cli import _render_top_rows

    pulls = {"h1:7060": {
        "timeseries": {"last": {"tasks_per_s": 5.0}},
        "anomalies": {"active": {}},
        "heartbeat_ages": {},
        "device": {"hbm_bytes_in_use": 6 << 30,
                   "hbm_bytes_limit": 16 << 30, "mfu": 0.423},
    }}
    row = _render_top_rows(pulls)[0]
    assert "6.0GB/16.0GB" in row
    assert "42.3%" in row


def test_telemetry_snapshot_carries_device_surface():
    fiber_tpu.init()
    with DEVICE.transfer("unit", 5):
        pass
    snap = telemetry.snapshot()
    assert snap["device"]["transfers"]["unit"]["count"] == 1
