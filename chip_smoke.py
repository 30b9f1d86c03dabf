#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fiber_tpu still starts on the chip.

    python chip_smoke.py                # on a TPU machine; fails without one
    python chip_smoke.py --rehearse-cpu # tiny shapes, 8 virtual CPU devices

One process, the entry points a user would call, nothing mocked, at the
full width of what the repo ships (depth and step counts are cut; the
weights are random, made from a seed). The mesh is ``jax.devices()`` —
all of them, one chip or four. Every phase checks what comes out against
the repo's own reference and fails the run on its own; the last line of
standard output is one JSON object with exactly these keys,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

(the line before it, ``summary: {...}``, carries the per-phase verdicts,
the JAX version, the fiber backend and the compile-cache hits), and the
exit code is 0 only if every phase passed. With no accelerator
the default invocation prints why and exits 2 — it never falls back to
the CPU. It checks correctness, not speed: the seconds it prints are
set-up (first call: trace + compile + run) and run (later calls) times
for orientation, not measurements.

``--rehearse-cpu`` is an explicit request to run the same phases on the
CPU so the script cannot rot between chip runs: tiny shapes, and the
Pallas kernels in the interpreter because THIS SCRIPT asks for it
(``interpret=True``); its summary line says ``"rehearsal": true`` and
its verdict names the CPU devices it ran on.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

import fiber_tpu

# ---------------------------------------------------------------------------
# Sizes. FULL is the smoke's own shapes (small: it checks results); REHEARSAL
# cuts every one of them so the CPU and the Pallas interpreter finish in
# about a minute.
# ---------------------------------------------------------------------------

FULL = {
    "map_items": 4096,           # device-hinted Pool.map, float32 items
    "bcast_elems": 1 << 16,      # 256 KB shared array (>= the 64 KB lift)
    "bcast_tasks": 64,
    "es": {"hidden": (32, 32), "pop": 4096, "steps": 500, "gens": 8},
    "lm": {"vocab": 256, "dim": 256, "heads": 8, "layers": 4,
           "seq": 16384, "steps": 4},
    "parity": {"seq": 4096, "heads": 8, "kv_heads": 2, "head_dim": 64,
               "window": 1024},
    "mesh_attn": {"seq": 4096, "heads": 8, "head_dim": 64},
    "host_tasks": 64,
}
REHEARSAL = {
    "map_items": 64,
    "bcast_elems": 1 << 14,      # 64 KB: still through the device tier
    "bcast_tasks": 8,
    "es": {"hidden": (8,), "pop": 64, "steps": 50, "gens": 6},
    "lm": {"vocab": 64, "dim": 32, "heads": 8, "layers": 1,
           "seq": 256, "steps": 3},
    "parity": {"seq": 256, "heads": 8, "kv_heads": 2, "head_dim": 16,
               "window": 64},
    "mesh_attn": {"seq": 256, "heads": 8, "head_dim": 16},
    "host_tasks": 16,
}

# ---------------------------------------------------------------------------
# Tolerances, each with its reason. Errors are max|got - want| over
# max|want| (one number per tensor), against a float32 reference computed
# with jax.default_matmul_precision("highest") — i.e. real float32.
# ---------------------------------------------------------------------------

#: Flash kernels (Mosaic) vs the float32 reference: outputs, lse and all
#: three gradients. Inside the kernels the matmuls take float32 operands
#: and accumulate in float32, but on the MXU Mosaic's default contract
#: precision rounds the operands to bfloat16 (8 mantissa bits, 2^-9
#: relative rounding each); over head_dim-64 products and 4096-term
#: softmax sums that lands at several 1e-3 of the tensor's scale —
#: 7.3e-3 worst over the 3 cases x 6 tensors on a v5e (PR 21). On the
#: CPU (interpreter, real float32) the same check reads 3e-7.
KERNEL_TOL = {"tpu": 2e-2, "cpu": 2e-5}

#: XLA sequence-parallel planes (ring, Ulysses) vs the same reference:
#: XLA's DEFAULT matmul precision on the chip is likewise one bfloat16
#: pass for float32 operands (3.1e-3 measured on a v5e, PR 21).
XLA_ATTN_TOL = {"tpu": 2e-2, "cpu": 2e-5}

#: LM loss, flash plane vs ring plane, same seed, step by step, relative.
#: Both planes run the identical XLA projections/MLP at the chip's
#: default precision; they differ only in which bfloat16-rounded
#: attention they compute (kernel vs chunked XLA), and the optimizer
#: compounds that a little each step (1.5e-4 over 4 steps measured on a
#: v5e, PR 21, while the loss itself fell 5.61 -> 3.90).
LM_LOSS_RTOL = {"tpu": 5e-3, "cpu": 1e-4}


# ---------------------------------------------------------------------------
# Task functions (module level: pool workers import them by reference)
# ---------------------------------------------------------------------------


@fiber_tpu.meta(device=True)
def _dev_square(x):
    return x * x


@fiber_tpu.meta(device=True)
def _dev_scaled_extrema(shared, s):
    import jax.numpy as jnp

    return jnp.stack([jnp.max(shared), jnp.min(shared)]) * s


def _host_probe(x):
    """A plain Python task: what does a host-plane worker look like?"""
    time.sleep(0.02)  # spread the tasks over the workers
    return (x * x, os.getpid(), "jax" in sys.modules,
            os.environ.get("JAX_PLATFORMS"))


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _devices_of(arr) -> set:
    return {shard.device for shard in arr.addressable_shards}


def _full_matrix_attention(q, k, v, window=None):
    """Causal attention the plain way — full (h, S, S) scores — with
    GQA by KV repeat and an optional sliding window; returns (out, lse).
    The smoke's reference where the library's ``reference_attention``
    has no window and returns no logsumexp (it is checked against that
    function where both apply)."""
    import jax
    import jax.numpy as jnp

    reps = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, reps, axis=1)
    v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    pos = jnp.arange(q.shape[0])
    keep = pos[:, None] >= pos[None, :]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)
    s = jnp.where(keep[None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("hqk,khd->qhd", p, v), lse


# ---------------------------------------------------------------------------
# Phases. Each returns a one-line detail string and raises on failure.
# ---------------------------------------------------------------------------


def phase_pool_map_device(ctx) -> str:
    """Device-hinted Pool.map, and a starmap whose shared array travels
    through the store's device tier onto every mesh device."""
    import jax

    from fiber_tpu import serialization, telemetry
    from fiber_tpu import store as storemod
    from fiber_tpu.store.core import digest_of

    size = ctx["size"]
    items = np.arange(size["map_items"], dtype=np.float32)
    puts = telemetry.counter("store_device_puts")
    puts_before = puts.value()
    with fiber_tpu.Pool() as pool:
        t0 = time.perf_counter()
        out = pool.map(_dev_square, items)
        setup_s = time.perf_counter() - t0
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), items * items)
        t0 = time.perf_counter()
        out = pool.map(_dev_square, items)
        run_s = time.perf_counter() - t0
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), items * items)

        shared = np.random.default_rng(0).standard_normal(
            size["bcast_elems"]).astype(np.float32)
        assert shared.nbytes >= 64 << 10
        scales = [np.float32(i + 1) for i in range(size["bcast_tasks"])]
        want = np.stack([np.array([shared.max(), shared.min()]) * s
                         for s in scales])
        tier = storemod.device_store_tier()
        for _ in range(2):  # second generation: same digest, tier hit
            got = pool.starmap(_dev_scaled_extrema,
                               [(shared, s) for s in scales])
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), want)
        stats = tier.stats()
    assert puts.value() > puts_before, "store_device_puts did not rise"
    assert stats["put_dedup_hits"] >= 1, stats
    resident = tier.get(digest_of(serialization.dumps(shared)))
    assert resident is not None, "broadcast array not in the device tier"
    mesh_devices = set(ctx["mesh"].devices.flat)
    for leaf in jax.tree.leaves(resident):
        assert leaf.sharding.is_fully_replicated
        assert _devices_of(leaf) == mesh_devices, (
            _devices_of(leaf), mesh_devices)
    return (f"{len(items)} items exact; {shared.nbytes >> 10} KB shared "
            f"array resident on {len(mesh_devices)} device(s), "
            f"device-tier puts +{int(puts.value() - puts_before)}, "
            f"dedup hits {stats['put_dedup_hits']}; "
            f"set-up {setup_s:.2f}s run {run_s:.3f}s")


def phase_flagship_es(ctx) -> str:
    """EvolutionStrategy.run_fused on CartPole: the flagship path."""
    import jax

    from fiber_tpu.models import CartPole, MLPPolicy
    from fiber_tpu.ops import EvolutionStrategy

    cfg = ctx["size"]["es"]
    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim,
                       hidden=cfg["hidden"])

    def eval_fn(theta, key):
        return CartPole.rollout(policy.act, theta, key,
                                max_steps=cfg["steps"])

    es = EvolutionStrategy(eval_fn, dim=policy.dim, pop_size=cfg["pop"],
                           sigma=0.1, lr=0.03, mesh=ctx["mesh"])
    assert es.pop_size == cfg["pop"], (es.pop_size, cfg["pop"])
    params = policy.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    history = []
    seconds = []
    for _ in range(2):  # same compiled program twice: set-up, then run
        key, k = jax.random.split(key)
        t0 = time.perf_counter()
        params, stats = es.run_fused(params, k, cfg["gens"])
        jax.block_until_ready(stats)
        seconds.append(time.perf_counter() - t0)
        history.append(np.asarray(jax.device_get(stats)))
    history = np.concatenate(history)
    assert history.shape == (2 * cfg["gens"], 3), history.shape
    assert np.all(np.isfinite(history)), history
    assert np.all(np.isfinite(np.asarray(jax.device_get(params))))
    assert _devices_of(params) == set(ctx["mesh"].devices.flat)
    first, last = float(history[0, 0]), float(history[-1, 0])
    assert last > first, (
        f"mean fitness did not rise: first {first:.2f} last {last:.2f}")
    return (f"pop {es.pop_size} x {cfg['steps']} steps x "
            f"{2 * cfg['gens']} gens; mean fitness {first:.1f} -> "
            f"{last:.1f}; set-up {seconds[0]:.1f}s run {seconds[1]:.2f}s")


def phase_lm_trainer(ctx) -> str:
    """TinyLM through make_train_step + optax.adamw: a few steps with
    ring attention and with the flash kernels, same seed."""
    import jax
    import jax.numpy as jnp
    import optax

    from fiber_tpu.models import TinyLM, make_train_step

    cfg = ctx["size"]["lm"]
    seq, vocab = cfg["seq"], cfg["vocab"]
    # A seeded period-64 token stream: low entropy, so a handful of
    # adamw steps visibly lowers the loss (uniform noise would not).
    period = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, vocab)
    toks = jnp.tile(period, seq // 64)
    opt = optax.adamw(1e-3)
    losses = {}
    detail = []
    for attention in ("ring", "flash"):
        kwargs = {}
        if attention == "flash" and ctx["interpret"]:
            kwargs["interpret"] = True  # rehearsal only: no Mosaic on CPU
        model = TinyLM(vocab=vocab, dim=cfg["dim"], heads=cfg["heads"],
                       layers=cfg["layers"], max_seq=seq, mesh=ctx["mesh"],
                       attention=attention, **kwargs)
        step = make_train_step(model, opt)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        if attention == "flash" and not ctx["interpret"]:
            # Through Mosaic, not the interpreter: the model was built
            # with interpret=False (its default), and the program that
            # is about to run holds the kernels as TPU custom calls —
            # forward, dq and dkv.
            assert model.interpret is False
            hlo = step.lower(params, opt_state, toks).as_text()
            n_calls = hlo.count("tpu_custom_call")
            assert n_calls >= 3, f"{n_calls} tpu_custom_call in the step"
            detail.append(f"flash step holds {n_calls} tpu_custom_call")
        seconds = []
        trace = []
        for _ in range(cfg["steps"]):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, toks)
            trace.append(float(jax.device_get(loss)))
            seconds.append(time.perf_counter() - t0)
        assert np.all(np.isfinite(trace)), (attention, trace)
        assert trace[-1] < trace[0], (
            f"{attention} loss did not decrease: {trace}")
        leaves = jax.tree_util.tree_leaves(params)
        assert all(np.all(np.isfinite(np.asarray(jax.device_get(x))))
                   for x in leaves)
        losses[attention] = trace
        detail.append(
            f"{attention} loss " + " ".join(f"{x:.4f}" for x in trace)
            + f" (set-up {seconds[0]:.1f}s, step {min(seconds[1:]):.3f}s)")
    rtol = LM_LOSS_RTOL[ctx["platform"]]
    gaps = [abs(f - r) / abs(r)
            for f, r in zip(losses["flash"], losses["ring"])]
    assert max(gaps) <= rtol, (
        f"flash vs ring loss gap {max(gaps):.2e} > {rtol:.0e}: {losses}")
    detail.append(f"flash-vs-ring max rel gap {max(gaps):.2e} "
                  f"(tol {rtol:.0e})")
    return f"{seq} tokens, dim {cfg['dim']} x {cfg['layers']} layers; " \
        + "; ".join(detail)


def phase_kernel_parity(ctx) -> str:
    """flash_attention / flash_attention_lse against the float32
    full-matrix reference: output, logsumexp and jax.grad."""
    import jax
    import jax.numpy as jnp

    from fiber_tpu.ops.pallas_attention import (
        flash_attention,
        flash_attention_lse,
    )
    from fiber_tpu.ops.ring_attention import reference_attention

    cfg = ctx["size"]["parity"]
    S, H, D = cfg["seq"], cfg["heads"], cfg["head_dim"]
    tol = KERNEL_TOL[ctx["platform"]]
    interpret = ctx["interpret"]
    worst = 0.0
    cases = (("causal", H, None),
             (f"causal+window={cfg['window']}", H, cfg["window"]),
             (f"gqa {H}/{cfg['kv_heads']}", cfg["kv_heads"], None))
    for name, kvh, window in cases:
        kq, kk, kv, kw, kl = jax.random.split(jax.random.PRNGKey(7), 5)
        q = jax.random.normal(kq, (S, H, D), jnp.float32)
        k = jax.random.normal(kk, (S, kvh, D), jnp.float32)
        v = jax.random.normal(kv, (S, kvh, D), jnp.float32)
        w_out = jax.random.normal(kw, (S, H, D), jnp.float32)
        w_lse = jax.random.normal(kl, (H, S), jnp.float32)

        def kernel_loss(q, k, v):
            out, lse = flash_attention_lse(
                q, k, v, causal=True, window=window, interpret=interpret)
            return jnp.vdot(out, w_out) + jnp.vdot(lse, w_lse), (out, lse)

        def reference_loss(q, k, v):
            out, lse = _full_matrix_attention(q, k, v, window)
            return jnp.vdot(out, w_out) + jnp.vdot(lse, w_lse), (out, lse)

        grad_k, (out_k, lse_k) = jax.grad(
            kernel_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        out_plain = flash_attention(q, k, v, causal=True, window=window,
                                    interpret=interpret)
        with jax.default_matmul_precision("highest"):
            grad_r, (out_r, lse_r) = jax.grad(
                reference_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            if window is None:
                # tie the smoke's reference to the library's own
                reps = H // kvh
                lib = reference_attention(
                    q, jnp.repeat(k, reps, axis=1),
                    jnp.repeat(v, reps, axis=1), causal=True)
                # (two float32 formulations: 1e-7 apart on the CPU,
                # 4e-5 under the chip's emulated "highest" precision)
                assert _rel_err(out_r, lib) < 1e-3, _rel_err(out_r, lib)
        errs = {"out": _rel_err(out_plain, out_r),
                "out(lse api)": _rel_err(out_k, out_r),
                "lse": _rel_err(lse_k, lse_r)}
        for label, gk, gr in zip(("dq", "dk", "dv"), grad_k, grad_r):
            errs[label] = _rel_err(gk, gr)
        for label, err in errs.items():
            assert np.isfinite(err) and err <= tol, (
                f"{name}: {label} error {err:.2e} > {tol:.0e} ({errs})")
        worst = max(worst, *errs.values())
    return (f"S={S} heads={H} head_dim={D}: {len(cases)} cases x "
            f"(out, lse, dq, dk, dv) worst rel err {worst:.2e} "
            f"(tol {tol:.0e})")


def phase_mesh_width(ctx) -> str:
    """The mesh is every device: sharded inputs and outputs occupy
    len(jax.devices()) distinct devices, ring and Ulysses attention
    over the pool axis agree with the reference, and a broadcast is
    resident everywhere. (On one chip the same assertions hold with
    n = 1.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fiber_tpu.ops.collectives import broadcast_to_mesh
    from fiber_tpu.ops.ring_attention import (
        reference_attention,
        ring_attention,
    )
    from fiber_tpu.ops.ulysses_attention import ulysses_attention

    mesh = ctx["mesh"]
    every = set(mesh.devices.flat)
    n = len(every)
    assert n == len(jax.devices())
    cfg = ctx["size"]["mesh_attn"]
    S, H, D = cfg["seq"], cfg["heads"], cfg["head_dim"]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    sharded = NamedSharding(mesh, P("pool"))
    q, k, v = (jax.device_put(jax.random.normal(key, (S, H, D)), sharded)
               for key in (kq, kk, kv))
    assert _devices_of(q) == every
    with jax.default_matmul_precision("highest"):
        want = reference_attention(q, k, v, causal=True)
    tol = XLA_ATTN_TOL[ctx["platform"]]
    errs = {}
    planes = {
        "ring": lambda: ring_attention(q, k, v, mesh=mesh, causal=True),
        "ring x flash": lambda: ring_attention(
            q, k, v, mesh=mesh, causal=True, local="flash",
            interpret=ctx["interpret"]),
        "ulysses": lambda: ulysses_attention(
            q, k, v, mesh=mesh, causal=True),
    }
    for name, run in planes.items():
        out = run()
        assert _devices_of(out) == every, (name, _devices_of(out))
        assert {s.data.shape for s in out.addressable_shards} == {
            (S // n, H, D)}, name
        errs[name] = _rel_err(out, want)
        assert errs[name] <= tol, (
            f"{name} error {errs[name]:.2e} > {tol:.0e}")
    payload = np.arange(1 << 16, dtype=np.float32)
    everywhere = broadcast_to_mesh(payload, mesh)
    assert _devices_of(everywhere) == every
    for shard in everywhere.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), payload)
    return (f"{n} distinct device(s) hold every sharded input/output; "
            + ", ".join(f"{k} err {e:.2e}" for k, e in errs.items())
            + f" (tol {tol:.0e}); broadcast resident on {n}")


def phase_host_pool_beside_chip(ctx) -> str:
    """One process per chip: this process holds the chip by now; a host
    pool started beside it works, and its workers are JAX-free and
    pinned to the CPU."""
    import jax

    assert jax.devices()[0].platform == ctx["platform"]  # chip is held
    n_tasks = ctx["size"]["host_tasks"]
    with fiber_tpu.Pool(4) as pool:
        out = pool.map(_host_probe, range(n_tasks), chunksize=1)
    assert [r[0] for r in out] == [x * x for x in range(n_tasks)]
    pids = {r[1] for r in out}
    assert os.getpid() not in pids
    assert len(pids) >= 2, f"only {len(pids)} worker(s) took tasks"
    assert not any(r[2] for r in out), "a host worker imported jax"
    assert {r[3] for r in out} == {"cpu"}, {r[3] for r in out}
    return (f"{n_tasks} tasks over {len(pids)} workers, none imported "
            "jax, all pinned JAX_PLATFORMS=cpu")


def phase_compile_cache(ctx) -> str:
    """Where compiled programs persist, and how many this invocation
    found there: 0 on a clean machine, and more than 0 on a second run
    on the same machine (tests/test_chip_smoke.py pins that on the CPU
    rehearsal; CHANGES.md records it for the chip)."""
    from fiber_tpu.telemetry.device import DEVICE

    snap = DEVICE.snapshot()
    assert snap["jax_monitoring"], "compile listeners not installed"
    cache_dir = ctx["cache_dir"]
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    assert entries > 0, f"nothing was cached under {cache_dir}"
    return (f"dir {cache_dir}: {ctx['cache_entries_at_start']} entries "
            f"at start, {entries} now; cache_hits "
            f"{snap['compile_cache_hits']}, entries written "
            f"{snap['compiles']}, trace+compile "
            f"{snap['compile_seconds']:.1f}s")


PHASES = (
    ("pool_map_device", phase_pool_map_device),
    ("flagship_es", phase_flagship_es),
    ("lm_trainer", phase_lm_trainer),
    ("kernel_parity", phase_kernel_parity),
    ("mesh_width", phase_mesh_width),
    ("host_pool_beside_chip", phase_host_pool_beside_chip),
    ("compile_cache", phase_compile_cache),
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same phases at tiny shapes on 8 virtual CPU "
             "devices, Pallas kernels in the interpreter")
    args = parser.parse_args(argv)

    if args.rehearse_cpu:
        # Before jax is imported: nothing preloads it, so this is enough.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        from fiber_tpu.utils.misc import ensure_cpu_collective_timeout_flags

        ensure_cpu_collective_timeout_flags()
    # Nothing the smoke needs comes from ~/.fiber_tpu: staging is a
    # fresh directory, removed at the end.
    staging = tempfile.mkdtemp(prefix="fiber-smoke-")
    os.environ["FIBER_AGENT_STAGING"] = staging
    try:
        return _run(args)
    finally:
        shutil.rmtree(staging, ignore_errors=True)


def _run(args) -> int:
    t_start = time.perf_counter()
    import jax
    from jax.sharding import Mesh

    from fiber_tpu import _native
    from fiber_tpu.backends import _select_backend, get_backend
    from fiber_tpu.telemetry.device import DEVICE
    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    DEVICE.install_listeners()
    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    sniffed_name, explicit = _select_backend()
    backend = get_backend().name
    print(f"jax {jax.__version__} python {sys.version.split()[0]}")
    print(f"platform={platform} device_kind={device['kind']!r} "
          f"devices={len(devices)}")
    print(f"fiber backend={backend} (selected {sniffed_name!r}, "
          f"{'explicit' if explicit else 'sniffed'}"
          + ("" if backend == sniffed_name else
             f"; no agent answered, fell back to {backend!r}") + ")")
    print("pump engine=" + ("native (libfiberpump, built from pump.cpp)"
                            if _native.available() else
                            "python (no native pump: is g++ missing?)"))
    placed = "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"
    print(f"compile cache={cache_dir} (JAX_COMPILATION_CACHE_DIR {placed})",
          flush=True)

    rehearsal = bool(args.rehearse_cpu)
    if platform != "tpu" and not rehearsal:
        print(f"chip_smoke: jax.devices()[0].platform is {platform!r}, not "
              "'tpu' — no chip, no result. (--rehearse-cpu runs the tiny "
              "CPU rehearsal.)", file=sys.stderr)
        return 2
    if rehearsal and platform != "cpu":
        print("chip_smoke: --rehearse-cpu landed on "
              f"{platform!r}", file=sys.stderr)
        return 2

    ctx = {
        "size": REHEARSAL if rehearsal else FULL,
        "platform": platform,
        "interpret": rehearsal,  # the script's explicit request, CPU only
        "mesh": Mesh(np.asarray(devices), ("pool",)),
        "cache_dir": cache_dir,
        "cache_entries_at_start": (len(os.listdir(cache_dir))
                                   if os.path.isdir(cache_dir) else 0),
    }
    verdicts = {}
    for name, phase in PHASES:
        t0 = time.perf_counter()
        try:
            detail = phase(ctx)
            verdicts[name] = "pass"
        except Exception:  # noqa: BLE001 - reported, and fails the run
            traceback.print_exc()
            detail = "see traceback above"
            verdicts[name] = "FAIL"
        print(f"[{verdicts[name]}] {name} "
              f"({time.perf_counter() - t0:.1f}s): {detail}", flush=True)

    ok = all(v == "pass" for v in verdicts.values())
    print("phases: " + " ".join(f"{k}={v}" for k, v in verdicts.items())
          + f"; total {time.perf_counter() - t_start:.0f}s")
    summary = {"phases": verdicts, "jax": jax.__version__,
               "backend": backend, "rehearsal": rehearsal,
               "compile_cache_hits":
                   DEVICE.snapshot()["compile_cache_hits"]}
    print("summary: " + json.dumps(summary))
    # The verdict: the last line of stdout, these two keys and no other
    # (the driver's contract); everything else is on the lines above.
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
