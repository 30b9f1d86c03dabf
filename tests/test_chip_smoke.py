"""chip_smoke.py and the rules it pins, as far as a machine without a
chip can check them: the default invocation refuses to run here; the
explicit CPU rehearsal runs every phase end to end (so the script cannot
rot between chip runs) and a second run hits the compile cache; the
cache lives at one fixed place unless placed from outside; host-plane
workers are pinned to the CPU; and the Pallas kernels compile through
Mosaic for a v5e (ahead of time — libtpu needs no chip for that)."""

import json
import os
import subprocess
import sys

import pytest

import fiber_tpu
from tests import targets

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _python(args, env=None, cwd=REPO, timeout=600):
    full_env = dict(os.environ)
    full_env.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full_env,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_to_run_without_a_chip():
    """No accelerator: non-zero exit, the reason on stderr, and no
    result line — never a CPU run under the chip's name."""
    proc = _python([SMOKE], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "not 'tpu'" in proc.stderr
    assert "platform=cpu" in proc.stdout       # the header still says what
    assert "compile cache=" in proc.stdout     # it found
    assert not any(line.startswith("{")
                   for line in proc.stdout.splitlines())


def test_smoke_cpu_rehearsal_end_to_end_and_second_run_hits_cache(
        tmp_path):
    """The explicit rehearsal drives every phase (tiny shapes, 8 virtual
    devices, kernels interpreted at the script's own request); a second
    invocation against the same cache directory reports cache hits."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jaxcache")}
    verdicts = []
    for _ in range(2):
        proc = _python([SMOKE, "--rehearse-cpu"], env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = proc.stdout.splitlines()
        # The verdict is the LAST line and holds exactly "ok" and
        # "device" (platform, kind, count) — the driver's contract; the
        # rest is on the summary line before it.
        assert lines[-2].startswith("summary: ")
        verdicts.append((json.loads(lines[-1]),
                         json.loads(lines[-2][len("summary: "):])))
    (first, first_summary), (second, second_summary) = verdicts
    assert first == second == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    assert first_summary["rehearsal"] is True
    assert set(first_summary["phases"].values()) == {"pass"}
    assert len(first_summary["phases"]) == 7
    assert first_summary["compile_cache_hits"] == 0
    assert second_summary["compile_cache_hits"] > 0


_CACHE_PROBE = (
    "import os, sys; sys.path.insert(0, {repo!r}); "
    "import fiber_tpu.utils.jaxcompat as jc, jax; "
    "print(jax.config.jax_compilation_cache_dir); "
    "print(jc.ensure_compile_cache())"
)


def test_compile_cache_place(tmp_path):
    """Unset, the cache is ONE fixed directory inside the checkout —
    identical across fresh interpreters and working directories (the
    path is part of what a hit depends on). Placed from outside, the
    library leaves jax's own setting alone."""
    code = _CACHE_PROBE.format(repo=REPO)
    unset = dict(os.environ)
    unset.pop("JAX_COMPILATION_CACHE_DIR", None)
    seen = set()
    for cwd in (REPO, str(tmp_path)):
        proc = subprocess.run([sys.executable, "-c", code], cwd=cwd,
                              env=unset, capture_output=True, text=True,
                              timeout=120)
        assert proc.returncode == 0, proc.stderr
        configured, returned = proc.stdout.split()
        assert configured == returned
        seen.add(configured)
    assert seen == {os.path.join(REPO, ".jax_cache")}

    outside = str(tmp_path / "elsewhere")
    proc = _python(["-c", code], env={"JAX_COMPILATION_CACHE_DIR": outside},
                   cwd=str(tmp_path), timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [outside, outside]


def test_host_worker_is_pinned_to_cpu_and_unpickles_arrays_there(
        monkeypatch):
    """One process per chip: a Pool worker started without a device
    hint gets JAX_PLATFORMS=cpu whatever the master's environment says,
    so it cannot take the chip, and a pickled jax.Array lands on its
    CPU device."""
    import jax.numpy as jnp

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # what a TPU host exports
    fiber_tpu.init()
    arr = jnp.arange(16.0)
    with fiber_tpu.Pool(2) as pool:
        views = pool.map(targets.jax_worker_view, [arr, arr])
    for view in views:
        assert view["pid"] != os.getpid()
        assert view["JAX_PLATFORMS"] == "cpu"
        assert view["is_jax_array"] is True
        assert view["platforms"] == ["cpu"]
        assert view["default_backend"] == "cpu"
        assert view["sum"] == 120.0


def test_device_hinted_job_inherits_the_launching_environment(
        monkeypatch):
    """...and a job that DOES carry a device hint is not pinned: it
    inherits what the master exports (on a pod host, the accelerator)."""
    from fiber_tpu.backends import get_backend
    from fiber_tpu.launcher import JobLauncher

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    launcher = JobLauncher.__new__(JobLauncher)
    launcher.backend = get_backend()
    plain = fiber_tpu.Process(target=targets.noop)
    hinted = fiber_tpu.Process(target=targets.noop,
                               meta_hints={"device": True})
    assert launcher._job_spec(plain, ["true"]).env["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in launcher._job_spec(hinted, ["true"]).env


_AOT = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as err:
    print("NO_TOPOLOGY", repr(err)); sys.exit(0)
from fiber_tpu.ops.pallas_attention import flash_attention_lse
sharding = SingleDeviceSharding(topo.devices[0])
def loss(q, k, v):
    out, lse = flash_attention_lse(q, k, v, causal=True, window=1024)
    return jnp.sum(out * out) + jnp.sum(lse)
S, H, KVH, D = 2048, 8, 2, 32
args = [jax.ShapeDtypeStruct((S, h, D), jnp.float32, sharding=sharding)
        for h in (H, KVH, KVH)]
lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
    lowering_platforms=("tpu",))
print("CUSTOM_CALLS", lowered.as_text().count("tpu_custom_call"))
compiled = lowered.compile()
print("COMPILED", topo.devices[0].device_kind)
import re
print("KERNELS", " ".join(sorted(re.findall(
    r"^\s*%([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
    compiled.as_text(), re.M))))

# The ES engine's pair step (ops/es.py pair_fitness), one generation as
# run_fused compiles it: 128 pairs of a 14-128-128-16 policy.
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from fiber_tpu.models import MLPPolicy, ParamBipedWalker
from fiber_tpu.ops.es import EvolutionStrategy, build_fused_runner
PAIRS = 128
policy = MLPPolicy(ParamBipedWalker.obs_dim, ParamBipedWalker.act_dim,
                   hidden=(128, 128))
course = jnp.zeros((6,), jnp.float32)
es = EvolutionStrategy(
    lambda theta, key: ParamBipedWalker.rollout_p(
        policy.act, course, theta, key, 20),
    policy.dim, 2 * PAIRS, optimizer="adam",
    mesh=Mesh(np.asarray(topo.devices[:1]), ("pool",)))
everywhere = NamedSharding(es.mesh, PartitionSpec())
vec = jax.ShapeDtypeStruct((policy.dim,), jnp.float32, sharding=everywhere)
text = build_fused_runner(es._device_step_fn, es.mesh, 4, 1).lower(
    vec, vec, vec,
    jax.ShapeDtypeStruct((), jnp.float32, sharding=everywhere),
    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=everywhere),
).compile().as_text()
# every member's own vector or layer, in either order of a layer's axes
whole = {{f"f32[{{2 * PAIRS}},{{policy.dim}}]", f"f32[{{PAIRS}},2,{{policy.dim}}]"}}
for a, b in zip(policy.sizes, policy.sizes[1:]):
    whole |= {{f"f32[{{2 * PAIRS}},{{a}},{{b}}]", f"f32[{{2 * PAIRS}},{{b}},{{a}}]",
              f"f32[{{PAIRS}},2,{{a}},{{b}}]", f"f32[{{PAIRS}},2,{{b}},{{a}}]"}}
in_fusion, held = False, 0
for line in text.splitlines():
    if line.endswith("{{"):
        in_fusion = "fused_computation" in line.split("(")[0]
    made = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])", line)
    held += bool(made and not in_fusion and made.group(1) in whole)
loops = [line for line in text.splitlines() if " while(" in line]
print("ES_PAIR_STEP whole_arrays", held, "loops_carrying_noise",
      sum(f"f32[{{PAIRS}},128,128]" in line for line in loops))
"""


@pytest.fixture(scope="module")
def aot_v5e():
    """The flash kernels' gradient program compiled for a described
    v5e, once for the tests below. The TPU's library is loaded by the
    child this fixture starts, never while a module is imported."""
    proc = _python(["-c", _AOT.format(repo=REPO)], timeout=600)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip("libtpu gives no compile-only v5e topology here: "
                    + proc.stdout.strip()[-300:])
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    return proc.stdout


def test_flash_kernels_compile_through_mosaic_for_v5e(aot_v5e):
    """Forward, dq and dkv kernels lower and compile for a TPU v5e with
    the installed libtpu — no chip needed for compilation, so a block
    spec or layout Mosaic refuses fails HERE, not on the first chip run.
    (GQA 8/2, head_dim 32, sliding window: the awkward corners.)"""
    assert "CUSTOM_CALLS 3" in aot_v5e, aot_v5e
    assert "COMPILED TPU v5" in aot_v5e, aot_v5e


def test_es_pair_step_compiled_for_v5e_holds_no_member_sized_array(aot_v5e):
    """In the ES engine's pair step as the v5e's compiler leaves it no
    instruction outside a fusion makes an array with a row per member
    (``thetas`` or a layer of it, as ``(2 * pairs, ...)`` or ``(pairs,
    2, ...)``), and the rollout's loop carries the noise's 128x128
    layer, ``(pairs, 128, 128)``: the sum ``base + scale * noise`` lives
    inside the step's fusion and was not hoisted out of the loop, where
    it would be every member's weights again (PERF.md, PR 30)."""
    (line,) = [ln for ln in aot_v5e.splitlines()
               if ln.startswith("ES_PAIR_STEP")]
    assert line.split()[1:] == ["whole_arrays", "0",
                                "loops_carrying_noise", "1"], line


def test_flash_kernels_are_named_in_the_compiled_v5e_program(aot_v5e):
    """The three ``pallas_call``s carry ``name=``, and the compiled
    program's instructions are called after them: a device trace shows
    forward, dq and dkv apart by name (and the benchmark's roofline
    readers, which look for ``attn`` in the name, still find them)."""
    (line,) = [ln for ln in aot_v5e.splitlines()
               if ln.startswith("KERNELS")]
    kernels = line.split()[1:]
    assert len(kernels) == 3, line
    stems = sorted(k.split(".")[0] for k in kernels)
    assert stems == ["flash_attn_dkv", "flash_attn_dq", "flash_attn_fwd"]
