"""Ahead-of-time compile check: each cell's program, at its real sizes, for a
described v5e (``v5e:2x2``), and what ``memory_analysis()`` says it holds on
a chip. A later PR that adds a cell reckons its bytes here, against the 25%
floor and the 15.75 GiB the runtime leaves of a chip, before it spends chip
time. Compiles, not chip runs: no time and no result comes from here.

Run by hand, in one process (minutes: about a quarter of one for the ES
program and one for each LM program):

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_aot_compile.py -q -s
    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_aot_compile.py -q -s -k ring

The topology is described inside a fixture, never while a module is
imported (only one process may load libtpu).
"""
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, ROOT)

GIB = 2.0 ** 30
CHIP_USABLE_GIB = 15.75
FLOOR_SHARE = 0.25


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [w["name"] for w in bench["workloads"]]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("workload", cells())
def test_cell_compiles_for_v5e_and_fits(topo, workload):
    import run as harness

    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, _ = harness.cell_files(bench, workload)
    runner = harness.load_runner(cfg)
    devices = list(topo.devices)[:int(cell["chips"])]
    compiled = runner.aot_lower(cfg, traffic, devices).compile()
    mem = compiled.memory_analysis()
    parts = {"arguments": mem.argument_size_in_bytes,
             "outputs": mem.output_size_in_bytes,
             "temporaries": mem.temp_size_in_bytes,
             "aliased": mem.alias_size_in_bytes}
    total = (parts["arguments"] + parts["outputs"] + parts["temporaries"]
             - parts["aliased"]) / GIB
    print(f"\n{workload}: {total:.2f} GiB a chip ("
          + ", ".join(f"{k} {v / GIB:.2f}" for k, v in parts.items()) + ")")
    peaks = harness.read_json(os.path.join(PERFBENCH, "peaks.json"))
    floor = FLOOR_SHARE * peaks["TPU v5 lite"]["hbm_bytes"] / GIB
    assert total <= CHIP_USABLE_GIB, "does not fit a v5e chip"
    assert total >= floor, f"under the {floor:.2f} GiB floor: too small a cell"
