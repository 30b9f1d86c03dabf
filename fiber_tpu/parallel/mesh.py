"""Mesh construction helpers.

The device plane uses one process-wide default mesh: a 1-D ``pool`` axis
over all addressable devices (task parallelism is embarrassingly parallel,
so a flat axis maps it; richer meshes can be passed explicitly anywhere a
mesh is accepted). ``mesh_shape`` in the config overrides the topology,
e.g. ``"4x2"`` for a (pool, model) grid.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence, Tuple

_default_mesh = None
_lock = threading.Lock()

POOL_AXIS = "pool"


def mesh_from_config() -> Optional[Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    from fiber_tpu import config

    shape_s = config.get().mesh_shape
    if not shape_s:
        return None
    dims = tuple(int(d) for d in shape_s.lower().split("x"))
    names = (POOL_AXIS, "model", "data")[: len(dims)]
    return dims, names


def make_mesh(shape: Optional[Sequence[int]] = None,
              names: Optional[Sequence[str]] = None):
    import jax
    from jax.sharding import Mesh
    import numpy as np

    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    devices = jax.devices()
    if shape is None:
        cfg = mesh_from_config()
        if cfg is not None:
            shape, names = cfg
        else:
            shape, names = (len(devices),), (POOL_AXIS,)
    names = tuple(names or (POOL_AXIS,))
    arr = np.asarray(devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(arr, names)


def default_mesh():
    """Process-wide default: all devices on one ``pool`` axis."""
    global _default_mesh
    with _lock:
        if _default_mesh is None:
            _default_mesh = make_mesh()
        return _default_mesh


def is_multidevice_cpu(mesh) -> bool:
    """True when ``mesh`` spans >1 CPU device — the configuration where
    XLA's in-process collective rendezvous can DEADLOCK if async
    dispatch interleaves two program generations over the CPU client's
    fixed thread pool (core-dump-verified on a 1-core dev box). Decides
    on the mesh's OWN devices, not the default backend: an explicit CPU
    mesh under an accelerator default must still count."""
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return False
    try:
        dev = mesh.devices.flat[0]
    except (AttributeError, IndexError):
        return False
    return getattr(dev, "platform", "") == "cpu"


def cpu_step_barrier(mesh, out) -> None:
    """Serialize multi-step Python loops on a multi-device CPU mesh:
    ``block_until_ready(out)`` so only ONE program generation is ever
    in flight (collective thunks block their pool threads in the
    rendezvous; a second interleaved generation can exhaust the pool —
    mutual waiting, then XLA's terminate-timeout abort). Costs nothing
    measurable on CPU (compute-bound); a TPU mesh keeps async
    dispatch. Every ES-family ``step()`` and ``make_train_step`` call
    this; fused ``lax.scan`` drivers are structurally immune."""
    if is_multidevice_cpu(mesh):
        import jax

        jax.block_until_ready(out)


def reset_default_mesh() -> None:
    global _default_mesh
    with _lock:
        _default_mesh = None
