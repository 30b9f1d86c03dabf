"""Object serialization for the control and data planes.

Policy (reference parity: fiber/popen_fiber_spawn.py:348-354, pool.py:60-63):
use the stdlib ``multiprocessing.reduction.ForkingPickler`` for normal
programs, and fall back to **cloudpickle** when the object graph needs
pickling-by-value (interactive shells, closures, lambdas).

Pickle protocol 5: large contiguous buffers (numpy arrays, bytes) are
captured **out-of-band** via ``buffer_callback`` and framed alongside the
pickle stream instead of being copied through it. In-band protocol-5
pickling costs two full copies of every big array (pickler write +
``BytesIO.getvalue``); the out-of-band envelope costs one gather copy on
``dumps`` and one (writability-preserving) copy on ``loads``. The object
store (fiber_tpu/store) reuses the same envelope as its on-disk and wire
format, so a stored payload is exactly ``loads``-able.

Envelope layout (only produced when at least one buffer went out-of-band;
plain pickles pass through untouched, so old payloads always load)::

    0xFB 0x05 | u32 nbuf | u64 len(pickle) | nbuf * u64 len | pickle | bufs

TPU-native extension: a reducer for ``jax.Array`` so device arrays can ride
the host plane — they are pulled to host memory as numpy on serialize and
re-materialized with ``jax.device_put`` on deserialize (device placement
happens on the *consuming* process, which is what the store's
resolve-on-worker contract needs). Cross-host device state otherwise never
touches pickle: bulk tensors move on the ICI plane via collectives, not
the host plane.
"""

from __future__ import annotations

import io
import pickle
import struct
from typing import Any, List, Tuple

from multiprocessing.reduction import ForkingPickler

from fiber_tpu.utils.misc import is_in_interactive_console

try:
    import cloudpickle
except ImportError:  # pragma: no cover
    cloudpickle = None

#: Envelope magic. Safe discriminator: every pickle this module can emit
#: (protocol >= 2, stdlib or cloudpickle) starts with 0x80.
_OOB_MAGIC = b"\xfb\x05"
_OOB_HEAD = struct.Struct(">IQ")
_OOB_LEN = struct.Struct(">Q")

#: Buffers smaller than this stay in-band: the envelope bookkeeping and
#: the extra frame slices cost more than one memcpy of a small array.
OOB_MIN_BYTES = 64 * 1024


def _jax_array_reduce(arr):
    import jax
    import numpy as np

    host = np.asarray(arr)
    return (_jax_array_rebuild, (host,))


def _jax_array_rebuild(host):
    import jax

    from fiber_tpu.telemetry.device import DEVICE

    # The device boundary of every pickled jax.Array (store resolution,
    # result deserialize): accounted per-site so `fiber-tpu explain`
    # can blame transfer seconds (docs/observability.md). The array
    # lands on THIS process's default device: the chip in the process
    # that holds it, the CPU in a host-plane worker (whose environment
    # the launcher pins to JAX_PLATFORMS=cpu).
    with DEVICE.transfer("deserialize", getattr(host, "nbytes", 0)):
        return jax.device_put(host)


_jax_reducer_registered = False


def register_jax_reducers() -> None:
    """Register the jax.Array reducer on both picklers (idempotent, lazy —
    only ever called once jax is already imported by user code)."""
    global _jax_reducer_registered
    if _jax_reducer_registered:
        return
    import sys

    if "jax" not in sys.modules:
        return
    # Pickle dispatch is exact-type, so the concrete ArrayImpl class must
    # be registered (not the jax.Array ABC). Import it without creating an
    # array: materializing even a scalar would initialize the TPU runtime
    # from whatever process happens to pickle first. A private path (jax
    # 0.9.0): if it moves, this import fails loudly rather than leaving
    # jax.Arrays to pickle device-by-device.
    from jax._src.array import ArrayImpl

    ForkingPickler.register(ArrayImpl, _jax_array_reduce)
    _jax_reducer_registered = True


class _OOBPickler(pickle.Pickler):
    """ForkingPickler's reducer table + protocol-5 ``buffer_callback``
    (ForkingPickler.__init__ takes ``*args`` and can't forward the
    keyword-only callback, so the table copy happens here instead)."""

    def __init__(self, file, buffer_callback) -> None:
        super().__init__(file, 5, buffer_callback=buffer_callback)
        self.dispatch_table = ForkingPickler._copyreg_dispatch_table.copy()
        self.dispatch_table.update(ForkingPickler._extra_reducers)


def _stdlib_pickle(obj: Any, buffer_callback) -> bytes:
    buf = io.BytesIO()
    _OOBPickler(buf, buffer_callback).dump(obj)
    return buf.getvalue()


def _cloud_pickle(obj: Any, buffer_callback) -> bytes:
    return cloudpickle.dumps(obj, protocol=5,
                             buffer_callback=buffer_callback)


def _pickle_oob(obj: Any, pickler) -> Tuple[bytes, List[memoryview]]:
    # Whichever pickler gets the same callback, so there is one
    # out-of-band format.
    register_jax_reducers()
    buffers: List[memoryview] = []

    def keep_oob(pb: pickle.PickleBuffer):
        # Pickler semantics: a FALSY return means out-of-band, truthy
        # means serialize in-band.
        try:
            view = pb.raw()
        except BufferError:
            return True  # non-contiguous: let pickle in-band it
        if view.nbytes < OOB_MIN_BYTES:
            return True
        buffers.append(view)
        return False

    return pickler(obj, keep_oob), buffers


def dumps_oob(obj: Any) -> Tuple[bytes, List[memoryview]]:
    """Serialize to ``(pickle_bytes, out_of_band_buffers)``. The buffers
    are zero-copy views into the caller's objects — valid only while
    those objects live and are not mutated. Raises the usual pickling
    errors; callers that want the cloudpickle fallback use :func:`dumps`.
    """
    return _pickle_oob(obj, _stdlib_pickle)


def pack_envelope(data, buffers) -> bytes:
    """Gather ``(pickle, buffers)`` into the single self-describing byte
    string :func:`loads` accepts (one copy of each buffer)."""
    parts = [
        _OOB_MAGIC,
        _OOB_HEAD.pack(len(buffers), len(data)),
    ]
    parts.extend(_OOB_LEN.pack(b.nbytes if isinstance(b, memoryview)
                               else len(b)) for b in buffers)
    parts.append(data)
    parts.extend(buffers)
    return b"".join(bytes(p) if isinstance(p, memoryview) else p
                    for p in parts)


def is_envelope(data) -> bool:
    return len(data) >= 2 and bytes(data[:2]) == _OOB_MAGIC


def unpack_envelope(data) -> Tuple[memoryview, List[memoryview]]:
    """Split an envelope into ``(pickle_view, buffer_views)`` without
    copying (views into ``data``)."""
    mv = memoryview(data)
    nbuf, ndata = _OOB_HEAD.unpack_from(mv, 2)
    off = 2 + _OOB_HEAD.size
    lens = []
    for _ in range(nbuf):
        (n,) = _OOB_LEN.unpack_from(mv, off)
        lens.append(n)
        off += _OOB_LEN.size
    head = mv[off:off + ndata]
    off += ndata
    bufs = []
    for n in lens:
        bufs.append(mv[off:off + n])
        off += n
    return head, bufs


def dumps(obj: Any) -> bytes:
    """Serialize with the stdlib reducer, or with cloudpickle on failure
    and in interactive sessions; either way protocol 5 with the
    out-of-band buffer envelope for large arrays."""
    by_value = cloudpickle is not None and is_in_interactive_console()
    try:
        data, buffers = _pickle_oob(
            obj, _cloud_pickle if by_value else _stdlib_pickle)
    except (pickle.PicklingError, AttributeError, TypeError):
        if cloudpickle is None or by_value:
            raise
        data, buffers = _pickle_oob(obj, _cloud_pickle)
    if not buffers:
        return data
    return pack_envelope(data, buffers)


def loads(data: Any) -> Any:
    """Inverse of :func:`dumps`; accepts bytes, bytearray or memoryview
    (the framing layer hands over bytearrays). Out-of-band buffers are
    re-materialized as private *writable* copies — handing callers views
    into a shared frame would make every deserialized array aliased and
    read-only, a silent behavior change from in-band pickling."""
    if is_envelope(data):
        head, views = unpack_envelope(data)
        return pickle.loads(head, buffers=[bytearray(v) for v in views])
    return pickle.loads(data)


def dump_to(obj: Any, fileobj) -> None:
    fileobj.write(dumps(obj))
