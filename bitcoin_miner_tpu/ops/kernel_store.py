"""Stored exports of the pallas dyn kernel, so a fresh process neither traces
nor lowers it again.

Tracing the dyn SHA-256 kernel (the unrolled rounds of both sieve passes)
and lowering it to a Mosaic module takes 13.8 s + 2.7 s of Python on a TPU
v5e host, in every process that uses it.  jax's persistent compilation cache
cannot save that time: its key is computed from the lowered module.  A
``jax.export`` of the kernel (1.5 MB), serialized once, is deserialized by a
later process in milliseconds; ``jax.jit(exported.call)`` then lowers in
~0.1 s, and its compile is what the persistent cache serves.  The executable
holds the same Mosaic kernel (tests/test_chip_compile.py), serialized at the
forward-compatible Mosaic version that the compiler upgrades as it reads it.

The store serves the single-device dyn kernel (``ops/sweep.py``) and the
sharded one (``parallel/sweep.py``: the same kernel under ``shard_map``
and the pmin cascade), the latter only in a single-process mesh: every
process of a multi-host mesh must enqueue identical collectives, and the
export is not checked across processes.  An operand whose sharding spans
several devices is exported with that sharding, so the export records the
mesh's in-shardings.

The store is the ``kernel_exports/`` subdirectory of the compile cache's
directory (jax's cache reads and evicts only the ``*-cache`` files at its
top level), so it is warm exactly when the compile cache is.  A file is
named by :func:`export_key`, a digest of everything that fixes the lowered
module: the kernel's parameters, its operands (with the mesh shape, axis
names and partition spec of a sharded one), the jax and jaxlib versions,
the platform and device, and the source of the modules traced into the
kernel, so a changed kernel never loads a stale export.  Its content is the
payload's sha256 followed by the payload: a torn or truncated file reads as
a miss, and is rewritten.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
import time
from functools import lru_cache
from pathlib import Path
from typing import Optional, Sequence, Tuple

import jax
from jax import export as jax_export

from ..utils.metrics import METRICS
from ..utils.platform import compile_cache_dir

SUBDIR = "kernel_exports"

#: The modules whose code is traced into the pallas kernels, in ``ops/``.
_SOURCES = ("pallas_sha256.py", "sha256.py")

#: What the sharded kernel traces besides, from the package root: the
#: ``shard_map`` and the collective cascade around the kernel.
MESH_SOURCES = ("parallel/sweep.py",)


def store_dir() -> Path:
    """Where exports are stored: beside the persistent compile cache."""
    return Path(compile_cache_dir()) / SUBDIR


@lru_cache(maxsize=4)
def source_digest(extra: Tuple[str, ...] = ()) -> str:
    """sha256 over the source of the modules traced into the kernel:
    :data:`_SOURCES`, then ``extra`` (paths from the package root)."""
    h = hashlib.sha256()
    here = Path(__file__).resolve().parent
    for name in _SOURCES:
        h.update(name.encode())
        h.update((here / name).read_bytes())
    for name in extra:
        h.update(name.encode())
        h.update((here.parent / name).read_bytes())
    return h.hexdigest()


def runtime_versions() -> dict:
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def _spans_devices(sharding) -> bool:
    return sharding is not None and len(sharding.device_set) > 1


def _operand(spec) -> list:
    """One operand's part of the key: shape and dtype, and for an operand
    sharded over a mesh (a ``NamedSharding``) the mesh's shape, its axis
    names and the operand's partition spec."""
    desc = [list(spec.shape), str(spec.dtype)]
    sharding = spec.sharding
    if _spans_devices(sharding):
        desc.append({
            "mesh": list(sharding.mesh.devices.shape),
            "axes": list(sharding.mesh.axis_names),
            "spec": str(sharding.spec),
        })
    return desc


def export_key(
    params: dict, specs: Sequence, sources: Tuple[str, ...] = ()
) -> str:
    """The store's name for one kernel: a digest of the factory's
    parameters, the operands (:func:`_operand`), the runtime versions, the
    platform and device kind, and :func:`source_digest` over ``sources``
    besides the kernel's own modules."""
    desc = {
        "params": params,
        "operands": [_operand(s) for s in specs],
        "versions": runtime_versions(),
        "platform": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
        "sources": source_digest(sources),
    }
    return hashlib.sha256(
        json.dumps(desc, sort_keys=True).encode()
    ).hexdigest()


def _read(path: Path) -> Optional[jax_export.Exported]:
    """The export stored at ``path``, or None when it is missing, torn, or
    cannot be deserialized."""
    try:
        blob = path.read_bytes()
    except OSError:
        return None
    digest, payload = blob[:32], blob[32:]
    if hashlib.sha256(payload).digest() != digest:
        return None
    try:
        return jax_export.deserialize(bytearray(payload))
    except Exception:  # a file this jax cannot read: a miss, rewritten
        return None


def _write(path: Path, payload: bytes) -> None:
    """Store ``payload`` at ``path`` atomically: a temp file, then
    ``os.replace``.  A store that cannot be written costs only the next
    process's trace, so an OSError is dropped."""
    tmp = None
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(hashlib.sha256(payload).digest())
            f.write(payload)
        os.replace(tmp, path)
        tmp = None
    except OSError:
        pass
    finally:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


class StoredKernel:
    """A jitted kernel served from the store.

    The first call loads the export named by :func:`export_key` for its
    operands (``sweep.kernel_export_hits``), or exports the kernel, which
    traces it once, and stores it (``sweep.kernel_export_misses``).  Every
    call dispatches through ``jax.jit(exported.call)``.
    ``sweep.kernel_build_s`` is the first call's seconds: the export
    loaded or made, lowered, compiled (or loaded from the persistent
    cache) and enqueued.  One kernel serves one operand signature, which
    is what the dyn kernel's callers pass.  ``sources`` are the modules
    traced into ``fn`` besides the kernel's own (:func:`source_digest`).
    """

    def __init__(
        self, fn, params: dict, directory: Path, sources: Tuple[str, ...] = ()
    ) -> None:
        self._fn = fn
        self._params = params
        self._dir = Path(directory)
        self._sources = sources
        self._lock = threading.Lock()
        self._call = None  # guarded-by: _lock

    def __call__(self, *args):
        call = self._call  # unguarded: set once, under the lock, below
        if call is not None:
            return call(*args)
        with self._lock:
            if self._call is not None:
                return self._call(*args)
            t0 = time.monotonic()
            call = jax.jit(self._exported(args).call)
            out = call(*args)
            METRICS.set_gauge("sweep.kernel_build_s", time.monotonic() - t0)
            self._call = call
            return out

    def _exported(self, args) -> jax_export.Exported:  # guarded-by: _lock
        # A sharded operand is exported with its sharding, so the export
        # records the mesh's in-shardings; a single-device one without.
        specs = [
            jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if _spans_devices(a.sharding) else None,
            )
            for a in args
        ]
        key = export_key(self._params, specs, self._sources)
        path = self._dir / f"{key}.jaxexport"
        exp = _read(path)
        if exp is not None:
            METRICS.inc("sweep.kernel_export_hits")
            return exp
        METRICS.inc("sweep.kernel_export_misses")
        exp = jax_export.export(self._fn)(*specs)
        _write(path, exp.serialize())
        return exp


@lru_cache(maxsize=64)
def stored_kernel(fn, sources: Tuple[str, ...] = (), **params) -> StoredKernel:
    """The one :class:`StoredKernel` per jitted kernel ``fn`` (itself
    lru_cached by its factory), so it stays the stable class key of the
    sweep drivers' single-flight build locks.  ``params`` are the
    factory's arguments, for the key; ``sources`` as for
    :class:`StoredKernel`."""
    return StoredKernel(fn, params, store_dir(), sources)
