"""A store of exported step programs, kept beside the compile cache.

The first dispatch of every (batch, chunk, pages) shape used to stop the
engine loop 4.7 s in every process, compile cache warm or not: tracing the
Python step function and lowering it to StableHLO were 77-92% of that (PERF.md
section 6, PR 25). ``jax.export`` serialises what ``jit(f).lower()`` gives, in
~60 KB a program. This module keeps those blobs by a key of everything that
decides the module; ``runner._dispatch`` runs every step program through
``jax.jit(exported.call)``, so a process that finds the blob pays deserialise +
a ~0.05 s wrapper, and the executable still comes from JAX's persistent
compile cache exactly as before. Cold and warm runs compile the same module.

The store lives in ``step_programs/`` inside the directory
``utils/compile_cache.py`` resolved; where that resolved none there is no
store. Nothing is loaded at start-up and nothing is configurable.

No silent fallback: a blob that does not deserialise is deleted, rebuilt and
counted; a program ``jax.export`` refuses is named in ``bypassed`` with the
reason and runs through its plain jit.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import tempfile
from typing import Any, Optional

import jax
from jax import export as jax_export

from production_stack_tpu.utils import compile_cache
from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUFFIX = ".jaxexport"


@functools.cache
def package_digest(root: str = _PACKAGE_ROOT) -> str:
    """sha256 over the CONTENT of every ``.py`` file under ``root``, by
    relative path: two checkouts of one tree at different paths agree, one
    changed byte anywhere in the package does not."""
    h = hashlib.sha256()
    for dirpath, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).replace(os.sep, "/").encode())
            h.update(b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def toolchain(device) -> dict:
    """What lowers and compiles the programs that run on ``device``."""
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": device.platform,
        "device_kind": device.device_kind,
        # names the PJRT plugin and its build (libtpu's, on a TPU)
        "backend": device.client.platform_version,
        # what changes the lowering of the same Python function
        "config": {
            name: repr(getattr(jax.config, name, None))
            for name in (
                "jax_enable_x64", "jax_default_matmul_precision",
                "jax_default_prng_impl", "jax_threefry_partitionable",
                "jax_use_shardy_partitioner", "jax_numpy_dtype_promotion",
                "jax_export_calling_convention_version",
            )
        },
    }


def _sharding_key(x: Any, n_devices: int) -> str:
    sh = getattr(x, "sharding", None)
    if sh is None:
        return "host"
    kind = getattr(sh, "memory_kind", None)
    if isinstance(sh, jax.sharding.NamedSharding):
        return f"named{tuple(sh.mesh.shape.items())}{sh.spec}@{kind}"
    if n_devices == 1 or isinstance(sh, jax.sharding.SingleDeviceSharding):
        return f"single@{kind}"  # which device is the compile cache's business
    return repr(sh)


def abstract_args(args: tuple, n_devices: int) -> dict:
    """The call's tree structure and every leaf's shape, dtype and sharding."""
    leaves, tree = jax.tree.flatten(args)
    return {
        "tree": str(tree),
        "leaves": [
            [list(x.shape), str(x.dtype), _sharding_key(x, n_devices)]
            for x in leaves
        ],
    }


def program_key(fields: dict, device) -> str:
    """One program's file name: a hash of ``fields`` (what the caller knows
    decides the module), the toolchain behind ``device`` and the package's
    content. Nothing in it differs between two processes that would build the
    same module (no ``hash()``, no id, no absolute path)."""
    doc = dict(fields, package=package_digest(), toolchain=toolchain(device))
    blob = json.dumps(doc, sort_keys=True, default=repr).encode()
    return hashlib.sha256(blob).hexdigest()


class StepProgramStore:
    """Exported step programs in one directory, and how often it engaged."""

    def __init__(self, root: str):
        self.root = root
        # read now, as the process starts, not at some later first dispatch:
        # the files on disk are then what this process imported
        package_digest()
        self.hits = 0
        self.writes = 0
        self.errors = 0
        # program name -> why it runs through its plain jit
        self.bypassed: dict[str, str] = {}

    @classmethod
    def beside_compile_cache(cls) -> Optional["StepProgramStore"]:
        root = compile_cache.step_program_dir()
        return cls(root) if root else None

    def path(self, key: str) -> str:
        return os.path.join(self.root, key + SUFFIX)

    def exported(self, key: str, jitted, args: tuple):
        """``(Exported, "hit" | "write" | "error")`` for ``jitted`` called
        with ``args``: read from ``key``'s file, or exported now and written
        (``"error"``: the file was there and did not deserialise). Raises
        what ``jax.export`` raises where it refuses the program."""
        status = "write"
        try:
            with open(self.path(key), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            blob = None
        if blob is not None:
            try:
                exported = jax_export.deserialize(bytearray(blob))
            except Exception as e:  # noqa: BLE001 - whatever refuses the blob
                self.discard(key, f"{type(e).__name__}: {e}")
                status = "error"
            else:
                self.hits += 1
                return exported, "hit"
        blob = jax_export.export(jitted)(*args).serialize()
        self._write(key, blob)
        # through the blob on this side too: a hit runs what the write ran
        return jax_export.deserialize(blob), status

    def _write(self, key: str, blob: bytes) -> None:
        """Temporary file + rename: a reader never sees half a file, and of
        two writers of one key one whole file is left."""
        os.makedirs(self.root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.root, prefix=key[:16] + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self.path(key))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.writes += 1

    def discard(self, key: str, why: str) -> None:
        """A blob that cannot be used is deleted and counted, never skipped."""
        self.errors += 1
        logger.warning("step program %s discarded: %s", key[:16], why[:500])
        try:
            os.unlink(self.path(key))
        except FileNotFoundError:
            pass

    def bypass(self, name: str, why: str) -> None:
        """``name`` could not be exported: it runs through its plain jit, and
        ``/stats`` says so."""
        self.errors += 1
        self.bypassed[name] = why[:500]
        logger.warning("step program %s bypasses the store: %s", name, why[:500])


def store_stats(store: Optional[StepProgramStore]) -> dict:
    """The store's part of the engine's ``/stats`` (zeros and no directory
    where there is no store)."""
    return {
        "step_program_store_dir": store and store.root,
        "step_program_store_hits_total": store.hits if store else 0,
        "step_program_store_writes_total": store.writes if store else 0,
        "step_program_store_errors_total": store.errors if store else 0,
        "step_program_store_bypassed": dict(store.bypassed) if store else {},
    }
