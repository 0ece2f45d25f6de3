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
store. Nothing is configurable.

What is left of a warm first dispatch, 0.3-1.4 s a shape, is the host building
an executable on the engine loop's thread: deserialise, lower the wrapper, read
the executable from the compile cache and load it (PERF.md section 6, PR 50).
So the store also keeps a listing by IDENTITY, a hash of every field of a
program's key that does not name the batch's shape: one small file a program
in ``<identity>.list/``, with its name, ``family``, ``sig`` and where in a
run it was first met. A process that finds its identity listed builds those
executables at start-up on a few threads of its own (``Preloader``), beside
the drawing of the weights, in first-met order; ``runner._dispatch`` then
takes a built one for a shape's first dispatch, which costs its run alone.
An empty listing, no store or a mesh over several processes loads nothing.

No silent fallback: a blob that does not deserialise is deleted, rebuilt and
counted; a program ``jax.export`` refuses is named in ``bypassed`` with the
reason and runs through its plain jit; a listed program that cannot be found,
built or called is taken off the listing, counted, and its dispatch runs the
path of a process that preloaded nothing.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Callable, Optional

import jax
from jax import export as jax_export

from production_stack_tpu.engine import devicemon
from production_stack_tpu.utils import compile_cache
from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUFFIX = ".jaxexport"
LISTING = ".list"


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

    def exported(self, key: str, jitted, args: tuple, listed=None):
        """``(Exported, "hit" | "write" | "error")`` for ``jitted`` called
        with ``args``: read from ``key``'s file, or exported now and written
        (``"error"``: the file was there and did not deserialise). Raises
        what ``jax.export`` raises where it refuses the program. ``listed``,
        ``(identity, entry)``, is what ``note`` keeps of a program served."""
        exported, status = self._exported(key, jitted, args)
        if listed is not None:
            self.note(listed[0], key, listed[1])
        return exported, status

    def _exported(self, key: str, jitted, args: tuple):
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
        _write_whole(self.path(key), blob)
        self.writes += 1

    def _entry_path(self, identity: str, key: str) -> str:
        return os.path.join(self.root, identity + LISTING, key + ".json")

    def note(self, identity: str, key: str, entry: dict) -> None:
        """Keep what a later process of ``identity`` needs to find ``key``
        again and build it before traffic asks: ``entry`` (the program's
        name, ``family``, ``sig`` and ``order``, how many shapes its process
        had dispatched before it). The first process to meet a program lists
        it; its entry stays."""
        path = self._entry_path(identity, key)
        if os.path.exists(path):
            return
        try:
            _write_whole(path, json.dumps(entry).encode())
        except OSError as e:
            # (a store on a read-only volume still serves its blobs)
            self.errors += 1
            logger.warning("step program %s not listed: %s: %s",
                           key[:16], type(e).__name__, e)

    def listed(self, identity: str) -> list[dict]:
        """The entries kept for ``identity``, each with its ``key``, in the
        order in which a run meets them. One that cannot be read is deleted
        and counted."""
        try:
            names = os.listdir(os.path.join(self.root, identity + LISTING))
        except FileNotFoundError:
            return []
        entries = []
        for name in names:
            key, ext = os.path.splitext(name)
            if ext != ".json":
                continue  # a writer's temporary file
            try:
                with open(self._entry_path(identity, key), "rb") as f:
                    entry = json.load(f)
                entries.append({
                    "key": key, "program": str(entry["program"]),
                    "family": str(entry["family"]), "sig": tuple(entry["sig"]),
                    "order": int(entry["order"]),
                })
            except FileNotFoundError:
                continue  # another process took it off meanwhile
            except (ValueError, KeyError, TypeError) as e:
                self.errors += 1
                logger.warning("step program listing %s/%s discarded: %s: %s",
                               identity[:16], key[:16], type(e).__name__, e)
                self.unlist(identity, key)
        return sorted(entries, key=lambda e: (e["order"], e["key"]))

    def unlist(self, identity: str, key: str) -> None:
        try:
            os.unlink(self._entry_path(identity, key))
        except FileNotFoundError:
            pass
        except OSError as e:
            logger.warning("step program %s stays listed: %s: %s",
                           key[:16], type(e).__name__, e)

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


class _Program:
    """One listed program on its way to an executable. ``state``: "queued",
    "loading" (a worker builds it; ``done`` is set when it has), "ready"
    (``program`` is the executable), "failed", or "taken" (a dispatch came
    before a worker did, or has the executable now)."""

    __slots__ = ("key", "name", "wrap", "state", "program", "done")

    def __init__(self, key: str, name: str, wrap: Callable):
        self.key, self.name, self.wrap = key, name, wrap
        self.state, self.program = "queued", None
        self.done = threading.Event()


class Preloader:
    """Builds the executables of the step programs the store lists for one
    identity, off the caller's thread and in first-met order, and hands each
    to the first dispatch that asks for its key (``take``).

    What a worker does for a program is what its first dispatch would have
    done on the engine loop's thread, less the run: deserialise the blob, jit
    ``exported.call`` the way the runner jits that step, lower it on the
    abstract arguments the blob describes, compile (a read of the persistent
    compile cache where an earlier process compiled it)."""

    # Threads that build. Two, by measurement on the chip
    # (scripts/preload_workers.py over jamba2-3b.chat's 33 listed programs,
    # the dearest listing of the four cells; PERF.md section 6, PR 50): the
    # loader's wall is 22.6 s on one thread, 12.7 on two, 9.8 / 9.0 / 8.6 on
    # three / four / eight (a program costs its worker ~0.06 s of lowering,
    # which holds the interpreter, and ~0.57 s of cache read and load, which
    # overlap up to about three), while the engine beside it is built in
    # 2.45 s with no loader and 2.54 / 2.70 / 3.13 / 3.32 / 4.71 s beside 1 /
    # 2 / 3 / 4 / 8. With two, no first dispatch of a warm run overtook the
    # loader or waited for it (2.6 programs/s; the ramp asks for 17 shapes in
    # ~10 s); one thread builds 1.45 a second; more than two only slow the
    # start-up they share the host with.
    WORKERS = 2

    def __init__(self, store: Optional[StepProgramStore], fields: dict, mesh,
                 wrapper: Callable[[str, tuple], tuple]):
        """``fields``: every field of a program's key that does not name the
        batch's shape. ``wrapper(family, sig)``: a listed program's name and
        the function that jits an exported call as the runner jits that step
        (it raises for a ``sig`` this runner does not build)."""
        self.store, self.mesh = store, mesh
        self.identity = program_key(fields, mesh.devices.flat[0])
        self.listed = 0       # programs the listing held as this process started
        self.loaded = 0       # executables built
        self.failed = 0       # listed, and not found, built or callable
        self.served = 0       # first dispatches that ran a preloaded executable
        # programs still to build when the first dispatch came (None: none came yet)
        self.pending_at_first_dispatch: Optional[int] = None
        self._programs: dict[str, _Program] = {}
        self._queue: list[_Program] = []
        self._lock = threading.Lock()
        self._t0 = self._t1 = time.perf_counter()
        self._threads: list[threading.Thread] = []
        self._working = 0
        if store is None or jax.process_count() != 1:
            return
        for entry in store.listed(self.identity):
            self.listed += 1
            try:
                name, wrap = wrapper(entry["family"], entry["sig"])
                if name != entry["program"]:
                    raise ValueError(f"the runner calls it {name}")
            except Exception as e:  # noqa: BLE001 - whatever refuses the entry
                self._fail(entry["key"], f"{entry['program']}: {type(e).__name__}: {e}")
                continue
            prog = _Program(entry["key"], name, wrap)
            self._programs[prog.key] = prog
            self._queue.append(prog)
        if not self._queue:
            return
        devicemon.install_compile_listener()
        self._threads = [
            threading.Thread(target=self._work, name=f"pstpu-preload-{i}", daemon=True)
            for i in range(min(self.WORKERS, len(self._queue)))
        ]
        self._working = len(self._threads)
        for t in self._threads:
            t.start()

    def _work(self) -> None:
        while True:
            with self._lock:
                prog = next((p for p in self._queue if p.state == "queued"), None)
                if prog is None:
                    self._working -= 1
                    if not self._working:
                        self._t1 = time.perf_counter()
                        logger.info(
                            "preloaded %d of %d listed step programs in %.2f s "
                            "(%d failed or stale)", self.loaded, self.listed,
                            self._t1 - self._t0, self.failed)
                    return
                prog.state = "loading"
            t0, state = time.perf_counter(), "failed"
            try:
                with devicemon.capture_first_dispatch() as phases:
                    prog.program = self._build(prog)
                state = "ready"
                logger.info(
                    "preloaded %s %s: %.2f s (lower %.2f, compile or load %.2f, "
                    "cache %s)", prog.name, prog.key[:12],
                    time.perf_counter() - t0, phases["lower"], phases["compile"],
                    "hit" if phases["cache_hits"] else "miss")
            except Exception as e:  # noqa: BLE001 - the dispatch builds it itself
                self._fail(prog.key, f"{prog.name}: {type(e).__name__}: {e}")
            finally:
                # whatever happened, a dispatch that waits for it goes on
                with self._lock:
                    self.loaded += state == "ready"
                    prog.state = state
                prog.done.set()

    def _build(self, prog: _Program):
        store = self.store
        try:
            with open(store.path(prog.key), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            raise LookupError("listed, and its blob is gone") from None
        try:
            exported = jax_export.deserialize(bytearray(blob))
        except Exception as e:  # noqa: BLE001 - whatever refuses the blob
            store.discard(prog.key, f"{type(e).__name__}: {e}")
            raise
        # the blob describes its own arguments: nothing here can drift from it
        args, kwargs = jax.tree.unflatten(exported.in_tree, [
            jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding)
            for aval, sharding in zip(
                exported.in_avals, exported.in_shardings_jax(self.mesh))
        ])
        wrap, prog.wrap = prog.wrap, None  # (it holds the runner: not past its use)
        return wrap(exported.call).lower(*args, **kwargs).compile()

    def _fail(self, key: str, why: str) -> None:
        """A listed program that cannot be used is taken off the listing and
        counted; what dispatches its shape lists it again if it serves."""
        with self._lock:
            self.failed += 1
        logger.warning("listed step program %s not preloaded: %s", key[:12], why[:500])
        self.store.unlist(self.identity, key)

    def take(self, key: str):
        """The executable built for ``key``, for its shape's first dispatch,
        or None: not listed, failed, or still queued (the dispatch then
        builds it itself, as it would have, and no worker will). One that a
        worker is building is waited for: nothing is built twice."""
        with self._lock:
            if self.pending_at_first_dispatch is None:
                self.pending_at_first_dispatch = sum(
                    p.state in ("queued", "loading") for p in self._queue)
            prog = self._programs.get(key)
            if prog is None or prog.state not in ("loading", "ready"):
                if prog is not None and prog.state == "queued":
                    prog.state = "taken"
                return None
        prog.done.wait()
        with self._lock:
            program, prog.program = prog.program, None
            if prog.state == "ready":
                prog.state = "taken"
        return program

    def call(self, key: str, args: tuple):
        """``(executable, its result for args)`` where one was built for
        ``key``, for its shape's first dispatch, else None. An executable
        checks its arguments before anything runs or is donated: one that
        refuses them is counted and dropped, and the dispatch builds its
        program as if nothing had been preloaded."""
        program = self.take(key)
        if program is None:
            return None
        try:
            out = jax.block_until_ready(program(*args))
        except Exception as e:  # noqa: BLE001 - whatever refuses the call
            self._fail(key, f"refused at its call: {type(e).__name__}: {e}")
            return None
        self.served += 1
        return program, out

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Whether every worker has ended (after waiting ``timeout`` s)."""
        for t in self._threads:
            t.join(timeout)
        return not any(t.is_alive() for t in self._threads)

    def stats(self) -> dict:
        """The loader's part of the engine's ``/stats``."""
        with self._lock:
            working = self._working > 0
            return {
                "step_program_preload_listed": self.listed,
                "step_program_preloaded_total": self.loaded,
                "step_program_preload_failed_total": self.failed,
                "step_program_preload_served_total": self.served,
                "step_program_preload_seconds": round(
                    (time.perf_counter() if working else self._t1) - self._t0, 4),
                "step_program_preload_pending_at_first_dispatch":
                    self.pending_at_first_dispatch,
            }


def _write_whole(path: str, data: bytes) -> None:
    """Temporary file + rename: a reader never sees half a file, and of two
    writers of one path one whole file is left."""
    root = os.path.dirname(path)
    os.makedirs(root, exist_ok=True)
    fd, tmp = tempfile.mkstemp(
        dir=root, prefix=os.path.basename(path)[:16] + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


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
