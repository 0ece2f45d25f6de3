"""Persistent XLA compilation cache wiring.

A serving engine compiles dozens of (batch bucket, pages bucket) program
variants, seconds each on a v5e host (PERF.md "Bring-up" has the count and
the total as set-up time). The reference stack never pays this (vLLM ships precompiled CUDA
kernels); the TPU-native equivalent is JAX's persistent compilation cache,
which serves every repeat compile from disk — across engine restarts, test
runs, and benchmark runs.

Called from engine startup (engine/engine.py) and the test harness
(tests/conftest.py). In Kubernetes the cache directory is a
PVC mounted into the engine pod (helm/templates/deployment-engine.yaml) so
restarts and same-model replicas skip straight to warm starts.
"""

from __future__ import annotations

import hashlib
import os
import sys

from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

_DEFAULT_DIR = os.path.join(
    os.environ.get("PSTPU_CACHE_ROOT", os.path.expanduser("~/.cache")),
    "production_stack_tpu",
    "xla_cache",
)

_enabled_dir: str | None = None
# the UNSCOPED base the enabled dir was derived from: later scoped calls
# must re-derive from this, never from the already-scoped result
_base_dir: str | None = None


def step_program_dir() -> str | None:
    """Where the exported step programs live (engine/step_programs.py):
    ``step_programs/`` inside the cache directory in effect, so whatever
    carries the compile cache from one process to the next (a PVC, a fixed
    path) carries them too. None where no cache directory was resolved."""
    return os.path.join(_enabled_dir, "step_programs") if _enabled_dir else None


def _cpu_feature_scope() -> str:
    """Subdirectory name isolating XLA:CPU AOT entries by writer configuration.

    XLA:CPU serializes executables as AOT results whose embedded machine
    features must match the loading process exactly; a mismatch (different
    host ISA, jaxlib, or tuning flags flipped by co-loaded frameworks such
    as TensorFlow/torch initializing LLVM differently) makes
    cpu_aot_loader.cc reject — or worse, mis-accept — every entry. Keying
    the directory on those inputs means a process only ever reads entries
    written by an identically-configured process.
    """
    import jax
    import jaxlib

    parts = [
        jax.__version__,
        jaxlib.__version__,
        os.environ.get("XLA_FLAGS", ""),
        ",".join(sorted(m for m in ("tensorflow", "torch") if m in sys.modules)),
    ]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    parts.append(line.strip())
                    break
    except OSError:
        import platform

        parts.append(platform.processor() or platform.machine())
    digest = hashlib.sha1("\n".join(parts).encode()).hexdigest()[:12]
    return f"cpu-{digest}"


def enable_persistent_cache(
    cache_dir: str | None = None, scope: str | None = None
) -> str | None:
    """Point JAX's compilation cache at a persistent directory. Idempotent.

    Resolution order: explicit arg > $PSTPU_COMPILE_CACHE_DIR > JAX's own
    $JAX_COMPILATION_CACHE_DIR (left untouched if set) > ~/.cache default.
    Set PSTPU_COMPILE_CACHE_DIR=off to disable. Returns the directory in
    effect, or None when disabled.

    ``scope`` appends a subdirectory — multi-host serving passes its process
    topology (engine/engine.py): an executable compiled for one topology
    must never be served to another (same device ids, different process
    boundaries — observed to hang the jax.distributed rendezvous), and
    per-process subdirs also keep concurrent writers apart.
    """
    global _enabled_dir, _base_dir
    import jax

    env = os.environ.get("PSTPU_COMPILE_CACHE_DIR")
    cache_dir = cache_dir or env
    if cache_dir in ("off", "none", "0"):
        return None
    if cache_dir is None:
        # respect a cache dir the operator already configured via JAX's env
        cache_dir = jax.config.jax_compilation_cache_dir
        if cache_dir is not None and cache_dir == _enabled_dir:
            # already wired by an earlier call in this process (conftest,
            # bench, a previous engine): the configured dir is the SCOPED
            # result, and re-scoping it would nest cpu-<digest> subdirs one
            # level deeper per engine construction — every engine then
            # compiles against a brand-new empty cache (observed: a
            # 23-level-deep .cache/xla chain and a tier-1 suite that
            # recompiled cold for every LLMEngine test)
            if not scope:
                return _enabled_dir
            # a scoped request (multi-host topology) must derive from the
            # ORIGINAL base, not the already-scoped result
            if _base_dir is not None:
                cache_dir = _base_dir
    if cache_dir is None:
        # Default-on only for TPU backends, where a cold compile costs
        # 20-40 s per program. XLA:CPU AOT cache loads are NOT robust: an
        # entry written by a process with different CPU tuning features
        # (e.g. TensorFlow loaded via sentence-transformers flips
        # prefer-no-scatter/-gather) fails the loader's machine check and
        # can spin for minutes per entry — observed hanging engine startup.
        # CPU users opt in with an explicit dir (tests/conftest.py does).
        if jax.default_backend() != "tpu":
            return None
        cache_dir = _DEFAULT_DIR
    _base_dir = cache_dir
    if scope:
        cache_dir = os.path.join(cache_dir, scope)
    try:
        if jax.default_backend() == "cpu":
            # Explicitly-enabled CPU caches (tests, dryruns) get a
            # writer-config scope so feature-mismatched AOT entries are never
            # even offered to the loader (see _cpu_feature_scope).
            cache_dir = os.path.join(cache_dir, _cpu_feature_scope())
    except Exception as e:  # noqa: BLE001 - no backend yet: don't risk a shared dir
        logger.warning("compilation cache disabled (%s: %s)", type(e).__name__, e)
        return None
    if _enabled_dir == cache_dir:
        return _enabled_dir
    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # default thresholds (1 s / 0 bytes) skip exactly the small programs
        # whose compiles add up across a 150-test suite — cache everything
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _enabled_dir = cache_dir
        logger.info("persistent XLA compilation cache at %s", cache_dir)
    except Exception as e:  # noqa: BLE001 - cache is an optimization, never fatal
        logger.warning("compilation cache disabled (%s: %s)", type(e).__name__, e)
        return None
    return _enabled_dir
