"""Test harness: the suite runs on the CPU, on a virtual 8-device mesh.

``JAX_PLATFORMS=cpu`` (tier-1 sets it; the default below pins it anyway) and
eight virtual host devices, so the multi-chip shardings (dp/tp/sp/ep/pp
meshes) run exactly as ``dryrun_multichip`` runs them; Pallas kernels run in
interpret mode. Nothing here touches an accelerator: the chip is reached
only through the builder's tool (``python3 chip_smoke.py``, and
tests/test_kernels_compile_v5e.py compiles for a v5e without one).

PSTPU_TEST_TPU=1 leaves the platform alone, for running tests on the chip
machine through the tool (``chiprun -- env PSTPU_TEST_TPU=1 python -m pytest
tests/test_model.py``): every jitted program then compiles for the chip.
Kernel calls that pass ``interpret=True`` stay interpreted even there — the
COMPILED kernels' check is ``chip_smoke.py`` phase K.
"""

import os

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

if not os.environ.get("PSTPU_TEST_TPU"):
    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: every engine test pays fresh jit compiles
# otherwise, which is what kept the fast suite from finishing in CI time.
# Repo-local so the first full run warms every later one.
#
# torch MUST be imported before the cache is enabled: loading it flips
# XLA:CPU's LLVM tuning features (prefer-no-scatter/-gather) for every
# compile AFTER the import, and the cache directory is scoped by a
# writer-config hash computed at enable time (compile_cache.py
# _cpu_feature_scope). A test importing torch mid-session would otherwise
# write feature-flipped AOT entries into a dir whose readers don't expect
# them — cpu_aot_loader then rejects (or worse, SIGILLs on) every load.
try:
    import torch  # noqa: E402,F401
except ImportError:
    # torch-less envs stay self-consistent: the cache scope hash keys on
    # whether torch is in sys.modules, so skipping the eager import here is
    # safe — only the model-family/real-model tests need torch and they
    # guard their own imports
    pass

from production_stack_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache(
    os.path.join(os.path.dirname(__file__), os.pardir, ".cache", "xla")
)

import pytest  # noqa: E402

from production_stack_tpu.engine.step_programs import StepProgramStore  # noqa: E402
from production_stack_tpu.utils import compile_cache  # noqa: E402

# The suite's runners share the store beside that cache, and a runner builds at
# start-up every step program the store lists for its identity
# (step_programs.Preloader): each of the suite's thousand runners would load, on
# threads of its own, whatever every test before it dispatched. The SHARED
# store lists nothing; the loader's tests give their runners a store of their
# own (tests/test_step_program_store.py).
_listed = StepProgramStore.listed
StepProgramStore.listed = lambda self, identity: (
    [] if self.root == compile_cache.step_program_dir() else _listed(self, identity)
)


@pytest.fixture(autouse=True, scope="module")
def _bounded_memory_maps():
    """Keep the one pytest process under ``vm.max_map_count`` (65530).

    XLA:CPU maps ~3.5 regions per live executable, the module-level jits
    (both kernels in interpret mode, the attention oracles) keep one per
    shape they ever saw, and the suite compiles thousands: the count reached
    63.6k at ~85% of the run, the next mmap failed, and XLA segfaulted in
    whatever compiled next (always tests/test_tp_serving.py). Dropping the
    jit caches at a module boundary once the count is high costs a retrace
    of what later modules share (entries come back from the persistent
    cache) and happens about twice per run."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:
        return
    if n_maps > 30000:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return devs
