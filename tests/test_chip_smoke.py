"""chip_smoke.py's phases, end to end, on the CPU with llama-debug.

The script itself has no switch for this: its ``main()`` insists on a TPU and
must exit non-zero without one. The phase functions take the expected
platform, so the test passes ``"cpu"`` explicitly — same children (the real
``engine.api_server`` and ``router.app`` entry points), same traffic shape,
same checks, toy sizes; kernels in interpret mode.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture()
def cpu_children(monkeypatch):
    # children inherit the environment; the sandbox holds JAX to the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def test_phases_pass_on_cpu_with_llama_debug(tmp_path, cpu_children):
    out = str(tmp_path)
    dev = chip_smoke.phase_probe(out, "cpu")
    assert dev["platform"] == "cpu" and dev["count"] >= 1

    rep = chip_smoke.phase_k(out, "cpu", interpret=True)
    assert rep["ok"] and rep["interpret"] and len(rep["cases"]) >= 1
    assert rep["steps"]["prefill"] == rep["steps"]["decode"] == "pallas_interpret"
    assert all(reason for reason in rep["excluded"].values())

    res = chip_smoke.phase_a(out, "cpu", chip_smoke.Serving(
        name="A", model="llama-debug",
        engine_args=("--max-model-len", "256", "--prefill-chunk", "32",
                     "--num-pages", "64"),
        long_prompt=80, burst_prompt=20, burst_step=3, bursts=(4, 4), gen=6,
        start_timeout=120.0, expect_decode=("xla",),
    ))
    assert res["platform"] == "cpu" and res["requests"] == 3 + 1 + 4 + 1 + 4
    assert res["attn_impl_prefill"] == res["attn_impl_decode"] == "xla"
    assert res["attn_impl_reason"] == "no TPU backend (platform=cpu)"
    assert res["cached_tokens_on_repeat"] >= 64 and res["compile_events"] > 0
    # every child was waited on: nothing is left holding a device
    assert chip_smoke._children == []


def test_a_phase_fails_on_the_wrong_platform(tmp_path, cpu_children):
    with pytest.raises(chip_smoke.SmokeFailure, match="platform is 'cpu'"):
        chip_smoke.phase_probe(str(tmp_path), "tpu")


def _run_main(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_main_exits_nonzero_without_a_tpu_and_prints_no_result(tmp_path):
    # a copy beside a link to the package: main() writes its output
    # directory next to the script, so the repo's own chiprun_out/ (which
    # may hold a real chip run) is left alone
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    os.symlink(os.path.join(REPO, "production_stack_tpu"),
               tmp_path / "production_stack_tpu")
    r = _run_main(tmp_path)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
    with open(tmp_path / "chiprun_out" / "chip_smoke" / "summary.json") as f:
        summary = json.load(f)
    assert summary["ok"] is False and summary["claim"] is None


def test_main_fails_alone_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_main(tmp_path)
    assert r.returncode != 0 and '"ok"' not in r.stdout
