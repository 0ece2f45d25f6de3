"""Both Pallas kernels AOT-compiled for a TPU v5e, on the CPU box.

For the attention shape of every ``llama.PRESETS`` family x {one chip, tp=4
shard} x {bf16 pools, int8 pools}: the runner's rule
(``engine/runner.kernel_refusal``) says whether ``auto`` may pick the kernels
there. Where it may, the kernels must compile with the real XLA:TPU + Mosaic
pipeline (``testing/v5e_aot.py`` — libtpu compiles for a topology it cannot
see); where it may not, Mosaic must indeed refuse, so the rule never hides a
shape that works. A kernel edit Mosaic rejects therefore fails here, not on
the chip. Evidence of compilation only — numerics are ``chip_smoke.py``
phase K's.

The compiles run in a CHILD process (one per suite half), which keeps libtpu
out of the suite's one long-lived pytest process.
"""

import json
import subprocess
import sys

import pytest

from production_stack_tpu.engine import runner
from production_stack_tpu.testing import v5e_aot
from production_stack_tpu.testing.procs import REPO_ROOT, cpu_env


def _matrix(tmp_path_factory, slow: bool) -> dict:
    out = tmp_path_factory.mktemp("v5e_aot") / "result.json"
    argv = [sys.executable, "-m", "production_stack_tpu.testing.v5e_aot",
            "--out", str(out)] + (["--slow"] if slow else [])
    r = subprocess.run(argv, cwd=REPO_ROOT, env=cpu_env(), timeout=900,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-3000:]
    result = json.loads(out.read_text())
    if "unavailable" in result:
        pytest.skip(f"get_topology_desc(tpu, v5e:2x2): {result['unavailable']}")
    return result


@pytest.fixture(scope="module")
def tier1(tmp_path_factory):
    return _matrix(tmp_path_factory, slow=False)


@pytest.fixture(scope="module")
def slow_half(tmp_path_factory):
    return _matrix(tmp_path_factory, slow=True)


def test_decode_kernel_compiles_or_rule_excludes(tier1):
    assert len(tier1["decode"]) >= 8
    decode_only = set()
    for cid, r in tier1["decode"].items():
        if r["refusal"] is None:
            assert r["compiled"], f"{cid}: {r['error']}"
        elif "sublane tile" in r["refusal"]:
            # kv heads a shard that do not fill a sublane tile: the decode
            # kernel reads a page as [page * KH, D] rows and compiles; the
            # rule stands for the PREFILL kernel, which DMAs [page, KH, D]
            # (the slow half shows Mosaic refusing it), and because no chip
            # run has checked the decode kernel's numbers at these shapes
            assert r["compiled"], f"{cid}: {r['error']}"
            decode_only.add(cid)
        else:
            # the rule must not hide a shape that works: Mosaic does refuse
            assert not r["compiled"], f"{cid} compiles but the rule excludes it"
            assert "aligned to tiling" in r["error"], f"{cid}: {r['error']}"
    assert decode_only == {"llama-3-8b/tp4/int8", "qwen2.5-7b/tp4/bf16",
                           "qwen2.5-7b/tp4/int8"}
    compiled = {c for c, r in tier1["decode"].items() if r["compiled"]}
    assert {"llama-3-8b/tp1/bf16", "llama-3-8b/tp1/int8",
            "qwen2.5-7b/tp1/bf16", "llama-3-8b/tp4/bf16"} <= compiled
    # every (batch, pages) bucket the benchmark's two cells dispatch, with
    # the block the kernel derives there (PR 27 died at one of these on the
    # chip with every interpret-mode test green)
    assert len(v5e_aot.CELL_BUCKETS) == 10
    assert set(v5e_aot.CELL_BUCKETS) <= compiled


@pytest.mark.parametrize(
    "bucket",
    [dict(B=64, max_pages=2048, NH=32, KH=8),   # the largest default bucket
     dict(B=64, max_pages=64, NH=32, KH=8),     # mistral-7b-d16.chat
     dict(B=32, max_pages=64, NH=28, KH=4)],    # qwen2.5-7b-d14.sessions
    ids=["b64xp2048", "chat-b64xp64", "sessions-b32xp64"],
)
def test_decode_smem_bytes_is_the_scalar_prefetch_operands_size(bucket):
    """The rule's SMEM count (kernel_refusal holds it against 960 KiB) is
    the traced pallas_call's scalar-prefetch operands, byte for byte."""
    from production_stack_tpu.ops.pallas.paged_attention import (
        decode_smem_bytes,
    )

    assert v5e_aot.smem_operand_bytes(**bucket) == decode_smem_bytes(
        bucket["B"], bucket["max_pages"], 64, bucket["KH"], 128, 2
    )


def test_prefill_kernel_compiles_at_the_widest_shape(tier1):
    assert list(tier1["prefill"]) == ["llama-3-8b/tp1/bf16"]
    for cid, r in tier1["prefill"].items():
        assert r["compiled"], f"{cid}: {r['error']}"


def test_tp4_decode_step_compiles_through_shard_map(tier1):
    """The kernel must sit in a region where EVERY mesh axis is manual, or
    XLA:TPU refuses: 'Mosaic kernels cannot be automatically partitioned'."""
    r = tier1["step_programs"]["mistral-7b/tp4/burst-b8xp64"]
    assert r["compiled"], r["error"]


@pytest.mark.parametrize("pid", list(v5e_aot.STEP_PROGRAMS))
def test_step_program_compiles_through_the_store_with_its_pools_donated(tier1, pid):
    """What runner._dispatch runs (export -> serialise -> deserialise ->
    jit(exported.call)) compiles for the v5e, keeps every Mosaic kernel of the
    plain lowering (the prefill step carries its kernel on one chip), and
    still aliases both pools to its outputs: a step that copied them would
    not fit the chip."""
    r = tier1["step_programs"][pid]
    assert r["compiled"], r["error"]
    assert r["custom_calls_blob"] == r["custom_calls_plain"] >= 1
    # a prefill step that takes decode rows along holds both attention kernels
    # (the llama family), or both SSD kernels beside the grouped product's two
    # calls (nemotron: Mosaic takes ``ssd_step_decode`` next to
    # ``ssd_scan_prefill`` in one program)
    if "riders" in v5e_aot.STEP_PROGRAMS[pid]:
        assert r["custom_calls_blob"] == (4 if pid.startswith("nemotron") else 2)
    # (a family with recurrent state donates its state pool too; its
    # convolution tails, 3 rows of bf16 each, are padded to the tile)
    donated = r["pool_bytes"] + r["state_bytes"]
    assert 0 < donated <= r["alias_bytes"] <= 1.01 * donated
    assert r["alias_bytes"] == donated or r["state_bytes"]
    assert (r["state_bytes"] > 0) == pid.startswith(("jamba", "lfm2", "nemotron"))
    assert r["blob_bytes"] < 256 << 10


@pytest.mark.parametrize("sid", list(v5e_aot.SSM_SCANS))
def test_selective_scan_kernel_compiles_under_both_names(tier1, sid):
    """ops/pallas/ssm_scan.py at the shapes the jamba2-3b.chat cell
    dispatches: Mosaic takes the SMEM-blocked B and C, the slot-indexed state
    blocks and the in-kernel time loop, the custom call carries the name the
    trace readers look for (``ssm_step_decode`` / ``ssm_scan_prefill``), and
    the state pool is updated in place."""
    r = tier1["ssm_scan"][sid]
    assert r["compiled"], r["error"]


@pytest.mark.slow
def test_prefill_kernel_compiles_at_every_other_shape(slow_half):
    assert len(slow_half["prefill"]) >= 4
    for cid, r in slow_half["prefill"].items():
        assert r["compiled"], f"{cid}: {r['error']}"


@pytest.mark.slow
def test_prefill_kernel_is_refused_where_the_sublane_rule_says(slow_half):
    """The shapes at which the decode kernel alone compiles (above): the
    rule's sublane constraint is the prefill kernel's page DMA."""
    assert len(slow_half["prefill_refused"]) == 3
    for cid, r in slow_half["prefill_refused"].items():
        assert not r["compiled"], f"{cid} compiles but the rule excludes it"
        assert "aligned to tiling" in r["error"], f"{cid}: {r['error']}"


@pytest.mark.slow
def test_largest_default_bucket_fits_smem_and_the_next_does_not(slow_half):
    """64 rows x 2048 pages (max_num_seqs default x a 128k context) is inside
    the rule's SMEM budget and compiles; 128 x 2048 is outside and XLA says
    why."""
    kw = dict(head_dim=128, kv_heads_per_shard=8, pool_itemsize=2)
    assert runner.kernel_refusal(max_batch=64, max_pages=2048, **kw) is None
    assert slow_half["smem"]["64"]["compiled"], slow_half["smem"]["64"]["error"]
    assert "SMEM" in runner.kernel_refusal(max_batch=128, max_pages=2048, **kw)
    assert not slow_half["smem"]["128"]["compiled"]
    assert "smem" in slow_half["smem"]["128"]["error"]
