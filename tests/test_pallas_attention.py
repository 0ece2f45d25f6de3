"""Pallas ragged paged-attention decode kernel vs the XLA oracle
(ops/attention.paged_attention_decode), and end-to-end through the engine."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import paged_attention_decode
from production_stack_tpu.ops.pallas.paged_attention import ragged_paged_attention_decode


def _case(B=4, NH=8, KH=2, D=128, page=16, P=32, maxp=4, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, NH, D), dtype)
    kp = jnp.asarray(rng.randn(P, page, KH, D), dtype)
    vp = jnp.asarray(rng.randn(P, page, KH, D), dtype)
    pt = jnp.asarray(
        rng.choice(P, (B * maxp), replace=False).reshape(B, maxp), jnp.int32
    )
    return q, kp, vp, pt


class TestKernelVsOracle:
    def test_ragged_lengths(self):
        q, kp, vp, pt = _case()
        lens = jnp.asarray([5, 16, 33, 64], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_gqa_groups_and_odd_dims(self):
        q, kp, vp, pt = _case(B=3, NH=12, KH=4, D=64, page=8, P=24, maxp=6, seed=1)
        lens = jnp.asarray([1, 24, 48], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_padded_batch_row(self):
        """kv_len=0 rows (scheduler padding) must produce zeros, not NaN."""
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=8, maxp=2, seed=2)
        lens = jnp.asarray([10, 0], jnp.int32)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        assert not np.any(np.isnan(np.asarray(out)))
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)

    def test_bf16_inputs(self):
        q, kp, vp, pt = _case(dtype=jnp.bfloat16, seed=3)
        lens = jnp.asarray([7, 16, 40, 64], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
        )


class TestWindowAndSoftcap:
    def test_sliding_window_matches_oracle(self):
        q, kp, vp, pt = _case(B=3, NH=8, KH=2, D=64, page=8, P=24, maxp=6, seed=4)
        lens = jnp.asarray([5, 23, 48], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens, window=10)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, window=10, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_window_page_remap_long_context(self):
        """Window smaller than one page and much smaller than the context:
        exercises the index-map remap to the first visible page."""
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=8, seed=5)
        lens = jnp.asarray([64, 61], jnp.int32)
        for w in (3, 8, 17):
            ref = paged_attention_decode(q, kp, vp, pt, lens, window=w)
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, window=w, interpret=True
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5, err_msg=f"w={w}"
            )

    def test_logit_softcap_matches_oracle(self):
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=4, seed=6)
        lens = jnp.asarray([9, 30], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens, logit_softcap=50.0)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, logit_softcap=50.0, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_window_and_softcap_traced_window(self):
        """Traced window scalar (the per-layer scan case, Gemma-2)."""
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=4, seed=7)
        lens = jnp.asarray([20, 31], jnp.int32)
        w = jnp.asarray(6, jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens, window=6, logit_softcap=30.0)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, window=w, logit_softcap=30.0, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


class TestEngineWithPallasDecode:
    def _run(self, model, attn_impl, prompt="hello pallas world", max_tokens=6):
        import asyncio

        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.engine import LLMEngine
        from production_stack_tpu.engine.scheduler import SamplingParams

        eng = LLMEngine(EngineConfig(
            model=model, max_model_len=128, max_num_seqs=2,
            num_pages=32, page_size=8, prefill_chunk=32, attn_impl=attn_impl,
        ))
        assert eng.runner.cfg.attn_impl == attn_impl
        eng.start()
        try:
            async def go():
                toks = []
                async for out in eng.generate(
                    "pk-1", prompt=prompt,
                    params=SamplingParams(
                        max_tokens=max_tokens, temperature=0.0, ignore_eos=True
                    ),
                ):
                    toks.extend(out.token_ids)
                return toks

            toks = asyncio.run(go())
            assert len(toks) == max_tokens  # engine errors produce no tokens
            return toks
        finally:
            eng.stop()

    def test_greedy_matches_xla_engine(self):
        """Same engine, pallas_interpret vs xla decode attention — greedy
        outputs must be identical token-for-token."""
        assert self._run("llama-debug", "pallas_interpret") == \
            self._run("llama-debug", "xla")

    @pytest.mark.slow  # ~30 s: four full engines (two windowed families x
    # two attn impls); window semantics are kernel-covered above
    def test_windowed_families_match_xla_engine(self):
        """Mistral (static window) and Gemma-2 (per-layer traced window +
        softcap) through the kernel's windowed path."""
        for model in ("mistral-debug", "gemma2-debug"):
            assert self._run(model, "pallas_interpret") == \
                self._run(model, "xla"), model


class TestShardedKernel:
    """The kernel under dp x tp meshes (shard_map path): per-shard execution
    must match the single-device kernel and the XLA oracle exactly."""

    @pytest.mark.parametrize("dp,tp", [(1, 2), (2, 1), (2, 2), (1, 4)])
    def test_matches_oracle_on_mesh(self, eight_devices, dp, tp):
        import jax
        from production_stack_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_decode_sharded,
        )
        from production_stack_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(dp=dp, tp=tp)
        q, kp, vp, pt = _case(B=4, NH=8, KH=4, D=32, page=8, P=32, maxp=4, seed=7)
        lens = jnp.asarray([5, 16, 23, 32], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = jax.jit(
            lambda *a: ragged_paged_attention_decode_sharded(
                mesh, *a, interpret=True
            )
        )(q, kp, vp, pt, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_post_write_cur_kv_on_mesh(self, eight_devices):
        import jax
        from production_stack_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_decode_sharded,
        )
        from production_stack_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(dp=2, tp=2)
        rng = np.random.RandomState(9)
        B, NH, KH, D, page, P_, maxp = 4, 8, 4, 32, 8, 32, 4
        q = jnp.asarray(rng.randn(B, NH, D), jnp.float32)
        kp = jnp.asarray(rng.randn(P_, page, KH, D), jnp.float32)
        vp = jnp.asarray(rng.randn(P_, page, KH, D), jnp.float32)
        pt = jnp.asarray(
            rng.choice(P_, (B * maxp), replace=False).reshape(B, maxp), jnp.int32
        )
        lens = jnp.asarray([6, 17, 24, 31], jnp.int32)
        kc = jnp.asarray(rng.randn(B, KH, D), jnp.float32)
        vc = jnp.asarray(rng.randn(B, KH, D), jnp.float32)
        ref = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, k_cur=kc, v_cur=vc
        )
        out = jax.jit(
            lambda *a: ragged_paged_attention_decode_sharded(
                mesh, *a, interpret=True, k_cur=kc, v_cur=vc
            )
        )(q, kp, vp, pt, lens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_engine_pallas_interpret_on_tp_mesh(self, eight_devices):
        """Full runner equivalence: pallas_interpret decode on a dp x tp mesh
        vs the XLA path, greedy tokens identical."""
        from production_stack_tpu.engine.runner import ModelRunner, StepInput
        from production_stack_tpu.models import llama
        from production_stack_tpu.parallel.mesh import make_mesh

        cfg = dataclasses.replace(
            llama.PRESETS["llama-debug"], num_heads=8, num_kv_heads=4
        )
        rng = np.random.RandomState(0)
        B, T = 4, 16
        prefill = StepInput(
            input_ids=rng.randint(0, cfg.vocab_size, (B, T)),
            positions=np.broadcast_to(np.arange(T), (B, T)).copy(),
            page_table=np.arange(B * 4).reshape(B, 4),
            kv_lens=np.full((B,), T),
            temperature=np.zeros(B), top_k=np.zeros(B, int), top_p=np.ones(B),
        )
        dec_ids = rng.randint(0, cfg.vocab_size, (B, 1))

        def run(attn_impl):
            mesh = make_mesh(dp=2, tp=2)
            r = ModelRunner(
                dataclasses.replace(cfg, attn_impl=attn_impl),
                mesh=mesh, num_pages=32, page_size=8, seed=0,
            )
            r.step(prefill)
            dec = StepInput(
                input_ids=dec_ids, positions=np.full((B, 1), T),
                page_table=prefill.page_table, kv_lens=np.full((B,), T + 1),
                temperature=np.zeros(B), top_k=np.zeros(B, int),
                top_p=np.ones(B),
            )
            ids, logits = r.step(dec)
            return np.asarray(ids), np.asarray(logits)

        ids_x, log_x = run("xla")
        ids_p, log_p = run("pallas_interpret")
        np.testing.assert_array_equal(ids_p, ids_x)
        np.testing.assert_allclose(log_p, log_x, rtol=5e-2, atol=5e-2)


class TestAutoImplResolution:
    """attn_impl=auto must only pick the kernels where they can compile and
    run (engine/runner.resolve_attn_impl — the one rule on platform and
    shapes), and must say why when it does not."""

    def _resolve(self, monkeypatch, tp, dp=1, num_heads=8, num_kv_heads=4,
                 head_dim=128, attn_impl="auto"):
        import jax

        from production_stack_tpu.engine.runner import ModelRunner
        from production_stack_tpu.models import llama
        from production_stack_tpu.parallel.mesh import make_mesh

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = dataclasses.replace(
            llama.PRESETS["llama-debug"],
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, attn_impl=attn_impl,
        )
        r = ModelRunner(
            cfg, mesh=make_mesh(tp=tp, dp=dp), num_pages=16, page_size=8, seed=0
        )
        assert r.cfg.attn_impl == r.attn.impl
        return r.attn

    def test_one_device_picks_both_kernels(self, monkeypatch):
        a = self._resolve(monkeypatch, tp=1)
        assert (a.impl, a.prefill, a.decode, a.reason) == (
            "pallas_prefill", "pallas", "pallas", ""
        )

    def test_mesh_runs_decode_kernel_per_shard_and_xla_prefill(
        self, monkeypatch, eight_devices
    ):
        a = self._resolve(monkeypatch, tp=2)
        assert (a.impl, a.prefill, a.decode) == (
            "pallas", "xla", "pallas_shard_map"
        )
        assert "prefill" in a.reason

    def test_uneven_kv_heads_fall_back_to_xla(self, monkeypatch, eight_devices):
        a = self._resolve(monkeypatch, tp=4, num_heads=4, num_kv_heads=2)
        assert a.impl == "xla" and "do not divide tp=4" in a.reason

    def test_uneven_heads_fall_back_to_xla(self, monkeypatch, eight_devices):
        # 6 q / 2 kv heads at tp=4: neither divides (for valid GQA configs
        # tp | kv_heads already implies tp | num_heads, so the q check only
        # fires together with the kv one)
        a = self._resolve(monkeypatch, tp=4, num_heads=6, num_kv_heads=2)
        assert a.impl == "xla" and "do not divide" in a.reason

    def test_head_dim_64_is_excluded_with_the_compilers_message(
        self, monkeypatch
    ):
        a = self._resolve(monkeypatch, tp=1, head_dim=64)
        assert a.impl == a.prefill == a.decode == "xla"
        assert "aligned to tiling (128), but is 64" in a.reason

    def test_one_kv_head_per_shard_is_excluded(self, monkeypatch, eight_devices):
        # qwen2.5-7b at tp=4: 4 kv heads / 4 = 1 bf16 row per shard
        a = self._resolve(monkeypatch, tp=4, num_heads=8, num_kv_heads=4)
        assert a.impl == "xla"
        assert "aligned to tiling (2), but is 1" in a.reason

    def test_explicit_kernel_that_cannot_compile_fails_at_startup(
        self, monkeypatch
    ):
        with pytest.raises(ValueError, match="cannot compile here"):
            self._resolve(
                monkeypatch, tp=1, head_dim=64, attn_impl="pallas_prefill"
            )

    def test_no_tpu_means_xla_and_says_so(self):
        from production_stack_tpu.engine.runner import resolve_attn_impl

        a = resolve_attn_impl(
            "auto", platform="cpu", n_devices=1, fwd_takes_mesh=True,
            num_heads=32, num_kv_heads=8, head_dim=128, tp=1, pool_itemsize=2,
        )
        assert a.impl == "xla" and a.reason == "no TPU backend (platform=cpu)"

    def test_smem_budget_bounds_the_largest_decode_bucket(self):
        from production_stack_tpu.engine.runner import kernel_refusal

        kw = dict(head_dim=128, kv_heads_per_shard=8, pool_itemsize=2)
        # 64 rows x 2048 pages compiled for v5e; 128 x 2048 ran out of SMEM
        assert kernel_refusal(max_batch=64, max_pages=2048, **kw) is None
        assert "SMEM" in kernel_refusal(max_batch=128, max_pages=2048, **kw)


class TestShardedKernelOnParallelMeshes:
    """pallas decode on sp/ep/pp meshes (VERDICT r2 #4): the sharded kernel
    maps sp/ep replicated-manual, and under pp it nests inside the
    pipeline's manual region with stage-local layer pools — no more XLA
    gather fallback for exactly the configs where bandwidth matters most."""

    def _run(self, attn_impl, mesh_kw, cfg, prefill, dec_ids):
        from production_stack_tpu.engine.runner import ModelRunner, StepInput
        from production_stack_tpu.parallel.mesh import make_mesh

        B = prefill.input_ids.shape[0]
        T = prefill.input_ids.shape[1]
        r = ModelRunner(
            dataclasses.replace(cfg, attn_impl=attn_impl),
            mesh=make_mesh(**mesh_kw), num_pages=32, page_size=8, seed=0,
        )
        r.step(prefill)
        dec = StepInput(
            input_ids=dec_ids, positions=np.full((B, 1), T),
            page_table=prefill.page_table, kv_lens=np.full((B,), T + 1),
            temperature=np.zeros(B), top_k=np.zeros(B, int), top_p=np.ones(B),
        )
        ids, logits = r.step(dec)
        return np.asarray(ids), np.asarray(logits)

    @pytest.mark.parametrize(
        "mesh_kw",
        [{"pp": 2, "tp": 2}, {"sp": 2, "tp": 2}, {"ep": 2, "tp": 2}],
        ids=["pp2xtp2", "sp2xtp2", "ep2xtp2"],
    )
    def test_matches_xla_on_mesh(self, mesh_kw, eight_devices):
        from production_stack_tpu.engine.runner import StepInput
        from production_stack_tpu.models import llama

        cfg = dataclasses.replace(
            llama.PRESETS["llama-debug"], num_heads=8, num_kv_heads=4
        )
        rng = np.random.RandomState(0)
        B, T = 2, 16
        prefill = StepInput(
            input_ids=rng.randint(0, cfg.vocab_size, (B, T)),
            positions=np.broadcast_to(np.arange(T), (B, T)).copy(),
            page_table=np.arange(B * 4).reshape(B, 4),
            kv_lens=np.full((B,), T),
            temperature=np.zeros(B), top_k=np.zeros(B, int), top_p=np.ones(B),
        )
        dec_ids = rng.randint(0, cfg.vocab_size, (B, 1))
        ids_x, log_x = self._run("xla", mesh_kw, cfg, prefill, dec_ids)
        ids_p, log_p = self._run("pallas_interpret", mesh_kw, cfg, prefill, dec_ids)
        np.testing.assert_array_equal(ids_p, ids_x)
        np.testing.assert_allclose(log_p, log_x, rtol=5e-2, atol=5e-2)

    def test_parallel_meshes_resolve_pallas(self, monkeypatch, eight_devices):
        """sp/ep/pp serving meshes now pick the kernel on TPU (r2 VERDICT #4
        — they used to regress decode to the XLA gather path)."""
        import jax

        from production_stack_tpu.engine.runner import ModelRunner
        from production_stack_tpu.models import llama
        from production_stack_tpu.parallel.mesh import make_mesh

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for mesh_kw in ({"pp": 2, "tp": 2}, {"sp": 2, "tp": 2},
                        {"ep": 2, "tp": 2}, {"sp": 2, "ep": 2, "tp": 2}):
            cfg = dataclasses.replace(
                llama.PRESETS["llama-debug"],
                num_heads=8, num_kv_heads=4, head_dim=128, attn_impl="auto",
            )
            r = ModelRunner(
                cfg, mesh=make_mesh(**mesh_kw), num_pages=16, page_size=8,
                seed=0,
            )
            # decode runs the kernel per shard; multi-device prefill stays
            # on the XLA/ring path and the resolution says so
            assert r.cfg.attn_impl == "pallas", mesh_kw
            assert r.attn.decode == "pallas_shard_map", mesh_kw


class TestMultiPageBlocks:
    """pages_per_block > 1: N pages stream per grid cell (each its own input
    block), shrinking the grid N-fold — the fix for small-page decode
    throughput (876 tok/s at page 16 vs 1,501 at 128, engine/config.py)."""

    @pytest.mark.parametrize("n", [1, 2, 3, 4, 8])
    def test_matches_oracle_any_block_factor(self, n):
        q, kp, vp, pt = _case(B=3, NH=8, KH=2, D=64, page=8, P=32, maxp=8, seed=11)
        lens = jnp.asarray([5, 33, 64], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, pages_per_block=n
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5, err_msg=f"n={n}"
        )

    @pytest.mark.parametrize("n", [2, 4])
    def test_window_with_multipage_blocks(self, n):
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=8, seed=12)
        lens = jnp.asarray([64, 49], jnp.int32)
        for w in (5, 16, 40):
            ref = paged_attention_decode(q, kp, vp, pt, lens, window=w)
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, window=w, interpret=True, pages_per_block=n
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"n={n} w={w}",
            )

    def test_has_cur_with_multipage_blocks(self):
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=4, seed=13)
        lens = jnp.asarray([9, 26], jnp.int32)
        rng = np.random.RandomState(14)
        kc = jnp.asarray(rng.randn(2, 2, 32), q.dtype)
        vc = jnp.asarray(rng.randn(2, 2, 32), q.dtype)
        ref = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, k_cur=kc, v_cur=vc,
            pages_per_block=1,
        )
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, k_cur=kc, v_cur=vc,
            pages_per_block=4,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


class TestRaggedGridAndPrefetch:
    """v2 memory pipeline: the packed ragged grid (live cells scale with
    real kv_lens; trailing dead cells no-op) and the manual DMA ring
    (prefetch_pages page copies in flight) must be invisible to numerics —
    every case checks against the XLA oracle."""

    def test_short_seqs_in_large_bucket(self):
        """The headline ragged shape: tiny sequences in a bucket sized for
        long ones (64 pages for <=6 pages of live context) — v1 ran every
        bucket page; v2 packs ~1-6 live cells per row and no-ops the rest."""
        q, kp, vp, pt = _case(B=4, NH=8, KH=2, D=64, page=8, P=300, maxp=64, seed=20)
        lens = jnp.asarray([3, 17, 48, 1], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_mixed_short_and_bucket_filling(self):
        """One row fills the bucket exactly while its neighbors are short:
        the packed grid mixes 1-cell and max-cell rows in one dispatch."""
        q, kp, vp, pt = _case(B=3, NH=4, KH=2, D=32, page=8, P=128, maxp=32, seed=21)
        lens = jnp.asarray([256, 8, 70], jnp.int32)  # full, 1 page, partial
        for n in (1, 2, 4):
            ref = paged_attention_decode(q, kp, vp, pt, lens)
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, interpret=True, pages_per_block=n
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"n={n}",
            )

    @pytest.mark.parametrize("r", [2, 3, 5, 8])
    def test_prefetch_depth_sweep(self, r):
        """Ring depth is a pure performance knob: any R >= 2 must match."""
        q, kp, vp, pt = _case(B=3, NH=8, KH=2, D=64, page=8, P=32, maxp=8, seed=22)
        lens = jnp.asarray([5, 33, 64], jnp.int32)
        ref = paged_attention_decode(q, kp, vp, pt, lens)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, prefetch_pages=r
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5, err_msg=f"R={r}"
        )

    def test_window_and_softcap_in_large_bucket(self):
        """Windowed rows start their live range mid-bucket (lo_page remap)
        while packed next to full-causal-short rows; softcap rides along."""
        q, kp, vp, pt = _case(B=3, NH=4, KH=2, D=32, page=8, P=96, maxp=24, seed=23)
        lens = jnp.asarray([192, 11, 100], jnp.int32)
        for w, cap in ((7, None), (24, 30.0), (64, 50.0)):
            ref = paged_attention_decode(
                q, kp, vp, pt, lens, window=w, logit_softcap=cap
            )
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, window=w, logit_softcap=cap,
                interpret=True, pages_per_block=2, prefetch_pages=3,
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"w={w} cap={cap}",
            )

    def test_burst_window_ragged_batch(self):
        """Multi-token deferred-burst window (has_cur, per-row cur_lens) on
        a ragged batch in an oversized bucket — the full serving decode
        shape — against the oracle's burst_kv_positions contract."""
        rng = np.random.RandomState(24)
        B, NH_, KH_, D_, page, P_, maxp, C = 4, 8, 2, 32, 8, 160, 40, 4
        q = jnp.asarray(rng.randn(B, NH_, D_), jnp.float32)
        kp = jnp.asarray(rng.randn(P_, page, KH_, D_), jnp.float32)
        vp = jnp.asarray(rng.randn(P_, page, KH_, D_), jnp.float32)
        pt = jnp.asarray(
            rng.choice(P_, (B * maxp), replace=False).reshape(B, maxp), jnp.int32
        )
        lens = jnp.asarray([9, 120, 33, 2], jnp.int32)
        cur = jnp.asarray([1, 4, 2, 1], jnp.int32)
        kc = jnp.asarray(rng.randn(B, C, KH_, D_), jnp.float32)
        vc = jnp.asarray(rng.randn(B, C, KH_, D_), jnp.float32)
        ref = paged_attention_decode(
            q, kp, vp, pt, lens, k_cur=kc, v_cur=vc, cur_lens=cur
        )
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, k_cur=kc, v_cur=vc,
            cur_lens=cur, pages_per_block=3, prefetch_pages=4,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_window_burst_softcap_combined(self):
        """Everything at once: sliding window + multi-token stale burst
        window + softcap on a ragged batch with a small cell size and a
        small ring — the full Gemma-2-under-burst decode shape."""
        rng = np.random.RandomState(30)
        B, NH_, KH_, D_, page, P_, maxp, C = 3, 4, 2, 32, 8, 120, 30, 3
        q = jnp.asarray(rng.randn(B, NH_, D_), jnp.float32)
        kp = jnp.asarray(rng.randn(P_, page, KH_, D_), jnp.float32)
        vp = jnp.asarray(rng.randn(P_, page, KH_, D_), jnp.float32)
        pt = jnp.asarray(
            rng.choice(P_, B * maxp, replace=False).reshape(B, maxp), jnp.int32
        )
        lens = jnp.asarray([9, 200, 45], jnp.int32)
        cur = jnp.asarray([1, 3, 2], jnp.int32)
        kc = jnp.asarray(rng.randn(B, C, KH_, D_), jnp.float32)
        vc = jnp.asarray(rng.randn(B, C, KH_, D_), jnp.float32)
        for w in (2, 11, 64):
            ref = paged_attention_decode(
                q, kp, vp, pt, lens, window=w, k_cur=kc, v_cur=vc,
                cur_lens=cur, logit_softcap=40.0,
            )
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, window=w, logit_softcap=40.0,
                interpret=True, k_cur=kc, v_cur=vc, cur_lens=cur,
                pages_per_block=2, prefetch_pages=3,
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"w={w}",
            )

    def test_all_rows_padded(self):
        """A fully-padded batch (every kv_len 0 — scheduler bucket edge)
        must produce zeros without NaN: each row keeps one masked cell."""
        q, kp, vp, pt = _case(B=2, NH=4, KH=2, D=32, page=8, P=16, maxp=4, seed=25)
        lens = jnp.asarray([0, 0], jnp.int32)
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
        assert not np.any(np.isnan(np.asarray(out)))
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_runner_decode_dispatch_token_identical(self):
        """Single-device runner dispatch end-to-end: context built through
        T=1 steps (stacked pools + traced layer + single-token k_cur fold),
        then a fused burst (deferred kv_burst window) — greedy tokens must
        match the XLA path exactly, including with tuned pipeline knobs.
        (The engine-level variant of this test is blocked on the prefill
        kernel's pre-existing CompilerParams incompatibility; this covers
        the DECODE dispatch without touching that path.)"""
        from production_stack_tpu.engine.runner import ModelRunner, StepInput
        from production_stack_tpu.models import llama

        cfg0 = llama.PRESETS["llama-debug"]
        rng = np.random.RandomState(0)
        B, T = 2, 5
        ids = rng.randint(0, cfg0.vocab_size, (B, T))

        def run(attn_impl, **cfgkw):
            cfg = dataclasses.replace(cfg0, attn_impl=attn_impl, **cfgkw)
            r = ModelRunner(cfg, num_pages=32, page_size=8, seed=0)
            for t in range(T):
                r.step(StepInput(
                    input_ids=ids[:, t:t + 1], positions=np.full((B, 1), t),
                    page_table=np.arange(B * 4).reshape(B, 4),
                    kv_lens=np.full((B,), t + 1),
                    temperature=np.zeros(B), top_k=np.zeros(B, int),
                    top_p=np.ones(B),
                ))
            dec = StepInput(
                input_ids=np.full((B, 1), 5), positions=np.full((B, 1), T),
                page_table=np.arange(B * 4).reshape(B, 4),
                kv_lens=np.full((B,), T + 1),
                temperature=np.zeros(B), top_k=np.zeros(B, int),
                top_p=np.ones(B), kv_limits=np.full((B,), 28),
            )
            return np.asarray(r.step_multi(dec, 3))

        tx = run("xla")
        np.testing.assert_array_equal(
            run("pallas_interpret", decode_pages_per_block=2,
                decode_prefetch_pages=3),
            tx,
        )

    def test_stacked_pools_traced_layer(self):
        """Stacked [L, ...] pools with a traced layer index — the per-layer
        scan contract — through the DMA ring."""
        rng = np.random.RandomState(26)
        L, P_, page, KH_, D_, B, NH_, maxp = 3, 48, 8, 2, 32, 2, 4, 12
        kp = jnp.asarray(rng.randn(L, P_, page, KH_, D_), jnp.float32)
        vp = jnp.asarray(rng.randn(L, P_, page, KH_, D_), jnp.float32)
        q = jnp.asarray(rng.randn(B, NH_, D_), jnp.float32)
        pt = jnp.asarray(
            rng.choice(P_, (B * maxp), replace=False).reshape(B, maxp), jnp.int32
        )
        lens = jnp.asarray([5, 90], jnp.int32)
        for layer in range(L):
            ref = paged_attention_decode(q, kp[layer], vp[layer], pt, lens)
            out = ragged_paged_attention_decode(
                q, kp, vp, pt, lens, interpret=True,
                layer=jnp.asarray(layer, jnp.int32),
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"layer={layer}",
            )


class TestGemma2ShardedDecode:
    """Gemma-2 on a dp x tp mesh now reaches the sharded pallas kernel
    (per-layer traced windows + softcap included) instead of regressing to
    the XLA gather path on multi-chip."""

    def test_gemma2_tp_mesh_matches_xla(self, eight_devices):
        from production_stack_tpu.engine.runner import ModelRunner, StepInput
        from production_stack_tpu.models import gemma2
        from production_stack_tpu.parallel.mesh import make_mesh

        cfg = gemma2.PRESETS["gemma2-debug"]
        rng = np.random.RandomState(3)
        B, T = 2, 16
        prefill = StepInput(
            input_ids=rng.randint(0, cfg.vocab_size, (B, T)),
            positions=np.broadcast_to(np.arange(T), (B, T)).copy(),
            page_table=np.arange(B * 4).reshape(B, 4),
            kv_lens=np.full((B,), T),
            temperature=np.zeros(B), top_k=np.zeros(B, int), top_p=np.ones(B),
        )
        dec_ids = rng.randint(0, cfg.vocab_size, (B, 1))

        def run(attn_impl):
            r = ModelRunner(
                dataclasses.replace(cfg, attn_impl=attn_impl),
                mesh=make_mesh(dp=2, tp=2), num_pages=32, page_size=8, seed=0,
            )
            r.step(prefill)
            dec = StepInput(
                input_ids=dec_ids, positions=np.full((B, 1), T),
                page_table=prefill.page_table, kv_lens=np.full((B,), T + 1),
                temperature=np.zeros(B), top_k=np.zeros(B, int),
                top_p=np.ones(B),
            )
            ids, logits = r.step(dec)
            return np.asarray(ids), np.asarray(logits)

        ids_x, log_x = run("xla")
        ids_p, log_p = run("pallas_interpret")
        np.testing.assert_array_equal(ids_p, ids_x)
        np.testing.assert_allclose(log_p, log_x, rtol=5e-2, atol=5e-2)

    def test_gemma2_rejects_sp_pp(self, eight_devices):
        from production_stack_tpu.engine.runner import ModelRunner
        from production_stack_tpu.models import gemma2
        from production_stack_tpu.parallel.mesh import make_mesh

        cfg = gemma2.PRESETS["gemma2-debug"]
        for kw in ({"sp": 2}, {"pp": 2}):
            with pytest.raises(ValueError, match="sequence/pipeline"):
                ModelRunner(cfg, mesh=make_mesh(**kw), num_pages=16,
                            page_size=8, seed=0)


# -- the two benchmark configurations' decode shapes ---------------------------
# mistral-7b-d16 (KH 8, 4 query heads a kv head, window 4096) and
# qwen2.5-7b-d14 (KH 4, 7 a kv head, no window) at the real head dim and page
# size, every batch bucket x page bucket their cells dispatch (PERF.md
# section 4). A 4096 window cannot start inside a bucket of <= 64 pages, so
# the windowed configuration runs it capped to half the bucket plus 37 slots:
# every long row then starts mid-page, mid-block. The pool dtype and the
# burst window's size rotate so that each (configuration, page bucket) meets
# all four combinations over its batches.
_BENCH_CONFIGS = {"kh8-g4-window": (8, 4, 4096), "kh4-g7": (4, 7, None)}
_BENCH_COMBOS = [("bf16", 8), ("int8", 8), ("bf16", 1), ("int8", 1)]


def _bench_cases():
    for cfg in _BENCH_CONFIGS:
        for bi, B in enumerate((8, 16, 32, 64)):
            for pi, bucket in enumerate((16, 32, 64)):
                pool, C = _BENCH_COMBOS[(bi + pi) % 4]
                yield pytest.param(
                    cfg, B, bucket, pool, C,
                    id=f"{cfg}-b{B}-p{bucket}-{pool}-c{C}",
                )


class TestBenchmarkDecodeShapes:
    @pytest.mark.parametrize("cfg,B,bucket,pool,C", list(_bench_cases()))
    def test_matches_oracle(self, cfg, B, bucket, pool, C):
        from production_stack_tpu.ops.pallas.paged_attention import (
            decode_block_shape,
        )
        from production_stack_tpu.ops.quant import quantize_page_host

        KH, G, window = _BENCH_CONFIGS[cfg]
        D, page, P = 128, 64, 96
        if window is not None:
            window = min(window, bucket * page // 2 + 37)
        N, _ = decode_block_shape(bucket, page, KH, D, 1 if pool == "int8" else 2)
        rng = np.random.RandomState(B * 1000 + bucket)
        q = jnp.asarray(rng.randn(B, KH * G, D), jnp.bfloat16)
        kf = rng.randn(P, page, KH, D).astype(np.float32)
        vf = rng.randn(P, page, KH, D).astype(np.float32)
        pt = jnp.asarray(rng.randint(0, P, (B, bucket)), jnp.int32)
        cap = bucket * page
        lens = rng.randint(1, cap + 1, size=B)
        # rows every batch holds: the full bucket (under the window: a start
        # mid-block), a live range that ends one page into its last block,
        # one page, only the burst window (no paged slot at all), a padded
        # row, and a range that ends exactly on a block boundary
        lens[:6] = [cap, min(cap, (N + 1) * page + C - 7), 40, C, 0,
                    min(cap, N * page + C)]
        cur = np.minimum(rng.randint(1, C + 1, size=B), np.maximum(lens, 1))
        cur[3] = C
        lens, cur = jnp.asarray(lens, jnp.int32), jnp.asarray(cur, jnp.int32)
        kc = jnp.asarray(rng.randn(B, C, KH, D), jnp.bfloat16)
        vc = jnp.asarray(rng.randn(B, C, KH, D), jnp.bfloat16)
        kw = dict(window=window, k_cur=kc, v_cur=vc, cur_lens=cur)
        if pool == "int8":
            # the host quantizer reads the pool's pages as its layer axis:
            # one scale per (page, kv head), the pool's contract
            (kp, ks), (vp, vs) = quantize_page_host(kf), quantize_page_host(vf)
            kp, vp = jnp.asarray(kp), jnp.asarray(vp)
            kw.update(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
        else:
            kp, vp = jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)
        ref = paged_attention_decode(q, kp, vp, pt, lens, **kw)
        out = ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, **kw
        )
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        assert not np.isnan(out).any()
        np.testing.assert_array_equal(out[4], 0.0)  # the padded row
        np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)


class TestBlockDerivation:
    """The block of pages a grid cell consumes is derived from the shapes
    the call sees (ops/pallas/paged_attention._auto_pages_per_block)."""

    def test_is_a_pure_function_of_the_shapes(self):
        import inspect

        from production_stack_tpu.ops.pallas import paged_attention as pa

        assert list(inspect.signature(pa._auto_pages_per_block).parameters) == [
            "max_pages", "page_size", "kv_heads", "head_dim", "itemsize",
        ]
        # the two benchmark configurations at their 64-page bucket: 4 MiB of
        # K + V a block (8192 rows), three blocks in the ring
        assert pa.decode_block_shape(64, 64, 8, 128, 2) == (16, 3)
        assert pa.decode_block_shape(64, 64, 4, 128, 2) == (32, 3)
        # int8 pools: the row cap holds the tile where the bytes halve
        assert pa.decode_block_shape(64, 64, 8, 128, 1) == (16, 3)
        # never past the bucket; the overrides stay overrides
        assert pa.decode_block_shape(4, 64, 8, 128, 2) == (4, 3)
        assert pa.decode_block_shape(64, 64, 8, 128, 2, 2, 8) == (2, 4)
        assert pa.decode_block_shape(64, 64, 8, 128, 2, None, 8) == (16, 2)
        for args in [(64, 64, 8, 128, 2), (2048, 16, 2, 256, 1)]:
            assert pa._auto_pages_per_block(*args) == pa._auto_pages_per_block(*args)

    @pytest.mark.parametrize("family", ["llama", "gemma2", "opt"])
    def test_ring_and_block_stay_inside_the_vmem_budget(self, family):
        import importlib

        from production_stack_tpu.ops.pallas import paged_attention as pa

        presets = importlib.import_module(
            f"production_stack_tpu.models.{family}"
        ).PRESETS
        seen = 0
        for cfg in presets.values():
            for tp in (1, 2, 4, 8):
                if cfg.num_heads % tp or cfg.num_kv_heads % tp:
                    continue
                KH, NH, D = cfg.num_kv_heads // tp, cfg.num_heads // tp, cfg.head_dim
                for itemsize in (1, 2, 4):
                    for page in (16, 64, 128):
                        for max_pages in (1, 4, 64, 2048):
                            n, ring = pa.decode_block_shape(
                                max_pages, page, KH, D, itemsize
                            )
                            assert 1 <= n <= max_pages and ring in (2, 3)
                            rows = n * page * KH
                            block = 2 * rows * D * itemsize
                            if n > 1:
                                assert rows <= pa._BLOCK_ROWS
                                assert block <= pa._BLOCK_BYTES
                            assert ring * block <= pa._RING_VMEM_BYTES or n == 1
                            # the ring, the float32 V view, and the score,
                            # probability and mask tiles of the block
                            tiles = rows * D * 4 + 4 * max(NH, 8) * rows * 4
                            assert ring * block + tiles <= pa._VMEM_LIMIT_BYTES or n == 1
                            seen += 1
        assert seen >= 36

    def test_stats_show_the_block_of_a_dispatched_bucket(self):
        import asyncio

        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.engine import LLMEngine
        from production_stack_tpu.engine.scheduler import SamplingParams
        from production_stack_tpu.ops.pallas.paged_attention import (
            decode_block_shape,
        )

        eng = LLMEngine(EngineConfig(
            model="llama-debug", max_model_len=128, max_num_seqs=2,
            num_pages=32, page_size=8, prefill_chunk=32,
            attn_impl="pallas_interpret",
        ))
        assert eng.stats()["decode_kernel_blocks"] == {}
        eng.start()
        try:
            async def go():
                async for _ in eng.generate(
                    "blk-1", prompt="which block",
                    params=SamplingParams(
                        max_tokens=4, temperature=0.0, ignore_eos=True
                    ),
                ):
                    pass

            asyncio.run(go())
            blocks = eng.stats()["decode_kernel_blocks"]
        finally:
            eng.stop()
        assert blocks, "no decode bucket was dispatched"
        cfg = eng.runner.cfg
        for bucket, got in blocks.items():
            pages = int(bucket.split("x")[1])
            n, ring = decode_block_shape(
                pages, 8, cfg.num_kv_heads, cfg.head_dim,
                np.dtype(eng.runner.kv_pool_dtype).itemsize,
            )
            assert got == {"pages_per_block": n, "ring_blocks": ring}
