"""KV offload tier tests: serde, tiers, cache server, KV-index controller,
end-to-end engine offload (evict -> restore with correct KV), and the
integrity layer (checksums, quarantine, recompute fallback)."""

import asyncio
import threading

import numpy as np
import pytest

from production_stack_tpu.kvoffload.serde import (
    KVIntegrityError,
    get_serde,
    seal_bytes,
    verify_blob,
)
from production_stack_tpu.kvoffload.tiers import CPUTier, DiskTier, TieredKVStore


def _kv(shape=(2, 8, 2, 4), seed=0):
    rng = np.random.RandomState(seed)
    import ml_dtypes

    k = rng.randn(*shape).astype(ml_dtypes.bfloat16)
    v = rng.randn(*shape).astype(ml_dtypes.bfloat16)
    return k, v


class TestSerde:
    def test_naive_roundtrip(self):
        k, v = _kv()
        s = get_serde("naive")
        k2, v2 = s.deserialize(s.serialize(k, v))
        np.testing.assert_array_equal(np.asarray(k2), k)
        np.testing.assert_array_equal(np.asarray(v2), v)

    def test_int8_roundtrip_close(self):
        k, v = _kv()
        s = get_serde("int8")
        blob = s.serialize(k, v)
        k2, v2 = s.deserialize(blob)
        np.testing.assert_allclose(
            np.asarray(k2, np.float32), np.asarray(k, np.float32), atol=0.05, rtol=0.05
        )
        # int8 blob must be materially smaller than the bf16 naive one
        naive = get_serde("naive").serialize(k, v)
        assert len(blob) < 0.75 * len(naive)

    def test_unknown_serde(self):
        with pytest.raises(ValueError):
            get_serde("bogus")

    def test_cross_serde_dispatch(self):
        """Blobs carry their serde name; readers with a different configured
        serde must still parse them (shared cache server scenario)."""
        from production_stack_tpu.kvoffload import serde as serde_mod

        k, v = _kv()
        blob = get_serde("int8").serialize(k, v)
        k2, v2 = serde_mod.deserialize(blob)  # reader configured with naive
        np.testing.assert_allclose(
            np.asarray(k2, np.float32), np.asarray(k, np.float32), atol=0.05, rtol=0.05
        )
        blob_n = get_serde("naive").serialize(k, v)
        k3, _ = serde_mod.deserialize(blob_n)
        np.testing.assert_array_equal(np.asarray(k3), k)


class TestTiers:
    def test_cpu_lru_eviction(self):
        t = CPUTier(max_bytes=100)
        assert t.put("a", b"x" * 60) == []
        assert t.put("b", b"y" * 60) == [("a", b"x" * 60)]
        assert t.get("a") is None
        assert t.get("b") == b"y" * 60

    def test_disk_tier_roundtrip(self, tmp_path):
        t = DiskTier(str(tmp_path), max_bytes=1000)
        t.put("k1", b"hello")
        assert t.get("k1") == b"hello"
        # restart recovers the index
        t2 = DiskTier(str(tmp_path), max_bytes=1000)
        assert t2.get("k1") == b"hello"

    def test_spill_cpu_to_disk_and_drop(self, tmp_path):
        dropped = []
        # sealed payloads: tier reads verify checksums, so stored blobs must
        # carry the integrity envelope (raw bytes would read as corrupt)
        blobs = {k: seal_bytes(c.encode() * 80) for k, c in
                 (("a", "1"), ("b", "2"), ("c", "3"))}
        sz = len(blobs["a"])
        st = TieredKVStore(
            cpu_bytes=sz + sz // 4,
            disk_path=str(tmp_path),
            disk_bytes=2 * sz - sz // 4,
            on_local_drop=dropped.append,
        )
        st.put("a", blobs["a"])
        st.put("b", blobs["b"])  # a spills to disk
        assert st.get("a") == blobs["a"]  # disk hit, promoted
        assert st.hits["disk"] == 1
        st.put("c", blobs["c"])  # b spills; disk holds a+b > cap -> a drops
        assert dropped  # something was fully dropped locally
        assert st.stats()["disk_bytes"] <= 2 * sz - sz // 4


def _run_server(coro_factory):
    """Start an asyncio server in a thread; returns (port, stop_fn)."""
    loop = asyncio.new_event_loop()
    server_box = {}
    ready = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def start():
            server = await coro_factory("127.0.0.1", 0)
            server_box["port"] = server.sockets[0].getsockname()[1]
            server_box["server"] = server
            ready.set()

        loop.run_until_complete(start())
        loop.run_forever()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    assert ready.wait(10)

    def stop():
        async def shutdown():
            server_box["server"].close()
            await server_box["server"].wait_closed()
            loop.stop()

        asyncio.run_coroutine_threadsafe(shutdown(), loop)
        th.join(timeout=5)

    return server_box["port"], stop


class TestIntegrity:
    """Offload-tier integrity (ISSUE 5): per-page checksums + versioned
    headers; corrupt or version-mismatched entries are never served — they
    are rejected, quarantined, counted, and the caller recomputes."""

    def _blob(self):
        k, v = _kv()
        return get_serde("naive").serialize(k, v)

    def test_bitflip_rejected(self):
        blob = bytearray(self._blob())
        blob[-3] ^= 0x40  # flip one bit deep in the V payload
        with pytest.raises(KVIntegrityError):
            verify_blob(bytes(blob))
        from production_stack_tpu.kvoffload import serde as serde_mod

        with pytest.raises(KVIntegrityError):
            serde_mod.deserialize(bytes(blob))

    def test_truncation_rejected(self):
        blob = self._blob()
        with pytest.raises(KVIntegrityError):
            verify_blob(blob[: len(blob) - 7])

    def test_future_version_rejected(self):
        import json
        import struct

        hdr = json.dumps({"v": 99, "serde": "naive"}).encode()
        blob = struct.pack("!I", len(hdr)) + hdr + b"body"
        with pytest.raises(KVIntegrityError):
            verify_blob(blob)

    def test_garbage_header_rejected(self):
        with pytest.raises(KVIntegrityError):
            verify_blob(b"not a frame at all")

    def test_v1_blob_without_crc_still_parses(self):
        """Pre-upgrade blobs (no crc field) must keep deserializing — a disk
        tier surviving a rolling upgrade is the whole point of warm starts."""
        import json
        import struct

        k, v = _kv()
        hdr = json.dumps(
            {"serde": "naive", "shape": list(k.shape), "dtype": "bfloat16"}
        ).encode()
        legacy = struct.pack("!I", len(hdr)) + hdr + k.tobytes() + v.tobytes()
        from production_stack_tpu.kvoffload import serde as serde_mod

        k2, v2 = serde_mod.deserialize(legacy)
        np.testing.assert_array_equal(np.asarray(k2), k)

    def test_cpu_tier_quarantines_and_counts(self):
        st = TieredKVStore(cpu_bytes=1 << 20)
        blob = self._blob()
        st.put("k", blob)
        bad = bytearray(blob)
        bad[-1] ^= 0xFF
        st.cpu._data["k"] = bytes(bad)  # bit rot in DRAM
        assert st.get("k") is None  # never served
        assert st.corrupt_pages == 1
        assert st.stats()["corrupt_pages"] == 1
        assert "k" not in st.cpu  # quarantined, not left to re-fail forever

    def test_disk_corruption_falls_back_to_remote_copy(self, tmp_path):
        """A bit-flip on disk must fall THROUGH to the next tier, not poison
        the get: the remote copy still serves, and the disk entry is gone."""
        from production_stack_tpu.kvoffload import cache_server

        port, stop = _run_server(
            lambda h, p: cache_server.serve(h, p, max_bytes=1 << 20)
        )
        try:
            st = TieredKVStore(
                disk_path=str(tmp_path), disk_bytes=1 << 20,
                remote_url=f"127.0.0.1:{port}",
            )
            blob = self._blob()
            st.put("k", blob)  # disk + write-through to remote
            # corrupt the on-disk file in place
            f = tmp_path / "k.kv"
            raw = bytearray(f.read_bytes())
            raw[len(raw) // 2] ^= 0x01
            f.write_bytes(bytes(raw))
            assert st.get("k") == blob  # served from the REMOTE copy
            assert st.corrupt_pages == 1
            assert st.hits["remote"] == 1
        finally:
            stop()

    def test_truncated_disk_file_rejected(self, tmp_path):
        st = TieredKVStore(disk_path=str(tmp_path), disk_bytes=1 << 20)
        blob = self._blob()
        st.put("k", blob)
        f = tmp_path / "k.kv"
        f.write_bytes(f.read_bytes()[: len(blob) // 2])  # torn write
        assert st.get("k") is None
        assert st.corrupt_pages == 1

    def test_cache_server_quarantines_corrupt_entry(self):
        from production_stack_tpu.kvoffload.cache_server import CacheServer

        cs = CacheServer(max_bytes=1 << 20)
        blob = self._blob()
        bad = bytearray(blob)
        bad[-2] ^= 0x10
        cs.put("k", bytes(bad))
        assert cs.get("k") is None  # shared server never fans corruption out
        assert cs.corrupt == 1
        assert cs.get("k") is None and cs.corrupt == 1  # gone, not re-failed
        assert cs.stats()["corrupt"] == 1


class TestShardBoundary:
    """Tensor-parallel shard gather/scatter at the serde boundary (ISSUE
    12): tier blobs are whole logical pages — one logical page = tp
    physical head-shards, gathered before serialize and scattered after
    deserialize — so a blob corrupted in ANY shard's head slice converts to
    a miss, and split/join round the shard decomposition exactly."""

    def _page(self, KH=4):
        rng = np.random.RandomState(7)
        k = rng.randn(2, 8, KH, 16).astype(np.float32)
        v = rng.randn(2, 8, KH, 16).astype(np.float32)
        return k, v

    def test_split_join_roundtrip(self):
        from production_stack_tpu.kvoffload.serde import (
            join_kv_heads,
            split_kv_heads,
        )

        k, v = self._page()
        for shards in (1, 2, 4):
            parts = split_kv_heads(k, v, shards)
            assert len(parts) == shards
            for ks, vs in parts:
                assert ks.shape[2] == 4 // shards
            k2, v2 = join_kv_heads(parts)
            np.testing.assert_array_equal(k, k2)
            np.testing.assert_array_equal(v, v2)

    def test_split_rejects_uneven_heads(self):
        from production_stack_tpu.kvoffload.serde import split_kv_heads

        k, v = self._page(KH=2)
        with pytest.raises(ValueError, match="split"):
            split_kv_heads(k, v, 4)

    def test_blob_is_shard_invariant(self):
        """serialize(gathered page) == serialize(join(shards)) — the tier
        never sees which tp shape wrote a blob."""
        from production_stack_tpu.kvoffload.serde import (
            join_kv_heads,
            split_kv_heads,
        )

        k, v = self._page()
        whole = get_serde("naive").serialize(k, v)
        rejoined = get_serde("naive").serialize(
            *join_kv_heads(split_kv_heads(k, v, 4))
        )
        assert whole == rejoined

    def test_corruption_in_one_shard_slice_rejected(self):
        """Flip one byte inside EACH head-shard's slice of the body in
        turn: the CRC covers the whole gathered page, so damage to any
        single shard's bytes converts the blob to a miss, never to a
        silently wrong shard scattered back into the pool."""
        from production_stack_tpu.kvoffload import serde as serde_mod

        k, v = self._page()
        blob = get_serde("naive").serialize(k, v)
        hdr_len = 4 + int.from_bytes(blob[:4], "big")
        body_len = len(blob) - hdr_len
        for shard in range(4):
            bad = bytearray(blob)
            # a byte within shard i's kv-head slice of the K payload
            off = hdr_len + (body_len // 2) * shard // 4 + 5
            bad[off] ^= 0x01
            with pytest.raises(KVIntegrityError):
                verify_blob(bytes(bad))
            with pytest.raises(KVIntegrityError):
                serde_mod.deserialize(bytes(bad))


class TestCorruptionRecomputeFallback:
    """End-to-end: a corrupted offload tier must yield token-identical output
    via recompute — checksum rejection converts a restore into a miss, never
    into wrong KV (acceptance: corrupt pages are never served)."""

    @pytest.fixture(scope="class")
    def engine(self):
        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.engine import LLMEngine

        cfg = EngineConfig(
            model="llama-debug", max_model_len=256, max_num_seqs=4,
            num_pages=28, page_size=8, prefill_chunk=32,
            kv_offload_cpu_gb=0.001,
        )
        eng = LLMEngine(cfg)
        eng.start()
        yield eng
        eng.stop()

    def _greedy(self, engine, prompt, n=4):
        from production_stack_tpu.engine.scheduler import SamplingParams

        async def run():
            toks = []
            async for out in engine.generate(
                f"cor-{np.random.randint(1 << 30)}", prompt=prompt,
                params=SamplingParams(max_tokens=n, temperature=0.0,
                                      ignore_eos=True),
            ):
                toks.extend(out.token_ids)
            return toks

        return asyncio.run(run())

    def test_bitflipped_spill_recomputes_token_identical(self, engine):
        prompt = "integrity check: the five boxing wizards jump quickly " * 3
        first = self._greedy(engine, prompt)
        # churn the pool so the prompt's pages spill to the CPU tier
        for i in range(6):
            self._greedy(engine, f"corruption filler number {i} padding " * 3)
        store = engine._offload.store
        assert store.cpu is not None and len(store.cpu) > 0
        # flip a bit in EVERY spilled blob: any restore attempt must reject
        for key in list(store.cpu._data):
            raw = bytearray(store.cpu._data[key])
            raw[-1] ^= 0x01
            store.cpu._data[key] = bytes(raw)
        c0 = engine.stats()["kv_corrupt_pages_total"]
        again = self._greedy(engine, prompt)
        assert again == first, "recompute fallback must be token-identical"
        stats = engine.stats()
        # the corruption was detected + quarantined (counter incremented),
        # and the corrupt pages were never scattered into the pool
        assert stats["kv_corrupt_pages_total"] > c0
        assert stats["kv_corrupt_pages_total"] == store.corrupt_pages


class TestCacheServer:
    def test_put_get_over_tcp(self):
        from production_stack_tpu.kvoffload import cache_server
        from production_stack_tpu.kvoffload.tiers import RemoteTier

        port, stop = _run_server(
            lambda h, p: cache_server.serve(h, p, max_bytes=1 << 20)
        )
        try:
            remote = RemoteTier(f"127.0.0.1:{port}")
            assert remote.get("nope") is None
            blob = seal_bytes(b"payload-bytes")
            remote.put("key1", blob)
            assert remote.get("key1") == blob
            assert "key1" in remote
            remote.close()
        finally:
            stop()

    def test_store_with_remote_tier(self):
        from production_stack_tpu.kvoffload import cache_server

        port, stop = _run_server(
            lambda h, p: cache_server.serve(h, p, max_bytes=1 << 20)
        )
        try:
            # two stores sharing one server: what one puts, the other gets
            a = TieredKVStore(cpu_bytes=1000, remote_url=f"127.0.0.1:{port}")
            b = TieredKVStore(cpu_bytes=1000, remote_url=f"127.0.0.1:{port}")
            blob = seal_bytes(b"kv-blob")
            a.put("shared", blob)
            assert b.get("shared") == blob
            assert b.hits["remote"] == 1
        finally:
            stop()


class TestController:
    def test_admit_lookup_evict(self):
        from production_stack_tpu.engine.kv_manager import prefix_hashes
        from production_stack_tpu.kvoffload import controller as ctl

        port, stop = _run_server(lambda h, p: ctl.serve(h, p))
        try:
            page = 8
            tokens = list(range(32))  # 4 chunks
            hashes = [h.hex() for h in prefix_hashes(tokens, page)]

            w1 = ctl.WorkerClient(f"127.0.0.1:{port}", "eng-1")
            w1.register("http://e1:8100", page)
            w1.admit(hashes[:3])
            w2 = ctl.WorkerClient(f"127.0.0.1:{port}", "eng-2")
            w2.register("http://e2:8100", page)
            w2.admit(hashes[:1])

            async def lookup(toks):
                c = ctl.ControllerClient(f"127.0.0.1:{port}")
                res = await c.lookup(toks)
                await c.close()
                return res

            res = asyncio.run(lookup(tokens))
            assert res["instance_id"] == "eng-1"  # longest chain wins
            assert res["url"] == "http://e1:8100"
            assert res["matched_chunks"] == 3

            w1.evict(hashes[:3])
            res = asyncio.run(lookup(tokens))
            assert res["instance_id"] == "eng-2"
            assert res["matched_chunks"] == 1

            w2.deregister()
            res = asyncio.run(lookup(tokens))
            assert res["instance_id"] is None
            w1.close()
            w2.close()
        finally:
            stop()


class TestEngineOffload:
    """Evicted pages spill to host DRAM and are restored with correct KV."""

    @pytest.fixture(scope="class")
    def engine(self):
        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.engine import LLMEngine

        cfg = EngineConfig(
            model="llama-debug",
            max_model_len=256,
            max_num_seqs=4,
            num_pages=28,  # small pool -> frequent eviction
            page_size=8,
            prefill_chunk=32,
            kv_offload_cpu_gb=0.001,  # 1 MB: plenty for debug-size pages
        )
        eng = LLMEngine(cfg)
        eng.start()
        yield eng
        eng.stop()

    def _greedy(self, engine, prompt, n=4):
        from production_stack_tpu.engine.scheduler import SamplingParams

        async def run():
            toks = []
            async for out in engine.generate(
                f"off-{np.random.randint(1 << 30)}", prompt=prompt,
                params=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True),
            ):
                toks.extend(out.token_ids)
            return toks

        return asyncio.run(run())

    def test_evict_restore_correct(self, engine):
        prompt_a = "the quick brown fox jumps over the lazy dog " * 3
        first = self._greedy(engine, prompt_a)
        # Evict A's pages by filling the pool with other prompts.
        for i in range(6):
            self._greedy(engine, f"filler prompt number {i} with padding text " * 3)
        assert engine._offload.saved_pages > 0, "eviction should have spilled pages"
        again = self._greedy(engine, prompt_a)
        assert engine.kv.offload_hits > 0, "second run should restore from offload"
        assert again == first, "restored KV must reproduce greedy output"
        stats = engine.stats()
        assert stats["kv_offload_loaded_pages_total"] > 0


class TestCappedOffloadIO:
    """kv_offload_max_io_pages: per-operation spill/restore budget for slow
    host<->device links (EngineConfig doc: past the cap the prefix
    recomputes instead of restoring)."""

    class _FakeOffload:
        def __init__(self):
            self.store = {}
            self.evicted = []

        def save_pages(self, pairs):
            for pid, h in pairs:
                self.store.setdefault(h, pid)

        def report_evict(self, hs):
            self.evicted.extend(hs)

        def report_admit(self, hs):
            pass

        def has(self, h):
            return h in self.store

        def load_pages(self, pairs):
            return len(pairs)

    def test_spill_keeps_chain_head_and_reports_dropped(self):
        from production_stack_tpu.engine.kv_manager import KVPageManager

        off = self._FakeOffload()
        kv = KVPageManager(8, 4, offload=off, max_io_pages=2)
        toks = list(range(32))
        pages = kv.allocate(8)
        kv.register_filled(toks, pages)
        kv.free(pages)
        kv.free(kv.allocate(8))  # evict all 8: spill 2 (head), drop 6
        assert len(off.store) == 2
        assert len(off.evicted) == 6
        # prefix restore finds the chain HEAD (eviction order = free order =
        # head first) and truncates at the cap; the tail recomputes
        _, cached = kv.match_prefix(toks)
        assert cached == 8

    def test_unbounded_by_default(self):
        from production_stack_tpu.engine.kv_manager import KVPageManager

        off = self._FakeOffload()
        kv = KVPageManager(8, 4, offload=off)
        toks = list(range(32))
        pages = kv.allocate(8)
        kv.register_filled(toks, pages)
        kv.free(pages)
        kv.free(kv.allocate(8))
        assert len(off.store) == 8 and not off.evicted
        _, cached = kv.match_prefix(toks)
        assert cached == 32
