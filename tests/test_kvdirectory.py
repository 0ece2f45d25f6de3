"""Fleet-wide KV directory (ISSUE 9, docs/kv-directory.md).

Four layers:

- **KVDirectory units**: publish/lookup, generation-fenced expiry,
  withdraw-on-evict semantics, TTL liveness, blob-map consistency,
  snapshot persistence.
- **Router ranking units**: KV-aware v2's resident > restorable > cold
  ordering, restore-cap weighting, and the prefix-trie discovery-dropout
  sweep (satellite bugfix).
- **Wire units**: DirectoryPublisher (dirty-batched engine publisher) and
  DirectoryPuller (admission prefetch) against a real cache server process.
- **3-engine HTTP acceptance**: engine A builds a fleet-warm shared prefix,
  engine C (cold) achieves a first-round prefix hit rate >= 0.5 via
  cross-engine pull through the shared cache server, with zero corrupt-page
  serves, and the directory survives an engine SIGTERM/restart via
  generation fencing.
"""

import asyncio
import re
import signal
import time

import pytest
import requests

from production_stack_tpu.engine.kv_manager import KVPageManager, prefix_hashes
from production_stack_tpu.engine.tokenizer import ByteTokenizer
from production_stack_tpu.kvdirectory import (
    DirectoryPublisher,
    DirectoryPuller,
    KVDirectory,
)
from production_stack_tpu.kvoffload.protocol import BlockingClient
from production_stack_tpu.kvoffload.serde import get_serde
from production_stack_tpu.kvoffload.tiers import TieredKVStore
from production_stack_tpu.router.hashtrie import HashTrie
from production_stack_tpu.router.routing_logic import (
    KvawareRouter,
    PrefixAwareRouter,
)
from production_stack_tpu.router.service_discovery import EndpointInfo
from production_stack_tpu.testing.procs import (
    free_port,
    start_proc,
    stop_proc,
    wait_healthy,
)

A, B, C = "http://a:1", "http://b:1", "http://c:1"


def _entries(n, start=0):
    return [(f"h{start + i:02d}", start + i, 1.0) for i in range(n)]


def _hexes(n, start=0):
    return [f"h{start + i:02d}" for i in range(n)]


class TestKVDirectory:
    def test_publish_and_contiguous_lookup(self):
        d = KVDirectory()
        d.register(A, 8, 1)
        d.publish(A, 1, _entries(3), "hbm")
        res = d.lookup_hashes(_hexes(4))
        assert res["resident"] == {A: 3}
        assert res["shared"] == [False] * 4
        # a hole breaks contiguity: withdraw the middle chunk
        d.withdraw(A, ["h01"], "all")
        assert d.lookup_hashes(_hexes(4))["resident"] == {A: 1}

    def test_shared_claims_and_withdraw_scopes(self):
        d = KVDirectory()
        d.register(A, 8, 1)
        d.publish(A, 1, _entries(2), "hbm")
        d.publish(A, 1, _entries(2), "shared")
        # withdraw-on-evict WITH a restorable blob: resident claim drops,
        # shared stays (the blob still exists in the tier)
        d.withdraw(A, ["h00"], "resident")
        res = d.lookup_hashes(_hexes(2))
        assert res["resident"] == {}  # h00 no longer resident -> chain breaks
        assert res["shared"] == [True, True]
        # evict-without-spill: nothing restorable remains
        d.withdraw(A, _hexes(2), "all")
        res = d.lookup_hashes(_hexes(2))
        assert res["shared"] == [False, False]
        assert d.stats()["kv_directory_entries"] == 0

    def test_generation_fence_expires_older_claims(self):
        d = KVDirectory()
        d.publish(A, 1, _entries(4), "hbm", page_size=8)
        assert d.lookup_hashes(_hexes(4))["resident"] == {A: 4}
        # the reborn incarnation registers with a higher generation: every
        # older-generation claim expires instead of poisoning lookups
        d.register(A, 8, 2)
        assert d.lookup_hashes(_hexes(4))["resident"] == {}
        assert d.expired_entries_total == 4
        # ...and the FENCED incarnation's late flush is dropped outright
        d.publish(A, 1, _entries(4), "hbm")
        assert d.lookup_hashes(_hexes(4))["resident"] == {}
        d.publish(A, 2, _entries(2), "hbm")
        assert d.lookup_hashes(_hexes(4))["resident"] == {A: 2}

    def test_lazy_stale_entry_is_counted_and_dropped(self):
        """Backstop for states the eager fence walk cannot see (e.g. a
        snapshot raced a generation bump): lookup-time fencing counts the
        stale hit and drops the entry."""
        d = KVDirectory()
        d.publish(A, 1, _entries(2), "hbm", page_size=8)
        d.engines[A].generation = 5  # simulate un-walked bump
        assert d.lookup_hashes(_hexes(2))["resident"] == {}
        assert d.stale_hits_total > 0
        assert d.lookup_hashes(_hexes(2))["shared"] == [False, False]

    def test_ttl_drops_resident_but_keeps_shared(self):
        d = KVDirectory(engine_timeout=0.05)
        d.publish(A, 1, _entries(2), "hbm", page_size=8)
        d.publish(A, 1, _entries(2), "shared")
        time.sleep(0.08)
        res = d.lookup_hashes(_hexes(2))
        # the engine's HBM is presumed gone; the cache-server blobs are not
        assert res["resident"] == {}
        assert res["shared"] == [True, True]
        assert d.expired_entries_total == 2

    def test_blob_check_governs_restorable(self):
        present = {"h00"}
        d = KVDirectory(blob_check=lambda k: k in present)
        d.publish(A, 1, _entries(2), "shared", page_size=8)
        assert d.lookup_hashes(_hexes(2))["shared"] == [True, False]
        # the claim for the vanished blob was dropped, not just skipped
        assert "h01" not in d.chunks

    def test_blob_evicted_clears_shared(self):
        d = KVDirectory()
        d.publish(A, 1, _entries(1), "shared", page_size=8)
        d.publish(A, 1, _entries(1), "hbm")
        d.blob_evicted("h00")
        res = d.lookup_hashes(["h00"])
        assert res["shared"] == [False]
        assert res["resident"] == {A: 1}  # HBM claim unaffected

    def test_lookup_tokens_per_page_size_chains(self):
        d = KVDirectory()
        tokens = list(range(32))
        h8 = [h.hex() for h in prefix_hashes(tokens, 8)]
        h16 = [h.hex() for h in prefix_hashes(tokens, 16)]
        d.publish(A, 1, [(h, i, 1.0) for i, h in enumerate(h8[:3])], "hbm",
                  page_size=8)
        d.publish(B, 1, [(h16[0], 0, 1.0)], "hbm", page_size=16)
        d.publish(B, 1, [(h16[0], 0, 1.0)], "shared")
        res = d.lookup_tokens(tokens)
        assert res["engines"][A]["resident_tokens"] == 24
        assert res["engines"][B]["resident_tokens"] == 16
        # restorable is per page size: only B's 16-token chunk is shared
        assert res["restorable"] == {"16": 16}

    def test_snapshot_roundtrip_keeps_fencing(self):
        d = KVDirectory()
        d.publish(A, 1, _entries(3), "shared", page_size=8)
        d.publish(A, 1, _entries(3), "hbm")
        doc = d.snapshot()
        d2 = KVDirectory()
        assert d2.load_snapshot(doc) == 3
        assert d2.lookup_hashes(_hexes(3))["resident"] == {A: 3}
        # a reborn engine fences the snapshot-restored claims too
        d2.register(A, 8, 2)
        assert d2.lookup_hashes(_hexes(3))["resident"] == {}


class TestKvawareV2Ranking:
    @staticmethod
    def _router():
        r = KvawareRouter.__new__(KvawareRouter)
        r.route_class_counts = {"resident": 0, "restorable": 0, "cold": 0}
        return r

    @staticmethod
    def _eps(*urls):
        return [EndpointInfo(url=u, model_names=["m"], added_timestamp=0.0)
                for u in urls]

    class _ES:
        def __init__(self, cap):
            self.kv_offload_max_io_pages = cap

    def test_resident_beats_restorable(self):
        r = self._router()
        res = {
            "engines": {A: {"resident_tokens": 128, "page_size": 8}},
            "restorable": {"8": 512},
        }
        cls, url = r._rank_v2(res, self._eps(A, B), {}, {})
        assert (cls, url) == ("resident", A)

    def test_resident_claim_outside_endpoints_is_ignored(self):
        r = self._router()
        res = {"engines": {C: {"resident_tokens": 128}}, "restorable": {}}
        cls, url = r._rank_v2(res, self._eps(A, B), {}, {})
        assert (cls, url) == ("cold", None)

    def test_restorable_weighted_by_restore_cap(self):
        """The engine-exported linkprobe cap is the restore-vs-recompute
        crossover: a backend that would only restore 1 page scores 8 tokens;
        an unbounded one scores the whole shared prefix and wins."""
        r = self._router()
        res = {"engines": {}, "restorable": {"8": 80}}
        stats = {A: self._ES(cap=1), B: self._ES(cap=0)}  # 0/-1 = unbounded
        cls, url = r._rank_v2(res, self._eps(A, B), stats, {})
        assert (cls, url) == ("restorable", B)
        # unscraped backends count as unbounded too (hint, verified on pull)
        cls, url = r._rank_v2(res, self._eps(A, C), {A: self._ES(1)}, {})
        assert (cls, url) == ("restorable", C)
        # a SCRAPED backend whose cap metric is absent (-1) has no offload
        # tiers at all: it cannot pull, so it must not win restorable — a
        # fleet of such backends degrades to cold, not to recompute-routing
        stats = {A: self._ES(cap=-1.0), B: self._ES(cap=-1.0)}
        cls, url = r._rank_v2(res, self._eps(A, B), stats, {})
        assert (cls, url) == ("cold", None)
        stats = {A: self._ES(cap=-1.0), B: self._ES(cap=2)}
        cls, url = r._rank_v2(res, self._eps(A, B), stats, {})
        assert (cls, url) == ("restorable", B)

    def test_restorable_requires_page_size_compatibility(self):
        """Chunk identity is page-size-dependent: a backend registered at a
        different page size cannot consume the shared blobs and must not be
        credited for them (unknown backends stay optimistic)."""
        r = self._router()
        res = {
            "engines": {},
            "restorable": {"16": 160},
            "page_sizes": {A: 32, B: 16},
        }
        cls, url = r._rank_v2(res, self._eps(A, B), {}, {})
        assert (cls, url) == ("restorable", B)
        # only incompatible backends available: cold, not a doomed pull
        cls, url = r._rank_v2(res, self._eps(A), {}, {})
        assert (cls, url) == ("cold", None)

    def test_cold_when_directory_knows_nothing(self):
        r = self._router()
        cls, url = r._rank_v2(
            {"engines": {}, "restorable": {}}, self._eps(A, B), {}, {}
        )
        assert (cls, url) == ("cold", None)
        assert r.route_class_counts == {"resident": 0, "restorable": 0,
                                        "cold": 0}  # counted by caller


class TestTrieDropoutSweep:
    """Satellite bugfix: the per-backend hash trie retained entries for
    backends removed from service discovery, so a departed backend kept
    winning locality scores."""

    @staticmethod
    def _router():
        r = PrefixAwareRouter.__new__(PrefixAwareRouter)
        r.trie = HashTrie()
        r._trie_urls = set()
        return r

    def test_departed_backend_is_swept_from_trie(self):
        r = self._router()
        prompt = "x" * 300

        async def run():
            await r.trie.insert(prompt, A)
            r._trie_urls.add(A)
            await r.trie.insert("y" * 300, B)
            r._trie_urls.add(B)
            pre = await r.trie.longest_prefix_match(prompt, {A, B})
            # discovery drops A (config removal / stale-drop)
            await r.sweep_departed({B})
            post = await r.trie.longest_prefix_match(prompt, {A, B})
            return pre, post

        (pre_m, pre_c), (post_m, post_c) = asyncio.run(run())
        # before the sweep the departed backend WINS the locality score —
        # the bug this satellite fixes
        assert pre_c == {A} and pre_m > 0
        # after: no match (the fallback set is "anyone", not a locality win)
        assert post_m == 0
        assert A not in r._trie_urls

    def test_surviving_backends_keep_their_claims(self):
        r = self._router()

        async def run():
            await r.trie.insert("z" * 300, B)
            r._trie_urls.add(B)
            await r.sweep_departed({B})  # B still discovered: no-op
            return await r.trie.longest_prefix_match("z" * 300, {B})

        matched, cands = asyncio.run(run())
        assert cands == {B} and matched > 0


# ---------------------------------------------------------------------------
# Wire units: publisher + puller against a real cache server process
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_server():
    port = free_port()
    proc = start_proc([
        "-m", "production_stack_tpu.kvoffload.cache_server",
        "--port", str(port), "--host", "127.0.0.1", "--directory",
    ])
    # frame server: poll with a ping instead of HTTP health
    deadline = time.time() + 30
    last = None
    while time.time() < deadline:
        try:
            c = BlockingClient("127.0.0.1", port, timeout=2)
            c.request({"op": "ping"})
            c.close()
            break
        except Exception as e:  # noqa: BLE001 - still booting
            last = e
            time.sleep(0.1)
    else:
        stop_proc(proc)
        raise RuntimeError(f"cache server never came up: {last}")
    yield f"127.0.0.1:{port}"
    stop_proc(proc)


def _dir_dump(url: str) -> dict:
    host, port = url.split(":")
    c = BlockingClient(host, int(port), timeout=5)
    try:
        hdr, _ = c.request({"op": "dir_dump"})
        return hdr
    finally:
        c.close()


class TestPublisherWire:
    def test_dirty_batched_publish_withdraw_ordering(self, cache_server):
        toks = list(range(16))
        hashes = prefix_hashes(toks, 4)  # 4 chunks
        pub = DirectoryPublisher(
            cache_server, "http://e:1", page_size=4, generation=3,
            flush_interval_s=0.1,
        )
        try:
            pub.publish_resident([(h, i, 1.0) for i, h in enumerate(hashes)])
            # enqueued AFTER the publish: the flush must preserve order
            pub.withdraw([hashes[-1]], "all")
            deadline = time.time() + 10
            while time.time() < deadline:
                d = _dir_dump(cache_server)
                eng = d.get("engines", {}).get("http://e:1") or {}
                # the publisher counts a withdrawal once its request has
                # RETURNED: the server can show it a moment earlier
                if eng.get("resident_chunks") == 3 and pub.withdrawals == 1:
                    break
                time.sleep(0.1)
            d = _dir_dump(cache_server)
            eng = d["engines"]["http://e:1"]
            assert eng["resident_chunks"] == 3, d
            assert eng["generation"] == 3
            assert pub.publishes == 4 and pub.withdrawals == 1
        finally:
            pub.stop()

    def test_shared_disabled_publisher_never_claims_shared(self, cache_server):
        pub = DirectoryPublisher(
            cache_server, "http://e:2", page_size=4, generation=1,
            flush_interval_s=0.1, shared_enabled=False,
        )
        try:
            pub.publish_shared([(b"\x01" * 16, 0, 1.0)])
            pub.publish_resident([(b"\x02" * 16, 0, 1.0)])
            deadline = time.time() + 10
            while time.time() < deadline:
                d = _dir_dump(cache_server)
                eng = d.get("engines", {}).get("http://e:2") or {}
                if eng.get("resident_chunks", 0) > 0:
                    break
                time.sleep(0.1)
            eng = _dir_dump(cache_server)["engines"]["http://e:2"]
            # a disk-only tier is private: no shared claims advertised
            assert eng["shared_chunks"] == 0
            assert eng["resident_chunks"] == 1
        finally:
            pub.stop()


class TestPublisherBounds:
    def test_pending_is_bounded_by_entry_count_not_batch_count(self):
        """One batch can carry a whole working set; the outage bound must
        count ENTRIES or a directory outage grows engine memory unboundedly."""
        batches = [("hbm", [("h", i, 1.0)] * 100) for i in range(10)]
        kept = DirectoryPublisher._trim_entries(batches, 250)
        assert len(kept) == 2  # newest 2 x 100 entries fit; a 3rd would not
        assert kept == batches[-2:]
        assert DirectoryPublisher._trim_entries(batches, 5000) == batches

    def test_put_drops_oldest_entries_when_over_cap(self):
        pub = DirectoryPublisher.__new__(DirectoryPublisher)
        import queue as queue_mod
        import threading as threading_mod

        pub._q = queue_mod.Queue()
        pub._queued_entries = 0
        pub._entries_lock = threading_mod.Lock()
        big = [(bytes([i]) * 16, 0, 1.0) for i in range(200)]
        old_cap = DirectoryPublisher.MAX_PENDING
        try:
            DirectoryPublisher.MAX_PENDING = 300
            pub.publish_resident(big)   # 200 entries
            pub.publish_resident(big)   # 400 -> oldest batch dropped
            assert pub._queued_entries == 200
            assert pub._q.qsize() == 1
        finally:
            DirectoryPublisher.MAX_PENDING = old_cap


class TestPullerWire:
    def test_prefetch_pulls_shared_blobs_into_local_tier(self, cache_server):
        import numpy as np

        toks = list(range(12))
        hashes = prefix_hashes(toks, 4)  # 3 chunks
        serde = get_serde("naive")
        blob = serde.serialize(
            np.zeros((1, 4, 1, 2), np.float32), np.zeros((1, 4, 1, 2), np.float32)
        )
        # "another engine" spilled the first two chunks into the shared tier
        store = TieredKVStore(cpu_bytes=1 << 20, remote_url=cache_server)
        for h in hashes[:2]:
            store.remote.put(h.hex(), blob)
        host, port = cache_server.split(":")
        c = BlockingClient(host, int(port))
        c.request({
            "op": "dir_publish", "url": "http://far:1", "generation": 1,
            "tier": "shared", "page_size": 4,
            "entries": [[h.hex(), i, 1.0] for i, h in enumerate(hashes[:2])],
        })
        c.close()
        kv = KVPageManager(8, 4)
        puller = DirectoryPuller(cache_server, kv, store, page_size=4)
        got = asyncio.run(puller.maybe_prefetch(toks))
        assert got == 2
        for h in hashes[:2]:
            assert store.contains_local(h.hex())
        assert puller.stats()["kv_directory_pulled_pages_total"] == 2
        assert puller.stats()["kv_directory_lookup_hits_total"] == 1
        # nothing restorable for a disjoint prompt: no pull, no local writes
        assert asyncio.run(puller.maybe_prefetch(list(range(100, 112)))) == 0

    def test_local_match_short_circuits(self, cache_server):
        toks = list(range(8))
        kv = KVPageManager(8, 4)
        pages = kv.allocate(2)
        kv.register_filled(toks, pages)
        store = TieredKVStore(cpu_bytes=1 << 20, remote_url=cache_server)
        puller = DirectoryPuller(cache_server, kv, store, page_size=4)
        assert asyncio.run(puller.maybe_prefetch(toks)) == 0
        assert puller.lookups == 0  # fully local: no directory round trip


# ---------------------------------------------------------------------------
# 3-engine HTTP acceptance: fleet-warm cross-engine pull + restart fencing
# ---------------------------------------------------------------------------

PAGE = 8
SHARED = "S" * (8 * PAGE)  # 8-page fleet-wide shared prefix
USERS = 4

VLLM_RE = re.compile(r"(vllm:[a-z_]+)\{[^}]*\} ([0-9.eE+-]+)$")


def _counters(base: str) -> dict:
    out = {}
    for line in requests.get(f"{base}/metrics", timeout=10).text.splitlines():
        m = VLLM_RE.match(line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def _engine_argv(port: int, cache_url: str, xla_cache: str) -> list:
    return [
        "-m", "production_stack_tpu.engine.api_server",
        "--model", "llama-debug", "--port", str(port),
        "--max-model-len", "256", "--num-pages", "64",
        "--page-size", str(PAGE), "--prefill-chunk", "64",
        "--kv-offload-cpu-gb", "0.1",
        "--kv-remote-url", cache_url,
        "--kv-directory-url", cache_url,
        "--kv-directory-flush-s", "0.5",
        "--warm-start", "--warm-start-namespace", f"dir-e2e-{port}",
        "--warm-start-interval-s", "2",
        "--compilation-cache-dir", xla_cache,
    ]


def _post(base, prompt, errors, max_tokens=4):
    r = requests.post(
        f"{base}/v1/completions",
        json={"model": "llama-debug", "prompt": prompt,
              "max_tokens": max_tokens, "temperature": 0.0,
              "ignore_eos": True},
        timeout=120,
    )
    if r.status_code not in (200, 429):
        errors.append((r.status_code, r.text[:200]))
    return r


@pytest.mark.slow  # ~55 s: 3 subprocess engines + cache server; the
# directory protocol itself has in-process coverage above
def test_three_engine_fleet_warm_cross_engine_pull(tmp_path):
    """Acceptance (ISSUE 9): engine A serves a long shared prefix and its
    warm-start spill lands the blobs in the shared cache server + directory;
    engine C — a COLD process that never saw the prefix — achieves a
    first-round prefix hit rate >= 0.5 by pulling it cross-engine (cold
    baseline ~0), with zero corrupt-page serves. Then A is SIGTERM-restarted:
    the directory survives via generation fencing (A republishes under
    generation+1) and serving continues with zero non-429 errors."""
    xla_cache = str(tmp_path / "xla-cache")
    errors: list = []

    cache_port = free_port()
    cache = start_proc([
        "-m", "production_stack_tpu.kvoffload.cache_server",
        "--port", str(cache_port), "--host", "127.0.0.1", "--directory",
        "--directory-persist-path", str(tmp_path / "dir.snap"),
    ])
    cache_url = f"127.0.0.1:{cache_port}"

    ports = {n: free_port() for n in "ABC"}
    bases = {n: f"http://127.0.0.1:{p}" for n, p in ports.items()}
    procs = {}
    try:
        # A boots first and pays the XLA compile; B and C then boot in
        # parallel against the shared compilation cache
        procs["A"] = start_proc(_engine_argv(ports["A"], cache_url, xla_cache))
        wait_healthy(f"{bases['A']}/health", procs["A"], timeout=300)
        procs["B"] = start_proc(_engine_argv(ports["B"], cache_url, xla_cache))
        procs["C"] = start_proc(_engine_argv(ports["C"], cache_url, xla_cache))
        for n in "BC":
            wait_healthy(f"{bases[n]}/health", procs[n], timeout=300)

        # --- build the fleet-warm set on A (B gets its own light round so
        # the directory tracks a real 3-engine fleet) --------------------
        for rnd in range(2):
            for u in range(USERS):
                _post(bases["A"], SHARED + f"a{u:02d}" + "q" * (2 * PAGE - 3)
                      + f"r{rnd}", errors)
        _post(bases["B"], "B-only " + "b" * 80, errors)
        assert not errors, errors

        # wait for A's warm-start spill to land the shared-prefix blobs in
        # the cache server and the shared claims in the directory
        deadline = time.time() + 30
        shared_seen = 0
        while time.time() < deadline:
            d = _dir_dump(cache_url)
            shared_seen = max(
                (e.get("shared_chunks", 0)
                 for e in (d.get("engines") or {}).values()),
                default=0,
            )
            if shared_seen >= 8:
                break
            time.sleep(0.5)
        assert shared_seen >= 8, _dir_dump(cache_url)
        assert len(_dir_dump(cache_url).get("engines", {})) == 3

        # --- THE acceptance number: C's FIRST round ----------------------
        c0 = _counters(bases["C"])
        assert c0.get("vllm:gpu_prefix_cache_queries_total", 0) == 0
        for u in range(USERS):
            _post(bases["C"], SHARED + f"c{u:02d}" + "w" * (PAGE - 3), errors)
        assert not errors, errors
        c1 = _counters(bases["C"])
        hits = (c1["vllm:gpu_prefix_cache_hits_total"]
                - c0.get("vllm:gpu_prefix_cache_hits_total", 0))
        queries = (c1["vllm:gpu_prefix_cache_queries_total"]
                   - c0.get("vllm:gpu_prefix_cache_queries_total", 0))
        assert queries > 0
        hit_rate = hits / queries
        assert hit_rate >= 0.5, (
            f"cold engine stayed cold: first-round hit rate {hit_rate:.3f} "
            f"(hits={hits:.0f} queries={queries:.0f})"
        )
        # the hits came through the cross-engine pull path
        assert c1.get("vllm:kv_directory_pulled_pages_total", 0) >= 8, c1
        assert c1.get("vllm:kv_directory_lookup_hits_total", 0) > 0, c1
        # zero corrupt-page serves anywhere (CRC fallback never tripped)
        for n in "ABC":
            assert _counters(bases[n]).get("vllm:kv_corrupt_pages_total", 0) == 0

        # --- SIGTERM A: the directory survives via generation fencing ----
        pre = _dir_dump(cache_url)
        a_url = next(
            u for u in pre["engines"]
            if u.endswith(f":{ports['A']}")
        )
        pre_gen = pre["engines"][a_url]["generation"]
        procs["A"].send_signal(signal.SIGTERM)
        assert procs["A"].wait(timeout=120) == 0
        procs["A"] = start_proc(_engine_argv(ports["A"], cache_url, xla_cache))
        wait_healthy(f"{bases['A']}/health", procs["A"], timeout=300)
        # the reborn A claimed generation+1 and republished its restored
        # working set under it (boot republish + publisher flush)
        deadline = time.time() + 20
        reborn = {}
        while time.time() < deadline:
            reborn = _dir_dump(cache_url)["engines"].get(a_url) or {}
            if (reborn.get("generation", 0) > pre_gen
                    and reborn.get("resident_chunks", 0) > 0):
                break
            time.sleep(0.5)
        assert reborn.get("generation", 0) > pre_gen, reborn
        assert reborn.get("resident_chunks", 0) > 0, reborn
        # serving continues fleet-wide, zero non-429 errors
        for n in "ABC":
            _post(bases[n], SHARED + f"post-{n}", errors)
        assert not errors, errors
    finally:
        for p in procs.values():
            p.kill()
            p.wait(timeout=10)
        stop_proc(cache)
