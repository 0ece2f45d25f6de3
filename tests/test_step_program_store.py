"""The store of exported step programs (ISSUE 35, engine/step_programs.py):
a first dispatch that finds its program neither traces nor lowers the step
function, serves bit for bit what the write served and what the plain jit
serves, and nothing stale or broken is ever served in silence."""

import dataclasses
import os
import shutil
import sys
import threading
import time

import jax
import numpy as np
import pytest

from production_stack_tpu import tracing
from production_stack_tpu.engine import step_programs
from production_stack_tpu.engine.runner import ModelRunner, StepInput
from production_stack_tpu.engine.step_programs import (
    SUFFIX,
    StepProgramStore,
    package_digest,
    store_stats,
)
from production_stack_tpu.models import gemma2, llama, opt
from production_stack_tpu.utils import compile_cache

CFG = llama.PRESETS["llama-debug"]


def _runner(store_dir, cfg=CFG, **kw):
    """A runner whose store is ``store_dir`` (None: no store)."""
    r = ModelRunner(cfg, num_pages=16, page_size=8, seed=0, **kw)
    r.step_store = StepProgramStore(str(store_dir)) if store_dir else None
    return r


def _inp(B, ctx, T=1, k=None, temperature=0.0):
    pages = 2
    return StepInput(
        input_ids=(np.arange(B * T, dtype=np.int32).reshape(B, T) * 7 + 3) % 500,
        positions=np.broadcast_to(np.arange(ctx, ctx + T, dtype=np.int32), (B, T)),
        page_table=np.arange(B * pages, dtype=np.int32).reshape(B, pages),
        kv_lens=np.full((B,), ctx + T, np.int32),
        temperature=np.full(B, temperature, np.float32),
        top_k=np.zeros(B, np.int32), top_p=np.ones(B, np.float32),
        kv_limits=None if k is None else np.full((B,), ctx + T + k, np.int32),
    )


def _serve(r):
    """A prefill with log-probabilities, a greedy and a sampled decode burst
    and a sampled step: everything the runner returns, on the host."""
    ids, logits, lps = r.step(_inp(2, 0, T=4), want_logprobs=True)
    toks, blps = r.step_multi(_inp(2, 4, k=4), 4, want_logprobs=True)
    sampled = r.step_multi(_inp(2, 8, k=4, temperature=0.8), 4)
    one, _ = r.step(_inp(2, 12, temperature=0.8))
    return [np.asarray(x) for x in (ids, logits, *lps, toks, *blps, sampled, one)]


def _files(store_dir):
    return sorted(f for f in os.listdir(store_dir) if f.endswith(SUFFIX))


def _deserialises(path):
    return jax.export.deserialize(bytearray(path.read_bytes()))


def _events():
    return [e["data"] for e in tracing.get_flightrecorder().events(kind="compile")
            if e["data"].get("event") == "first_dispatch"]


class _Traced:
    """Names of the functions JAX traces on this thread while it is on."""

    def __init__(self):
        self.on, self.names = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, fun_name=None, **_kw):
        if self.on and name == "/jax/core/compile/jaxpr_trace_duration":
            self.names.append(fun_name)


@pytest.fixture(scope="module")
def traced():
    return _Traced()


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """A store one runner has served through: (directory, what it served)."""
    d = tmp_path_factory.mktemp("store")
    r = _runner(d)
    served = _serve(r)
    assert r.step_store.writes == r.first_dispatch["count"] == 4
    assert r.step_store.hits == r.step_store.errors == 0
    return d, served, dict(r.first_dispatch)


def test_a_second_runner_hits_every_first_dispatch_and_serves_the_same_bits(written, traced):
    d, served, cold = written
    tracing.get_flightrecorder().reset()
    r = _runner(d)
    traced.names.clear()
    traced.on = True
    try:
        again = _serve(r)
    finally:
        traced.on = False
    fd, store = r.first_dispatch, r.step_store
    assert store.hits == fd["count"] == 4 and store.writes == store.errors == 0
    for a, b in zip(served, again):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # the step function was never traced: what JAX traced in those four
    # dispatches are the four wrappers over exported.call, nothing inside
    assert sorted(traced.names) == [
        "pstpu_multi_step_k4", "pstpu_multi_step_k4_lp", "pstpu_step", "pstpu_step_lp"]
    assert fd["trace"] + fd["lower"] < 0.5 * (cold["trace"] + cold["lower"])
    assert [e["store"] for e in _events()] == ["hit"] * 4
    assert len(_files(d)) == 4 and not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_the_store_path_serves_what_the_plain_jit_serves(written):
    _d, served, _ = written
    tracing.get_flightrecorder().reset()
    r = _runner(None)
    plain = _serve(r)
    for a, b in zip(served, plain):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert [e["store"] for e in _events()] == ["off"] * 4
    assert store_stats(None) == {
        "step_program_store_dir": None, "step_program_store_hits_total": 0,
        "step_program_store_writes_total": 0, "step_program_store_errors_total": 0,
        "step_program_store_bypassed": {}}


def _key_of(tmp_path, cfg=CFG, B=2, want_logprobs=False, **kw):
    """The one file a decode step of this runner leaves in a fresh store."""
    r = _runner(tmp_path, cfg, **kw)
    r.step(_inp(B, 4), want_logprobs=want_logprobs)
    (name,) = _files(tmp_path)
    return name


@pytest.fixture(scope="module")
def base_key(tmp_path_factory):
    return _key_of(tmp_path_factory.mktemp("base"))


def test_the_same_program_has_the_same_key_in_another_directory(tmp_path, base_key):
    assert _key_of(tmp_path) == base_key


@pytest.mark.parametrize("variant", [
    dict(cfg=dataclasses.replace(CFG, rope_theta=5e5)),
    dict(cfg=dataclasses.replace(CFG, kv_cache_dtype="int8")),
    dict(cfg=gemma2.PRESETS["gemma2-debug"]),
    dict(cfg=opt.PRESETS["opt-debug"]),
    dict(B=4),
    dict(want_logprobs=True),
    dict(enable_lora=True),
], ids=["config-field", "int8-pools", "gemma2", "opt", "shape", "sig", "lora"])
def test_whatever_decides_the_module_gives_another_key(tmp_path, base_key, variant):
    assert _key_of(tmp_path, **variant) != base_key


def test_one_changed_byte_in_a_package_file_gives_another_digest(tmp_path, monkeypatch):
    root = os.path.dirname(os.path.abspath(step_programs.__file__))
    for copy in ("a", "b"):
        shutil.copytree(root, tmp_path / copy,
                        ignore=shutil.ignore_patterns("__pycache__"))
    # content by relative path: where the tree lies does not matter
    assert package_digest(str(tmp_path / "a")) == package_digest(str(tmp_path / "b"))
    assert package_digest(str(tmp_path / "a")) == package_digest(root)
    with open(tmp_path / "b" / "runner.py", "ab") as f:
        f.write(b"#")
    (tmp_path / "a" / "renamed.py").write_bytes((tmp_path / "a" / "lora.py").read_bytes())
    os.unlink(tmp_path / "a" / "lora.py")
    package_digest.cache_clear()  # one reading a process: the files changed under it
    digests = {package_digest(str(tmp_path / c)) for c in ("a", "b")} | {package_digest(root)}
    assert len(digests) == 3
    # and the digest is in the key
    device = jax.devices()[0]
    before = step_programs.program_key({"program": "p"}, device)
    assert step_programs.program_key({"program": "p"}, device) == before
    assert step_programs.program_key({"program": "q"}, device) != before
    monkeypatch.setattr(step_programs, "package_digest",
                        lambda: package_digest(str(tmp_path / "b")))
    assert step_programs.program_key({"program": "p"}, device) != before


def test_a_truncated_blob_is_deleted_rebuilt_and_counted(tmp_path):
    tracing.get_flightrecorder().reset()
    first, _ = _runner(tmp_path).step(_inp(2, 4))
    (name,) = _files(tmp_path)
    whole = (tmp_path / name).read_bytes()
    (tmp_path / name).write_bytes(whole[: len(whole) // 2])
    r = _runner(tmp_path)
    again, _ = r.step(_inp(2, 4))
    assert np.array_equal(np.asarray(first), np.asarray(again))
    assert (r.step_store.hits, r.step_store.writes, r.step_store.errors) == (0, 1, 1)
    # whole again (not the same bytes: debug locations hold the caller's line)
    assert _files(tmp_path) == [name] and _deserialises(tmp_path / name)
    assert [e["store"] for e in _events()] == ["write", "error"]
    r = _runner(tmp_path)
    r.step(_inp(2, 4))
    assert (r.step_store.hits, r.step_store.writes, r.step_store.errors) == (1, 0, 0)


def test_a_blob_whose_call_does_not_lower_is_deleted_rebuilt_and_counted(tmp_path):
    """A file that deserialises but is another program's (here: another batch
    bucket's): its call raises while tracing, before anything is donated."""
    tracing.get_flightrecorder().reset()
    r = _runner(tmp_path)
    first, _ = r.step(_inp(2, 4))
    (small,) = _files(tmp_path)
    r.step(_inp(4, 4))
    (large,) = set(_files(tmp_path)) - {small}
    shutil.copyfile(tmp_path / large, tmp_path / small)
    r = _runner(tmp_path)
    again, _ = r.step(_inp(2, 4))
    assert np.array_equal(np.asarray(first), np.asarray(again))
    assert (r.step_store.hits, r.step_store.writes, r.step_store.errors) == (1, 1, 1)
    assert (tmp_path / small).read_bytes() != (tmp_path / large).read_bytes()
    shapes = {a.shape for a in _deserialises(tmp_path / small).in_avals}
    assert (2, 1) in shapes and (4, 1) not in shapes
    assert [e["store"] for e in _events()][-1] == "error"
    assert r.first_dispatch["count"] == 1 and not r.step_store.bypassed


def test_a_program_jax_export_refuses_runs_its_plain_jit_and_stats_say_why(tmp_path):
    class Odd:  # a pytree jax.export has no serialisation for
        def __init__(self, x):
            self.x = x

    jax.tree_util.register_pytree_node(
        Odd, lambda o: ((o.x,), None), lambda _aux, xs: Odd(*xs))
    tracing.get_flightrecorder().reset()
    r = _runner(tmp_path)

    def odd_program(_params, _k, _v, ids, odd):
        return (ids + odd.x,)

    odd_program.__name__ = "pstpu_odd"
    fn = r._jit(odd_program, (), (None,))
    ids = np.ones((2, 1), np.int32)
    staged = {"input_ids": ids, "page_table": np.zeros((2, 2), np.int32)}
    out = r._dispatch(fn, "step", ("odd",), staged, (None, None, None, ids, Odd(ids)))
    assert np.array_equal(np.asarray(out[0]), 2 * ids)
    stats = store_stats(r.step_store)
    assert stats["step_program_store_errors_total"] == 1
    assert stats["step_program_store_writes_total"] == 0
    assert "Odd" in stats["step_program_store_bypassed"]["pstpu_odd"]
    assert [e["store"] for e in _events()] == ["error"] and not _files(tmp_path)
    # the shape has dispatched: the plain jit serves it from now on
    assert np.array_equal(
        np.asarray(r._dispatch(fn, "step", ("odd",), staged,
                               (None, None, None, ids, Odd(ids)))[0]), 2 * ids)
    assert r.first_dispatch["count"] == 1 and r.step_store.errors == 1


def test_two_writers_of_one_key_leave_one_whole_file(tmp_path):
    store = StepProgramStore(str(tmp_path))
    blobs = [bytes([i]) * (200_000 + i) for i in range(16)]
    seen, stop = [], threading.Event()

    def read():
        while not stop.is_set():
            try:
                seen.append((tmp_path / ("k" + SUFFIX)).read_bytes())
            except FileNotFoundError:
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        writers = [threading.Thread(target=store._write, args=("k", b)) for b in blobs]
        for t in writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not any(t.is_alive() for t in writers)
    assert store.writes == 16 and os.listdir(tmp_path) == ["k" + SUFFIX]
    # no reader ever saw half a file, and what is left is one writer's whole blob
    assert seen and set(seen) <= set(blobs)
    assert (tmp_path / ("k" + SUFFIX)).read_bytes() in blobs


def test_without_a_cache_directory_there_is_no_store_and_no_file(tmp_path, monkeypatch):
    assert compile_cache.step_program_dir() == os.path.join(
        compile_cache._enabled_dir, "step_programs")  # conftest enabled one
    assert ModelRunner(CFG, num_pages=16, page_size=8).step_store.root == \
        compile_cache.step_program_dir()
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.setattr(StepProgramStore, "_write", lambda *_a: pytest.fail("wrote"))
    assert compile_cache.step_program_dir() is None
    tracing.get_flightrecorder().reset()
    r = ModelRunner(CFG, num_pages=16, page_size=8, seed=0)
    assert r.step_store is None
    r.step(_inp(2, 4))
    assert [e["store"] for e in _events()] == ["off"]


# -- the loader: what the store lists for a runner's identity is built at start-up --
# (ISSUE 50, step_programs.Preloader)

def _beside(store_dir, cfg=CFG, wait=True, **kw):
    """A runner that FINDS ``store_dir`` as the store beside its compile cache
    (so its loader reads that store's listing as it is built), the loader
    finished unless ``wait`` is False."""
    kw = {"num_pages": 16, "page_size": 8, **kw}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(StepProgramStore, "beside_compile_cache",
                   classmethod(lambda cls: StepProgramStore(str(store_dir))))
        r = ModelRunner(cfg, seed=0, **kw)
    assert r.step_store.root == r.preloaded.store.root == str(store_dir)
    if wait:
        assert r.preloaded.wait(120)
    return r


def _listing(store_dir):
    """The listing's files, whatever identity they stand under."""
    return sorted(
        os.path.join(store_dir, d, f)
        for d in os.listdir(store_dir) if d.endswith(step_programs.LISTING)
        for f in os.listdir(os.path.join(store_dir, d)))


def _listed_keys(store_dir):
    """The listed programs' keys, in the order in which they were met."""
    import json

    entries = {os.path.basename(p)[:-len(".json")]: json.load(open(p))
               for p in _listing(store_dir)}
    return sorted(entries, key=lambda k: entries[k]["order"])


def _state_inp(r, *a, **kw):
    inp = _inp(*a, **kw)
    if r.has_state:
        inp.state_slots = np.arange(inp.kv_lens.shape[0], dtype=np.int32)
    return inp


def _serve_family(r):
    """``_serve`` for any family: rows with state slots where it keeps state."""
    ids, logits, lps = r.step(_state_inp(r, 2, 0, T=4), want_logprobs=True)
    toks, blps = r.step_multi(_state_inp(r, 2, 4, k=4), 4, want_logprobs=True)
    sampled = r.step_multi(_state_inp(r, 2, 8, k=4, temperature=0.8), 4)
    one, _ = r.step(_state_inp(r, 2, 12, temperature=0.8))
    return [np.asarray(x) for x in (ids, logits, *lps, toks, *blps, sampled, one)]


def _family(name):
    from production_stack_tpu.models import jamba, lfm2
    from production_stack_tpu.parallel.mesh import make_mesh

    return {
        "llama": lambda: (CFG, {}),
        "llama-tp2": lambda: (CFG, {"mesh": make_mesh(tp=2, devices=jax.devices()[:2])}),
        "jamba": lambda: (jamba.PRESETS["jamba-debug"], {"state_slots": 4}),
        "lfm2": lambda: (lfm2.PRESETS["lfm2-debug"], {"state_slots": 4}),
    }[name]()


@pytest.mark.parametrize("family", ["llama", "llama-tp2", "jamba", "lfm2"])
def test_a_second_runner_beside_a_filled_store_serves_its_first_dispatches_preloaded(
        tmp_path, family, traced):
    cfg, kw = _family(family)
    first = _beside(tmp_path, cfg, **kw)
    assert first.preloaded.stats() == {
        "step_program_preload_listed": 0, "step_program_preloaded_total": 0,
        "step_program_preload_failed_total": 0, "step_program_preload_served_total": 0,
        "step_program_preload_seconds": 0.0,
        "step_program_preload_pending_at_first_dispatch": None}
    served = _serve_family(first)
    assert first.step_store.writes == 4 and len(_listing(tmp_path)) == 4
    assert first.preloaded.pending_at_first_dispatch == 0 and not first.preloaded._threads
    # the un-preloaded path of a process that finds the blobs: today's "hit"
    tracing.get_flightrecorder().reset()
    plain = _runner(tmp_path, cfg, **kw)
    hit = _serve_family(plain)
    assert [e["store"] for e in _events()] == ["hit"] * 4
    # and a runner that found the listing as it started
    tracing.get_flightrecorder().reset()
    r = _beside(tmp_path, cfg, **kw)
    pre = r.preloaded
    assert (pre.listed, pre.loaded, pre.failed, pre.served) == (4, 4, 0, 0)
    traced.names.clear()
    traced.on = True
    try:
        again = _serve_family(r)
    finally:
        traced.on = False
    for a, b, c in zip(served, hit, again):
        assert a.dtype == b.dtype == c.dtype
        assert np.array_equal(a, b) and np.array_equal(a, c)
    events = _events()
    assert [e["store"] for e in events] == ["preloaded"] * 4
    # nothing was traced, lowered, compiled or read from a cache on the
    # dispatching thread, and the store was not asked
    fd = r.first_dispatch
    assert fd["count"] == 4 and fd["trace"] == fd["lower"] == fd["compile"] == 0.0
    assert all(e["cache"] == "none" and e["compile_s"] == 0 for e in events)
    assert not [n for n in traced.names if n and n.startswith("pstpu_")]
    store = r.step_store
    assert store.hits == store.writes == store.errors == 0
    assert pre.stats() == {
        "step_program_preload_listed": 4, "step_program_preloaded_total": 4,
        "step_program_preload_failed_total": 0, "step_program_preload_served_total": 4,
        "step_program_preload_seconds": pre.stats()["step_program_preload_seconds"],
        "step_program_preload_pending_at_first_dispatch": 0}
    assert 0 < pre.stats()["step_program_preload_seconds"] < 120
    # first-met order, as the first runner met them
    assert [p.name for p in pre._queue] == [
        "pstpu_step_lp", "pstpu_multi_step_k4_lp", "pstpu_multi_step_k4", "pstpu_step"]
    # a dispatched shape runs its executable from then on, and a shape nobody
    # has met is built and listed as before
    r.step(_state_inp(r, 2, 13, temperature=0.8))
    r.step(_state_inp(r, 4, 4))
    assert fd["count"] == 5 and store.writes == 1 and len(_listing(tmp_path)) == 5
    assert _events()[-1]["store"] == "write"


@pytest.mark.parametrize("variant", [
    dict(cfg=dataclasses.replace(CFG, rope_theta=5e5)),
    dict(page_size=16),
    dict(num_pages=32),
    dict(mesh="tp2"),
    dict(digest="another package"),
    dict(processes=2),
], ids=["config-field", "page-size", "pool-size", "mesh", "package-digest", "processes"])
def test_another_identity_preloads_nothing(written, monkeypatch, variant):
    d, served, _ = written
    _runner(d).step(_inp(2, 4))  # a hit lists what the write of an older tree did not
    assert _beside(d).preloaded.listed >= 1  # the control: this identity is listed
    before = _listing(d)
    variant = dict(variant)
    if "digest" in variant:
        monkeypatch.setattr(step_programs, "package_digest",
                            lambda digest=variant.pop("digest"): digest)
    if "processes" in variant:
        monkeypatch.setattr(jax, "process_count", lambda n=variant.pop("processes"): n)
    if "mesh" in variant:
        from production_stack_tpu.parallel.mesh import make_mesh

        variant["mesh"] = make_mesh(tp=2, devices=jax.devices()[:2])
    r = _beside(d, variant.pop("cfg", CFG), **variant)
    pre = r.preloaded
    assert (pre.listed, pre.loaded, pre.failed) == (0, 0, 0) and not pre._threads
    tracing.get_flightrecorder().reset()
    r.step(_inp(2, 4))
    assert [e["store"] for e in _events()] == ["write"] and pre.served == 0
    assert set(before) < set(_listing(d))


def test_a_mesh_over_several_processes_lists_and_preloads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    first, _ = _beside(tmp_path).step(_inp(2, 4))
    assert len(_listing(tmp_path)) == 1  # listed under its identity all the same
    tracing.get_flightrecorder().reset()
    r = _beside(tmp_path)
    assert r.preloaded.listed == 0 and not r.preloaded._threads
    again, _ = r.step(_inp(2, 4))
    assert np.array_equal(np.asarray(first), np.asarray(again))
    assert [e["store"] for e in _events()] == ["hit"]


def test_an_empty_store_and_no_store_preload_nothing(tmp_path, monkeypatch):
    r = _beside(tmp_path)
    assert r.preloaded.listed == 0 and not r.preloaded._threads and not os.listdir(tmp_path)
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    r = ModelRunner(CFG, num_pages=16, page_size=8, seed=0)
    assert r.step_store is None and r.preloaded.store is None
    assert r.preloaded.listed == 0 and not r.preloaded._threads
    r.step(_inp(2, 4))
    assert r.preloaded.stats()["step_program_preload_served_total"] == 0


def test_a_truncated_blob_and_a_stale_entry_are_discarded_counted_and_answered(tmp_path):
    r = _beside(tmp_path)
    first, _ = r.step(_inp(2, 4))
    burst = np.asarray(r.step_multi(_inp(2, 5, k=4), 4))
    r.step(_inp(4, 4))
    stale, truncated, whole = _listed_keys(tmp_path)
    os.unlink(tmp_path / (stale + SUFFIX))
    blob = (tmp_path / (truncated + SUFFIX)).read_bytes()
    (tmp_path / (truncated + SUFFIX)).write_bytes(blob[: len(blob) // 2])
    with open(os.path.join(os.path.dirname(_listing(tmp_path)[0]), "0" * 64 + ".json"), "w") as f:
        f.write('{"program": "pstpu_step", "family": "step"')  # half an entry
    tracing.get_flightrecorder().reset()
    r = _beside(tmp_path)
    pre, store = r.preloaded, r.step_store
    assert (pre.listed, pre.loaded, pre.failed) == (3, 1, 2)
    assert store.errors == 2  # the blob that did not deserialise, the half entry
    assert not (tmp_path / (truncated + SUFFIX)).exists()
    assert _listed_keys(tmp_path) == [whole]
    again, _ = r.step(_inp(2, 4))
    assert np.array_equal(np.asarray(first), np.asarray(again))
    assert np.array_equal(burst, np.asarray(r.step_multi(_inp(2, 5, k=4), 4)))
    r.step(_inp(4, 4))
    assert [e["store"] for e in _events()] == ["write", "write", "preloaded"]
    assert (pre.served, pre.failed, store.writes) == (1, 2, 2)
    # what served is listed again, with its blob
    assert len(_listing(tmp_path)) == len(_files(tmp_path)) == 3
    r = _beside(tmp_path)
    assert (r.preloaded.listed, r.preloaded.loaded, r.preloaded.failed) == (3, 3, 0)


def test_an_entry_the_runner_does_not_build_and_a_program_refused_at_its_call(tmp_path):
    r = _beside(tmp_path)
    first, _ = r.step(_inp(2, 4))
    r.step(_inp(4, 4))
    small, large = _listed_keys(tmp_path)
    # the 4-row program under the 2-row program's key: it builds, and its call
    # refuses the 2-row batch before anything runs
    shutil.copyfile(tmp_path / (large + SUFFIX), tmp_path / (small + SUFFIX))
    for sig, name in (('["x", 3]', "pstpu_step"), ("[false, false]", "pstpu_other"),
                      ("[1, 2, 3, 4]", "pstpu_step")):
        other = os.path.join(os.path.dirname(_listing(tmp_path)[0]), f"{len(sig):064d}.json")
        with open(other, "w") as f:
            f.write('{"program": "%s", "family": "step", "sig": %s, "order": 9}' % (name, sig))
    tracing.get_flightrecorder().reset()
    r = _beside(tmp_path)
    pre, store = r.preloaded, r.step_store
    assert (pre.listed, pre.loaded, pre.failed) == (5, 2, 3)
    again, _ = r.step(_inp(2, 4))
    assert np.array_equal(np.asarray(first), np.asarray(again))
    # refused at the call and counted; then the blob itself is judged as
    # before: hit, its call does not lower, deleted, exported again
    assert (pre.served, pre.failed, store.hits, store.writes, store.errors) == (0, 4, 1, 1, 1)
    assert [e["store"] for e in _events()] == ["error"]
    assert r.first_dispatch["count"] == 1 and not store.bypassed
    r.step(_inp(4, 4))
    assert pre.served == 1 and [e["store"] for e in _events()] == ["error", "preloaded"]
    assert sorted(_listed_keys(tmp_path)) == sorted([small, large])


def test_a_dispatch_that_arrives_mid_load_waits_and_nothing_is_built_twice(tmp_path, monkeypatch):
    r = _beside(tmp_path)
    want = [np.asarray(r.step(_inp(B, 4))[0]) for B in (1, 2, 4)]
    monkeypatch.setattr(step_programs.Preloader, "WORKERS", 1)
    build, built = step_programs.Preloader._build, []
    entered = [threading.Event() for _ in want]
    gates = [threading.Event() for _ in want]

    def slow(self, listed):
        built.append(listed.key)
        entered[len(built) - 1].set()
        assert gates[len(built) - 1].wait(60)
        return build(self, listed)

    monkeypatch.setattr(step_programs.Preloader, "_build", slow)
    tracing.get_flightrecorder().reset()
    r = _beside(tmp_path, wait=False)
    pre = r.preloaded
    assert entered[0].wait(60)
    assert [p.state for p in pre._queue] == ["loading", "queued", "queued"]
    # the first listed program is being built: its dispatch waits for that build
    threading.Timer(0.5, gates[0].set).start()
    t0 = time.perf_counter()
    got = [np.asarray(r.step(_inp(1, 4))[0])]
    assert time.perf_counter() - t0 > 0.4 and pre.pending_at_first_dispatch == 3
    # the worker stands in the second; the third is still queued: its dispatch
    # builds it itself ("hit", as a process that preloaded nothing would), and
    # no worker will
    assert entered[1].wait(60)
    got.append(np.asarray(r.step(_inp(4, 4))[0]))
    assert [p.state for p in pre._queue] == ["taken", "loading", "taken"]
    gates[1].set()
    got.insert(1, np.asarray(r.step(_inp(2, 4))[0]))
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    assert pre.wait(60) and built == [p.key for p in pre._queue[:2]]
    assert [e["store"] for e in _events()] == ["preloaded", "hit", "preloaded"]
    assert (pre.loaded, pre.served, pre.failed) == (2, 2, 0)
    assert (r.step_store.hits, r.step_store.writes) == (1, 0)
    assert _events()[0]["run_s"] > 0.4 and _events()[0]["compile_s"] == 0


def test_many_dispatches_race_the_workers_and_each_program_is_built_once(tmp_path, monkeypatch):
    shapes = [(B, ctx) for B in (1, 2, 4, 8) for ctx in (4, 12)]

    def serve(r):
        return [np.asarray(r.step(_inp(B, ctx))[0]) for B, ctx in shapes]

    serve(_beside(tmp_path))
    want = serve(_runner(tmp_path))
    build, built = step_programs.Preloader._build, []

    def counted(self, listed):
        built.append(listed.key)
        return build(self, listed)

    monkeypatch.setattr(step_programs.Preloader, "_build", counted)
    monkeypatch.setattr(step_programs.Preloader, "WORKERS", 16)  # more than the programs
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        r = _beside(tmp_path, wait=False)
        got = serve(r)
        assert r.preloaded.wait(120)
    finally:
        sys.setswitchinterval(interval)
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    pre, store = r.preloaded, r.step_store
    # 4 programs (the context does not name a shape): each was built by a
    # worker or by its dispatch, never by both, and each dispatch was served
    assert pre.listed == 4 and len(built) == len(set(built)) == pre.loaded
    assert pre.loaded + store.hits == 4 and pre.served == pre.loaded and pre.failed == 0
    assert r.first_dispatch["count"] == 4 and store.writes == 0
