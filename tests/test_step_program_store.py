"""The store of exported step programs (ISSUE 35, engine/step_programs.py):
a first dispatch that finds its program neither traces nor lowers the step
function, serves bit for bit what the write served and what the plain jit
serves, and nothing stale or broken is ever served in silence."""

import dataclasses
import os
import shutil
import sys
import threading

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
