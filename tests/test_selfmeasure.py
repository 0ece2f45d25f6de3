"""The engine measures itself (ISSUE 25): first dispatches by shape and phase,
named step programs, the engine loop's sections as one helper and as spans in
the profiler's trace, and work per dispatch counted from the scheduler's batch."""

import asyncio
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from production_stack_tpu import tracing
from production_stack_tpu.engine import devicemon
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine, _kv_tokens_read
from production_stack_tpu.engine.runner import ModelRunner, StepInput
from production_stack_tpu.engine.scheduler import SamplingParams
from production_stack_tpu.models import llama
from production_stack_tpu.tracing import profiler

CFG = llama.PRESETS["llama-debug"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = llama.PRESETS["mistral-debug"].sliding_window  # 8


def _decode_input(B, ctx, ctx_pages, k=None):
    return StepInput(
        input_ids=np.ones((B, 1), np.int32),
        positions=np.full((B, 1), ctx, np.int32),
        page_table=np.arange(B * ctx_pages, dtype=np.int32).reshape(B, ctx_pages),
        kv_lens=np.full((B,), ctx + 1, np.int32),
        temperature=np.zeros(B, np.float32), top_k=np.zeros(B, np.int32),
        top_p=np.ones(B, np.float32),
        kv_limits=None if k is None else np.full((B,), ctx + k, np.int32),
    )


def _first_dispatch_events():
    return [e["data"] for e in tracing.get_flightrecorder().events(kind="compile")
            if e["data"].get("event") == "first_dispatch"]


def test_first_dispatch_is_counted_once_a_shape_and_split_by_phase():
    tracing.get_flightrecorder().reset()
    r = ModelRunner(CFG, num_pages=16, page_size=8, seed=0)
    fd = r.first_dispatch
    assert fd["count"] == 0
    r.step(_decode_input(2, 8, 2))
    assert fd["count"] == 1
    r.step(_decode_input(2, 9, 2))  # the same shape again: nothing
    assert fd["count"] == 1
    r.step(_decode_input(4, 8, 2))  # another batch bucket
    r.step_multi(_decode_input(2, 8, 2, k=4), 4)  # another family
    assert fd["count"] == 3
    phases = sum(fd[p] for p in ("trace", "lower", "compile", "run"))
    assert phases == pytest.approx(fd["seconds"], rel=1e-6) and fd["seconds"] > 0
    # JAX reported the phases on this thread (a warm persistent cache still
    # traces and lowers; the backend-compile event covers a cache load)
    assert fd["trace"] > 0 and fd["lower"] > 0 and fd["compile"] > 0
    events = _first_dispatch_events()
    assert [(e["family"], e["ids_shape"], e["pages_shape"]) for e in events] == [
        ("step", [2, 1], [2, 2]), ("step", [4, 1], [4, 2]), ("multi_step", [2, 1], [2, 2])]
    for e in events:
        assert e["seconds"] == pytest.approx(
            e["trace_s"] + e["lower_s"] + e["compile_s"] + e["run_s"], abs=1e-3)
        assert e["cache"] in ("hit", "miss", "uncached")
        # the store beside the test session's compile cache took part in each
        assert e["store"] in ("hit", "write")
    store = r.step_store
    assert store.hits + store.writes == fd["count"] and store.errors == 0
    assert [e["store"] for e in events].count("hit") == store.hits
    assert events[2]["sig"] == "(4, False, False)"
    # the marker this event replaces is gone
    assert not hasattr(r, "_note_program_variant")
    assert not [e for e in tracing.get_flightrecorder().events(kind="compile")
                if e["data"].get("event") == "program_variant"]


def test_a_first_dispatch_runs_from_a_frame_larger_than_a_data_stack_chunk():
    """CPython frees a 16 KiB chunk of a thread's frame stack the moment it
    empties; calls that straddle a boundary pay two system calls each. The
    first dispatch (JAX's deep trace) runs below one frame that owns a chunk
    of its own."""
    import sys

    from production_stack_tpu.engine.runner import _roomy

    assert _roomy.__code__.co_stacksize * 8 >= 32 * 16 * 1024
    assert _roomy(lambda a, b: (a, b), 1, 2) == (1, 2)
    with pytest.raises(KeyError):
        _roomy({}.__getitem__, "x")
    # the frames called from it lie in its chunk: no depth costs a call more
    # than a few times the median (a straddled boundary costs ~100 x)
    def leaf(x):
        return x

    def hot():
        t = time.perf_counter()
        for _ in range(2000):
            leaf(1)
        return time.perf_counter() - t

    def deep(n):
        return hot() if n == 0 else deep(n - 1)

    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(limit, 1000))
    try:
        costs = sorted(min(_roomy(deep, d) for _ in range(3)) for d in range(300))
    finally:
        sys.setrecursionlimit(limit)
    assert costs[-1] < 20 * costs[len(costs) // 2]


def test_a_trace_event_of_an_inner_jit_is_not_counted_twice():
    devicemon.install_compile_listener()
    with devicemon.capture_first_dispatch() as phases:
        for name, secs in (("jaxpr_trace_duration", 0.2), ("jaxpr_trace_duration", 0.3),
                           ("jaxpr_trace_duration", 1.0), ("jaxpr_to_mlir_module_duration", 0.5),
                           ("backend_compile_duration", 2.0)):
            devicemon._on_event_duration("/jax/core/compile/" + name, secs)
        devicemon._on_event("/jax/compilation_cache/cache_hits")
    assert phases == {"trace": 1.0, "lower": 0.5, "compile": 2.0, "cache_hits": 1, "cache_misses": 0}
    before = dict(phases)
    devicemon._on_event_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)  # capture closed
    assert phases == before


PROGRAM_NAMES = """
import json, numpy as np
from production_stack_tpu.engine.runner import ModelRunner, StepInput
from production_stack_tpu.models import llama
r = ModelRunner(llama.PRESETS["llama-debug"], num_pages=8, page_size=8, seed=0)
r._get_step(False, False); r._get_step(True, True)
print(json.dumps(sorted(f.__name__ for f in r._steps.values())))
"""


def test_step_programs_carry_names_that_are_equal_in_two_processes():
    r = ModelRunner(CFG, num_pages=8, page_size=8, seed=0)
    r._get_step(False, False)
    r._get_step(True, True)
    r.step_multi(_decode_input(2, 8, 2, k=4), 4)
    here = sorted(f.__name__ for f in r._steps.values())
    assert here == ["pstpu_step", "pstpu_step_lp_pen"]
    assert [f.__name__ for f in r._multi_steps.values()] == ["pstpu_multi_step_k4"]
    outs = []
    for hashseed in ("1", "2"):  # no id, no hash, no fingerprint in a name
        env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", PROGRAM_NAMES], env=env, cwd=ROOT,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1] == here


def test_kv_tokens_read_is_the_sum_of_windowed_contexts():
    def by_hand(kv_len, steps, window):
        return sum(min(c, window) if window else c
                   for L, n in zip(kv_len, steps) for c in range(L, L + max(n, 0)))
    kv_len, steps = np.array([1, 5, 8, 9, 40, 7]), np.array([8, 8, 3, 1, 16, -2])
    for window in (None, 8, 4096, 1):
        assert _kv_tokens_read(kv_len, steps, window) == by_hand(kv_len, steps, window)
    assert _kv_tokens_read(np.array([10]), 4, 12) == 10 + 11 + 12 + 12


@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(EngineConfig(
        model="mistral-debug", max_model_len=256, max_num_seqs=8, num_pages=64,
        page_size=8, prefill_chunk=32, kv_cache_memory_gb=0.01))
    eng.start()
    yield eng
    eng.stop()


def _generate(engine, prompt, n):
    async def run():
        last = None
        async for out in engine.generate(
            f"t-{np.random.randint(1 << 30)}", prompt=prompt,
            params=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True),
        ):
            last = out
        return last
    return asyncio.run(run())


def test_work_per_dispatch_equals_a_hand_count_with_a_window_and_a_prefix_hit(engine):
    s0 = engine.stats()
    prompt = "the engine counts what it dispatches, from the batch itself"
    first = _generate(engine, prompt, 20)
    again = _generate(engine, prompt, 20)
    assert again.cached_tokens > 0 and first.cached_tokens == 0
    s1 = engine.stats()
    # prefill: every prompt token but the cached ones ran through prefill
    computed = 2 * first.prompt_tokens - again.cached_tokens
    assert s1["prompt_tokens_total"] - s0["prompt_tokens_total"] == computed
    # decode: the first output token comes from the prefill; each of the 19
    # others attended min(context, window) KV tokens, and every context here
    # (prompt + outputs so far) is past the window of 8
    assert first.prompt_tokens > WINDOW
    by_hand = 2 * sum(min(first.prompt_tokens + i, WINDOW) for i in range(1, 20))
    assert s1["decode_kv_tokens_read_total"] - s0["decode_kv_tokens_read_total"] == by_hand
    steps = [e["data"] for e in tracing.get_flightrecorder().events(kind="step")]
    assert sum(e.get("kv_tokens_read", 0) for e in steps) >= by_hand
    assert sum(e.get("prefill_tokens", 0) for e in steps) >= computed


def test_loop_sections_are_disjoint_sum_to_the_wall_and_keep_their_keys(engine):
    def snap():
        return time.perf_counter(), dict(engine.loop_seconds)
    (t0, a) = snap()
    queued = sum(engine.stats()["queued_ahead_dispatches_total"].values())
    _generate(engine, "sections of the loop, summed", 24)  # warm: every shape met
    _generate(engine, "sections of the loop, summed", 24)
    # the second pass ran with a dispatch queued behind the one that ran
    assert sum(engine.stats()["queued_ahead_dispatches_total"].values()) > queued
    time.sleep(0.3)  # an idle stretch: the loop waits on its inbox
    (t1, b) = snap()
    delta = {k: b[k] - a[k] for k in b}
    top = sum(delta[k] for k in ("wait", "schedule", "step", "apply", "emit"))
    # the loop blocks on its inbox at either edge of the interval, so up to
    # one wait (0.5 s timeout) straddles it; nothing else is unaccounted
    assert top == pytest.approx(t1 - t0, abs=0.6)
    assert delta["step"] > 0 and delta["apply"] > 0 and delta["emit"] > 0
    # the parts of a dispatch lie inside `step`
    for part in ("stage", "call", "fetch", "hold", "chain_dispatch", "chain_fetch", "runahead"):
        assert 0 <= delta[part] <= delta["step"] + 1e-9
    assert delta["call"] >= delta["stage"] > 0 and delta["fetch"] > 0
    stats = engine.stats()
    # /stats keeps every engine_loop_* key it had, and no more: the two parts
    # of step that are new stand under a prefix of their own
    assert {k for k in stats if k.startswith("engine_loop_")} == {
        f"engine_loop_{k}_seconds_total" for k in (
            "wait", "schedule", "step", "apply", "emit", "chain_dispatch", "chain_fetch")}
    assert {k for k in stats if k.startswith("engine_dispatch_")} == {
        "engine_dispatch_stage_seconds_total", "engine_dispatch_runahead_seconds_total",
        "engine_dispatch_fetch_seconds_total", "engine_dispatch_hold_seconds_total",
        "engine_dispatch_call_seconds_total"}


def test_apply_and_emit_inside_a_dispatch_are_taken_off_it(engine):
    secs = {k: 0.0 for k in engine.loop_seconds}
    from production_stack_tpu.engine.engine import _LoopSection

    def slept(seconds):  # what the sleep took on a loaded machine, not what was asked
        t = time.perf_counter()
        time.sleep(seconds)
        return time.perf_counter() - t

    with _LoopSection(secs, "step", {}) as step:
        own = slept(0.02)
        with _LoopSection(secs, "chain_fetch", {}):
            with _LoopSection(secs, "apply", {}):
                applied = slept(0.03)
            with _LoopSection(secs, "emit", {}):
                emitted = slept(0.01)
    assert secs["apply"] == pytest.approx(applied, abs=0.005)
    assert secs["step"] == pytest.approx(own, abs=0.005) and step.seconds == secs["step"]
    assert secs["chain_fetch"] < 0.005
    assert secs["step"] + secs["apply"] + secs["emit"] == pytest.approx(own + applied + emitted, abs=0.01)


def test_under_a_profile_the_trace_holds_the_loop_spans_and_the_program_names(engine, tmp_path):
    from jax.profiler import ProfileData

    assert not profiler.active()
    profiler.start(str(tmp_path))
    with pytest.raises(RuntimeError, match="already running"):
        profiler.start(str(tmp_path))
    try:
        assert profiler.active()
        _generate(engine, "a new prompt whose length meets a new shape " * 3, 12)
    finally:
        done = profiler.stop()
    assert not profiler.active() and done["stop_s"] >= 0 and done["path"] == str(tmp_path)
    with pytest.raises(RuntimeError, match="no profile"):
        profiler.stop()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names, by = set(), {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name.startswith("pstpu.loop.") and dict(ev.stats):
                    by.setdefault(ev.name[len("pstpu.loop."):], []).append(dict(ev.stats))
    for section in ("wait", "schedule", "step", "stage", "call", "fetch", "apply", "emit"):
        assert "pstpu.loop." + section in names
    # a turn that enqueues a dispatch says which, and why nothing was queued ahead of it
    steps = {a["seq"]: a for a in by["step"]}
    assert len(steps) == len(by["step"]) >= 3
    for a in steps.values():
        assert {"kind", "family", "rows", "chunk", "pages", "bursts", "seq"} <= set(a) and "step" not in a
        # a dispatch that was not queued ahead says what had emptied the loop
        assert a.get("drain", "idle") in engine.queue_ahead_drains
    # the dispatch after the loop waited for work drained as `idle` or met a new shape
    assert {a.get("drain") for a in steps.values()} & {"idle", "first_dispatch"}
    # `call` hands over and `fetch` waits for the dispatches the steps enqueued
    assert {a["seq"] for a in by["call"]} == set(steps)
    assert {a["seq"] for a in by["fetch"]} <= set(steps) and by["fetch"]
    assert all(set(a) == {"seq"} for a in by["call"] + by["fetch"] + by.get("hold", []))
    # the program's name, as JAX's own dispatch span shows it on the host plane
    # (on the TPU the device plane's module line reads jit_pstpu_step(...))
    assert any("pstpu_step" in n or "pstpu_multi_step" in n for n in names)
    with open(path, "rb") as f:
        assert b"jit_pstpu_" in f.read()


def test_with_no_profile_the_loop_builds_no_span_attributes(engine, monkeypatch):
    """Off = what it cost before the spans said which dispatch: the shared
    no-op span, the shared empty dictionary, and `_dispatch_attrs` never called."""
    from production_stack_tpu.engine import engine as engine_mod

    assert not profiler.active()
    monkeypatch.setattr(engine, "_dispatch_attrs", lambda batch: pytest.fail("built while off"))
    monkeypatch.setattr(engine_mod._LoopSection, "annotate",
                        lambda self, **attrs: pytest.fail("annotated while off"))
    before = engine.step_idx
    out = _generate(engine, "no profile runs, so no span says anything", 12)
    assert out.finish_reason == "length" and engine.step_idx > before
    assert engine._seq_attr(7) is engine_mod._NO_ATTRS and engine_mod._NO_ATTRS == {}
    assert engine._section("call", **engine._seq_attr(7))._span is profiler._NO_SPAN
    assert profiler._NO_SPAN.set_metadata(seq=1) is None


@pytest.mark.parametrize("what", ["hits", "writes", "errors"])
def test_the_store_counters_stand_in_stats_and_in_metrics(engine, what):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    _generate(engine, "a first dispatch or two", 4)
    name = f"step_program_store_{what}_total"
    stats = engine.stats()
    store = engine.runner.step_store
    assert stats[name] == getattr(store, what)
    assert stats["step_program_store_dir"] == store.root
    assert stats["step_program_store_bypassed"] == {}
    assert (stats["step_program_store_hits_total"] + stats["step_program_store_writes_total"]
            == stats["first_dispatches_total"] > 0)

    async def scrape():
        cfg = EngineConfig(model="mistral-debug")
        async with TestClient(TestServer(EngineServer(cfg, engine).build_app())) as client:
            return await (await client.get("/metrics")).text()

    text = asyncio.run(scrape())
    assert f"# TYPE vllm:{name} counter" in text
    assert f'vllm:{name}{{model_name="mistral-debug"}} {stats[name]}' in text


@pytest.fixture(scope="module")
def preloaded(tmp_path_factory):
    """(``/stats``, ``/metrics``) of an engine that started beside a store a
    first engine of its identity had filled, after the same request."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer
    from production_stack_tpu.engine.step_programs import StepProgramStore

    store_dir = str(tmp_path_factory.mktemp("store"))
    cfg = EngineConfig(
        model="mistral-debug", max_model_len=256, max_num_seqs=8, num_pages=64,
        page_size=8, prefill_chunk=32, kv_cache_memory_gb=0.01)
    for _ in range(2):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(StepProgramStore, "beside_compile_cache",
                       classmethod(lambda cls: StepProgramStore(store_dir)))
            eng = LLMEngine(cfg)
        assert eng.runner.preloaded.wait(120)
        eng.start()
        try:
            assert _generate(eng, "a first dispatch or two", 12).finish_reason == "length"
            stats = eng.stats()

            async def scrape():
                async with TestClient(TestServer(EngineServer(cfg, eng).build_app())) as client:
                    return await (await client.get("/metrics")).text()

            text = asyncio.run(scrape())
        finally:
            eng.stop()
    return stats, text


@pytest.mark.parametrize("name,kind", [
    ("step_program_preload_listed", "gauge"), ("step_program_preloaded_total", "counter"),
    ("step_program_preload_failed_total", "counter"),
    ("step_program_preload_served_total", "counter"),
    ("step_program_preload_seconds", "gauge"),
    ("step_program_preload_pending_at_first_dispatch", "gauge")])
def test_the_loader_counters_stand_in_stats_and_in_metrics(preloaded, name, kind):
    stats, text = preloaded
    firsts = stats["first_dispatches_total"]
    assert firsts >= 2 and stats["first_dispatch_compile_seconds_total"] == 0
    assert stats["step_program_store_hits_total"] == stats["step_program_store_writes_total"] == 0
    want = {"step_program_preload_listed": firsts, "step_program_preloaded_total": firsts,
            "step_program_preload_failed_total": 0, "step_program_preload_served_total": firsts,
            "step_program_preload_seconds": stats["step_program_preload_seconds"],
            "step_program_preload_pending_at_first_dispatch": 0}
    assert stats[name] == want[name] and 0 < stats["step_program_preload_seconds"] < 120
    assert f"# TYPE vllm:{name} {kind}" in text
    assert f'vllm:{name}{{model_name="mistral-debug"}} {stats[name]}' in text


def test_before_a_first_dispatch_the_loader_says_nothing_of_it(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer
    from production_stack_tpu.engine.runner import ModelRunner

    fresh = ModelRunner(llama.PRESETS["mistral-debug"], num_pages=16, page_size=8)
    assert fresh.preloaded.stats()["step_program_preload_pending_at_first_dispatch"] is None

    class NotYet:  # the engine's stats before anything was dispatched
        def __getattr__(self, name):
            return getattr(engine, name)

        def stats(self):
            return dict(engine.stats(), **fresh.preloaded.stats())

    async def scrape():
        cfg = EngineConfig(model="mistral-debug")
        async with TestClient(TestServer(EngineServer(cfg, NotYet()).build_app())) as client:
            return await (await client.get("/metrics")).text()

    text = asyncio.run(scrape())
    assert "vllm:step_program_preload_listed" in text
    assert "step_program_preload_pending_at_first_dispatch" not in text


@pytest.mark.parametrize("name,label", [("queued_ahead_dispatches_total", "kind"),
                                        ("queue_ahead_drains_total", "reason")])
def test_the_queue_ahead_counters_stand_in_stats_and_in_metrics(engine, name, label):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    _generate(engine, "one dispatch queued behind the one that runs", 24)
    _generate(engine, "one dispatch queued behind the one that runs", 24)
    counts = engine.stats()[name]
    assert sum(counts.values()) > 0
    if label == "kind":
        assert set(counts) == {"decode", "prefill"} and counts["decode"] > 0
    else:
        assert {"idle", "late", "first_dispatch", "device_cmd", "host_staged_rows"} <= set(counts)
        assert counts["idle"] > 0  # the dispatch after the loop waited for work

    async def scrape():
        cfg = EngineConfig(model="mistral-debug")
        async with TestClient(TestServer(EngineServer(cfg, engine).build_app())) as client:
            return await (await client.get("/metrics")).text()

    text = asyncio.run(scrape())
    assert f"# TYPE vllm:{name} counter" in text
    for key, n in counts.items():
        assert f'vllm:{name}{{model_name="mistral-debug",{label}="{key}"}} {n}' in text


def _ride(engine):
    """A long answer, and a prompt of its own that arrives once it decodes."""
    async def run():
        decoding = asyncio.Event()

        async def one(prompt, n, wait):
            if wait:
                await decoding.wait()
            async for _ in engine.generate(
                f"t-{np.random.randint(1 << 30)}", prompt=prompt,
                params=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True),
            ):
                decoding.set()

        tag = str(np.random.randint(1 << 30))   # nothing of it is cached
        await asyncio.gather(
            one("a row that decodes while another prompt is prefilled", 200, False),
            one(tag + " the late prompt of two chunks and more" * 2, 8, True))
    asyncio.run(run())


@pytest.mark.parametrize("name", [
    "prefill_dispatches_total", "prefill_rider_dispatches_total",
    "prefill_rider_rows_total", "prefill_riderless_dispatches_total"])
def test_the_rider_counters_stand_in_stats_and_in_metrics(engine, name):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    assert engine.stats()["rider_refusal"] == ""   # a llama runner on one device
    s0 = engine.stats()
    _ride(engine)
    s1 = engine.stats()
    if name == "prefill_riderless_dispatches_total":
        assert set(s1[name]) == {"cannot_ride", "over_width", "no_page"}
        assert s1[name] == s0[name]   # every dispatch with decode demand carried its rows
    else:
        assert s1[name] > s0[name]
    # the rows that rode made a token each, and their reads are decode work
    rows = s1["prefill_rider_rows_total"] - s0["prefill_rider_rows_total"]
    assert rows >= s1["prefill_rider_dispatches_total"] - s0["prefill_rider_dispatches_total"] > 0
    steps = [e["data"] for e in tracing.get_flightrecorder().events(kind="step")]
    assert sum(e.get("rider_rows", 0) for e in steps) >= rows
    assert all(e["kv_tokens_read"] == WINDOW * e["rider_rows"]
               for e in steps if e.get("rider_rows"))   # contexts past the window of 8

    async def scrape():
        cfg = EngineConfig(model="mistral-debug")
        async with TestClient(TestServer(EngineServer(cfg, engine).build_app())) as client:
            return await (await client.get("/metrics")).text()

    text, value = asyncio.run(scrape()), engine.stats()[name]
    assert f"# TYPE vllm:{name} counter" in text
    if isinstance(value, dict):
        for why, n in value.items():
            assert f'vllm:{name}{{model_name="mistral-debug",reason="{why}"}} {n}' in text
    else:
        assert f'vllm:{name}{{model_name="mistral-debug"}} {value}' in text


def test_profile_endpoints_ride_the_debug_gate(engine, tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.api_server import EngineServer

    async def drive(debug):
        cfg = EngineConfig(model="mistral-debug", enable_debug_endpoints=debug)
        async with TestClient(TestServer(EngineServer(cfg, engine).build_app())) as client:
            r = await client.post("/v1/debug/profile/start", json={"dir": str(tmp_path)})
            if not debug:
                return r.status, None
            assert r.status == 200 and profiler.active()
            assert (await client.post("/v1/debug/profile/start", json={"dir": "x"})).status == 409
            r = await client.post("/v1/debug/profile/stop")
            assert (await client.post("/v1/debug/profile/stop")).status == 409
            assert (await client.post("/v1/debug/profile/start", json={})).status == 400
            return r.status, await r.json()

    assert asyncio.run(drive(False))[0] in (404, 405)  # not registered without the flag
    status, done = asyncio.run(drive(True))
    assert status == 200 and done["stop_s"] >= 0 and done["path"] == str(tmp_path)
    assert not profiler.active() and glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
