#!/usr/bin/env python3
"""One cell, once:  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the engine child (`engine_main.py`, which holds the chip) and the
router child, sends every request to the ROUTER, warms up, measures for
`--seconds`, checks correctness, prints ONE JSON line last, and exits. This
parent never imports JAX (asserted at exit): a parent that touched JAX would
hold the chip its child needs.

It exits non-zero and prints no result when the engine does not report
`platform == "tpu"` with at least the chips the cell asks for, and when the
program is not beside it. `--trace 0` prints the cell's end-to-end metrics
(profiler off); `--trace 1` prints its per-layer metrics from a traced run of
the same traffic. Earlier lines (stderr) carry the set-up split, the request
counts, generator lateness and sample counts.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import aiohttp  # noqa: E402  (not JAX: the parent may use it)

import manifest  # noqa: E402
import stats as pstats  # noqa: E402
from readers_common import prom  # noqa: E402

REQUEST_TIMEOUT_S = 120.0
SETUP_TIMEOUT_S = 1000.0  # a set-up phase on a cell's first run compiles every shape it meets
TRACE_SECONDS = 3.0
STALL_S = 1.0  # the load generator may run this late before a window is void
MAX_WINDOWS = 3  # a void window is measured again, twice at the most
RUN_LIMIT_S, RUN_MARGIN_S = 360.0, 30.0  # the driver cuts a run at the first; a further window has to end this far under it
TAIL_S = 40.0  # what follows a window: its requests awaited, the checks, the readers, the children stopped (14-39 s warm)


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def note(msg: str) -> None:
    print(f"[perfbench +{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


# -- children -------------------------------------------------------------------

class Children:
    def __init__(self, out_dir: str, env: dict):
        self.out_dir, self.env, self.procs = out_dir, env, []

    def spawn(self, argv: list[str], log_name: str) -> subprocess.Popen:
        with open(os.path.join(self.out_dir, log_name), "w") as log:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=ROOT, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        self.procs.append(proc)
        return proc

    def stop_all(self, grace: float = 20.0) -> None:
        """SIGTERM, then the whole group is killed; returns once every child
        is waited on."""
        for proc in reversed(self.procs):
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + grace
        for proc in self.procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait()
        self.procs.clear()

    def tail(self, log_name: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.out_dir, log_name), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout: the path is part of the cache key
        env["PSTPU_COMPILE_CACHE_DIR"] = os.path.join(HERE, ".jax_cache")
    env.pop("PSTPU_FLIGHTRECORDER_DIR", None)
    return env


# -- HTTP -----------------------------------------------------------------------

async def get_text(session, url: str, timeout: float = 10.0) -> tuple[int, str]:
    async with session.get(url, timeout=aiohttp.ClientTimeout(total=timeout)) as r:
        return r.status, await r.text()


async def get_json(session, url: str, timeout: float = 10.0):
    status, body = await get_text(session, url, timeout)
    if status != 200:
        raise BenchFailure(f"GET {url}: HTTP {status} {body[:200]}")
    return json.loads(body)


async def post_json(session, url: str, body: dict, timeout: float = 300.0):
    async with session.post(url, json=body, timeout=aiohttp.ClientTimeout(total=timeout)) as r:
        text = await r.text()
        if r.status != 200:
            raise BenchFailure(f"POST {url}: HTTP {r.status} {text[:300]}")
        return json.loads(text)


async def wait_healthy(session, url, proc, children, log_name, timeout) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise BenchFailure(f"{log_name}: exited rc={proc.returncode} before {url} "
                               f"answered\n{children.tail(log_name)}")
        try:
            status, _ = await get_text(session, url, 3.0)
            if status == 200:
                return
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            pass
        await asyncio.sleep(0.25)
    raise BenchFailure(f"{url} not healthy after {timeout:.0f}s\n{children.tail(log_name)}")


# -- the load generator ---------------------------------------------------------

class Load:
    """Sends requests to the router and keeps the request log. One thread."""

    def __init__(self, session, base: str, model: str):
        self.session, self.base, self.model = session, base, model
        self.log: list[dict] = []
        self.tasks: set = set()
        self.waiting: set = set()  # open arrivals that are not due yet

    async def fire(self, req: dict, due: float, *, greedy=False, logprobs=0,
                   timeout=REQUEST_TIMEOUT_S) -> dict:
        body = {
            "model": self.model, "messages": req["messages"], "stream": True,
            "max_tokens": req["max_tokens"], "ignore_eos": True,
            "temperature": 0.0 if greedy else 0.7,
            "stream_options": {"include_usage": True},
        }
        if logprobs:
            body.update(logprobs=True, top_logprobs=logprobs)
        rec = {
            "due": due, "sent": time.monotonic(), "first": None, "last": None,
            "chunks": [], "ok": False, "status": 0, "finish": None, "error": None,
            "prompt_tokens": None, "cached_tokens": 0, "output_tokens": 0,
            "want_tokens": req["max_tokens"], "want_prompt_tokens": req.get("prompt_tokens"),
            "stream": req.get("stream"), "measured": False, "logprobs": [],
        }
        self.log.append(rec)
        try:
            async with self.session.post(
                self.base + "/v1/chat/completions", json=body,
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as r:
                rec["status"] = r.status
                if r.status != 200:
                    rec["error"] = (await r.text())[:300]
                    return rec
                async for raw in r.content:
                    if not raw.startswith(b"data:") or raw.startswith(b"data: [DONE]"):
                        continue
                    now = time.monotonic()
                    doc = json.loads(raw[5:])
                    if doc.get("usage"):
                        u = doc["usage"]
                        rec["prompt_tokens"] = u.get("prompt_tokens")
                        rec["output_tokens"] = u.get("completion_tokens") or 0
                        rec["cached_tokens"] = (
                            (u.get("prompt_tokens_details") or {}).get("cached_tokens") or 0)
                    for choice in doc.get("choices") or []:
                        delta = choice.get("delta") or {}
                        if choice.get("finish_reason"):
                            rec["finish"] = choice["finish_reason"]
                        lp = (choice.get("logprobs") or {}).get("content") or []
                        rec["logprobs"].extend(lp)
                        if "role" in delta and not lp and not delta.get("content"):
                            continue  # the role chunk carries no token
                        if not rec["chunks"] or rec["chunks"][-1] != now:
                            rec["chunks"].append(now)
            if rec["chunks"]:
                rec["first"], rec["last"] = rec["chunks"][0], rec["chunks"][-1]
            rec["ok"] = (
                rec["finish"] == "length" and rec["output_tokens"] == rec["want_tokens"]
                and rec["first"] is not None
                and rec["want_prompt_tokens"] in (None, rec["prompt_tokens"])
            )
            if not rec["ok"]:
                rec["error"] = (f"finish={rec['finish']} output={rec['output_tokens']}/"
                                f"{rec['want_tokens']} prompt={rec['prompt_tokens']}/"
                                f"{rec['want_prompt_tokens']}")
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    def spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self.tasks.add(task)
        task.add_done_callback(self.tasks.discard)
        return task

    async def phase(self, phase: dict) -> list[dict]:
        """A set-up phase: its requests sent together, or each when due; all
        awaited."""
        now = time.monotonic()
        return list(await asyncio.gather(*(
            [self.fire(r, now, timeout=SETUP_TIMEOUT_S) for r in phase.get("requests", [])]
            + [self.open_arrival(r, now, timeout=SETUP_TIMEOUT_S) for r in phase.get("open", [])])))

    async def open_arrival(self, req: dict, t0: float, timeout=REQUEST_TIMEOUT_S) -> dict:
        due = t0 + req["due_s"]
        me = asyncio.current_task()
        self.waiting.add(me)
        try:
            await asyncio.sleep(max(0.0, due - time.monotonic()))
        finally:
            self.waiting.discard(me)
        return await self.fire(req, due, timeout=timeout)

    async def client(self, c: dict, first_due: float, stop: asyncio.Event) -> None:
        """A closed-loop client: its next turn is due think_s after its last
        reply ended; it runs until told to stop."""
        due, last = first_due, None
        for req in c["turns"]:
            if last is not None:
                due = last + req.get("think_s", c["think_s"])
            delay = due - time.monotonic()
            if delay > 0:
                try:
                    await asyncio.wait_for(stop.wait(), delay)
                except asyncio.TimeoutError:
                    pass
            if stop.is_set():
                return
            rec = await self.fire(req, due)
            last = rec["last"] or time.monotonic()
        raise BenchFailure(f"a closed-loop client ran out of its {len(c['turns'])} generated "
                           "turns: lower min_turn_s")

    async def heartbeat(self, w: dict, void_from: float, void: asyncio.Event,
                        every: float = 0.05) -> None:
        """The load generator's own pulse beside window `w`: how late each
        50 ms sleep returned, and beside each stall what the parent can read of
        who had the cores (`host_sample`). The engine and the router are other
        processes on the same host: a frozen host, or a system that takes the
        cores this process needs, makes it late, and then the window measured
        the host: past STALL_S it is void from that beat on, not from t1."""
        before = host_sample()
        while True:
            t = time.monotonic()
            await asyncio.sleep(every)
            over = time.monotonic() - t - every
            after = host_sample()
            if over > 0.05:
                w["stalls"].append((t, over, stall_cause(over + every, before, after)))
            if over > STALL_S and void_from <= t < w["t1"] and not void.is_set():
                w["void"] = ran_late(over)
                void.set()
            before = after


SCHEDSTAT = "/proc/self/schedstat"  # the main thread's: run ns, run-queue wait ns, slices


def host_sample() -> dict:
    """This process's CPU seconds and, where the host shows it, the seconds
    its thread waited on a run queue. (The sealed machine that holds the chip
    does not: its `/proc` has no schedstat and no pressure, and its load
    average reads 0.00 under any load, so neither is read here.)"""
    out = {"cpu_s": time.process_time(), "waited_s": None}
    if os.path.exists(SCHEDSTAT):
        with open(SCHEDSTAT) as f:
            out["waited_s"] = int(f.read().split()[1]) / 1e9
    return out


def ran_late(late_s: float) -> str:
    return (f"the load generator ran {late_s * 1000:.0f} ms late in the window (limit "
            f"{STALL_S * 1000:.0f}): the host was frozen or starved")


def stall_cause(span_s: float, before: dict, after: dict) -> dict:
    """What a stalled beat of `span_s` seconds was: `busy` where the parent's
    own CPU time advanced by half of it or more (its one thread was at work),
    `starved` where it stood as long on a run queue (it had no core), `stopped`
    where neither moved (the process or the whole host stood still), and
    `starved_or_stopped` where the host does not show the run queue."""
    cpu_s = after["cpu_s"] - before["cpu_s"]
    waited_s = None if after["waited_s"] is None else after["waited_s"] - before["waited_s"]
    if cpu_s >= 0.5 * span_s:
        kind = "busy"
    elif waited_s is None:
        kind = "starved_or_stopped"
    else:
        kind = "starved" if waited_s >= 0.5 * span_s else "stopped"
    return {"kind": kind, "cpu_s": cpu_s, "waited_s": waited_s}


def streams_beside(log: list, t: float, over: float, look: float = 0.25) -> str:
    """Whether the engine went on while the load generator stood still, told
    from what the generator read once it woke: the chunks of `over` seconds lay
    in its sockets and come within `look` seconds, or only `look` seconds' worth
    come, and the engine (or the whole host) stood too."""
    end = t + 0.05 + over
    times = [c for r in log for c in r["chunks"] if t - 2.0 <= c < end + look]
    before = sum(1 for c in times if c < t) / 2.0  # chunks a second, the 2 s before the stall
    after = sum(1 for c in times if c >= end)
    if before * look < 5:
        return f"{after} chunks in the {look} s after it, too few streams to tell"
    went_on = after >= 0.5 * before * (over + look)
    return (f"{after} chunks in the {look} s after it against {before * look:.0f} in such a span "
            f"before: the engine {'went on' if went_on else 'stood too'}")


def stall_table(stalls: list, t0: float, log: list) -> str:
    """One line a stall: when (s into the window), late by, and what it was."""
    def ms(v):
        return "-" if v is None else f"{v * 1000:.0f}"
    return "\n".join(
        f"    stall at {t - t0:+7.2f}s late {over * 1000:5.0f} ms: {c['kind']}; own cpu "
        f"{ms(c['cpu_s'])} ms, on a run queue {ms(c['waited_s'])} ms"
        + ("; " + streams_beside(log, t, over) if over > STALL_S / 2 else "")
        for t, over, c in stalls)


def window_seed(seed: int, k: int) -> int:
    """The traffic seed of a run's window k: `--seed` itself for the first,
    and for a further one a number made of `--seed` and k alone, so that two
    runs of one seed that void alike measure again alike."""
    if k == 0:
        return seed
    return int.from_bytes(hashlib.sha256(f"{seed}/window/{k}".encode()).digest()[:4], "big")


def room_for_a_window(elapsed_s: float, window_s: float) -> bool:
    """Whether a further window of `window_s` (its set-up phases, lead-in and
    measured seconds), opened `elapsed_s` into the run, and what follows a
    window end under the driver's cut less the margin."""
    return elapsed_s + window_s + TAIL_S <= RUN_LIMIT_S - RUN_MARGIN_S


# -- one run --------------------------------------------------------------------

class Serving:
    """The engine child and the router child, up and checked; `async with`."""

    def __init__(self, args, cell_name, cell, doc, base_dir, allow_platform, out_dir):
        self.args, self.cell_name, self.cell, self.doc = args, cell_name, cell, doc
        self.base_dir, self.allow_platform, self.out_dir = base_dir, allow_platform, out_dir
        self.children = Children(out_dir, child_env())
        self.load = None
        self.ebase, self.cbase, self.rbase = (f"http://127.0.0.1:{free_port()}" for _ in range(3))

    async def __aenter__(self):
        traced = bool(self.args.trace)
        port = lambda base: base.rsplit(":", 1)[1]  # noqa: E731
        self.session = aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0))
        try:
            engine = self.children.spawn([
                os.path.join(HERE, "engine_main.py"),
                "--config", os.path.join(self.base_dir, "configs", self.cell["config"] + ".json"),
                "--cell", os.path.join(self.base_dir, "cells", self.cell_name + ".json"),
                "--port", port(self.ebase), "--control-port", port(self.cbase),
                "--seed", str(self.args.seed), "--platform", self.allow_platform,
            ] + (["--debug"] if traced else []), "engine.log")
            await wait_healthy(self.session, self.ebase + "/health", engine, self.children,
                               "engine.log", 900)
            self.load_s = time.monotonic() - T_START
            router = self.children.spawn([
                "-m", "production_stack_tpu.router.app", "--host", "127.0.0.1",
                "--port", port(self.rbase), "--static-backends", self.ebase,
                "--static-models", self.doc["name"],
            ] + (["--enable-debug-endpoints"] if traced else []), "router.log")
            await wait_healthy(self.session, self.rbase + "/health", router, self.children,
                               "router.log", 60)
            stats = await get_json(self.session, self.ebase + "/stats")
            self.device = device = await get_json(self.session, self.cbase + "/device")
            if device["platform"] != self.allow_platform or stats["platform"] != self.allow_platform:
                raise BenchFailure(
                    f"the engine runs on platform={device['platform']!r}, not "
                    f"{self.allow_platform!r}: the benchmark measures nothing without the chip")
            if device["count"] < self.cell["chips"]:
                raise BenchFailure(
                    f"the cell needs {self.cell['chips']} chips, JAX shows {device['count']}")
            note(f"engine up on {device['kind']} x{device['count']} after {self.load_s:.1f}s; "
                 f"attention prefill={stats['attn_impl_prefill']} decode={stats['attn_impl_decode']}"
                 f" {stats.get('attn_impl_reason') or ''}; HBM in use after load "
                 f"{device['memory_in_use_bytes'] / 1e9:.2f} GB of "
                 f"{device['memory_limit_bytes'] / 1e9:.2f}")
            self.step_errors0 = stats["engine_step_errors_total"]
            self.load = Load(self.session, self.rbase, self.doc["name"])
        except BaseException:
            await self.__aexit__(None, None, None)
            raise
        return self

    async def __aexit__(self, *exc):
        tasks = list(self.load.tasks) if self.load else []
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await self.session.close()
        self.children.stop_all()

    async def setup_phases(self, phases: list) -> dict:
        """Each phase done before the next; the seconds each took, by name."""
        took = {}
        for phase in phases:
            t = time.monotonic()
            recs = await self.load.phase(phase)
            bad = [r for r in recs if not r["ok"]]
            note(f"set-up phase {phase['name']}: {len(recs)} requests in "
                 f"{time.monotonic() - t:.1f}s, {len(bad)} failed"
                 + (f" ({bad[0]['error']})" if bad else ""))
            if bad:
                raise BenchFailure(f"set-up phase {phase['name']} failed: {bad[0]['error']}")
            took[phase["name"]] = time.monotonic() - t
        return took

    async def snapshot(self) -> dict:
        s, (_, m) = await asyncio.gather(
            get_json(self.session, self.ebase + "/stats"),
            get_text(self.session, self.ebase + "/metrics"))
        return {"t": time.monotonic(), "stats": s, "metrics": m}


def load_cell(cell_name: str, base_dir: str, args):
    cell = manifest.load_json("cells", cell_name + ".json", base=base_dir)
    doc = manifest.load_json("configs", cell["config"] + ".json", base=base_dir)
    generator = manifest.load_module("traffic", cell["traffic"]["generator"], base_dir)
    out_dir = args.out or os.path.join(HERE, ".out", f"{cell_name}.{args.seed}.{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    return cell, doc, generator, out_dir


async def sleep_until(t: float, void: asyncio.Event) -> bool:
    """Sleeps to `t` on the monotonic clock, or until the window is void;
    says which."""
    delay = t - time.monotonic()
    if delay > 0 and not void.is_set():
        try:
            await asyncio.wait_for(void.wait(), delay)
        except asyncio.TimeoutError:
            pass
    return void.is_set()


async def take_trace(sv, out_dir: str, seconds: float, w: dict, taken: list) -> dict:
    """The profiler for TRACE_SECONDS, and the reduction of what it wrote (a
    child, awaited off the loop). `bad` says why a trace should be taken again:
    the load generator stalled beside it, or its device plane is empty (the
    engine stood at a first dispatch all through)."""
    nth = f".{len(taken)}" if taken else ""
    trace_dir = os.path.join(out_dir, "trace" + nth)
    ta = time.monotonic()
    await post_json(sv.session, sv.cbase + "/profile/start", {"dir": trace_dir})
    tb = time.monotonic()
    await asyncio.sleep(min(TRACE_SECONDS, max(0.5, 0.2 * seconds)))
    tc = time.monotonic()
    await post_json(sv.session, sv.cbase + "/profile/stop", {}, timeout=600)
    sub = {"start_lo": ta, "start_hi": tb, "stop_lo": tc, "stop_hi": time.monotonic(),
           "dir": trace_dir, "trace": None, "bad": None}
    taken.append(sub)
    try:
        sub["trace"] = await asyncio.get_running_loop().run_in_executor(
            None, reduce_trace, sub, os.path.join(out_dir, f"trace_reduced{nth}.json"))
    except BenchFailure as e:
        if "no device plane" not in str(e) and sv.allow_platform == "tpu":
            raise
        sub["bad"] = f"the trace holds no device plane with events ({str(e)[-300:].strip()})"
    beside = [over for t, over, _ in w["stalls"]
              if over > STALL_S / 2 and t < sub["stop_hi"] and t + over + 0.05 > ta]
    if beside and not sub["bad"]:
        sub["bad"] = f"the load generator stalled {max(beside) * 1000:.0f} ms beside the trace"
    return sub


async def measure_window(sv, plan: dict, seconds: float, traced: bool, out_dir: str,
                         taken: list, another_window) -> dict:
    """One window over `plan`: its traffic spawned, the wait to t0, the
    snapshot, in a traced run the profile, the wait to t1, the snapshot, the
    generator's lateness; then nothing new is sent and what is in flight is
    awaited. The window comes back with `void` None where it held, and else
    with why it measured nothing: it is abandoned the moment the heartbeat sees
    the stall, not at t1. `taken` holds the run's traces so far: a bad one is
    taken again ONCE in a run, in this window where t1 leaves the time and
    else in a further one, if `another_window()` says the run has room."""
    session, load = sv.session, sv.load
    stop, void = asyncio.Event(), asyncio.Event()
    # warm-up traffic runs from now; the window opens `warm_seconds` later
    t0 = time.monotonic() + plan["warm_seconds"] + 0.05
    t1 = t0 + seconds
    w = {"t0": t0, "t1": t1, "plan": plan, "void": None, "sub": None, "snap0": None,
         "snap1": None, "stalls": []}
    beat = asyncio.ensure_future(load.heartbeat(w, t0 - 1.0, void))
    for c in plan["clients"]:
        load.spawn(load.client(c, t0 + c["first_due_s"], stop))
    for req in plan["open"]:
        load.spawn(load.open_arrival(req, t0))
    if not await sleep_until(t0, void):
        if traced:
            await post_json(session, sv.ebase + "/metrics/reset", {})
        w["snap0"] = await sv.snapshot()
        m0 = w["snap0"]["metrics"]
        note(f"window opens {t0 - T_START:.2f}s into the run (engine healthy at "
             f"{sv.load_s:.1f}s; {prom(m0, 'vllm:compile_events_total'):.0f} compile events, "
             f"{prom(m0, 'vllm:compile_seconds_total'):.1f}s of compile or cache load)")
    if traced and not await sleep_until(t0 + 0.4 * seconds, void):
        first = not any(sub["bad"] for sub in taken)
        w["sub"] = sub = await take_trace(sv, out_dir, seconds, w, taken)
        took = sub["stop_hi"] - sub["start_lo"]
        if sub["bad"] and first and sv.allow_platform == "tpu" and not void.is_set():
            if time.monotonic() + took + 1.0 <= t1:
                note(f"trace taken again in this window: {sub['bad']}")
                w["sub"] = await take_trace(sv, out_dir, seconds, w, taken)
            elif another_window():
                w["void"] = f"{sub['bad']}, and t1 leaves no {took:.0f} s to take it again"
                void.set()
    await sleep_until(t1, void)
    if not void.is_set():
        w["snap1"] = await sv.snapshot()
        # TTFT counts from the time a request was DUE, so lateness up to the
        # limit is in the numbers. Past it the window measured the host, not
        # the system: it is void, and the run measures another (`run_cell`).
        late = max([over for t, over, _ in w["stalls"] if t0 - 1.0 <= t < t1] + [
            r["sent"] - r["due"] for r in load.log if t0 <= r["due"] < t1], default=0.0)
        note(f"the load generator ran at most {late * 1000:.0f} ms late in the window "
             f"(limit {STALL_S * 1000:.0f})")
        if late > STALL_S:
            w["void"] = ran_late(late)
    stop.set()
    beat.cancel()
    await asyncio.gather(beat, return_exceptions=True)
    for task in list(load.waiting):  # a void window's arrivals that are not sent yet
        task.cancel()
    for r in load.log:
        r["measured"] = not w["void"] and t0 <= r["due"] < t1
    # requests due in the window are awaited; nothing new is sent
    pending = list(load.tasks)
    if pending:
        done, late_tasks = await asyncio.wait(pending, timeout=REQUEST_TIMEOUT_S)
        for task in late_tasks:
            task.cancel()
        for task in done:
            if not task.cancelled() and task.exception():
                raise task.exception()
    if w["stalls"]:  # now that what lay in the sockets beside the last stall is read
        note(f"{len(w['stalls'])} heartbeat stalls over 50 ms beside this window\n"
             + stall_table(w["stalls"], t0, load.log))
    return w


async def run_cell(args, cell_name: str, base_dir: str, allow_platform: str = "tpu") -> dict:
    """Runs the cell and returns the result object. `base_dir` is the
    perfbench directory the data files are read from, and `allow_platform`
    is "tpu" on every path the command line reaches: only perfbench/tests pass
    another (a CPU rehearsal, whose numbers are printed under no metric's name).

    A window that comes back void is measured again on the same engine and
    router, MAX_WINDOWS in all at the most and only while `room_for_a_window`:
    the traffic of window k is the cell's own under `window_seed(--seed, k)`,
    after the set-up phases its plan marks `every_window` (state that belongs
    to the plan's own text: the sessions' cached histories) and its own
    lead-in, with no ramp and no drained warm-up, since every shape exists by
    then. `setup_s` is the FIRST window's opening whatever happens after; every
    other number comes from the window that held."""
    cell, doc, generator, out_dir = load_cell(cell_name, base_dir, args)
    traced = bool(args.trace)
    seconds = float(args.seconds)
    params, tok = cell["traffic"]["params"], doc["perfbench"]["tokenizer"]
    plan = generator.generate(params, args.seed, seconds, tok)
    async with Serving(args, cell_name, cell, doc, base_dir, allow_platform, out_dir) as sv:
        session, load, device = sv.session, sv.load, sv.device
        ebase, cbase, rbase = sv.ebase, sv.cbase, sv.rbase
        phase_s = await sv.setup_phases(plan["setup"])
        every_window = [p["name"] for p in plan["setup"] if p.get("every_window")]
        # what a further window costs: its phases (at what they cost the first), lead-in, seconds
        window_s = sum(phase_s[n] for n in every_window) + plan["warm_seconds"] + seconds

        # the load generator is one thread: no collector pause may fall on it
        gc.collect()
        gc.freeze()
        gc.disable()
        voided, taken, setup_s = [], [], None

        def another_window() -> bool:
            return (len(voided) + 1 < MAX_WINDOWS
                    and room_for_a_window(time.monotonic() - T_START, window_s))

        while True:
            w = await measure_window(sv, plan, seconds, traced, out_dir, taken, another_window)
            if setup_s is None:
                setup_s = w["t0"] - T_START
            if not w["void"]:
                break
            note(f"window {len(voided) + 1} voided: {w['void']}")
            if not another_window():
                raise BenchFailure(
                    f"{w['void']}, and no room to measure again: window {len(voided) + 1} of "
                    f"{MAX_WINDOWS}, {time.monotonic() - T_START:.0f} s gone, another takes "
                    f"{window_s:.0f} s and the {TAIL_S:.0f} s after a window, of "
                    f"{RUN_LIMIT_S - RUN_MARGIN_S:.0f}; the run gives no result")
            voided.append(w["void"])
            plan = generator.generate(params, window_seed(args.seed, len(voided)), seconds, tok)
            await sv.setup_phases([p for p in plan["setup"] if p["name"] in every_window])
        t0, t1, snap0, snap1, sub = w["t0"], w["t1"], w["snap0"], w["snap1"], w["sub"]
        m0 = snap0["metrics"]
        if voided:
            note(f"window {len(voided) + 1} held after {len(voided)} voided; setup_s stays the "
                 f"first window's opening, {setup_s:.2f}")
        gc.enable()
        device1 = await get_json(session, cbase + "/device")
        spans = {}
        if traced:
            for who, base in (("router", rbase), ("engine", ebase)):
                spans[who] = await get_json(session, base + "/v1/traces?limit=100000", 60)

        checks = await correctness(session, load, generator, cell, doc, cbase, ebase,
                                   plan, sv.step_errors0, args.seed)

    requests = load.log
    measured = [r for r in requests if r["measured"]]
    failed = [r for r in measured if not r["ok"]]
    late = sorted((r["sent"] - r["due"]) * 1000 for r in measured)
    note(f"requests: {len(requests)} sent in all, {len(measured)} due in the window, "
         f"{len(measured) - len(failed)} completed, {len(failed)} failed"
         + (f" (first: {failed[0]['error']})" if failed else "")
         + (f"; generator lateness p50 {pstats.percentile(late, 50):.2f} ms, "
            f"max {late[-1]:.2f} ms" if late else ""))
    d0, d1 = snap0["stats"], snap1["stats"]
    compiles = prom(snap1["metrics"], "vllm:compile_events_total") - prom(m0, "vllm:compile_events_total")
    note(f"window: {compiles:.0f} compile events inside it; engine counted "
         f"{d1['generation_tokens_total'] - d0['generation_tokens_total']} output tokens, "
         f"{d1['prompt_tokens_total'] - d0['prompt_tokens_total']} prompt tokens; "
         f"pool usage {d1.get('gpu_cache_usage_perc', 0):.3f}")
    if not measured:
        raise BenchFailure("no request was due in the window")
    # read by people, whatever the cell holds to a bound
    ttft = [pstats.ttft_ms(r, REQUEST_TIMEOUT_S * 1000.0) for r in measured]
    tpot = [v for v in (pstats.tpot_ms(r, REQUEST_TIMEOUT_S * 1000.0) for r in measured)
            if v is not None] or [0.0]
    inflight = [sum(1 for r in requests if r["sent"] <= t and (r["last"] or t1 + 1e9) > t)
                for t in (t0 + (t1 - t0) * k / 20 for k in range(1, 20))]
    note(f"client view over {len(ttft)} requests: ttft p50 {pstats.percentile(ttft, 50):.1f} "
         f"p95 {pstats.percentile(ttft, 95):.1f} ms; tpot p50 {pstats.percentile(tpot, 50):.2f} "
         f"p95 {pstats.percentile(tpot, 95):.2f} ms; in flight (19 looks) min {min(inflight)} "
         f"median {sorted(inflight)[9]} max {max(inflight)}")

    worst_ms = REQUEST_TIMEOUT_S * 1000.0
    context = {
        "cell": cell, "config": doc, "requests": requests, "window": (t0, t1),
        "snap0": snap0, "snap1": snap1, "spans": spans, "trace": None, "sub": sub,
        "device_kind": device["kind"], "base_dir": base_dir, "worst_ms": worst_ms,
        "windows_voided": len(voided),
    }
    result_device = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": device1["memory_peak_bytes"],
    }
    metrics: dict = {}
    breakdown = None

    def report(name, value, unit, detail=""):
        note(f"{name}: {value} {unit}{detail}")
        if value is not None:  # a reader that finds nothing to read leaves the metric out
            metrics[name] = {"value": value, "unit": unit}

    if not traced:
        for name in cell["end_to_end"]:
            spec = manifest.load_json("end_to_end", name + ".json", base=base_dir)
            if spec["stat"]["kind"] == "setup":
                value, n = setup_s, 1
            else:
                value, n = pstats.end_to_end(spec, requests, (t0, t1), worst_ms)
            report(name, value, spec["unit"], f" over {n} samples")
    else:
        if sub["bad"]:
            note(f"the trace is kept as it is: {sub['bad']}")
        if sub["trace"] is None and allow_platform == "tpu":
            raise BenchFailure(f"{sub['bad']}, taken {len(taken)} times")
        # a CPU rehearsal has no device plane: the trace readers return nothing
        context["trace"] = sub["trace"] or {
            "busy_s": 0.0, "window_s": 0.0, "top_ops": [], "top_gaps": [], "ops": {},
            "modules": {}, "devices": 1}
        if allow_platform == "tpu":  # an unknown device kind is an error, not a default
            context["peaks"] = manifest.peaks(device["kind"], base_dir)
        tr = context["trace"]
        result_device["busy_s"], result_device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": [[n[:160], t] for n, t in tr["top_ops"][:10]],
                     "idle_gaps": idle_gaps(tr, d0, d1)}
        for name in cell["per_layer"]:
            spec = manifest.load_json("layer_metrics", name + ".json", base=base_dir)
            reader = manifest.load_module("readers", spec["reader"], base_dir)
            report(name, reader.read(context, spec.get("params", {})), spec["unit"])
    with open(os.path.join(out_dir, "requests.json"), "w") as f:
        json.dump({"cell": cell_name, "window": [t0, t1], "setup_s": setup_s, "voided": voided,
                   "sub": sub and {k: v for k, v in sub.items() if k != "trace"},
                   "snap0": snap0, "snap1": snap1, "requests": [
            {k: v for k, v in r.items() if k != "logprobs"} for r in requests]}, f)
    for name, c in checks.items():
        note(f"check {name}: {'ok' if c['ok'] else 'FAILED'} {c.get('detail', '')}")
    if allow_platform != "tpu":
        # a number from a CPU run never stands under a device metric's name
        metrics = {"cpu_rehearsal." + k: v for k, v in metrics.items()}
    result = {
        "correct": all(c["ok"] for c in checks.values()) and not failed,
        "attempted": len(measured), "failed": len(failed), "metrics": metrics,
        "device": result_device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    return result


async def run_sweep(args, cell_name: str, base_dir: str, allow_platform: str = "tpu") -> dict:
    """Finds the knee of an open-loop cell, once: one set-up, then each rate of
    `--sweep` offered for `--seconds`, the system drained between rates. The
    knee is the highest rate at which completions keep pace with arrivals and
    the in-flight count does not grow through the step; it is read off the
    table by hand and written into the cell's file as a number."""
    import copy

    cell, doc, generator, out_dir = load_cell(cell_name, base_dir, args)
    tok = doc["perfbench"]["tokenizer"]
    rates = [float(r) for r in args.sweep.split(",")]
    seconds = float(args.seconds)
    rows = []
    async with Serving(args, cell_name, cell, doc, base_dir, allow_platform, out_dir) as sv:
        warm = generator.generate(cell["traffic"]["params"], args.seed, 1.0, tok)
        await sv.setup_phases(warm["setup"])
        for i, rate in enumerate(rates):
            params = copy.deepcopy(cell["traffic"]["params"])
            stream = next(s for s in params["streams"] if s["kind"] == "open")
            stream.update(rate_rps=rate, warm_seconds=0, lead_seconds=0, ramp=None)
            plan = generator.generate(params, args.seed + i, seconds, tok)
            first = len(sv.load.log)
            t0 = time.monotonic() + 0.05
            for req in plan["open"]:
                sv.load.spawn(sv.load.open_arrival(req, t0))
            snap0 = await sv.snapshot()
            inflight = []
            for frac in (0.25, 0.5, 0.75, 1.0):
                await asyncio.sleep(max(0.0, t0 + frac * seconds - time.monotonic()))
                recs = sv.load.log[first:]
                inflight.append(sum(1 for r in recs if r["first"] is None or r["last"] is None
                                    or r["finish"] is None) - sum(1 for r in recs if r["error"]))
            snap1 = await sv.snapshot()
            done_in_step = sum(1 for r in sv.load.log[first:] if r["finish"] is not None)
            if sv.load.tasks:
                await asyncio.wait(list(sv.load.tasks), timeout=REQUEST_TIMEOUT_S)
            drain = time.monotonic() - (t0 + seconds)
            recs = sv.load.log[first:]
            ok = [r for r in recs if r["ok"]]
            ttft = [pstats.ttft_ms(r, 120000.0) for r in recs]
            tpot = [v for v in (pstats.tpot_ms(r, 120000.0) for r in recs) if v is not None]
            d0, d1 = snap0["stats"], snap1["stats"]
            row = {
                "rate_rps": rate, "arrivals": len(recs), "completed_in_step": done_in_step,
                "failed": len(recs) - len(ok), "inflight_at_25_50_75_100": inflight,
                "drain_s": drain, "ttft_p50_ms": pstats.percentile(ttft, 50),
                "ttft_p95_ms": pstats.percentile(ttft, 95),
                "tpot_p50_ms": pstats.percentile(tpot, 50) if tpot else None,
                "engine_output_tokens_per_s": (
                    d1["generation_tokens_total"] - d0["generation_tokens_total"]) / seconds,
                "compile_events": prom(snap1["metrics"], "vllm:compile_events_total")
                - prom(snap0["metrics"], "vllm:compile_events_total"),
                "waiting_at_end": d1["num_requests_waiting"], "running_at_end": d1["num_requests_running"],
            }
            rows.append(row)
            note("sweep " + json.dumps(row))
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return {"sweep": rows, "device": sv.device}


def idle_gaps(tr: dict, d0: dict, d1: dict) -> list:
    """The program writes no host spans into the profiler's trace, so a gap
    cannot be named by what the host did in it. The longest gaps carry their
    position; the idle time as a whole is attributed by the engine loop's own
    section seconds over the window (host clock)."""
    sections = {
        k[len("engine_loop_"):-len("_seconds_total")]: d1[k] - d0.get(k, 0.0)
        for k in d1 if k.startswith("engine_loop_") and k.endswith("_seconds_total")
    }
    total = sum(sections.values()) or 1.0
    host = [(f"engine_loop.{k}.share_of_loop_wall", v / total)
            for k, v in sorted(sections.items(), key=lambda kv: -kv[1])][:5]
    return [[f"gap_after {name[:120]}", secs] for name, secs in tr["top_gaps"][:5]] + [
        list(h) for h in host]


def reduce_trace(sub: dict, out: str) -> dict:
    """`tracereduce.py` in a child of its own with JAX held to the CPU: it
    reads the profile with JAX's reader and this parent stays off JAX."""
    found = []
    for d, _, files in os.walk(sub["dir"]):
        found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
    if not found:
        raise BenchFailure(f"the profiler wrote no .xplane.pb under {sub['dir']}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tracereduce.py"), found[0], "--out", out],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise BenchFailure(f"tracereduce failed: {proc.stderr[-2000:]}")
    with open(out) as f:
        return json.load(f)


# -- correctness ----------------------------------------------------------------

def prompt_ids(messages: list[dict], tok: dict) -> list[int]:
    """The byte tokenizer's ids for a chat prompt (one token a character, BOS
    256, `<|role|>\\n...\\n` around each message, `<|assistant|>\\n` last). The
    served `usage.prompt_tokens` is held against the length."""
    if tok["kind"] != "byte":
        raise BenchFailure(f"no tokenizer model for kind {tok['kind']!r}")
    s = "".join(f"<|{m['role']}|>\n{m['content']}\n" for m in messages) + "<|assistant|>\n"
    return [256] * tok["bos_tokens"] + list(s.encode("ascii"))


async def correctness(session, load, gen, cell, doc, cbase, ebase, plan, step_errors0,
                      seed) -> dict:
    """Outside the window, with nothing else in flight."""
    import random

    tok = doc["perfbench"]["tokenizer"]
    spec = cell["correctness"]
    rng = random.Random(f"{seed}/correctness")
    checks = {}
    # 1 a greedy request repeated with nothing else in flight: identical tokens
    req = gen.one_shot(rng, f"g{seed:x} ", spec["greedy_prompt_tokens"], 24, tok, "check")
    now = time.monotonic()
    a = await load.fire(req, now, greedy=True, logprobs=1)
    b = await load.fire(req, now, greedy=True, logprobs=1)
    same = a["ok"] and b["ok"] and [
        (e["token"], e["logprob"]) for e in a["logprobs"]] == [
        (e["token"], e["logprob"]) for e in b["logprobs"]] and len(a["logprobs"]) == 24
    checks["greedy_repeat"] = {"ok": bool(same), "detail": a["error"] or b["error"] or ""}
    # 2 a turn answered from cached pages equals the same turn on a cold cache.
    # The turn is sent twice under another first character (so it shares no
    # page with the window's traffic): cold, then from the pages the first left.
    # The first token's top-20 log-probabilities are compared, value for value:
    # later tokens would compare free-running samples across a near-tie.
    if spec.get("cached_vs_cold"):
        turn = next(c for c in plan["clients"] if c["turns"])["turns"][0]
        head = turn["messages"][0]
        msgs = [dict(head, content="~" + head["content"][1:])] + turn["messages"][1:]
        probe = dict(turn, messages=msgs, max_tokens=8)
        cold = await load.fire(probe, now, greedy=True, logprobs=20)
        warm = await load.fire(probe, now, greedy=True, logprobs=20)
        worst = 9.9
        if cold["ok"] and warm["ok"] and cold["logprobs"] and warm["logprobs"]:
            worst = max(abs(x["logprob"] - y["logprob"]) for x, y in zip(
                cold["logprobs"][0]["top_logprobs"], warm["logprobs"][0]["top_logprobs"]))
        checks["cached_vs_cold"] = {
            "ok": warm["cached_tokens"] > cold["cached_tokens"]
            and worst <= spec["cached_vs_cold"]["tolerance"],
            "detail": f"cached {cold['cached_tokens']} -> {warm['cached_tokens']} of "
                      f"{warm['prompt_tokens']} tokens, max |dlogprob| {worst:.4f} "
                      f"{cold['error'] or warm['error'] or ''}"}
    # 3 the plain reference, at the published widths, on the engine's own
    # parameters: one seeded prompt served greedily with the top-20
    # log-probabilities, followed through the reference token by token
    ref = spec["reference"]
    req = gen.one_shot(rng, f"f{seed:x} ", ref["prompt_tokens"], ref["output_tokens"], tok, "check")
    served = await load.fire(req, time.monotonic(), greedy=True, logprobs=20)
    if not served["ok"] or len(served["logprobs"]) != ref["output_tokens"]:
        checks["reference"] = {"ok": False, "detail": f"the served request failed: {served['error']}"}
    else:
        ids = prompt_ids(req["messages"], tok)
        steps = [{"chosen": e["logprob"], "top": [t["logprob"] for t in e["top_logprobs"]]}
                 for e in served["logprobs"]]
        pad = -(-(len(ids) + len(steps)) // 128) * 128
        res = await post_json(session, cbase + "/reference", {
            "prompt_ids": ids, "steps": steps, "tolerance": ref["tolerance"], "pad_to": pad,
        }, timeout=900)
        checks["reference"] = {
            "ok": bool(res["ok"]) and len(ids) == served["prompt_tokens"],
            "detail": f"{res['steps_matched']}/{res['steps']} steps, max |dlogprob| "
                      f"{res['max_abs_diff']}, tolerance {res['tolerance']}, "
                      f"{res['ties_tried']} near-ties tried; prompt {len(ids)} tokens "
                      f"(served {served['prompt_tokens']})"}
    stats = await get_json(session, ebase + "/stats")
    errs = stats["engine_step_errors_total"] - step_errors0
    checks["step_errors"] = {"ok": errs == 0, "detail": f"{errs} step errors {stats.get('engine_program_fault') or ''}"}
    return checks


# -- entry ----------------------------------------------------------------------

def parse(argv):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default=None, help="directory for logs, request log and trace")
    p.add_argument("--sweep", default=None, metavar="R1,R2,...",
                   help="not a benchmark run: offer each rate for --seconds and print the table")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    if not os.path.isdir(os.path.join(ROOT, "production_stack_tpu")):
        print("perfbench: the program (production_stack_tpu/) is not beside perfbench/",
              file=sys.stderr)
        return 2
    try:
        runner = run_sweep if args.sweep else run_cell
        result = asyncio.run(runner(args, args.workload, HERE))
    except BenchFailure as e:
        print(f"perfbench FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    assert "jax" not in sys.modules, "the benchmark's parent must never import JAX"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
