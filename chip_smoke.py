#!/usr/bin/env python3
"""Does the serving path still start on the chip?  ``python3 chip_smoke.py``

Drives the normal entry points once — router.app -> engine.api_server ->
scheduler -> kv_manager -> jitted step -> Pallas kernels — on the TPU JAX
finds, and checks what comes out. It is a smoke, not a benchmark: no rate,
latency or utilisation is measured or printed.

Phases (each must pass; any failure exits non-zero and prints no result):
  probe  a child asks JAX for its devices; anything but a TPU stops here.
  K      both Pallas kernels, COMPILED, against the XLA oracle
         (production_stack_tpu/testing/kernel_oracle.py), in a child.
  A      one chip: llama-3.2-1b at published widths and full depth, seeded
         random weights, behind the router; a few dozen chat completions.
  B      four chips (only when JAX shows >= 4 TPU devices): mistral-7b at
         full depth, --tensor-parallel 4, behind the router, same traffic;
         plus pool shard layout and per-device memory balance.

This process NEVER imports JAX (asserted at exit): a parent that touched JAX
would hold the chip its children need. Every child is waited on before the
next one that needs the chip starts. Children share a fresh persistent
compile cache inside the output directory; nothing a run compiles or loads
comes from a file git would not commit.

Output: ``chiprun_out/chip_smoke/`` (child logs, kernel report, summary.json).
The last line of stdout is the result object, device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

PROBE = """
import importlib.metadata as md, json, jax, jaxlib
d = jax.devices()
def ver(p):
    try:
        return md.version(p)
    except md.PackageNotFoundError:
        return None
print(json.dumps({
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": ver("libtpu"),
}))
"""


class SmokeFailure(Exception):
    """A phase did not pass; the message says which check."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- children -----------------------------------------------------------------

_children: list[subprocess.Popen] = []


def child_env(out_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # fresh, shared by this run's children, never the repo's .cache/xla
    env["PSTPU_COMPILE_CACHE_DIR"] = os.path.join(out_dir, "xla_cache")
    env["PSTPU_FLIGHTRECORDER_DIR"] = os.path.join(out_dir, "flightrecorder")
    return env


def spawn(argv: list[str], log_name: str, out_dir: str) -> subprocess.Popen:
    log = open(os.path.join(out_dir, log_name), "w")
    proc = subprocess.Popen(
        [sys.executable] + argv, cwd=ROOT, env=child_env(out_dir),
        stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
    )
    log.close()
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace: float = 60.0) -> None:
    """SIGTERM (the engine drains and exits), then the whole group is
    killed; returns only once the child is waited on — the chip is free."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()
    if proc in _children:
        _children.remove(proc)


def stop_all() -> None:
    for proc in list(_children):
        stop(proc, grace=5.0)


def log_tail(out_dir: str, log_name: str, n: int = 40) -> str:
    try:
        with open(os.path.join(out_dir, log_name), errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def run_child(argv, log_name, out_dir, timeout) -> int:
    proc = spawn(argv, log_name, out_dir)
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        return -1
    finally:
        stop(proc, grace=5.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- HTTP (stdlib; the parent stays off aiohttp as well as JAX) -----------------

def http_get(url: str, timeout: float = 10.0) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(errors="replace")


def wait_healthy(url, proc, log_name, out_dir, timeout) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{log_name}: process exited rc={proc.returncode} before "
                f"{url} answered\n{log_tail(out_dir, log_name)}"
            )
        try:
            if http_get(url, timeout=3.0)[0] == 200:
                return
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    raise SmokeFailure(
        f"{url} not healthy after {timeout:.0f}s\n{log_tail(out_dir, log_name)}"
    )


def chat(base: str, model: str, prompt: str, max_tokens: int, *,
         stream: bool, temperature: float = 0.7, logprobs: bool = False,
         timeout: float = 600.0) -> dict:
    """One chat completion through the router. Returns status, finish
    reason, usage, text and (when asked) the per-token logprobs."""
    body = {
        "model": model, "max_tokens": max_tokens, "stream": stream,
        "temperature": temperature, "ignore_eos": True,
        "messages": [{"role": "user", "content": prompt}],
    }
    if logprobs:
        body.update(logprobs=True, top_logprobs=1)
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    out = {"status": 0, "finish": None, "usage": {}, "text": "",
           "logprobs": [], "stream": stream}
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            out["status"] = r.status
            if not stream:
                doc = json.loads(r.read())
                choice = doc["choices"][0]
                out["finish"] = choice["finish_reason"]
                out["usage"] = doc.get("usage") or {}
                out["text"] = choice["message"]["content"] or ""
                lp = choice.get("logprobs") or {}
                out["logprobs"] = [
                    (e["token"], e["logprob"]) for e in lp.get("content") or []
                ]
                return out
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data:") or line.endswith("[DONE]"):
                    continue
                doc = json.loads(line[5:])
                if doc.get("usage"):
                    out["usage"] = doc["usage"]
                for choice in doc.get("choices") or []:
                    out["text"] += (choice.get("delta") or {}).get("content") or ""
                    if choice.get("finish_reason"):
                        out["finish"] = choice["finish_reason"]
    except urllib.error.HTTPError as e:
        out["status"] = e.code
        out["text"] = e.read().decode(errors="replace")[:500]
    return out


def metric(text: str, name: str) -> float:
    """Sum of a Prometheus series over its label sets (0.0 if absent)."""
    vals = re.findall(
        rf"^{re.escape(name)}(?:{{[^}}]*}})? ([0-9.eE+-]+)$", text, re.M
    )
    return sum(float(v) for v in vals)


def per_device(text: str, name: str) -> dict:
    """A per-device Prometheus series as {device label: value}."""
    return {
        dev: float(v) for dev, v in re.findall(
            rf'^{re.escape(name)}{{[^}}]*device="([^"]+)"[^}}]*}} ([0-9.eE+-]+)$',
            text, re.M,
        )
    }


# -- phases -----------------------------------------------------------------------

def phase_probe(out_dir: str, expect_platform: str) -> dict:
    rc = run_child(["-c", PROBE], "probe.log", out_dir, timeout=180)
    tail = log_tail(out_dir, "probe.log")
    check(rc == 0, f"probe: JAX found no usable device (rc={rc})\n{tail}")
    dev = json.loads(tail.strip().splitlines()[-1])
    check(
        dev["platform"] == expect_platform,
        f"probe: platform is {dev['platform']!r}, not {expect_platform!r} — "
        "chip_smoke.py proves nothing without the accelerator",
    )
    print(
        f"probe: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
        f"libtpu={dev['libtpu']}", flush=True,
    )
    return dev


def phase_k(out_dir: str, expect_platform: str, *, interpret: bool = False,
            timeout: float = 600.0) -> dict:
    """Kernels against the oracle in a child of their own."""
    report = os.path.join(out_dir, "kernel_oracle.json")
    argv = ["-m", "production_stack_tpu.testing.kernel_oracle", "--out", report]
    if interpret:
        argv += ["--interpret", "--tiny"]
    rc = run_child(argv, "kernel_oracle.log", out_dir, timeout)
    tail = log_tail(out_dir, "kernel_oracle.log")
    check(os.path.exists(report), f"K: no report (rc={rc})\n{tail}")
    with open(report) as f:
        rep = json.load(f)
    check(rep["platform"] == expect_platform,
          f"K: ran on {rep['platform']!r}, not {expect_platform!r}")
    check(rep["interpret"] == interpret, "K: wrong kernel mode")
    bad = [n for n, c in rep["cases"].items() if not c["ok"]]
    check(not bad, f"K: kernel cases outside tolerance: {bad}\n{tail}")
    check(rep["steps"]["ok"], f"K: step programs off the XLA path: {rep['steps']}")
    check(rc == 0 and rep["ok"], f"K: failed (rc={rc})\n{tail}")
    worst = max(c["max_abs_err"] for c in rep["cases"].values())
    print(
        f"K: {len(rep['cases'])} kernel cases "
        f"{'interpreted' if interpret else 'compiled'} on {rep['platform']}, "
        f"max |err| vs oracle {worst:.4g} (tol {rep['tol']}); step programs "
        f"({rep['steps']['model']}; prefill={rep['steps']['prefill']} "
        f"decode={rep['steps']['decode']}) max |dlogit| vs XLA path "
        f"{max(rep['steps']['max_abs_err'].values()):.4g}; "
        f"{len(rep['excluded'])} shapes excluded by the rule", flush=True,
    )
    return rep


@dataclasses.dataclass
class Serving:
    """One serving phase: the model, how it is sized, the traffic's sizes."""

    name: str
    model: str
    engine_args: tuple = ()
    tensor_parallel: int = 1
    long_prompt: int = 1400   # tokens; > 2 default prefill_chunks of 512
    burst_prompt: int = 300   # request i of a burst adds i * burst_step
    burst_step: int = 37
    bursts: tuple = (8, 16)   # requests arriving together, twice
    gen: int = 24
    start_timeout: float = 600.0
    expect_decode: tuple = ("pallas", "pallas_shard_map", "xla")


def _prompt(tag: str, n: int) -> str:
    """~n byte-tokenizer tokens (one per ASCII character), distinct per tag
    from the first page on so bursts do not share prefixes by accident."""
    words = f"{tag} the quick brown fox jumps over the lazy dog "
    return (words * (n // len(words) + 1))[:n]


def _check_response(r: dict, want_tokens: int, what: str) -> None:
    check(r["status"] == 200, f"{what}: HTTP {r['status']} {r['text'][:300]}")
    # ignore_eos is set, so anything but "length" (above all "error") fails
    check(r["finish"] == "length",
          f"{what}: finish_reason={r['finish']!r}, wanted 'length'")
    got = r["usage"].get("completion_tokens")
    check(got == want_tokens,
          f"{what}: completion_tokens={got}, asked for {want_tokens}")


def serve_and_check(out_dir: str, expect_platform: str, sv: Serving) -> dict:
    """Engine child + router child, traffic to the ROUTER, checks, teardown.
    The engine child is waited on before this returns."""
    eport, rport = free_port(), free_port()
    elog, rlog = f"{sv.name}_engine.log", f"{sv.name}_router.log"
    engine = spawn(
        ["-m", "production_stack_tpu.engine.api_server", "--model", sv.model,
         "--host", "127.0.0.1", "--port", str(eport),
         "--tensor-parallel", str(sv.tensor_parallel), *sv.engine_args],
        elog, out_dir,
    )
    router = None
    try:
        ebase = f"http://127.0.0.1:{eport}"
        t0 = time.monotonic()
        wait_healthy(ebase + "/health", engine, elog, out_dir, sv.start_timeout)
        load_s = time.monotonic() - t0
        router = spawn(
            ["-m", "production_stack_tpu.router.app", "--host", "127.0.0.1",
             "--port", str(rport), "--static-backends", ebase,
             "--static-models", sv.model],
            rlog, out_dir,
        )
        base = f"http://127.0.0.1:{rport}"
        wait_healthy(base + "/health", router, rlog, out_dir, 60.0)

        stats0 = json.loads(http_get(ebase + "/stats")[1])
        check(stats0["platform"] == expect_platform,
              f"{sv.name}: engine reports platform={stats0['platform']!r}")
        check(stats0["attn_impl_decode"] in sv.expect_decode,
              f"{sv.name}: decode resolved to {stats0['attn_impl_decode']!r}")
        mem_loaded = per_device(
            http_get(ebase + "/metrics")[1], "vllm:tpu_hbm_bytes_in_use"
        )

        n_req = 0
        # 1 · warm-up, then a greedy pair with NOTHING else in flight: same
        # programs, same inputs, so the tokens must be identical (short
        # prompt: under one page, no prefix-cache hit to change the shapes)
        _check_response(chat(base, sv.model, "hello", 8, stream=False), 8, "warm")
        pair = [
            chat(base, sv.model, "count to ten", sv.gen, stream=False,
                 temperature=0.0, logprobs=True)
            for _ in range(2)
        ]
        for r in pair:
            _check_response(r, sv.gen, "greedy")
        check(len(pair[0]["logprobs"]) == sv.gen, "greedy: logprobs missing")
        check(
            pair[0]["text"] == pair[1]["text"]
            and pair[0]["logprobs"] == pair[1]["logprobs"],
            "greedy repeat returned different tokens",
        )
        n_req += 3
        # 2 · a prompt longer than two prefill chunks, streamed
        long_p = _prompt("long", sv.long_prompt)
        r = chat(base, sv.model, long_p, sv.gen, stream=True)
        _check_response(r, sv.gen, "long")
        check(r["usage"]["prompt_tokens"] >= sv.long_prompt, "long: prompt cut")
        # 3 · prompts arriving together: B>1 prefill batch and B>1 decode
        # bursts; streaming and non-streaming mixed, ragged lengths
        def burst(tag, n, lo):
            with concurrent.futures.ThreadPoolExecutor(n) as pool:
                futs = [
                    pool.submit(
                        chat, base, sv.model,
                        _prompt(f"{tag}{i}", sv.burst_prompt + sv.burst_step * i),
                        lo + 2 * i, stream=bool(i % 2),
                    )
                    for i in range(n)
                ]
                for i, f in enumerate(futs):
                    _check_response(f.result(), lo + 2 * i, f"{tag}[{i}]")
            return n
        n_req += 1 + burst("burst-a", sv.bursts[0], sv.gen)
        # 4 · the long prompt again: its whole pages come from the prefix cache
        r = chat(base, sv.model, long_p, sv.gen, stream=False)
        _check_response(r, sv.gen, "repeat")
        cached = r["usage"]["prompt_tokens_details"]["cached_tokens"]
        check(cached > 0, "repeat: no prefix-cache hit on a repeated prompt")
        n_req += 1 + burst("burst-b", sv.bursts[1], 8)

        stats = json.loads(http_get(ebase + "/stats")[1])
        mtxt = http_get(ebase + "/metrics")[1]
        check(http_get(ebase + "/health")[0] == 200, f"{sv.name}: /health not 200")
        check(stats["engine_step_errors_total"] == 0,
              f"{sv.name}: {stats['engine_step_errors_total']} step errors: "
              f"{stats['engine_program_fault']}")
        check(stats["gpu_prefix_cache_hits_total"] > 0, "no prefix-cache hits")
        res = {
            "model": sv.model, "requests": n_req, "load_seconds": round(load_s, 1),
            "platform": stats["platform"], "device_kind": stats["device_kind"],
            "device_count": stats["device_count"],
            "mesh_devices": stats["mesh_devices"],
            "attn_impl_prefill": stats["attn_impl_prefill"],
            "attn_impl_decode": stats["attn_impl_decode"],
            "attn_impl_reason": stats["attn_impl_reason"],
            "compile_events": int(metric(mtxt, "vllm:compile_events_total")),
            "compile_seconds": round(metric(mtxt, "vllm:compile_seconds_total"), 1),
            "cached_tokens_on_repeat": cached,
            "prompt_tokens_total": stats["prompt_tokens_total"],
            "generation_tokens_total": stats["generation_tokens_total"],
            "hbm_bytes_in_use_loaded": mem_loaded,
            "hbm_bytes_in_use_after": per_device(mtxt, "vllm:tpu_hbm_bytes_in_use"),
            "kv_pool_shard_bytes": per_device(mtxt, "vllm:kv_pool_shard_bytes"),
        }
        print(
            f"{sv.name}: {sv.model} answered {n_req} requests through the "
            f"router on platform={res['platform']} "
            f"device_kind={res['device_kind']} devices={res['device_count']} "
            f"(mesh {res['mesh_devices']}); attention prefill="
            f"{res['attn_impl_prefill']} decode={res['attn_impl_decode']}"
            + (f" [{res['attn_impl_reason']}]" if res["attn_impl_reason"] else "")
            + f"; set-up: load+listen {res['load_seconds']}s, "
            f"{res['compile_events']} compiles in {res['compile_seconds']}s; "
            "step errors 0; speed: not measured", flush=True,
        )
        return res
    finally:
        if router is not None:
            stop(router, grace=10.0)
        stop(engine)


def phase_a(out_dir: str, expect_platform: str, sv: Serving | None = None) -> dict:
    """One chip, full model. llama-3.2-1b is the only preset that fits one
    16 GB chip whole; its head_dim 64 is outside what the kernels' page DMA
    can do, so the rule resolves attention to XLA and the run says so."""
    sv = sv or Serving(
        name="A", model="llama-3.2-1b",
        # sizing only: head_dim-64 pools are stored lane-sparse and every
        # step relayouts them through temporaries of twice their size
        # (PERF.md "Bring-up"), so the pool is half the 4 GB default
        engine_args=("--max-model-len", "4096", "--kv-cache-memory-gb", "2"),
    )
    return serve_and_check(out_dir, expect_platform, sv)


def phase_b(out_dir: str, expect_platform: str, sv: Serving | None = None) -> dict:
    """Four chips: mistral-7b, full depth, tp=4 behind the router; the
    decode kernel runs per shard through shard_map, prefill on XLA."""
    sv = sv or Serving(
        name="B", model="mistral-7b", tensor_parallel=4,
        engine_args=("--max-model-len", "8192"), start_timeout=900.0,
        expect_decode=("pallas_shard_map",),
    )
    res = serve_and_check(out_dir, expect_platform, sv)
    tp = sv.tensor_parallel
    shards = res["kv_pool_shard_bytes"]
    check(len(shards) == tp and all(d.startswith(f"{expect_platform}:") for d in shards),
          f"B: pool shard layout {shards}")
    check(len(set(shards.values())) == 1, f"B: unequal pool shares {shards}")
    for when in ("hbm_bytes_in_use_loaded", "hbm_bytes_in_use_after"):
        mem = res[when]
        check(len(mem) >= tp, f"B: {when} covers {len(mem)} devices")
        lo, hi = min(mem.values()), max(mem.values())
        check(lo > 0 and hi <= 1.5 * lo,
              f"B: {when} unbalanced across chips: {mem}")
    print(
        "B: pool shards " + json.dumps(shards) + "; bytes_in_use after load "
        + json.dumps(res["hbm_bytes_in_use_loaded"]) + ", after traffic "
        + json.dumps(res["hbm_bytes_in_use_after"]), flush=True,
    )
    return res


def main() -> int:
    check(
        os.path.isdir(os.path.join(ROOT, "production_stack_tpu")),
        "chip_smoke.py runs from a checkout of the repo; the program is not here",
    )
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    t0 = time.monotonic()
    summary: dict = {"ok": False}
    try:
        dev = phase_probe(OUT_DIR, "tpu")
        summary["probe"] = dev
        summary["K"] = phase_k(OUT_DIR, "tpu")
        summary["A"] = phase_a(OUT_DIR, "tpu")
        if dev["count"] >= 4:
            summary["B"] = phase_b(OUT_DIR, "tpu")
        else:
            print(f"B: skipped, JAX shows {dev['count']} device(s); "
                  "tp=4 needs a four-chip host", flush=True)
        summary["ok"] = True
    finally:
        stop_all()
        # the compile cache served its run; it is tens of MB nobody reads
        shutil.rmtree(os.path.join(OUT_DIR, "xla_cache"), ignore_errors=True)
        summary["seconds"] = round(time.monotonic() - t0, 1)
        summary["claim"] = None  # a smoke: no speed is measured or claimed
        with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    assert "jax" not in sys.modules, "the smoke's parent must never import JAX"
    print(f"chip_smoke: all phases passed in {summary['seconds']}s; "
          f"output in {os.path.relpath(OUT_DIR, ROOT)}/; claim: null", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
