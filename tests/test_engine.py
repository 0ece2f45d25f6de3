"""Engine tests: generation loop, continuous batching, prefix cache, and the
OpenAI HTTP surface (real server subprocess, reference test strategy §4.2)."""

import asyncio
import json

import numpy as np
import pytest
import requests

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.scheduler import SamplingParams
from production_stack_tpu.testing.procs import free_port, start_proc, stop_proc, wait_healthy


def _cfg(**kw):
    base = dict(
        model="llama-debug",
        max_model_len=256,
        max_num_seqs=8,
        num_pages=64,
        page_size=8,
        prefill_chunk=32,
        kv_cache_memory_gb=0.01,
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def engine():
    eng = LLMEngine(_cfg())
    eng.start()
    yield eng
    eng.stop()


def _collect(engine, prompt, **params):
    async def run():
        outs = []
        async for out in engine.generate(
            f"t-{np.random.randint(1 << 30)}", prompt=prompt,
            params=SamplingParams(**params),
        ):
            outs.append(out)
        return outs

    return asyncio.run(run())


def test_generate_deterministic_greedy(engine):
    outs = _collect(engine, "hello world", max_tokens=8, temperature=0.0, ignore_eos=True)
    assert outs[-1].finished and outs[-1].finish_reason == "length"
    assert outs[-1].completion_tokens == 8
    toks1 = [o.token_ids[0] for o in outs if o.token_ids]
    outs2 = _collect(engine, "hello world", max_tokens=8, temperature=0.0, ignore_eos=True)
    toks2 = [o.token_ids[0] for o in outs2 if o.token_ids]
    assert toks1 == toks2  # greedy must be reproducible


def test_concurrent_requests_batched(engine):
    async def run():
        async def one(i):
            outs = []
            async for out in engine.generate(
                f"c-{i}", prompt=f"prompt number {i}",
                params=SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True),
            ):
                outs.append(out)
            return outs

        return await asyncio.gather(*[one(i) for i in range(6)])

    results = asyncio.run(asyncio.wait_for(run(), 120))
    for outs in results:
        assert outs[-1].finished
        assert outs[-1].completion_tokens == 12


def test_prefix_cache_hit(engine):
    prompt = "a shared system prompt that is long enough to span pages " * 4
    _collect(engine, prompt, max_tokens=4, temperature=0.0, ignore_eos=True)
    outs = _collect(engine, prompt, max_tokens=4, temperature=0.0, ignore_eos=True)
    assert outs[-1].cached_tokens > 0
    # cached generation must not change greedy output
    outs_again = _collect(engine, prompt, max_tokens=4, temperature=0.0, ignore_eos=True)
    assert [o.token_ids for o in outs] == [o.token_ids for o in outs_again]


def test_prompt_too_long_rejected(engine):
    with pytest.raises(ValueError):
        _collect(engine, "x" * 5000, max_tokens=4)


def test_stop_strings(engine):
    # byte tokenizer: every 1-byte token decodes to a char; pick a stop char
    # that greedy decode of this prompt actually emits, by first sampling freely
    outs = _collect(engine, "abc", max_tokens=6, temperature=0.0, ignore_eos=True)
    text = "".join(o.text_delta for o in outs)
    if len(text) >= 2:
        stop_char = text[1]
        outs2 = _collect(
            engine, "abc", max_tokens=6, temperature=0.0, ignore_eos=True, stop=[stop_char]
        )
        text2 = "".join(o.text_delta for o in outs2)
        assert stop_char not in text2
        assert outs2[-1].finish_reason in ("stop", "length")


@pytest.mark.slow
class TestHTTPServer:
    @pytest.fixture(scope="class")
    def server(self):
        port = free_port()
        proc = start_proc(
            [
                "-m", "production_stack_tpu.engine.api_server",
                "--model", "llama-debug", "--port", str(port),
                "--max-model-len", "256", "--num-pages", "64", "--page-size", "8",
                "--enable-sleep-mode",
            ]
        )
        base = f"http://127.0.0.1:{port}"
        try:
            wait_healthy(f"{base}/health", proc)
            yield base
        finally:
            out = stop_proc(proc)
            print(out[-2000:])

    def test_models(self, server):
        r = requests.get(f"{server}/v1/models").json()
        assert r["data"][0]["id"] == "llama-debug"

    def test_chat_nonstream(self, server):
        r = requests.post(
            f"{server}/v1/chat/completions",
            json={
                "model": "llama-debug",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 8, "temperature": 0, "ignore_eos": True,
            },
            headers={"X-Request-Id": "test-123"},
        )
        assert r.status_code == 200
        assert r.headers.get("X-Request-Id") == "test-123"
        body = r.json()
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 8

    def test_chat_stream(self, server):
        r = requests.post(
            f"{server}/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 6, "temperature": 0, "ignore_eos": True, "stream": True,
            },
            stream=True,
        )
        assert r.status_code == 200
        chunks = []
        for line in r.iter_lines():
            if line.startswith(b"data: "):
                payload = line[6:]
                if payload == b"[DONE]":
                    chunks.append("DONE")
                else:
                    chunks.append(json.loads(payload))
        assert chunks[-1] == "DONE"
        assert any(
            c != "DONE" and c.get("usage", {}).get("completion_tokens") == 6 for c in chunks
        )

    def test_completions(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "once upon", "max_tokens": 5, "temperature": 0, "ignore_eos": True},
        )
        assert r.status_code == 200
        assert r.json()["usage"]["completion_tokens"] == 5

    def test_tokenize_detokenize(self, server):
        toks = requests.post(f"{server}/tokenize", json={"prompt": "hello"}).json()
        assert toks["count"] == len(toks["tokens"]) > 0
        text = requests.post(
            f"{server}/detokenize", json={"tokens": toks["tokens"]}
        ).json()["prompt"]
        assert "hello" in text

    def test_metrics(self, server):
        text = requests.get(f"{server}/metrics").text
        assert 'vllm:num_requests_running{model_name="llama-debug"}' in text
        assert "vllm:generation_tokens_total" in text

    def test_stats_and_metrics_name_the_device_and_attention_paths(self, server):
        s = requests.get(f"{server}/stats").json()
        assert s["platform"] == "cpu" and s["device_kind"] and s["device_count"] >= 1
        assert s["attn_impl_requested"] == "auto"
        assert s["attn_impl_prefill"] == s["attn_impl_decode"] == "xla"
        assert s["attn_impl_reason"] == "no TPU backend (platform=cpu)"
        assert s["engine_step_errors_total"] == 0 and s["engine_program_fault"] == ""
        text = requests.get(f"{server}/metrics").text
        assert 'vllm:device_info{model_name="llama-debug",platform="cpu"' in text
        assert 'attn_impl_decode="xla"} 1' in text
        assert 'vllm:engine_step_errors_total{model_name="llama-debug"} 0' in text

    def test_n_parallel_sampling_nonstream(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "choices", "max_tokens": 4, "temperature": 0.9,
                  "n": 3, "ignore_eos": True},
        )
        assert r.status_code == 200
        body = r.json()
        assert [c["index"] for c in body["choices"]] == [0, 1, 2]
        assert all(c["finish_reason"] == "length" for c in body["choices"])
        assert body["usage"]["completion_tokens"] == 12  # summed over choices
        assert body["usage"]["total_tokens"] == body["usage"]["prompt_tokens"] + 12

    def test_n_parallel_sampling_stream(self, server):
        r = requests.post(
            f"{server}/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 3, "temperature": 0.8, "n": 2,
                  "ignore_eos": True, "stream": True},
            stream=True,
        )
        assert r.status_code == 200
        seen = {0: 0, 1: 0}
        finish = {}
        import json as json_mod
        for line in r.iter_lines():
            if not line.startswith(b"data:") or b"[DONE]" in line:
                continue
            chunk = json_mod.loads(line[5:])
            for c in chunk.get("choices", []):
                i = c["index"]
                if "delta" in c:
                    seen[i] += 1
                if c.get("finish_reason"):
                    finish[i] = c["finish_reason"]
        assert finish == {0: "length", 1: "length"}
        # every choice streams its role chunk plus per-output chunks. Count
        # chunks, not printable text: random-weight byte-tokenizer sampling
        # can legitimately produce 3 tokens that all decode to empty text,
        # which made a content-based assertion flaky.
        assert seen[0] >= 2 and seen[1] >= 2

    def test_n_rejects_bad_values(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "n": 0},
        )
        assert r.status_code == 400
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "n": 2, "best_of": 3},
        )
        assert r.status_code == 400

    def test_completion_logprobs(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "lp test", "max_tokens": 4, "temperature": 0,
                  "logprobs": 3, "ignore_eos": True},
        )
        assert r.status_code == 200
        c = r.json()["choices"][0]
        lp = c["logprobs"]
        assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 4
        assert all(isinstance(x, float) and x <= 0 for x in lp["token_logprobs"])
        assert all(len(d) <= 3 for d in lp["top_logprobs"])
        # greedy: the chosen token is the argmax, so its logprob equals the
        # best top-logprob
        for chosen, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
            assert abs(chosen - max(top.values())) < 1e-5
        assert lp["text_offset"][0] == 0
        assert lp["text_offset"] == sorted(lp["text_offset"])

    def test_chat_logprobs_stream(self, server):
        import json as json_mod
        r = requests.post(
            f"{server}/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 3, "temperature": 0.5, "logprobs": True,
                  "top_logprobs": 2, "ignore_eos": True, "stream": True},
            stream=True,
        )
        assert r.status_code == 200
        entries = []
        for line in r.iter_lines():
            if not line.startswith(b"data:") or b"[DONE]" in line:
                continue
            chunk = json_mod.loads(line[5:])
            for c in chunk.get("choices", []):
                if c.get("logprobs"):
                    entries.extend(c["logprobs"]["content"])
        assert len(entries) == 3
        for e in entries:
            assert e["logprob"] <= 0
            assert len(e["top_logprobs"]) == 2
            assert isinstance(e["bytes"], list)

    def test_logprobs_rejected_out_of_range(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "logprobs": 50},
        )
        assert r.status_code == 400

    def test_n_siblings_share_prompt_kv(self, server):
        """Parallel-sampling siblings launch after choice 0's prefill and
        hit the prefix cache on the shared prompt (registered at prefill
        completion, not at finish)."""
        before = requests.get(f"{server}/metrics").text
        def hits(text):
            for line in text.splitlines():
                if line.startswith("vllm:gpu_prefix_cache_hits_total"):
                    return float(line.rsplit(" ", 1)[1])
            return 0.0
        prompt = "share this prompt kv " * 4  # >> one page (8 tokens)
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": prompt, "max_tokens": 3, "temperature": 0.8,
                  "n": 3, "ignore_eos": True},
        )
        assert r.status_code == 200
        after = requests.get(f"{server}/metrics").text
        assert hits(after) > hits(before)

    def test_penalties_accepted_and_plumbed(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "penalty run", "max_tokens": 6, "temperature": 0,
                  "frequency_penalty": 2.0, "presence_penalty": 1.0,
                  "repetition_penalty": 1.3, "ignore_eos": True},
        )
        assert r.status_code == 200
        assert r.json()["usage"]["completion_tokens"] == 6

    def test_penalties_rejected_out_of_range(self, server):
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "presence_penalty": 3.0},
        )
        assert r.status_code == 400
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "repetition_penalty": 0},
        )
        assert r.status_code == 400

    def test_sleep_wake(self, server):
        assert requests.get(f"{server}/is_sleeping").json()["is_sleeping"] is False
        assert requests.post(f"{server}/sleep?level=1").status_code == 200
        assert requests.get(f"{server}/is_sleeping").json()["is_sleeping"] is True
        r = requests.post(
            f"{server}/v1/completions", json={"prompt": "x", "max_tokens": 2}
        )
        assert r.status_code == 503
        assert requests.post(f"{server}/wake_up").status_code == 200
        assert requests.get(f"{server}/is_sleeping").json()["is_sleeping"] is False
        r = requests.post(
            f"{server}/v1/completions",
            json={"prompt": "x", "max_tokens": 2, "ignore_eos": True},
        )
        assert r.status_code == 200 and r.json()["usage"]["completion_tokens"] == 2

    def test_sleep_level2_restores_params_exactly(self, server):
        """Level 2 offloads the weights to host RAM; after wake the SAME
        greedy continuation must come back — a corrupted restore would
        serve plausible-looking garbage."""
        body = {"prompt": "weights roundtrip", "max_tokens": 6,
                "temperature": 0.0, "ignore_eos": True}
        before = requests.post(f"{server}/v1/completions", json=body).json()
        assert requests.post(f"{server}/sleep?level=2").status_code == 200
        assert requests.get(f"{server}/is_sleeping").json()["is_sleeping"] is True
        assert requests.post(f"{server}/wake_up").status_code == 200
        after = requests.post(f"{server}/v1/completions", json=body).json()
        assert after["choices"][0]["text"] == before["choices"][0]["text"]


def test_logit_bias_forces_and_bans_tokens(engine):
    """OpenAI logit_bias: +100 on one token makes greedy pick it every step;
    -100 bans the otherwise-greedy token."""
    base = _collect(engine, "bias me", max_tokens=4, temperature=0.0,
                    ignore_eos=True)
    base_toks = [t for o in base for t in o.token_ids]

    forced = _collect(engine, "bias me", max_tokens=4, temperature=0.0,
                      ignore_eos=True, logit_bias={123: 100.0})
    assert [t for o in forced for t in o.token_ids] == [123] * 4

    banned = _collect(engine, "bias me", max_tokens=4, temperature=0.0,
                      ignore_eos=True, logit_bias={base_toks[0]: -100.0})
    banned_toks = [t for o in banned for t in o.token_ids]
    assert banned_toks[0] != base_toks[0]


def test_min_tokens_suppresses_eos(engine):
    """With EOS forced via logit_bias, min_tokens MASKS EOS from the
    distribution until the floor (vLLM semantics — an EOS must never be
    sampled into the context early), then EOS finishes the sequence. The
    mask is per-dispatch, so the floor may round up to a burst boundary."""
    eos = engine.tokenizer.eos_token_id
    outs = _collect(engine, "stop early", max_tokens=32, temperature=0.0,
                    logit_bias={eos: 100.0}, min_tokens=5)
    last = outs[-1]
    assert last.finished and last.finish_reason == "stop"
    toks = [t for o in outs for t in o.token_ids]
    assert 5 <= len(toks) <= 32
    assert toks[-1] == eos          # the forced EOS lands once allowed
    assert eos not in toks[:4]      # and NEVER below the floor


def test_first_dispatch_failure_is_a_program_build_error():
    """A shape that fails the first time it is dispatched (trace, lowering,
    compile) is not a per-batch fault; one that has run before is."""
    from production_stack_tpu.engine.runner import ModelRunner, ProgramBuildError
    from production_stack_tpu.models import llama

    r = ModelRunner(llama.PRESETS["llama-debug"], num_pages=8, page_size=8)
    staged = {"input_ids": np.zeros((1, 4)), "page_table": np.zeros((1, 2))}

    def refuse(*_a):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(ProgramBuildError, match="Mosaic failed to compile"):
        r._dispatch(refuse, "step", (False, False), staged, ())
    assert r._dispatch(lambda: 7, "step", (True, False), staged, ()) == 7
    with pytest.raises(RuntimeError) as e:  # ran before: the original error
        r._dispatch(refuse, "step", (True, False), staged, ())
    assert not isinstance(e.value, ProgramBuildError)


def test_program_build_failure_takes_the_engine_out_of_rotation():
    """The engine must not stay green serving errors: the request finishes
    with 'error', the step is counted, and /health answers 503 from then
    on, naming the program."""
    from production_stack_tpu.engine.api_server import EngineServer

    eng = LLMEngine(_cfg())

    def refuse(*_a, **_k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    eng.runner._get_step = lambda *_a: refuse
    eng.start()
    try:
        outs = _collect(eng, "hello there", max_tokens=4)
        assert outs[-1].finished and outs[-1].finish_reason == "error"
        s = eng.stats()
        assert s["engine_step_errors_total"] >= 1
        assert "Mosaic failed to compile" in s["engine_program_fault"]
        resp = asyncio.run(EngineServer(eng.cfg, eng).health(None))
        assert resp.status == 503 and "program fault" in resp.text
    finally:
        eng.stop()
