"""Serving-layer tests: paged KV correctness, continuous batching, HTTP API.

The key test is greedy equivalence: prefill+paged-decode must produce the
same tokens as running the dense training-side forward step by step —
proving the paged cache path and the model share numerics (the reference
has no such test; its KV cache was dead code, SURVEY §2.4.2).
"""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import ServeConfig
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    InferenceServer,
    Request,
    RequestState,
    SamplingParams,
)


@pytest.fixture(scope="module")
def model_cfg():
    return get_model_config("gpt-test")


def make_engine(model_cfg, **overrides) -> InferenceEngine:
    kw = dict(model="gpt-test", max_batch_size=4, max_seq_len=128,
              prefill_chunk=32, kv_block_size=8, dtype="float32")
    kw.update(overrides)
    return InferenceEngine(model_cfg, ServeConfig(**kw), seed=0)


def greedy_reference(params, cfg, prompt, n_new):
    """Dense-forward greedy decoding, recompute-from-scratch every step."""
    tokens = list(prompt)
    for _ in range(n_new):
        logits = gpt.forward(params, jnp.asarray([tokens], jnp.int32), cfg)
        tokens.append(int(jnp.argmax(logits[0, -1])))
    return tokens[len(prompt):]


class TestPagedDecodeCorrectness:
    def test_greedy_matches_dense_forward(self, model_cfg):
        eng = make_engine(model_cfg)
        prompt = [5, 17, 99, 3, 42, 7, 23]
        n_new = 12
        [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                      max_tokens=n_new))
        expected = greedy_reference(eng.params, model_cfg, prompt, n_new)
        assert req.generated_tokens == expected

    def test_greedy_matches_with_concurrent_requests(self, model_cfg):
        """Multiple resident sequences must not corrupt each other's KV."""
        eng = make_engine(model_cfg)
        prompts = [[5, 17, 99], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11],
                   [200, 100], [42] * 20]
        reqs = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                    max_tokens=8))
        for prompt, req in zip(prompts, reqs):
            assert req.generated_tokens == greedy_reference(
                eng.params, model_cfg, prompt, 8), f"prompt {prompt}"

    def test_long_prompt_multiple_pages(self, model_cfg):
        eng = make_engine(model_cfg, kv_block_size=8, prefill_chunk=16)
        prompt = list(np.random.default_rng(0).integers(1, 250, size=50))
        prompt = [int(x) for x in prompt]
        [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                      max_tokens=6))
        assert req.generated_tokens == greedy_reference(
            eng.params, model_cfg, prompt, 6)


class TestContinuousBatching:
    def test_requests_join_and_leave_running_batch(self, model_cfg):
        """Requests with different lengths finish at different steps while
        the batch keeps running — the defect the reference never fixed
        (SURVEY §2.4.1: one token then hang)."""
        eng = make_engine(model_cfg)
        r_short = Request("short", [1, 2, 3],
                          SamplingParams(temperature=0.0, max_tokens=2))
        r_long = Request("long", [4, 5, 6],
                         SamplingParams(temperature=0.0, max_tokens=10))
        assert eng.scheduler.add_request(r_short)
        assert eng.scheduler.add_request(r_long)
        eng.run_until_idle()
        assert r_short.state is RequestState.FINISHED
        assert r_long.state is RequestState.FINISHED
        assert len(r_short.generated_tokens) == 2
        assert len(r_long.generated_tokens) == 10
        assert r_short.finish_reason == "length"

    def test_queue_overflow_rejected(self, model_cfg):
        eng = make_engine(model_cfg, max_queue=2)
        ok = [eng.scheduler.add_request(
            Request(f"r{i}", [1, 2], SamplingParams(max_tokens=1)))
            for i in range(4)]
        assert ok == [True, True, False, False]

    def test_too_long_request_fails_cleanly(self, model_cfg):
        eng = make_engine(model_cfg, max_seq_len=64)
        r = Request("big", [1] * 60, SamplingParams(max_tokens=20))
        assert not eng.scheduler.add_request(r)
        assert r.state is RequestState.FAILED
        assert "exceeds" in r.error

    def test_kv_pages_released_after_finish(self, model_cfg):
        eng = make_engine(model_cfg)
        free0 = eng.kv.free_pages
        eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_tokens=5,
                                                       temperature=0.0))
        assert eng.kv.free_pages == free0

    def test_seeded_sampling_deterministic(self, model_cfg):
        eng = make_engine(model_cfg)
        s = SamplingParams(temperature=0.9, top_k=50, top_p=0.95,
                           max_tokens=8, seed=1234)
        [a] = eng.generate([[7, 8, 9]], s)
        [b] = eng.generate([[7, 8, 9]], s)
        assert a.generated_tokens == b.generated_tokens

    @pytest.mark.parametrize("ignore_eos", [False, True])
    def test_ignore_eos_runs_a_reply_to_max_tokens(self, model_cfg,
                                                   ignore_eos):
        """The request field ``ignore_eos``: the engine's EOS ends a reply
        ("stop") unless the request asked to run to ``max_tokens``; a
        ``stop_token_ids`` hit ends it either way."""
        eng = make_engine(model_cfg)
        greedy = SamplingParams(temperature=0.0, max_tokens=6)
        [plain] = eng.generate([[1, 2, 3]], greedy)
        eng.eos_token_id = plain.generated_tokens[2]
        first = plain.generated_tokens.index(eng.eos_token_id)
        [r] = eng.generate([[1, 2, 3]], SamplingParams(
            temperature=0.0, max_tokens=6, ignore_eos=ignore_eos))
        if ignore_eos:
            assert r.generated_tokens == plain.generated_tokens
            assert r.finish_reason == "length"
        else:
            assert r.generated_tokens == plain.generated_tokens[:first + 1]
            assert r.finish_reason == "stop"
        [s] = eng.generate([[1, 2, 3]], SamplingParams(
            temperature=0.0, max_tokens=6, ignore_eos=ignore_eos,
            stop_token_ids=(plain.generated_tokens[0],)))
        assert s.generated_tokens == plain.generated_tokens[:1]

    def test_ignore_eos_is_parsed_and_goes_over_the_fleet_wire(self):
        from distributed_llm_training_and_inference_system_tpu.serve.fleet \
            import remote
        from distributed_llm_training_and_inference_system_tpu.serve.server \
            import BadRequest, parse_completion_body
        body = {"prompt": [1, 2], "max_tokens": 4}
        assert not parse_completion_body(body, None, 512)[1].ignore_eos
        s = parse_completion_body(dict(body, ignore_eos=True), None, 512)[1]
        assert s.ignore_eos
        assert remote.sampling_from_wire(remote.sampling_to_wire(s)) == s
        with pytest.raises(BadRequest, match="ignore_eos"):
            parse_completion_body(dict(body, ignore_eos="yes"), None, 512)

    def test_static_scheduler_mode(self, model_cfg):
        eng = make_engine(model_cfg, scheduler="static")
        reqs = eng.generate([[1, 2], [3, 4], [5, 6]],
                            SamplingParams(temperature=0.0, max_tokens=3))
        assert all(r.state is RequestState.FINISHED for r in reqs)


class TestHTTPServer:
    @pytest.fixture()
    def server(self, model_cfg):
        srv = InferenceServer(model_cfg, ServeConfig(
            model="gpt-test", max_batch_size=4, max_seq_len=128,
            prefill_chunk=32, kv_block_size=8, dtype="float32",
            host="127.0.0.1", port=0))
        loop = asyncio.new_event_loop()
        started = threading.Event()
        state = {}

        def run():
            asyncio.set_event_loop(loop)

            async def main():
                runner = await srv.start_async()
                # discover the bound port (port=0 = ephemeral)
                state["port"] = runner.addresses[0][1]
                state["runner"] = runner
                started.set()

            loop.run_until_complete(main())
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(timeout=30)
        yield srv, state["port"]
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        srv.stop_engine()

    def test_completions_models_health(self, server):
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"

        r = rq.get(f"{base}/v1/models", timeout=10)
        assert r.status_code == 200
        assert r.json()["data"][0]["id"] == "gpt-test"

        r = rq.post(f"{base}/v1/completions", json={
            "prompt": [1, 2, 3, 4], "max_tokens": 5, "temperature": 0.0,
        }, timeout=60)
        assert r.status_code == 200
        body = r.json()
        assert body["object"] == "text_completion"
        assert len(body["choices"][0]["token_ids"]) == 5
        assert body["usage"]["completion_tokens"] == 5
        assert body["metrics"]["ttft_ms"] is not None

        r = rq.get(f"{base}/health", timeout=10)
        assert r.status_code == 200
        assert r.json()["status"] == "healthy"
        assert r.json()["engine"]["finished"] >= 1

    def test_cors_preflight_and_headers(self, server):
        """Browser cross-origin parity (reference serve/server.py:276-282):
        preflight OPTIONS answers 204 with allow headers; responses carry
        Access-Control-Allow-Origin."""
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"

        r = rq.options(f"{base}/v1/completions", headers={
            "Origin": "http://example.com",
            "Access-Control-Request-Method": "POST",
            "Access-Control-Request-Headers": "content-type",
        }, timeout=10)
        assert r.status_code == 204
        # wildcard mode: literal "*" and NO Allow-Credentials (reflecting
        # the origin while asserting credentials would be a credentialed-
        # wildcard misconfiguration, more permissive than the reference)
        assert r.headers["Access-Control-Allow-Origin"] == "*"
        assert "Access-Control-Allow-Credentials" not in r.headers
        assert "POST" in r.headers["Access-Control-Allow-Methods"]
        assert r.headers["Access-Control-Allow-Headers"] == "content-type"

        r = rq.get(f"{base}/health",
                   headers={"Origin": "http://example.com"}, timeout=10)
        assert r.headers["Access-Control-Allow-Origin"] == "*"

        # SSE streams: headers go out at prepare() — CORS must be on the
        # stream response itself, not added post-handler
        r = rq.post(f"{base}/v1/completions", json={
            "prompt": [1, 2, 3], "max_tokens": 2, "temperature": 0.0,
            "stream": True,
        }, headers={"Origin": "http://example.com"}, stream=True,
            timeout=60)
        assert r.headers["Content-Type"].startswith("text/event-stream")
        assert r.headers["Access-Control-Allow-Origin"] == "*"
        r.close()

    def test_cors_explicit_origin_list(self):
        """Explicit origin lists: reflect only listed origins, assert
        credentials; unlisted origins get nothing."""
        from types import SimpleNamespace
        from distributed_llm_training_and_inference_system_tpu.serve.server import (
            InferenceServer)
        fake = SimpleNamespace(serve_cfg=SimpleNamespace(
            cors_origins="http://a.com, http://b.com"))

        def req(origin):
            return SimpleNamespace(headers={"Origin": origin})

        h = InferenceServer._cors_headers(fake, req("http://a.com"))
        assert h["Access-Control-Allow-Origin"] == "http://a.com"
        assert h["Access-Control-Allow-Credentials"] == "true"
        # responses vary by Origin — without this a shared cache could
        # serve one origin's grant (or a denial) to a different origin,
        # so even DENIED origins must carry Vary (and nothing else)
        assert "Origin" in h["Vary"]
        denied = InferenceServer._cors_headers(fake, req("http://evil.com"))
        assert "Access-Control-Allow-Origin" not in denied
        assert "Access-Control-Allow-Credentials" not in denied
        assert "Origin" in denied["Vary"]
        fake.serve_cfg.cors_origins = ""
        assert InferenceServer._cors_headers(fake, req("http://a.com")) == {}

    def test_text_prompt_roundtrip(self, server):
        import requests as rq
        srv, port = server
        r = rq.post(f"http://127.0.0.1:{port}/v1/completions", json={
            "prompt": "hello", "max_tokens": 3, "temperature": 0.0,
        }, timeout=60)
        assert r.status_code == 200
        assert isinstance(r.json()["choices"][0]["text"], str)

    def test_streaming_completions(self, server):
        """`stream: true` emits OpenAI-style SSE chunks ending in [DONE];
        concatenated chunk texts equal the non-streaming completion (greedy
        is deterministic), and the final chunk carries finish_reason."""
        import json as _json

        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"
        ref = rq.post(f"{base}/v1/completions", json={
            "prompt": [5, 17, 99], "max_tokens": 6, "temperature": 0.0,
        }, timeout=60).json()

        r = rq.post(f"{base}/v1/completions", json={
            "prompt": [5, 17, 99], "max_tokens": 6, "temperature": 0.0,
            "stream": True,
        }, stream=True, timeout=60)
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        texts, finish = [], None
        saw_done = False
        for line in r.iter_lines():
            if not line:
                continue
            payload = line.decode().removeprefix("data: ")
            if payload == "[DONE]":
                saw_done = True
                break
            obj = _json.loads(payload)
            choice = obj["choices"][0]
            texts.append(choice["text"])
            if choice["finish_reason"]:
                finish = choice["finish_reason"]
        assert saw_done
        assert finish == "length"
        assert "".join(texts) == ref["choices"][0]["text"]

    def test_bad_request(self, server):
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"
        r = rq.post(f"{base}/v1/completions",
                    json={"prompt": "", "max_tokens": 3}, timeout=10)
        assert r.status_code == 400
        # max_tokens < 1 is invalid, not "generate one token anyway"
        r = rq.post(f"{base}/v1/completions",
                    json={"prompt": [1, 2], "max_tokens": 0}, timeout=10)
        assert r.status_code == 400
        # out-of-vocab token ids must 400, not clamp silently
        r = rq.post(f"{base}/v1/completions",
                    json={"prompt": [1, 10**9], "max_tokens": 3}, timeout=10)
        assert r.status_code == 400
        assert "token id" in r.json()["error"]
        # non-integer seed would raise inside the engine thread
        r = rq.post(f"{base}/v1/completions",
                    json={"prompt": [1, 2], "max_tokens": 3, "seed": "x"},
                    timeout=10)
        assert r.status_code == 400
        assert "seed" in r.json()["error"]

    def test_engine_crash_returns_500_and_degrades_health(self, server):
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"

        def boom():
            raise RuntimeError("device exploded")
        # Pin recover() to failure: with a warm compile cache (earlier tests
        # in the same process) the real recover() probe succeeds and clears
        # _engine_error before our /health GET, flipping 503→200
        # nondeterministically (ADVICE r2). This test asserts the degraded
        # path; the success path is test_engine_recovery_clears_degraded.
        orig_step, orig_recover = srv.engine.step, srv.engine.recover
        srv.engine.step = boom
        srv.engine.recover = lambda: False
        try:
            r = rq.post(f"{base}/v1/completions", json={
                "prompt": [1, 2, 3], "max_tokens": 5}, timeout=30)
            assert r.status_code == 500
            assert "device exploded" in r.json()["error"]
            h = rq.get(f"{base}/health", timeout=10)
            assert h.status_code == 503
            assert h.json()["status"] == "degraded"
            assert "device exploded" in h.json()["last_engine_error"]
            assert h.json()["engine_error_count"] >= 1
        finally:
            srv.engine.step = orig_step
            srv.engine.recover = orig_recover

    def test_engine_recovery_clears_degraded(self, server):
        """recover() success must clear the degraded flag (server.py path:
        crash → fail_all → recover()==True → _engine_error=None → 200)."""
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"

        def boom():
            raise RuntimeError("transient device loss")
        orig_step, orig_recover = srv.engine.step, srv.engine.recover
        srv.engine.step = boom
        srv.engine.recover = lambda: True   # deterministic success
        try:
            r = rq.post(f"{base}/v1/completions", json={
                "prompt": [1, 2, 3], "max_tokens": 5}, timeout=30)
            assert r.status_code == 500     # the in-flight request still fails
            # fail_all answers the request BEFORE the engine thread runs
            # recover(): wait for the flag to clear, not for the clock
            deadline = time.monotonic() + 10.0
            h = rq.get(f"{base}/health", timeout=10)
            while h.status_code != 200 and time.monotonic() < deadline:
                time.sleep(0.02)
                h = rq.get(f"{base}/health", timeout=10)
            assert h.status_code == 200
            assert h.json()["last_engine_error"] is None
        finally:
            srv.engine.step = orig_step
            srv.engine.recover = orig_recover
        # and the server still serves real requests afterwards
        r = rq.post(f"{base}/v1/completions", json={
            "prompt": [1, 2, 3], "max_tokens": 2}, timeout=30)
        assert r.status_code == 200

    def test_compile_failure_stays_degraded(self, server):
        """A program that fails its FIRST call failed to compile (on the
        chip: Mosaic refusing a kernel). recover()'s device probe cannot
        see that and the same shape fails again, so the engine must not be
        reported healthy — even after other shapes serve fine."""
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"
        eng = srv.engine

        def refuse(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")
        from distributed_llm_training_and_inference_system_tpu.serve import (
            engine as engine_mod)
        # one prefill bucket covers every short prompt: a prompt longer
        # than it needs a program this engine has not compiled yet
        fresh = eng._bucket(1) + 1
        bucket = eng._bucket(fresh)
        assert bucket not in eng._prefill_cache
        eng._prefill_cache[bucket] = engine_mod._Program(
            f"prefill {bucket}", refuse, eng.failed_programs)
        try:
            r = rq.post(f"{base}/v1/completions", json={
                "prompt": [7] * fresh, "max_tokens": 2}, timeout=60)
            assert r.status_code == 500
            assert f"prefill {bucket}" in eng.failed_programs
            assert not eng.recover()
            # a shape that compiles still serves ...
            r = rq.post(f"{base}/v1/completions", json={
                "prompt": [1, 2, 3], "max_tokens": 2}, timeout=60)
            assert r.status_code == 200
            # ... and /health still names the failure
            h = rq.get(f"{base}/health", timeout=10)
            assert h.status_code == 503
            assert "scoped vmem" in h.json()["last_engine_error"]
            assert h.json()["engine_error_count"] >= 1
        finally:
            eng.failed_programs.clear()
            del eng._prefill_cache[bucket]
            srv._engine_error = None

    def test_transient_first_call_failure_clears(self, server):
        """A program whose first call failed for a passing reason (HBM
        full while other slots held it) and whose next call runs is not a
        compile failure: /health returns to ok with that call."""
        import requests as rq
        srv, port = server
        base = f"http://127.0.0.1:{port}"
        eng = srv.engine
        from distributed_llm_training_and_inference_system_tpu.serve import (
            engine as engine_mod)
        fresh = eng._bucket(1) + 1
        bucket = eng._bucket(fresh)
        cached = eng._prefill_cache.pop(bucket, None)
        real = eng._prefill_fn(bucket)._fn
        calls = []

        def once(*a):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: hbm")
            return real(*a)
        eng._prefill_cache[bucket] = engine_mod._Program(
            f"prefill {bucket}", once, eng.failed_programs)
        body = {"prompt": [7] * fresh, "max_tokens": 2}
        try:
            r = rq.post(f"{base}/v1/completions", json=body, timeout=60)
            assert r.status_code == 500
            assert f"prefill {bucket}" in eng.failed_programs
            assert rq.get(f"{base}/health", timeout=10).status_code == 503
            r = rq.post(f"{base}/v1/completions", json=body, timeout=60)
            assert r.status_code == 200
            assert not eng.failed_programs
            assert eng.recover()
            h = rq.get(f"{base}/health", timeout=10)
            assert h.status_code == 200
            assert h.json()["last_engine_error"] is None
        finally:
            eng.failed_programs.clear()
            if cached is None:
                del eng._prefill_cache[bucket]
            else:
                eng._prefill_cache[bucket] = cached
            srv._engine_error = None


class TestReviewRegressions:
    def test_top_p_zero_is_greedy(self, model_cfg):
        """top_p=0 must degrade to greedy, not mask every token to id 0."""
        eng = make_engine(model_cfg)
        [req] = eng.generate([[5, 17, 99]], SamplingParams(
            temperature=0.8, top_p=0.0, max_tokens=6, seed=7))
        expected = greedy_reference(eng.params, model_cfg, [5, 17, 99], 6)
        assert req.generated_tokens == expected

    def test_kv_oversized_request_rejected_not_wedged(self, model_cfg):
        """A request that could never fit the cache must fail fast instead of
        head-of-line-blocking the queue forever."""
        eng = make_engine(model_cfg, kv_block_size=8, kv_num_blocks=4,
                          max_seq_len=128)
        big = Request("big", [1] * 20, SamplingParams(max_tokens=20))
        assert not eng.scheduler.add_request(big)
        assert big.state is RequestState.FAILED
        assert "capacity" in big.error
        # a small request behind it still runs fine
        [ok] = eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                                        max_tokens=2))
        assert ok.state is RequestState.FINISHED

    def test_negative_top_k_means_disabled_not_greedy(self, model_cfg):
        """top_k=-1 is the reference's 'disabled' convention; clipping it to
        1 silently turned sampling into argmax (ADVICE r1)."""
        eng = make_engine(model_cfg)
        s = dict(temperature=0.9, top_p=1.0, max_tokens=8, seed=123)
        [neg] = eng.generate([[7, 8, 9]], SamplingParams(top_k=-1, **s))
        [zero] = eng.generate([[7, 8, 9]], SamplingParams(top_k=0, **s))
        [one] = eng.generate([[7, 8, 9]], SamplingParams(top_k=1, **s))
        assert neg.generated_tokens == zero.generated_tokens
        greedy = greedy_reference(eng.params, model_cfg, [7, 8, 9], 8)
        assert one.generated_tokens == greedy  # top_k=1 IS greedy
        assert neg.generated_tokens != greedy  # -1 must not be

    def test_cancel_during_prefill_releases_slot(self, model_cfg):
        """Cancel of a PREFILLING request is deferred to the next step
        boundary instead of leaking the slot + KV pages (ADVICE r1)."""
        eng = make_engine(model_cfg)
        free0 = eng.kv.free_pages
        r = Request("c1", [1, 2, 3], SamplingParams(temperature=0.0,
                                                    max_tokens=5))
        assert eng.scheduler.add_request(r)
        [admitted] = eng.scheduler.admit()
        assert admitted.state is RequestState.PREFILLING
        assert eng.scheduler.cancel("c1")       # cancel-pending, not False
        assert r.cancel_requested
        eng._finish_prefill(*eng._prefill(r))
        eng.scheduler.step_finished(eng.eos_token_id)
        assert r.state is RequestState.CANCELLED
        assert eng.scheduler.active_count == 0
        assert eng.kv.free_pages == free0       # pages reclaimed

    def test_engine_failure_fails_requests_not_hangs(self, model_cfg):
        """A crashed engine step must FAIL in-flight requests (waiters fire)
        rather than leaving them hanging (ADVICE r1)."""
        eng = make_engine(model_cfg)
        r1 = Request("f1", [1, 2], SamplingParams(max_tokens=4))
        r2 = Request("f2", [3, 4], SamplingParams(max_tokens=4))
        assert eng.scheduler.add_request(r1)
        eng.scheduler.admit()
        eng._prefill(r1)                        # r1 resident
        assert eng.scheduler.add_request(r2)    # r2 queued
        notified = []
        eng.on_finish = lambda req: notified.append(req.request_id)
        eng.fail_all("RuntimeError: boom")
        assert r1.state is RequestState.FAILED
        assert r2.state is RequestState.FAILED
        assert "boom" in r1.error and "boom" in r2.error
        assert set(notified) >= {"f1", "f2"}
        assert eng.scheduler.active_count == 0 and eng.scheduler.queue_depth == 0

    def test_fail_before_prefill_returns_reservation(self, model_cfg):
        """A request admitted (pages reserved) but failed before its prefill
        must return its reservation — otherwise every crash permanently
        shrinks admissible KV capacity (code-review r2)."""
        eng = make_engine(model_cfg)
        r = Request("rsv", [1, 2, 3], SamplingParams(max_tokens=5))
        assert eng.scheduler.add_request(r)
        eng.scheduler.admit()                  # reserves pages, no prefill yet
        assert eng._reserved_pages > 0
        eng.fail_all("RuntimeError: boom")
        assert eng._reserved_pages == 0
        assert not eng._reserved_by
        # capacity is intact: a fresh request still runs
        [ok] = eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                                        max_tokens=2))
        assert ok.state is RequestState.FINISHED


class TestPrefillDecodeInterleaving:
    def test_long_prompt_burst_does_not_stall_resident_stream(self, model_cfg):
        """With a prefill token budget per step, a burst of long prompts is
        admitted across MULTIPLE engine steps, and the resident stream
        gains one token per step throughout (round-1 verdict weak #4 /
        next-round #9)."""
        eng = make_engine(model_cfg, max_batch_size=8,
                          prefill_budget_tokens=40,
                          decode_steps_per_dispatch=1)
        # resident stream first
        resident = Request(request_id="res", prompt_tokens=[5, 17, 99],
                           sampling=SamplingParams(temperature=0.0,
                                                   max_tokens=100))
        assert eng.scheduler.add_request(resident)
        eng.step()
        assert resident.state is RequestState.RUNNING

        # burst of 5 long prompts (40 tokens each; budget admits ~1/step).
        # They share no prefix: the budget charges a request the tokens the
        # prefix cache does NOT hold, and five copies of one prompt would
        # be admitted together behind the first (tests/test_latent.py)
        burst = [Request(request_id=f"b{i}",
                         prompt_tokens=list(range(1 + i, 41 + i)),
                         sampling=SamplingParams(temperature=0.0,
                                                 max_tokens=4))
                 for i in range(5)]
        for r in burst:
            assert eng.scheduler.add_request(r)

        admits_per_step = []
        for _ in range(6):
            before = eng.scheduler.total_admitted
            tokens_before = len(resident.generated_tokens)
            eng.step()
            admits_per_step.append(eng.scheduler.total_admitted - before)
            # the resident stream advanced THIS step — no multi-prefill stall
            assert len(resident.generated_tokens) == tokens_before + 1
        # the burst was spread over multiple steps, not swallowed in one
        assert max(admits_per_step) <= 2
        assert sum(admits_per_step) >= 4
        # a request the budget stopped after its pages were promised is
        # promised them once, not again at every step
        eng.run_until_idle()
        assert eng._reserved_pages == 0

    def test_padded_slot_accounting(self, model_cfg):
        eng = make_engine(model_cfg, max_batch_size=4)
        [req] = eng.generate([[5, 17, 99]],
                             SamplingParams(temperature=0.0, max_tokens=5))
        stats = eng.stats()
        assert stats["padded_slot_steps"] > 0          # 3 idle slots/step
        assert 0.0 < stats["decode_slot_utilization"] < 1.0


class TestMultiStepDecode:
    def test_multi_step_matches_single_step(self, model_cfg):
        """K decode iterations fused into one dispatch must generate exactly
        the same tokens as the host-driven single-step loop — greedy AND
        sampled (the per-position key folding is identical)."""
        prompts = [[5, 17, 99, 3], [42, 7], [23, 1, 2, 3, 4, 5]]
        for sampling in (SamplingParams(temperature=0.0, max_tokens=11),
                         SamplingParams(temperature=0.9, top_k=40,
                                        max_tokens=11, seed=7)):
            eng1 = make_engine(model_cfg, decode_steps_per_dispatch=1)
            engK = make_engine(model_cfg, decode_steps_per_dispatch=4)
            out1 = [r.generated_tokens for r in eng1.generate(prompts, sampling)]
            outK = [r.generated_tokens for r in engK.generate(prompts, sampling)]
            assert out1 == outK

    def test_multi_step_respects_max_tokens_and_pages(self, model_cfg):
        """max_tokens not divisible by K: the request stops at exactly
        max_tokens and its pages are all reclaimed (overshoot iterations
        wrote only scratch/reserved pages)."""
        eng = make_engine(model_cfg, decode_steps_per_dispatch=8)
        free0 = eng.kv.free_pages
        [req] = eng.generate([[5, 17, 99]],
                             SamplingParams(temperature=0.0, max_tokens=5))
        assert len(req.generated_tokens) == 5
        assert req.finish_reason == "length"
        assert eng.kv.free_pages == free0


class TestMoEServing:
    """Serving an MoE model: the decode/extend bodies route through
    moe_block (token-choice top-k experts, dropless since PR 27, as the
    training-side forward's default is) — greedy must match that forward
    exactly, like the dense-model tests above. Against an independent
    reference: tests/test_olmoe.py."""

    def test_moe_greedy_matches_dense(self):
        cfg = get_model_config("gpt-test-moe")
        eng = InferenceEngine(cfg, ServeConfig(
            model="gpt-test-moe", max_batch_size=2, max_seq_len=64,
            prefill_chunk=16, kv_block_size=8, dtype="float32"), seed=0)
        prompt = [5, 17, 99, 3, 42, 7, 23]
        [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                      max_tokens=8))
        assert req.generated_tokens == greedy_reference(
            eng.params, cfg, prompt, 8)

    def test_moe_with_speculation_and_chunked_prefill(self):
        cfg = get_model_config("gpt-test-moe")
        eng = InferenceEngine(cfg, ServeConfig(
            model="gpt-test-moe", max_batch_size=2, max_seq_len=64,
            prefill_chunk=16, kv_block_size=8, dtype="float32",
            speculative="ngram", speculative_tokens=4,
            chunked_prefill_tokens=8), seed=0)
        prompt = [7, 8, 9, 10] * 5
        [req] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                      max_tokens=6))
        assert req.generated_tokens == greedy_reference(
            eng.params, cfg, prompt, 6)


def test_engine_release_frees_and_next_engine_works(model_cfg):
    """Bench sweeps build engines back-to-back; release() must drop the dead
    engine's device buffers/programs so the next engine's pool allocation
    can't RESOURCE_EXHAUST (observed on the 4th engine of a round-3 TPU
    serve-load sweep)."""
    outputs = []
    prev = None
    for _ in range(3):
        if prev is not None:
            prev.release()
            assert prev.params is None and prev.kv is None
            assert prev._decode_jit is None and not prev._prefill_cache
        eng = make_engine(model_cfg)
        [req] = eng.generate([[5, 17, 99, 3]],
                             SamplingParams(temperature=0.0, max_tokens=4))
        outputs.append(req.generated_tokens)
        prev = eng
    assert outputs[0] == outputs[1] == outputs[2]


def test_latency_adaptive_dispatch_identical_and_engaged(model_cfg):
    """Splitting a decode dispatch must be BITWISE identical output (the
    scan runs the same per-step program), and the short program engages
    exactly when it can help: queued head + free slot + admissible pages
    (round-3: open-loop p99 device TTFT was bound by arrivals waiting out
    a full K-step dispatch)."""
    prompts = [[5, 17, 99, 3], [7, 23, 41, 2]]
    kw = dict(max_batch_size=1, decode_steps_per_dispatch=8)
    base = make_engine(model_cfg, latency_dispatch_steps=0, **kw)
    want = [r.generated_tokens for r in base.generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=24))]
    eng = make_engine(model_cfg, latency_dispatch_steps=2, **kw)
    got = [r.generated_tokens for r in eng.generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=24))]
    assert got == want

    # engagement probe (the synchronous generate() loop admits before
    # every dispatch, so the queued+admissible state only arises from
    # mid-dispatch arrivals — construct it directly)
    from distributed_llm_training_and_inference_system_tpu.serve import (
        Request)
    eng2 = make_engine(model_cfg, latency_dispatch_steps=2,
                       max_batch_size=2, decode_steps_per_dispatch=8)
    with eng2.lock:
        assert not eng2._short_dispatch_ok()        # empty queue
    r1 = Request(request_id="r1", prompt_tokens=[5, 6, 7, 8],
                 sampling=SamplingParams(temperature=0.0, max_tokens=8))
    assert eng2.scheduler.add_request(r1)
    with eng2.lock:
        # queued + free slot + pages available -> short dispatch
        assert eng2._short_dispatch_ok()
    # a pages-starved head must NOT shorten (paying extra round trips
    # cannot admit it at any boundary)
    eng3 = make_engine(model_cfg, latency_dispatch_steps=2,
                       max_batch_size=2, decode_steps_per_dispatch=8,
                       kv_block_size=8, kv_num_blocks=10,
                       admission="reserve")
    r_big_hold = Request(request_id="hold", prompt_tokens=[5, 17, 99, 3],
                         sampling=SamplingParams(temperature=0.0,
                                                 max_tokens=56))
    assert eng3.scheduler.add_request(r_big_hold)
    eng3.step()          # admit + prefill + first decode dispatch
    big = Request(request_id="big", prompt_tokens=list(range(2, 40)),
                  sampling=SamplingParams(temperature=0.0, max_tokens=30))
    assert eng3.scheduler.add_request(big)
    with eng3.lock:
        # hold reserves 8 of 9 usable pages; big needs 9 -> starved
        assert not eng3._short_dispatch_ok()

    # occupancy gate: near-full batches must NOT shorten even with a
    # queued admissible head (the queue-only guard measured -21%
    # saturation goodput, BASELINE.md battery 5) — pin the threshold
    eng4 = make_engine(model_cfg, latency_dispatch_steps=2,
                       max_batch_size=8, decode_steps_per_dispatch=8)
    for i in range(3):
        r = Request(request_id=f"occ{i}", prompt_tokens=[5 + i, 6, 7, 8],
                    sampling=SamplingParams(temperature=0.0, max_tokens=40))
        assert eng4.scheduler.add_request(r)
    eng4.step()                       # 3 residents decoding (cap is 2)
    q = Request(request_id="q", prompt_tokens=[9, 9, 9, 9],
                sampling=SamplingParams(temperature=0.0, max_tokens=4))
    assert eng4.scheduler.add_request(q)
    with eng4.lock:
        assert eng4.scheduler.active_count == 3
        assert not eng4._short_dispatch_ok()
    # and a single-slot engine never shortens while its slot is busy
    eng5 = make_engine(model_cfg, latency_dispatch_steps=2,
                       max_batch_size=1, decode_steps_per_dispatch=8)
    r = Request(request_id="solo", prompt_tokens=[5, 6, 7, 8],
                sampling=SamplingParams(temperature=0.0, max_tokens=40))
    assert eng5.scheduler.add_request(r)
    eng5.step()
    q2 = Request(request_id="q2", prompt_tokens=[9, 9, 9, 9],
                 sampling=SamplingParams(temperature=0.0, max_tokens=4))
    assert eng5.scheduler.add_request(q2)
    with eng5.lock:
        assert eng5.scheduler.active_count == 1
        assert not eng5._short_dispatch_ok()


def test_compiled_program_inventory(model_cfg):
    """stats()['compiled_programs'] tracks the resident executables per
    kind — the observable the battery-9 second-executable deficit
    investigation keyed on. Round 5 REMOVED the second decode
    executable (adaptive dispatch now chains units of one program), so
    decode_short must report 0 even with adaptivity configured."""
    eng = make_engine(model_cfg, latency_dispatch_steps=2)
    progs = eng.stats()["compiled_programs"]
    assert progs["decode"] == 1 and progs["decode_short"] == 0
    assert eng._decode_units == 4 and eng._decode_unit_len == 2
    before = progs["total"]
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=2, temperature=0.0))
    progs2 = eng.stats()["compiled_programs"]
    assert progs2["prefill_dense_buckets"] >= 1     # prefill compiled
    assert progs2["total"] > before
    eng.release()


def test_short_dispatch_fires_and_matches_plain(model_cfg):
    """Unit-chained adaptive decode (round 5: ONE compiled program;
    short dispatch = 1 unit, full dispatch = K//L chained units) must
    produce greedy output bitwise-identical to the adaptive-off engine.

    The organic trigger is an arrival landing between a step's admission
    phase and its dispatch — a thread race generate() cannot reproduce
    deterministically — so the decision hook is forced: EVERY dispatch
    is a single unit, the strictest version of the splitting-
    preserves-output property."""
    prompts = [[5, 17, 99, 3], [1, 2, 3, 4, 5], [200, 100, 7],
               [42, 43, 44, 45, 46, 47]]
    sp = SamplingParams(temperature=0.0, max_tokens=10)

    ref_eng = make_engine(model_cfg, max_batch_size=2)
    ref = [r.generated_tokens for r in ref_eng.generate(prompts, sp)]

    eng = make_engine(model_cfg, max_batch_size=2,
                      latency_dispatch_steps=2)
    eng._short_dispatch_ok = lambda: True
    got = [r.generated_tokens for r in eng.generate(prompts, sp)]
    assert got == ref
    assert eng.total_short_dispatches > 0
    assert eng.stats()["compiled_programs"]["decode_short"] == 0


def test_unit_chained_full_dispatch_matches_plain(model_cfg):
    """A FULL adaptive dispatch is ceil(K/L) chained units of the one
    compiled program (round 5); its output — greedy AND sampled rows —
    must be bitwise-identical to the plain K-step engine. L=3 with K=8
    exercises the ceil split (3 units x 3 steps per group — at least
    the configured K, never silently fewer)."""
    prompts = [[5, 17, 99, 3], [1, 2, 3, 4, 5]]
    sp = SamplingParams(temperature=0.7, top_k=5, max_tokens=9, seed=11)

    ref_eng = make_engine(model_cfg, max_batch_size=2)
    ref = [r.generated_tokens for r in ref_eng.generate(prompts, sp)]

    eng = make_engine(model_cfg, max_batch_size=2,
                      latency_dispatch_steps=3)
    assert eng._decode_units == 3 and eng._decode_unit_len == 3
    got = [r.generated_tokens for r in eng.generate(prompts, sp)]
    # PRNG folds by position, so the dispatch split is invisible to
    # sampling — byte-equal even for the temperature/top-k rows
    assert got == ref
    assert eng.total_short_dispatches == 0     # gate never fired here


def test_pipelined_and_adaptive_compose(model_cfg):
    """pipelined_decode=True + latency_dispatch_steps>0: pipelined
    groups chain onto groups (the group record exposes a unit's carry
    keys); tokens must match the plain engine bitwise."""
    prompts = [[5, 17, 99, 3], [1, 2, 3, 4, 5], [200, 100, 7],
               [42, 43, 44, 45, 46, 47]]
    sp = SamplingParams(temperature=0.0, max_tokens=12)

    ref_eng = make_engine(model_cfg, max_batch_size=4)
    ref = [r.generated_tokens for r in ref_eng.generate(prompts, sp)]

    eng = make_engine(model_cfg, max_batch_size=4,
                      latency_dispatch_steps=2, pipelined_decode=True)
    got = [r.generated_tokens for r in eng.generate(prompts, sp)]
    assert got == ref


def test_pipelined_adaptive_tight_pool_reserves_group_length(model_cfg):
    """The in-flight pipelined GROUP can be ceil(K/L)*L > K steps ahead
    of host positions; page reservation must use the group length, not
    K (review r5: lag=K under-reserved by up to unit_len*units-K and
    the decode scan would write through an unassigned block-table
    entry). Tight pool + non-divisor L + long generations force page
    growth while a chained group is in flight; tokens must match the
    plain engine bitwise."""
    prompts = [[5, 17, 99, 3], [1, 2, 3, 4]]
    sp = SamplingParams(temperature=0.0, max_tokens=40)

    ref_eng = make_engine(model_cfg, max_batch_size=2, kv_num_blocks=20)
    ref = [r.generated_tokens for r in ref_eng.generate(prompts, sp)]

    eng = make_engine(model_cfg, max_batch_size=2, kv_num_blocks=20,
                      latency_dispatch_steps=3, pipelined_decode=True,
                      admission="ondemand")
    assert eng._decode_units * eng._decode_unit_len == 9   # > K=8
    got = [r.generated_tokens for r in eng.generate(prompts, sp)]
    assert got == ref
