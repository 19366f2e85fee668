"""LLM decode path + continuous-batching engine + Serve integration.

Covers BASELINE config 5 (continuous-batched text generation) at test
scale: the paged chunk and step programs against the full-forward oracle,
engine token streams held to the plain reference
(tests/plain_reference.py), mid-flight request admission, streaming, and
an LLMDeployment behind Serve.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import plain_reference
from ray_tpu.models import gpt
from ray_tpu.models.paged_kv import (
    decode_step_paged,
    init_paged_kv,
    prefill_chunk_paged,
    sample_token,
)
from ray_tpu.serve.llm import LLMEngine

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def lively_params(params):
    """Weights whose greedy continuation does not settle on one token."""
    return plain_reference.lively(params)


def _paged_decode_vs_forward(cfg, params, prompt, *, slot, n_slots, steps):
    """One chunk row writes `prompt` into `slot`'s pages, then `steps`
    single-token steps advance ALL slots (the others idle on the null
    page): at every position the slot's logits are the full forward's."""
    ps, n_pg = 4, 8
    pool = init_paged_kv(cfg, n_slots * n_pg, ps)
    tables = np.zeros((n_slots, n_pg), np.int32)
    tables[slot] = 1 + slot * n_pg + np.arange(n_pg)
    row = np.zeros((1, 8), np.int32)
    row[0, :len(prompt)] = prompt
    last, pool = prefill_chunk_paged(
        cfg, params, jnp.asarray(row), pool,
        jnp.asarray(tables[slot:slot + 1]), jnp.zeros(1, jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32))
    full = gpt.forward(params, jnp.asarray([prompt]), cfg)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(full[0, -1]),
                               rtol=2e-4, atol=2e-4)
    seq = list(prompt)
    tokens = np.zeros(n_slots, np.int32)
    positions = np.zeros(n_slots, np.int32)
    tok = int(np.argmax(np.asarray(last[0])))
    for _ in range(steps):
        seq.append(tok)
        tokens[slot] = tok
        positions[slot] = len(seq) - 1
        logits, pool = decode_step_paged(
            cfg, params, jnp.asarray(tokens), pool, jnp.asarray(positions),
            jnp.asarray(tables))
        full = gpt.forward(params, jnp.asarray([seq]), cfg)
        np.testing.assert_allclose(
            np.asarray(logits[slot]), np.asarray(full[0, -1]),
            rtol=2e-4, atol=2e-4)
        tok = int(np.argmax(np.asarray(logits[slot])))


class TestDecodePath:
    def test_paged_step_logits_match_full_forward(self, params):
        """Chunk + step logits equal full-forward logits position by
        position (same math, page path vs no-cache path), for the middle
        slot of three."""
        _paged_decode_vs_forward(CFG, params, [5, 9, 2, 7, 11], slot=1,
                                 n_slots=3, steps=4)

    def test_slots_are_independent(self, params):
        """Two prompts decoded in adjacent slots give the same results as
        each decoded alone."""
        def run_alone(prompt, steps):
            eng = LLMEngine(CFG, params, n_slots=1, max_len=64)
            req = eng.submit(prompt, max_tokens=steps)
            while not req.done.is_set():
                eng.step()
            return req.out_ids

        a_alone = run_alone([5, 9, 2], 5)
        b_alone = run_alone([17, 3], 5)

        eng = LLMEngine(CFG, params, n_slots=2, max_len=64)
        ra = eng.submit([5, 9, 2], max_tokens=5)
        rb = eng.submit([17, 3], max_tokens=5)
        while not (ra.done.is_set() and rb.done.is_set()):
            eng.step()
        assert ra.out_ids == a_alone
        assert rb.out_ids == b_alone

    def test_chunk_rows_match_sequential(self, params):
        """One dispatch of three chunk rows (three prompts, three slots)
        produces the same last-token logits and pool as three dispatches
        of one row each."""
        rng = np.random.default_rng(5)
        prompts = [list(rng.integers(0, CFG.vocab_size, n))
                   for n in (3, 7, 5)]
        chunk, ps = 8, 4
        padded = np.zeros((3, chunk), np.int32)
        lengths = np.array([len(p) for p in prompts], np.int32)
        for i, p in enumerate(prompts):
            padded[i, :len(p)] = p
        tables = 1 + np.arange(6, dtype=np.int32).reshape(3, 2)
        zeros = jnp.zeros(3, jnp.int32)

        seq_pool = init_paged_kv(CFG, 8, ps)
        seq_logits = []
        for i in range(3):
            last, seq_pool = prefill_chunk_paged(
                CFG, params, jnp.asarray(padded[i:i + 1]), seq_pool,
                jnp.asarray(tables[i:i + 1]), zeros[:1],
                jnp.asarray(lengths[i:i + 1]))
            seq_logits.append(np.asarray(last[0]))

        bat_logits, bat_pool = prefill_chunk_paged(
            CFG, params, jnp.asarray(padded), init_paged_kv(CFG, 8, ps),
            jnp.asarray(tables), zeros, jnp.asarray(lengths))
        np.testing.assert_allclose(
            np.asarray(bat_logits), np.stack(seq_logits), rtol=2e-4,
            atol=2e-4)
        for plane in ("k", "v"):
            # (page 0 is the null page: every row's padding lands there)
            np.testing.assert_allclose(
                np.asarray(bat_pool[plane][:, 1:]),
                np.asarray(seq_pool[plane][:, 1:]), rtol=2e-4, atol=2e-4)

    def test_sample_token_temperature(self):
        logits = jnp.asarray([0.0, 10.0, 0.0, 0.0])
        assert int(sample_token(logits)) == 1
        key = jax.random.key(0)
        draws = {int(sample_token(logits, temperature=5.0, top_k=2,
                                  key=jax.random.fold_in(key, i)))
                 for i in range(50)}
        assert draws <= {0, 1, 2, 3} and 1 in draws


class TestModelRegistry:
    def test_by_name_and_param_counts(self):
        for name, lo, hi in (("gpt2_124m", 0.1e9, 0.15e9),
                             ("opt_1_3b", 1.2e9, 1.5e9),
                             ("gptj_6b", 5.8e9, 6.3e9)):
            c = gpt.GPTConfig.by_name(name)
            assert lo < gpt.num_params(c) < hi, name
        with pytest.raises(KeyError):
            gpt.GPTConfig.by_name("nope")

    def test_untied_paged_decode_matches_forward(self):
        """gptj/opt-style untied head through the paged chunk and step."""
        cfg = gpt.GPTConfig.by_name("tiny_untied", dtype=jnp.float32)
        params = gpt.init_params(cfg, jax.random.key(7))
        _paged_decode_vs_forward(cfg, params, [3, 14, 15, 9], slot=0,
                                 n_slots=2, steps=3)


class TestContinuousBatching:
    def test_midflight_admission(self, params):
        """A request submitted while another is decoding joins without
        perturbing the first request's output."""
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64)
        solo = LLMEngine(CFG, params, n_slots=2, max_len=64)
        r_solo = solo.submit([5, 9, 2], max_tokens=8)
        while not r_solo.done.is_set():
            solo.step()

        r1 = eng.submit([5, 9, 2], max_tokens=8)
        for _ in range(3):
            eng.step()
        r2 = eng.submit([17, 3], max_tokens=4)  # joins mid-flight
        while not (r1.done.is_set() and r2.done.is_set()):
            eng.step()
        assert r1.out_ids == r_solo.out_ids
        assert len(r2.out_ids) == 4
        m = eng.metrics()
        assert m["completed"] == 2 and m["tokens_generated"] == 12

    def test_more_requests_than_slots(self, params):
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64)
        reqs = [eng.submit([3 + i], max_tokens=3) for i in range(5)]
        for _ in range(100):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
        assert all(len(r.out_ids) == 3 for r in reqs)

    def test_engine_thread_and_streaming(self, params):
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64)
        eng.start()
        try:
            req = eng.submit([5, 9], max_tokens=6, stream=True)
            streamed = []
            while True:
                tok = req.stream.get(timeout=60)
                if tok is None:
                    break
                streamed.append(tok)
            assert streamed == req.out_ids and len(streamed) == 6
            assert req.done.is_set()
            m = eng.metrics()
            assert m["ttft_mean_s"] > 0
        finally:
            eng.stop()

    def test_engine_death_fails_requests_loudly(self, params):
        """If the engine thread dies (e.g. XLA OOM at compile), queued and
        active requests error out immediately instead of hanging until
        client timeout, and later submits are poisoned."""
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64)
        eng.step = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        req = eng.submit([5, 9], max_tokens=4, stream=True)  # pre-queued
        eng.start()
        assert req.done.wait(10)
        assert req.error and "boom" in req.error
        assert req.stream.get(timeout=5) is None  # stream closed
        with pytest.raises(RuntimeError, match="engine died"):
            eng.submit([1], max_tokens=1)
        eng.stop()

    def test_multi_step_matches_single_step(self, params):
        """Decode windows (on-device sampling, a step in flight)
        reproduce the exact greedy token sequence of the one-step tick
        (`decode_step_paged`, sampled on the host)."""
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        decode_block=8)
        ref = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        decode_block=1)
        eng.start()
        ref.start()
        try:
            a = eng.generate([5, 9, 2], max_tokens=16)
            b = ref.generate([5, 9, 2], max_tokens=16)
            assert a == b and len(a) == 16
        finally:
            eng.stop()
            ref.stop()

    def test_max_len_finishes_cleanly(self, params):
        eng = LLMEngine(CFG, params, n_slots=1, max_len=12)
        req = eng.submit([1, 2, 3], max_tokens=100)
        for _ in range(50):
            if req.done.is_set():
                break
            eng.step()
        assert req.done.is_set()
        assert len(req.out_ids) < 100  # cut off by cache capacity


class TestPagedKV:
    """The engine over its block-paged KV cache (models/paged_kv.py):
    every token the plain forward's greedy one, under pool back-pressure
    and under preempt-by-recompute in a pool too small for the working
    set (VERDICT r4 next #2)."""

    def _run(self, params, prompts, *, max_tokens=6, **kw):
        eng = LLMEngine(CFG, params, n_slots=4, max_len=64, **kw)
        reqs = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
        for _ in range(500):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
        assert all(r.done.is_set() for r in reqs)
        assert all(r.error is None for r in reqs)
        plain_reference.assert_gpt_greedy(
            CFG, params, [r.prompt_ids[:r.n_prompt] for r in reqs],
            [r.out_ids for r in reqs], n=max_tokens)
        return [r.out_ids for r in reqs], eng

    def test_paged_matches_plain_forward(self, lively_params):
        """Greedy: the engine emits the plain forward's own continuation
        of every prompt (the gather view reconstitutes each slot's exact
        timeline), and the continuations are not one token repeated."""
        prompts = [[5, 9, 2], [17, 3], [1, 2, 3, 4, 5, 6, 7], [11]]
        paged, eng = self._run(lively_params, prompts, page_size=16)
        assert all(len(set(out)) > 2 for out in paged), paged
        m = eng.metrics()
        # All pages returned to the pool after the requests retired.
        assert m["kv_pages_free"] == m["kv_pages_total"]
        assert m["preemptions"] == 0

    def test_pool_backpressure_queues_admissions(self, lively_params):
        """A pool with fewer pages than slots×need still completes every
        request — admission defers instead of failing."""
        prompts = [[3 + i, 1, 4] for i in range(6)]
        paged, eng = self._run(lively_params, prompts, page_size=4,
                               n_pages=2, prefill_chunk=4,
                               prefill_token_budget=8, max_tokens=4)
        assert all(len(o) == 4 for o in paged)
        assert eng.metrics()["kv_pages_free"] == 2

    def test_preemption_recompute_is_exact(self, lively_params):
        """Pool sized so concurrent slots MUST run dry mid-generation:
        victims are evicted by recompute (context = prompt + generated)
        and still produce the exact greedy continuation."""
        prompts = [[5, 9, 2], [17, 3], [2, 4, 6], [8, 1, 0]]
        # Each request grows to 13 tokens → 4 pages of 4; four slots need
        # 16 pages but the pool has 7 → eviction pressure mid-flight.
        _, eng = self._run(lively_params, prompts, page_size=4, n_pages=7,
                           prefill_chunk=4, prefill_token_budget=8,
                           max_tokens=10)
        m = eng.metrics()
        assert m["preemptions"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_infeasible_prompt_rejected_at_submit(self, params):
        """A prompt the pool can never cover is rejected loudly instead of
        requeueing forever."""
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        page_size=4, n_pages=2)
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit(list(range(12)), max_tokens=4)

    def test_engine_side_metrics_present(self, params):
        """The engine reports device-side throughput split from the
        client path: decode tok/s, prefill tok/s, occupancy (VERDICT r4
        next #3)."""
        _, eng = self._run(params, [[5, 9, 2], [7, 7]], page_size=16,
                           max_tokens=8)
        m = eng.metrics()
        assert m["engine_decode_tok_s"] > 0
        assert m["engine_prefill_tok_s"] > 0
        assert 0 < m["slot_occupancy"] <= 1
        assert m["decode_windows"] > 0


class TestServeIntegration:
    def test_llm_deployment_parallel_requests(self):
        import ray_tpu
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4)
        try:
            from ray_tpu.serve.llm import LLMDeployment

            dep = serve.deployment(LLMDeployment, name="llm").options(
                num_replicas=1).bind(
                "tiny", n_slots=4, max_len=64, jax_platform="cpu",
                engine_kwargs={"page_size": 16})
            handle = serve.run(dep)
            refs = [
                handle.method("generate", [5 + i, 9], max_tokens=4)
                for i in range(6)
            ]
            outs = ray_tpu.get(refs, timeout=180)
            assert all(len(o["output_ids"]) == 4 for o in outs)
            assert all(o["ttft_s"] > 0 for o in outs)
            m = ray_tpu.get(handle.method("metrics"), timeout=60)
            assert m["completed"] >= 6
            # Per-request TTFT/decode histograms flush from the replica's
            # worker to the cluster metrics hub in histogram exposition.
            from ray_tpu import state as _state

            deadline = time.time() + 30
            text = ""
            while time.time() < deadline:
                text = _state.prometheus_metrics()
                if "serve_llm_ttft_s_bucket" in text:
                    break
                time.sleep(0.5)
            assert "serve_llm_ttft_s_bucket" in text
            assert "serve_llm_ttft_s_count" in text
            assert "serve_llm_decode_tok_s_bucket" in text
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_streaming_handle_and_http_sse(self):
        """VERDICT r2 item 2: clients see tokens BEFORE generation
        completes — via DeploymentHandle.stream and via the HTTP proxy's
        SSE path (first data event must arrive well before [DONE])."""
        import json
        import socket

        import ray_tpu
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4)
        try:
            from ray_tpu.serve.llm import LLMDeployment

            dep = serve.deployment(LLMDeployment, name="llmstream").options(
                num_replicas=1, route_prefix="/llm").bind(
                "tiny", n_slots=4, max_len=512, jax_platform="cpu",
                engine_kwargs={"page_size": 16})
            handle = serve.run(dep)

            # Warm: first generate compiles the prefill bucket + decode
            # step; timing assertions below must measure streaming, not XLA
            # compile latency. Warm the STREAM path too — it exercises the
            # cursor-protocol RPCs and any stream-only engine code, which a
            # plain generate does not.
            ray_tpu.get(handle.method(
                "generate", [5, 9, 2], max_tokens=4), timeout=300)
            for _ in handle.stream(
                    {"prompt_ids": [5, 9, 2], "max_tokens": 3}):
                pass

            # --- handle streaming: tokens arrive incrementally
            arrivals = []
            toks = []
            t0 = time.perf_counter()
            for tok in handle.stream(
                    {"prompt_ids": [5, 9, 2], "max_tokens": 64}):
                arrivals.append(time.perf_counter() - t0)
                toks.append(tok)
            assert len(toks) == 64
            # First token must land in a fraction of total stream time.
            assert arrivals[0] < arrivals[-1] * 0.5, (
                f"first token at {arrivals[0]:.3f}s vs last "
                f"{arrivals[-1]:.3f}s — stream was buffered")

            # --- HTTP SSE through the proxy
            from ray_tpu.serve.http_proxy import start_proxy

            _proxy, port = start_proxy()
            time.sleep(1.0)  # route table refresh
            body = json.dumps({"prompt_ids": [5, 9, 2],
                               "max_tokens": 64, "stream": True}).encode()
            req = (b"POST /llm HTTP/1.1\r\nHost: x\r\n"
                   b"Content-Type: application/json\r\n"
                   b"Content-Length: " + str(len(body)).encode() +
                   b"\r\n\r\n" + body)
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=120) as s:
                s.sendall(req)
                s.settimeout(120)
                chunks = []           # (t, bytes)
                buf = b""
                t0 = time.perf_counter()
                while b"data: [DONE]" not in buf:
                    data = s.recv(4096)
                    if not data:
                        break
                    chunks.append((time.perf_counter() - t0, data))
                    buf += data
            assert b"data: [DONE]" in buf, buf[-200:]
            # (split on b"\n\n" would glue the first event to the \r\n\r\n
            # header terminator — count events directly)
            n_tokens = buf.count(b'data: {"token"')
            assert n_tokens == 64, f"got {n_tokens} token events"
            t_first = next(t for t, d in chunks if b"data: {" in d)
            t_done = chunks[-1][0]
            assert t_first < t_done * 0.5, (
                f"first SSE bytes at {t_first:.3f}s vs done {t_done:.3f}s "
                "— the proxy buffered the response")
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
