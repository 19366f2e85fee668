"""Chaos bench: zero-drop serving under replica kill + scale-down drain.

The acceptance scenario for the serve tier's fault-tolerance layer
(drain protocol + cross-replica decode failover + chaos harness):

    N concurrent SSE streams run against a multi-replica LLM deployment
    through the async HTTP proxy while (a) one serving replica is
    SIGKILLed mid-decode (a seeded `llm.decode_window` chaos rule inside
    the victim process) and (b) one replica is drained away by a
    scale-down. Every stream must end in [DONE] with EXACTLY the token
    sequence an uninterrupted run of the same seeded workload produces —
    zero dropped requests, zero duplicated or missing tokens — and the
    row records the failover latency clients actually saw (max
    inter-token gap per stream).

Run:

    python bench_chaos.py [--clients 32] [--replicas 3] [--json-out FILE]

Prints one JSON line:
  {"metric": "serve_chaos", "clients": N, "dropped": 0,
   "mismatched_streams": 0, "failover_gap_ms_max": ..., ...}

tests/test_chaos.py runs this exact scenario (smaller budget) via
run_scenario(), so the bench and the committed test cannot drift apart.
"""

from __future__ import annotations

import argparse
import json
import socket
import threading
import time

import numpy as np


def _sse_stream(port: int, route: str, payload: dict,
                timeout_s: float = 300.0) -> dict:
    """One SSE client: POST `payload` (+stream) to the proxy, collect
    tokens with arrival timestamps until [DONE]/error/EOF."""
    body = json.dumps(dict(payload, stream=True)).encode()
    req = (b"POST " + route.encode() + b" HTTP/1.1\r\nHost: x\r\n"
           b"Content-Type: application/json\r\n"
           b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
           + body)
    tokens: list[int] = []
    arrivals: list[float] = []
    done = False
    error = None
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout_s) as s:
            s.sendall(req)
            s.settimeout(timeout_s)
            buf = b""
            # Consume the HTTP response head first — it would otherwise
            # glue onto the first SSE event and swallow its token.
            while b"\r\n\r\n" not in buf:
                data = s.recv(65536)
                if not data:
                    return {"tokens": [], "arrivals": [], "done": False,
                            "error": "connection closed before headers"}
                buf += data
            buf = buf.split(b"\r\n\r\n", 1)[1]
            while True:
                idx = buf.find(b"\n\n")
                if idx < 0:
                    data = s.recv(65536)
                    if not data:
                        break
                    buf += data
                    continue
                event, buf = buf[:idx], buf[idx + 2:]
                line = event.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[len(b"data: "):]
                if data == b"[DONE]":
                    done = True
                    break
                obj = json.loads(data)
                if "token" in obj:
                    tokens.append(int(obj["token"]))
                    arrivals.append(time.perf_counter())
                elif "error" in obj:
                    error = obj["error"]
                    break
    except Exception as e:  # noqa: BLE001 — a client-side failure IS a drop
        error = f"client: {e!r}"
    return {"tokens": tokens, "arrivals": arrivals, "done": done,
            "error": error}


def _pctl(xs: list[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, int(len(s) * q))]


def run_scenario(*, clients: int = 32, replicas: int = 3,
                 scale_down_to: int = 2, max_tokens: int = 12,
                 prompt_len: int = 12, n_slots: int = 4, max_len: int = 96,
                 kill_after_windows: int = 8, drain_timeout_s: float = 2.0,
                 kill_delay_s: float = 0.3, drain_delay_s: float = 0.8,
                 prefill_chunk: int = 8, seed: int = 0,
                 keep_cluster: bool = False) -> dict:
    """Build the cluster, run the seeded chaos workload, return the row.

    Deterministic inputs: prompts come from `seed`, the replica kill is a
    counter-based chaos rule (Nth decode window of the victim process),
    greedy decoding makes the expected token streams a pure function of
    the prompts — so the exactness check is a strict equality against an
    uninterrupted in-process baseline of the same workload.
    """
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import gpt
    from ray_tpu.serve.api import _get_controller
    from ray_tpu.serve.llm import LLMDeployment, LLMEngine
    from ray_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)
    cfg = gpt.GPTConfig.by_name("tiny")
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, prompt_len)]
               for _ in range(clients)]
    engine_kwargs = {"page_size": 16,
                     "prefill_chunk": prefill_chunk,
                     "prefill_token_budget": max(prefill_chunk,
                                                 n_slots * prefill_chunk)}

    # --- uninterrupted baseline: the exact greedy streams the chaos run
    # must reproduce (same model name + params seed as the replicas).
    base_engine = LLMEngine(cfg, None, n_slots=n_slots, max_len=max_len,
                            **engine_kwargs)
    expected = []
    for p in prompts:
        req = base_engine.submit(p, max_tokens=max_tokens)
        while not req.done.is_set():
            base_engine.step()
        expected.append(list(req.out_ids))

    ray_tpu.init(num_cpus=4, _system_config={
        "serve_drain_timeout_s": drain_timeout_s})
    row: dict = {"metric": "serve_chaos", "clients": clients,
                 "replicas": replicas, "scale_down_to": scale_down_to,
                 "max_tokens": max_tokens, "prompt_len": prompt_len,
                 "drain_timeout_s": drain_timeout_s, "seed": seed}
    try:
        dep = serve.deployment(LLMDeployment, name="llmchaos").options(
            num_replicas=replicas, route_prefix="/llm").bind(
            "tiny", n_slots=n_slots, max_len=max_len, jax_platform="cpu",
            engine_kwargs=engine_kwargs)
        handle = serve.run(dep, timeout=300.0)
        _proxy, port = serve.start_proxy()
        time.sleep(1.0)  # route table refresh

        # Warm every replica's compile cache before the chaos phase so the
        # measured gaps are failover latency, not XLA compile time.
        for _ in range(replicas * 3):
            ray_tpu.get(handle.method(
                "generate", prompts[0], max_tokens=2), timeout=300)

        # Victim selection + seeded kill: the FIRST routable replica gets
        # a counter-based decode-window kill rule — the process exits
        # abruptly (os._exit) with streams mid-decode.
        ctrl = _get_controller()
        table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
        victims = table["routes"]["llmchaos"]["replicas"]
        assert len(victims) == replicas

        results: list[dict | None] = [None] * clients
        t0 = time.perf_counter()

        def client(i: int):
            results[i] = _sse_stream(port, "/llm", {
                "prompt_ids": prompts[i], "max_tokens": max_tokens})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        time.sleep(kill_delay_s)
        kill_at = time.perf_counter() - t0
        ray_tpu.get(victims[0].install_chaos.remote(
            [{"site": "llm.decode_window", "action": "kill",
              "after": kill_after_windows, "seed": seed}]), timeout=30)
        time.sleep(max(0.0, drain_delay_s - kill_delay_s))
        drain_at = time.perf_counter() - t0
        # Scale-down mid-burst: same config, fewer replicas → the
        # controller resizes in place and sheds the excess replica
        # through the drain protocol (never a hard kill before
        # serve_drain_timeout_s).
        serve.run(dep.options(num_replicas=scale_down_to), timeout=300.0)
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        dropped = sum(1 for r in results
                      if r is None or r["error"] or not r["done"])
        mismatched = sum(1 for r, exp in zip(results, expected)
                         if r is not None and r["tokens"] != exp)
        gaps = []
        for r in results:
            if r and len(r["arrivals"]) > 1:
                a = r["arrivals"]
                gaps.append(max(b - c for b, c in zip(a[1:], a)))
        # Wait out the drain window so the final replica count reflects
        # the reaped state, then snapshot it.
        deadline = time.time() + drain_timeout_s + 10
        status = serve.status()["llmchaos"]
        while time.time() < deadline and (
                status["draining_replicas"]
                or status["live_replicas"] != scale_down_to):
            time.sleep(0.5)
            status = serve.status()["llmchaos"]
        row.update({
            "dropped": dropped,
            "mismatched_streams": mismatched,
            "completed": sum(1 for r in results if r and r["done"]),
            "tokens_expected": sum(len(e) for e in expected),
            "tokens_received": sum(len(r["tokens"])
                                   for r in results if r),
            "kill_at_s": round(kill_at, 3),
            "drain_at_s": round(drain_at, 3),
            "wall_s": round(wall, 2),
            # Max inter-token gap per stream: streams that crossed the
            # kill/drain paid one failover (re-pick + teacher-forced
            # re-prefill) inside this gap.
            "failover_gap_ms_p50": round(_pctl(gaps, 0.50) * 1000, 1),
            "failover_gap_ms_p95": round(_pctl(gaps, 0.95) * 1000, 1),
            "failover_gap_ms_max": round(max(gaps) * 1000, 1)
            if gaps else 0.0,
            "final_live_replicas": status["live_replicas"],
            "final_draining_replicas": status["draining_replicas"],
        })
        return row
    finally:
        if not keep_cluster:
            serve.shutdown()
            ray_tpu.shutdown()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--scale-down-to", type=int, default=2)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--kill-after-windows", type=int, default=8)
    ap.add_argument("--drain-timeout", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    row = run_scenario(
        clients=args.clients, replicas=args.replicas,
        scale_down_to=args.scale_down_to, max_tokens=args.max_tokens,
        prompt_len=args.prompt_len, n_slots=args.n_slots,
        kill_after_windows=args.kill_after_windows,
        drain_timeout_s=args.drain_timeout, seed=args.seed)
    print(json.dumps(row), flush=True)
    if args.json_out:
        json.dump(row, open(args.json_out, "w"))


if __name__ == "__main__":
    main()
