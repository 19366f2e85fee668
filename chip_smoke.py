"""Chip smoke: drive the serve and train main paths once on a TPU.

    python chip_smoke.py             # one chip: kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: tp=4 serving, fsdp=4 training

The quickest proof that this tree still starts on the chip. It moves no
metric: every time it prints is a smoke observation, not a measurement.

Process layout: this parent process NEVER initialises a JAX backend.
Every phase is a child process (``--phase`` is the parent's internal way
of starting one; it is not a user option), run one after the other, each
in its own process group that is killed when the phase ends — so exactly
one process holds the chip at any time:

  kernels       child holds the chip: the Pallas kernels with
                interpret=False against their in-repo references.
  serve:kernel  child is a JAX-FREE driver; ``ray_tpu.init`` +
                ``serve.run`` start a cluster whose replica WORKER holds
                the chip. Requests go through the deployment handle and
                the HTTP proxy (one streamed).
  serve:gather  the same prompts on a second cluster with
                attn_impl="gather" (the reference path), started only
                after the first cluster is gone.
  train         child holds the chip: ``spmd.build_training`` steps.
  (--chips 4)   tp and fsdp children, each holding all four chips.

The device fields of the last line are reported up by the processes that
held the chip (the kernels child, the replica worker, the train child).
Weights are random from ``SEED``; the serving weights are written by this
parent as a bf16 checkpoint directory with numpy (no JAX), and the
replica loads them through ``params_checkpoint``.

Greedy-token agreement rule (kernel, gather, tp=4, tp=1): every engine
computes in bf16 and reassociates sums differently, so on random weights
— whose logits are nearly flat — a near-tie can flip an argmax, after
which two streams legitimately diverge (first chip run: 4 of 6 requests
diverged somewhere in 64 tokens, one tp=4 request at token 0). Comparing
two engines with each other therefore checks little, and nothing that
both share (page tables, the pool, chunked prefill). So EVERY emitted
token of EVERY engine is held to an independent reference: one dense
plain-XLA forward (`gpt.forward`, attn_impl="xla", fp32 logits, no
paging, no chunks) over prompt+output gives the reference's logits at
every position of the engine's own stream, and each emitted token's
logit must be within ``TIE_TOL`` of that row's maximum — greedy up to
bf16 ties, at all 64 positions. Two streams that both pass are identical
up to their first difference, and there both tokens are near-ties of the
same row; the common-prefix lengths are printed for the record.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only if every phase passed on a TPU. Anything else exits non-zero and
never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

SEED = 0
# Logits of the seeded random OPT-1.3B have std ~0.9 and 24 layers of
# bf16 activations move one by about 1e-2: on the chip the worst emitted
# token of any engine lay 0.023 under the dense reference's best logit
# (375 of 384 were its top-1). 0.05 is ~5 % of a standard deviation.
TIE_TOL = 0.05
PHASE_TIMEOUT_S = 900
# bench.py's 3e-4 is a throughput setting: without parameter scaling it
# moves every weight 1.5 % of its scale per step, and on the chip the
# repeated-batch loss went 11.25, 10.76, 12.04, 10.67.
TRAIN_LR = 1e-4
MOSAIC = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Size:
    """One problem size. ``full`` is what runs on the chip; ``tiny`` is
    the CPU rehearsal of the same control flow (tests only)."""

    model: str
    # kernels: decode q [B,H,K] vs the whole pool [KERNEL_LAYERS,P,ps,H*K],
    # tables [B,n_pg]; prefill chunk C; flash [fb, fs, H, K].
    B: int
    H: int
    K: int
    P: int
    ps: int
    n_pg: int
    C: int
    fb: int
    fs: int
    # serve
    n_slots: int
    max_len: int
    n_pages: int
    prefill_chunk: int
    prompt_lens: tuple
    max_tokens: int
    # train
    train_batch: int
    train_seq: int
    train_steps: int
    # None = leave the worker's JAX alone (the chip); "cpu" for rehearsal.
    jax_platform: str | None = None


# Layers of the pool the kernel phase builds (the kernels read the last).
KERNEL_LAYERS = 3

SIZES = {
    # OPT-1.3B at full width; pool = 16 slots x 2048 tokens.
    "full": Size(model="opt_1_3b", B=16, H=32, K=64, P=512, ps=64, n_pg=16,
                 C=128, fb=8, fs=1024, n_slots=16, max_len=2048, n_pages=512,
                 prefill_chunk=128,
                 prompt_lens=(150, 210, 300, 330, 390, 450), max_tokens=64,
                 train_batch=8, train_seq=1024, train_steps=5),
    "tiny": Size(model="tiny", B=2, H=4, K=8, P=9, ps=8, n_pg=4, C=16,
                 fb=1, fs=128, n_slots=4, max_len=128, n_pages=48,
                 prefill_chunk=16, prompt_lens=(20, 35, 12, 27, 18, 40),
                 max_tokens=12, train_batch=8, train_seq=128, train_steps=3,
                 jax_platform="cpu"),
}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------- helpers


def device_report() -> dict:
    """Device identity and memory as the calling process's JAX sees it."""
    import jax

    devs = jax.devices()
    per_device = []
    for d in devs:
        stats = d.memory_stats() or {}
        per_device.append({"id": d.id,
                           "bytes_in_use": stats.get("bytes_in_use"),
                           "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "per_device": per_device}


def require_platform(expect: str) -> None:
    platform = device_report()["platform"]
    if platform != expect:
        raise SystemExit(
            f"chip_smoke: JAX platform is {platform!r}, this phase needs "
            f"{expect!r} — refusing to run on anything else")


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))
    except OSError:
        return 0


def make_prompts(size: Size, vocab: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [[int(t) for t in rng.integers(1, vocab, n)]
            for n in size.prompt_lens]


def seeded_bf16_params(model: str) -> dict:
    """Random bf16 weights for ``model`` from ``SEED``, made with numpy
    only (no JAX backend: this also runs in the JAX-free parent)."""
    import ml_dtypes
    import numpy as np

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig.by_name(model)
    rng = np.random.default_rng(SEED)
    params = {}
    for name, spec in sorted(gpt.param_specs(cfg).items()):
        if spec["init"] == "normal":
            a = rng.standard_normal(spec["shape"], np.float32) * spec["scale"]
        elif spec["init"] == "ones":
            a = np.ones(spec["shape"], np.float32)
        else:
            a = np.zeros(spec["shape"], np.float32)
        params[name] = a.astype(ml_dtypes.bfloat16)
    return params


def write_bf16_checkpoint(model: str, path: str) -> int:
    """`seeded_bf16_params` as a Checkpoint directory. → bytes."""
    from ray_tpu.train.checkpoint import Checkpoint

    params = seeded_bf16_params(model)
    Checkpoint.from_dict({"params": params}).to_directory(path)
    os.sync()    # flush now, not while the cluster that reads it boots
    return sum(a.nbytes for a in params.values())


def common_prefix(a: list[int], b: list[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


_DENSE_FORWARD: dict = {}


def _dense_forward(cfg):
    """One jitted plain forward per config: (params, toks [1, S]) → per
    position, the reference row's max logit, argmax, and the logit of the
    token that actually follows. A plain dict, not functools.cache: this
    function travels to the replica worker by value inside the deployment
    class, and a cache wrapper pickles by reference to a `__main__` the
    worker lacks."""
    if cfg not in _DENSE_FORWARD:
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt

        def fn(params, toks):
            logits = gpt.forward(params, toks, cfg)[0].astype(jnp.float32)
            nxt = jnp.roll(toks[0], -1)            # row i predicts toks[i+1]
            follows = jnp.take_along_axis(logits, nxt[:, None], axis=1)[:, 0]
            return logits.max(axis=1), logits.argmax(axis=1), follows

        _DENSE_FORWARD[cfg] = jax.jit(fn)
    return _DENSE_FORWARD[cfg]


def stream_deficits(params, cfg, prompt: list[int], output: list[int],
                    pad_to: int) -> dict:
    """The reference the agreement rule appeals to: a dense plain-XLA
    forward of ``params`` over prompt+output (run by whichever process
    holds the device). → for each emitted token, how far its reference
    logit lies under the reference row's maximum (0 = the reference's own
    greedy choice), and how many tokens are the reference's top-1."""
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(output)
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :len(seq)] = seq
    top, arg, follows = _dense_forward(dataclasses.replace(
        cfg, attn_impl="xla", remat=False))(params, jnp.asarray(toks))
    rows = slice(len(prompt) - 1, len(seq) - 1)    # rows that predict output
    deficits = np.asarray(top[rows] - follows[rows], np.float32)
    return {"deficits": [float(d) for d in deficits],
            "n_top1": int(np.sum(np.asarray(arg[rows]) == np.asarray(output)))}


def check_streams(label: str, prompts, outs, deficits_fn) -> float:
    """The rule in the module docstring, for one engine's streams.
    ``deficits_fn(prompt, output)`` → `stream_deficits` dict. → the worst
    deficit over every emitted token."""
    worst = 0.0
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        r = deficits_fn(list(prompt), list(out))
        d = r["deficits"]
        if len(d) != len(out):
            raise AssertionError(f"{label}: request {i}: {len(d)} reference "
                                 f"rows for {len(out)} tokens")
        bad = [j for j, x in enumerate(d) if not x <= TIE_TOL]   # NaN too
        j = bad[0] if bad else max(range(len(d)), key=d.__getitem__)
        log(f"{label}: request {i}: {r['n_top1']}/{len(out)} tokens are the "
            f"dense reference's top-1; worst deficit {d[j]:.4f} at token {j} "
            f"(tolerance {TIE_TOL})")
        if bad:
            raise AssertionError(
                f"{label}: request {i} token {j} ({out[j]}) is {d[j]:.4f} "
                f"under the dense reference's best logit — not a bf16 tie")
        worst = max(worst, d[j])
    return worst


def _pad_len(size: Size) -> int:
    return -(-(max(size.prompt_lens) + size.max_tokens) // 128) * 128


def _max_err(a, b) -> tuple[float, float]:
    """(max |a-b|, max(1, max |b|)) in fp32."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))), max(1.0, float(np.max(np.abs(b))))


# ---------------------------------------------------------------- phases


def phase_kernels(size: Size, expect: str = "tpu") -> dict:
    """Each Pallas kernel with interpret=False on the chip against its
    in-repo reference, at the tolerance of the interpret-mode tests
    (bf16: atol 3e-2, scaled by the reference's magnitude for grads)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, reference_attention
    from ray_tpu.ops.paged_attention import (
        paged_attention, paged_prefill_attention, reference_paged_attention,
        reference_paged_prefill_attention)

    interpret = expect != "tpu"      # CPU rehearsal only
    atol = 3e-2
    rng = np.random.default_rng(SEED)
    B, H, K, P, ps, n_pg, C = (size.B, size.H, size.K, size.P, size.ps,
                               size.n_pg, size.C)
    bf = jnp.bfloat16

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf)

    # Ragged slots over distinct pages; page 0 is the null page.
    tables = np.zeros((B, n_pg), np.int32)
    lengths = rng.integers(1, n_pg * ps + 1, B).astype(np.int32)
    lengths[0] = n_pg * ps                       # one slot at full length
    free = list(rng.permutation(np.arange(1, P)))
    for b in range(B):
        need = -(-int(lengths[b]) // ps)
        if need > len(free):                     # tiny pools: share pages
            free = list(rng.permutation(np.arange(1, P)))
        tables[b, :need] = [free.pop() for _ in range(need)]
    tables_j, lengths_j = jnp.asarray(tables), jnp.asarray(lengths)
    # The pool as the programs hold it: every layer in one plane, heads
    # flattened into the minor axis. The kernels read layer `layer` of it
    # in place (not layer 0: a wrong block index would read zeros there).
    L, layer = KERNEL_LAYERS, jnp.int32(KERNEL_LAYERS - 1)
    shape = (L, P, ps, H * K)
    k_pool, v_pool = normal(*shape), normal(*shape)
    k_i8 = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    v_i8 = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    k_sc = jnp.asarray(rng.uniform(0.005, 0.02, (L, P)), jnp.float32)
    v_sc = jnp.asarray(rng.uniform(0.005, 0.02, (L, P)), jnp.float32)
    q = normal(B, H, K)
    results = {}

    def compare(name, fn, ref_fn, *args):
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*args)
        has_mosaic = MOSAIC in lowered.as_text()
        got = jax.block_until_ready(lowered.compile()(*args))
        dt = time.perf_counter() - t0
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if not np.all(np.isfinite(np.asarray(g, np.float32))):
                raise AssertionError(f"{name}: non-finite output")
            err, scale = _max_err(g, w)
            worst = max(worst, err / scale)
        if worst > atol:
            raise AssertionError(
                f"{name}: max scaled error {worst:.4g} > {atol}")
        if expect == "tpu" and not has_mosaic:
            raise AssertionError(f"{name}: no {MOSAIC} in the lowered text")
        results[name] = {"max_scaled_err": worst, "mosaic": has_mosaic,
                         "compile_and_run_s": round(dt, 2)}
        log(f"kernel {name}: max scaled err {worst:.3g} (atol {atol}), "
            f"mosaic={has_mosaic}, compile+run {dt:.1f}s")

    compare("paged_attention[bf16]",
            lambda q, k, v, l, t, n: paged_attention(
                q, k, v, l, t, n, interpret=interpret),
            reference_paged_attention,
            q, k_pool, v_pool, layer, tables_j, lengths_j)
    compare("paged_attention[int8]",
            lambda q, k, v, l, t, n, ks, vs: paged_attention(
                q, k, v, l, t, n, interpret=interpret,
                k_scale=ks, v_scale=vs),
            lambda q, k, v, l, t, n, ks, vs: reference_paged_attention(
                q, k, v, l, t, n, k_scale=ks, v_scale=vs),
            q, k_i8, v_i8, layer, tables_j, lengths_j, k_sc, v_sc)
    # Prefill: each slot's chunk of C queries ends at its kv length.
    offsets = jnp.asarray(np.maximum(lengths - C, 0).astype(np.int32))
    compare("paged_prefill_attention[bf16]",
            lambda q, k, v, l, t, o, n: paged_prefill_attention(
                q, k, v, l, t, o, n, interpret=interpret),
            reference_paged_prefill_attention,
            normal(B, C, H, K), k_pool, v_pool, layer, tables_j, offsets,
            lengths_j)

    fq, fk, fv, fw = (normal(size.fb, size.fs, H, K) for _ in range(4))

    def flash_loss(attn):
        def f(q, k, v, w):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    compare("flash_attention[fwd+bwd]",
            flash_loss(lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=interpret)),
            flash_loss(lambda q, k, v: reference_attention(
                q, k, v, causal=True)),
            fq, fk, fv, fw)
    return {"kernels": results}


def _http_unary(port: int, route: str, payload: dict) -> dict:
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=PHASE_TIMEOUT_S) as resp:
        return json.loads(resp.read())


def _http_sse(port: int, route: str, payload: dict) -> list[int]:
    """One streamed request over the proxy's SSE path. → tokens; raises
    on an error event or a stream that never says [DONE]."""
    import socket

    body = json.dumps(dict(payload, stream=True)).encode()
    head = (f"POST {route} HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    buf = b""
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=PHASE_TIMEOUT_S) as s:
        s.sendall(head + body)
        while b"data: [DONE]" not in buf:
            data = s.recv(65536)
            if not data:
                break
            buf += data
    if b"data: [DONE]" not in buf:
        raise AssertionError(f"SSE stream ended early: {buf[-300:]!r}")
    toks = []
    for line in buf.split(b"\n"):
        line = line.strip()
        if not line.startswith(b"data: {"):
            continue
        ev = json.loads(line[len(b"data: "):])
        if ev.get("error"):
            raise AssertionError(f"SSE error event: {ev['error']}")
        if "token" in ev:
            toks.append(int(ev["token"]))
    return toks


def _replica_report(self, decode_width: int, prefill_width: int) -> dict:
    """Runs inside the replica worker (bound onto the deployment class in
    `phase_serve`): the device it holds, whether its engine died, and
    whether its two serving programs — the decode step and the prefill
    chunk, lowered from the engine's own state at the page-table widths
    it dispatched them with — really contain the Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import paged_kv

    eng = self.engine
    shapes = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    n = eng.n_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    params, pool = shapes(eng.params), shapes(eng.cache)
    decode = paged_kv._decode_sample_paged.lower(
        eng.cfg, params, i32(n), pool, i32(n), i32(n, decode_width),
        jax.ShapeDtypeStruct((n,), jnp.float32), shapes(jax.random.key(0)),
        attn_impl=eng.attn_impl).as_text()
    prefill = paged_kv.prefill_chunk_paged.lower(
        eng.cfg, params, i32(n, eng.prefill_chunk), pool,
        i32(n, prefill_width), i32(n), i32(n), return_logits=True,
        attn_impl=eng.attn_impl).as_text()
    return {"device": device_report(), "fatal": eng._fatal,
            "mosaic": {"decode": MOSAIC in decode,
                       "prefill": MOSAIC in prefill},
            "param_dtypes": sorted({str(a.dtype) for a in
                                    jax.tree.leaves(eng.params)})}


def _replica_stream_deficits(self, prompt, output, pad_to) -> dict:
    """Runs inside the replica worker: `stream_deficits` on its own
    weights."""
    return stream_deficits(self.engine.params, self.engine.cfg, prompt,
                           output, pad_to)


def phase_serve(size: Size, attn_impl: str, ckpt_dir: str,
                expect: str = "tpu") -> dict:
    """serve.run(LLMDeployment) on the paged/chunked engine, on a cluster
    with default settings; requests via the handle (concurrent), the HTTP
    proxy (unary) and SSE (streamed). This process stays off JAX — the
    replica worker owns the device, and computes the dense reference
    every emitted token is then held to (the agreement rule)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import gpt
    from ray_tpu.serve.http_proxy import start_proxy
    from ray_tpu.serve.llm import LLMDeployment

    # The deployment under test plus one read-only report, made by the
    # process that holds the chip (the class travels to the worker by
    # value, helper included).
    SmokeLLM = type("SmokeLLM", (LLMDeployment,),
                    {"smoke_report": _replica_report,
                     "stream_deficits": _replica_stream_deficits})

    cfg = gpt.GPTConfig.by_name(size.model)
    prompts = make_prompts(size, cfg.vocab_size)
    gen = {"max_tokens": size.max_tokens, "temperature": 0.0}
    engine_kwargs = dict(page_size=size.ps, n_pages=size.n_pages,
                         prefill_chunk=size.prefill_chunk,
                         attn_impl=attn_impl)
    t_start = time.perf_counter()
    ray_tpu.init(resources={"TPU": 1})
    try:
        dep = serve.deployment(SmokeLLM, name="llm").options(
            num_replicas=1, route_prefix="/llm",
            ray_actor_options={"num_tpus": 1}).bind(
            size.model, n_slots=size.n_slots, max_len=size.max_len,
            params_checkpoint=ckpt_dir, jax_platform=size.jax_platform,
            engine_kwargs=engine_kwargs)
        handle = serve.run(dep)
        t_up = time.perf_counter()
        log(f"serve[{attn_impl}]: replica up in {t_up - t_start:.1f}s")

        n_handle = len(prompts) - 2
        refs = [handle.method("generate", p, **gen)
                for p in prompts[:n_handle]]
        # A failed request raises here: generate() turns req.error (a
        # compile failure, an engine death) into a RuntimeError.
        outs = [o["output_ids"] for o in
                ray_tpu.get(refs, timeout=PHASE_TIMEOUT_S)]
        t_first = time.perf_counter()
        log(f"serve[{attn_impl}]: {n_handle} concurrent handle requests in "
            f"{t_first - t_up:.1f}s (first-touch compiles included)")

        _proxy, port = start_proxy()
        time.sleep(1.0)                          # route table refresh
        reply = _http_unary(port, "/llm",
                            dict(gen, prompt_ids=prompts[n_handle]))
        # (a failed request is a 500, which urlopen raises on)
        outs.append(reply["result"]["output_ids"])
        outs.append(_http_sse(port, "/llm",
                              dict(gen, prompt_ids=prompts[n_handle + 1])))
        t_http = time.perf_counter()
        log(f"serve[{attn_impl}]: HTTP unary + SSE stream in "
            f"{t_http - t_first:.1f}s")

        for i, o in enumerate(outs):
            if len(o) != size.max_tokens:
                raise AssertionError(
                    f"request {i}: {len(o)} tokens, wanted {size.max_tokens}")
        metrics = ray_tpu.get(handle.method("metrics"), timeout=120)
        # The widths the engine dispatched with: prefill's widest bucket
        # from its own counters, decode's from the longest request.
        from ray_tpu.serve.llm import _pow2_width

        pages = -(-(max(size.prompt_lens) + size.max_tokens) // size.ps)
        widths = metrics.get("prefill_dispatch_widths") or {}
        report = ray_tpu.get(handle.method(
            "smoke_report", _pow2_width(pages),
            max(map(int, widths), default=_pow2_width(pages))), timeout=300)
        if report["fatal"]:
            raise AssertionError(f"engine died: {report['fatal']}")
        if report["device"]["platform"] != expect:
            raise AssertionError(
                f"replica ran on {report['device']['platform']!r}")
        if metrics["llm_attn_impl"] != attn_impl:
            raise AssertionError(f"engine resolved {metrics['llm_attn_impl']}")
        if expect == "tpu" and set(report["mosaic"].values()) != {
                attn_impl == "kernel"}:
            raise AssertionError(
                f"attn_impl={attn_impl} but {MOSAIC} in the serving "
                f"programs is {report['mosaic']}")
        log(f"serve[{attn_impl}]: weights {metrics['weight_bytes'] / 2**30:.2f}"
            f" GiB {report['param_dtypes']}, kv pool "
            f"{metrics['kv_pool_bytes'] / 2**30:.2f} GiB "
            f"({metrics['kv_pages_total']} pages x {metrics['kv_page_size']}),"
            f" completed {metrics['completed']}, tokens "
            f"{metrics['tokens_generated']}, prefill dispatch widths "
            f"{metrics.get('prefill_dispatch_widths')}, {MOSAIC} in the "
            f"serving programs: {report['mosaic']}, device "
            f"{report['device']['kind']} peak "
            f"{report['device']['per_device'][0]['peak_bytes_in_use']}")
        worst = check_streams(
            f"serve[{attn_impl}] vs dense reference", prompts, outs,
            lambda prompt, out: ray_tpu.get(handle.method(
                "stream_deficits", prompt, out, _pad_len(size)), timeout=600))
        log(f"serve[{attn_impl}]: all {sum(map(len, outs))} emitted tokens "
            f"are greedy under the dense reference up to bf16 ties (worst "
            f"deficit {worst:.4f}, tolerance {TIE_TOL})")
        return {"tokens": outs, "device": report["device"],
                "worst_deficit": worst,
                "metrics": {k: metrics.get(k) for k in (
                    "weight_bytes", "kv_pool_bytes", "completed",
                    "tokens_generated", "preemptions",
                    "prefill_dispatch_widths")}}
    except BaseException:
        _dump_cluster_logs()
        raise
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _dump_cluster_logs(tail: int = 40) -> None:
    """On a failed serve phase: the end of every cluster log (GCS, raylet,
    workers — the replica's traceback lives there, not in this process)."""
    from ray_tpu import api

    node = getattr(api, "_node", None)
    if node is None:
        return
    logs = os.path.join(node.session_dir, "logs")
    for name in sorted(os.listdir(logs)):
        with open(os.path.join(logs, name), errors="replace") as f:
            lines = f.readlines()[-tail:]
        if lines:
            print(f"----- {name} (last {len(lines)} lines)\n"
                  + "".join(lines), file=sys.stderr, flush=True)


def phase_train(size: Size, expect: str = "tpu") -> dict:
    """A few SPMD training steps at the settings BENCH_SCALE.md records
    as fitting one chip (adafactor, flash attention, remat). The chunked
    cross-entropy head those notes add no longer fits under the installed
    compiler (34 MB over at compile); the unchunked head does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import spmd

    cfg = gpt.GPTConfig.by_name(size.model, max_seq=size.train_seq,
                                remat=True, attn_impl="flash")
    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1, sp=1, tp=1))
    optimizer = optax.adafactor(TRAIN_LR, multiply_by_parameter_scale=False)
    t0 = time.perf_counter()
    params, opt_state, step = spmd.build_training(
        cfg, mesh, optimizer, jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    toks = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (size.train_batch, size.train_seq)), jnp.int32)
    batch = (toks, jnp.roll(toks, -1, axis=1))
    has_mosaic = MOSAIC in step.lower(params, opt_state, batch).as_text()
    if expect == "tpu" and not has_mosaic:
        raise AssertionError(f"no {MOSAIC} in the training step")
    losses, times = [], []
    for _ in range(size.train_steps):
        t1 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(jax.block_until_ready(loss)))
        times.append(round(time.perf_counter() - t1, 2))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    dev = device_report()
    log(f"train {size.model} B={size.train_batch} S={size.train_seq}: losses "
        f"{[round(x, 4) for x in losses]}, step seconds {times} (first "
        f"includes compile; total {time.perf_counter() - t0:.1f}s), "
        f"{MOSAIC} in step: {has_mosaic}, peak HBM "
        f"{dev['per_device'][0]['peak_bytes_in_use']}")
    return {"losses": losses, "step_seconds": times}


def _run_engine(eng, prompts, max_tokens):
    """Drive an in-process LLMEngine over the prompts. → token streams."""
    eng.start()
    try:
        reqs = [eng.submit(p, max_tokens=max_tokens, temperature=0.0)
                for p in prompts]
        for r in reqs:
            if not r.done.wait(PHASE_TIMEOUT_S):
                raise AssertionError("request timed out")
            if r.error or eng._fatal:
                raise AssertionError(f"request failed: {r.error or eng._fatal}")
        return [[int(t) for t in r.out_ids] for r in reqs]
    finally:
        eng.stop()


def phase_tp(size: Size, expect: str = "tpu", tp: int = 4) -> dict:
    """The paged engine at tp=4 and at tp=1 in this one process (it holds
    all four chips): same weights, same prompts, greedy; every token of
    both is held to the dense reference (the agreement rule); per-device
    bytes show the split."""
    import gc

    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.llm import LLMEngine

    cfg = gpt.GPTConfig.by_name(size.model)
    params = seeded_bf16_params(size.model)
    prompts = make_prompts(size, cfg.vocab_size)[:4]
    kw = dict(n_slots=size.n_slots, max_len=size.max_len,
              page_size=size.ps, n_pages=size.n_pages,
              prefill_chunk=size.prefill_chunk,
              attn_impl="kernel" if expect == "tpu" else "gather")
    out = {}
    for n in (tp, 1):
        eng = LLMEngine(cfg, params, tp=n, **kw)
        toks = _run_engine(eng, prompts, size.max_tokens)
        m = eng.metrics()
        in_use = [d["bytes_in_use"] for d in device_report()["per_device"]]
        log(f"tp={n}: weights+pool on devices, bytes_in_use per device "
            f"{in_use}; pool_shard_bytes {m.get('pool_shard_bytes')}, "
            f"kv_pool_bytes {m['kv_pool_bytes']}")
        out[n] = {"tokens": toks, "bytes_in_use": in_use}
        del eng
        gc.collect()
    on_device = jax.device_put(params, jax.devices()[0])
    worst = max(check_streams(
        f"tp={n} vs dense reference", prompts, out[n]["tokens"],
        lambda prompt, toks: stream_deficits(on_device, cfg, prompt, toks,
                                             _pad_len(size)))
        for n in (tp, 1))
    prefixes = [common_prefix(a, b)
                for a, b in zip(out[tp]["tokens"], out[1]["tokens"])]
    log(f"tp={tp} and tp=1: every emitted token is greedy under the dense "
        f"reference up to bf16 ties (worst deficit {worst:.4f}, tolerance "
        f"{TIE_TOL}); common prefixes {prefixes} of {size.max_tokens}")
    used = out[tp]["bytes_in_use"]
    if expect == "tpu" and (min(used[:tp]) == 0
                            or max(used[:tp]) > 1.5 * min(used[:tp])):
        raise AssertionError(f"tp={tp} bytes are not split evenly: {used}")
    return {"prefixes": prefixes, "bytes_in_use": out[tp]["bytes_in_use"]}


def phase_fsdp(size: Size, expect: str = "tpu", fsdp: int = 4) -> dict:
    """Three SPMD steps on MeshConfig(fsdp=4); step-1 loss equals the
    one-device step-1 loss within LOSS_RTOL; per-device peak bytes."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import spmd

    LOSS_RTOL = 5e-3
    cfg = gpt.GPTConfig.by_name(size.model, max_seq=size.train_seq,
                                remat=True, attn_impl="flash")
    rng = np.random.default_rng(SEED)
    batch_n = max(size.train_batch, fsdp)
    toks = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (batch_n, size.train_seq)), jnp.int32)
    batch = (toks, jnp.roll(toks, -1, axis=1))
    first = {}
    for n, steps in ((fsdp, 3), (1, 1)):
        mesh = make_mesh(MeshConfig(dp=1, fsdp=n, sp=1, tp=1),
                         devices=jax.devices()[:n])
        optimizer = optax.adafactor(TRAIN_LR, multiply_by_parameter_scale=False)
        params, opt_state, step = spmd.build_training(
            cfg, mesh, optimizer, jax.random.key(SEED))
        losses = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(jax.block_until_ready(loss)))
        peaks = [d["peak_bytes_in_use"]
                 for d in device_report()["per_device"]]
        log(f"fsdp={n}: losses {[round(x, 4) for x in losses]}, "
            f"peak bytes per device {peaks}")
        first[n] = losses[0]
        if n == fsdp:
            if not (all(np.isfinite(losses)) and losses[-1] < losses[0]):
                raise AssertionError(f"fsdp={n} losses: {losses}")
            split_peaks = peaks
        del params, opt_state, step
        gc.collect()
    rel = abs(first[fsdp] - first[1]) / abs(first[1])
    log(f"fsdp={fsdp} vs one device: step-1 loss {first[fsdp]:.5f} vs "
        f"{first[1]:.5f}, rel diff {rel:.2e} (rtol {LOSS_RTOL})")
    if rel > LOSS_RTOL:
        raise AssertionError(f"step-1 loss differs: {first}")
    return {"first_losses": first, "peaks": split_peaks}


# ------------------------------------------------------- child / parent

PHASES = {
    "kernels": lambda size, a: phase_kernels(size),
    "serve": lambda size, a: phase_serve(size, a["attn_impl"], a["ckpt"]),
    "train": lambda size, a: phase_train(size),
    "tp": lambda size, a: phase_tp(size),
    "fsdp": lambda size, a: phase_fsdp(size),
}
# Phases whose OWN process holds the chip (serve's replica worker does).
HOLDS_CHIP = ("kernels", "train", "tp", "fsdp")


def _child(phase: str, args_json: str, out_path: str) -> None:
    args = json.loads(args_json)
    if phase in HOLDS_CHIP:
        require_platform("tpu")          # before any work, not after
    result = PHASES[phase](SIZES["full"], args)
    if phase in HOLDS_CHIP:
        result["device"] = device_report()
    with open(out_path, "w") as f:
        json.dump(result, f)


def run_phase(name: str, phase: str, workdir: str, **args) -> dict:
    """Run one phase as a child in its own process group; the group is
    killed when the child ends, so nothing of it can still hold the chip
    when the next phase starts. Raises if the child failed."""
    out_path = os.path.join(workdir, f"{name.replace(':', '_')}.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--phase-args", json.dumps(args), "--phase-out", out_path]
    log(f"phase {name}: start")
    t0 = time.perf_counter()
    # Cluster daemons are started with `python -m ray_tpu...`; make the
    # checkout importable for them wherever this script was started from.
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (root, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.Popen(cmd, start_new_session=True, env=env, cwd=root)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        rc = -1
        log(f"phase {name}: timed out after {PHASE_TIMEOUT_S}s")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    dt = time.perf_counter() - t0
    if rc != 0 or not os.path.exists(out_path):
        raise RuntimeError(f"phase {name} failed (exit code {rc}, {dt:.1f}s)")
    log(f"phase {name}: ok in {dt:.1f}s")
    with open(out_path) as f:
        return json.load(f)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--phase-args", default="{}", help=argparse.SUPPRESS)
    ap.add_argument("--phase-out", help=argparse.SUPPRESS)
    ns = ap.parse_args(argv)

    from ray_tpu.utils.platform import place_compile_cache

    cache_dir = place_compile_cache()
    # Cache every program, not only those slower than JAX's 1 s default.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if ns.phase:
        _child(ns.phase, ns.phase_args, ns.phase_out)
        return 0

    t0 = time.perf_counter()
    before = cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {before} entries before "
        f"({'warm' if before else 'cold'} run)")
    from ray_tpu import _native

    log("object-store allocator: "
        + ("native (built from arena.cc)" if _native.load() is not None
           else "python fallback"))
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    devices = []
    try:
        if ns.chips == 4:
            devices.append(run_phase("tp", "tp", workdir)["device"])
            devices.append(run_phase("fsdp", "fsdp", workdir)["device"])
        else:
            devices.append(run_phase("kernels", "kernels", workdir)["device"])
            ckpt = os.path.join(workdir, "params_bf16")
            t1 = time.perf_counter()
            nbytes = write_bf16_checkpoint(SIZES["full"].model, ckpt)
            log(f"wrote seeded bf16 checkpoint: {nbytes / 2**30:.2f} GiB in "
                f"{time.perf_counter() - t1:.1f}s (numpy, no JAX)")
            kern = run_phase("serve:kernel", "serve", workdir,
                             attn_impl="kernel", ckpt=ckpt)
            gath = run_phase("serve:gather", "serve", workdir,
                             attn_impl="gather", ckpt=ckpt)
            prefixes = [common_prefix(a, b) for a, b in
                        zip(kern["tokens"], gath["tokens"])]
            log(f"kernel vs gather: both engines' streams are greedy under "
                f"the dense reference up to bf16 ties (worst deficits "
                f"{kern['worst_deficit']:.4f}, {gath['worst_deficit']:.4f}); "
                f"common prefixes {prefixes} of {SIZES['full'].max_tokens}")
            devices += [kern["device"], gath["device"]]
            devices.append(run_phase("train", "train", workdir)["device"])
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        log(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    after = cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {after} entries after "
        f"({after - before} new); total {time.perf_counter() - t0:.1f}s "
        "(smoke observation, not a metric)")
    dev = devices[0]
    if any((d["platform"], d["kind"], d["count"])
           != (dev["platform"], dev["kind"], dev["count"]) for d in devices):
        log(f"FAILED: phases saw different devices: {devices}")
        return 1
    if dev["platform"] != "tpu" or dev["count"] != ns.chips:
        log(f"FAILED: wanted {ns.chips} tpu device(s), phases saw {dev}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
