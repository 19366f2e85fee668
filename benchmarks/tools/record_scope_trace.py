"""Record the small trace benchmarks/tests/data/ keeps for
harness/scope_times.py: on the chip, three runs inside `bench.window` of
one jitted train step whose parts carry scopes of the programs'
vocabulary (ray_tpu/ops/scopes.py): a `lax.scan` over two layers,
each `attn.in` then `mlp` under `jax.checkpoint`, a `loss`, a `grad`
(so `transpose(jvp(..))` and `rematted_computation` paths occur) and an
`optimizer` update; one matmul before the scan lies in no scope. Writes
chiprun_out/tiny_scopes.xplane.pb and tiny_scopes.expect.json (what
`scope_times.scope_times` made of it when it was recorded, and every
distinct `tf_op` path of the file, so the test pins the reduction and a
reader sees what the trace really carries). The file is written without
its `/host:metadata` plane (the programs' HLO protos, which no reducer
reads and which would be four fifths of it)."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.dirname(HERE), ROOT]

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from harness import scope_times, trace_reduce  # noqa: E402
from ray_tpu.ops import scopes  # noqa: E402

RUNS = 3
HLO_PLANE = b"/host:metadata"   # the programs' HLO protos: 125 of 157 KB


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out + bytes([n]))


def _copy_without_hlo(src: str, dst: str) -> None:
    """The XSpace of `src` minus the plane no reducer reads, so the
    fixture stays small: planes are field 1 of the top message."""
    with open(src, "rb") as f:
        space = memoryview(f.read())
    with open(dst, "wb") as f:
        for number, value in scope_times._fields(space):
            if number != 1:      # errors, warnings, hostnames: none here
                continue
            name = next((bytes(v) for n, v in scope_times._fields(value)
                         if n == 2), b"")
            if name != HLO_PLANE:
                f.write(b"\x0a" + _varint(len(value)) + bytes(value))


def main() -> None:
    def layer(x, w):
        with jax.named_scope(scopes.ATTN_IN):
            h = jnp.tanh(x @ w)
        with jax.named_scope(scopes.MLP):
            return x + jax.nn.gelu(h) @ w.T

    def loss_fn(ws, x):
        x = x @ ws[0]                                   # in no scope
        x, _ = jax.lax.scan(
            lambda c, w: (jax.checkpoint(layer)(c, w), None), x, ws)
        with jax.named_scope(scopes.LOSS):
            return jnp.mean(x.astype(jnp.float32) ** 2)

    @jax.jit
    def scoped_step(ws, x):
        loss, grads = jax.value_and_grad(loss_fn)(ws, x)
        with jax.named_scope(scopes.OPTIMIZER):
            return ws - 0.01 * grads.astype(ws.dtype), loss

    ws = jnp.full((2, 512, 512), 0.01, jnp.bfloat16)
    x = jnp.ones((256, 512), jnp.bfloat16)
    jax.block_until_ready(scoped_step(ws, x))
    out = os.path.join(ROOT, "chiprun_out")
    tmp = os.path.join(out, "tiny_scopes_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.005)       # the device's clock runs ~1 ms ahead
        for _ in range(RUNS):
            jax.block_until_ready(scoped_step(ws, x))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    dst = os.path.join(out, "tiny_scopes.xplane.pb")
    _copy_without_hlo(trace_reduce.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    red = trace_reduce.reduce_trace(dst, 1)
    table = scope_times.scope_times(dst, red)
    with open(os.path.join(out, "tiny_scopes.expect.json"), "w") as f:
        json.dump({"runs": RUNS, "busy_s": red["busy_s"],
                   "window_s": red["window_s"], "table": table,
                   "tf_ops": sorted(set(scope_times._op_paths(
                       dst, os.path.getmtime(dst)).values()))},
                  f, indent=1)
    print(os.path.getsize(dst), "bytes;", json.dumps(table)[:3000])


if __name__ == "__main__":
    main()
