"""Record the small trace benchmarks/tests/data/ keeps: three runs of one
jitted program inside `bench.window` with 10 ms sleeps between them, on
the chip. Writes chiprun_out/tiny_tpu.xplane.pb and tiny_tpu.expect.json
(what `trace_reduce.reduce_trace` made of it when it was recorded, so
the test pins the reduction, and a by-hand check of the same file pins
the expectation)."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from harness import trace_reduce  # noqa: E402


def main() -> None:
    @jax.jit
    def tiny_step(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.1
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(tiny_step(x))
    tmp = os.path.join(ROOT, "chiprun_out", "tiny_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            jax.block_until_ready(tiny_step(x))
            time.sleep(0.01)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    dst = os.path.join(ROOT, "chiprun_out", "tiny_tpu.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    red = trace_reduce.reduce_trace(dst, 1)
    with open(os.path.join(ROOT, "chiprun_out", "tiny_tpu.expect.json"), "w") as f:
        json.dump({"window_s": red["window_s"], "busy_s": red["busy_s"],
                   "runs": 3, "programs": red["programs"],
                   "ops": red["ops"][:10], "idle_gaps": red["idle_gaps"]}, f,
                  indent=1)
    print(os.path.getsize(dst), "bytes;", red["programs"], red["idle_gaps"][:4])


if __name__ == "__main__":
    main()
