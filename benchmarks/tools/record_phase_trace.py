"""Record the small trace benchmarks/tests/data/ keeps for
harness/host_phases.py: on the chip, three rounds inside `bench.window`
of one jitted program dispatched under `llm.decode.dispatch`, waited for
under `llm.decode.pull`, then 10 ms of sleep under the host-only
`llm.emit` and 5 ms of sleep under no annotation. Writes
chiprun_out/tiny_phases.xplane.pb and tiny_phases.expect.json (what
`host_phases.idle_split` and `trace_reduce.reduce_trace` made of it when
it was recorded, so the test pins both and their agreement)."""

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

from harness import host_phases, trace_reduce  # noqa: E402

ROUNDS, EMIT_S, NO_PHASE_S = 3, 0.010, 0.005


def main() -> None:
    @jax.jit
    def tiny_step(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.1
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(tiny_step(x))
    out = os.path.join(ROOT, "chiprun_out")
    tmp = os.path.join(out, "tiny_phases_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    note = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with note("bench.window"):
        for _ in range(ROUNDS):
            with note("llm.decode.dispatch"):
                y = tiny_step(x)
            with note("llm.decode.pull"):
                jax.block_until_ready(y)
            with note("llm.emit"):
                time.sleep(EMIT_S)
            time.sleep(NO_PHASE_S)
    jax.profiler.stop_trace()
    dst = os.path.join(out, "tiny_phases.xplane.pb")
    shutil.copy(trace_reduce.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    red = trace_reduce.reduce_trace(dst, 1)
    split = host_phases.idle_split(dst)
    with open(os.path.join(out, "tiny_phases.expect.json"), "w") as f:
        json.dump({"rounds": ROUNDS, "emit_s": EMIT_S,
                   "no_phase_s": NO_PHASE_S, "window_s": red["window_s"],
                   "busy_s": red["busy_s"], "idle_gaps": red["idle_gaps"],
                   "split": split}, f, indent=1)
    print(os.path.getsize(dst), "bytes;", split, red["idle_gaps"][:6])


if __name__ == "__main__":
    main()
