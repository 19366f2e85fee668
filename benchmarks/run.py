"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips for its whole life. It loads,
warms up every shape the cell uses (set-up, reported as `setup_s`),
measures for `--seconds`, checks the outputs against the plain reference
under benchmarks/harness/reference/, prints what it likes on earlier
lines and, as its LAST line of standard output, one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
when traced) and, last, `compared`: each number `correct` rests on beside
its limit, which are also the last lines of standard error. With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. The line before it says where the
run's wall seconds went, phase by phase (`RunContext.mark`): a cell's
WHOLE run has to end well inside the driver's 360 s.

It refuses to run (exit code other than 0, no result line) without a
TPU, with fewer chips than the cell asks for, on a `device_kind` missing
from harness/peaks.py, and when a program was compiled or loaded inside
the measured window.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name BENCHMARK.json gives it:
configs/<config>.json, traffic/<mix>.json, layer_metrics/<metric>.json
(or .py; a metric's name says what is read, its entry's `workloads` in
which cells: harness/readers.py). What belongs to a model class (the
program's configuration object and model module, the plain reference,
the bytes and operations a step must do) is the configuration's FAMILY,
families/<family>.py, and the reference file the configuration names
(harness/families.py). Adding a cell, a metric or an architecture is
adding files and entries.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def place_compile_cache() -> str:
    """An outside JAX_COMPILATION_CACHE_DIR wins; else the fixed path
    benchmarks/.jax_cache in this checkout (the path is part of the
    cache's key). Set before jax is imported; every program is cached,
    not only the slow ones."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(HERE, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.makedirs(cache, exist_ok=True)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return cache


class CompileCounter:
    """Programs built inside this process: XLA backend compiles, and
    requests to the persistent cache (a hit loads a program: cheaper
    than a compile, still not something a measured window may hold)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/compile_requests_use_cache")
    MISS = "/jax/compilation_cache/cache_misses"   # compiled, then written

    def __init__(self):
        from jax import monitoring

        self.counts = dict.fromkeys(self.EVENTS, 0)
        self.misses = 0
        monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: self._hit(event))
        monitoring.register_event_listener(
            lambda event, **_kw: self._hit(event))

    def _hit(self, event: str) -> None:
        if event in self.counts:
            self.counts[event] += 1
        elif event == self.MISS:
            self.misses += 1

    def __call__(self) -> int:
        return max(self.counts.values())


class Tracer:
    """The profiler, from the benchmark's side only. With --trace 1 it
    records the LAST `trace_s` seconds of the window, so that stopping it
    (seconds of host work) falls outside; the span `bench.window` marks
    exactly what was traced."""

    def __init__(self, enabled: bool, trace_dir: str, trace_s: float):
        self.enabled, self.dir, self.trace_s = enabled, trace_dir, trace_s
        self._span = None
        self.stopped = False
        self.t_started = None        # perf_counter when the trace began

    def maybe_start(self, t: float, t_end: float, step_s: float = 0.0) -> None:
        if (not self.enabled or self._span is not None or self.stopped
                or t_end - t > self.trace_s + step_s):
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_started = time.perf_counter()
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        if self._span is None or self.stopped:
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True


@dataclasses.dataclass
class RunContext:
    cell: dict
    config: dict
    traffic: dict
    family: object          # families/<family>.py, harness/families.py
    reference: object       # the configuration's plain reference module
    seed: int
    seconds: float
    platform: str
    devices: list
    tracer: Tracer
    compiles: CompileCounter
    setup_end: float | None = None
    marks: list = dataclasses.field(default_factory=list)

    def log(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {msg}",
              flush=True)

    def mark_setup_end(self, t: float) -> None:
        self.setup_end = t

    def mark(self, phase: str, t: float | None = None) -> None:
        """`phase` ended at `t` (now): it began where the phase before it
        ended, the first at the process's start, so the phases' seconds
        add up to the run's."""
        self.marks.append((phase, time.perf_counter() if t is None else t))

    def phase_line(self) -> str:
        last, parts = T_PROCESS, []
        for phase, t in self.marks:
            parts.append(f"{phase} {t - last:.1f}")
            last = t
        return (f"phases, wall seconds: {', '.join(parts)}; sum "
                f"{last - T_PROCESS:.1f}")

    def memory_stats(self) -> dict:
        """`memory_stats()` of the fullest of the chips used, and under
        `bytes_in_use_min` what the emptiest of them holds right now. A
        cell calls this at the end of its window: the peak is then the
        process's so far (set-up included, the runtime keeps one peak),
        the bytes in use are the cell's steady state."""
        best, in_use = {}, []
        for d in self.devices:
            s = d.memory_stats() or {}
            in_use.append(s.get("bytes_in_use"))
            if s.get("peak_bytes_in_use", 0) >= best.get("peak_bytes_in_use", 0):
                best = s
        best = dict(best)
        if all(b is not None for b in in_use):
            best["bytes_in_use_min"] = min(in_use)
        return best


def main(argv=None, *, root: str = ROOT, rehearsal: bool = False,
         degrade=None, after=None) -> int:
    """`root` and `rehearsal` are for benchmarks/tests only: another
    directory holding a BENCHMARK.json, and a CPU run of the same control
    flow whose line carries no device metric (every name is prefixed
    `cpu_rehearsal.` and the line says `"rehearsal": true`). `degrade`
    and `after` are for benchmarks/tools/probe_precision.py only: the
    weights a serving cell's engine gets in place of the true ones, and a
    callback handed the cell's result before the line is printed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    from harness import configs, families, peaks, readers, trace_reduce

    bench = configs.load_benchmark(root)
    cell = configs.find_cell(bench, ns.workload)
    config = configs.load_config(root, bench, cell["config"])
    mix = configs.load_traffic(root, bench, cell["traffic"])
    cache_dir = "off" if rehearsal else place_compile_cache()

    import jax

    if rehearsal:       # the CPU backend's cache is not worth its hazards
        jax.config.update("jax_enable_compilation_cache", False)

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not rehearsal:
        raise SystemExit(f"benchmarks/run.py: JAX platform is {platform!r}; "
                         "the benchmark measures a TPU and runs on nothing "
                         "else")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chips, "
                         f"JAX sees {len(devs)}")
    chip_peaks = {} if rehearsal else peaks.peaks_for(kind)  # unknown: fail now
    family, reference = families.load(root, bench, config)
    devices = devs[:cell["chips"]]
    trace_dir = os.path.join(HERE, ".trace", cell["name"])
    rc = RunContext(cell=cell, config=config, traffic=mix, family=family,
                    reference=reference, seed=ns.seed,
                    seconds=ns.seconds, platform=platform, devices=devices,
                    tracer=Tracer(bool(ns.trace), trace_dir,
                                  float(mix.get("trace_s", 4.0))),
                    compiles=CompileCounter())
    rc.mark("jax_open")
    rc.log(f"cell {cell['name']} seed {ns.seed} seconds {ns.seconds} trace "
           f"{ns.trace}; {len(devices)} x {kind} ({platform}); compile cache "
           f"{cache_dir}")
    if mix["kind"] == "train_job":
        from harness import train_cell as runner
    else:
        from harness import serve_cell as runner
    result = runner.run(rc) if degrade is None else runner.run(rc, degrade)
    if after is not None:
        after(result, rc)
    if result["compiles_in_window"]:
        raise SystemExit(f"{result['compiles_in_window']} program(s) were "
                         "compiled or loaded inside the measured window; "
                         "the warm-up missed a shape")
    setup_s = rc.setup_end - T_PROCESS
    end_to_end = dict(result["end_to_end"], setup_s=setup_s)
    rc.log(f"end to end: {end_to_end}; attempted {result['attempted']} "
           f"failed {result['failed']} correct {result['correct']}")
    rc.log(f"programs this process: {rc.compiles()} asked of the compile "
           f"cache, {rc.compiles.misses} not found there and compiled")
    memory = result["memory"]           # read at the end of the window
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory.get("peak_bytes_in_use"),
              "memory_in_use_bytes": memory.get("bytes_in_use_min")}
    rc.log(f"device memory at the window's end: peak so far "
           f"{memory.get('peak_bytes_in_use')} B on the fullest chip, in use "
           f"{memory.get('bytes_in_use_min')} B on the emptiest")
    out = {"correct": result["correct"], "attempted": result["attempted"],
           "failed": result["failed"]}
    if not ns.trace:
        wanted = configs.metrics_for_cell(bench, "end_to_end", cell["name"])
        out["metrics"] = {
            m["name"]: {"value": end_to_end[configs.quantity_of(m["name"])],
                        "unit": m["unit"]} for m in wanted}
        missing = [n for n, v in out["metrics"].items() if v["value"] is None]
        if missing:
            raise SystemExit(f"no value for end-to-end metric(s) {missing}")
    else:
        xplane = trace_reduce.find_xplane(trace_dir)
        red = trace_reduce.reduce_trace(xplane, len(devices)) if xplane else None
        if red is not None and red.get("window_s") is None:
            rc.log(f"trace held no device operations; lines: {red['lines']}")
        ctx = dict(result["ctx"], trace=red, peaks=chip_peaks)
        wanted = configs.metrics_for_cell(bench, "per_layer", cell["name"])
        out["metrics"] = readers.read_all(
            configs.metrics_dirs(root, bench), wanted, ctx, end_to_end)
        if red and red.get("window_s"):
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["breakdown"] = {
                "device_ops": [[trace_reduce.short_op(f"{p}:{o}" if p else o), t]
                               for p, o, t in red["ops"][:10]],
                "idle_gaps": [[n, t] for n, t in red["idle_gaps"][:5]]}
            rc.log(f"programs in the traced window: {red['programs']}")
    out["device"] = device
    rc.mark("reduce")
    rc.log(rc.phase_line())
    if rehearsal:
        out["rehearsal"] = True
        out["metrics"] = {"cpu_rehearsal." + n: v
                          for n, v in out["metrics"].items()}
    # What `correct` rests on, each number beside its limit: the line's
    # last key, and the last lines of standard error.
    out["compared"] = result["compared"]
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} "
              f"{'>=' if c.get('at_least') else '<='} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
