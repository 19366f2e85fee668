"""idle_prefill_dispatch_share.batch: device idle time that falls inside
the engine's `llm.prefill.dispatch` phase, as a share of the traced
window, chip 0, in percent: the device's queue is empty while the host
builds and hands over the tick's next chunk program, which dispatching a
tick's programs back to back (ROADMAP S3) would close.

`idle_dispatch_share.batch` lumps every `*.dispatch` with every `*.pull`;
this is one part of it, `idle_pull_share.batch` another
(harness/host_phases.py `idle_split()["by_phase"]`).
"""

from harness import host_phases

PHASES = ("llm.prefill.dispatch",)


def read(ctx):
    if not (ctx.get("trace") or {}).get("window_s"):
        return None
    path = host_phases.newest_xplane()
    split = host_phases.idle_split(path) if path else None
    if not split or not split["window_s"]:
        return None
    idle = sum(split["by_phase"].get(p, 0.0) for p in PHASES)
    return idle / split["window_s"] * 100.0
