"""idle_pull_share.batch: device idle time that falls inside the engine's
`llm.prefill.pull` and `llm.decode.pull` phases, as a share of the traced
window, chip 0, in percent: the host waits for a result while the device
has nothing queued behind it (a prompt's last chunk, the tick's end).

`idle_dispatch_share.batch` lumps every `*.dispatch` with every `*.pull`;
this is one part of it, `idle_prefill_dispatch_share.batch` another
(harness/host_phases.py `idle_split()["by_phase"]`).
"""

from harness import host_phases

PHASES = ("llm.prefill.pull", "llm.decode.pull")


def read(ctx):
    if not (ctx.get("trace") or {}).get("window_s"):
        return None
    path = host_phases.newest_xplane()
    split = host_phases.idle_split(path) if path else None
    if not split or not split["window_s"]:
        return None
    idle = sum(split["by_phase"].get(p, 0.0) for p in PHASES)
    return idle / split["window_s"] * 100.0
