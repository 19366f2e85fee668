"""idle_host_work_share.batch: device idle time that falls inside the
engine's host-only phases (llm.admit, llm.prefill.build,
llm.prefill.graduate, llm.plan, llm.emit), as a share of the traced
window, chip 0: the device waited for Python.

With `idle_dispatch_share.batch` it splits `device_idle_share.batch`;
what is left over is idle while the engine was in no phase
(harness/host_phases.py).
"""

from harness import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "host_work_s")
