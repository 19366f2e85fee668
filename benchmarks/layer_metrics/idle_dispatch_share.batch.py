"""idle_dispatch_share.batch: device idle time that falls inside the
engine's llm.*.dispatch and llm.*.pull phases (and llm.spec_verify), as a
share of the traced window, chip 0: the device waited for the runtime to
hand it a program, or for a result to leave it.

With `idle_host_work_share.batch` it splits `device_idle_share.batch`;
what is left over is idle while the engine was in no phase
(harness/host_phases.py).
"""

from harness import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "dispatch_s")
