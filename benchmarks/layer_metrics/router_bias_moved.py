"""router_bias_moved: of the choices the router made for decoding rows
in the window (top-k a row and sparse layer by s + b), the share that is
NOT among the top-k by s alone: what the balancing bias moved
(`LLMEngine.metrics()`: `moe_rows_bias_moved / moe_rows_routed`, counted
on the device by the decode programs). None where the program counts no
such thing (a family without a router bias, an older engine).
"""


def read(ctx):
    engine = ctx.get("engine") or {}
    moved, routed = (engine.get("moe_rows_bias_moved"),
                     engine.get("moe_rows_routed"))
    if moved is None or not routed:
        return None
    return moved / routed * 100.0
