"""expert_rows_held_share: of the choices the router made for decoding
rows in the window (top-k a row and expert layer, over ALL the model's
experts), the share that landed on an expert THIS chip holds
(`LLMEngine.metrics()`: `moe_rows_held / moe_rows_routed`, counted on
the device by the decode programs). Under a router with no preference
the share is the share of the experts held. None where the program
counts no held rows (a family that holds every expert, an older engine).
"""


def read(ctx):
    engine = ctx.get("engine") or {}
    held, routed = engine.get("moe_rows_held"), engine.get("moe_rows_routed")
    if held is None or not routed:
        return None
    return held / routed * 100.0
