"""expert_rows_held_share.longform: of the choices the router made for
decoding rows in the window (top-10 a row and layer, over all 512
experts), the share that landed on an expert THIS chip holds
(`LLMEngine.metrics()`: `moe_rows_held / moe_rows_routed`, counted on
the device by the decode programs). A chip that holds 128 of 512 under a
router with no preference reads ~25: the share is the share.
"""


def read(ctx):
    engine = ctx.get("engine") or {}
    held, routed = engine.get("moe_rows_held"), engine.get("moe_rows_routed")
    if held is None or not routed:
        return None
    return held / routed * 100.0
