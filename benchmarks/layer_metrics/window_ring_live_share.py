"""window_ring_live_share: of the window kind's bytes in the pool (a
ring of pages a slot), the share that every slot's live window needs
(`LLMEngine.metrics()`: `kv_bytes_window_live / kv_bytes_window`). The
rest is room for the pages one chunk dispatch writes before any row
attends (models/laguna.py `ring_pages`). None where the engine reports
no such split.
"""


def read(ctx):
    engine = ctx.get("engine") or {}
    live, whole = (engine.get("kv_bytes_window_live"),
                   engine.get("kv_bytes_window"))
    if live is None or not whole:
        return None
    return live / whole * 100.0
