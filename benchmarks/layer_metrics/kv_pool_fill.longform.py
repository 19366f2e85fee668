"""kv_pool_fill.longform: the fullest the KV pool (the full layers'
pages) got in the window, as a share of its pages
(`LLMEngine.metrics()["kv_pages_free_min"]`, the free list's low-water
mark since `reset_stats()`, against the cell's `n_pages`). The linear
layers' recurrent state is a fixed size a slot and has no free list: it
is whole from the start.
"""


def read(ctx):
    free_min = (ctx.get("engine") or {}).get("kv_pages_free_min")
    n_pages = (ctx.get("consts") or {}).get("n_pages")
    if free_min is None or not n_pages:
        return None
    return (n_pages - free_min) / n_pages * 100.0
