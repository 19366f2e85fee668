"""kv_pool_fill: the fullest the KV pool's paged kind got in the window,
as a share of its pages (`LLMEngine.metrics()["kv_pages_free_min"]`, the
free list's low-water mark since `reset_stats()`, against the cell's
`n_pages`): how much of the memory the cell reserves its traffic uses.
What a family keeps by the slot has no free list and is whole from the
start, so it is not in the share.
"""


def read(ctx):
    free_min = (ctx.get("engine") or {}).get("kv_pages_free_min")
    n_pages = (ctx.get("consts") or {}).get("n_pages")
    if free_min is None or not n_pages:
        return None
    return (n_pages - free_min) / n_pages * 100.0
