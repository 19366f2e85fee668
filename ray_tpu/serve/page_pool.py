"""Host-side accounting of the paged KV pool: which pages are free, who
holds each of the others, and which pages each engine slot's table names.

`PagePool` is the ONLY writer of that state. The engine (serve/llm.py)
asks it for pages and hands the device programs its table views; the
prefix cache (serve/prefix_cache.py) takes and drops references through
`ref_pages` / `unref_pages`. The device arrays (models/paged_kv.py
`init_paged_kv`) are not here: page id p is row p of every device pool
that shares this one (the target's and a draft model's). Page 0 is the
null page: never handed out, and what an empty table cell names. numpy
and lists only: no JAX, no engine.
"""

from __future__ import annotations

from typing import Callable

import numpy as np


def pages_for(last_pos, page_size: int):
    """Pages needed to cover writes up to position `last_pos`."""
    return last_pos // page_size + 1


class PagePool:
    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 max_pages_per_slot: int):
        self.n_pages = n_pages
        self.page_size = page_size
        # pop() hands out ascending ids; 0 stays reserved (null page).
        self._free = list(range(n_pages, 0, -1))
        # Per-page reference counts: a slot's table, a prefix-cache entry
        # and an in-flight donation each hold one per page; a page
        # returns to the free list only when the LAST one drops.
        self._refs = np.zeros(n_pages + 1, np.int32)
        self._tables = np.zeros((n_slots, max_pages_per_slot), np.int32)
        self._held = np.zeros(n_slots, np.int64)
        # Low-water mark of the free list since `rebase_low_water()`
        # (peak pool occupancy = n_pages - min_free).
        self.min_free = n_pages

    # ------------------------------------------------------------ reads

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def slot_n_pages(self) -> np.ndarray:
        """Pages each slot's table holds (read-only view)."""
        return _read_only(self._held.view())

    def pages_for(self, last_pos):
        return pages_for(last_pos, self.page_size)

    def row(self, slot: int, width: int | None = None) -> np.ndarray:
        """`slot`'s table, its first `width` cells (read-only view)."""
        return _read_only(self._tables[slot, :width])

    def table_view(self, width: int, blank=()) -> np.ndarray:
        """A copy of every slot's table cut to `width` cells, for a dispatch,
        the `blank` slots' rows zeroed (their writes hit the null page)."""
        view = self._tables[:, :width].copy()
        if len(blank):
            view[blank] = 0
        return view

    def rebase_low_water(self) -> None:
        self.min_free = len(self._free)

    # ------------------------------------------------------- references

    def take_page(self) -> int | None:
        """One exclusive page off the free list (one reference, the
        caller's), or None when the pool is dry."""
        if not self._free:
            return None
        pg = self._free.pop()
        self._refs[pg] = 1
        if len(self._free) < self.min_free:
            self.min_free = len(self._free)
        return pg

    def ref_pages(self, pages) -> None:
        """One more reference on each of `pages` (one id, or distinct ids)."""
        self._refs[pages] += 1

    def unref_pages(self, pages) -> None:
        """Drop one reference from each of `pages` (one id, or an array
        of distinct ids); a page returns to the free list at zero.
        Shared pages simply outlive any one holder."""
        pages = np.atleast_1d(pages)
        self._refs[pages] -= 1
        freed = pages[self._refs[pages] <= 0]
        self._refs[freed] = 0
        self._free.extend(freed.tolist())

    # ------------------------------------------------------------ slots

    def grow(self, slots, last_pos,
             reclaim: Callable[[int], None] | None = None,
             spare: int = 0) -> bool:
        """Take exclusive pages so that each of `slots` (one slot, or an
        array of them) covers its `last_pos`, and leave `spare` pages
        free (what the caller has set aside for somebody else). All or
        nothing: short of pages, `reclaim(pages_needed)` is asked to
        free some first, and if the pool is still short nothing
        changes."""
        if isinstance(slots, (int, np.integer)):
            slots, last_pos = (slots,), (last_pos,)
        # Plain ints: numpy's fixed cost a call would be most of the work.
        plan, total = [], 0
        for slot, last in zip(slots, last_pos):
            have = int(self._held[slot])
            need = int(last) // self.page_size + 1 - have
            if need > 0:
                plan.append((slot, have, need))
                total += need
        if total:
            total += spare
        if total > len(self._free) and reclaim is not None:
            reclaim(total)
        if total > len(self._free):
            return False
        for slot, have, need in plan:
            for cell in range(have, have + need):
                self._tables[slot, cell] = self.take_page()
            self._held[slot] = have + need
        return True

    def share(self, slot: int, pages) -> None:
        """Bind already-written `pages` (another holder's: a prefix-cache
        entry's) as the head of empty `slot`'s table, read-only by
        contract; the slot takes one reference on each."""
        n = len(pages)
        self.ref_pages(np.asarray(pages, np.int64))
        self._tables[slot, :n] = pages
        self._held[slot] = n

    def free_slot(self, slot: int) -> None:
        self.unref_pages(self._tables[slot, :self._held[slot]])
        self._tables[slot, :] = 0
        self._held[slot] = 0

    def truncate(self, slots, cursors) -> None:
        """Cut each of `slots` back to the pages covering positions below
        its cursor, in one masked update (this runs on every speculative
        tick). Each cell past the cursor drops its one reference: a page
        grown for the rejected window was exclusive and goes back to the
        free list, a shared one stays. One call cuts distinct pages."""
        rows = np.asarray(slots, np.int64)
        keep = self.pages_for(np.asarray(cursors, np.int64) - 1)
        have = self._held[rows]
        cols = np.arange(self._tables.shape[1])[None, :]
        drop = (cols >= keep[:, None]) & (cols < have[:, None])
        if drop.any():
            tbl = self._tables[rows]
            self.unref_pages(tbl[drop])
            tbl[drop] = 0
            self._tables[rows] = tbl
            self._held[rows] = np.minimum(have, keep)

    # ---------------------------------------------------------- closure

    def accounting(self, cached: set, cached_refs: Callable[[int], int],
                   exporting: dict) -> dict:
        """Closure check: every page is exactly one of free / referenced,
        and every reference is owned by a slot's table, a prefix-cache
        entry (`cached` pages, `cached_refs(page)` references each) or an
        in-flight donation (`exporting`: page -> references)."""
        live: dict[int, int] = {}
        for slot in range(len(self._held)):
            for pg in self._tables[slot, :self._held[slot]].tolist():
                live[pg] = live.get(pg, 0) + 1
        allocated = set(live) | cached | set(exporting)
        refs_ok = all(
            int(self._refs[pg]) == (live.get(pg, 0) + cached_refs(pg)
                                    + exporting.get(pg, 0))
            for pg in allocated)
        free = len(self._free)
        return {
            "total": self.n_pages,
            "free": free,
            "live": len(live),
            "cached": len(cached),
            "cached_only": len(cached - set(live)),
            "exporting": len(exporting),
            "shared": sum(1 for pg in live if live[pg] > 1 or pg in cached),
            "closure": free + len(allocated) == self.n_pages,
            "refs_consistent": refs_ok and not (set(self._free) & allocated),
        }


def _read_only(view: np.ndarray) -> np.ndarray:
    view.flags.writeable = False
    return view
