"""serve/page_pool.py alone: no JAX, no engine.

A seeded random walk over the pool's operations against a model that
knows every holder of every page (the slots' tables, and "outside"
holders standing in for prefix-cache entries and in-flight donations),
with the closure asserted after every step; then the single invariants
the engine leans on, one test each."""

import numpy as np
import pytest

from ray_tpu.serve.page_pool import PagePool, pages_for

# (n_pages, n_slots, max_pages_per_slot): dry most of the time, about
# balanced, never dry.
POOLS = [(6, 3, 4), (24, 4, 8), (96, 5, 8)]
PAGE_SIZES = [1, 4, 16]


class Model:
    """Who holds what, kept beside the pool by the test."""

    def __init__(self, pool: PagePool, n_slots: int):
        self.pool = pool
        self.tables = [[] for _ in range(n_slots)]
        self.outside: dict[int, int] = {}      # page -> references

    def holders(self) -> dict[int, int]:
        held = dict(self.outside)
        for table in self.tables:
            for pg in table:
                held[pg] = held.get(pg, 0) + 1
        return held

    def check(self) -> None:
        pool, held = self.pool, self.holders()
        free = list(pool._free)
        assert 0 not in held and 0 not in free, "the null page moved"
        assert len(set(free)) == len(free), "a page is free twice"
        assert not set(free) & set(held), "a page is free and held"
        assert len(free) + len(held) == pool.n_pages == len(
            set(free) | set(held)), "free + held != total"
        assert pool.n_free == len(free)
        counts = pool._refs
        assert counts[0] == 0 and all(counts[pg] == 0 for pg in free)
        assert all(counts[pg] == n for pg, n in held.items()), (
            "a count differs from its holders")
        for slot, table in enumerate(self.tables):
            assert pool.slot_n_pages[slot] == len(table)
            row = pool.row(slot)
            assert row[:len(table)].tolist() == table
            assert not row[len(table):].any(), "a cell past the count"
        acct = pool.accounting(set(self.outside),
                               lambda pg: self.outside.get(pg, 0), {})
        assert acct["closure"] and acct["refs_consistent"], acct
        assert acct["free"] == len(free)
        assert acct["live"] == len({p for t in self.tables for p in t})

    def drop_outside(self, pg: int) -> None:
        self.pool.unref_pages(pg)
        self.outside[pg] -= 1
        if not self.outside[pg]:
            del self.outside[pg]

    def reclaim(self, need: int) -> None:
        """The engine's hook: give outside references back, oldest
        first, until `need` pages are free or none is left to give."""
        for pg in list(self.outside):
            if self.pool.n_free >= need:
                return
            while pg in self.outside:
                self.drop_outside(pg)


@pytest.mark.parametrize("page_size", PAGE_SIZES)
@pytest.mark.parametrize("n_pages,n_slots,width", POOLS)
def test_random_walk_keeps_the_closure(n_pages, n_slots, width, page_size):
    rng = np.random.default_rng(n_pages * 131 + page_size)
    pool = PagePool(n_pages, page_size, n_slots, width)
    m = Model(pool, n_slots)
    m.check()
    low_water = pool.min_free
    assert low_water == n_pages
    max_pos = width * page_size - 1
    done = dict.fromkeys(
        ("take", "dry", "share", "drop", "grow", "refused", "reclaimed",
         "truncate", "bind", "free"), 0)
    for _step in range(400):
        op = rng.choice(["take", "share", "drop", "grow", "grow_hook",
                         "grow_many", "truncate", "bind", "free"])
        slot = int(rng.integers(n_slots))
        held = m.holders()
        if op == "take":
            was_free = pool.n_free
            pg = pool.take_page()
            if was_free == 0:
                assert pg is None
                done["dry"] += 1
            else:
                assert pg is not None and pg > 0 and pg not in held
                m.outside[pg] = 1
                done["take"] += 1
        elif op == "share" and held:
            pg = int(rng.choice(sorted(held)))
            pool.ref_pages(pg)
            m.outside[pg] = m.outside.get(pg, 0) + 1
            done["share"] += 1
        elif op == "drop" and m.outside:
            m.drop_outside(int(rng.choice(sorted(m.outside))))
            done["drop"] += 1
        elif op in ("grow", "grow_hook", "grow_many"):
            slots = ([slot] if op != "grow_many" else sorted(
                rng.choice(n_slots, int(rng.integers(1, n_slots + 1)),
                           replace=False).tolist()))
            last = rng.integers(0, max_pos + 1, len(slots))
            need = sum(max(0, pages_for(int(p), page_size)
                           - len(m.tables[s])) for s, p in zip(slots, last))
            before = [list(t) for t in m.tables]
            was_free = pool.n_free
            hook = m.reclaim if op != "grow" else None
            reclaimable = sum(1 for pg, n in m.outside.items()
                              if held[pg] == n) if hook else 0
            if len(slots) == 1:     # the scalar form, as the engine calls it
                ok = pool.grow(slots[0], int(last[0]), hook)
            else:
                ok = pool.grow(slots, last, hook)
            assert ok == (need <= was_free + reclaimable)
            if ok:
                fresh, still_held = [], m.holders()     # after the hook
                for s, p in zip(slots, last):
                    want = max(len(before[s]), pages_for(int(p), page_size))
                    row = pool.row(s, want).tolist()
                    assert row[:len(before[s])] == before[s]
                    fresh += row[len(before[s]):]
                    m.tables[s] = row
                assert len(fresh) == need == len(set(fresh))
                assert not set(fresh) & set(still_held), (
                    "a held page was handed out")
                done["grow"] += 1
                done["reclaimed"] += was_free < need
            else:
                # All or nothing: no slot of the request changed.
                for s in slots:
                    assert pool.row(s).tolist()[:len(before[s])] == before[s]
                    assert pool.slot_n_pages[s] == len(before[s])
                if hook is None:
                    assert pool.n_free == was_free
                done["refused"] += 1
        elif op == "truncate":
            slots = sorted(rng.choice(
                n_slots, int(rng.integers(1, n_slots + 1)),
                replace=False).tolist())
            cursors = [int(rng.integers(0, max_pos + 2)) for _ in slots]
            was_free = set(pool._free)
            cut = []
            for s, c in zip(slots, cursors):
                keep = pages_for(c - 1, page_size)
                cut += m.tables[s][keep:]
                m.tables[s] = m.tables[s][:keep]
            if len(set(cut)) == len(cut):
                pool.truncate(slots, np.asarray(cursors))
            else:
                # One call drops distinct pages (in the engine the cells
                # past a cursor are exclusive): two slots that bound the
                # same outside page are cut one after the other.
                for s, c in zip(slots, cursors):
                    pool.truncate([s], [c])
            # Exactly the pages whose every holder was a cut cell.
            assert set(pool._free) - was_free == {
                pg for pg in cut if held[pg] == cut.count(pg)}
            done["truncate"] += 1
        elif op == "bind" and not m.tables[slot] and m.outside:
            n = int(rng.integers(0, min(width, len(m.outside)) + 1))
            pages = rng.choice(sorted(m.outside), n, replace=False).tolist()
            pool.share(slot, pages)
            m.tables[slot] = pages
            done["bind"] += 1
        elif op == "free":
            pool.free_slot(slot)
            m.tables[slot] = []
            done["free"] += 1
        m.check()
        assert pool.min_free <= low_water, "the low-water mark rose"
        assert pool.min_free <= pool.n_free
        low_water = pool.min_free
    # The walk went through every branch it is here to hold.
    for name in ("take", "share", "drop", "grow", "truncate", "bind", "free"):
        assert done[name], (name, done)
    if n_pages <= n_slots * width // 2:
        assert done["refused"] and done["reclaimed"], done
    pool.rebase_low_water()
    assert pool.min_free == pool.n_free


@pytest.mark.parametrize("page_size", PAGE_SIZES)
def test_pages_for_is_the_cover_of_a_position(page_size):
    pool = PagePool(8, page_size, 1, 8)
    for pos in range(0, 3 * page_size + 1):
        assert pool.pages_for(pos) == len(
            {p // page_size for p in range(pos + 1)})
    assert pool.pages_for(-1) == 0          # a cursor at 0 covers nothing
    assert pool.pages_for(np.array([0, page_size])).tolist() == [1, 2]


def test_page_zero_is_never_handed_out_and_ids_ascend():
    pool = PagePool(5, 4, 1, 5)
    assert [pool.take_page() for _ in range(6)] == [1, 2, 3, 4, 5, None]
    assert pool.min_free == 0


@pytest.mark.parametrize("hook", [False, True])
def test_failed_grow_changes_nothing(hook):
    pool = PagePool(4, 2, 2, 4)
    assert pool.grow(0, 3)                           # 2 pages
    table, free = pool.row(0).tolist(), pool.n_free
    asked = []
    assert not pool.grow([0, 1], [7, 3], asked.append if hook else None)
    assert asked == ([4] if hook else [])            # 2 more + 2, and 2 free
    assert pool.row(0).tolist() == table and pool.slot_n_pages[0] == 2
    assert pool.slot_n_pages[1] == 0 and pool.n_free == free
    assert pool.grow([0, 1], [5, 1])                 # 1 + 1 fits


def test_reclaim_hook_is_tried_before_refusing():
    pool = PagePool(3, 1, 1, 3)
    kept = [pool.take_page(), pool.take_page()]      # an outside holder
    assert not pool.grow(0, 2)
    assert pool.grow(0, 2, lambda need: pool.unref_pages(np.array(kept)))
    assert sorted(pool.row(0).tolist()) == [1, 2, 3]


@pytest.mark.parametrize("spare,fits", [(0, True), (1, True), (2, False)])
def test_grow_leaves_the_spare_pages_free(spare, fits):
    """What the caller set aside for somebody else stays free: a grow
    that would dip into it is refused whole, the hook is asked for the
    pages AND the spare, and a grow that needs no page never is."""
    pool = PagePool(4, 2, 2, 4)
    assert pool.grow(0, 1)                           # 1 page, 3 free
    asked = []
    assert pool.grow(1, 3, asked.append, spare=spare) == fits   # 2 pages
    assert asked == ([] if fits else [4])
    assert pool.n_free == (1 if fits else 3)
    assert pool.slot_n_pages[1] == (2 if fits else 0)
    assert pool.grow(0, 1, asked.append, spare=99)   # covered already
    assert asked == ([] if fits else [4])


def test_truncate_frees_exclusive_pages_and_never_a_shared_one():
    pool = PagePool(8, 4, 2, 4)
    assert pool.grow([0, 1], [15, 15])               # 4 pages each
    shared = int(pool.row(0)[2])
    pool.ref_pages(shared)                           # an outside holder
    rows = [pool.row(s).tolist() for s in (0, 1)]
    pool.truncate([0, 1], np.array([5, 16]))         # keep 2 pages; all 4
    assert pool.row(0).tolist() == rows[0][:2] + [0, 0]
    assert pool.row(1).tolist() == rows[1]
    assert pool.n_free == 1 and shared not in pool._free
    assert pool.accounting({shared}, lambda pg: int(pg == shared),
                           {})["refs_consistent"]
    pool.unref_pages(shared)                         # the last holder goes
    assert pool.n_free == 2


def test_unref_serves_one_page_and_an_array_alike():
    one, many = PagePool(4, 1, 1, 4), PagePool(4, 1, 1, 4)
    for pool in (one, many):
        assert pool.grow(0, 3)
        pool.ref_pages(pool.row(0)[1:3])             # pages 2, 3 shared
    for pg in one.row(0).tolist():
        one.unref_pages(pg)
    many.unref_pages(many.row(0))
    assert one._free == many._free and one._free[-2:] == [1, 4]
    assert one._refs.tolist() == many._refs.tolist() == [0, 0, 1, 1, 0]


def test_views_are_read_only_and_a_dispatch_gets_a_copy():
    pool = PagePool(8, 4, 3, 4)
    assert pool.grow([0, 1, 2], [7, 3, 11])
    for view in (pool.row(0), pool.row(0, 2), pool.slot_n_pages):
        with pytest.raises(ValueError):
            view[...] = 9
    for copy in (pool.table_view(2), pool.table_view(4, blank=[1])):
        copy[...] = 9                   # the caller's own: the pool is as it was
    assert 9 not in pool.row(0).tolist() + pool.row(1).tolist()
    assert pool.table_view(4, blank=[0, 2]).tolist() == [
        [0] * 4, pool.row(1).tolist(), [0] * 4]
    assert pool.row(0).tolist()[:2] == pool.table_view(2)[0].tolist()
    assert pool.slot_n_pages.tolist() == [2, 1, 3]


def test_accounting_tells_a_leak_and_a_lost_reference():
    pool = PagePool(6, 4, 2, 3)
    assert pool.grow(0, 7)
    assert pool.accounting(set(), lambda pg: 0, {})["closure"]
    leaked = pool.take_page()               # a holder nobody declares
    acct = pool.accounting(set(), lambda pg: 0, {})
    assert not acct["closure"]
    acct = pool.accounting(set(), lambda pg: 0, {leaked: 1})
    assert acct["closure"] and acct["refs_consistent"]
    assert acct["exporting"] == 1
    acct = pool.accounting({leaked}, lambda pg: 2 * (pg == leaked), {})
    assert acct["closure"] and not acct["refs_consistent"]


def test_the_module_imports_neither_jax_nor_the_engine():
    import ast

    from ray_tpu.serve import page_pool

    with open(page_pool.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "typing", "numpy"}
