"""RES-PAIR: paired acquire/release path analysis for the repo's
hand-rolled resource protocols, declared in a table (the same way
jax_compat.py declares symbols).

Two checks:

1. Path pairing: inside one function, an acquire call whose function also
   contains the matching release must reach that release on EVERY exit
   path. A release inside a `finally:` (or an `except` rollback handler)
   of a try that covers the acquire counts — that is exactly the PR 15
   donation-ref fix shape. Otherwise any `return`/`raise`/`break` (or a
   `_chaos.hit(...)` site, which may raise an injected fault) lexically
   between the acquire and the first matching release is an exit that
   leaks the resource. A function with acquires but NO matching release
   transfers ownership (pages registered in slot tables, handles returned
   to the caller) and stays quiet — cross-function pairing is out of
   scope by design, like two-hop calls in v2. A `break` only counts as
   an exit when the release lives INSIDE the loop being exited — a
   rollback loop placed after the allocation loop is the normal
   shortfall-recovery shape, not a leak.

2. Thread lifecycle: a `threading.Thread`/`Timer` stored on `self` and
   started must be stoppable — some shutdown-ish method either joins it
   or sets a flag/Event the thread's target reads. Fire-and-forget
   daemons held in locals are exempt (nothing can ever join them, by
   construction).
"""

from __future__ import annotations

import ast
import dataclasses

from tools.graftlint.callgraph import ClassModel, _self_attr, class_models
from tools.graftlint.engine import FileContext, Finding, Rule
from tools.graftlint.rules._shared import dotted


@dataclasses.dataclass(frozen=True)
class ResourceProtocol:
    """One acquire/release pairing, matched by trailing call name."""

    name: str
    acquires: tuple[str, ...]
    releases: tuple[str, ...]


PROTOCOLS: tuple[ResourceProtocol, ...] = (
    # Paged-KV refcounts (serve/page_pool.py's methods; the prefix cache
    # holds them as _ref_page/_unref_page): a ref bumped for a donation
    # must drop on every path out.
    ResourceProtocol("page-ref",
                     ("ref_pages", "take_page", "_ref_page", "_alloc_page"),
                     ("unref_pages", "free_slot", "_unref_page",
                      "_free_slot_pages", "_free_page")),
    # Prefix-cache pins and raw lock/semaphore handles share the
    # acquire()/release() spelling — and the same pairing obligation.
    ResourceProtocol("acquire/release", ("acquire",), ("release",)),
)

_STOPPISH = ("stop", "shutdown", "close", "quit", "terminate", "__exit__",
             "__del__", "drain", "down")


def _tail(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _is_chaos_hit(call: ast.Call) -> bool:
    d = dotted(call.func) or ""
    return d.endswith("chaos.hit")


class ResPairRule(Rule):
    id = "RES-PAIR"
    summary = ("resource acquire with an exit path not covered by the "
               "matching release/rollback, or a stored thread with no "
               "join/stop path from shutdown")

    def __init__(self, protocols: tuple[ResourceProtocol, ...] = PROTOCOLS):
        self.protocols = protocols

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_fn(ctx, node))
        for cm in class_models(ctx):
            out.extend(self._check_threads(ctx, cm))
        return out

    # ----------------------------------------------------- path pairing

    def _own_nodes(self, fn: ast.AST) -> list[ast.AST]:
        """fn's subtree minus nested function bodies (they run later)."""
        skip: set[int] = set()
        for n in ast.walk(fn):
            if n is not fn and isinstance(
                    n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                skip.update(id(x) for x in ast.walk(n) if x is not n)
        return [n for n in ast.walk(fn)
                if id(n) not in skip and n is not fn]

    def _check_fn(self, ctx: FileContext, fn) -> list[Finding]:
        out: list[Finding] = []
        nodes = self._own_nodes(fn)
        calls = [n for n in nodes if isinstance(n, ast.Call)]
        trys = [n for n in nodes if isinstance(n, ast.Try)]
        parents: dict[int, ast.AST] = {}
        for n in [fn] + nodes:
            for c in ast.iter_child_nodes(n):
                parents[id(c)] = n
        for proto in self.protocols:
            acquires = [c for c in calls if _tail(c) in proto.acquires]
            releases = [c for c in calls if _tail(c) in proto.releases]
            if not acquires or not releases:
                continue   # no local pairing expected: ownership transfer
            out.extend(self._check_pairing(ctx, fn, proto, acquires,
                                           releases, nodes, trys, parents))
        return out

    def _check_pairing(self, ctx, fn, proto, acquires, releases, nodes,
                       trys, parents) -> list[Finding]:
        def subtree_ids(stmts) -> set[int]:
            ids: set[int] = set()
            for s in stmts:
                ids.update(id(n) for n in ast.walk(s))
            return ids

        def covered(acq: ast.Call) -> bool:
            """A try whose finally/except releases, and which either
            contains the acquire or starts after it (the PR 15 shape:
            refs bumped, THEN try/finally rolls them back)."""
            for t in trys:
                cleanup = subtree_ids(t.finalbody)
                for h in t.handlers:
                    cleanup |= subtree_ids(h.body)
                if not any(id(r) in cleanup for r in releases):
                    continue
                if id(acq) in subtree_ids(t.body) or t.lineno >= acq.lineno:
                    return True
            return False

        out: list[Finding] = []
        exits = [n for n in nodes
                 if isinstance(n, (ast.Return, ast.Raise, ast.Break))
                 or (isinstance(n, ast.Call) and _is_chaos_hit(n))]
        for acq in acquires:
            if covered(acq):
                continue
            later = [r.lineno for r in releases if r.lineno > acq.lineno]
            if not later:
                out.append(ctx.finding(
                    self.id, acq,
                    f"[{proto.name}] `{_tail(acq)}` at line {acq.lineno} "
                    f"has no matching release "
                    f"({'/'.join(proto.releases)}) on any path after it "
                    f"in `{fn.name}` — the resource leaks on every exit"))
                continue
            first_rel = min(later)

            def escapes(e: ast.AST) -> bool:
                # A break only skips the release when the release is
                # inside the loop the break exits; a rollback loop AFTER
                # the allocation loop still runs.
                if not isinstance(e, ast.Break):
                    return True
                cur = parents.get(id(e))
                while cur is not None and not isinstance(
                        cur, (ast.For, ast.AsyncFor, ast.While)):
                    cur = parents.get(id(cur))
                if cur is None:
                    return True
                return first_rel <= getattr(cur, "end_lineno", 10 ** 9)

            bad = [e for e in exits
                   if acq.lineno < e.lineno < first_rel and escapes(e)]
            if bad:
                bad.sort(key=lambda n: n.lineno)
                what = ("a chaos fault-injection site"
                        if isinstance(bad[0], ast.Call) else
                        type(bad[0]).__name__.lower())
                out.append(ctx.finding(
                    self.id, bad[0],
                    f"[{proto.name}] exit path ({what}, line "
                    f"{bad[0].lineno}) between `{_tail(acq)}` (line "
                    f"{acq.lineno}) and its release (line {first_rel}) "
                    f"in `{fn.name}` — the resource leaks on this path; "
                    "release in a `finally:` instead"))
        return out

    # -------------------------------------------------- thread lifecycle

    def _check_threads(self, ctx: FileContext, cm: ClassModel
                       ) -> list[Finding]:
        if not cm.stored_threads:
            return []
        stop_methods = [m for name, m in cm.methods.items()
                        if any(s in name.split(".")[-1].lower()
                               for s in _STOPPISH)]
        # Signals a stop method raises: attrs it writes, or Events it
        # `.set()`s — `self._stop = True` and `self._shutdown.set()` both.
        signals: set[str] = set()
        joins: set[str] = set()
        for m in stop_methods:
            for a in m.accesses:
                if a.kind == "write":
                    signals.add(a.attr)
            for call, _callee, _held in m.calls:
                f = call.func
                if isinstance(f, ast.Attribute):
                    attr = _self_attr(f.value)
                    if attr is not None and f.attr == "set":
                        signals.add(attr)
                    if attr is not None and f.attr == "join":
                        joins.add(attr)
        out: list[Finding] = []
        for attr, target, site in cm.stored_threads:
            if attr in joins:
                continue
            if target is not None and target in cm.methods:
                reads = {a.attr for a in cm.methods[target].accesses}
                # One hop: the loop body may delegate to a helper that
                # checks the flag.
                for _call, callee, _held in cm.methods[target].calls:
                    if callee and callee in cm.methods:
                        reads |= {a.attr
                                  for a in cm.methods[callee].accesses}
                if reads & signals:
                    continue
            elif target is None:
                continue   # unresolvable target: stay quiet
            out.append(ctx.finding(
                self.id, site,
                f"`{cm.name}.{attr}` stores a thread whose target "
                f"`{target}` reads no stop flag/Event set by any "
                f"shutdown-ish method, and nothing joins it — the thread "
                "outlives shutdown(); add a stop signal its loop checks "
                "or join it on shutdown"))
        return out
