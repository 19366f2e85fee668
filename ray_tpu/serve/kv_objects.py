"""KV page-set objects: finished KV pages as object-store citizens.

PAPER.md's layer map makes the object store the substrate every tier
leans on — yet the hottest serving state, finished KV pages, used to die
with its replica: every failover and every cross-replica migration paid
a teacher-forced re-prefill of the whole context. This module makes KV
pages first-class: a finishing prefill (or a draining replica's
exporter) DONATES its written pages as refcounted page-set objects, and
an admitting engine ADOPTS them by reference — binding them into its
allocator exactly like a local prefix-cache warm hit — instead of
re-prefilling from token ids.

Keying
------
Donations are keyed by the SAME parent-chained chunk digests the prefix
cache uses (`prefix_cache.extend_chunk_chain` — one digest scheme for
the whole repo, so local warm hits, affinity routing, and cross-replica
adoption all speak one key space). One donated sequence of ``d`` full
chunks produces ``d`` entries; entry ``d`` holds only the pages NEW to
depth ``d`` (``page_span``), so adopting depths ``1..j`` materializes
exactly the pages covering ``j·chunk`` tokens and a missing deeper
entry degrades to a PARTIAL adoption, never a failed one. The engine
REQUIRES ``chunk % page_size == 0`` for KV transfer: entries are
deduped per depth ACROSS donations, and only page-aligned spans make a
chain composed of depths from different donations self-contained (a
mid-page chunk boundary would share a page between depths that only
one donation fully wrote — adopting the composite would serve garbage
KV for the boundary positions). ``page_span`` itself handles the
general case for the arithmetic's sake.

Adoption ladder (the failover contract)
---------------------------------------
adopt (refs resolve) → partial-adopt + cold-suffix prefill (a prefix
resolves) → teacher-forced re-prefill (nothing resolves — PR 9's
unchanged last resort). Every rung is byte-identical to an
uninterrupted greedy stream: adopted pages hold exactly the K/V the
donor computed for those tokens, and the cold suffix re-prefills from
token ids as before.

Backends
--------
- ``ObjectKVStore``: the cluster path. Payloads (numpy K/V planes)
  travel through ``ray_tpu.put(..., _cache_local=False)`` — the
  per-node shm arena holds the only copy, zero-copy serialized — and a
  GCS-KV index (namespace ``serve_kv_pages``) maps digest → object id +
  meta so any replica can discover a donation by key alone. The donor
  process holds the owning ObjectRefs (bounded by
  ``serve_kv_object_budget``; oldest withdrawn first), so a cleanly
  exiting donor releases its objects, while ``sweep_cluster`` — run by
  the serve controller on its reconcile cadence — frees entries whose
  donor is dead or whose TTL expired, so a SIGKILLed donor's objects
  can't leak the store.
- ``LocalKVStore``: in-process dict with the same surface, shared as a
  process-global singleton by every engine constructed OFF-cluster —
  unit tests exercise the full donate/adopt/chaos ladder without
  booting a cluster (and constructing a store must never auto-boot one:
  backend selection gates on ``api._client is not None``).

Chaos sites: ``serve.kv.donate`` fires at the ENGINE's donation entry
(LLMEngine._donate_kv — every attempt, including ones the store would
dedup; raise → donation skipped, engine keeps serving, page accounting
still closes; kill → donor dies mid-donation), ``serve.kv.adopt`` at
every store fetch (drop → the ladder falls a rung; delay → slow
transfer).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict
from typing import Any

from ray_tpu import chaos as _chaos

logger = logging.getLogger(__name__)

INDEX_NS = "serve_kv_pages"


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages covering tokens [0, n_tokens)."""
    return 0 if n_tokens <= 0 else (n_tokens - 1) // page_size + 1


def page_span(depth: int, chunk: int, page_size: int) -> tuple[int, int]:
    """Page indices NEW to chain depth ``depth`` (1-based): the half-open
    span [P((d-1)·c), P(d·c)) over the slot's page table. When a chunk
    boundary lands mid-page, the boundary page already belongs to the
    shallower depth (with its full final content), so spans never
    overlap and their union over depths 1..j is exactly [0, P(j·c))."""
    return (pages_for_tokens((depth - 1) * chunk, page_size),
            pages_for_tokens(depth * chunk, page_size))


def engine_fingerprint(cfg, page_size: int, chunk: int,
                       draft_cfg=None, kv_dtype: str = "bf16") -> str:
    """Compatibility fingerprint: adopted page payloads are raw K/V
    planes, so donor and adopter must agree on model geometry, dtype,
    page size, AND chunk granularity (the key schedule). The draft
    geometry rides along when speculative decoding is on — the draft
    pool mirrors target pages, so adoption must fill both. A quantized
    pool (int8 planes + per-page scales) appends its kv_dtype: its
    payloads carry an extra plane set a bf16 adopter has no slot for,
    and vice versa. ``:hk`` names the payload layout — K/V planes
    [L, n, page_size, H*K], heads flattened into the minor axis as the
    pool stores them (models/paged_kv.py) — so a page set donated in the
    older [L, n, page_size, H, K] shape never matches and falls through
    to re-prefill; like the rest it is tp-invariant (full-head
    geometry)."""
    fp = (f"{cfg.n_layers}x{cfg.n_heads}x{cfg.head_dim}"
          f":{cfg.dtype.__name__ if hasattr(cfg.dtype, '__name__') else cfg.dtype}"
          f":ps{page_size}:c{chunk}:hk")
    if draft_cfg is not None:
        fp += (f":d{draft_cfg.n_layers}x{draft_cfg.n_heads}"
               f"x{draft_cfg.head_dim}")
    if kv_dtype and kv_dtype != "bf16":
        fp += f":q{kv_dtype}"
    return fp


def make_meta(key_hex: str, depth: int, chunk: int, page_size: int,
              fingerprint: str, donor: str, n_pages: int,
              draft: bool, tp: int = 1) -> dict:
    # tp: the DONOR's tensor-parallel degree. tp=1 payloads are the
    # original unsharded planes ({"k","v",...}); tp>1 payloads carry one
    # plane per head shard ("k@0".."k@{tp-1}", partition.
    # split_head_planes) with replicated _scale planes unsuffixed. The
    # fingerprint stays tp-INVARIANT (full-head geometry): an adopter at
    # any degree reassembles full heads and re-slices per its own mesh.
    return {
        "key": key_hex,
        "depth": depth,
        "n_tokens": depth * chunk,
        "chunk": chunk,
        "page_size": page_size,
        "n_pages": n_pages,
        "fingerprint": fingerprint,
        "donor": donor,
        "draft": draft,
        "tp": int(tp),
        "ts": time.time(),
    }


class LocalKVStore:
    """In-process page-set store: the off-cluster backend (unit tests,
    single-process engines). Same donate/resolve/fetch/withdraw/sweep
    surface as ObjectKVStore; payloads are held as numpy arrays."""

    def __init__(self, budget: int = 64):
        self.budget = max(1, int(budget))
        self._lock = threading.Lock()
        # key_hex -> {"meta": dict, "payload": {"k": np, "v": np, ...}}
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self.donations = 0
        self.withdrawals = 0

    def donate(self, meta: dict, payload: dict) -> dict:
        with self._lock:
            if meta["key"] not in self._entries:
                self._entries[meta["key"]] = {
                    "meta": dict(meta), "payload": payload}
                self.donations += 1
                while len(self._entries) > self.budget:
                    self._entries.popitem(last=False)
                    self.withdrawals += 1
            return dict(self._entries[meta["key"]]["meta"])

    def resolve(self, keys: list[str]) -> dict[str, dict]:
        with self._lock:
            return {k: dict(self._entries[k]["meta"])
                    for k in keys if k in self._entries}

    def fetch(self, meta: dict, timeout: float = 30.0) -> dict:
        _chaos.hit("serve.kv.adopt")
        with self._lock:
            ent = self._entries.get(meta["key"])
            if ent is None:
                raise KeyError(f"kv page-set {meta['key']} is gone")
            return ent["payload"]

    def withdraw(self, key: str) -> bool:
        with self._lock:
            if self._entries.pop(key, None) is not None:
                self.withdrawals += 1
                return True
            return False

    def sweep(self, live_donors: set[str] | None = None,
              ttl_s: float | None = None, now: float | None = None) -> int:
        """Drop entries whose donor is no longer live and/or whose TTL
        expired. → entries freed."""
        now = time.time() if now is None else now
        freed = 0
        with self._lock:
            for key in list(self._entries):
                meta = self._entries[key]["meta"]
                dead = (live_donors is not None
                        and meta.get("donor") not in live_donors)
                expired = (ttl_s is not None
                           and now - meta.get("ts", 0.0) > ttl_s)
                if dead or expired:
                    del self._entries[key]
                    freed += 1
            # Inside the lock: withdraw()/donate() bump this counter under
            # it too, and an unguarded += is a read-modify-write that loses
            # counts against a concurrent withdraw.
            self.withdrawals += freed
        return freed

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries),
                    "donations": self.donations,
                    "withdrawals": self.withdrawals,
                    "budget": self.budget}


class ObjectKVStore:
    """Cluster page-set store: payloads in the per-node object store
    (plasma equivalent), discovery via a GCS-KV digest index. The donor
    instance OWNS its donations' ObjectRefs — dropping one (budget
    withdrawal, process exit) releases the object through the ordinary
    distributed refcount; `sweep_cluster` force-frees what a SIGKILLed
    donor could never release."""

    def __init__(self, client, budget: int = 64, donor: str = ""):
        self._client = client
        self.budget = max(1, int(budget))
        self.donor = donor
        self._lock = threading.Lock()
        self._owned: "OrderedDict[str, Any]" = OrderedDict()  # key -> ref
        self.donations = 0
        self.withdrawals = 0

    def donate(self, meta: dict, payload: dict) -> dict:
        key = meta["key"]
        raw = self._client.kv_get(INDEX_NS, key.encode())
        if raw:
            # Another donor already published this digest — byte-identical
            # content by construction, so reuse its entry (no second copy).
            try:
                return json.loads(raw)
            except Exception:  # graftlint: disable=EXC-SWALLOW (corrupt index row: fall through and overwrite it with a fresh donation)
                pass
        # The shm extent is the only copy (cache_local=False): donated KV
        # must not also pin a pickled twin in the donor's process RAM.
        ref = self._client.put(payload, cache_local=False)
        meta = dict(meta, ref=ref.hex())
        self._client.kv_put(INDEX_NS, key.encode(),
                            json.dumps(meta).encode())
        with self._lock:
            self._owned[key] = ref
            self.donations += 1
            drop = []
            while len(self._owned) > self.budget:
                drop.append(self._owned.popitem(last=False))
        for old_key, old_ref in drop:
            self._withdraw_entry(old_key, old_ref)
        return meta

    def _withdraw_entry(self, key: str, ref) -> None:
        # Callers (donate's budget eviction, withdraw) invoke this AFTER
        # releasing _lock — the kv_get/kv_del below are RPCs that must not
        # run under it. The counter bump still needs the lock: += races a
        # concurrent donate's bump otherwise.
        with self._lock:
            self.withdrawals += 1
        try:
            # Compare-and-delete: only remove the index row if it still
            # points at OUR object. After a TTL sweep reaped this
            # donor's stale row, another donor may have re-published
            # the same digest — an unconditional kv_del here would
            # delete that donor's LIVE row and strand its object
            # undiscoverable for its whole lifetime.
            raw = self._client.kv_get(INDEX_NS, key.encode())
            row = json.loads(raw) if raw else None
            if row is not None and row.get("ref") == ref.hex():
                self._client.kv_del(INDEX_NS, key.encode())
        except Exception as e:  # noqa: BLE001 — sweep is the backstop
            logger.debug("kv index del %s failed (sweep will reap): %s",
                         key[:12], e)
        try:
            self._client.free([ref])
        except Exception as e:  # noqa: BLE001 — sweep is the backstop
            logger.debug("kv object free %s failed (sweep will reap): %s",
                         key[:12], e)

    def resolve(self, keys: list[str]) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for k in keys:
            try:
                raw = self._client.kv_get(INDEX_NS, k.encode())
            except Exception as e:  # noqa: BLE001 — GCS blip = no hit
                logger.debug("kv index read %s failed: %s", k[:12], e)
                continue
            if not raw:
                continue
            try:
                out[k] = json.loads(raw)
            except Exception:  # graftlint: disable=EXC-SWALLOW (corrupt index row reads as a miss; the adoption ladder has a fallback rung)
                continue
        return out

    def fetch(self, meta: dict, timeout: float = 30.0) -> dict:
        _chaos.hit("serve.kv.adopt")
        from ray_tpu import api as _api

        ref = _api.ObjectRef.from_hex(meta["ref"])
        return _api.get(ref, timeout=timeout)

    def withdraw(self, key: str) -> bool:
        with self._lock:
            ref = self._owned.pop(key, None)
        if ref is None:
            return False
        self._withdraw_entry(key, ref)
        return True

    def sweep(self, live_donors: set[str] | None = None,
              ttl_s: float | None = None, now: float | None = None) -> int:
        return sweep_cluster(self._client, live_donors, ttl_s, now=now)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._owned),
                    "donations": self.donations,
                    "withdrawals": self.withdrawals,
                    "budget": self.budget}


def sweep_cluster(client, live_donors: set[str] | None = None,
                  ttl_s: float | None = None,
                  now: float | None = None) -> int:
    """Orphan-page sweep over the cluster index: free every donated
    page-set whose donor is no longer live (a SIGKILLed replica never
    releases its owned refs — without this its pages leak the node
    store) and every entry past its TTL. The serve controller runs this
    on full reconcile passes (`serve_kv_sweep_interval_s`); it is
    idempotent and safe against concurrent adopters — an adopter whose
    fetch loses the race falls down the adoption ladder. → freed."""
    from ray_tpu import api as _api

    now = time.time() if now is None else now
    freed = 0
    try:
        keys = client.kv_keys(INDEX_NS)
    except Exception as e:  # noqa: BLE001 — next pass retries
        logger.debug("kv sweep index listing failed: %s", e)
        return 0
    for key in keys:
        kb = key if isinstance(key, bytes) else key.encode()
        try:
            raw = client.kv_get(INDEX_NS, kb)
            meta = json.loads(raw) if raw else None
        except Exception:  # graftlint: disable=EXC-SWALLOW (unreadable row: skipped this pass, the TTL sweep reaps it eventually)
            continue
        if meta is None:
            continue
        dead = (live_donors is not None
                and meta.get("donor") not in live_donors)
        expired = ttl_s is not None and now - meta.get("ts", 0.0) > ttl_s
        if not (dead or expired):
            continue
        try:
            client.kv_del(INDEX_NS, kb)
            if meta.get("ref"):
                client.free([_api.ObjectRef.from_hex(meta["ref"])])
            freed += 1
        except Exception as e:  # noqa: BLE001 — next pass retries
            logger.debug("kv sweep of %s failed: %s",
                         str(meta.get("key", ""))[:12], e)
    if freed:
        logger.info("kv orphan sweep freed %d page-set entries", freed)
    return freed


_local_store: LocalKVStore | None = None
_local_lock = threading.Lock()


def get_store(budget: int | None = None, donor: str = ""):
    """Backend selection for an engine enabling KV transfer. A client
    already attached → the cluster store; otherwise the process-global
    LocalKVStore (shared, so two engines in one test process exercise
    the full donate/adopt path). NEVER calls `_ensure_client` — building
    an engine off-cluster must not boot a cluster as a side effect (the
    PR 12 handle-constructor lesson)."""
    from ray_tpu import api as _api
    from ray_tpu.core.config import runtime_config

    if budget is None:
        budget = runtime_config().serve_kv_object_budget
    if _api._client is not None:
        return ObjectKVStore(_api._client, budget=budget, donor=donor)
    global _local_store
    with _local_lock:
        if _local_store is None:
            _local_store = LocalKVStore(budget=budget)
        return _local_store


def reset_local_store() -> None:
    """Tests: drop the process-global local store between cases."""
    global _local_store
    with _local_lock:
        _local_store = None


__all__ = [
    "LocalKVStore", "ObjectKVStore", "get_store", "reset_local_store",
    "sweep_cluster", "page_span", "pages_for_tokens",
    "engine_fingerprint", "INDEX_NS",
]
