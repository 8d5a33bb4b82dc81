"""Serving cache layouts: the legacy contiguous slot pool and the paged
pool (fixed-size pages + block tables + refcounts + copy-on-write).

**Slot pool** (``init`` / ``reset_slots``): every slot owns a contiguous
cache row — the layout PR 2 shipped, kept as the differential baseline
(``Engine(layout="slotted")`` and the tests' paged-vs-slotted matrix).

**Paged pool** (``PagedPool``): physical storage is a pool of fixed-size
pages and every token-indexed leaf is read/written through a per-slot
*block table* indirection:

  * attention kv rings and MLA latent caches become
    ``(stack, n_pages, page, ...)`` pools; a slot's logical ring row
    ``r`` lives at ``(block_table[slot, r // page], r % page)``.  Page 0
    is a reserved null page (position tags -1, always masked) so
    unallocated block-table entries read as empty.
  * recurrent state (rwkv shift/wkv, mamba conv/ssd) becomes a
    ``(L, n_state_pages, ...)`` pool indexed by a one-entry-per-slot
    ``state_table`` — the same indirection with block count 1, which is
    what lets state snapshots live in the same pool as live slots.

The allocator half (``BlockAllocator``) is pure host-side numpy — free
list, refcounts, block tables — so its invariants (no page leaked, no
page double-owned, copy-on-write never mutates a shared page) are
property-testable without a device.  ``PagedPool`` drives it, packs the
resulting device edits (page-tag resets, page copies, table uploads)
into ONE int32 vector per dirty dispatch (``drain``) that the engine
applies INSIDE its compiled step (``apply_cache_ops`` — clean
dispatches skip it entirely), and implements prefix caching on top:
published full pages / state snapshots are refcounted by a
``prefix_cache.PrefixCache`` and shared into new slots' tables at
admission; the first divergent write to a shared page triggers
copy-on-write (the scheduler is count-based, so the engine knows every
page a dispatch will write BEFORE dispatching it).

Every stacked leaf keeps the page/batch dim at axis 1 (axis 0 = layer
stack) and the top-level ``pos`` at axis 0 — both layouts rely on
exactly this invariant.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.serving.prefix_cache import PrefixCache


class PoolExhausted(RuntimeError):
    """A paged pool ran out of pages (after prefix-cache eviction).
    Recoverable under preemption: the engine spills a victim slot to
    the host pool and retries — admission paths raise it with the pool
    FULLY rolled back (no leaked refs, no half-attached slot)."""


def ring_cfg(cfg: ModelConfig, chunk: int) -> ModelConfig:
    """Config used ONLY for cache allocation: window + chunk ring slack."""
    if cfg.sliding_window:
        cfg = cfg.replace(sliding_window=cfg.sliding_window + chunk)
    if cfg.family == "hybrid" and cfg.shared_attn_window:
        cfg = cfg.replace(shared_attn_window=cfg.shared_attn_window + chunk)
    return cfg


# ==========================================================================
# slot-pool layout (contiguous per-slot rows) — the PR 2 baseline
# ==========================================================================

def _upgrade(node, n_slots: int):
    if not isinstance(node, dict):
        return node
    if "k" in node and "pos" in node:           # attention ring cache
        out = dict(node)
        lead, L = node["pos"].shape[:-1], node["pos"].shape[-1]
        out["pos"] = jnp.full(lead + (n_slots, L), -1, jnp.int32)
        return out
    if "c_kv" in node:                          # MLA latent cache
        out = dict(node)
        out["pos"] = jnp.full(node["c_kv"].shape[:-1], -1, jnp.int32)
        return out
    return {k: _upgrade(v, n_slots) for k, v in node.items()}


def init(cfg: ModelConfig, n_slots: int, max_len: int,
         chunk: int = 0, dtype=None) -> Dict:
    """Allocate the slot-pool cache for ``n_slots`` sequences of up to
    ``max_len`` positions, consumable by the ``prefill_chunk`` steps."""
    chunk = chunk or cfg.serve_chunk
    api = get_model(cfg)
    assert api.cache_init is not None, f"{cfg.name} has no decode cache"
    cache = api.cache_init(ring_cfg(cfg, chunk), n_slots, max_len,
                           dtype or cfg.jdtype)
    out = {k: _upgrade(v, n_slots) for k, v in cache.items()}
    out["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return out


def reset_slots(cache: Dict, slots) -> Dict:
    """Recycle cache slots: zero state / -1 kv tags / 0 position for every
    slot where ``slots`` (n_slots,) bool is True, leaving the rest
    untouched.  jit-safe (one select per leaf)."""
    slots = jnp.asarray(slots)

    def leaf(key, a):
        fill = -1 if key == "pos" else 0
        m = slots.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, jnp.full((), fill, a.dtype), a)

    def walk(node):
        out = {}
        for k, v in node.items():
            out[k] = walk(v) if isinstance(v, dict) else leaf(k, v)
        return out

    out = {k: (walk(v) if isinstance(v, dict) else leaf(k, v))
           for k, v in cache.items() if k != "pos"}
    out["pos"] = jnp.where(slots, 0, cache["pos"])
    return out


# ==========================================================================
# paged layout: tree walkers
# ==========================================================================

_TABLE_KEYS = ("pos", "block_table", "state_table")


def _is_kv_node(node) -> bool:
    return isinstance(node, dict) and (
        ("k" in node and "pos" in node) or "c_kv" in node)


def map_kv_nodes(tree, fn):
    """Apply ``fn`` to every token-indexed cache node (attention ring /
    MLA latent dicts), leaving everything else untouched."""
    if _is_kv_node(tree):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: map_kv_nodes(v, fn) for k, v in tree.items()}
    return tree


def map_state_leaves(tree, fn):
    """Apply ``fn`` to every recurrent-state leaf (any array leaf NOT
    inside a token-indexed node)."""
    if _is_kv_node(tree):
        return tree
    if isinstance(tree, dict):
        return {k: map_state_leaves(v, fn) for k, v in tree.items()}
    return fn(tree)


def _pool_dims(cache) -> Tuple[int, int]:
    """-> (n_pages, n_state_pages) read off the paged cache's leaves
    (0 when the model has no leaves of that kind)."""
    n_pages = n_spages = 0

    def kv(node):
        nonlocal n_pages
        tag = node["pos"]
        if isinstance(tag, tuple):               # per-layer pool leaves
            tag = tag[0]
        n_pages = tag.shape[-2]
        return node

    def stl(a):
        nonlocal n_spages
        n_spages = a.shape[1]
        return a

    for k, v in cache.items():
        if k in _TABLE_KEYS:
            continue
        map_kv_nodes(v, kv)
        map_state_leaves(v, stl)
    return n_pages, n_spages


def apply_cache_ops(cache: Dict, ops, kv_copy_max: int,
                    st_copy_max: int) -> Dict:
    """Apply one batch of host-planned pool edits to the device cache —
    page-tag resets for freshly allocated pages, page copies (COW /
    state snapshot+restore), and table/pos uploads.  Pure and jit-safe;
    the engine fuses it into the compiled dispatch step, so a dirty
    dispatch costs ONE extra host->device transfer (``ops`` is a single
    packed int32 vector, laid out by ``PagedPool._build_ops``) and a
    clean dispatch skips the whole thing (``ops=None`` selects a
    separately-compiled step without it)."""
    has_kv = "block_table" in cache
    has_state = "state_table" in cache
    n_slots = cache["pos"].shape[0]
    n_pages, n_spages = _pool_dims(cache)

    def take(n):
        nonlocal i
        sl = ops[i:i + n]            # static offsets: plain slices
        i += n
        return sl

    i = 0
    out = {"pos": take(n_slots)}
    if has_kv:
        n_blocks = cache["block_table"].shape[1]
        out["block_table"] = take(n_slots * n_blocks).reshape(n_slots,
                                                              n_blocks)
    if has_state:
        out["state_table"] = take(n_slots)
    kv_reset = take(n_pages).astype(bool) if has_kv else None
    kv_src = take(kv_copy_max) if has_kv else None
    kv_dst = take(kv_copy_max) if has_kv else None
    s_reset = take(n_spages).astype(bool) if has_state else None
    s_src = take(st_copy_max) if has_state else None
    s_dst = take(st_copy_max) if has_state else None

    def kv(node):
        # leaves are either layer-stacked ((L, n_pages, page, ...)) or —
        # the serving pool's in-place layout — a per-layer TUPLE of
        # (n_pages, page, ...) arrays (tuple leaves keep every scatter
        # aliasable to its own donated buffer; a stacked leaf threaded
        # through the layer scan gets copied wholesale each iteration)
        node = dict(node)

        def reset(tag):
            m = kv_reset.reshape((-1,) + (1,) * (tag.ndim - 1))
            return jnp.where(m, jnp.full((), -1, tag.dtype), tag)

        def copy(a):
            # pads carry an out-of-bounds index and are dropped (the
            # clamped OOB gather on the src side feeds a dropped write)
            return a.at[kv_dst].set(a[kv_src], mode="drop")

        per_layer = isinstance(node["pos"], tuple)
        if per_layer:
            node["pos"] = tuple(reset(t) for t in node["pos"])
        else:
            tag = node["pos"]
            m = kv_reset.reshape((1, -1) + (1,) * (tag.ndim - 2))
            node["pos"] = jnp.where(m, jnp.full((), -1, tag.dtype), tag)
        if kv_copy_max == 0:         # copy-free round ({0, max} buckets)
            return node
        for key, a in node.items():
            if per_layer:
                node[key] = tuple(copy(x) for x in a)
            else:
                node[key] = a.at[:, kv_dst].set(a[:, kv_src], mode="drop")
        return node

    def stl(a):
        m = s_reset.reshape((1, -1) + (1,) * (a.ndim - 2))
        a = jnp.where(m, jnp.zeros((), a.dtype), a)
        # sequential: a restore may read a snapshot taken earlier in
        # the same batch (pads are OOB and dropped)
        for j in range(st_copy_max):
            a = a.at[:, s_dst[j]].set(a[:, s_src[j]], mode="drop")
        return a

    for k, v in cache.items():
        if k in _TABLE_KEYS:
            continue
        if has_kv:
            v = map_kv_nodes(v, kv)
        if has_state:
            v = map_state_leaves(v, stl)
        out[k] = v
    return out


def ops_counts(cache: Dict, ops, kv_copy_max: int,
               st_copy_max: int) -> Dict:
    """Count the page edits an ops vector will perform — same static
    ``take`` walk as ``apply_cache_ops``, reduced to four int32 scalars
    for the obs device-metrics block.  Jit-safe; copy pads carry an
    out-of-bounds destination (== local page count), so valid copies
    are the in-bounds destinations.  Under ``shard_map`` this sees the
    shard's own ops row against the shard-local page count, making the
    counts shard-local (the metrics block sums rows at read)."""
    has_kv = "block_table" in cache
    has_state = "state_table" in cache
    n_slots = cache["pos"].shape[0]
    n_pages, n_spages = _pool_dims(cache)

    def take(n):
        nonlocal i
        sl = ops[i:i + n]
        i += n
        return sl

    i = n_slots                                  # skip pos upload
    if has_kv:
        i += n_slots * cache["block_table"].shape[1]
    if has_state:
        i += n_slots
    zero = jnp.zeros((), jnp.int32)
    out = {"kv_page_resets": zero, "kv_page_copies": zero,
           "state_page_resets": zero, "state_page_copies": zero}
    if has_kv:
        kv_reset = take(n_pages)
        take(kv_copy_max)                        # kv_src
        kv_dst = take(kv_copy_max)
        out["kv_page_resets"] = kv_reset.astype(bool).sum(
            dtype=jnp.int32)
        if kv_copy_max:
            out["kv_page_copies"] = (kv_dst < n_pages).sum(
                dtype=jnp.int32)
    if has_state:
        s_reset = take(n_spages)
        take(st_copy_max)                        # s_src
        s_dst = take(st_copy_max)
        out["state_page_resets"] = s_reset.astype(bool).sum(
            dtype=jnp.int32)
        if st_copy_max:
            out["state_page_copies"] = (s_dst < n_spages).sum(
                dtype=jnp.int32)
    return out


def _scan_structure(cache) -> Tuple[bool, bool, int]:
    """-> (has_kv, has_state, kv ring length in rows)."""
    has_kv, has_state, ring = False, False, 0

    def kv(node):
        nonlocal has_kv, ring
        has_kv = True
        rows = (node["k"].shape[-3] if "k" in node
                else node["c_kv"].shape[-2])
        ring = max(ring, rows)
        return node

    def st(leaf):
        nonlocal has_state
        has_state = True
        return leaf

    for k, v in cache.items():
        if k in _TABLE_KEYS:
            continue
        map_kv_nodes(v, kv)
        map_state_leaves(v, st)
    return has_kv, has_state, ring


# ==========================================================================
# BlockAllocator: host-side page accounting (property-tested)
# ==========================================================================

class BlockAllocator:
    """Free list + refcounts + per-slot block tables for one page pool.

    Page ids are ints in ``[1, n_pages)``; id 0 is the reserved null
    page (reads of it are masked by -1 position tags) and is never
    allocated.  A page's refcount equals the number of holders: block
    table entries pointing at it plus external retains (prefix-cache
    entries).  ``ref == 1`` with a single table entry means the slot
    owns the page exclusively and may write it in place; ``write_plan``
    enforces that, allocating fresh pages for null entries and
    copy-on-writing shared ones.

    With ``n_shards > 1`` the pool is MESH-SHARDED (ISSUE 5): page ids
    stay global but the id space is partitioned into ``n_shards``
    contiguous ranges of ``pages_per_shard`` — shard ``s`` physically
    holds ids ``[s*pps, (s+1)*pps)`` — and the allocator becomes
    ownership-aware: a page pins to the shard that holds it for its
    whole lifetime, fresh allocations round-robin the shards (most-free
    first) to balance occupancy, and copy-on-write destinations are
    allocated on the SOURCE page's shard so every device page copy is
    shard-local (the packed ops vector splits cleanly per shard, no
    cross-device traffic in ``apply_cache_ops``)."""

    def __init__(self, n_pages: int, n_slots: int, n_blocks: int,
                 n_shards: int = 1):
        assert n_pages >= 2 and n_slots >= 1 and n_blocks >= 1
        assert n_shards >= 1 and n_pages % n_shards == 0, \
            "page count must divide evenly over the mesh shards"
        self.n_pages = n_pages
        self.n_shards = n_shards
        self.pages_per_shard = n_pages // n_shards
        self.table = np.zeros((n_slots, n_blocks), np.int32)
        self.ref = np.zeros((n_pages,), np.int64)
        self.ref[0] = 1                          # null page, pinned
        # per-shard LIFO free lists (shard 0 excludes the null page);
        # n_shards == 1 degenerates to the historical single list
        pps = self.pages_per_shard
        self._free: List[List[int]] = [
            list(range((s + 1) * pps - 1, max(1, s * pps) - 1, -1))
            for s in range(n_shards)]
        self._rr = 0                             # round-robin tiebreak
        # occupancy accounting per shard (current / high-water) — the
        # shard-balance invariants and serve report read these
        self.in_use = np.zeros((n_shards,), np.int64)
        self.hiwater = np.zeros((n_shards,), np.int64)
        # cumulative alloc/free event counts (obs registry export)
        self.events = {"alloc": 0, "free": 0}

    @property
    def free(self) -> List[int]:
        """All free page ids (flattened across shards)."""
        return [p for fl in self._free for p in fl]

    def shard_of(self, page: int) -> int:
        return page // self.pages_per_shard

    # -- primitive ops -----------------------------------------------------
    def alloc(self, prefer: Optional[int] = None) -> Optional[int]:
        """Allocate a page.  ``prefer`` pins the allocation to one shard
        (COW destinations must live on their source's shard); without it
        shards are round-robined most-free-first to balance occupancy."""
        if prefer is not None:
            if not self._free[prefer]:
                return None
            p = self._free[prefer].pop()
        else:
            s = min(range(self.n_shards),
                    key=lambda i: (-len(self._free[i]),
                                   (i - self._rr) % self.n_shards))
            if not self._free[s]:
                return None
            self._rr = (s + 1) % self.n_shards
            p = self._free[s].pop()
        assert self.ref[p] == 0, "free list held a referenced page"
        self.ref[p] = 1
        sh = self.shard_of(p)
        self.in_use[sh] += 1
        self.hiwater[sh] = max(self.hiwater[sh], self.in_use[sh])
        self.events["alloc"] += 1
        return p

    def retain(self, page: int) -> None:
        assert page != 0 and self.ref[page] > 0, "retain of unowned page"
        self.ref[page] += 1

    def unalloc(self, page: int) -> None:
        """Return a just-allocated (sole-ref) page to the free list."""
        assert self.ref[page] == 1, "unalloc of a shared page"
        self.ref[page] = 0
        self._free[self.shard_of(page)].append(page)
        self.in_use[self.shard_of(page)] -= 1
        self.events["free"] += 1

    def drop(self, page: int) -> bool:
        """Drop one reference; returns True if the page was freed."""
        assert page != 0 and self.ref[page] > 0, "drop of unowned page"
        self.ref[page] -= 1
        if self.ref[page] == 0:
            self._free[self.shard_of(page)].append(page)
            self.in_use[self.shard_of(page)] -= 1
            self.events["free"] += 1
            return True
        return False

    # -- table ops ---------------------------------------------------------
    def share(self, slot: int, block: int, page: int) -> None:
        """Point a (null) block-table entry at an existing page."""
        assert self.table[slot, block] == 0, "share over an owned block"
        self.retain(page)
        self.table[slot, block] = page

    def write_plan(self, slot: int, blocks: Sequence[int], alloc=None,
                   on_copy=None) -> Tuple[List[int], List[Tuple[int, int]]]:
        """Make every listed block exclusively owned by ``slot`` before a
        dispatch writes it.  Returns ``(fresh, copies)``: ``fresh`` pages
        were allocated for null entries (device must reset their position
        tags), ``copies`` are (src, dst) copy-on-write pairs (dst is a
        fresh page; src keeps its remaining holders and is NEVER written
        — the COW invariant).  ``on_copy(src, dst)`` fires the moment a
        pair is created — BEFORE any later block's alloc — so the caller
        can pin src against eviction by that very alloc.

        Sharded pools allocate the COW destination on the SOURCE page's
        shard (``alloc(prefer=...)``) so the device copy is shard-local."""
        alloc = alloc or self.alloc
        fresh: List[int] = []
        copies: List[Tuple[int, int]] = []
        for b in blocks:
            cur = int(self.table[slot, b])
            if cur != 0 and self.ref[cur] == 1:
                continue                          # already exclusive
            prefer = (self.shard_of(cur)
                      if cur != 0 and self.n_shards > 1 else None)
            new = alloc(prefer=prefer)
            if new is None:
                raise PoolExhausted("paged KV pool exhausted")
            if cur == 0:
                fresh.append(new)
            else:
                copies.append((cur, new))
                if on_copy is not None:
                    on_copy(cur, new)
                self.drop(cur)                    # ref > 1: never frees
            self.table[slot, b] = new
        return fresh, copies

    def release_slot(self, slot: int) -> List[int]:
        """Drop the slot's references; returns the pages actually freed."""
        freed = []
        for b in np.nonzero(self.table[slot])[0]:
            p = int(self.table[slot, b])
            if self.drop(p):
                freed.append(p)
        self.table[slot, :] = 0
        return freed

    # -- invariants (asserted by the property tests) -----------------------
    def check(self, external_refs: Optional[Dict[int, int]] = None) -> None:
        """No page leaked, no page double-owned: every non-null page is
        either on the free list (ref 0) or referenced, with its refcount
        equal to its holder count (table occurrences + external refs)."""
        free = set(self.free)
        assert len(free) == len(self.free), "free list has duplicates"
        assert 0 not in free and self.ref[0] == 1
        for s, fl in enumerate(self._free):
            assert all(self.shard_of(p) == s for p in fl), \
                f"shard {s} free list holds a foreign page"
        counts = np.bincount(self.table.reshape(-1),
                             minlength=self.n_pages).astype(np.int64)
        counts[0] = 1
        for p, n in (external_refs or {}).items():
            counts[p] += n
        for p in range(1, self.n_pages):
            if p in free:
                assert self.ref[p] == 0, f"page {p} free but referenced"
                assert counts[p] == 0, f"page {p} free but held"
            else:
                assert self.ref[p] == counts[p], \
                    f"page {p}: ref {self.ref[p]} != holders {counts[p]}"
        # occupancy accounting consistent with the refcounts
        owned = np.zeros((self.n_shards,), np.int64)
        for p in range(1, self.n_pages):
            if self.ref[p] > 0:
                owned[self.shard_of(p)] += 1
        assert np.array_equal(owned, self.in_use), \
            f"per-shard in_use {self.in_use} != owned {owned}"


# ==========================================================================
# spill records: host-side page images for preempted slots
# ==========================================================================

# deterministic kv-node leaf order shared by the spill gather and the
# restore scatter (k and v share shape+dtype, so a stable walk order —
# not just stable shapes — is what keeps the flat host lists aligned)
_KV_KEYS = ("k", "v", "c_kv", "k_pe", "pos")


@dataclass
class SpecFork:
    """Host-side restore point for one slot's speculative round: the
    committed position, the block-table row at fork time (tells
    rollback which pages the round allocated fresh), and — for
    recurrent-state families — a backup state page holding the
    pre-round state (a page COPY, because draft/verify dispatches
    advance the live state page in place; KV pages need no backup at
    all — stale future rows self-mask on the causal position check, so
    KV rollback is pure position truncation + fresh-page drop)."""
    slot: int
    pos: int
    kv_row: Optional[np.ndarray] = None
    st_backup: int = 0


@dataclass
class SpillRecord:
    """Host-side image of a preempted slot — everything ``restore``
    needs to resume the request in ANY slot later: the slot's position,
    its last sampled token (the engine splices it back into its
    device-resident pending vector), the content of its exclusively
    owned pages (copied to host), and the ids of its SHARED pages
    (prefix-trie / multi-slot pages are retained by reference instead
    of copied — the trie stays consistent and restore just points the
    new block table back at them)."""
    rid: int = -1
    pos: int = 0
    last_token: int = 0
    kv_kept: List[Tuple[int, int]] = field(default_factory=list)
    kv_blocks: List[int] = field(default_factory=list)
    kv_host: List[np.ndarray] = field(default_factory=list)
    st_host: List[np.ndarray] = field(default_factory=list)
    nbytes: int = 0


def _scatter_spill(cache: Dict, kv_ids, kv_host, st_page, st_host) -> Dict:
    """Upload a spill record's host page images into freshly allocated
    pool pages (jit-safe, cache donated — the restore path's one device
    call).  ``kv_host``/``st_host`` are flat tuples in the same walk
    order ``PagedPool._gather_kv_pages``/``_gather_state`` produced;
    empty tuples skip that half entirely (static pytree structure)."""
    kv_it = iter(kv_host)

    def kv(node):
        node = dict(node)
        for key in _KV_KEYS:
            if key not in node:
                continue
            v = node[key]
            if isinstance(v, tuple):
                node[key] = tuple(a.at[kv_ids].set(next(kv_it))
                                  for a in v)
            else:
                node[key] = v.at[:, kv_ids].set(next(kv_it))
        return node

    st_it = iter(st_host)

    def stl(a):
        return a.at[:, st_page].set(next(st_it))

    out = {}
    for k, v in cache.items():
        if k in _TABLE_KEYS:
            out[k] = v
            continue
        if len(kv_host):
            v = map_kv_nodes(v, kv)
        if len(st_host):
            v = map_state_leaves(v, stl)
        out[k] = v
    return out


# ==========================================================================
# PagedPool: device pool + prefix caching on top of the allocator
# ==========================================================================

class PagedPool:
    """The paged serving cache: builds the device pytree, owns the host
    allocators and the prefix cache, and turns host-side decisions into
    ONE packed ops vector per dirty dispatch (``drain``) that the engine
    fuses into its compiled step — clean dispatches upload nothing and
    run a separately-compiled step without the apply.

    The device cache is NOT stored here — ``build()`` returns it and
    every mutating method takes and returns it (the engine owns the
    single live copy because the dispatch step donates it).

    With ``n_shards > 1`` (and the serving page ``mesh``) the pools are
    MESH-SHARDED: every ``(stack, n_pages, ...)`` leaf is partitioned on
    its page axis across the mesh's page dimension, the allocators
    become ownership-aware (see ``BlockAllocator``), and ``_build_ops``
    emits one packed ops ROW per shard — resets and copies routed to the
    shard that physically holds the pages, with shard-LOCAL indices — so
    ``apply_cache_ops`` runs unchanged inside ``shard_map``."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 chunk: int = 0, page: int = 0, dtype=None,
                 spare_pages: Optional[int] = None,
                 snap_slots: Optional[int] = None,
                 prefix_cache: bool = True, n_shards: int = 1,
                 mesh=None):
        chunk = chunk or cfg.serve_chunk
        page = page or cfg.serve_page
        assert page >= 1
        assert n_shards >= 1
        self.cfg, self.n_slots, self.max_len = cfg, n_slots, max_len
        self.chunk, self.page = chunk, page
        self.n_shards, self.mesh = n_shards, mesh
        api = get_model(cfg)
        assert api.cache_init is not None, f"{cfg.name} has no decode cache"
        proto = api.cache_init(ring_cfg(cfg, chunk), 1, max_len,
                               dtype or cfg.jdtype)
        self.has_kv, self.has_state, rows = _scan_structure(proto)
        self._proto = proto
        # ring length rounded up to a page multiple: position p maps to
        # ring row p % ring, block r // page, offset r % page
        self.n_blocks = max(1, -(-rows // page)) if self.has_kv else 0
        self.ring = self.n_blocks * page
        if self.has_kv:
            spare = (n_slots * self.n_blocks if spare_pages is None
                     else spare_pages)
            n_pages = 1 + n_slots * self.n_blocks + spare
            n_pages += (-n_pages) % n_shards     # even split per shard
            self.n_pages = n_pages
            self.kv = BlockAllocator(self.n_pages, n_slots, self.n_blocks,
                                     n_shards)
        else:
            self.n_pages, self.kv = 0, None
        if self.has_state:
            n_snap = (n_slots if (snap_slots is None and prefix_cache)
                      else (snap_slots or 0))
            # one live page per slot + one spare per slot (admission
            # cycles to a fresh page before the old one is dropped) +
            # the snapshot budget; page 0 reserved as null for symmetry
            n_spages = 1 + 2 * n_slots + n_snap
            n_spages += (-n_spages) % n_shards
            self.n_spages = n_spages
            self.st = BlockAllocator(self.n_spages, n_slots, 1, n_shards)
            for s in range(n_slots):
                self.st.table[s, 0] = self.st.alloc()
        else:
            self.n_spages, self.st = 0, None
        self.prefix = PrefixCache(page) if prefix_cache else None
        self.pos = np.zeros((n_slots,), np.int64)
        self.counters = {
            "prefix_queries": 0, "prefix_hits": 0, "tokens_reused": 0,
            "pages_shared": 0, "pages_published": 0, "pages_cowed": 0,
            "pages_evicted": 0, "snapshots": 0, "snap_restores": 0,
        }
        # pending device ops, applied by the next flush
        self._kv_reset: set = set()
        self._kv_copies: List[Tuple[int, int]] = []
        self._st_reset: set = set()
        self._st_copies: List[Tuple[int, int]] = []
        self._dirty = False
        # pages held alive BY REFERENCE for spilled (preempted) requests
        # — shared pages are not copied to host, their SpillRecord just
        # retains them ({page: n_holds}; see ``spill``/``restore``)
        self._spill_kv: Dict[int, int] = {}
        self.spill_events = {"spills": 0, "restores": 0,
                             "spilled_bytes": 0}
        self._scatter = (jax.jit(_scatter_spill, donate_argnums=(0,))
                         if mesh is None else None)
        self.kv_copy_max = max(1, n_slots * (chunk // page + 2))
        # restores + snapshots per dispatch rarely exceed the slot
        # count; bursts overflow into extra pre-step apply rounds
        self.st_copy_max = max(1, n_slots)
        # per-dispatch copy pad widths of the LAST ``_build_ops`` round
        # ({0, copy_max} buckets) — the engine passes them into its
        # fused step as static args
        self.last_pads: Tuple[int, int] = (self.kv_copy_max,
                                           self.st_copy_max)
        assert n_shards == 1 or mesh is not None, \
            "sharded pool needs the page mesh"
        if mesh is None:
            self._apply = jax.jit(
                lambda cache, ops, pads: apply_cache_ops(cache, ops,
                                                         *pads),
                static_argnums=(2,), donate_argnums=(0,))
        else:
            # mesh present (even 1-shard): ops come as per-shard rows,
            # so the standalone apply must be the shard_map one —
            # built by build() (needs the cache's partition specs)
            self._apply = None

    # -- device cache ------------------------------------------------------
    def build(self) -> Dict:
        """Allocate the paged device cache (all pools zeroed, position
        tags -1, block tables null, state table at each slot's page)."""
        n_pages, page, n_spages = self.n_pages, self.page, self.n_spages

        def kv(node):
            # per-LAYER tuple leaves, one (n_pages, page, ...) array per
            # stack entry: the layer loop unrolls over tuple elements so
            # each page-pool scatter aliases its own donated buffer
            # in-place (a single stacked leaf threaded through lax.scan
            # is copied wholesale every layer on CPU backends)
            out = {}
            stack = None
            for key in ("k", "v", "c_kv", "k_pe"):
                if key in node:
                    a = node[key]
                    lead, feat = a.shape[:-3], a.shape[-1:]
                    if key in ("k", "v"):
                        feat = a.shape[-2:]
                        lead = a.shape[:-4]
                    assert len(lead) == 1, f"kv node {key}: lead {lead}"
                    stack = lead[0]
                    out[key] = tuple(
                        jnp.zeros((n_pages, page) + feat, a.dtype)
                        for _ in range(stack))
            out["pos"] = tuple(jnp.full((n_pages, page), -1, jnp.int32)
                               for _ in range(stack))
            return out

        def st(a):
            return jnp.zeros(a.shape[:1] + (n_spages,) + a.shape[2:],
                             a.dtype)

        cache: Dict = {}
        for k, v in self._proto.items():
            if k in _TABLE_KEYS:
                continue
            v = map_kv_nodes(v, kv)
            v = map_state_leaves(v, st)
            cache[k] = v
        cache["pos"] = jnp.zeros((self.n_slots,), jnp.int32)
        if self.has_kv:
            cache["block_table"] = jnp.zeros((self.n_slots, self.n_blocks),
                                             jnp.int32)
        if self.has_state:
            cache["state_table"] = jnp.asarray(self.st.table[:, 0],
                                               jnp.int32)
        if self.mesh is not None:
            # place the pools page-sharded on the mesh and compile the
            # standalone (overflow-round) apply as a shard_map step
            # (mesh-keyed, like _build_ops' per-shard rows — a 1-shard
            # mesh still takes this path)
            from repro.serving.mesh import (cache_partition_specs,
                                            shard_cache, sharded_apply)
            specs = cache_partition_specs(cache)
            cache = shard_cache(cache, self.mesh, specs)
            self._apply = sharded_apply(self.mesh, specs)
        return cache

    def _take_copies(self, pending: List[Tuple[int, int]], alloc,
                     budget: int):
        """Pop up to ``budget`` pending copies PER SHARD (routed by the
        src page's shard — COW/snapshot destinations are allocated on
        the same shard, asserted), dropping each emitted pair's
        pending-src pin.  Returns (src, dst) local-index arrays shaped
        (n_shards, budget); pads are the OOB sentinel ``pages_per_shard``
        — dropped by ``apply_cache_ops``'s scatter.  A (0, 0) self-copy
        pad would COLLIDE with a real copy whose destination is local
        page 0 (on shards >= 1 that is an allocatable page, unlike the
        global null page), and a duplicate-index scatter may let the
        stale pad win over the real copy."""
        P_, pps = alloc.n_shards, alloc.pages_per_shard
        src = np.full((P_, budget), pps, np.int32)
        dst = np.full((P_, budget), pps, np.int32)
        fill = [0] * P_
        rest: List[Tuple[int, int]] = []
        for s, d in pending:
            sh = alloc.shard_of(s)
            assert alloc.shard_of(d) == sh, \
                "page copy crosses shards (allocator ownership bug)"
            if fill[sh] < budget:
                src[sh, fill[sh]] = s - sh * pps
                dst[sh, fill[sh]] = d - sh * pps
                fill[sh] += 1
                alloc.drop(s)            # release the pending-src pin
            else:
                rest.append((s, d))
        pending[:] = rest
        return src, dst

    def _take_resets(self, reset: set, alloc) -> np.ndarray:
        """Pending page-tag resets as (n_shards, pages_per_shard) rows
        of shard-local flags; clears the set."""
        P_, pps = alloc.n_shards, alloc.pages_per_shard
        out = np.zeros((P_, pps), np.int32)
        for p in reset:
            out[alloc.shard_of(p), p % pps] = 1
        reset.clear()
        return out

    def _build_ops(self):
        """Materialise ONE round of pending edits as a packed int32
        vector (layout mirrored by ``apply_cache_ops``) — one
        host->device transfer per dirty dispatch.  Sharded pools emit
        one ROW per shard, (n_shards, row_len): the replicated sections
        (pos, block/state tables, global ids) are duplicated into every
        row while resets and copies carry shard-LOCAL page indices, so
        each shard applies exactly its own edits inside shard_map."""
        P_ = self.n_shards
        base = [np.asarray(self.pos, np.int32)]
        if self.has_kv:
            base.append(self.kv.table.reshape(-1).astype(np.int32))
        if self.has_state:
            base.append(self.st.table[:, 0].astype(np.int32))
        # copy pads bucket to {0, copy_max}: the common dirty dispatch
        # (fresh page allocated — resets + table upload, NO copies)
        # would otherwise gather-and-drop copy_max pages per pool leaf
        # inside the fused step, a pure ineffectual-work tax.  The pad
        # widths ride to ``apply_cache_ops`` as static args
        # (``last_pads``), so each bucket is its own executable.
        kv_parts = st_parts = None
        kv_pad = self.kv_copy_max if self._kv_copies else 0
        st_pad = self.st_copy_max if self._st_copies else 0
        if self.has_kv:
            reset = self._take_resets(self._kv_reset, self.kv)
            src, dst = self._take_copies(self._kv_copies, self.kv, kv_pad)
            kv_parts = (reset, src, dst)
        if self.has_state:
            reset = self._take_resets(self._st_reset, self.st)
            src, dst = self._take_copies(self._st_copies, self.st, st_pad)
            st_parts = (reset, src, dst)
        self.last_pads = (kv_pad if self.has_kv else 0,
                          st_pad if self.has_state else 0)
        rows = []
        for s in range(P_):
            parts = list(base)
            if kv_parts is not None:
                parts += [p[s] for p in kv_parts]
            if st_parts is not None:
                parts += [p[s] for p in st_parts]
            rows.append(np.concatenate(parts))
        if self.mesh is None:
            return jnp.asarray(rows[0])      # single-device: flat vector
        return jnp.asarray(np.stack(rows))   # sharded step: one row/shard

    def drain(self, cache: Dict) -> Tuple[Dict, Optional[jnp.ndarray]]:
        """-> (cache, ops): the pending edits as ONE packed vector for
        the engine to fuse into its compiled step, or None when clean
        (the engine's clean-step executable skips the apply entirely).
        Overflow rounds (more COW/snapshot copies than the pad width —
        rare) are applied to the cache directly."""
        if not self._dirty:
            return cache, None
        ops = self._build_ops()
        while self._kv_copies or self._st_copies:
            cache = self._apply(cache, ops, self.last_pads)
            ops = self._build_ops()
        self._dirty = False
        return cache, ops

    def flush(self, cache: Dict) -> Dict:
        """Apply all pending edits now (standalone jitted call — the
        engine prefers ``drain`` + its fused step).  No-op when clean."""
        cache, ops = self.drain(cache)
        if ops is not None:
            cache = self._apply(cache, ops, self.last_pads)
        return cache

    # -- pending page copies: the src is PINNED (one extra ref) from
    # queueing until ``_build_ops`` emits the pair, so no interleaved
    # eviction/free can recycle it and reset/zero it ahead of the copy
    def _push_kv_copy(self, src: int, dst: int) -> None:
        self.kv.retain(src)
        self._kv_copies.append((src, dst))
        self._kv_reset.add(dst)
        self._dirty = True

    def _push_st_copy(self, src: int, dst: int) -> None:
        self.st.retain(src)
        self._st_copies.append((src, dst))
        self._dirty = True

    # -- allocation with prefix-cache eviction -----------------------------
    # ``prefer`` pins the allocation (and, when eviction is needed to
    # satisfy it, the eviction hunt) to one mesh shard: COW and
    # snapshot-restore destinations must live on their source's shard
    # ``reset=False`` (spill restore) allocates a page whose CONTENT is
    # about to be uploaded from host — queuing the usual tag reset would
    # wipe that upload at the next dispatch, so the pending reset (if a
    # rolled-back admission left one behind on this id) is discarded
    def _kv_alloc(self, prefer: Optional[int] = None,
                  reset: bool = True) -> Optional[int]:
        p = self.kv.alloc(prefer=prefer)
        while p is None and self.prefix is not None:
            # evict only entries whose page actually frees (an entry
            # still shared into a live slot reclaims nothing — keep it
            # for future hits); same for snapshots via their kv pages
            pg = self.prefix.evict_lru_page(
                lambda q: self.kv.ref[q] == 1 and
                (prefer is None or self.kv.shard_of(q) == prefer))
            if pg is not None:
                self.kv.drop(pg)
                self.counters["pages_evicted"] += 1
            else:
                e = self.prefix.evict_lru_snap(
                    lambda s: any(
                        self.kv.ref[q] == 1 and
                        (prefer is None or self.kv.shard_of(q) == prefer)
                        for q in s.kv_pages))
                if e is None:
                    break
                self._drop_snap(e)
            p = self.kv.alloc(prefer=prefer)
        if p is not None:
            if reset:
                self._kv_reset.add(p)
            else:
                self._kv_reset.discard(p)
            self._dirty = True
        return p

    def _st_alloc(self, prefer: Optional[int] = None,
                  reset: bool = True) -> Optional[int]:
        p = self.st.alloc(prefer=prefer)
        while p is None and self.prefix is not None:
            # a pinned snapshot (mid-restore this step) has spage ref
            # > 1 and is excluded; everything else frees its state page
            e = self.prefix.evict_lru_snap(
                lambda s: self.st.ref[s.spage] == 1 and
                (prefer is None or self.st.shard_of(s.spage) == prefer))
            if e is None:
                break
            self._drop_snap(e)
            p = self.st.alloc(prefer=prefer)
        if p is not None:
            if reset:
                self._st_reset.add(p)
            else:
                self._st_reset.discard(p)
            self._dirty = True
        return p

    def _drop_snap(self, e) -> None:
        if self.st.drop(e.spage):
            self.counters["pages_evicted"] += 1
        for pg in e.kv_pages:
            if self.kv.drop(pg):
                self.counters["pages_evicted"] += 1

    # -- engine lifecycle ---------------------------------------------------
    def admit(self, slot: int, prompt: np.ndarray) -> int:
        """Attach a fresh request to ``slot``: match the prompt against
        the prefix cache, share hit pages / restore the hit snapshot,
        cycle the slot onto a fresh state page, and reset its position.
        Returns the number of leading tokens whose prefill is skipped
        (always < len(prompt): the last token is recomputed to produce
        the first sampled logit)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_cached = 0
        shared_pages: List[int] = []
        snap = None
        if self.prefix is not None:
            self.counters["prefix_queries"] += 1
            limit = len(prompt) - 1
            if self.has_state:
                snap = self.prefix.match_state(prompt, limit)
                if snap is not None:
                    n_cached = snap.n_tokens
                    shared_pages = snap.kv_pages
            elif self.has_kv:
                shared_pages = self.prefix.match_pages(prompt, limit)
                n_cached = len(shared_pages) * self.page
            if n_cached:
                self.counters["prefix_hits"] += 1
                self.counters["tokens_reused"] += n_cached
                self.counters["pages_shared"] += len(shared_pages)
        for i, pg in enumerate(shared_pages):
            self.kv.share(slot, i, pg)
        if self.has_state:
            if snap is not None:
                # pin the matched snapshot across the alloc below: its
                # eviction would free (and possibly recycle) the very
                # page the restore copy is about to read
                self.st.retain(snap.spage)
            # a restore copies snapshot -> fresh page: the fresh page
            # must live on the snapshot's shard (shard-local copy)
            prefer = (self.st.shard_of(snap.spage)
                      if snap is not None and self.n_shards > 1 else None)
            new = self._st_alloc(prefer=prefer)
            if new is None:
                # ROLL BACK before surfacing the failure: the shared
                # prefix pages were already retained into the slot's
                # table and the snapshot pinned — leaving them leaks
                # refcounts and half-attaches the slot (the old
                # RuntimeError path did exactly that).  After rollback
                # the failure is deferrable: the scheduler keeps the
                # request queued and the engine may spill a victim.
                if snap is not None:
                    self.st.drop(snap.spage)     # release the admit pin
                for i in range(len(shared_pages)):
                    pg = int(self.kv.table[slot, i])
                    self.kv.table[slot, i] = 0
                    self.kv.drop(pg)
                raise PoolExhausted("paged state pool exhausted")
            old = int(self.st.table[slot, 0])
            if old:
                self.st.drop(old)
            self.st.table[slot, 0] = new
            if snap is not None:
                self._push_st_copy(snap.spage, new)
                self.st.drop(snap.spage)         # release the admit pin
                self.counters["snap_restores"] += 1
        self.pos[slot] = n_cached
        self._dirty = True
        return n_cached

    def plan_writes(self, n_valid: np.ndarray) -> None:
        """Pre-dispatch (host only): make every page this dispatch will
        write exclusively owned — fresh alloc for null blocks,
        copy-on-write for shared ones."""
        if not self.has_kv:
            return
        for s, nv in enumerate(np.asarray(n_valid)):
            if nv <= 0:
                continue
            p0 = int(self.pos[s])
            blocks = sorted({(p % self.ring) // self.page
                             for p in range(p0, p0 + int(nv))})
            fresh, copies = self.kv.write_plan(s, blocks,
                                               alloc=self._kv_alloc,
                                               on_copy=self._push_kv_copy)
            self.counters["pages_cowed"] += len(copies)
            if fresh:
                self._dirty = True

    def prepare(self, cache: Dict, n_valid: np.ndarray) -> Dict:
        """plan_writes + standalone flush (the engine instead drains the
        ops into its fused compiled step)."""
        self.plan_writes(n_valid)
        return self.flush(cache)

    def advance(self, n_valid: np.ndarray) -> None:
        self.pos += np.asarray(n_valid, np.int64)

    def active_blocks(self, n_valid: np.ndarray) -> Optional[int]:
        """Block-table width this dispatch actually NEEDS (host-side,
        count-based — no device sync): every position any slot has
        written or will write this step lies below
        ``max(pos + n_valid)``, so block-table columns past
        ``ceil(need / page)`` hold only null pages — ineffectual rows
        the attend would gather, mask and softmax for nothing.  The
        engine slices the table to this width inside its compiled step.

        Safety: a width ``W < n_blocks`` changes the ring modulus to
        ``W * page``, which is only sound while no slot has wrapped —
        ``pos + n_valid`` is clamped to ``ring`` so any wrap (windowed
        rings) forces the full width.  The result is bucketed to the
        next power of two blocks (capped at ``n_blocks``) — coarse
        enough that the engine compiles O(log n_blocks) step variants
        (each a whole-model program: at published widths every variant
        costs seconds of compile), fine enough that the attend width
        stays within 2x of the longest live sequence instead of
        snapping to the full ring."""
        if not self.has_kv:
            return None
        need = int(np.minimum(self.pos + np.asarray(n_valid, np.int64),
                              self.ring).max(initial=0))
        w = max(1, -(-need // self.page))
        return min(1 << (w - 1).bit_length(), self.n_blocks)

    def maybe_snapshot(self, slot: int, prompt: np.ndarray,
                       offset: int) -> None:
        """Called just before the dispatch that finishes ``slot``'s
        prompt: snapshot the recurrent state at ``offset`` (page-aligned
        chunk boundary) keyed by ``prompt[:offset]``, retaining the
        shared-attention pages below it for hybrid models."""
        if self.prefix is None or not self.has_state:
            return
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if offset <= 0 or offset % self.page or offset > len(prompt) - 1:
            return
        if self.has_kv and offset > self.ring:
            return                       # ring wrapped: pages incomplete
        if self.prefix.has_state(prompt, offset):
            return
        cur = int(self.st.table[slot, 0])
        spage = self._st_alloc(
            prefer=self.st.shard_of(cur) if self.n_shards > 1 else None)
        if spage is None:
            return                       # snapshot budget exhausted
        self._push_st_copy(cur, spage)
        kv_pages: List[int] = []
        if self.has_kv:
            kv_pages = [int(self.kv.table[slot, i])
                        for i in range(offset // self.page)]
            for pg in kv_pages:
                self.kv.retain(pg)
        self.prefix.insert_state(prompt, offset, spage, kv_pages)
        self.counters["snapshots"] += 1
        self._dirty = True

    def publish(self, slot: int, prompt: np.ndarray) -> None:
        """Called when ``slot`` finishes prefill (attention families):
        publish the full pages of its prompt into the prefix trie.
        Prompts longer than the sliding-window ring have wrapped by now
        (pages hold the TAIL positions, not the prefix) — those were
        already published at the last pre-wrap page boundary by
        ``maybe_publish_prewrap``, so nothing is lost here."""
        if self.prefix is None or not self.has_kv or self.has_state:
            return
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) > self.ring:
            return                       # ring wrapped: prewrap published
        n_full = (len(prompt) // self.page) * self.page
        new = self.prefix.insert_pages(
            prompt, n_full, lambda i: self.kv.table[slot, i])
        for pg in new:
            self.kv.retain(pg)
        self.counters["pages_published"] += len(new)

    def maybe_publish_prewrap(self, slot: int, prompt: np.ndarray,
                              offset: int, take: int) -> None:
        """Close the windowed-prompt prefix-cache gap (ROADMAP): a
        sliding-window prompt longer than its ring used to publish
        NOTHING — by the time prefill ends the ring has wrapped and the
        pages hold the tail, not the prefix.  Called pre-dispatch for
        every prefilling slot about to consume ``take`` tokens at
        ``offset``: on the dispatch that first writes past the ring,
        publish a state-snapshot-style entry at the LAST PRE-WRAP page
        boundary — full pages [0, offset) for attention families, the
        recurrent-state snapshot at ``offset`` (page-aligned) for
        ssm/hybrid — while the prefix is still intact."""
        if self.prefix is None or not self.has_kv:
            return
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) <= self.ring:
            return                       # no wrap: publish() covers it
        if not (offset <= self.ring < offset + take):
            return                       # not the wrap-crossing dispatch
        if self.has_state:               # hybrid: snapshot-style entry
            self.maybe_snapshot(slot, prompt, offset)
            return
        n_full = (min(offset, len(prompt) - 1) // self.page) * self.page
        if n_full <= 0:
            return
        new = self.prefix.insert_pages(
            prompt, n_full, lambda i: self.kv.table[slot, i])
        for pg in new:
            self.kv.retain(pg)
        self.counters["pages_published"] += len(new)

    def release(self, slot: int) -> None:
        """Evict a finished request: drop its page refs (pages still
        pinned by the prefix cache survive for future hits)."""
        if self.has_kv:
            self.kv.release_slot(slot)
        self.pos[slot] = 0
        self._dirty = True

    # -- preemption: spill / restore ---------------------------------------
    def _gather_kv_pages(self, cache: Dict, ids: List[int]
                         ) -> List[np.ndarray]:
        """Device -> host copy of kv-pool pages ``ids`` as a flat list
        in the fixed ``_KV_KEYS`` walk order (``_scatter_spill`` replays
        the identical walk on restore)."""
        out: List[np.ndarray] = []
        idx = np.asarray(ids, np.int32)

        def kv(node):
            # walk order must match _scatter_spill exactly — k and v
            # share shape+dtype so a swap would corrupt silently
            for key in _KV_KEYS:
                if key not in node:
                    continue
                v = node[key]
                if isinstance(v, tuple):
                    out.extend(np.asarray(a[idx]) for a in v)
                else:
                    out.append(np.asarray(v[:, idx]))
            return node

        for k, v in cache.items():
            if k in _TABLE_KEYS:
                continue
            map_kv_nodes(v, kv)
        return out

    def _gather_state(self, cache: Dict, spage: int) -> List[np.ndarray]:
        out: List[np.ndarray] = []

        def stl(a):
            out.append(np.asarray(a[:, spage]))
            return a

        for k, v in cache.items():
            if k in _TABLE_KEYS:
                continue
            map_state_leaves(v, stl)
        return out

    def spill(self, slot: int, cache: Dict
              ) -> Tuple[Dict, SpillRecord]:
        """Preempt ``slot``: move its cache content off the device pool
        so the pages can serve other requests, returning a record
        ``restore`` can replay into ANY free slot later.

        Shared pages (prefix trie / other slots hold refs) are NOT
        copied — the record retains them by reference, so a restore is
        free for the prefix-hit part of the sequence and the trie stays
        consistent throughout.  Exclusive pages and the slot's recurrent
        state are copied to host; pending COW/snapshot edits are flushed
        FIRST so the copies read post-edit content.  The slot keeps its
        state page attached (every live slot always owns one — admission
        cycles it), only the CONTENT moves."""
        assert self.mesh is None, \
            "spill/restore is single-device (layout='paged')"
        cache = self.flush(cache)
        rec = SpillRecord(pos=int(self.pos[slot]))
        if self.has_kv:
            copied: List[Tuple[int, int]] = []
            for b in np.nonzero(self.kv.table[slot])[0]:
                pg = int(self.kv.table[slot, b])
                if self.kv.ref[pg] > 1:
                    self.kv.retain(pg)
                    self._spill_kv[pg] = self._spill_kv.get(pg, 0) + 1
                    rec.kv_kept.append((int(b), pg))
                else:
                    copied.append((int(b), pg))
            if copied:
                rec.kv_blocks = [b for b, _ in copied]
                rec.kv_host = self._gather_kv_pages(
                    cache, [pg for _, pg in copied])
            self.kv.release_slot(slot)
        if self.has_state:
            rec.st_host = self._gather_state(
                cache, int(self.st.table[slot, 0]))
        self.pos[slot] = 0
        self._dirty = True
        rec.nbytes = int(sum(a.nbytes for a in rec.kv_host + rec.st_host))
        self.spill_events["spills"] += 1
        self.spill_events["spilled_bytes"] += rec.nbytes
        return cache, rec

    def restore(self, slot: int, rec: SpillRecord, cache: Dict) -> Dict:
        """Re-admit a spilled request into (free) ``slot``: allocate
        fresh pages for the copied content, re-attach the
        retained-by-reference shared pages, upload the host images in
        one jitted donated scatter, and restore the position.  All
        allocation happens BEFORE any table mutation — on exhaustion the
        fresh pages are returned and ``PoolExhausted`` surfaces with the
        pool unchanged (the engine can spill another victim and retry)."""
        assert self.mesh is None
        fresh: List[int] = []
        for _ in rec.kv_blocks:
            p = self._kv_alloc(reset=False)
            if p is None:
                for q in fresh:
                    self.kv.unalloc(q)
                raise PoolExhausted("paged KV pool exhausted (restore)")
            fresh.append(p)
        st_new = 0
        if self.has_state:
            st_new = self._st_alloc(reset=False)
            if st_new is None:
                for q in fresh:
                    self.kv.unalloc(q)
                raise PoolExhausted(
                    "paged state pool exhausted (restore)")
        if self.has_kv:
            assert not self.kv.table[slot].any(), "restore into live slot"
            for b, pg in rec.kv_kept:
                # the spill hold becomes the table's ref — no net change
                self.kv.table[slot, b] = pg
                n = self._spill_kv[pg] - 1
                if n:
                    self._spill_kv[pg] = n
                else:
                    del self._spill_kv[pg]
            for b, p in zip(rec.kv_blocks, fresh):
                self.kv.table[slot, b] = p
        if self.has_state:
            old = int(self.st.table[slot, 0])
            self.st.table[slot, 0] = st_new
            if old:
                self.st.drop(old)
        self.pos[slot] = rec.pos
        self._dirty = True
        if rec.kv_host or rec.st_host:
            cache = self._scatter(
                cache, jnp.asarray(fresh or [0], jnp.int32),
                tuple(rec.kv_host), st_new, tuple(rec.st_host))
        self.spill_events["restores"] += 1
        return cache

    # -- speculative decoding: fork / rollback ------------------------------
    # A speculative round is a block-table operation, not a cache copy:
    # fork records the committed position + the slot's block-table row
    # and backs up the recurrent state to a spare page; rollback drops
    # the pages the round allocated past the accepted prefix and
    # truncates the position.  KV content never moves — draft rows past
    # the committed position carry tags > any future query position and
    # self-mask on the causal check, and the next dispatch's
    # write-before-attend overwrites the committed frontier row.

    def spec_fork(self, slot: int) -> SpecFork:
        """Host-side restore point for ``slot`` before a speculative
        round.  Raises ``PoolExhausted`` when no spare state page is
        available for the backup (the caller falls back to vanilla
        decode for this step)."""
        rec = SpecFork(slot=slot, pos=int(self.pos[slot]))
        if self.has_kv:
            rec.kv_row = self.kv.table[slot].copy()
        if self.has_state:
            backup = self._st_alloc(reset=False)
            if backup is None:
                raise PoolExhausted(
                    "paged state pool exhausted (spec fork)")
            rec.st_backup = backup
            # content copy rides the next drain, BEFORE the first draft
            # dispatch advances the live page in place
            self._push_st_copy(int(self.st.table[slot, 0]), backup)
        return rec

    def spec_set_pos(self, slot: int, pos: int) -> None:
        """Host-side position override (pre-verify reset to the fork
        point / post-verify truncation to the accepted prefix); dirties
        the pool so the next drain re-uploads the position row."""
        self.pos[slot] = int(pos)
        self._dirty = True

    def spec_restore_state(self, rec: SpecFork) -> None:
        """Queue backup -> live state-page copy (the live page holds
        draft-advanced or over-verified state; the backup holds the
        state at the fork point)."""
        if rec.st_backup:
            self._push_st_copy(rec.st_backup,
                               int(self.st.table[rec.slot, 0]))

    def spec_rollback_pages(self, rec: SpecFork, committed_pos: int
                            ) -> int:
        """Drop blocks the round allocated FRESH entirely past the
        accepted prefix (null in the fork row, live now, first position
        >= committed).  COW'd blocks are kept — their shared source was
        already released by write_plan, and their stale draft rows
        self-mask.  Fresh blocks only exist pre-wrap, where block ``b``
        covers positions ``[b*page, (b+1)*page)`` exactly, so the
        position test is well defined.  Returns the drop count."""
        if not self.has_kv:
            return 0
        dropped = 0
        for b in range(self.n_blocks):
            pg = int(self.kv.table[rec.slot, b])
            if pg and rec.kv_row[b] == 0 and \
                    b * self.page >= committed_pos:
                self.kv.drop(pg)
                self.kv.table[rec.slot, b] = 0
                dropped += 1
        if dropped:
            self._dirty = True
        return dropped

    def spec_drop_backup(self, rec: SpecFork) -> None:
        """Release the state backup page.  Safe while a restore copy is
        still queued: ``_push_st_copy`` pinned the source until
        ``_build_ops`` emits the pair."""
        if rec.st_backup:
            self.st.drop(rec.st_backup)
            rec.st_backup = 0

    def spec_abort(self, rec: SpecFork) -> None:
        """Unwind a round that died mid-flight (pool exhausted during a
        draft/verify plan): truncate to the fork point, drop any pages
        the partial round allocated, restore the state backup.  Handles
        ``write_plan``'s partial mutation on raise — the fork-row diff
        covers exactly the blocks it touched."""
        self.spec_rollback_pages(rec, rec.pos)
        if rec.st_backup:
            self.spec_restore_state(rec)
            self.spec_drop_backup(rec)
        self.spec_set_pos(rec.slot, rec.pos)

    def external_refs(self, table: str = "kv") -> Dict[int, int]:
        """Refcount holders OUTSIDE the block tables — prefix-trie
        retains, pending-copy source pins, and spilled requests' kept
        pages — keyed by page id, in the shape
        ``BlockAllocator.check`` expects (invariant audits in tests)."""
        refs: Dict[int, int] = {}

        def add(p: int, n: int = 1) -> None:
            if p:
                refs[p] = refs.get(p, 0) + n

        if table == "kv":
            if self.prefix is not None:
                for p, n in self.prefix.page_refs().items():
                    add(p, n)
            for s, _ in self._kv_copies:
                add(s)
            for p, n in self._spill_kv.items():
                add(p, n)
        else:
            if self.prefix is not None:
                for p, n in self.prefix.state_refs().items():
                    add(p, n)
            for s, _ in self._st_copies:
                add(s)
        return refs

    # -- reporting ----------------------------------------------------------
    def alloc_events(self) -> Dict:
        """Cumulative allocator alloc/free event counts per table."""
        out: Dict = {}
        if self.has_kv:
            out["kv_alloc"] = self.kv.events["alloc"]
            out["kv_free"] = self.kv.events["free"]
        if self.has_state:
            out["state_alloc"] = self.st.events["alloc"]
            out["state_free"] = self.st.events["free"]
        return out

    def reset_event_counters(self) -> None:
        """Zero the cumulative event counters (prefix counters + alloc
        events); occupancy/hiwater accounting is left intact."""
        for k in self.counters:
            self.counters[k] = 0
        for k in self.spill_events:
            self.spill_events[k] = 0
        for al in (self.kv, self.st):
            if al is not None:
                al.events = {"alloc": 0, "free": 0}

    def shard_report(self) -> Dict:
        """Per-shard page occupancy: current in-use and high-water marks
        (the null page on shard 0 is excluded by the allocator's
        accounting — it is pinned, never allocated)."""
        rep: Dict = {"n_shards": self.n_shards}
        if self.has_kv:
            rep["kv_pages_per_shard"] = self.kv.pages_per_shard
            rep["kv_pages_in_use_per_shard"] = self.kv.in_use.tolist()
            rep["kv_pages_hiwater_per_shard"] = self.kv.hiwater.tolist()
        if self.has_state:
            rep["state_pages_per_shard"] = self.st.pages_per_shard
            rep["state_pages_in_use_per_shard"] = self.st.in_use.tolist()
            rep["state_pages_hiwater_per_shard"] = self.st.hiwater.tolist()
        return rep

    def report(self) -> Dict:
        rep = {
            "page": self.page, "n_blocks": self.n_blocks,
            "ring": self.ring, "n_pages": self.n_pages,
            "n_state_pages": self.n_spages,
            "prefix_caching": self.prefix is not None,
        }
        if self.has_kv:
            rep["pages_in_use"] = int(np.sum(self.kv.ref > 0) - 1)
        if any(self.spill_events.values()):
            rep.update({f"spill_{k}": v
                        for k, v in self.spill_events.items()})
        if self.n_shards > 1:
            rep["sharding"] = self.shard_report()
        if self.prefix is not None:
            q = max(self.counters["prefix_queries"], 1)
            n_pages, n_snaps = self.prefix.n_entries
            rep.update(self.counters,
                       hit_rate=self.counters["prefix_hits"] / q,
                       trie_pages=n_pages, trie_snapshots=n_snaps)
        return rep
