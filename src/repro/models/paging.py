"""Paged KV-cache subsystem: page pools, refcounted allocator, prefix index.

Block-paged KV management (the PagedAttention design) replaces the serving
engine's one-ring-per-slot reservation with a *pool* of fixed-size pages per
attention layer. A request REFERENCES only the pages that cover the tokens
it has actually produced, so short requests stop stranding the HBM the
scheduler budgeted for ``max_len`` — and the freed memory converts into
admitted traffic. The saving composes multiplicatively with NBL: linearized
layers carry NO pool at all (paper §4.2), so m of K layers linearized
shrinks the per-request page bill by m/K on top of the page-granular
allocation — and, under prefix sharing, the reduction applies to the shared
pool too (shared pages exist only in caching attention layers).

Reference semantics (copy-on-write prefix sharing)
--------------------------------------------------
Pages are REFCOUNTED, not owned. ``PageAllocator.alloc`` hands out pages at
refcount 1; ``ref`` pins extra holders; ``unref`` (alias ``free``) drops
one reference and a page returns to the free list only at refcount 0. Both
``ref`` and ``unref`` are ATOMIC: the whole id list — including duplicate
ids within one call — is validated against current refcounts before any
mutation, so a rejected call leaves the allocator exactly as it found it.

Sharing is copy-on-write by construction rather than by copying: a shared
page is always a FULL prompt-prefix page, and every writer (suffix prefill,
decode) lands at positions at or beyond its slot's first divergent page, so
shared pages are never written after publication — a "write" to a shared
logical range is simply a fresh page for the writing slot. The last
(partial) page of a prompt is never shared.

``PrefixIndex`` is the host-side radix/trie over prompt-token page-chunks:
each full page of a previously-served prompt prefix maps its ``page_size``
tokens to the physical page that caches them. The index holds one
reference per mapped page, so published prefixes survive the publishing
request's retirement (the retiring slot only ``unref``s). On admission the
engine looks up the longest page-aligned cached prefix, ``ref``s the hit
pages, points the new slot's page-table row at them, and prefills only the
suffix. Under pool pressure, UNREFERENCED index entries (refcount 1 — held
by nothing but the index) are evicted leaf-first in LRU order BEFORE any
request is preempted; billing (launch/scheduler.nbl_page_budget) counts
pages referenced with shared pages billed once.

Layout
------
Every caching attention layer owns one pool pair, stacked over the group's
scan dim exactly like the slot cache:

    k_pages / v_pages : (L, n_pages, KV, page_size, hd)

Pages are POSITION-ALIGNED: logical page ``l`` of a request always holds
absolute positions [l*page_size, (l+1)*page_size). Validity is therefore
derivable from the request's current length — no per-token ``kpos`` array
exists in the paged layout. Sliding-window layers keep full-length pages and
mask in the kernel (they trade the ring's compaction for page sharing).

One page TABLE is shared by all layers (allocation is synchronized: a page
id is valid in every layer's pool simultaneously). It lives on the HOST as
an ``(n_slots, pages_per_seq)`` int32 array owned by the engine, entries -1
= unallocated, and is passed to the decode jit as a regular (tiny) argument
— appending a page mid-decode is a host-side table write, never a cache-tree
surgery.

Non-attention state (SSM, conv, cross-attn KV) is not pageable (it is O(1)
per slot, not O(seq)); those blocks keep the slot-indexed layout from
``kv_cache.init_slot_cache`` inside the same cache tree.

Unit of account: ``page_bytes(cfg, page_size)`` is the byte size of ONE page
in ONE layer — the scheduler's page budget (launch/scheduler.nbl_page_budget)
divides an HBM budget by (caching layers x page_bytes) to size the pool.

Scatter/gather safety: -1 table entries would *wrap* under numpy indexing
semantics, so every device-side consumer sanitizes ids first —
``sanitize_page_ids`` maps negatives to ``n_pages`` (out of bounds, dropped
by scatter mode="drop"); gathers clip to 0 and rely on the position mask.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.kv_cache import _block_cache

DEFAULT_PAGE_SIZE = 64


def pages_per_seq(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1): the bucketing unit for
    prompt lengths AND prefix tables — ONE definition so the engine's jit
    keys (launch/engine.py) and the dry-run input shapes (launch/specs.py)
    can never disagree about which widths actually compile."""
    return 1 << max(0, (int(n) - 1).bit_length())


def span_pages(start_tok: int, end_tok: int,
               page_size: int) -> tuple[int, int]:
    """Logical page range [start_pg, end_pg) covering the token span
    [start_tok, end_tok) — the chunk-granular allocation unit of the
    engine's chunked prefill. ``start_tok`` must be page-aligned: a chunk
    resumes only on a page boundary (its prefix table covers whole pages)."""
    assert start_tok % page_size == 0, (start_tok, page_size)
    assert end_tok > start_tok, (start_tok, end_tok)
    return start_tok // page_size, pages_per_seq(end_tok, page_size)


def n_caching_attn_layers(cfg: ModelConfig) -> int:
    """Attention invocations that carry a KV pool (shared blocks count once
    per invocation, like their caches; nbl/drop/mamba/cross contribute 0)."""
    return sum(1 for b in cfg.blocks() if b.kind == "attn")


def page_bytes(cfg: ModelConfig, page_size: int) -> int:
    """Bytes of ONE page in ONE attention layer (K + V)."""
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    return 2 * page_size * cfg.n_kv_heads * cfg.head_dim * itemsize


def pool_pages_for_budget(cfg: ModelConfig, budget_bytes: int,
                          page_size: int) -> Optional[int]:
    """Per-layer pool size (pages) a byte budget buys across all caching
    layers. None when the stack has no caching attention layer at all."""
    a = n_caching_attn_layers(cfg)
    if a == 0:
        return None
    return int(budget_bytes // (a * page_bytes(cfg, page_size)))


def sanitize_page_ids(ids: jax.Array, n_pages: int) -> jax.Array:
    """Map unallocated (-1) entries to an out-of-bounds id so scatters with
    mode="drop" skip them instead of wrapping to the last page."""
    return jnp.where(ids >= 0, ids, n_pages).astype(jnp.int32)


# --------------------------------------------------------------- pools ------

def init_paged_cache(cfg: ModelConfig, n_slots: int, max_len: int, *,
                     page_size: int = DEFAULT_PAGE_SIZE,
                     n_pages: Optional[int] = None):
    """Cache tree for the paged serving engine. Attention blocks get page
    pools; SSM/conv/cross-attn blocks keep slot-indexed state rows. The tree
    mirrors the stack plan ({"groups": [{"blocks": [...]}]}), so the stack
    executor scans it unchanged."""
    if n_pages is None:
        n_pages = n_slots * pages_per_seq(max_len, page_size)
    dtype = jnp.dtype(cfg.compute_dtype)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    groups = []
    for g in cfg.stack:
        blocks = []
        for blk in g.unit:
            stack = g.repeat
            if blk.kind == "attn":
                shp = (stack, n_pages, kv, page_size, hd)
                blocks.append({"k_pages": jnp.zeros(shp, dtype),
                               "v_pages": jnp.zeros(shp, dtype)})
            else:
                blocks.append(_block_cache(cfg, blk, n_slots, max_len, stack,
                                           dtype, per_slot_pos=True))
        groups.append({"blocks": blocks})
    return {"groups": groups}


def kv_pool_bytes_per_device(cache) -> int:
    """Bytes of the K and V page pools on the device that holds the most
    of them (0 for a cache without pools). Read from the arrays' shards,
    so a pool sharded over a mesh counts each device's part."""
    held: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if str(getattr(path[-1], "key", "")) in ("k_pages", "v_pages"):
            for sh in leaf.addressable_shards:
                held[sh.device] = held.get(sh.device, 0) + sh.data.nbytes
    return max(held.values(), default=0)


def assign_pages(cfg: ModelConfig, paged_cache, prefill_cache, slot,
                 page_ids, *, page_size: int):
    """Write a batch=1 POSITION-ALIGNED prefill cache into the page pools.

    ``prefill_cache`` must come from ``prefill(..., paged=True)`` with
    ``cache_len`` a multiple of ``page_size`` (no ring wrap). ``page_ids``
    holds >= cache_len // page_size int32 entries (a full page-table row is
    fine); entry i is the physical page for logical page i, -1 for prompt
    pages that were never allocated (bucket padding) — those tiles are
    dropped. Non-attention
    block state is written into slot row ``slot`` wholesale, so a recycled
    slot's SSM/conv/cross state can never leak across tenancies.
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)

    def row_assign(dst, src):
        if src.ndim == dst.ndim - 1:            # kpos (L, W) -> (L, 1, W)
            src = src[:, None]
        idx = (0, slot) + (0,) * (dst.ndim - 2)
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), idx)

    def page_assign(dst, src):                  # src: (L, 1, KV, S, hd)
        l, _, kv, s, hd = src.shape
        npg = s // page_size
        assert npg * page_size == s and npg <= page_ids.shape[0], \
            (s, page_size, page_ids.shape)
        ids = page_ids[:npg]
        tiles = src[:, 0].reshape(l, kv, npg, page_size, hd)
        tiles = tiles.transpose(0, 2, 1, 3, 4).astype(dst.dtype)
        ids = sanitize_page_ids(ids, dst.shape[1])
        return dst.at[:, ids].set(tiles, mode="drop")

    new_groups = []
    for gi, g in enumerate(cfg.stack):
        blocks = []
        for u, blk in enumerate(g.unit):
            dst = paged_cache["groups"][gi]["blocks"][u]
            src = prefill_cache["groups"][gi]["blocks"][u]
            if dst is None:
                blocks.append(None)
            elif blk.kind == "attn":
                blocks.append({"k_pages": page_assign(dst["k_pages"], src["k"]),
                               "v_pages": page_assign(dst["v_pages"], src["v"])})
            else:
                blocks.append(jax.tree.map(row_assign, dst, src))
        new_groups.append({"blocks": blocks})
    return {"groups": new_groups}


# ----------------------------------------------------------- allocator ------

class DoubleFreeError(RuntimeError):
    pass


@dataclass
class PageAllocator:
    """Host-side REFCOUNTED free-list allocator over page ids [0, n_pages).

    alloc is all-or-nothing (returns None when the pool cannot satisfy the
    request — the caller reclaims or defers) and hands pages out at
    refcount 1. ``ref`` pins additional holders (prefix sharing: a slot
    pointing its page table at an already-cached prefix, or the prefix
    index publishing a page); ``unref`` — ``free`` is an alias — drops one
    reference, and the page returns to the free list only at refcount 0.

    ref/unref are ATOMIC: the whole id list is validated first (duplicate
    ids in one call count once per occurrence against the refcount), so a
    rejected call never leaves the allocator half-mutated. Retirement stays
    copy-free: a page released at refcount 0 goes back untouched, and
    isolation is positional (a reallocated page's stale tokens sit at
    positions the new holder has not reached, hence masked; they are
    overwritten before ever becoming valid).

    Threading: the allocator (and ``PrefixIndex``) is STEP-THREAD-ONLY —
    every mutation happens inside ``Engine.step()``/``cancel()``, which the
    AsyncEngine serializes on its step loop (client-thread cancels go
    through the inbox, never here directly). That single-owner rule is why
    there are no locks and no ``# guarded-by:`` annotations in this module;
    ``repro.analysis`` checks the annotated engine state that upholds it.
    """
    n_pages: int
    _free: list = field(default_factory=list)
    _refs: dict = field(default_factory=dict)     # pid -> refcount >= 1
    peak_in_use: int = 0

    def __post_init__(self):
        self._free = list(range(self.n_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._refs)

    def refcount(self, pid: int) -> int:
        return self._refs.get(pid, 0)

    def refcount_histogram(self) -> dict:
        """``{refcount: n_pages}`` over live pages — how shared the pool is
        (rc 1 = private or index-only, rc >= 2 = actively shared). O(in_use)
        on the host; the obs step timeline records it every step."""
        hist: dict = {}
        for c in self._refs.values():
            hist[c] = hist.get(c, 0) + 1
        return hist

    def alloc(self, n: int) -> Optional[list[int]]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for pid in ids:
            self._refs[pid] = 1
        self.peak_in_use = max(self.peak_in_use, len(self._refs))
        return ids

    def ref(self, ids) -> None:
        """Add one reference per occurrence of each id. Atomic: every id
        must be allocated or nothing is referenced."""
        ids = list(ids)
        for pid in ids:                           # validate, then mutate
            if pid not in self._refs:
                raise DoubleFreeError(f"page {pid} is not allocated")
        for pid in ids:
            self._refs[pid] += 1

    def unref(self, ids) -> None:
        """Drop one reference per occurrence of each id; a page returns to
        the free list at refcount 0. Atomic: the whole list — duplicate ids
        counted per occurrence — is validated against current refcounts
        before any mutation, so a raising call changes nothing."""
        ids = list(ids)
        need: dict = {}
        for pid in ids:
            need[pid] = need.get(pid, 0) + 1
        for pid, n in need.items():               # validate, then mutate
            if self._refs.get(pid, 0) < n:
                raise DoubleFreeError(
                    f"page {pid}: {n} release(s) requested but refcount is "
                    f"{self._refs.get(pid, 0)}")
        for pid in ids:
            self._refs[pid] -= 1
            if self._refs[pid] == 0:
                del self._refs[pid]
                self._free.append(pid)

    free = unref                                  # pre-refcount API name

    def check_invariants(self) -> None:
        """Free-list conservation: referenced and free pages partition
        [0, n_pages), and every live refcount is >= 1."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate ids on free list"
        assert not (free & self._refs.keys()), "page both free and referenced"
        assert free | self._refs.keys() == set(range(self.n_pages)), \
            "page lost"
        assert all(c >= 1 for c in self._refs.values()), "zombie refcount"


# ---------------------------------------------------------- prefix index ---

class _TrieNode:
    __slots__ = ("children", "page", "last_used")

    def __init__(self, page: int, clock: int):
        self.children: dict = {}                  # chunk tokens -> _TrieNode
        self.page = page                          # physical page id
        self.last_used = clock


class PrefixIndex:
    """Host-side radix/trie over prompt-token page-chunks.

    Each node maps one FULL page of a previously-served prompt prefix —
    keyed by its ``page_size`` token values, position-implicit through its
    trie depth — to the physical page already holding that prefix's KV in
    every caching layer (allocation is layer-synchronized, so one id names
    the page in all pools). The index holds ONE allocator reference per
    mapped page (taken at ``insert``), which is what lets a published
    prefix outlive the request that prefilled it.

    ``lookup`` returns the longest page-aligned cached prefix of a prompt,
    capped at ``(len(prompt) - 1) // page_size`` pages so the admission
    suffix always contains at least the final prompt token (its logits seed
    decoding); the last (partial) page is never indexed at all. ``evict_lru``
    drops the least-recently-used leaf whose page nothing but the index
    references (refcount 1) — leaf-first keeps every surviving node
    reachable from the root, and skipping still-referenced pages means
    eviction only runs when it actually frees pool capacity.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self.root: dict = {}                      # chunk tokens -> _TrieNode
        self._clock = 0
        self.n_entries = 0
        self.n_evictions = 0                      # lifetime LRU pages dropped

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _chunk(self, prompt, i: int) -> tuple:
        ps = self.page_size
        return tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])

    def lookup(self, prompt) -> tuple[int, list[int]]:
        """Longest cached page-aligned proper prefix of ``prompt``: returns
        (n_pages, physical ids). Touches each hit node's LRU stamp."""
        max_k = max(0, (len(prompt) - 1) // self.page_size)
        node_map, ids = self.root, []
        now = self._tick()
        for i in range(max_k):
            node = node_map.get(self._chunk(prompt, i))
            if node is None:
                break
            node.last_used = now
            ids.append(node.page)
            node_map = node.children
        return len(ids), ids

    def insert(self, prompt, page_ids, allocator: PageAllocator) -> int:
        """Publish every FULL page of ``prompt`` (len // page_size chunks;
        ``page_ids[i]`` is chunk i's physical page). Newly-created nodes
        take one allocator reference; chunks already indexed keep their
        existing mapping (identical tokens at identical positions produce
        identical KV, so either physical page is valid — the incumbent
        stays, avoiding a ref/unref churn). Returns #new entries."""
        n_full = len(prompt) // self.page_size
        node_map, added = self.root, 0
        now = self._tick()
        for i in range(n_full):
            key = self._chunk(prompt, i)
            node = node_map.get(key)
            if node is None:
                pid = int(page_ids[i])
                allocator.ref([pid])
                node = _TrieNode(pid, now)
                node_map[key] = node
                self.n_entries += 1
                added += 1
            else:
                node.last_used = now
            node_map = node.children
        return added

    def evictable_pages(self, allocator: PageAllocator) -> int:
        """EXACT count of pages leaf-first eviction could free: an entry is
        reclaimable iff its page has refcount 1 AND its whole subtree is
        reclaimable — an rc-1 node above a still-referenced descendant
        (possible under SWA window release, where a slot drops a parent
        page but keeps referencing a child's) never becomes a leaf while
        that descendant lives. Exactness is what lets _reclaim_pages keep
        its all-or-nothing promise: a reclaim that would fall short evicts
        nothing."""
        nodes = []                                # parents before children
        stack = [self.root]
        while stack:
            node_map = stack.pop()
            for node in node_map.values():
                nodes.append(node)
                if node.children:
                    stack.append(node.children)
        ok: dict = {}                             # id(node) -> reclaimable
        count = 0
        for node in reversed(nodes):              # children first
            r = allocator.refcount(node.page) == 1 and \
                all(ok[id(c)] for c in node.children.values())
            ok[id(node)] = r
            count += r
        return count

    def evict_lru(self, allocator: PageAllocator, max_pages: int = 1) -> int:
        """Drop up to ``max_pages`` LRU *leaf* entries whose pages only the
        index references (refcount 1), unref'ing their pages back to the
        free list — one trie walk collects every candidate, so reclaiming
        k pages costs one traversal per cascade level (evicting a leaf can
        expose its parent), not one per page. Returns the number of pages
        freed; 0 means no evictable leaf exists and the caller must fall
        back to preemption."""
        cand: list[tuple] = []                    # (last_used, parent, key)
        stack = [self.root]                       # iterative: a prefix can
        while stack:                              # be 1000s of pages deep
            node_map = stack.pop()
            for key, node in node_map.items():
                if node.children:
                    stack.append(node.children)
                elif allocator.refcount(node.page) == 1:
                    cand.append((node.last_used, node_map, key))
        cand.sort(key=lambda c: c[0])
        freed = 0
        for _, parent, key in cand[:max(0, max_pages)]:
            node = parent.pop(key)
            self.n_entries -= 1
            allocator.unref([node.page])
            freed += 1
        self.n_evictions += freed
        return freed


def release_tail_pages(page_tbl_row: np.ndarray, committed_len: int,
                       page_size: int, allocator: PageAllocator) -> list[int]:
    """Speculative-rollback helper: free every allocated logical page of one
    slot's table row STRICTLY beyond the page containing position
    ``committed_len`` (the next position the slot will write). Because pages
    are position-aligned, rejecting draft tokens needs no kpos repair — the
    committed length itself is the rollback, and this just returns the
    surplus candidate-span pages (always private: _ensure_decode_pages
    allocated them fresh, shared prefix pages live at the head of the row)
    to the pool. Mutates ``page_tbl_row`` in place (-1 = unallocated) and
    returns the freed physical ids (possibly empty)."""
    keep = committed_len // page_size             # last page still writable
    freed = [int(page_tbl_row[l])
             for l in range(keep + 1, page_tbl_row.shape[0])
             if page_tbl_row[l] >= 0]
    if freed:
        page_tbl_row[keep + 1:] = -1
        allocator.unref(freed)
    return freed


# --------------------------------------------------------------- stats ------

def build_page_table(n_slots: int, max_len: int,
                     page_size: int) -> np.ndarray:
    return np.full((n_slots, pages_per_seq(max_len, page_size)), -1, np.int32)
