"""Paged KV-cache subsystem: kernel parity, engine parity vs the ring
decode path, recycled-page isolation, refcounted allocator invariants,
prefix sharing (copy-on-write pages), chunked prefill (page-aligned
prefill-decode interleaving), page budget, preemption, and prompt-length
bucketing."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.configs import get_config
from repro.core.surgery import compress_config, nbl_variant
from repro.launch.engine import Engine
from repro.launch.scheduler import latency_stats, nbl_page_budget, Request
from repro.launch.serve import generate
from repro.models import init_params
from repro.models.paging import (
    DoubleFreeError, PageAllocator, PrefixIndex, n_caching_attn_layers,
    page_bytes, pages_per_seq,
)


def _setup(arch="tiny-dense", seed=0):
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _ref(cfg, params, prompt, max_new):
    out = generate(cfg, params, jnp.asarray(prompt)[None], max_new=max_new)
    return np.asarray(out)[0]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ----------------------------------------------------- kernel parity -------

_RAGGED = (18, 5, 32, 0)      # mid-page, one page, page-exact, inactive


@pytest.mark.parametrize("rep,kv,window,softcap,lens", [
    (1, 2, None, None, _RAGGED),       # MHA
    (2, 2, None, None, _RAGGED),       # GQA
    (2, 2, 6, None, _RAGGED),          # GQA + sliding window
    (2, 2, None, 30.0, _RAGGED),       # GQA + logit softcap
    (2, 2, 6, 30.0, _RAGGED),
    (1, 6, None, None, _RAGGED),       # many KV heads, rep 1 (MiniCPM)
    (2, 2, 12, None, (29, 32, 20, 14)),  # window starts past page 0
    (2, 2, None, None, (16, 8, 32, 24)),  # spans end on page boundaries
    (2, 2, None, None, (32, 0, 32, 0)),   # empty rows beside full ones
], ids=["1-None-None", "2-None-None", "2-6-None", "2-None-30.0", "2-6-30.0",
        "kv6-rep1", "window-mid-sequence", "page-boundary", "empty-rows"])
def test_paged_kernel_matches_xla_ref(rep, kv, window, softcap, lens):
    """Interpret-mode Pallas kernel == XLA gather reference across
    GQA/window/softcap and head counts, with ragged lengths, inactive
    rows (all-zero output) and, under a window, pages below it
    unallocated as the engine's window release leaves them."""
    from repro.kernels.paged_attention import paged_attention, paged_decode_xla

    rng = np.random.default_rng(0)
    b, hd, ps, npg, pool = 4, 16, 8, 4, 16
    q = jnp.asarray(rng.standard_normal((b, kv, rep, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pool, kv, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pool, kv, ps, hd)), jnp.float32)
    tbl = np.full((b, npg), -1, np.int32)
    free = iter([4, 7, 1, 2, 0, 3, 5, 6, 8, 9, 10, 11, 12, 13, 14, 15])
    for i, n in enumerate(lens):
        # an inactive row may hold a page: paged_mixed's invalid virtual
        # rows repeat their slot's table
        for j in range(max(-(-n // ps), 1)):
            tbl[i, j] = next(free)
    if window is not None:
        tbl[0, :max(lens[0] - window, 0) // ps] = -1   # released below it
    ln = jnp.asarray(lens, jnp.int32)

    out = paged_attention(q, kp, vp, jnp.asarray(tbl), ln,
                          window=window, softcap=softcap, interpret=True)
    ref = paged_decode_xla(q, kp, vp, jnp.asarray(tbl), ln,
                           window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out)[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("rep,window,softcap", [
    (1, None, None),
    (2, 6, None),
    (2, None, 30.0),
])
def test_paged_mixed_matches_virtual_rows(rep, window, softcap):
    """The fused step's mixed-row attention (one per-slot gather + dense
    masked softmax) == the same queries run as B*W virtual decode rows
    through the interpret-mode Pallas kernel — the TPU dispatch route —
    across a decode row, a mid-chunk row, an inactive row, and a short
    row with an invalid tail."""
    from repro.kernels.paged_attention import paged_attention, paged_mixed_xla

    rng = np.random.default_rng(1)
    b, kv, hd, ps, npg, pool, w = 4, 2, 16, 8, 4, 12, 4
    q = jnp.asarray(rng.standard_normal((b, kv, rep, w, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pool, kv, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pool, kv, ps, hd)), jnp.float32)
    tbl = np.full((b, npg), -1, np.int32)
    tbl[0, :3] = [4, 7, 1]          # decode row at pos 17 (18 tokens)
    tbl[1, :2] = [2, 8]             # chunk row resuming at pos 8
    tbl[2, :1] = [3]                # short row: 2 valid + 2 invalid tail
    row_pos = jnp.asarray([17, 8, 1, 0], jnp.int32)
    row_len = jnp.asarray([1, w, 2, 0], jnp.int32)   # slot 3 inactive

    out = paged_mixed_xla(q, kp, vp, jnp.asarray(tbl), row_pos, row_len,
                          window=window, softcap=softcap)

    qv = jnp.transpose(q, (0, 3, 1, 2, 4)).reshape(b * w, kv, rep, hd)
    tpos = np.asarray(row_pos)[:, None] + np.arange(w)[None, :]
    valid = np.arange(w)[None, :] < np.asarray(row_len)[:, None]
    lens = jnp.asarray(np.where(valid, tpos + 1, 0).reshape(-1), jnp.int32)
    ref = paged_attention(qv, kp, vp,
                          jnp.asarray(np.repeat(tbl, w, axis=0)), lens,
                          window=window, softcap=softcap, interpret=True)
    ref = ref.reshape(b, w, kv, rep, hd).transpose(0, 2, 3, 1, 4)
    vmask = valid[:, None, None, :, None]            # invalid: both finite,
    np.testing.assert_allclose(np.asarray(out) * vmask,     # values differ
                               np.asarray(ref) * vmask, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------ engine decode parity -----

@pytest.mark.parametrize("arch", ["tiny-dense", "tiny-swa", "tiny-gemma",
                                  "tiny-zamba"])
def test_paged_engine_parity_matches_generate(arch):
    """Greedy tokens from the paged engine match the single-request
    generate() loop across dense / sliding-window / softcap / hybrid-SSM
    stacks (the paged analogue of the ring parity test)."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, [6, 10, 8])
    refs = [_ref(cfg, params, p, 5) for p in prompts]

    eng = Engine(cfg, params, max_len=24, n_slots=2, paged=True, page_size=8)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i], err_msg=f"req {i}")


def test_paged_engine_parity_nbl_compressed():
    """Paged serving of an NBL-compressed stack: linearized layers carry no
    page pool, and decode parity with generate() is exact."""
    cfg, _ = _setup()
    ncfg = compress_config(cfg, cfg.attn_layer_indices()[-2:], "nbl")
    params = init_params(jax.random.PRNGKey(1), ncfg)
    prompts = _prompts(ncfg, [7, 9])
    refs = [_ref(ncfg, params, p, 4) for p in prompts]

    eng = Engine(ncfg, params, max_len=16, n_slots=2, paged=True, page_size=8)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i])


def test_paged_ring_same_tokens_under_load():
    """The two engines emit identical per-request tokens for an identical
    ragged stream (bit-comparable decode paths at the token level)."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [4, 12, 6, 9, 5], seed=3)
    outs = {}
    for paged in (False, True):
        eng = Engine(cfg, params, max_len=20, n_slots=2, paged=paged,
                     page_size=8)
        rids = [eng.submit(p, 4) for p in prompts]
        got = eng.run()
        outs[paged] = [got[r] for r in rids]
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


# -------------------------------------------- recycled-page isolation ------

def test_recycled_pages_no_stale_kv():
    """Sequential tenancy through ONE slot: the second request reuses the
    first tenant's freed pages (same physical ids), and its tokens must be
    identical to a fresh engine's — any stale KV surviving the position
    mask would corrupt them."""
    cfg, params = _setup()
    long_p, short_p = _prompts(cfg, [14, 4], seed=11)

    eng = Engine(cfg, params, max_len=20, n_slots=1, paged=True, page_size=4)
    rid_a = eng.submit(long_p, 6)
    rid_b = eng.submit(short_p, 6)
    out = eng.run()
    assert len(out[rid_a]) == 6
    assert eng.allocator.in_use == 0            # all pages back on free list

    fresh = Engine(cfg, params, max_len=20, n_slots=1, paged=True,
                   page_size=4)
    rid_f = fresh.submit(short_p, 6)
    np.testing.assert_array_equal(out[rid_b], fresh.run()[rid_f])
    np.testing.assert_array_equal(out[rid_b], _ref(cfg, params, short_p, 6))


def test_freed_pages_not_attendable_by_new_owner():
    """Direct paged-cache check (the paged analogue of reset_slot's
    guarantee): after a request's pages are freed and handed to a new
    request, decode logits depend only on the new owner's tokens — asserted
    by comparing against a pool that never had a previous tenant."""
    from repro.models import decode_step, prefill
    from repro.models.paging import (assign_pages, build_page_table,
                                     init_paged_cache)

    cfg, params = _setup()
    ps, max_len = 4, 16
    old_p, new_p = _prompts(cfg, [12, 5], seed=21)

    def run_once(cache, tbl, prompt, page_ids):
        logits, pc = prefill(cfg, params, jnp.asarray(prompt)[None],
                             cache_len=pages_per_seq(len(prompt), ps) * ps,
                             paged=True)
        tbl = tbl.copy()
        npg = pages_per_seq(len(prompt), ps)
        tbl[0, :npg] = page_ids[:npg]
        cache = assign_pages(cfg, cache, pc, jnp.int32(0),
                             jnp.asarray(tbl[0]), page_size=ps)
        tok = jnp.argmax(logits[0, -1])[None, None].astype(jnp.int32)
        out, _ = decode_step(cfg, params, tok, cache,
                             jnp.asarray([len(prompt)], jnp.int32),
                             page_tbl=jnp.asarray(tbl))
        return np.asarray(out)

    tbl0 = build_page_table(1, max_len, ps)
    # tenancy 1: old_p occupies pages [0,1,2]; then "freed" (table cleared)
    dirty = init_paged_cache(cfg, 1, max_len, page_size=ps, n_pages=4)
    logits, pc = prefill(cfg, params, jnp.asarray(old_p)[None],
                         cache_len=12, paged=True)
    dirty = assign_pages(cfg, dirty, pc, jnp.int32(0),
                         jnp.asarray([0, 1, 2], jnp.int32), page_size=ps)
    # tenancy 2 on the DIRTY pool reuses pages [0,1] for the new prompt
    got = run_once(dirty, tbl0, new_p, [0, 1])
    clean = init_paged_cache(cfg, 1, max_len, page_size=ps, n_pages=4)
    want = run_once(clean, tbl0, new_p, [0, 1])
    np.testing.assert_allclose(got, want, atol=1e-6)


# ------------------------------------------------------ prefix sharing -----

def _shared_prompts(cfg, sys_len, tails, seed=0):
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(0, cfg.vocab_size, sys_len).astype(np.int32)
    return [np.concatenate([sys_p, rng.integers(0, cfg.vocab_size, t)
                            .astype(np.int32)]) for t in tails]


@pytest.mark.parametrize("arch", ["tiny-dense", "tiny-swa", "tiny-gemma"])
def test_prefix_sharing_engine_parity(arch):
    """Shared-prefix batch served with prefix_sharing=True emits EXACTLY
    the single-request generate() tokens across dense-GQA / sliding-window
    / softcap stacks, and later admissions reuse the cached prefix (the
    suffix-only prefill path)."""
    cfg, params = _setup(arch)
    prompts = _shared_prompts(cfg, 17, [4, 7, 3, 5], seed=2)
    refs = [_ref(cfg, params, p, 5) for p in prompts]

    eng = Engine(cfg, params, max_len=48, n_slots=2, paged=True, page_size=8,
                 prefix_sharing=True)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i], err_msg=f"req {i}")
    s = eng.stats()
    assert s["n_prefix_hits"] >= 3         # every follower hit the index
    assert s["n_shared_prompt_tokens"] >= 3 * 16
    assert s["n_prefill_tokens"] < sum(len(p) for p in prompts)
    eng.allocator.check_invariants()


@pytest.mark.parametrize("m", [1, 2])
def test_prefix_sharing_parity_nbl_compressed(m):
    """Prefix sharing over NBL-compressed stacks: linearized layers carry
    no pool (nothing to share there) and token parity stays exact — the
    m/K page-bill reduction applies to the shared pool too."""
    cfg, _ = _setup()
    ncfg = compress_config(cfg, cfg.attn_layer_indices()[-m:], "nbl")
    params = init_params(jax.random.PRNGKey(1), ncfg)
    prompts = _shared_prompts(ncfg, 18, [3, 6, 4], seed=4)
    refs = [_ref(ncfg, params, p, 4) for p in prompts]

    eng = Engine(ncfg, params, max_len=40, n_slots=2, paged=True,
                 page_size=8, prefix_sharing=True)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i])
    assert eng.stats()["n_prefix_hits"] >= 2


def test_ring_vs_paged_sharing_same_tokens():
    """The ring engine and the paged engine WITH sharing emit identical
    per-request tokens on an identical shared-prefix stream."""
    cfg, params = _setup()
    prompts = _shared_prompts(cfg, 17, [4, 9, 2, 6, 5], seed=7)
    outs = {}
    for mode in ("ring", "shared"):
        kw = {} if mode == "ring" else dict(paged=True, page_size=8,
                                            prefix_sharing=True)
        eng = Engine(cfg, params, max_len=40, n_slots=2, **kw)
        rids = [eng.submit(p, 4) for p in prompts]
        got = eng.run()
        outs[mode] = [got[r] for r in rids]
    for a, b in zip(outs["ring"], outs["shared"]):
        np.testing.assert_array_equal(a, b)


def test_retiring_owner_keeps_shared_pages_alive():
    """A slot retiring while its prefix pages are still referenced (by the
    index, and transitively by a follower slot) must NOT free them: the
    refcount holds, the follower's decode stays exact, and the pages leave
    the pool only after every reference is dropped."""
    cfg, params = _setup()
    prompts = _shared_prompts(cfg, 17, [2, 6], seed=9)
    refs = [_ref(cfg, params, p, n) for p, n in zip(prompts, (2, 8))]

    # one slot: the publisher retires (short generation) while the index
    # still references its prefix pages; the follower then shares them.
    eng = Engine(cfg, params, max_len=40, n_slots=1, paged=True, page_size=8,
                 prefix_sharing=True)
    rid_a = eng.submit(prompts[0], 2)
    rid_b = eng.submit(prompts[1], 8)
    out = eng.run()
    np.testing.assert_array_equal(out[rid_a], refs[0])
    np.testing.assert_array_equal(out[rid_b], refs[1])
    s = eng.stats()
    assert s["n_prefix_hits"] == 1          # B reused A's published prefix
    # retirement dropped only the slots' references; the index still pins
    # its entries — nothing was freed that something still referenced.
    assert eng.allocator.in_use == eng.prefix_index.n_entries > 0
    eng.allocator.check_invariants()


def test_index_eviction_then_realloc_no_leakage():
    """Pages released at refcount 0 (after LRU index eviction under pool
    pressure) and REALLOCATED to a different prompt show no token-level
    leakage: the new tenant's output equals a fresh engine's."""
    cfg, params = _setup()
    a = _shared_prompts(cfg, 16, [3], seed=11)[0]
    b = _shared_prompts(cfg, 16, [4], seed=99)[0]   # disjoint prompt
    ref_b = _ref(cfg, params, b, 6)

    # pool too small to keep A's prefix cached while B runs: admitting B
    # must evict A's unreferenced index entries and reuse those pages.
    eng = Engine(cfg, params, max_len=32, n_slots=1, paged=True, page_size=8,
                 prefix_sharing=True)
    from repro.models.paging import PageAllocator as PA
    eng.allocator = PA(4)                   # = pages_per_seq(32, 8): 1 req
    eng.n_pages = 4
    rid_a = eng.submit(a, 4)
    rid_b = eng.submit(b, 6)
    out = eng.run(max_steps=200)
    assert len(out[rid_a]) == 4
    np.testing.assert_array_equal(out[rid_b], ref_b)
    assert eng.prefix_index.n_entries <= 2  # A's entries were evicted
    eng.allocator.check_invariants()


def test_prefix_index_lookup_insert_evict():
    """Index unit semantics: longest page-aligned PROPER prefix, last
    (partial or final) page never indexed/shared, leaf-first LRU eviction
    restricted to refcount-1 pages."""
    idx = PrefixIndex(4)
    alloc = PageAllocator(8)
    prompt = np.arange(10)                  # pages: [0..4) [4..8) | partial
    ids = alloc.alloc(3)
    assert idx.insert(prompt, ids, alloc) == 2     # 10 // 4 full pages
    assert alloc.refcount(ids[0]) == 2 and alloc.refcount(ids[2]) == 1
    # full re-insert of the same prefix adds nothing
    assert idx.insert(prompt, ids, alloc) == 0

    k, hit = idx.lookup(prompt)
    assert (k, hit) == (2, ids[:2])
    k, hit = idx.lookup(np.arange(8))       # aligned: cap at (8-1)//4 = 1
    assert (k, hit) == (1, ids[:1])
    k, hit = idx.lookup(np.arange(100, 110))
    assert (k, hit) == (0, [])

    alloc.unref(ids)                        # publisher retires
    assert alloc.in_use == 2                # index still pins 2 pages
    # blocked subtree (the SWA window-release shape): an rc-1 parent above
    # a still-referenced child frees nothing — the exact count knows it
    alloc.ref(ids[1:2])                     # child pinned by a "slot"
    assert idx.evictable_pages(alloc) == 0
    assert idx.evict_lru(alloc, 2) == 0
    alloc.unref(ids[1:2])
    assert idx.evictable_pages(alloc) == 2
    # deeper node is younger; eviction is LRU leaf-first: depth-2 first
    assert idx.evict_lru(alloc) == 1 and idx.n_entries == 1
    k, _ = idx.lookup(prompt)
    assert k == 1                           # shallow entry still serves
    assert idx.evict_lru(alloc) == 1 and idx.evict_lru(alloc) == 0
    assert alloc.in_use == 0
    alloc.check_invariants()


def test_prefix_sharing_gates_stateful_stacks():
    """Sharing keys the index on prompt TOKENS only, so any stack whose
    prefix KV is not a pure function of those tokens is refused: SSM
    (scanned state cannot resume) and cross-attention (KV downstream of a
    cross_attn block is conditioned on per-request enc embeddings)."""
    for arch in ("tiny-mamba", "tiny-zamba", "tiny-vlm"):
        cfg, params = _setup(arch)
        with pytest.raises(ValueError):
            Engine(cfg, params, max_len=16, n_slots=1, paged=True,
                   page_size=8, prefix_sharing=True)


def test_unadmittable_request_does_not_wipe_index():
    """A queued request that eviction provably cannot satisfy must defer
    WITHOUT evicting anything: wiping every warm prefix to still fail
    would convert other requests' future hits into full prefills."""
    cfg, params = _setup()
    a = _shared_prompts(cfg, 17, [0], seed=3)[0][:17]    # 2 full pages
    eng = Engine(cfg, params, max_len=40, n_slots=2, paged=True, page_size=8,
                 prefix_sharing=True)
    from repro.models.paging import PageAllocator as PA
    eng.allocator = PA(4)
    eng.n_pages = 4
    rid_a = eng.submit(a, 2)
    eng.run()
    assert len(eng.run()[rid_a]) == 2
    assert eng.prefix_index.n_entries == 2      # warm cache, rc 1 each
    big = _prompts(cfg, [33], seed=8)[0]        # 5 pages > 2 free + 2 evict
    eng.submit(big, 1)
    for _ in range(3):
        eng.step()
    assert len(eng.scheduler) == 1              # still deferred...
    assert eng.prefix_index.n_entries == 2      # ...and the cache survived
    eng.allocator.check_invariants()


def test_prefix_index_evicts_deep_chains():
    """Regression: eviction walks the trie iteratively — a prefix deeper
    than the interpreter recursion limit (thousands of full pages) must
    evict cleanly, leaf-first, without RecursionError."""
    import sys
    depth = sys.getrecursionlimit() + 200
    idx = PrefixIndex(1)                    # 1 token per page: deep chain
    alloc = PageAllocator(depth)
    ids = alloc.alloc(depth)
    idx.insert(np.arange(depth) % 7, ids, alloc)
    assert idx.n_entries == depth
    alloc.unref(ids)                        # publisher gone: all rc 1
    for _ in range(3):
        assert idx.evict_lru(alloc) == 1
    assert idx.n_entries == depth - 3
    alloc.check_invariants()


def test_nbl_page_budget_bills_shared_prefix_once():
    """Shared-prefix billing: the common prompt pages count once against
    the pool, not once per request — admitted concurrency rises, and stays
    monotone in NBL-m."""
    cfg, _ = _setup()
    budget = 12 * n_caching_attn_layers(cfg) * page_bytes(cfg, 8)
    plain = nbl_page_budget(cfg, budget, page_size=8, expected_len=48)
    shared = nbl_page_budget(cfg, budget, page_size=8, expected_len=48,
                             shared_prefix_len=32)
    assert plain == 2                       # 12 pages / 6 per request
    assert shared == 4                      # (12-4) / (6-4)
    seq = [nbl_page_budget(nbl_variant(cfg, m), budget, page_size=8,
                           expected_len=48, shared_prefix_len=32)
           for m in range(4)]
    assert seq == sorted(seq)


# ----------------------------------------------------- allocator -----------

def test_allocator_basic():
    a = PageAllocator(4)
    ids = a.alloc(3)
    assert sorted(ids) == sorted(set(ids)) and len(ids) == 3
    assert a.alloc(2) is None                  # all-or-nothing
    assert a.free_pages == 1
    a.free(ids[:1])
    assert a.free_pages == 2
    with pytest.raises(DoubleFreeError):
        a.free(ids[:1])
    with pytest.raises(DoubleFreeError):
        a.free([99])                           # foreign id
    a.check_invariants()


def test_allocator_refcounts():
    """ref pins a page across its allocator's release; unref at refcount 0
    — and only then — returns it to the free list."""
    a = PageAllocator(4)
    ids = a.alloc(2)
    a.ref(ids)                                 # rc 2 each
    a.unref(ids)
    assert a.in_use == 2 and a.free_pages == 2   # still pinned at rc 1
    a.unref(ids[:1])
    assert a.in_use == 1 and a.free_pages == 3
    with pytest.raises(DoubleFreeError):
        a.ref([ids[0]])                        # ref of a free page
    a.unref(ids[1:])
    a.check_invariants()
    assert a.in_use == 0


def test_allocator_free_is_atomic():
    """free/unref validates the WHOLE id list before mutating: a call that
    raises must leave every page exactly as it found it — including
    duplicate ids within one call, which count once per occurrence."""
    a = PageAllocator(6)
    ids = a.alloc(3)
    bad = [ids[0], 99]                         # good id first, then foreign
    with pytest.raises(DoubleFreeError):
        a.free(bad)
    assert a.refcount(ids[0]) == 1             # good id NOT freed
    assert a.in_use == 3 and a.free_pages == 3
    a.check_invariants()

    with pytest.raises(DoubleFreeError):       # dup ids exceed refcount 1
        a.free([ids[1], ids[1]])
    assert a.refcount(ids[1]) == 1
    a.check_invariants()

    a.ref([ids[2]])                            # rc 2: dup release is legal
    a.free([ids[2], ids[2]])
    assert a.refcount(ids[2]) == 0 and a.free_pages == 4
    with pytest.raises(DoubleFreeError):       # second free after rc hit 0
        a.free([ids[2], ids[0], ids[1]])
    assert a.in_use == 2                       # ids[0], ids[1] untouched
    a.check_invariants()


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 5)), max_size=40))
def test_allocator_invariants_property(ops):
    """Hypothesis property: under any alloc/ref/unref interleaving — with
    occasional invalid calls (double-unref, duplicate ids beyond the
    refcount) interleaved — no page is ever double-allocated, rejected
    calls mutate NOTHING (atomicity), and the free list + live refcounts
    always partition the pool (free-list conservation)."""
    a = PageAllocator(8)
    held: list[list[int]] = []                 # one entry per reference
    for op, n in ops:
        if op == 0:
            got = a.alloc(n)
            if got is not None:
                flat = [p for grp in held for p in grp]
                assert not (set(got) & set(flat)), "double allocation"
                held.append(got)
        elif op == 1 and held:                 # extra reference
            grp = held[n % len(held)]
            a.ref(grp)
            held.append(list(grp))
        elif op == 2 and held:                 # drop one reference
            a.unref(held.pop(n % len(held)))
        elif op == 3:                          # invalid: over-release
            grp = held[n % len(held)] if held else [n]
            counts = {p: a.refcount(p) for p in grp}
            with pytest.raises(DoubleFreeError):
                a.unref([p for p in grp
                         for _ in range(a.refcount(p) + 1)])
            for p, c in counts.items():        # atomic: nothing changed
                assert a.refcount(p) == c
        a.check_invariants()
    refs = {}
    for grp in held:
        for p in grp:
            refs[p] = refs.get(p, 0) + 1
    assert a.in_use == len(refs)
    assert all(a.refcount(p) == c for p, c in refs.items())


# ------------------------------------------------- page budget / NBL -------

def test_nbl_page_budget_monotone_in_m():
    """Fixed byte budget: linearizing more layers -> more admitted requests
    (linearized layers contribute zero pages)."""
    cfg, _ = _setup()
    budget = 6 * n_caching_attn_layers(cfg) * page_bytes(cfg, 8)  # 6 pages
    got = [nbl_page_budget(nbl_variant(cfg, m), budget, page_size=8,
                           expected_len=16) for m in range(4)]
    assert got[0] == 3                          # 6 pages / 2 per request
    assert got == sorted(got)
    assert got[-1] > got[0]


def test_paged_budget_beats_ring_on_short_prompts():
    """Equal HBM budget, short expected length: page-granular admission
    buys strictly more concurrency than max_len rings."""
    from repro.models.kv_cache import cache_bytes
    cfg, params = _setup()
    max_len = 64
    budget = 2 * cache_bytes(cfg, 1, max_len)
    ring = Engine(cfg, params, max_len=max_len, cache_budget_bytes=budget)
    paged = Engine(cfg, params, max_len=max_len, cache_budget_bytes=budget,
                   paged=True, page_size=8, expected_len=16)
    assert paged.n_slots > ring.n_slots
    assert ring.n_slots == 2


# ------------------------------------------------------- preemption --------

def test_pool_exhaustion_preempts_youngest_and_completes():
    """A pool too small for both in-flight requests to reach max_new: the
    younger request is preempted mid-decode (pages freed, requeued), the
    older finishes, and every request still completes with exactly the
    single-request reference tokens."""
    cfg, params = _setup()
    p1, p2 = _prompts(cfg, [8, 8], seed=5)
    refs = [_ref(cfg, params, p, 10) for p in (p1, p2)]

    # 2 slots x (8 prompt + 10 new = 18 tokens -> 5 pages of 4) but only
    # 7 pages: both admit (prompt needs 2 pages each + headroom), then the
    # pool runs dry as decode crosses page boundaries.
    eng = Engine(cfg, params, max_len=20, n_slots=2, paged=True, page_size=4)
    eng.allocator = PageAllocator(7)
    eng.n_pages = 7
    rids = [eng.submit(p1, 10), eng.submit(p2, 10)]
    out = eng.run(max_steps=200)
    assert eng.n_preemptions >= 1
    for rid, want in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], want)
    eng.allocator.check_invariants()
    assert eng.allocator.in_use == 0
    # preemption metrics split: restarted requests are counted and their
    # (rewound) TTFT surfaces separately, so restart latency can never
    # silently pollute a paged-vs-ring TTFT comparison.
    s = latency_stats([eng.finished[r] for r in rids])
    n_pre = sum(1 for r in (eng.finished[rid] for rid in rids)
                if r.n_preemptions > 0)
    assert s["n_preempted_requests"] == n_pre >= 1
    assert "p99_ttft_preempted_s" in s
    assert n_pre + sum(1 for rid in rids
                       if eng.finished[rid].n_preemptions == 0) == s["n"]


def test_sliding_window_releases_dead_pages_with_parity():
    """Pure-SWA stack: pages wholly below the attention window are freed
    mid-generation (the paged analogue of ring compaction), the pool's peak
    occupancy stays near O(window) instead of O(sequence), and the emitted
    tokens still exactly match generate()."""
    from repro.configs.base import dense_stack
    cfg = get_config("tiny-swa").replace(stack=dense_stack(4, window=8))
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = _prompts(cfg, [6], seed=2)[0]
    want = _ref(cfg, params, prompt, 20)       # runs to position 25

    eng = Engine(cfg, params, max_len=32, n_slots=1, paged=True, page_size=4)
    assert eng._page_window == 8
    rid = eng.submit(prompt, 20)
    out = eng.run()
    np.testing.assert_array_equal(out[rid], want)
    # 26 positions = 7 pages if nothing were freed; a W=8 window needs at
    # most 3 live 4-token pages (+1 write fault in flight)
    assert eng.allocator.peak_in_use <= 4
    assert eng.allocator.in_use == 0
    eng.allocator.check_invariants()

    # one global-attention layer pins everything: no release horizon
    dcfg, dparams = _setup()
    dense_eng = Engine(dcfg, dparams, max_len=16, n_slots=1, paged=True,
                       page_size=4)
    assert dense_eng._page_window is None


# -------------------------------------------------- chunked prefill --------

@pytest.mark.parametrize("arch", ["tiny-dense", "tiny-swa", "tiny-gemma"])
def test_chunked_prefill_parity_matrix(arch):
    """Chunked prefill emits EXACTLY the generate() tokens across
    dense-GQA / sliding-window / softcap stacks for chunk sizes of one
    page, an odd page multiple, and >= the whole prompt (single chunk)."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, [21, 5, 13], seed=6)
    refs = [_ref(cfg, params, p, 5) for p in prompts]
    for chunk in (8, 24, 999):                  # 1 page | odd multiple | all
        eng = Engine(cfg, params, max_len=32, n_slots=2, paged=True,
                     page_size=8, chunked_prefill=True,
                     prefill_chunk_tokens=chunk)
        rids = [eng.submit(p, 5) for p in prompts]
        out = eng.run(max_steps=300)
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(out[rid], refs[i],
                                          err_msg=f"chunk={chunk} req {i}")
        eng.allocator.check_invariants()
        assert eng.allocator.in_use == 0
        want_chunks = sum(-(-len(p) // eng.chunk_tokens) for p in prompts)
        assert eng.n_chunks == want_chunks, (chunk, eng.n_chunks)


@pytest.mark.parametrize("m", [1, 2])
def test_chunked_parity_nbl_compressed(m):
    """Chunked prefill over NBL-compressed stacks: linearized layers carry
    no pool (their chunk is a single GEMM, no pages) and parity is exact."""
    cfg, _ = _setup()
    ncfg = compress_config(cfg, cfg.attn_layer_indices()[-m:], "nbl")
    params = init_params(jax.random.PRNGKey(1), ncfg)
    prompts = _prompts(ncfg, [18, 7], seed=12)
    refs = [_ref(ncfg, params, p, 4) for p in prompts]

    eng = Engine(ncfg, params, max_len=32, n_slots=2, paged=True,
                 page_size=4, chunked_prefill=True, prefill_chunk_tokens=8)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run(max_steps=300)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i])


def test_chunked_composes_with_prefix_sharing():
    """chunked + prefix_sharing: the follower looks the shared prefix up
    ONCE at admission and chunks only its suffix — prefill tokens cover
    prompt minus the shared pages, parity stays exact."""
    cfg, params = _setup()
    prompts = _shared_prompts(cfg, 17, [4, 6], seed=13)
    refs = [_ref(cfg, params, p, 5) for p in prompts]

    eng = Engine(cfg, params, max_len=48, n_slots=1, paged=True, page_size=8,
                 prefix_sharing=True, chunked_prefill=True,
                 prefill_chunk_tokens=8)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run(max_steps=300)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i])
    s = eng.stats()
    assert s["n_prefix_hits"] == 1
    # the follower chunked ONLY the suffix past the 2 shared pages
    assert s["n_prefill_tokens"] == sum(len(p) for p in prompts) - 16
    eng.allocator.check_invariants()


def test_chunked_mid_prompt_preemption_requeue_resume():
    """Pool pressure preempts a mid-prompt chunking request (pages unref'd,
    requeued, progress discarded); it is re-admitted later, re-chunks from
    its prompt and completes with exactly the reference tokens."""
    cfg, params = _setup()
    p1, p2 = _prompts(cfg, [8, 16], seed=15)
    refs = [_ref(cfg, params, p, 10) for p in (p1, p2)]

    # p1 decodes across page boundaries while p2 (younger) chunks; a pool
    # of 8 cannot hold both, so p2 is torn down mid-prompt at least once.
    eng = Engine(cfg, params, max_len=32, n_slots=2, paged=True, page_size=4,
                 chunked_prefill=True, prefill_chunk_tokens=4)
    eng.allocator = PageAllocator(8)
    eng.n_pages = 8
    rids = [eng.submit(p1, 10), eng.submit(p2, 10)]
    out = eng.run(max_steps=300)
    assert eng.n_preemptions >= 1
    for rid, want in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], want)
    eng.allocator.check_invariants()
    assert eng.allocator.in_use == 0


def test_chunked_decodes_between_chunks():
    """The interleaving claim itself: while a long prompt is mid-chunking,
    already-running requests keep emitting tokens (the non-chunked engine
    would stall them for the whole prefill)."""
    cfg, params = _setup()
    shorts = _prompts(cfg, [4, 5], seed=16)
    longp = _prompts(cfg, [24], seed=17)[0]

    eng = Engine(cfg, params, max_len=40, n_slots=3, paged=True, page_size=4,
                 chunked_prefill=True, prefill_chunk_tokens=4)
    sids = [eng.submit(p, 12) for p in shorts]
    eng.step()
    eng.step()
    lid = eng.submit(longp, 4)

    def short_tokens():
        live = [r for r in eng.slot_req if r is not None]
        return sum(len(r.tokens) for r in live + list(eng.finished.values())
                   if r.rid in sids)

    interleaved = 0
    while eng.has_work:
        chunking = bool((eng.slot_chunk_pos >= 0).any())
        before = short_tokens()
        eng.step()
        if chunking and short_tokens() > before:
            interleaved += 1
    assert interleaved >= 3                     # 6 chunks, decode each step
    # the hand-counted steps validate the engine's own statistic (the one
    # ci.sh / benchmarks consume) against an independent measurement
    assert eng.stats()["n_interleaved_decode_steps"] >= 3
    for rid, p, n in [(sids[0], shorts[0], 12), (sids[1], shorts[1], 12),
                      (lid, longp, 4)]:
        np.testing.assert_array_equal(eng.finished[rid].tokens,
                                      _ref(cfg, params, p, n))


def test_chunked_gates_and_rounding():
    """chunked_prefill requires paged=True, refuses SSM stacks, and rounds
    the chunk size up to a page multiple."""
    cfg, params = _setup()
    with pytest.raises(ValueError):
        Engine(cfg, params, max_len=16, n_slots=1, chunked_prefill=True)
    for arch in ("tiny-mamba", "tiny-zamba"):
        c, p = _setup(arch)
        with pytest.raises(ValueError):
            Engine(c, p, max_len=16, n_slots=1, paged=True, page_size=8,
                   chunked_prefill=True)
    eng = Engine(cfg, params, max_len=16, n_slots=1, paged=True, page_size=8,
                 chunked_prefill=True, prefill_chunk_tokens=9)
    assert eng.chunk_tokens == 16               # rounded up to page multiple
    for bad in (0, -3):                         # 0 must not fall back to
        with pytest.raises(ValueError):         # the page-size default
            Engine(cfg, params, max_len=16, n_slots=1, paged=True,
                   page_size=8, chunked_prefill=True,
                   prefill_chunk_tokens=bad)


def test_chunked_age_order_survives_clock_ties(monkeypatch):
    """Regression: two same-step admissions tie on t_admit under a coarse
    monotonic clock; age comparisons key on admit_seq instead, so the
    steal-only-from-younger rule can still tell the slots apart and the
    engine drains rather than mutually suspending."""
    import repro.launch.engine as engine_mod
    cfg, params = _setup()
    monkeypatch.setattr(engine_mod.time, "monotonic", lambda: 12345.0)
    eng = Engine(cfg, params, max_len=32, n_slots=2, paged=True, page_size=4,
                 chunked_prefill=True, prefill_chunk_tokens=4)
    rids = [eng.submit(p, 3) for p in _prompts(cfg, [9, 9], seed=19)]
    eng.step()                          # both admitted in ONE step
    reqs = [r for r in eng.slot_req if r is not None]
    assert len(reqs) == 2
    assert reqs[0].t_admit == reqs[1].t_admit        # the tie
    assert reqs[0].admit_seq != reqs[1].admit_seq    # age still total
    out = eng.run(max_steps=300)
    assert all(len(out[r]) == 3 for r in rids)


def test_span_pages_unit():
    from repro.models.paging import span_pages
    assert span_pages(0, 5, 4) == (0, 2)
    assert span_pages(8, 9, 4) == (2, 3)
    assert span_pages(8, 16, 4) == (2, 4)
    with pytest.raises(AssertionError):
        span_pages(3, 8, 4)                     # unaligned resume point


# ------------------------------------------------------- bucketing ---------

def test_prefill_bucketing_bounds_jits_with_exact_parity():
    """Distinct prompt lengths within one power-of-two bucket share a
    single prefill jit, and emitted tokens still exactly match the
    per-length reference loop."""
    cfg, params = _setup()
    lens = [5, 6, 7, 8, 3]                     # buckets: 8, 8, 8, 8, 4
    prompts = _prompts(cfg, lens, seed=9)
    refs = [_ref(cfg, params, p, 4) for p in prompts]

    eng = Engine(cfg, params, max_len=16, n_slots=2)
    assert eng.bucket_prompts
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    assert len(eng._prefill_jits) == 2         # {8, 4}, not 5
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i], err_msg=f"req {i}")


def test_bucketing_gates_off_for_ssm_and_ring_windows():
    """Exactness gates: SSM stacks never bucket (padding corrupts scanned
    state); windowed attention buckets only under the position-aligned
    paged layout."""
    for arch, paged, want in [("tiny-mamba", False, False),
                              ("tiny-zamba", True, False),
                              ("tiny-swa", False, False),
                              ("tiny-swa", True, True),
                              ("tiny-dense", False, True)]:
        cfg, params = _setup(arch)
        eng = Engine(cfg, params, max_len=16, n_slots=1, paged=paged,
                     page_size=8)
        assert eng.bucket_prompts is want, (arch, paged)


# ------------------------------------------------------- stats -------------

def test_latency_stats_tail_fields():
    reqs = []
    for i in range(10):
        r = Request(rid=i, prompt=np.array([1]), max_new=4,
                    t_submit=0.0, t_admit=0.1, t_first=0.2 + i * 0.01,
                    t_finish=1.0 + i * 0.1)
        r.tokens = [1, 2, 3, 4]
        reqs.append(r)
    s = latency_stats(reqs)
    assert {"p99_ttft_s", "p50_ttft_s", "decode_tok_s_p50",
            "decode_tok_s_min"} <= set(s)
    assert s["p99_ttft_s"] >= s["p50_ttft_s"]
    assert s["decode_tok_s_min"] <= s["decode_tok_s_p50"]


def test_cache_bytes_memoized(monkeypatch):
    """cache_bytes hits its memo on repeat (cfg, batch, max_len) calls —
    it sits in the scheduler/benchmark hot path."""
    from repro.models import kv_cache
    cfg, _ = _setup()
    kv_cache.cache_bytes.cache_clear()
    calls = {"n": 0}
    real = jax.eval_shape

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(kv_cache.jax, "eval_shape", counting)
    a = kv_cache.cache_bytes(cfg, 1, 64)
    b = kv_cache.cache_bytes(cfg, 1, 64)
    assert a == b and calls["n"] == 1
    kv_cache.cache_bytes(cfg, 1, 128)
    assert calls["n"] == 2


def test_paged_stats_fields():
    cfg, params = _setup()
    eng = Engine(cfg, params, max_len=16, n_slots=2, paged=True, page_size=8)
    for p in _prompts(cfg, [5, 7], seed=1):
        eng.submit(p, 3)
    eng.run()
    s = eng.stats()
    assert s["n"] == 2 and s["n_pages"] == eng.n_pages
    assert 0.0 < s["pool_utilization"] <= 1.0
    assert s["pages_in_use"] == 0 and s["n_preemptions"] == 0


# ------------------------------------------- the pool under a mesh -------

# the tensor-parallel layout at a tiny size: an attention run then an NBL
# run, as many KV heads as chips, so "model" = 4 splits heads and pools
_TP4 = """
import warnings; warnings.filterwarnings('ignore')
import jax, numpy as np
from repro.configs import get_config
from repro.configs.base import dense_stack
from repro.core.surgery import compress_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
cfg = compress_config(get_config('tiny-dense', stack=dense_stack(4),
                                 n_kv_heads=4), [3])
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_mesh((1, 4), ('data', 'model'))
KW = dict(max_len=96, n_slots=4, paged=True, page_size=8)
"""
# one pool (3 layers x 48 pages x 4 heads x 8 x 16 float32) whole, and the
# quarter of it each device holds
_POOL_BYTES, _POOL_QUARTER = 294_912, 73_728


def test_sharded_engine_builds_each_pool_a_quarter_per_device(subproc):
    """Under a (1, 4) mesh no device ever holds a whole pool: every pool
    the engine builds is born sharded, and the gauge reads the fullest
    device's K and V bytes."""
    out = subproc(_TP4 + """
import repro.launch.engine as E
from repro.obs import Observability
held = []
_init = E.init_paged_cache
def spy(*a, **kw):
    c = _init(*a, **kw)
    for leaf in jax.tree.leaves(c):
        if not isinstance(leaf, jax.core.Tracer):   # built outside a jit
            held.append(max(s.data.nbytes for s in leaf.addressable_shards))
    return c
E.init_paged_cache = spy
obs = Observability()
with jax.set_mesh(mesh):
    eng = E.Engine(cfg, params, obs=obs, **KW)
pools = [b for g in eng.cache['groups'] for b in g['blocks'] if b]
per_dev = {}
for blk in pools:
    for leaf in (blk['k_pages'], blk['v_pages']):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
        print('POOL', leaf.nbytes, sorted({s.data.nbytes
                                           for s in leaf.addressable_shards}))
print('HELD', max(held, default=0))
print('DEVICES', len(per_dev), max(per_dev.values()))
print('GAUGE', obs.g_kv_pool.value)
""", n_devices=4)
    lines = dict(ln.split(" ", 1) for ln in out.splitlines()
                 if ln.split(" ")[0] in ("HELD", "DEVICES", "GAUGE"))
    pools = [ln for ln in out.splitlines() if ln.startswith("POOL")]
    assert pools == [f"POOL {_POOL_BYTES} [{_POOL_QUARTER}]"] * 2, out
    # the parent's path built each pool whole on every device first
    assert int(lines["HELD"]) == 0, out
    assert lines["DEVICES"] == f"4 {2 * _POOL_QUARTER}", out
    assert int(lines["GAUGE"]) == 2 * _POOL_QUARTER, out


def test_kv_pool_gauge_without_a_mesh():
    """Unsharded, the gauge reads both whole pools; the ring layout has
    none."""
    from repro.obs import Observability
    cfg, params = _setup()
    obs = Observability()
    eng = Engine(cfg, params, max_len=16, n_slots=2, paged=True,
                 page_size=8, obs=obs)
    pools = [leaf for g in eng.cache["groups"] for b in g["blocks"]
             for k, leaf in b.items() if k in ("k_pages", "v_pages")]
    assert pools and obs.g_kv_pool.value == sum(p.nbytes for p in pools)
    ring = Observability()
    Engine(cfg, params, max_len=16, n_slots=2, obs=ring)
    assert ring.g_kv_pool.value == 0


def test_sharded_nbl_engine_logits_match_unsharded(subproc):
    """The tp4 layout served sharded (the paged kernel in its shard_map)
    picks every token from logits that match the unsharded engine's."""
    out = subproc(_TP4 + """
import functools
from repro.kernels import paged_attention as pa
from repro.launch.engine import Engine
pa.paged_attention = functools.partial(pa.paged_attention, interpret=True)
pa.paged_mixed = functools.partial(pa.paged_mixed, use_kernel=True)
rows = []
_sample = Engine._sample
def spy(self, row):
    rows[-1].append(np.array(row))
    return _sample(self, row)
Engine._sample = spy
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (9, 17, 30)]
outs = []
for mesh_ in (None, mesh):
    rows.append([])
    if mesh_ is None:
        eng = Engine(cfg, params, **KW)
    else:
        with jax.set_mesh(mesh_):
            eng = Engine(cfg, params, **KW)
    rids = [eng.submit(p, 6) for p in prompts]
    res = eng.run()
    outs.append([list(res[r]) for r in rids])
assert outs[0] == outs[1], outs
assert len(rows[0]) == len(rows[1]) == 18
err = max(float(np.abs(a - b).max()) for a, b in zip(*rows))
assert err < 1e-4, err
print('OK', err)
""", n_devices=4)
    assert "OK" in out
