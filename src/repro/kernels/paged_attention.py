"""Paged-attention decode Pallas kernel (TPU target).

Single-token decode against the page-pool KV layout of models/paging.py:
each serving slot's K/V live in fixed-size, position-aligned pages scattered
through a per-layer pool, addressed by a per-slot page table.

Grid (slot, logical_page) with the page sweep innermost; each grid step
carries every KV head of one page. The page table and per-slot lengths
ride in as SCALAR-PREFETCH operands (pltpu.PrefetchScalarGridSpec), so the
K/V BlockSpec index_maps read the *physical* page id for the current
(slot, logical_page) cell and the pallas_call machinery DMAs exactly that
page, all heads at once, HBM->VMEM — the gather IS the block indexing, no
materialized (B, T) copy. The index_map clamps the logical page into the
row's live span (the pages holding keys the query attends: up to
lengths[b], and from the window's start where one is set), so steps
outside it repeat the previous block index and Pallas issues no DMA for
them; ``pl.when`` skips their compute, and a row of length 0 does no work
and returns zeros. Running max/denominator/output accumulator for every
head persist in VMEM scratch across the page sweep (online softmax, same
recurrence as kernels/flash_attention.py). q and K enter the MXU as
stored (bf16 on the chip) with float32 accumulation; the probabilities,
V and the accumulator are float32.

Masking is positional: logical page l covers absolute positions
[l*page_size, (l+1)*page_size); token t of slot b is valid iff
t < lengths[b], plus the sliding-window predicate and an allocated-page
check (steps on unallocated table entries are skipped). GQA (q heads
grouped per kv head), sliding window and Gemma-2 logit soft-capping match
kernels/flash_attention.py semantics.

MXU alignment for real TPUs wants head_dim padded to 128 lanes
(ops.attention-style); interpret mode accepts the tiny test shapes as-is.
For bf16 pools, page sizes 64 and 8 (below the 16-row sublane tile) both
compile for v5e (tests/test_chip_compile.py).

``paged_decode`` is the call-site dispatcher: the Pallas kernel on TPU, the
pure-XLA gather reference (``paged_decode_xla``) elsewhere — the kernel is
checked against the reference in interpret mode by tests/test_paging.py and
compiled on a TPU v5e by chip_smoke.py. Under a mesh the kernel runs in a
shard_map over KV heads (``_sharded_kernel``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.distributed.api import axis_size, mesh_axes

NEG_INF = -2.0e38


def _live_span(length, page_size: int, window: Optional[int]):
    """First and last logical page holding a key that the query at
    ``length - 1`` attends. A row of length 0 has no live page: callers
    test ``length > 0`` beside the span."""
    last = (jnp.maximum(length, 1) - 1) // page_size
    if window is None:
        return 0, last
    return jnp.maximum(length - window, 0) // page_size, last


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: Optional[int],
            softcap: Optional[float], page_size: int, n_lpages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[b]                              # valid tokens (pos + 1)
    first, last = _live_span(length, page_size, window)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # every page in the live span holds at least one unmasked key, so the
    # running max is finite from the first page on
    @pl.when((length > 0) & (p >= first) & (p <= last)
             & (tbl_ref[b, p] >= 0))
    def _page():
        q = q_ref[0]                                 # (KV, rep, hd)
        k = k_ref[0]                                 # (KV, page_size, hd)
        dt = jnp.promote_types(q.dtype, k.dtype)     # as stored, or wider
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (KV, rep, ps)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        t = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = t < length
        if window is not None:
            mask &= (length - 1 - t) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (KV, rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + pr.sum(axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pr, v_ref[0].astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(p == n_lpages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_tbl: jax.Array, lengths: jax.Array, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, KV, rep, hd); k_pages/v_pages: (n_pages, KV, page_size, hd);
    page_tbl: (B, n_lpages) int32 physical ids, -1 = unallocated;
    lengths: (B,) int32 valid tokens per slot (query sits at lengths-1).
    Returns (B, KV, rep, hd); a row of length 0 comes back all zero."""
    b, kvh, rep, hd = q.shape
    n_pages, kvh2, page_size, _ = k_pages.shape
    assert kvh == kvh2, (kvh, kvh2)
    n_lpages = page_tbl.shape[1]
    scale = hd ** -0.5 if scale is None else scale

    kern = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, n_lpages=n_lpages)

    def kv_map(bi, pi, tbl, lens):
        # physical page of the logical page clamped into the row's live
        # span: steps outside it repeat a neighbour's block index, so no
        # DMA is issued for them. Empty rows and the -1 sentinel read page
        # 0; the kernel skips those steps.
        first, last = _live_span(lens[bi], page_size, window)
        page = tbl[bi, jnp.minimum(jnp.maximum(pi, first), last)]
        return (jnp.where(lens[bi] > 0, jnp.maximum(page, 0), 0), 0, 0, 0)

    row = lambda bi, pi, tbl, lens: (bi, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_lpages),
        in_specs=[
            pl.BlockSpec((1, kvh, rep, hd), row),
            pl.BlockSpec((1, kvh, page_size, hd), kv_map),
            pl.BlockSpec((1, kvh, page_size, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, rep, hd), row),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, 1), jnp.float32),     # running max
            pltpu.VMEM((kvh, rep, 1), jnp.float32),     # running denom
            pltpu.VMEM((kvh, rep, hd), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(page_tbl.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


def paged_decode_xla(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_tbl: jax.Array, lengths: jax.Array, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None) -> jax.Array:
    """Pure-XLA reference with identical masking semantics: gather pages via
    the table, one softmax over the logical sequence. Same shapes as
    ``paged_attention``; the serving path on non-TPU backends runs this."""
    b, kvh, rep, hd = q.shape
    n_pages, _, page_size, _ = k_pages.shape
    n_lpages = page_tbl.shape[1]
    scale = hd ** -0.5 if scale is None else scale

    idx = jnp.clip(page_tbl, 0)                       # (B, P); mask kills -1
    kg = k_pages[idx]                                 # (B, P, KV, ps, hd)
    vg = v_pages[idx]
    t_total = n_lpages * page_size
    kg = kg.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t_total, hd)
    vg = vg.transpose(0, 2, 1, 3, 4).reshape(b, kvh, t_total, hd)

    s = jnp.einsum("bgrd,bgtd->bgrt", q.astype(jnp.float32),
                   kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    t = jnp.arange(t_total, dtype=jnp.int32)[None]        # (1, T)
    ln = lengths.astype(jnp.int32)[:, None]               # (B, 1)
    valid = (t < ln) & jnp.repeat(page_tbl >= 0, page_size, axis=1)
    if window is not None:
        valid &= (ln - 1 - t) < window
    valid = valid[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)

    m = s.max(axis=-1, keepdims=True)
    pr = jnp.where(valid, jnp.exp(s - m), 0.0)   # a keyless row gives 0
    denom = jnp.maximum(pr.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgrt,bgtd->bgrd", (pr / denom),
                     vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_mixed_xla(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_tbl: jax.Array, row_pos: jax.Array,
                    row_len: jax.Array, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None) -> jax.Array:
    """Mixed-row (multi-query) paged attention, pure XLA: each slot
    carries up to W new tokens at absolute positions ``row_pos[b] + i``
    (valid while ``i < row_len[b]``; ``row_len 0`` = inactive row) and
    token i attends logical positions ``[0, row_pos[b] + i]`` of its
    slot's sequence — write-before-attend puts the in-chunk keys in the
    pages, so the per-query causal mask alone gives exact chunk
    semantics. ONE page gather per SLOT feeds a dense masked softmax
    (the W queries share the gathered keys as a GEMM), which is what
    makes a wide chunk row cost prefill-like compute instead of W
    separate decode gathers.

    q: (B, KV, rep, W, hd); k_pages/v_pages: (n_pages, KV, page_size,
    hd); page_tbl: (B, n_lpages) int32, -1 = unallocated; row_pos /
    row_len: (B,) int32. Returns (B, KV, rep, W, hd); invalid query
    positions come back all-zero (denominator-guarded, finite)."""
    b, kvh, rep, w, hd = q.shape
    n_pages, _, page_size, _ = k_pages.shape
    n_lpages = page_tbl.shape[1]
    scale = hd ** -0.5 if scale is None else scale

    idx = jnp.clip(page_tbl, 0)                       # (B, P); mask kills -1
    t_total = n_lpages * page_size
    kg = k_pages[idx].transpose(0, 2, 1, 3, 4).reshape(b, kvh, t_total, hd)
    vg = v_pages[idx].transpose(0, 2, 1, 3, 4).reshape(b, kvh, t_total, hd)

    s = jnp.einsum("bgrwd,bgtd->bgrwt", q.astype(jnp.float32),
                   kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    t = jnp.arange(t_total, dtype=jnp.int32)[None, None]        # (1, 1, T)
    qpos = (row_pos.astype(jnp.int32)[:, None]
            + jnp.arange(w, dtype=jnp.int32)[None, :])          # (B, W)
    qvalid = jnp.arange(w, dtype=jnp.int32)[None, :] \
        < row_len.astype(jnp.int32)[:, None]                    # (B, W)
    valid = (t <= qpos[:, :, None]) \
        & jnp.repeat(page_tbl >= 0, page_size, axis=1)[:, None, :]
    if window is not None:
        valid &= (qpos[:, :, None] - t) < window
    valid &= qvalid[:, :, None]
    valid = valid[:, None, None, :, :]
    s = jnp.where(valid, s, NEG_INF)

    m = s.max(axis=-1, keepdims=True)
    pr = jnp.where(valid, jnp.exp(s - m), 0.0)   # a keyless query gives 0
    denom = jnp.maximum(pr.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgrwt,bgtd->bgrwd", (pr / denom),
                     vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _sharded_kernel(q, k_pages, v_pages, page_tbl, lengths, **kw):
    """``paged_attention`` under whatever mesh is ambient. A pallas_call
    has no GSPMD partitioning rule, so under a mesh it runs in a
    shard_map: q and the pools split over KV heads on "model" (each
    shard's heads read only their own slice of every page) and the page
    table and lengths are replicated. KV heads that do not divide the
    "model" axis run replicated."""
    fn = functools.partial(paged_attention, **kw)
    axes = mesh_axes()
    if not axes:
        return fn(q, k_pages, v_pages, page_tbl, lengths)
    kv = "model" if ("model" in axes
                     and q.shape[1] % axis_size("model") == 0) else None
    heads = P(None, kv, None, None)    # q (B, KV, rep, hd); pools likewise
    return jax.shard_map(fn, in_specs=(heads, heads, heads, P(), P()),
                         out_specs=heads, check_vma=False)(
        q, k_pages, v_pages, page_tbl, lengths)


def paged_mixed(q, k_pages, v_pages, page_tbl, row_pos, row_len, *,
                scale: Optional[float] = None, window: Optional[int] = None,
                softcap: Optional[float] = None,
                use_kernel: Optional[bool] = None) -> jax.Array:
    """Backend dispatcher for the mixed-row step attention: on TPU the
    W queries run as B*W virtual single-token rows through the Mosaic
    ``paged_attention`` kernel (the page sweep's BlockSpec gather keeps
    that cheap on-device, and the invalid rows, of length 0, do no work);
    elsewhere the dense-gather XLA path, whose
    shared per-slot gather is the fast shape for the serving loop."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return paged_mixed_xla(q, k_pages, v_pages, page_tbl, row_pos,
                               row_len, scale=scale, window=window,
                               softcap=softcap)
    b, kvh, rep, w, hd = q.shape
    qv = q.transpose(0, 3, 1, 2, 4).reshape(b * w, kvh, rep, hd)
    tpos = (row_pos.astype(jnp.int32)[:, None]
            + jnp.arange(w, dtype=jnp.int32)[None, :])
    valid = jnp.arange(w, dtype=jnp.int32)[None, :] \
        < row_len.astype(jnp.int32)[:, None]
    lens = jnp.where(valid, tpos + 1, 0).reshape(-1)
    out = _sharded_kernel(qv, k_pages, v_pages,
                          jnp.repeat(page_tbl, w, axis=0), lens,
                          scale=scale, window=window, softcap=softcap)
    return out.reshape(b, w, kvh, rep, hd).transpose(0, 2, 3, 1, 4)


def paged_decode(q, k_pages, v_pages, page_tbl, lengths, *,
                 scale: Optional[float] = None, window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 use_kernel: Optional[bool] = None) -> jax.Array:
    """Backend dispatcher: Mosaic kernel on TPU, XLA gather reference
    elsewhere (interpret-mode kernel execution is test-only — it is far
    slower than the XLA path for the serving loop)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return _sharded_kernel(q, k_pages, v_pages, page_tbl, lengths,
                               scale=scale, window=window, softcap=softcap)
    return paged_decode_xla(q, k_pages, v_pages, page_tbl, lengths,
                            scale=scale, window=window, softcap=softcap)
