"""Model assembly: stack-plan executor, init, loss, prefill, decode.

Execution walks the config's stack plan group-by-group; each group scans
(`lax.scan`) over its `repeat` dimension with stacked per-layer params, so HLO
size is O(#groups), not O(#layers) — essential for 512-way dry-run compiles of
61-126 layer models. Shared blocks (Zamba2) keep a single param copy closed
over by the scan body, with per-invocation caches scanned.

Block kinds (see configs.base.Block):
  attn        pre-norm GQA self-attention + residual
  cross_attn  pre-norm cross-attention over frontend embeddings + residual
  mamba       pre-norm Mamba2 SSD mixer + residual
  nbl         NBL-linearized attention sub-block: x + (x @ W + b). The LMMSE
              map is fit on the residual-stream input (norm folded in), so the
              compressed block is a single GEMM — the paper's replacement.
  drop        attention sub-block removed (Attn DROP baseline): x unchanged
  nbl_block   whole transformer block linearized: x + (x @ W + b); no ffn
  drop_block  whole block removed (SLEB / Block DROP baseline): identity
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import Block, ModelConfig
from repro.distributed import constrain
from repro.models.attention import (
    cross_attention, decode_attention, decode_cross_attention, init_attn,
    decode_paged_attention, fused_paged_attention, self_attention,
)
from repro.models.layers import embed_tokens, init_mlp, mlp, rmsnorm, softcap
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_mamba, mamba_block, mamba_decode


# --------------------------------------------------------------------------
# Parameter init
# --------------------------------------------------------------------------

def _ffn_dim(cfg: ModelConfig, blk: Block) -> int:
    if blk.ffn == "dense" and cfg.moe is not None and cfg.moe.dense_ff:
        return cfg.moe.dense_ff
    return cfg.d_ff


def init_nbl_linear(key: jax.Array, cfg: ModelConfig) -> dict:
    """Random-init NBL linear (real W, b come from core.lmmse surgery; this
    exists so compressed configs can be dry-run/inited without calibration)."""
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w": (jax.random.normal(key, (d, d)) * d ** -0.5).astype(dt),
        "b": jnp.zeros((d,), dt),
    }


def init_block(key: jax.Array, cfg: ModelConfig, blk: Block) -> dict:
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p: dict = {}
    if blk.kind in ("attn", "cross_attn"):
        p["norm1"] = jnp.zeros((d,), dt)
        p["mixer"] = init_attn(k1, cfg, cross=(blk.kind == "cross_attn"))
    elif blk.kind == "mamba":
        p["norm1"] = jnp.zeros((d,), dt)
        p["mixer"] = init_mamba(k1, cfg)
    elif blk.kind in ("nbl", "nbl_block"):
        p["mixer"] = init_nbl_linear(k1, cfg)
    elif blk.kind in ("drop", "drop_block"):
        pass
    else:
        raise ValueError(f"unknown block kind {blk.kind!r}")

    if blk.kind in ("nbl_block", "drop_block"):
        return p                                  # whole block replaced
    if blk.ffn == "dense":
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = init_mlp(k2, d, _ffn_dim(cfg, blk), dt)
    elif blk.ffn == "moe":
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = init_moe(k2, cfg)
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    dt = jnp.dtype(cfg.param_dtype)
    n_groups = len(cfg.stack)
    keys = jax.random.split(key, n_groups + 2)
    params: dict = {
        "embed": (jax.random.normal(keys[0], (v, d)) * d ** -0.5).astype(dt),
        "final_norm": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(keys[1], (d, v))
                          * d ** -0.5).astype(dt)
    groups = []
    for gi, g in enumerate(cfg.stack):
        gkeys = jax.random.split(keys[2 + gi], len(g.unit))
        scanned, shared = [], []
        for u, blk in enumerate(g.unit):
            if blk.shared:
                shared.append(init_block(gkeys[u], cfg, blk))
                scanned.append(None)
            else:
                lk = jax.random.split(gkeys[u], g.repeat)
                scanned.append(
                    jax.vmap(lambda kk: init_block(kk, cfg, blk))(lk))
                shared.append(None)
        groups.append({"scanned": scanned, "shared": shared})
    params["groups"] = groups
    return params


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic (eval_shape) parameter count. With ``active_only`` routed MoE
    expert weights are scaled by top_k/n_experts (6·N_active·D roofline)."""
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = 1
        for s in leaf.shape:
            n *= s
        if active_only and cfg.moe is not None:
            names = [getattr(k, "key", None) for k in path]
            if ("ffn" in names and leaf.ndim >= 3
                    and cfg.moe.n_experts in leaf.shape
                    and names[-1] in ("w_gate", "w_up", "w_down")):
                n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n
    return total


# --------------------------------------------------------------------------
# Block forward (one residual block, one mode)
# --------------------------------------------------------------------------

def _block_fwd(cfg: ModelConfig, blk: Block, p, x, *, mode: str,
               positions, enc, cache, pos, cache_len: int,
               page_tbl=None, paged: bool = False, valid_len=None,
               prefix_tbl=None, prefix_len=None, row_len=None):
    """Returns (x, new_cache, aux). ``cache`` is this block's slice.

    ``page_tbl``/``paged``/``valid_len`` serve the paged engine: a decode
    cache holding page pools (key "k_pages") dispatches to the paged kernel;
    a paged prefill keeps full-width position-aligned caches (no ring wrap);
    ``valid_len`` masks bucket-padding tokens out of the prefill cache.
    ``prefix_tbl``/``prefix_len`` serve the PARTIAL prefill under prefix
    sharing: in prefill mode ``cache`` is then this layer's page pools and
    the attention gathers the shared-prefix KV through the table.
    mode="fused" is the engine's single-dispatch mixed step (decode rows +
    prefill-chunk rows over the shared page table): ``pos`` is the per-row
    first-token position and ``row_len`` the per-row valid token count.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = None

    # ---- mixer -----------------------------------------------------------
    if blk.kind == "attn":
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if mode == "decode":
            if cache is not None and "k_pages" in cache:
                h, new_cache = decode_paged_attention(
                    cfg, p["mixer"], h, cache, pos, page_tbl,
                    window=blk.window)
            else:
                h, new_cache = decode_attention(cfg, p["mixer"], h, cache,
                                                pos, window=blk.window)
        elif mode == "fused":
            h, new_cache = fused_paged_attention(
                cfg, p["mixer"], h, cache, pos, row_len, page_tbl,
                window=blk.window)
        else:
            prefix = None
            if mode == "prefill" and prefix_tbl is not None:
                prefix = _gather_prefix(cache, prefix_tbl, prefix_len)
            h, (k, v) = self_attention(cfg, p["mixer"], h, window=blk.window,
                                       positions=positions, prefix=prefix)
            if mode == "prefill":
                new_cache = _ring_cache(cfg, blk, k, v, cache_len,
                                        paged=paged, valid_len=valid_len)
        x = x + h.astype(x.dtype)
    elif blk.kind == "cross_attn":
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if mode in ("decode", "fused"):
            # decode_cross_attention is query-length agnostic (its queries
            # carry no positions), so fused multi-token rows reuse it as-is
            h, new_cache = decode_cross_attention(cfg, p["mixer"], h, cache)
        else:
            h, (k, v) = cross_attention(cfg, p["mixer"], h, enc=enc)
            if mode == "prefill":
                new_cache = {"k": k.astype(x.dtype), "v": v.astype(x.dtype)}
        x = x + h.astype(x.dtype)
    elif blk.kind == "mamba":
        assert mode != "fused", \
            "fused step cannot resume SSM state mid-sequence (engine gates " \
            "mamba stacks onto the legacy path)"
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if mode == "decode":
            h, new_cache = mamba_decode(cfg, p["mixer"], h, cache)
        else:
            h, (state, conv) = mamba_block(cfg, p["mixer"], h)
            if mode == "prefill":
                new_cache = {"ssm": state, "conv": conv}
        x = x + h.astype(x.dtype)
    elif blk.kind in ("nbl", "nbl_block"):
        # the paper's replacement: one GEMM, residual retained (Alg. 2).
        with jax.named_scope("nbl"):
            h = (x @ p["mixer"]["w"].astype(x.dtype)
                 + p["mixer"]["b"].astype(x.dtype))
            x = x + h
    elif blk.kind in ("drop", "drop_block"):
        pass

    if blk.kind in ("nbl_block", "drop_block"):
        return x, new_cache, aux

    # ---- ffn --------------------------------------------------------------
    if blk.ffn == "dense":
        with jax.named_scope("mlp"):
            h = rmsnorm(x, p["norm2"], cfg.norm_eps)
            x = x + mlp(p["ffn"], h, cfg.mlp_act).astype(x.dtype)
    elif blk.ffn == "moe":
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        y, aux = moe_ffn(cfg, p["ffn"], h)
        x = x + y.astype(x.dtype)
    return x, new_cache, aux


def _gather_prefix(pool: dict, prefix_tbl, prefix_len):
    """Gather shared-prefix KV for a partial prefill: ``pool`` is one
    layer's page pools {k_pages, v_pages: (n_pages, KV, ps, hd)};
    ``prefix_tbl`` (Pb,) physical ids (-1 = past the prefix, clip-gathered
    and masked); ``prefix_len`` traced token count. Returns (k, v, kpos)
    with k/v (1, KV, Pb*ps, hd) and kpos -1 beyond prefix_len."""
    assert pool is not None and "k_pages" in pool, \
        "partial prefill needs the paged pools"
    idx = jnp.clip(jnp.asarray(prefix_tbl, jnp.int32), 0)
    kg = pool["k_pages"][idx]                     # (Pb, KV, ps, hd)
    vg = pool["v_pages"][idx]
    pb, kv, ps, hd = kg.shape
    kg = kg.transpose(1, 0, 2, 3).reshape(1, kv, pb * ps, hd)
    vg = vg.transpose(1, 0, 2, 3).reshape(1, kv, pb * ps, hd)
    t = jnp.arange(pb * ps, dtype=jnp.int32)
    kpos = jnp.where(t < jnp.asarray(prefix_len, jnp.int32), t, -1)
    return kg, vg, kpos


def _ring_cache(cfg: ModelConfig, blk: Block, k, v, cache_len: int, *,
                paged: bool = False, valid_len=None) -> dict:
    """Convert full-sequence (roped) K/V (B,KV,S,hd) into the ring-buffer
    cache layout used by decode (width = min(window, cache_len)).

    ``paged`` keeps the cache POSITION-ALIGNED at full ``cache_len`` width
    even for windowed layers (pages must map positions linearly; the window
    is enforced by the decode mask instead of ring compaction). ``valid_len``
    (traced scalar) masks positions >= it to kpos=-1 — bucket-padded prompt
    tokens are written but never attendable.
    """
    s = k.shape[2]
    if paged:
        w = cache_len
        assert w >= s, (w, s)
    else:
        w = min(blk.window, cache_len) if blk.window is not None else cache_len
    if w >= s:
        pad = w - s
        kr = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vr = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kpos = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                                jnp.full((pad,), -1, jnp.int32)])
    else:
        start = s - w
        slots = jnp.arange(w)
        src = (start + ((slots - start) % w)).astype(jnp.int32)
        kr = jnp.take(k, src, axis=2)
        vr = jnp.take(v, src, axis=2)
        kpos = src
    if valid_len is not None:
        kpos = jnp.where((kpos >= 0) & (kpos < valid_len), kpos, -1)
    dt = jnp.dtype(cfg.compute_dtype)
    return {"k": kr.astype(dt), "v": vr.astype(dt), "kpos": kpos}


# --------------------------------------------------------------------------
# Stack executor
# --------------------------------------------------------------------------

def _stack_fwd(cfg: ModelConfig, params: dict, x, *, mode: str,
               positions=None, enc=None, cache=None, pos=None,
               cache_len: int = 0, remat: bool = False,
               page_tbl=None, paged: bool = False, valid_len=None,
               prefix_tbl=None, prefix_len=None, row_len=None):
    """Run the full stack. Returns (x, new_cache_or_None, aux_sum)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_groups = []
    for gi, g in enumerate(cfg.stack):
        gp = params["groups"][gi]
        gcache = cache["groups"][gi]["blocks"] if cache is not None else None

        def body(carry, xs, _g=g, _gp=gp):
            xc, auxc = carry
            ps, cs = xs
            outs = []
            for u, blk in enumerate(_g.unit):
                p_u = _gp["shared"][u] if blk.shared else ps[u]
                c_u = cs[u] if cs is not None else None
                xc, nc, aux_u = _block_fwd(
                    cfg, blk, p_u, xc, mode=mode, positions=positions,
                    enc=enc, cache=c_u, pos=pos, cache_len=cache_len,
                    page_tbl=page_tbl, paged=paged, valid_len=valid_len,
                    prefix_tbl=prefix_tbl, prefix_len=prefix_len,
                    row_len=row_len)
                auxc = auxc + aux_u
                outs.append(nc)
            return (xc, auxc), outs

        fn = body
        if remat and mode == "train":
            fn = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        xs = (gp["scanned"], gcache)
        (x, aux_total), caches_out = jax.lax.scan(
            fn, (x, aux_total), xs, length=g.repeat)
        if mode in ("prefill", "decode", "fused"):
            new_groups.append({"blocks": caches_out})
        x = constrain(x, "dp", None, None)

    new_cache = ({"groups": new_groups}
                 if mode in ("prefill", "decode", "fused") else None)
    return x, new_cache, aux_total


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

def _logits(cfg: ModelConfig, params: dict, x) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_logit_softcap)
    return constrain(logits, "dp", None, "model")


def apply(cfg: ModelConfig, params: dict, tokens, *, enc=None,
          remat: bool = False):
    """Full-sequence forward. Returns (logits_f32 (B,S,V), moe_aux)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, dt)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, _, aux = _stack_fwd(cfg, params, x, mode="train", positions=positions,
                           enc=enc, remat=remat)
    return _logits(cfg, params, x), aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, *,
            remat: bool = True):
    """Causal-LM loss. batch: tokens (B,S), labels (B,S) with -1 = masked,
    optional enc (B,T,d). Returns (loss, metrics)."""
    logits, aux = apply(cfg, params, batch["tokens"], enc=batch.get("enc"),
                        remat=remat)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    # logsumexp − logit_at_label: one fewer full-vocab materialization than
    # log_softmax + gather (the (B,S,V) tensor is the dominant train-time
    # activation at 100k+ vocabs; see EXPERIMENTS.md §Perf).
    lse = jax.nn.logsumexp(logits, axis=-1)
    at = jnp.take_along_axis(
        logits, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - at
    ntok = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / ntok
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux, "ntokens": ntok}


def prefill(cfg: ModelConfig, params: dict, tokens, *, enc=None,
            cache_len: Optional[int] = None, paged: bool = False,
            valid_len=None, prefix_cache=None, prefix_tbl=None,
            prefix_len=None, n_logits: int = 1):
    """Process the prompt, build KV/state caches, return last-token logits.
    Logits are computed at the final position only (vocab-size safe at 32k+
    contexts). Returns (logits (B,1,V), cache).

    ``n_logits`` (STATIC) widens the logits window to the last n_logits
    valid positions — (B, n_logits, V), rows ordered oldest-first so row
    ``n_logits - 1`` is the usual last-token row. The speculative verify
    step uses γ+1 rows to score a whole candidate block from one
    cache-extend pass; everything else keeps the default of 1.

    ``paged`` builds POSITION-ALIGNED full-width caches (no ring wrap) for
    page-tiled assignment (models/paging.assign_pages). ``valid_len`` (a
    traced int32 scalar) supports prompt-length bucketing: ``tokens`` may be
    right-padded to a bucket length — logits come from position
    ``valid_len - 1`` and cache entries at positions >= valid_len are
    masked unattendable, so one jit serves every prompt length in the
    bucket. Not valid for SSM stacks (padding corrupts the scanned state).

    PARTIAL prefill (prefix sharing AND chunked prefill): with
    ``prefix_cache`` (the paged cache tree), ``prefix_tbl`` ((Pb,) int32
    physical page per logical prefix page, -1 padding) and ``prefix_len``
    (traced token count, a page multiple), ``tokens`` holds only the
    SUFFIX from the first divergent page — embedded at absolute positions
    prefix_len + i and attending the already-paged prefix KV through the
    table. The engine reuses this ONE code path for two callers that
    differ only in the table's provenance: prefix sharing points it at
    ANOTHER request's published prompt pages (launch/engine._admit),
    chunked prefill points it at the request's OWN earlier chunks
    (launch/engine._chunk_step) — there is no chunk-specific model code
    below the page table. The returned cache covers the suffix only;
    ``valid_len`` then counts valid SUFFIX tokens and logits come from
    suffix position valid_len - 1. Requires a stack with no SSM blocks
    (their scanned state cannot resume mid-sequence).
    """
    cache_len = cache_len or tokens.shape[1]
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, dt)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    if prefix_tbl is not None:
        assert paged, "partial prefill is a paged-engine path"
        assert not any(b.kind == "mamba" for b in cfg.blocks()), \
            "partial prefill cannot resume SSM state mid-sequence"
        positions = positions + jnp.asarray(prefix_len, jnp.int32)
    x, cache, _ = _stack_fwd(cfg, params, x, mode="prefill",
                             positions=positions, enc=enc,
                             cache=prefix_cache if prefix_tbl is not None
                             else None,
                             cache_len=cache_len, paged=paged,
                             valid_len=valid_len, prefix_tbl=prefix_tbl,
                             prefix_len=prefix_len)
    assert 1 <= n_logits <= tokens.shape[1], (n_logits, tokens.shape)
    if valid_len is None:
        x_last = x[:, -n_logits:]
    else:
        start = jnp.asarray(valid_len, jnp.int32) - n_logits
        x_last = jax.lax.dynamic_slice_in_dim(x, start, n_logits, axis=1)
    return _logits(cfg, params, x_last), cache


def decode_step(cfg: ModelConfig, params: dict, token, cache, pos,
                page_tbl=None):
    """One autoregressive step. token: (B,1) int32; pos: absolute position
    of this token — () int32 with a monolithic cache (all sequences at one
    position), or (B,) int32 with a slot cache (per-slot positions, the
    continuous-batching engine). With a PAGED cache (models/paging.py),
    ``page_tbl`` (B, n_lpages) int32 maps each slot's logical pages to
    physical pool pages. Returns (logits (B,1,V), new_cache)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], token, dt)
    x, new_cache, _ = _stack_fwd(cfg, params, x, mode="decode", cache=cache,
                                 pos=pos, page_tbl=page_tbl)
    return _logits(cfg, params, x), new_cache


def fused_step(cfg: ModelConfig, params: dict, tokens, cache, row_pos,
               row_len, page_tbl):
    """One FUSED engine step: a mixed batch of decode rows (1 new token) and
    page-aligned prefill-chunk rows (up to W new tokens) executed against
    the shared paged cache in a single dispatch (launch/engine's plan →
    execute → commit pipeline; see docs/architecture.md).

    tokens: (B, W) int32, each row right-padded past its valid span;
    row_pos: (B,) absolute position of each row's FIRST token; row_len:
    (B,) valid tokens this step — 1 for a decode row, the chunk span for a
    prefill row, 0 for an inactive row (empty slot / speculative slot
    stepped separately); page_tbl: (B, n_lpages) int32.

    Returns (logits (B,1,V), new_cache): per-row ``n_logits``-style
    extraction at each row's LAST valid token (a decode row's next-token
    logits; a final chunk's seed logits). Inactive rows yield finite
    garbage logits the caller discards. Requires a paged, SSM-free stack.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, dt)
    x, new_cache, _ = _stack_fwd(cfg, params, x, mode="fused", cache=cache,
                                 pos=row_pos, row_len=row_len,
                                 page_tbl=page_tbl)
    idx = jnp.clip(jnp.asarray(row_len, jnp.int32) - 1, 0)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    return _logits(cfg, params, x_last), new_cache


# --------------------------------------------------------------------------
# Unrolled forward with activation taps (NBL calibration path)
# --------------------------------------------------------------------------

def layer_params(cfg: ModelConfig, params: dict, layer_idx: int):
    """Slice the stacked params of global block ``layer_idx``."""
    i = 0
    for gi, g in enumerate(cfg.stack):
        for r in range(g.repeat):
            for u, blk in enumerate(g.unit):
                if i == layer_idx:
                    gp = params["groups"][gi]
                    if blk.shared:
                        return gp["shared"][u], blk
                    return jax.tree.map(lambda a: a[r], gp["scanned"][u]), blk
                i += 1
    raise IndexError(layer_idx)


def forward_with_taps(cfg: ModelConfig, params: dict, tokens, *, enc=None,
                      tap_layers=(), tap_block: bool = False,
                      need_logits: bool = False):
    """Python-unrolled forward recording (X, Y) per tapped layer.

    X  = residual-stream input to the block,
    Y  = mixer output pre-residual (tap_block=False, Attn NBL) or the whole
         block's delta (tap_block=True, Block NBL).
    Returns (logits, {layer_idx: (X, Y)}). Used by core.calibrate at modest
    batch sizes; the production path streams moments instead of storing taps.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, dt)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    taps = {}
    i = 0
    for gi, g in enumerate(cfg.stack):
        for r in range(g.repeat):
            for u, blk in enumerate(g.unit):
                p_u, _ = layer_params(cfg, params, i)
                want = i in tap_layers
                x_in = x if want else None
                if want and not tap_block and blk.kind in ("attn", "mamba"):
                    # mixer-only tap: run mixer, record, then ffn
                    h = rmsnorm(x, p_u["norm1"], cfg.norm_eps)
                    if blk.kind == "attn":
                        y, _kv = self_attention(cfg, p_u["mixer"], h,
                                                window=blk.window,
                                                positions=positions)
                    else:
                        y, _st = mamba_block(cfg, p_u["mixer"], h)
                    taps[i] = (x_in, y.astype(jnp.float32))
                    x = x + y.astype(x.dtype)
                    if blk.ffn == "dense":
                        h2 = rmsnorm(x, p_u["norm2"], cfg.norm_eps)
                        x = x + mlp(p_u["ffn"], h2, cfg.mlp_act).astype(x.dtype)
                    elif blk.ffn == "moe":
                        h2 = rmsnorm(x, p_u["norm2"], cfg.norm_eps)
                        y2, _ = moe_ffn(cfg, p_u["ffn"], h2)
                        x = x + y2.astype(x.dtype)
                else:
                    x, _, _ = _block_fwd(cfg, blk, p_u, x, mode="train",
                                         positions=positions, enc=enc,
                                         cache=None, pos=None, cache_len=0)
                    if want and tap_block:
                        taps[i] = (x_in, (x - x_in).astype(jnp.float32))
                i += 1
    logits = _logits(cfg, params, x) if need_logits else None
    return logits, taps
