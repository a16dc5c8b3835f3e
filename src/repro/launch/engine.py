"""Continuous-batching serving engine over slot-indexed or PAGED KV caches.

Architecture (frontend → scheduler → engine → cache):

  AsyncEngine (this module) + launch/server.py
      The serving HOST LOOP: a background thread drives ``Engine.step()``
      while client threads submit streaming requests (per-request token
      queues fed straight from ``_emit``), cancel them in any lifecycle
      state, and get reject-with-error backpressure past a bounded pending
      count. launch/server.py puts a newline-JSON TCP socket in front of
      it. Works over every engine layout below — it adds no model code.
  Scheduler (launch/scheduler.py)
      FIFO queue + NBL-aware admission budget: a fixed HBM byte budget
      divided by the per-request footprint. NBL-linearized layers carry no
      cache, so a compressed model admits more concurrent requests on the
      same budget (paper §4.2).
  Engine (this module)
      Owns params + ONE cache in one of two layouts:

      ring (default)   models/kv_cache.init_slot_cache — a full max_len
                       ring reserved per slot. Budget unit: bytes/slot.
      paged            models/paging.init_paged_cache — per-layer page
                       pools + a host-side refcounted PageAllocator and
                       page table. A request REFERENCES only the pages its
                       tokens cover; pages are allocated ON DEMAND as
                       decode crosses a page boundary, and when the pool
                       runs dry, unreferenced prefix-index entries are
                       evicted (LRU) first and only then is the YOUNGEST
                       in-flight request preempted (pages unref'd, request
                       requeued — it restarts from its prompt) so the
                       oldest requests always finish. Budget unit: pages
                       referenced, shared pages billed once
                       (scheduler.nbl_page_budget) — short requests stop
                       stranding max_len-sized rings, which converts
                       directly into admitted traffic.

      PREFIX SHARING (``prefix_sharing=True``, paged only): a host-side
      PrefixIndex maps full pages of previously-served prompt prefixes to
      the physical pages already caching them. Admission looks up the
      longest page-aligned cached prefix, bumps those pages' refcounts,
      points the new slot's page-table row at them, and prefills ONLY the
      suffix from the first divergent page (a partial prefill attending
      the shared KV through the table — the decode kernel needs no change,
      sharing is invisible below the table). Retirement/preemption only
      unref; the index holds its own reference per published page, so hot
      prefixes survive their publisher. Requires a stack with no SSM
      blocks (partial prefill cannot resume scanned state).

      CHUNKED PREFILL (``chunked_prefill=True``, paged only): a prompt is
      split into page-aligned chunks of ``prefill_chunk_tokens`` and at
      most ONE chunk is prefilled per ``step()``, interleaved with the
      batched decode of everything in flight — a long prompt no longer
      monopolizes a step, so active decodes keep emitting between chunks
      instead of stalling for the whole prefill. Each chunk reuses the
      partial-prefill path below: the request's OWN earlier chunks play
      the role of the "shared prefix" (prefix_tbl points at the slot's
      already-written pages), so no new model code path exists below the
      page table. Chunk pages are allocated chunk-by-chunk; under pool
      pressure a mid-prompt request SUSPENDS between chunks holding its
      pages (resuming when the pool recovers) and is torn down only by
      preemption. Composes with prefix sharing (lookup once at admission,
      then chunk only the suffix); gated off for SSM stacks like the
      other partial-prefill paths.

      SPECULATIVE DECODING (``drafts={m: (draft_cfg, draft_params)}`` +
      per-request ``submit(..., spec_gamma=k, draft_m=m)``, paged only):
      NBL hands the engine a free self-drafter — the SAME weights under a
      deeper linearization plan (launch/speculative.make_nbl_draft).
      Because the draft linearizes the DEEPEST layers, its surviving
      attention layers are the target's shallow ones, so the draft
      attends the target's own paged KV through the slot's page table
      (no draft cache exists). Each spec step runs one per-slot draft
      burst (γ greedy tokens from one scanned jit over a trace-time view
      of the target pools) and ONE verifier cache-extend — the PR 3/4
      partial-prefill jit re-run from the slot's last page boundary with
      γ+1 logits rows, the slot's own pages as the prefix; no new model
      code exists below the page table. The longest agreeing prefix plus
      one corrected token is emitted; rejection ROLLBACK is a pure
      per-slot length decrement (pages are position-aligned — no kpos to
      repair) plus returning the surplus candidate-span pages
      (models/paging.release_tail_pages). Greedy acceptance is EXACT:
      spec output is token-identical to ``generate()`` regardless of
      draft quality (the fuzz harness asserts it). Composes with prefix
      sharing and chunked prefill; requires temperature 0, an unsharded
      engine, and ``prompt + max_new + spec_gamma <= max_len`` (the
      candidate span must fit the page table). Sliding-window stacks
      keep ALL of a spec slot's pages resident (window page release is
      skipped: the verifier's prefix gather reads from page 0) — spec
      trades the SWA page saving for the draft/verify speedup.

      ``step()`` interleaves: (1) admission — for every free slot (and, when
      paged, enough free pages), pop a request, prefill it at batch=1,
      assign its cache (slot row / prompt pages), emit its first token
      (chunked: only record the slot as chunking — no prefill yet);
      (1b) chunked only: prefill ONE page-aligned chunk of the oldest
      chunking slot; the final chunk emits the first token and flips the
      slot to decoding within the same step; (2) one *batched* decode over
      all decoding slots with a per-slot position vector — retired/empty/
      chunking rows ride along masked (kpos = -1, an unallocated
      page-table row, or pos = -1); (3) retirement — EOS or max-token
      requests release their slot (and pages, copy-free: isolation under
      reuse is positional, see models/paging.py).

      FUSED STEP PIPELINE (``fused_step=True``, the default on paged
      SSM-free, cross-attn-free engines; docs/architecture.md): the step
      is restructured PLAN -> EXECUTE -> COMMIT. A host-side
      :class:`~repro.launch.stepplan.StepPlan` selects the step's work
      under one decode-priority TOKEN budget (``step_tokens``): every
      decoding slot is charged 1 token first, the remainder grants
      page-aligned chunk spans to mid-prompt slots (oldest admission
      first — possibly SEVERAL per step, unlike the legacy one-chunk
      rule), and the leftover paces admission
      (``Scheduler.admit(budget=...)``). Execution then launches ONE
      ``transformer.fused_step`` jit over a mixed (n_slots, W) batch —
      decode rows at width 1, chunk rows at their span width, W bucketed
      to a power of two, all sharing the live page table; per-row
      ``row_len`` masks inactive rows (0) and extracts each row's
      last-valid-token logits. Commit performs the step's single logits
      readback, emits decode tokens and final-chunk seed tokens,
      advances chunk progress (publishing prefix pages progressively)
      and retires. ``fused_step=False`` keeps the legacy two-dispatch
      path (chunk prefill + batched decode) as the parity oracle; the
      fuzz harness replays every mode through both, token-exactly.

      Slot state machine (per request)::

          admitted ──(chunked)──> chunking(pos) ──last chunk──> decoding
             │                        │   ▲                        │
             └──(non-chunked: full ───┼───┘ suspend/resume          │
                 prefill at admission)│     between steps           │
                                      ▼                             ▼
                             preempted: pages unref'd,      retired: EOS or
                             requeued, restarts from        max_new; slot +
                             its prompt                     pages recycled

      Mode compatibility (engine layout x stack family)::

          layout \\ stack      dense  SWA    SSM/hybrid  cross-attn (VLM)
          ring (default)       yes    yes    yes         yes
          paged                yes    yes    yes (slot   yes
                                             state rows)
          prefix_sharing       yes    yes    no (scan    no (enc-
                                             resume)     conditioned KV)
          chunked_prefill      yes    yes    no (scan    yes (enc rides
                                             resume)     every chunk)
          speculative          yes    yes    no (verify  no (the draft
          (drafts= + per-      (all pages    is a        must be a pure
          request spec_gamma)  stay          partial     attn/nbl plan;
                               resident)     prefill)    enc-conditioned
                                                         KV cannot be
                                                         drafted) —
                               unsharded engines only; greedy (temp 0)
          fused step           yes    yes    no (scan    no (the fused
          (fused_step=True,                  state needs batch carries
          the default; paged                 the scanned no enc rows)
          engines only)                      decode jit)
                               gates are SILENT fallbacks, not errors:
                               ring engines and SSM / cross-attn stacks
                               keep the legacy two-dispatch step path;
                               Engine(fused_step=False) forces ANY
                               engine onto it (the parity oracle the
                               fuzz harness replays against)
          async / server       yes    yes    yes*        yes*
                               (*inherits the WRAPPED layout's gates
                                verbatim: AsyncEngine/launch.server drive
                                step() from a thread and add no model code,
                                so e.g. async+chunked still refuses SSM
                                stacks and async+prefix_sharing refuses
                                SSM and cross-attn — the Engine
                                constructor raises before the host loop
                                ever starts)
          observability        yes    yes    yes         yes
          (obs=Observability)  (host-side hooks only — every layout above,
                                sync or async, carries the same metrics/
                                trace/timeline instrumentation; obs=None
                                (the default) reduces every hook site to
                                one predictable branch and the step path
                                issues ZERO additional device dispatches
                                either way)
          analysis             yes    yes    yes         yes
          (repro.analysis)     (static rules are layout-independent:
                                guarded-by/lock-order cover the Engine/
                                AsyncEngine/server locks in every mode;
                                jit-discipline covers the shared-jit
                                registry all UNSHARDED layouts route
                                through — sharded jits are allowlisted
                                per-instance by design; host-sync walks
                                _step_impl's call graph, so admission,
                                chunking, paging, decode and the spec
                                draft/verify path are all in scope, every
                                readback sanctioned per line; obs-hygiene
                                keeps the
                                observability row's zero-overhead
                                promise structural)
  Cache
      (L, n_slots, ...) slot rows, or (L, n_pages, KV, page_size, hd)
      pools + host page table (models/paging.py).

Prompt-length BUCKETING bounds the per-length prefill jits: prompts are
right-padded to the next power-of-two bucket and prefill takes a traced
``valid_len`` (logits read at valid_len-1; padded cache positions are
masked unattendable), so the jit cache holds O(log max_len) entries instead
of one per distinct length. Bucketing is auto-disabled for stacks it cannot
serve exactly: SSM/hybrid (padding corrupts the scanned state) and, in ring
mode only, sliding-window attention (padding evicts in-window ring slots;
the paged layout is position-aligned, so windows and bucketing compose).

The decode jit compiles ONCE (shapes are (n_slots, 1) regardless of how
many requests are in flight). Under a mesh the same engine runs sharded:
params/caches take their production PartitionSpecs (distributed/
sharding.py), batch/slot dims shard over "dp".
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import TYPE_CHECKING, Callable, Optional

if TYPE_CHECKING:   # the obs layer stays an optional, import-light dep
    from repro.obs import Observability

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.api import mesh_axes, shaped_spec
from repro.distributed.sharding import cache_specs, param_specs
from repro.jitcache import SHARED_JITS as _SHARED_JITS, shared_jit as _shared_jit
from repro.launch.scheduler import (
    Request, Scheduler, latency_stats, nbl_page_budget, nbl_slot_budget,
)
from repro.launch.stepplan import (
    ChunkRow, StepPlan, chunk_span, decode_first_budget,
)
from repro.models import decode_step, fused_step, prefill
from repro.models.kv_cache import assign_slot, init_slot_cache
from repro.launch.speculative import (
    accept_greedy, build_draft_cache_view, draft_burst, validate_draft,
)
from repro.models.paging import (
    DEFAULT_PAGE_SIZE, PageAllocator, PrefixIndex, assign_pages,
    build_page_table, init_paged_cache, kv_pool_bytes_per_device,
    n_caching_attn_layers, pages_per_seq, pool_pages_for_budget, pow2_ceil,
    release_tail_pages, span_pages,
)

_NULLCTX = nullcontext()     # shared no-op ctx for un-annotated jit calls


def _new_cache(make):
    """The engine's cache from ``make()``. Under a mesh it comes out of
    one jit already laid out by ``cache_specs``, so no device ever holds a
    whole pool; without one it is ``make()`` itself."""
    if not mesh_axes():
        return make()
    specs = cache_specs(jax.eval_shape(make))
    return jax.jit(make, out_shardings=specs)()  # nbl: disable=jit-discipline -- one call per engine


# The shared jit cache for UNSHARDED engines lives in repro.jitcache (so
# eval/calibrate/serve share the same registry without importing the
# engine); `_SHARED_JITS` / `_shared_jit` above are the historical local
# names. Engine closures capture only the (hashable, value-equal)
# ModelConfig plus static plan constants, so two engines over equal
# configs lower to identical jaxprs — handing them the SAME callable lets
# jax's trace cache reuse compilations across Engine instances
# (tests/benchmarks/the fuzz harness construct engines by the hundred;
# per-instance closures would recompile every one). Sharded engines keep
# per-instance jits: their in/out shardings are captured from the ambient
# mesh at construction and must not leak across meshes — those sites are
# allowlisted for the jit-discipline pass (repro.analysis) where built.


class Engine:
    """Request-level continuous-batching decode engine.

    Either ``n_slots`` or ``cache_budget_bytes`` (NBL-aware: converted via
    ``nbl_slot_budget`` / ``nbl_page_budget``) fixes the concurrency; given
    both, the budget is a ceiling. ``max_len`` bounds prompt + generated
    tokens per request.

    ``paged=True`` switches to the page-pool cache layout; ``page_size``
    must then be a power of two. ``expected_len`` is the page budget's
    per-request billing length (default ``max_len`` — conservative; pass
    the workload's typical prompt+generation length to admit more).
    ``prefix_sharing=True`` (paged, non-SSM stacks) enables copy-on-write
    prompt-prefix reuse through a PrefixIndex; ``shared_prefix_len`` is
    the billing hint for it — the prompt-prefix length (tokens) the
    workload shares, billed ONCE across the fleet instead of per request
    (scheduler.nbl_page_budget). ``chunked_prefill=True`` (paged,
    non-SSM) splits every prompt into page-aligned chunks of
    ``prefill_chunk_tokens`` (rounded up to a page multiple; default one
    page) and prefills at most one chunk per step, interleaved with the
    batched decode — see the module docstring for the slot state machine
    and the mode-compatibility table.

    ``fused_step=True`` (the default) routes paged SSM-free cross-attn-
    free engines through the plan -> execute -> commit pipeline: ONE
    fused jit per step executes the mixed decode + chunk-row batch
    (docs/architecture.md); other layouts silently keep the legacy
    two-dispatch path, and ``fused_step=False`` forces it everywhere
    (the fuzz harness's parity oracle). ``step_tokens`` (fused path
    only; default None = unbounded) is the per-step decode-priority
    token budget: decode rows are charged first, the remainder grants
    chunk spans and paces admission — it replaces the scheduler's
    ``max_prefill_tokens_per_step`` as the single pacing knob.

    Sharding is captured at CONSTRUCTION time: build the engine inside
    ``jax.set_mesh(mesh)`` to get sharded params/caches — an engine built
    un-meshed stays fully replicated even if later driven under a mesh.

    ``obs`` (an ``repro.obs.Observability``, default None = off) threads
    the metrics registry / request tracer / step timeline through every
    lifecycle transition; the registry is labeled ``engine_mode`` (ring /
    paged / prefix / chunked / chunked_shared) and ``nbl_m`` (linearized
    block count) at construction. All hooks are host-side — no device
    dispatch is ever added — and with ``obs=None`` each site costs one
    branch. ``stats_window`` (default 1024, None = unbounded) bounds the
    ``stats()`` percentile set to the most recently finished requests so a
    long-running server's stats call stops re-sorting its whole history;
    lifetime counts (``n``, counters) are unaffected.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_len: int,
                 n_slots: Optional[int] = None,
                 cache_budget_bytes: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 scheduler: Optional[Scheduler] = None,
                 donate: bool = True,
                 paged: bool = False,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 expected_len: Optional[int] = None,
                 bucket_prompts: bool = True,
                 prefix_sharing: bool = False,
                 shared_prefix_len: int = 0,
                 chunked_prefill: bool = False,
                 prefill_chunk_tokens: Optional[int] = None,
                 fused_step: bool = True,
                 step_tokens: Optional[int] = None,
                 obs: Optional["Observability"] = None,
                 stats_window: Optional[int] = 1024,
                 drafts: Optional[dict] = None):
        self.paged = bool(paged)
        self.page_size = int(page_size)
        if self.paged and self.page_size & (self.page_size - 1):
            raise ValueError(f"page_size must be a power of two, "
                             f"got {page_size}")
        self.chunked = bool(chunked_prefill)
        if self.chunked:
            if not self.paged:
                raise ValueError("chunked_prefill requires paged=True "
                                 "(chunks are page-aligned and resume "
                                 "through the page table)")
            if any(b.kind == "mamba" for b in cfg.blocks()):
                raise ValueError("chunked_prefill cannot serve SSM stacks "
                                 "(the partial prefill cannot resume "
                                 "scanned state mid-prompt)")
            ct = self.page_size if prefill_chunk_tokens is None \
                else int(prefill_chunk_tokens)
            if ct < 1:
                raise ValueError(f"prefill_chunk_tokens must be >= 1, "
                                 f"got {prefill_chunk_tokens}")
            # chunks must END on page boundaries so the next chunk's prefix
            # table covers whole pages: round UP to a page multiple
            self.chunk_tokens = -(-ct // self.page_size) * self.page_size
        else:
            self.chunk_tokens = 0
        self.prefix_sharing = bool(prefix_sharing)
        if self.prefix_sharing:
            if not self.paged:
                raise ValueError("prefix_sharing requires paged=True")
            if any(b.kind in ("mamba", "cross_attn") for b in cfg.blocks()):
                # mamba: partial prefill cannot resume scanned state.
                # cross_attn: prefix KV downstream of a cross-attn block is
                # conditioned on the request's enc embeddings, but the
                # index keys on prompt TOKENS only — sharing would reuse
                # another request's enc-contaminated KV.
                raise ValueError("prefix_sharing cannot serve SSM or "
                                 "cross-attention stacks (prefix KV is not "
                                 "a pure function of prompt tokens)")
        # speculative decoding: {draft_m: (draft_cfg, draft_params)}.
        # Registered at construction so the draft-burst jits can be keyed
        # and shared; per-request opt-in via submit(spec_gamma=, draft_m=).
        self.drafts: dict = dict(drafts) if drafts else {}
        if self.drafts:
            if not self.paged:
                raise ValueError("speculative decoding requires paged=True "
                                 "(the verifier re-prefills through the "
                                 "slot's page table)")
            if any(b.kind in ("mamba", "cross_attn") for b in cfg.blocks()):
                # mamba: the verifier is a partial prefill (cannot resume
                # scanned state). cross_attn: the draft plan has no enc
                # conditioning path, so drafted KV would diverge.
                raise ValueError("speculative decoding cannot serve SSM or "
                                 "cross-attention stacks")
            for m, (dcfg, _dp) in self.drafts.items():
                validate_draft(cfg, dcfg)
        expected_len = int(expected_len or max_len)

        n_pages = None
        if cache_budget_bytes is not None:
            if self.paged:
                n_pages = pool_pages_for_budget(cfg, cache_budget_bytes,
                                                self.page_size)
                budget_slots = nbl_page_budget(
                    cfg, cache_budget_bytes, page_size=self.page_size,
                    expected_len=expected_len,
                    shared_prefix_len=(shared_prefix_len
                                       if self.prefix_sharing else 0))
            else:
                budget_slots = nbl_slot_budget(cfg, cache_budget_bytes,
                                               max_len)
            # an explicit n_slots may narrow the budget, never exceed it
            n_slots = budget_slots if n_slots is None \
                else min(n_slots, budget_slots)
        elif n_slots is None:
            raise ValueError("need n_slots or cache_budget_bytes")
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg = cfg
        self.params = params
        self.max_len = int(max_len)
        self.n_slots = int(n_slots)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self._rng = np.random.default_rng(seed)
        self.scheduler = scheduler or Scheduler()

        blocks = cfg.blocks()
        has_mamba = any(b.kind == "mamba" for b in blocks)
        has_window = any(b.kind == "attn" and b.window is not None
                         for b in blocks)
        has_cross = any(b.kind == "cross_attn" for b in blocks)
        # exactness gates (see module docstring): SSM state is corrupted by
        # padded tokens; ring compaction evicts in-window slots on padding.
        self.bucket_prompts = (bool(bucket_prompts) and not has_mamba
                               and (self.paged or not has_window))
        # fused plan->execute->commit pipeline: a SILENT fast-path gate,
        # not an error — ring engines, SSM stacks (the fused batch cannot
        # resume scanned state mid-sequence) and cross-attn stacks (no enc
        # rows in the fused batch) keep the legacy two-dispatch step path.
        self.fused = bool(fused_step) and self.paged \
            and not has_mamba and not has_cross
        if step_tokens is not None and int(step_tokens) < 1:
            raise ValueError(f"step_tokens must be >= 1, got {step_tokens}")
        self.step_tokens = int(step_tokens) if step_tokens is not None \
            else None

        if self.paged:
            # pure sliding-window stacks can retire pages that fall out of
            # the window (the paged analogue of the ring's compaction): a
            # page is dead once it is below EVERY layer's window, so the
            # horizon is the widest window — and one global layer pins
            # everything (no release).
            windows = [b.window for b in blocks if b.kind == "attn"]
            self._page_window = (max(windows) if windows
                                 and all(w is not None for w in windows)
                                 else None)
            self._pps = pages_per_seq(self.max_len, self.page_size)
            if n_pages is None:
                n_pages = self.n_slots * self._pps   # full-reservation pool
            # a lone request must always be able to run to max_len
            if n_caching_attn_layers(cfg) > 0:
                n_pages = max(int(n_pages), self._pps)
            self.n_pages = int(n_pages)
            self.allocator = PageAllocator(self.n_pages)
            self.page_tbl = build_page_table(self.n_slots, self.max_len,
                                             self.page_size)
            self.slot_pages: list[list[int]] = [[] for _ in
                                                range(self.n_slots)]
            self.prefix_index = PrefixIndex(self.page_size) \
                if self.prefix_sharing else None
            self.cache = _new_cache(lambda: init_paged_cache(
                cfg, self.n_slots, self.max_len, page_size=self.page_size,
                n_pages=self.n_pages))
        else:
            self.n_pages = 0
            self.cache = _new_cache(lambda: init_slot_cache(
                cfg, self.n_slots, self.max_len))
        self.slot_req: list[Optional[Request]] = [None] * self.n_slots
        self.slot_pos = np.zeros(self.n_slots, np.int32)   # pos of last tok
        self.slot_tok = np.zeros(self.n_slots, np.int32)   # last emitted tok
        # chunked-prefill progress: -1 = not chunking (free or decoding);
        # >= 0 = prompt tokens already cached (always a page multiple
        # mid-prompt — only the FINAL chunk may end off a page boundary,
        # and it transitions the slot to decoding)
        self.slot_chunk_pos = np.full(self.n_slots, -1, np.int32)
        self.finished: dict[int, Request] = {}   # guarded-by: _finished_lock
        self.n_decode_steps = 0
        self.n_prefills = 0
        self.n_chunks = 0              # chunked-prefill chunks processed
        # steps whose batched decode emitted tokens WHILE a prompt was
        # still mid-chunking — the interleaving claim, counted natively so
        # smokes/benchmarks need not re-derive it from slot state
        self.n_interleaved_decode_steps = 0
        self.n_prefill_tokens = 0      # valid (unpadded) tokens prefilled
        self.n_preemptions = 0
        self.n_rejected = 0   # reject-with-error drops # guarded-by: _count_lock
        self.n_cancelled = 0           # cancel() terminal retirements
        # emission hooks (AsyncEngine installs these): on_token(req, tok)
        # fires for every generated token the moment _emit records it;
        # on_finish(req) fires exactly once when a request reaches ANY
        # terminal state (finished / rejected / cancelled); on_submit(req)
        # fires after a servable request is queued — AsyncEngine uses it to
        # wake its event-driven idle loop, so DIRECT submit() on a wrapped
        # engine is served without waiting for an unrelated wake. All run
        # on whichever thread drives the engine — keep them cheap.
        self.on_token: Optional[Callable] = None
        self.on_finish: Optional[Callable] = None
        self.on_submit: Optional[Callable] = None
        self._count_lock = threading.Lock()    # see n_rejected's guarded-by
        self._admit_seq = 0            # monotone admission counter (age)
        self.n_prefix_hits = 0         # admissions served a cached prefix
        self.n_shared_prompt_tokens = 0  # prompt tokens skipped via sharing
        # speculative counters — mirrored 1:1 by obs.on_spec_burst so the
        # fuzz harness can assert registry == engine state in lockstep
        self.n_spec_bursts = 0         # draft+verify rounds run
        self.n_spec_draft_tokens = 0   # gamma per burst (always full)
        self.n_spec_accepted_tokens = 0  # draft-origin tokens EMITTED
        self.n_spec_tokens = 0         # all spec-path tokens emitted
        # step-path dispatch split (the PR 6 "dispatch-count machinery"
        # consumer): fused counts ONE per fused-step jit launch; legacy
        # counts the dispatches the fused jit replaces — the batched
        # decode plus each chunk-prefill jit. Admission prefills and spec
        # draft/verify launches are identical on both paths and excluded.
        self.n_fused_dispatches = 0
        self.n_legacy_dispatches = 0
        self._budget_util_sum = 0.0    # per planned step, for stats()
        self._n_planned_steps = 0      # fused steps that planned any work
        self._pool_in_use_sum = 0      # allocator occupancy, per decode step
        self.n_finished = 0   # lifetime served count # guarded-by: _finished_lock
        # guards the finished dict + the stats window deque: _emit/_reject/
        # _finish_cancelled write on the step thread while stats() snapshots
        # (and AsyncEngine's retain_results=False pops) from client threads
        self._finished_lock = threading.Lock()
        self.stats_window = stats_window
        self._recent_done = (deque(maxlen=int(stats_window))  # guarded-by: _finished_lock
                             if stats_window else None)
        self.obs = obs
        if obs is not None:
            obs.bind(engine_mode=self.mode_name,
                     nbl_m=sum(1 for b in blocks if b.kind == "nbl"))
            obs.g_slots.set(self.n_slots)
            obs.g_kv_pool.set(kv_pool_bytes_per_device(self.cache))

        sharded = bool(mesh_axes())
        pspecs = param_specs(jax.eval_shape(lambda: params)) \
            if sharded else None
        cspecs = cache_specs(jax.eval_shape(lambda: self.cache)) \
            if sharded else None
        if sharded:
            # the jits below pin their inputs to these layouts, and jit
            # refuses a committed array laid out otherwise: place the
            # params once here (the cache is born in its layout)
            self.params = jax.device_put(params, pspecs)

        dkw = dict(donate_argnums=(2,)) if donate else {}
        akw = dict(donate_argnums=(0,)) if donate else {}
        if self.paged:
            def _decode(p, token, cache, pos, tbl):
                return decode_step(cfg, p, token, cache, pos, page_tbl=tbl)
        else:
            def _decode(p, token, cache, pos):
                return decode_step(cfg, p, token, cache, pos)

        def _assign(slot_cache, pcache, slot):
            return assign_slot(slot_cache, pcache, slot)

        if sharded:
            tok_spec = shaped_spec((self.n_slots, 1), "dp", None)
            pos_spec = shaped_spec((self.n_slots,), "dp")
            din = (pspecs, tok_spec, cspecs, pos_spec)
            if self.paged:
                din += (shaped_spec((self.n_slots, self._pps), "dp", None),)
            self._decode_jit = jax.jit(  # nbl: disable=jit-discipline -- sharded, per-instance by design
                _decode, in_shardings=din,
                out_shardings=(None, cspecs), **dkw)
            self._assign_jit = jax.jit(  # nbl: disable=jit-discipline -- sharded, per-instance by design
                _assign, in_shardings=(cspecs, None, None),
                out_shardings=cspecs, **akw)
        else:
            self._decode_jit = _shared_jit(
                ("decode", cfg, self.paged, donate),
                lambda: jax.jit(_decode, **dkw))
            self._assign_jit = _shared_jit(
                ("assign_slot", donate), lambda: jax.jit(_assign, **akw))
        self._akw, self._cspecs = akw, cspecs
        self._donate = bool(donate)
        # under a mesh the batch=1 prefill cache must come out in the same
        # production layout the slot cache uses, so assignment never
        # reshards on admission.
        self._pspecs = pspecs
        self._sharded = sharded
        # the mesh every step runs under: jits resolve their specs, and the
        # paged kernel its shard_map, against the AMBIENT mesh, which is
        # per-thread — AsyncEngine steps on a thread of its own
        self._mesh = jax.sharding.get_mesh() if sharded else None
        if self.drafts and sharded:
            # the draft-burst view gathers raw pool leaves at trace time;
            # it has no sharding specs, so spec stays unsharded-only
            raise ValueError("speculative decoding requires an unsharded "
                             "engine (the draft cache view carries no "
                             "sharding specs)")
        self._prefill_jits: dict = {}   # (bucket, with_enc) -> jit fn
        self._assign_paged_jits: dict = {}   # prefill cache_len -> jit fn
        self._spec_draft_jits: dict = {}     # (draft_m, gamma) -> burst jit
        self._fused_jits: dict = {}          # row width W -> fused-step jit

    # ------------------------------------------------------------- admin --

    @property
    def mode_name(self) -> str:
        """Canonical mode label (the ``engine_mode`` metrics label and the
        benchmark scenario axis): ring / paged / prefix / chunked /
        chunked_shared."""
        if self.chunked:
            return "chunked_shared" if self.prefix_sharing else "chunked"
        if self.prefix_sharing:
            return "prefix"
        return "paged" if self.paged else "ring"

    def _spec_guard(self, plen: int, max_new: int, spec_gamma: int,
                    draft_m) -> Optional[str]:
        """Why a ``spec_gamma > 0`` submission cannot be served, or None.
        Centralized so ``submit`` and the admission-time guard (direct
        scheduler submissions bypass ``submit``) reject identically."""
        if spec_gamma <= 0:
            return None
        if not self.drafts:
            return ("spec_gamma set but no drafts registered "
                    "(pass drafts= to the Engine constructor)")
        if draft_m is not None and draft_m not in self.drafts:
            return (f"draft_m={draft_m} not registered "
                    f"(have {sorted(self.drafts)})")
        if self.temperature > 0.0:
            return ("speculative decoding requires temperature 0 "
                    "(greedy acceptance)")
        if plen + max_new + spec_gamma > self.max_len:
            return (f"prompt({plen}) + max_new({max_new}) + spec_gamma"
                    f"({spec_gamma}) exceeds max_len={self.max_len} "
                    f"(the candidate span must fit the page table)")
        return None

    def submit(self, prompt, max_new: int, *, enc=None,
               spec_gamma: int = 0, draft_m: Optional[int] = None,
               strict: bool = False) -> int:
        """Queue a request; returns its id. ``prompt`` 1-D int tokens.

        ``spec_gamma > 0`` opts this request into speculative decoding
        (γ drafted tokens per step through the ``drafts`` registry;
        ``draft_m`` picks the linearization depth, default the first
        registered). Spec requests must satisfy
        ``prompt + max_new + spec_gamma <= max_len``.

        An unservable submission (empty prompt, ``max_new < 1``,
        prompt + max_new > max_len, or an unservable spec request) is
        REJECTED-WITH-ERROR: the request is
        recorded terminally (``Request.error`` set, surfaced in
        ``finished`` / ``n_rejected``, excluded from latency percentiles)
        and its rid still returned — the SAME surface the admission-time
        guard uses for direct scheduler submissions, so a serving frontend
        handles every rejection by reading one field instead of catching
        an exception that would kill its host loop mid-request.
        ``strict=True`` restores the raising behavior for direct/test
        use."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            err = "empty prompt"
        elif max_new < 1:
            err = f"max_new must be >= 1, got {max_new}"
        elif prompt.size + max_new > self.max_len:
            err = (f"prompt({prompt.size}) + max_new({max_new}) exceeds "
                   f"engine max_len={self.max_len}")
        elif (serr := self._spec_guard(prompt.size, max_new, spec_gamma,
                                       draft_m)) is not None:
            err = serr
        else:
            req = self.scheduler.make_request(prompt, max_new, enc=enc,
                                              spec_gamma=spec_gamma,
                                              draft_m=draft_m)
            self.scheduler.submit_request(req)
            if self.obs is not None:
                self.obs.on_submit(req, len(self.scheduler))
            if self.on_submit is not None:
                self.on_submit(req)
            return req.rid
        if strict:
            raise ValueError(err)
        return self._submit_rejected(prompt, max_new, err, enc=enc)

    def _submit_rejected(self, prompt, max_new: int, reason: str, *,
                         enc=None) -> int:
        """Record a request as rejected WITHOUT queueing it (unservable
        submission, or AsyncEngine backpressure); returns its rid."""
        req = self.scheduler.make_request(prompt, max_new, enc=enc)
        self._reject(req, reason)
        return req.rid

    @property
    def active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    @property
    def has_work(self) -> bool:
        return bool(self.active_slots) or len(self.scheduler) > 0

    # ----------------------------------------------------------- serving --

    def _prefill_plan(self, prompt_len: int) -> tuple[int, int, bool]:
        """(token_len, cache_len, masked) for a prompt. Bucketing pads the
        TOKENS to a power-of-two bucket and masks with valid_len; without
        it, tokens stay exact (mamba-safe) and only the paged CACHE length
        rounds up to a page multiple (pages tile the cache)."""
        if self.bucket_prompts:
            b = pow2_ceil(prompt_len)
            if self.paged:
                b = min(max(b, self.page_size), self._pps * self.page_size)
            else:
                b = min(b, self.max_len)
            return b, (b if self.paged else self.max_len), True
        if self.paged:
            cl = pages_per_seq(prompt_len, self.page_size) * self.page_size
            return prompt_len, cl, False
        return prompt_len, self.max_len, False

    def _prefill_fn(self, token_len: int, cache_len: int, masked: bool,
                    with_enc: bool, prefix_pages: int = 0,
                    n_logits: int = 1):
        """Jit cache keyed on the full prefill plan — the plan is computed
        once per admission in ``_admit`` and passed through, so the cached
        function can never disagree with the caller about cache width or
        padding masking. ``prefix_pages`` > 0 selects the PARTIAL prefill
        (prefix sharing): the jit additionally takes the engine's paged
        cache, a (prefix_pages,) physical-page table and the traced prefix
        token count, and the tokens are the suffix only; the bucket count
        is a power of two so the jit cache stays O(log²) in the plan.
        ``n_logits`` > 1 is the speculative VERIFIER: the last n_logits
        valid rows come back (oldest first) so one cache-extend scores a
        whole candidate block."""
        key = (token_len, cache_len, masked, with_enc, prefix_pages,
               n_logits)
        fn = self._prefill_jits.get(key)
        if fn is None:
            cfg, paged = self.cfg, self.paged

            if prefix_pages:
                def _prefill(p, tokens, valid_len, pool, ptbl, plen0,
                             enc=None):
                    return prefill(cfg, p, tokens, enc=enc,
                                   cache_len=cache_len, paged=paged,
                                   valid_len=valid_len if masked else None,
                                   prefix_cache=pool, prefix_tbl=ptbl,
                                   prefix_len=plen0, n_logits=n_logits)
            else:
                def _prefill(p, tokens, valid_len, enc=None):
                    return prefill(cfg, p, tokens, enc=enc,
                                   cache_len=cache_len, paged=paged,
                                   valid_len=valid_len if masked else None,
                                   n_logits=n_logits)

            if self._sharded:
                from repro.launch.specs import cache_shapes
                # prefill returns the POSITION-ALIGNED batch=1 layout even
                # when paged; its specs are the plain cache ones
                pcspecs = cache_specs(cache_shapes(cfg, 1, cache_len))
                ins = (self._pspecs, None, None)
                if prefix_pages:
                    ins += (self._cspecs, None, None)
                ins += (None,) if with_enc else ()
                kw = dict(in_shardings=ins,
                          out_shardings=(None, pcspecs))
                fn = jax.jit(_prefill, **kw)  # nbl: disable=jit-discipline -- sharded, per-instance by design
            else:
                fn = _shared_jit(("prefill", cfg, paged) + key,
                                 lambda: jax.jit(_prefill))
            self._prefill_jits[key] = fn
        return fn

    def _assign_paged_fn(self, cache_len: int):
        fn = self._assign_paged_jits.get(cache_len)
        if fn is None:
            cfg, ps = self.cfg, self.page_size

            def _assign(cache, pcache, slot, page_ids):
                return assign_pages(cfg, cache, pcache, slot, page_ids,
                                    page_size=ps)

            kw = dict(self._akw)
            if self._sharded:
                from repro.launch.specs import cache_shapes
                pcspecs = cache_specs(cache_shapes(cfg, 1, cache_len))
                kw.update(in_shardings=
                    (self._cspecs, pcspecs, None, None),
                    out_shardings=self._cspecs)
                fn = jax.jit(_assign, **kw)  # nbl: disable=jit-discipline -- sharded, per-instance by design
            else:
                fn = _shared_jit(("assign_paged", cfg, ps, bool(kw)),
                                 lambda: jax.jit(_assign, **kw))
            self._assign_paged_jits[cache_len] = fn
        return fn

    def _fused_fn(self, width: int):
        """Fused-step jit for row width ``width`` (a power of two — the
        StepPlan buckets spans, so the cache stays O(log chunk_tokens)):
        ONE dispatch executes the whole (n_slots, W) mixed batch of
        decode rows (len 1), chunk rows (their span) and inactive rows
        (len 0) against the live page table. Donated like the decode jit:
        the old cache buffers are dead once the step's pages are
        written."""
        fn = self._fused_jits.get(width)
        if fn is None:
            cfg = self.cfg

            def _fused(p, tokens, cache, row_pos, row_len, tbl):
                return fused_step(cfg, p, tokens, cache, row_pos, row_len,
                                  tbl)

            dkw = dict(donate_argnums=(2,)) if self._donate else {}
            if self._sharded:
                tok_spec = shaped_spec((self.n_slots, width), "dp", None)
                vec_spec = shaped_spec((self.n_slots,), "dp")
                din = (self._pspecs, tok_spec, self._cspecs, vec_spec,
                       vec_spec,
                       shaped_spec((self.n_slots, self._pps), "dp", None))
                fn = jax.jit(  # nbl: disable=jit-discipline -- sharded, per-instance by design
                    _fused, in_shardings=din,
                    out_shardings=(None, self._cspecs),
                    **dkw)
            else:
                fn = _shared_jit(("fused_step", cfg, width, self._donate),
                                 lambda: jax.jit(_fused, **dkw))
            self._fused_jits[width] = fn
        return fn

    def _sample(self, logits_row: np.ndarray) -> int:
        """logits_row: (V,) float32."""
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row / self.temperature
        z = z - z.max()
        p = np.exp(z)
        return int(self._rng.choice(z.shape[0], p=p / p.sum()))

    def _emit(self, req: Request, slot: int, tok: int, now: float) -> None:
        """Record one generated token; retire the slot when done."""
        req.tokens.append(tok)
        first = not req.t_first
        if first:
            req.t_first = now
        self.slot_tok[slot] = tok
        if self.obs is not None:
            self.obs.on_token(req, first, now)
        if self.on_token is not None:
            self.on_token(req, tok)
        done = (len(req.tokens) >= req.max_new
                or (self.eos_id is not None and tok == self.eos_id))
        if done:
            # no cache scrub needed: ring rows are overwritten wholesale at
            # the next tenancy; freed pages are position-masked until the
            # next owner overwrites them (models/paging.py).
            req.t_finish = now
            with self._finished_lock:
                self.finished[req.rid] = req
                self.n_finished += 1
                if self._recent_done is not None:
                    self._recent_done.append(req)
            self.slot_req[slot] = None
            if self.paged:
                self._release_pages(slot)
            if self.obs is not None:
                self.obs.on_retire(req, now)
            if self.on_finish is not None:
                self.on_finish(req)

    def _release_pages(self, slot: int) -> None:
        """Drop this slot's references; a page leaves the pool only when no
        other slot and no prefix-index entry still references it."""
        if self.slot_pages[slot]:
            self.allocator.unref(self.slot_pages[slot])
            self.slot_pages[slot] = []
        self.page_tbl[slot, :] = -1

    def _preempt(self, slot: int) -> None:
        """Evict the request in ``slot`` mid-decode: unref its pages and
        send it back to the queue front. It restarts from its prompt —
        generated tokens are discarded and the TTFT clock rewinds to
        unserved; the restart is counted on the request so latency_stats
        can split preempted from clean TTFT."""
        req = self.slot_req[slot]
        assert req is not None
        if self.obs is not None:
            self.obs.on_preempt(req, time.monotonic(), len(req.tokens))
        self._release_pages(slot)
        self.slot_req[slot] = None
        self.slot_chunk_pos[slot] = -1      # mid-prompt progress discarded
        req.tokens = []
        req.t_first = 0.0
        req.t_admit = 0.0
        req.n_preemptions += 1
        self.scheduler.requeue(req)
        self.n_preemptions += 1

    def _reclaim_pages(self, need: int) -> bool:
        """Free pool capacity without touching in-flight work: evict LRU
        unreferenced prefix-index entries until ``need`` pages are free.
        Runs BEFORE any preemption — cached-but-idle prefixes are the
        cheapest pages to give back. If eviction provably cannot reach
        ``need`` (an oversized ask), nothing is evicted at all: a request
        that will defer anyway must not wipe everyone else's warm cache."""
        if self.allocator.free_pages >= need:
            return True
        if not self.prefix_sharing:
            return False
        if self.allocator.free_pages + \
                self.prefix_index.evictable_pages(self.allocator) < need:
            return False
        while self.allocator.free_pages < need:
            if not self.prefix_index.evict_lru(
                    self.allocator, need - self.allocator.free_pages):
                return False   # unreachable: the evictable bound is exact
        return True

    def _youngest_active(self) -> int:
        return max(self.active_slots,
                   key=lambda s: self.slot_req[s].admit_seq)

    def _release_window_pages(self, slot: int, pos: int) -> None:
        """Free this slot's pages that sit entirely below the attention
        horizon (positions < pos - window + 1): the decode mask can provably
        never read them, so the -1 table entry and the window predicate
        coincide — token output is unchanged (asserted by the paged SWA
        parity test) while the pool stops pinning O(len) pages per
        request."""
        horizon = pos - self._page_window + 1
        n_dead = max(0, min(horizon // self.page_size, self._pps))
        dead = [int(p) for p in self.page_tbl[slot, :n_dead] if p >= 0]
        if dead:
            self.allocator.unref(dead)
            self.page_tbl[slot, :n_dead] = -1
            gone = set(dead)
            self.slot_pages[slot] = [p for p in self.slot_pages[slot]
                                     if p not in gone]

    def _ensure_decode_pages(self) -> None:
        """Allocate the page each active slot's next write lands in; on a
        dry pool, evict unreferenced prefix-index entries (LRU) first, then
        preempt the youngest request until the fault is served (each round
        frees >= 1 page, so this terminates). Decode writes always land at
        or past a slot's first divergent page, so a faulted page is never
        a shared one — sharing needs no copy here."""
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            if self.slot_chunk_pos[slot] >= 0:
                continue   # mid-prompt: the chunk path owns these pages
            g = req.spec_gamma if self.drafts else 0
            if self._page_window is not None and not g:
                # spec slots keep ALL pages resident: the verifier's prefix
                # gather reads the table row from page 0 and a released
                # (-1) entry would clip to physical page 0 — garbage KV
                self._release_window_pages(slot, int(self.slot_pos[slot]))
            pos = int(self.slot_pos[slot])
            # a spec slot's verify writes positions [pos, pos + g]; plain
            # decode writes only position pos (g = 0)
            first_pg = pos // self.page_size
            last_pg = (pos + g) // self.page_size
            for lp in range(first_pg, last_pg + 1):
                if self.page_tbl[slot, lp] >= 0:
                    continue
                while self.slot_req[slot] is not None:
                    ids = self.allocator.alloc(1)
                    if ids is not None:
                        self.page_tbl[slot, lp] = ids[0]
                        self.slot_pages[slot].append(ids[0])
                        break
                    if self._reclaim_pages(1):
                        continue
                    self._preempt(self._youngest_active())
                if self.slot_req[slot] is None:
                    break   # this slot itself got preempted mid-fault

    def _prefix_lookup(self, req: Request) -> tuple[int, list[int]]:
        """Longest page-aligned cached prefix of the prompt; the hit pages
        are ref'd (pinned) IMMEDIATELY so a subsequent reclaim pass can
        never evict them between lookup and admission. The pin becomes the
        slot's reference on admission; the caller must unref on deferral."""
        if not self.prefix_sharing:
            return 0, []
        k, ids = self.prefix_index.lookup(req.prompt)
        if k:
            self.allocator.ref(ids)
        return k, ids

    def _reject(self, req: Request, reason: str) -> None:
        """Drop an unservable request at admission (the engine-level guard
        behind Scheduler.submit, which cannot know this engine's max_len):
        marked errored + finished so run() terminates, excluded from
        latency percentiles."""
        req.error = reason
        req.t_finish = time.monotonic()
        with self._finished_lock:
            self.finished[req.rid] = req
        # the one counter two threads can bump (a client thread rejecting
        # in submit vs the step thread rejecting at admission): += is a
        # non-atomic read-modify-write
        with self._count_lock:
            self.n_rejected += 1
        if self.obs is not None:
            self.obs.on_reject(req, req.t_finish)
        if self.on_finish is not None:
            self.on_finish(req)

    def cancel(self, rid: int) -> bool:
        """Terminally retire request ``rid`` in ANY lifecycle state —
        queued (never admitted), chunking mid-prompt, or decoding — with
        allocator invariants intact: the slot's page references are
        dropped wholesale (``slot_pages`` covers prompt, decode AND
        pinned shared-prefix pages, so one unref releases every reference
        this request holds; pages another slot or the prefix index still
        references survive, exactly like retirement), the slot and its
        chunking progress are recycled, and the request is recorded
        cancelled-with-partial-tokens (generated-so-far tokens KEPT;
        ``latency_stats`` excludes it from percentiles so a 0.0 t_first
        sentinel can never become a garbage TTFT). Prefix-index entries
        this request published are NOT torn down — the index holds its own
        reference per page and hot prefixes outlive their publisher.

        Returns True if the request was found live and cancelled; False if
        it is already terminal (or unknown). NOT thread-safe: call from
        the thread driving ``step()`` — the async host loop routes client
        cancellations through an inbox drained between steps."""
        # terminal check under the lock: a client thread's reject-with-error
        # (submit on a wrapped engine) can be writing finished concurrently
        with self._finished_lock:
            if rid in self.finished:
                return False
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.rid == rid:
                if self.paged:
                    self._release_pages(slot)
                self.slot_req[slot] = None
                self.slot_chunk_pos[slot] = -1
                return self._finish_cancelled(req)
        req = self.scheduler.remove(rid)
        if req is not None:
            return self._finish_cancelled(req)
        return False

    def _finish_cancelled(self, req: Request) -> bool:
        req.cancelled = True
        req.t_finish = time.monotonic()
        with self._finished_lock:
            self.finished[req.rid] = req
        self.n_cancelled += 1
        if self.obs is not None:
            self.obs.on_cancel(req, req.t_finish)
        if self.on_finish is not None:
            self.on_finish(req)
        return True

    def partials(self) -> dict[int, np.ndarray]:
        """Generated-so-far tokens of every request still IN FLIGHT
        (admitted slots mid-generation, plus queued requests as empty
        arrays). ``run(max_steps)`` returning only ``finished`` used to
        silently discard these partial generations — a bounded drain now
        reads them here explicitly."""
        out = {}
        for req in self.slot_req:
            if req is not None:
                out[req.rid] = np.asarray(req.tokens, np.int32)
        for req in list(self.scheduler.queue):
            out[req.rid] = np.asarray(req.tokens, np.int32)
        return out

    def _admit(self, req: Request, slot: int, n_shared: int = 0,
               shared_ids=()) -> None:
        now = time.monotonic()
        req.t_admit = now
        self._admit_seq += 1
        req.admit_seq = self._admit_seq
        if self.obs is not None:
            self.obs.on_admit(req, now, self.chunked)
        plen = len(req.prompt)
        ps = self.page_size
        start = n_shared * ps                    # first suffix position
        if n_shared:
            self.page_tbl[slot, :n_shared] = shared_ids
            self.slot_pages[slot] = list(shared_ids)   # pin -> slot ref
        if n_shared:
            self.n_prefix_hits += 1
            self.n_shared_prompt_tokens += start
            if self.obs is not None:
                self.obs.on_prefix_hit(req, start)
        if self.chunked:
            # admitted -> chunking(start): no prefill here — _chunk_step
            # prefills one page-aligned chunk per step, starting past any
            # shared prefix (sharing composes: lookup once, chunk the
            # suffix only).
            self.slot_req[slot] = req
            self.slot_chunk_pos[slot] = start
            return
        if self.paged:
            npg = pages_per_seq(plen, ps)
            ids = self.allocator.alloc(npg - n_shared)
            assert ids is not None, "admission checked page availability"
            self.page_tbl[slot, n_shared:npg] = ids
            self.slot_pages[slot].extend(ids)    # [] or the shared prefix
            logits = self._run_partial_prefill(slot, req, start, plen)
        else:                                    # ring: n_shared is 0
            token_len, cache_len, masked = self._prefill_plan(plen)
            tokens = np.zeros(token_len, np.int32)
            tokens[:plen] = req.prompt
            fn = self._prefill_fn(token_len, cache_len, masked,
                                  req.enc is not None)
            args = (self.params, jnp.asarray(tokens)[None],
                    jnp.int32(plen))
            args += (jnp.asarray(req.enc)[None],) \
                if req.enc is not None else ()
            with (self.obs.annotate("nbl.prefill")
                  if self.obs is not None else _NULLCTX):
                logits, pcache = fn(*args)
            self.n_prefills += 1
            self.n_prefill_tokens += plen
            if self.obs is not None:
                self.obs.on_prefill(plen)
            self.cache = self._assign_jit(self.cache, pcache,
                                          jnp.int32(slot))
        self.slot_req[slot] = req
        self.slot_pos[slot] = plen               # position of its 1st token
        if self.obs is not None:
            self.obs.on_prefill_done(req, time.monotonic(), plen)
        # host-sync: readback -- the admission prefill's last-token logits
        # row: one deliberate device->host fetch per admitted request
        tok = self._sample(np.asarray(logits[0, -1], np.float32))
        self._emit(req, slot, tok, time.monotonic())

    def _run_partial_prefill(self, slot: int, req: Request,
                             start: int, end: int):
        """Prefill prompt[start:end) of ``slot``'s request into the PAGED
        cache (``start`` page-aligned; the span's table entries already
        allocated): pad/bucket the span, hand pages [0, start/ps) from the
        slot's own table row to the partial-prefill jit as the prefix,
        page-assign the returned cache, and publish full pages to the
        prefix index. BOTH partial-prefill callers run through here — the
        shared-prefix suffix at admission (_admit) and the chunked
        engine's per-step chunk (_chunk_step) — so their call conventions
        cannot drift apart. Returns the span's last-token logits."""
        ps = self.page_size
        span = req.prompt[start:end]
        token_len, cache_len, masked = self._prefill_plan(len(span))
        tokens = np.zeros(token_len, np.int32)
        tokens[:len(span)] = span
        start_pg = start // ps
        pb = pow2_ceil(start_pg) if start_pg else 0
        fn = self._prefill_fn(token_len, cache_len, masked,
                              req.enc is not None, prefix_pages=pb)
        args = (self.params, jnp.asarray(tokens)[None],
                jnp.int32(len(span)))
        if pb:
            ptbl = np.full(pb, -1, np.int32)
            ptbl[:start_pg] = self.page_tbl[slot, :start_pg]
            args += (self.cache, jnp.asarray(ptbl), jnp.int32(start))
        args += (jnp.asarray(req.enc)[None],) if req.enc is not None else ()
        with (self.obs.annotate("nbl.prefill")
              if self.obs is not None else _NULLCTX):
            logits, pcache = fn(*args)
        self.n_prefills += 1
        self.n_prefill_tokens += len(span)
        if self.obs is not None:
            self.obs.on_prefill(len(span))
        afn = self._assign_paged_fn(cache_len)
        # span tiles map to logical pages [start_pg, ...): hand the assign
        # jit the table row from there, right-padded back to the (static)
        # full row width
        row = np.full(self._pps, -1, np.int32)
        row[:self._pps - start_pg] = self.page_tbl[slot, start_pg:]
        self.cache = afn(self.cache, pcache, jnp.int32(slot),
                         jnp.asarray(row))
        if self.prefix_sharing and end // ps:
            # publish every FULL page written so far — PROGRESSIVELY for
            # chunks, so later admissions can share a long prompt's head
            # while its tail still chunks (earlier/shared pages are
            # already indexed; new nodes take the index's own reference)
            self.prefix_index.insert(req.prompt[:end],
                                     self.page_tbl[slot, :end // ps],
                                     self.allocator)
        return logits

    # ------------------------------------------------------- speculative --

    def _spec_draft_fn(self, m: int, gamma: int):
        """Draft-burst jit for registry entry ``m`` at width ``gamma``:
        builds the target-pool cache view at trace time and scans γ greedy
        decode steps. NOT donated — the target cache must survive the
        burst untouched (the view's in-burst KV writes die with the
        trace)."""
        key = (m, gamma)
        fn = self._spec_draft_jits.get(key)
        if fn is None:
            cfg = self.cfg
            dcfg, _dp = self.drafts[m]

            def _burst(dp, cache, token, pos, tbl):
                view = build_draft_cache_view(cfg, dcfg, cache)
                return draft_burst(dcfg, dp, view, token, pos, tbl, gamma)

            fn = _shared_jit(("spec_draft", cfg, dcfg, gamma),
                             lambda: jax.jit(_burst))
            self._spec_draft_jits[key] = fn
        return fn

    def _run_spec_verify(self, slot: int, req: Request, span: np.ndarray,
                         start: int, gamma: int):
        """Score a candidate block with ONE cache-extend: re-prefill
        ``span`` (the slot's tokens from its last page boundary ``start``
        plus the γ draft tokens) with the slot's own pages [0, start/ps)
        as the prefix, page-assign the result, and return the last γ+1
        logits rows (oldest first — rows for positions pos..pos+γ).
        The partial-prefill twin of ``_run_partial_prefill`` minus its
        prompt bookkeeping: no prefix-index publication, no n_prefills /
        prefill-token accounting — verify work is counted on the spec
        counters so the fuzz harness's prefill oracles stay exact."""
        ps = self.page_size
        token_len, cache_len, masked = self._prefill_plan(len(span))
        tokens = np.zeros(token_len, np.int32)
        tokens[:len(span)] = span
        start_pg = start // ps
        pb = pow2_ceil(start_pg) if start_pg else 0
        # enc is structurally None here: spec refuses cross-attn stacks
        fn = self._prefill_fn(token_len, cache_len, masked, False,
                              prefix_pages=pb, n_logits=gamma + 1)
        args = (self.params, jnp.asarray(tokens)[None],
                jnp.int32(len(span)))
        if pb:
            ptbl = np.full(pb, -1, np.int32)
            ptbl[:start_pg] = self.page_tbl[slot, :start_pg]
            args += (self.cache, jnp.asarray(ptbl), jnp.int32(start))
        with (self.obs.annotate("nbl.spec_verify")
              if self.obs is not None else _NULLCTX):
            logits, pcache = fn(*args)
        afn = self._assign_paged_fn(cache_len)
        row = np.full(self._pps, -1, np.int32)
        row[:self._pps - start_pg] = self.page_tbl[slot, start_pg:]
        self.cache = afn(self.cache, pcache, jnp.int32(slot),
                         jnp.asarray(row))
        return logits

    def _spec_slot_step(self, slot: int) -> int:
        """One draft-and-verify round for a spec slot: γ greedy draft
        tokens from the burst jit, one verifier cache-extend, per-row
        greedy acceptance, then ROLLBACK — the slot's committed length is
        whatever was emitted (a pure ``slot_pos`` bookkeeping fact; the
        rejected tail's KV is dead by the write-before-attend invariant)
        and surplus candidate-span pages go back to the pool. Returns
        #tokens emitted."""
        req = self.slot_req[slot]
        assert req is not None and req.spec_gamma > 0
        gamma = req.spec_gamma
        m = req.draft_m if req.draft_m is not None else next(iter(self.drafts))
        _dcfg, dparams = self.drafts[m]
        ps = self.page_size
        pos = int(self.slot_pos[slot])
        t0 = time.monotonic()
        fn = self._spec_draft_fn(m, gamma)
        with (self.obs.annotate("nbl.spec_draft")
              if self.obs is not None else _NULLCTX):
            prop = fn(dparams, self.cache,
                      jnp.asarray(self.slot_tok[slot:slot + 1, None]),
                      jnp.asarray(self.slot_pos[slot:slot + 1]),
                      jnp.asarray(self.page_tbl[slot:slot + 1]))
        # host-sync: readback -- the γ draft tokens must come host-side to
        # build the verify span (and the burst must complete before the
        # verifier's assign donates the cache)
        draft = np.asarray(prop[0], np.int32)               # (gamma,)
        # committed history covers positions [0, pos]; the verify span
        # restarts from the slot's last PAGE boundary so the prefix table
        # covers whole pages (span length >= gamma+1 since pos >= aligned)
        hist = np.concatenate([req.prompt,                  # host-only:
                               np.fromiter(req.tokens, np.int32,
                                           len(req.tokens))])
        aligned = (pos // ps) * ps
        span = np.concatenate([hist[aligned:], draft]).astype(np.int32)
        logits = self._run_spec_verify(slot, req, span, aligned, gamma)
        # host-sync: readback -- the verifier's γ+1 argmax rows drive
        # host-side acceptance (greedy: temperature 0 by construction)
        want = np.argmax(np.asarray(logits[0], np.float32),
                         axis=-1).astype(np.int32)          # (gamma+1,)
        n = int(accept_greedy(draft[None], want[None])[0])
        block = [int(t) for t in draft[:n]] + [int(want[n])]
        # the emission PLAN (post-truncation: max_new budget, first EOS)
        # is computed before any _emit so the burst's obs record lands
        # before a final token retires the request's trace
        remaining = req.max_new - len(req.tokens)
        plan: list[int] = []
        acc = 0
        for i, t in enumerate(block[:remaining]):
            plan.append(t)
            if i < n:
                acc += 1
            if self.eos_id is not None and t == self.eos_id:
                break
        self.n_spec_bursts += 1
        self.n_spec_draft_tokens += gamma
        self.n_spec_accepted_tokens += acc
        self.n_spec_tokens += len(plan)
        if self.obs is not None:
            self.obs.on_spec_burst(req, t0, time.monotonic(), gamma, acc,
                                   len(plan))
        now = time.monotonic()
        for t in plan:
            self.slot_pos[slot] += 1
            self._emit(req, slot, t, now)
        if self.slot_req[slot] is not None:
            # rollback: drop pages strictly beyond the one the slot's next
            # write (position slot_pos) lands in — the rejected tail's
            # pages are always private (aligned/ps >= any shared page)
            freed = release_tail_pages(self.page_tbl[slot],
                                       int(self.slot_pos[slot]), ps,
                                       self.allocator)
            if freed:
                gone = set(freed)
                self.slot_pages[slot] = [p for p in self.slot_pages[slot]
                                         if p not in gone]
        return len(plan)

    def _fault_pages(self, req: Request) -> int:
        """Worst-case pages this request can fault in ONE step once
        decoding: 1 (the next boundary crossing), plus the candidate-span
        pages a spec request's verifier may need (γ extra positions)."""
        if req.spec_gamma > 0 and self.drafts:
            return 1 + pages_per_seq(req.spec_gamma, self.page_size)
        return 1

    def _fault_reserve(self) -> int:
        """Headroom pages for everything in flight (the per-request fault
        bound summed) — spec requests reserve their candidate span, so
        admission cannot trade itself for a next-step preemption."""
        return sum(self._fault_pages(self.slot_req[s])
                   for s in self.active_slots)

    def _can_admit(self, req: Request, n_shared: int = 0) -> bool:
        """Paged admission gate, in REFERENCED pages (shared prefix pages
        are already referenced and bill nothing here): the prompt's NEW
        pages must be free, plus fault headroom per in-flight request
        (each may fault a page on the next boundary, γ+1 candidate-span
        pages for spec requests — admitting into that
        reserve would just trade the admission for a preemption). A
        page-aligned prompt faults a fresh page on its very first decode
        write, so it counts in the reserve too. Under pressure, LRU
        unreferenced prefix-index entries are reclaimed before giving up."""
        if not self.paged:
            return True
        plen = len(req.prompt)
        if self.chunked:
            # chunk-granular admission: only the FIRST chunk's new pages
            # must be free (later chunks allocate as they run, suspending
            # under pressure), plus the usual fault reserve per in-flight
            # request — chunked admission paces by actual page demand, not
            # the whole prompt.
            first_end = min(n_shared * self.page_size + self.chunk_tokens,
                            plen)
            need = (pages_per_seq(first_end, self.page_size) - n_shared
                    + self._fault_reserve())
            return (self.allocator.free_pages >= need
                    or self._reclaim_pages(need))
        npg = pages_per_seq(plen, self.page_size)
        own_fault = self._fault_pages(req) \
            if plen % self.page_size == 0 else self._fault_pages(req) - 1
        need = (npg - n_shared) + own_fault + self._fault_reserve()
        return self.allocator.free_pages >= need or self._reclaim_pages(need)

    def _chunk_step(self) -> int:
        """LEGACY path only (the fused pipeline plans chunk rows into its
        one dispatch instead — _plan_chunks): prefill ONE page-aligned
        chunk of the oldest chunking slot's prompt (FIFO over admission
        time), allocating only that chunk's pages. Non-final chunks leave the slot SUSPENDED until the next
        step — its pages are retained, its table row's tail stays
        unallocated so the batched decode masks it. The final chunk's
        logits seed decoding: the slot flips chunking -> decoding, its
        first token is emitted and it joins this same step's decode.
        Returns #tokens emitted (0 or 1)."""
        chunking = [s for s in self.active_slots
                    if self.slot_chunk_pos[s] >= 0]
        if not chunking:
            return 0
        slot = min(chunking, key=lambda s: self.slot_req[s].admit_seq)
        req = self.slot_req[slot]
        ps = self.page_size
        filled = int(self.slot_chunk_pos[slot])
        plen = len(req.prompt)
        end = min(filled + self.chunk_tokens, plen)
        start_pg, end_pg = span_pages(filled, end, ps)
        need = end_pg - start_pg                   # >= 1: end > filled
        t0 = time.monotonic()
        while True:
            ids = self.allocator.alloc(need)
            if ids is not None:
                break
            if self._reclaim_pages(need):
                continue
            # a chunking slot may steal pages only from slots YOUNGER than
            # itself (admit_seq order — tie-free where t_admit need not
            # be); with none to evict it SUSPENDS (pages retained) until
            # older requests finish. Preempting an older slot here would
            # break the oldest-always-finishes invariant and can livelock:
            # two part-prefilled requests ping-ponging each other's pages
            # forever (found by the serving-oracle fuzz harness).
            younger = [s for s in self.active_slots
                       if self.slot_req[s].admit_seq > req.admit_seq]
            if not younger:
                if self.obs is not None:
                    self.obs.on_suspend(req, time.monotonic())
                return 0
            self._preempt(max(younger,
                              key=lambda s: self.slot_req[s].admit_seq))
        self.page_tbl[slot, start_pg:end_pg] = ids
        self.slot_pages[slot].extend(ids)
        # the request's OWN earlier chunks are the "shared prefix"
        logits = self._run_partial_prefill(slot, req, filled, end)
        self.n_chunks += 1
        self.n_legacy_dispatches += 1      # the chunk's own prefill jit
        final = end >= plen
        if self.obs is not None:
            self.obs.on_chunk(req, t0, time.monotonic(), filled, end, final)
        if not final:
            self.slot_chunk_pos[slot] = end        # suspended till next step
            return 0
        # final chunk: chunking -> decoding
        self.slot_chunk_pos[slot] = -1
        self.slot_pos[slot] = plen
        # host-sync: readback -- final-chunk logits seed decoding: one
        # deliberate fetch when a prompt finishes chunking
        tok = self._sample(np.asarray(logits[0, -1], np.float32))
        self._emit(req, slot, tok, time.monotonic())
        return 1

    def step(self) -> int:
        """One engine iteration: admit into free slots, then one batched
        decode of everything in flight. Returns #tokens emitted (admission
        first-tokens included).

        With obs attached, the step is timed (host wall + the decode
        dispatch/readback split) and rolled up into one StepRecord +
        engine-track trace span; all of that is host-side bookkeeping —
        the device sees the exact same dispatch sequence either way. Its
        phases are annotated for the profiler: ``nbl.step`` around the
        whole step, with ``nbl.admission``, then on the fused path
        ``nbl.plan``, ``nbl.fused_step``, ``nbl.readback`` and
        ``nbl.commit`` inside it.

        A sharded engine steps under the mesh it was built in, whichever
        thread calls."""
        if self._mesh is not None:
            with jax.set_mesh(self._mesh):
                return self._step_observed()
        return self._step_observed()

    def _step_observed(self) -> int:
        if self.obs is None:
            return self._step_impl(None)
        t0 = time.monotonic()
        st = {"dispatch_s": 0.0, "n_decoding": 0, "n_chunking": 0,
              "chunk_tokens": 0, "prefill_tokens0": self.n_prefill_tokens,
              "tokens_planned": 0, "budget_utilization": 0.0}
        with self.obs.annotate("nbl.step"):
            emitted = self._step_impl(st)
        self.obs.on_step(
            self, t0=t0, t1=time.monotonic(), dispatch_s=st["dispatch_s"],
            n_decoding=st["n_decoding"], n_chunking=st["n_chunking"],
            tokens_emitted=emitted,
            prefill_tokens=self.n_prefill_tokens - st["prefill_tokens0"],
            chunk_tokens=st["chunk_tokens"],
            tokens_planned=st["tokens_planned"],
            budget_utilization=st["budget_utilization"])
        return emitted

    def _step_impl(self, st: Optional[dict]) -> int:
        """One step, as plan -> execute -> commit: admission planning is
        shared; the fused path then plans chunk rows under the token
        budget and launches ONE fused dispatch, while the legacy path
        keeps the historical two-dispatch sequence (at most one chunk
        prefill jit, then the batched decode jit) as the parity
        oracle."""
        emitted = self._plan_admission()
        if self.fused:
            return emitted + self._step_fused(st)
        return emitted + self._step_legacy(st)

    def _plan_admission(self) -> int:
        """PLAN, phase 1 — admission: pop queued requests into free slots
        (FIFO, page-gated). On the fused path the scheduler's pull is
        paced by what the step's token budget leaves after charging every
        decoding slot 1 token — decode priority extends to admission —
        while the queue HEAD is always admitted (Scheduler.admit), so an
        over-budget prompt cannot livelock."""
        with (self.obs.annotate("nbl.admission")
              if self.obs is not None else _NULLCTX):
            free = [i for i, r in enumerate(self.slot_req) if r is None]
            emitted = 0
            budget = None
            if self.fused and self.step_tokens is not None:
                n_dec = sum(1 for s in self.active_slots
                            if self.slot_chunk_pos[s] < 0)
                budget = decode_first_budget(self.step_tokens, n_dec)
            pending = self.scheduler.admit(len(free), budget=budget)
            while pending:
                req = pending.pop(0)
                if len(req.prompt) + req.max_new > self.max_len:
                    # length guard at ADMISSION: requests submitted
                    # directly to the scheduler bypass Engine.submit's
                    # check and would otherwise index past the page table
                    # mid-decode
                    self._reject(req, f"prompt({len(req.prompt)}) + max_new"
                                 f"({req.max_new}) exceeds max_len"
                                 f"={self.max_len}")
                    continue
                serr = self._spec_guard(len(req.prompt), req.max_new,
                                        req.spec_gamma, req.draft_m)
                if serr is not None:
                    # same guard submit() runs: direct scheduler submissions
                    # must not reach the spec path unservable
                    self._reject(req, serr)
                    continue
                n_shared, shared_ids = self._prefix_lookup(req)
                if not self._can_admit(req, n_shared):
                    if n_shared:
                        self.allocator.unref(shared_ids)   # drop the pin
                    for r in reversed([req] + pending):   # restore FIFO order
                        self.scheduler.requeue(r)
                    break
                self._admit(req, free.pop(), n_shared, shared_ids)
                if not self.chunked:
                    emitted += 1           # prefill emits a first token
            return emitted

    def _spec_rounds(self, active: list[int]) -> tuple[int, list[int]]:
        """Shared by both paths: one draft+verify round per live spec slot
        (they decode on their OWN jits, then sit out the step's batched /
        fused dispatch as masked rows). Returns (#tokens emitted, the
        slots still eligible for this step's dispatch) — a spec round can
        retire or preempt slots mid-list, so the survivors are
        re-filtered."""
        if not self.drafts:
            return 0, active
        emitted = 0
        spec = [s for s in active if self.slot_req[s].spec_gamma > 0]
        for slot in spec:
            emitted += self._spec_slot_step(slot)
        sset = set(spec)
        return emitted, [s for s in active
                         if s not in sset and self.slot_req[s] is not None]

    # ------------------------------------------------ fused step pipeline --

    def _plan_chunks(self, plan: StepPlan) -> dict[int, Request]:
        """PLAN, phase 2 (fused path) — chunk-row selection: grant
        page-aligned prompt spans to mid-chunking slots, OLDEST admission
        first, under what the token budget leaves after every decoding
        slot's 1-token charge (stepplan.decode_first_budget — decode rows
        are never displaced). Unlike the legacy one-chunk-per-step rule,
        several rows may be granted when the budget allows. Each granted
        row's pages are allocated here with the legacy discipline —
        reclaim LRU prefix entries, then preempt strictly-younger slots,
        else stop granting (the oldest suspended row must not be jumped
        by younger ones). Returns {slot: request} at grant time so commit
        can drop rows whose slot was preempted before execution."""
        row_req: dict[int, Request] = {}
        if not self.chunked:
            return row_req
        n_dec = sum(1 for s in self.active_slots
                    if self.slot_chunk_pos[s] < 0)
        remaining = decode_first_budget(self.step_tokens, n_dec)
        chunking = sorted(
            (s for s in self.active_slots if self.slot_chunk_pos[s] >= 0),
            key=lambda s: self.slot_req[s].admit_seq)
        ps = self.page_size
        for slot in chunking:
            req = self.slot_req[slot]
            if req is None or self.slot_chunk_pos[slot] < 0:
                continue   # preempted while an older row evicted youngers
            filled = int(self.slot_chunk_pos[slot])
            plen = len(req.prompt)
            end = chunk_span(filled, plen, self.chunk_tokens, remaining, ps)
            if end <= filled:
                break      # budget exhausted — younger rows wait too
            start_pg, end_pg = span_pages(filled, end, ps)
            need = end_pg - start_pg
            granted = True
            while True:
                ids = self.allocator.alloc(need)
                if ids is not None:
                    break
                if self._reclaim_pages(need):
                    continue
                younger = [s for s in self.active_slots
                           if self.slot_req[s].admit_seq > req.admit_seq]
                if not younger:
                    if self.obs is not None:
                        self.obs.on_suspend(req, time.monotonic())
                    granted = False
                    break
                self._preempt(max(younger,
                                  key=lambda s:
                                  self.slot_req[s].admit_seq))
            if not granted:
                break      # pool dry for the oldest row: stop granting
            self.page_tbl[slot, start_pg:end_pg] = ids
            self.slot_pages[slot].extend(ids)
            plan.chunk_rows.append(ChunkRow(slot, filled, end,
                                            final=end >= plen))
            row_req[slot] = req
            if remaining is not None:
                remaining -= end - filled
        return row_req

    def _step_fused(self, st: Optional[dict]) -> int:
        """Fused path: plan chunk rows, fault decode pages, run spec
        rounds, then EXECUTE one fused dispatch and COMMIT."""
        emitted = 0
        plan = StepPlan(budget=self.step_tokens)
        with (self.obs.annotate("nbl.plan")
              if self.obs is not None else _NULLCTX):
            row_req = self._plan_chunks(plan)
            self._ensure_decode_pages()          # fused implies paged
            active = self.active_slots
            if self.chunked:
                active = [s for s in active if self.slot_chunk_pos[s] < 0]
            se, active = self._spec_rounds(active)
        emitted += se
        # paging faults / spec rounds above may have preempted slots the
        # plan selected: keep decode rows from the survivors and chunk
        # rows whose slot still holds the request they were granted for
        # (an evicted row's pages were released with its slot).
        plan.decode_slots = active
        plan.chunk_rows = [c for c in plan.chunk_rows
                           if self.slot_req[c.slot] is row_req[c.slot]]
        if st is not None:
            # "still mid-chunking after this step's chunk progress": rows
            # whose final chunk rides this step flip to decoding at commit
            st["n_chunking"] = (
                int(np.sum(self.slot_chunk_pos >= 0))
                - sum(1 for c in plan.chunk_rows if c.final))
            st["n_decoding"] = len(plan.decode_slots)
        if not plan.has_work():
            return emitted
        self._budget_util_sum += plan.utilization
        self._n_planned_steps += 1
        if st is not None:
            st["tokens_planned"] = plan.tokens_planned
            st["budget_utilization"] = plan.utilization
        logits, td0 = self._execute_fused(plan)
        return emitted + self._commit_fused(plan, logits, td0, st)

    def _execute_fused(self, plan: StepPlan):
        """EXECUTE: build the (n_slots, W) mixed batch and launch the
        step's ONE device dispatch. Decode rows carry their last token at
        width 1; chunk rows carry their page-aligned prompt span;
        everything else (free slots, spec slots, suspended chunkers)
        rides with row_len 0 — the fused attention's explicit write mask
        drops their KV writes and a 0 length attends nothing, so the LIVE
        page table is shared with the dispatch as-is (no defensive
        copy)."""
        w = plan.width
        tokens = np.zeros((self.n_slots, w), np.int32)
        row_pos = np.zeros(self.n_slots, np.int32)
        row_len = np.zeros(self.n_slots, np.int32)
        for s in plan.decode_slots:
            tokens[s, 0] = self.slot_tok[s]
            row_pos[s] = self.slot_pos[s]
            row_len[s] = 1
        for c in plan.chunk_rows:
            tokens[c.slot, :c.length] = \
                self.slot_req[c.slot].prompt[c.start:c.end]
            row_pos[c.slot] = c.start
            row_len[c.slot] = c.length
        td0 = time.monotonic()
        with (self.obs.annotate("nbl.fused_step")
              if self.obs is not None else _NULLCTX):
            logits, self.cache = self._fused_fn(w)(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(row_pos), jnp.asarray(row_len),
                jnp.asarray(self.page_tbl))
        self.n_fused_dispatches += 1
        if plan.decode_slots:
            self.n_decode_steps += 1
            self._pool_in_use_sum += self.allocator.in_use
        return logits, td0

    def _commit_fused(self, plan: StepPlan, logits, td0: float,
                      st: Optional[dict]) -> int:
        """COMMIT: the step's single logits readback, then every host
        transition — chunk progress (+ progressive prefix publication),
        final-chunk seed emission, decode emission, retirement — all
        through the same _emit the legacy path uses."""
        with (self.obs.annotate("nbl.readback")
              if self.obs is not None else _NULLCTX):
            # host-sync: readback -- THE per-step readback: every row's
            # last-valid-token logits row comes host-side once; decode
            # sampling AND final-chunk seed tokens are both served from
            # this one fetch
            rows = np.asarray(logits[:, -1], np.float32)
        if st is not None:
            # dispatch + the logits device->host readback the sample needs
            st["dispatch_s"] = time.monotonic() - td0
        with (self.obs.annotate("nbl.commit")
              if self.obs is not None else _NULLCTX):
            emitted = 0
            now = time.monotonic()
            ps = self.page_size
            ctoks = 0
            for c in plan.chunk_rows:
                req = self.slot_req[c.slot]
                self.n_chunks += 1
                # same per-chunk accounting as the legacy
                # _run_partial_prefill path, so counters stay
                # path-independent per chunk
                self.n_prefills += 1
                self.n_prefill_tokens += c.length
                ctoks += c.length
                if self.obs is not None:
                    self.obs.on_prefill(c.length)
                    self.obs.on_chunk(req, td0, now, c.start, c.end, c.final)
                if self.prefix_sharing and c.end // ps:
                    # publish full pages PROGRESSIVELY (see
                    # _run_partial_prefill): later admissions can share a
                    # long prompt's head while its tail still chunks
                    self.prefix_index.insert(
                        req.prompt[:c.end],
                        self.page_tbl[c.slot, :c.end // ps], self.allocator)
                if c.final:
                    # chunking -> decoding: the row's last-token logits seed
                    # the request's first generated token
                    self.slot_chunk_pos[c.slot] = -1
                    self.slot_pos[c.slot] = len(req.prompt)
                    self._emit(req, c.slot, self._sample(rows[c.slot]), now)
                    emitted += 1
                else:
                    self.slot_chunk_pos[c.slot] = c.end
            if st is not None:
                st["chunk_tokens"] = ctoks
            if plan.decode_slots and np.any(self.slot_chunk_pos >= 0):
                self.n_interleaved_decode_steps += 1  # decode BETWEEN chunks
            for slot in plan.decode_slots:
                req = self.slot_req[slot]
                assert req is not None         # snapshot taken post-preempt
                self.slot_pos[slot] += 1
                self._emit(req, slot, self._sample(rows[slot]), now)
                emitted += 1
            return emitted

    # ----------------------------------------------------- legacy stepping --

    def _step_legacy(self, st: Optional[dict]) -> int:
        """Legacy two-dispatch path (``fused_step=False``, ring engines,
        SSM / cross-attn stacks): at most ONE prefill chunk on its own
        jit, then the batched decode jit — the fused pipeline's parity
        oracle, kept token-exact with the pre-fused engine."""
        emitted = 0
        if self.chunked:
            if st is not None:
                ct0 = self.n_prefill_tokens
            emitted += self._chunk_step()
            if st is not None:
                st["chunk_tokens"] = self.n_prefill_tokens - ct0
        if self.paged:
            self._ensure_decode_pages()
        active = self.active_slots
        if self.chunked:
            decoding = [s for s in active if self.slot_chunk_pos[s] < 0]
            if st is not None:
                st["n_chunking"] = len(active) - len(decoding)
            active = decoding
        se, active = self._spec_rounds(active)
        emitted += se
        if not active:
            return emitted
        if st is not None:
            st["n_decoding"] = len(active)
        token = jnp.asarray(self.slot_tok[:, None])
        live_spec = [s for s in self.active_slots
                     if self.slot_req[s].spec_gamma > 0] \
            if self.drafts else []
        if self.chunked and np.any(self.slot_chunk_pos >= 0) or live_spec:
            # chunking and spec slots ride the batched decode fully
            # masked: pos -1 gives them valid length 0 and the decode
            # scatter's EXPLICIT write mask (decode_paged_attention)
            # routes a dead row's KV write out of bounds — so the LIVE
            # page table is handed to the dispatch as-is. (Historically
            # pos -1 wrapped the write to the row's last table column and
            # spec rows needed a defensive per-step table copy; the mask
            # retired both.)
            posv = self.slot_pos.copy()
            if self.chunked:
                posv[self.slot_chunk_pos >= 0] = -1
            posv[live_spec] = -1
            pos = jnp.asarray(posv)
        else:
            pos = jnp.asarray(self.slot_pos)
        tbl = self.page_tbl if self.paged else None
        if st is not None:
            td0 = time.monotonic()
        with (self.obs.annotate("nbl.decode")
              if self.obs is not None else _NULLCTX):
            if self.paged:
                logits, self.cache = self._decode_jit(
                    self.params, token, self.cache, pos,
                    jnp.asarray(tbl))
                self._pool_in_use_sum += self.allocator.in_use
            else:
                logits, self.cache = self._decode_jit(self.params, token,
                                                      self.cache, pos)
        self.n_decode_steps += 1
        self.n_legacy_dispatches += 1
        if self.chunked and np.any(self.slot_chunk_pos >= 0):
            self.n_interleaved_decode_steps += 1   # decode BETWEEN chunks
        # host-sync: readback -- THE per-step readback: every slot's logits
        # row comes host-side once so sampling stays off-device
        rows = np.asarray(logits[:, -1], np.float32)
        if st is not None:
            # dispatch + the logits device->host readback the sample needs
            st["dispatch_s"] = time.monotonic() - td0
        now = time.monotonic()
        for slot in active:
            req = self.slot_req[slot]
            assert req is not None             # snapshot taken post-preempt
            self.slot_pos[slot] += 1
            self._emit(req, slot, self._sample(rows[slot]), now)
            emitted += 1
        return emitted

    def run(self, max_steps: Optional[int] = None) -> dict[int, np.ndarray]:
        """Drain the queue; returns {rid: generated tokens (np.int32)} of
        TERMINAL requests only — a ``max_steps``-bounded run may stop with
        work in flight, whose partial generations are exposed via
        ``partials()`` (they are not silently dropped, just not final)."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        with self._finished_lock:
            done = sorted(self.finished.items())
        return {rid: np.asarray(r.tokens, np.int32) for rid, r in done}

    def _drop_finished(self, rid: int) -> None:
        """Forget a terminal request's record (AsyncEngine's
        retain_results=False memory knob) without racing a concurrent
        ``stats()`` snapshot of the finished dict."""
        with self._finished_lock:
            self.finished.pop(rid, None)

    def stats(self) -> dict:
        """End-of-run / live summary: latency percentiles + engine
        counters. Thread-safe against the step loop (the finished-dict
        snapshot is taken under the same lock every terminal transition
        writes under). With ``stats_window`` set (the default), the
        percentiles cover the most recently finished ``stats_window``
        served requests — O(window) per call instead of O(lifetime), and
        immune to AsyncEngine's retain_results=False dropping records —
        while ``n`` stays the lifetime served count (``window_n`` reports
        the percentile subset size when it clipped)."""
        with self._finished_lock:
            if self._recent_done is not None:
                reqs = list(self._recent_done)
                n_finished = self.n_finished
            else:
                reqs = list(self.finished.values())
                n_finished = None
        with self._count_lock:
            # += on the client reject path is a non-atomic RMW; read the
            # counter under the same lock both writers take
            n_rejected = self.n_rejected
        s = latency_stats(reqs)
        if n_finished is not None:
            if s["n"] < n_finished:
                s["window_n"] = s["n"]
            s["n"] = n_finished
        s.update(n_slots=self.n_slots, n_decode_steps=self.n_decode_steps,
                 n_prefills=self.n_prefills,
                 n_prefill_tokens=self.n_prefill_tokens,
                 n_rejected=n_rejected, n_cancelled=self.n_cancelled,
                 # fused plan->execute->commit pipeline: the dispatch
                 # split and the average planned-tokens/budget pressure
                 # (0.0 when unbudgeted or fully legacy)
                 n_fused_dispatches=self.n_fused_dispatches,
                 n_legacy_dispatches=self.n_legacy_dispatches,
                 step_tokens=self.step_tokens,
                 step_budget_utilization=(self._budget_util_sum
                                          / max(1, self._n_planned_steps)))
        if self.paged:
            s.update(
                n_pages=self.n_pages,
                n_preemptions=self.n_preemptions,
                pages_in_use=self.allocator.in_use,
                peak_pages_in_use=self.allocator.peak_in_use,
                pool_utilization=(self._pool_in_use_sum
                                  / max(1, self.n_decode_steps)
                                  / max(1, self.n_pages)))
        if self.prefix_sharing:
            s.update(n_prefix_hits=self.n_prefix_hits,
                     n_shared_prompt_tokens=self.n_shared_prompt_tokens,
                     prefix_index_entries=self.prefix_index.n_entries)
        if self.chunked:
            s.update(n_chunks=self.n_chunks,
                     prefill_chunk_tokens=self.chunk_tokens,
                     n_interleaved_decode_steps=
                     self.n_interleaved_decode_steps)
        if self.drafts:
            s.update(
                n_spec_bursts=self.n_spec_bursts,
                n_spec_draft_tokens=self.n_spec_draft_tokens,
                n_spec_accepted_tokens=self.n_spec_accepted_tokens,
                n_spec_tokens=self.n_spec_tokens,
                # emitted tokens per verifier call — the speculative win
                spec_tokens_per_burst=(self.n_spec_tokens
                                       / max(1, self.n_spec_bursts)),
                spec_acceptance_rate=(self.n_spec_accepted_tokens
                                      / max(1, self.n_spec_draft_tokens)))
        return s


# --------------------------------------------------------------------------
# Async serving host loop
# --------------------------------------------------------------------------

_END = object()     # stream-queue sentinel: the request reached a terminal


class Stream:
    """One request's live token feed out of an :class:`AsyncEngine`.

    Iterating yields ints the moment the engine emits them and stops when
    the request reaches a terminal state (``status`` is then one of
    ``"finished"`` / ``"cancelled"`` / ``"rejected"`` / ``"aborted"``,
    with ``error`` carrying the reject/abort reason). ``result()`` blocks
    for the final token array instead. The feed is SINGLE-consumer: one
    iterator owns the queue (``tokens`` always holds everything delivered
    so far regardless).

    Preemption safety: when the engine preempts a request it discards and
    later REGENERATES its tokens from the prompt. The stream de-duplicates
    by token index, and greedy decoding regenerates an identical prefix,
    so a consumer never sees a token twice and the streamed sequence
    stays token-exact with ``generate()``. With ``temperature > 0`` the
    regenerated prefix may diverge from what was already streamed; at the
    terminal transition the stream ADOPTS the engine's final token list,
    so ``result()`` (and the server's "done" event) always return the
    sequence the model actually committed — only the live-iterated feed
    can contain stale pre-preemption samples.
    """

    def __init__(self, rid: int):
        self.rid = rid
        self.tokens: list[int] = []
        self.status: Optional[str] = None
        self.error: Optional[str] = None
        self._q: _queue.Queue = _queue.Queue()
        self._done = threading.Event()

    def _push(self, tok: int, index: int) -> None:
        if index < len(self.tokens):
            return          # preemption replay: this index already streamed
        self.tokens.append(int(tok))
        self._q.put(int(tok))

    def _end(self, status: str, error: Optional[str],
             final_tokens=None) -> None:
        if self._done.is_set():
            return          # first terminal transition wins
        if final_tokens is not None:
            # authoritative: under temperature > 0 a preemption replay may
            # have resampled, and the streamed prefix then disagrees with
            # what the engine committed — result() must not splice rollouts
            self.tokens = [int(t) for t in final_tokens]
        self.status, self.error = status, error
        self._done.set()
        self._q.put(_END)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _END:
                self._q.put(_END)   # stay terminal for any later iteration
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until terminal; returns the (possibly partial, if
        cancelled) generated tokens as np.int32."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight")
        return np.asarray(self.tokens, np.int32)


class AsyncEngine:
    """Async serving host loop: a background thread drives ``Engine.step()``
    while client threads stream, cancel, and get backpressure.

    The wrapped :class:`Engine` is NOT thread-safe, so every engine
    mutation that touches slots/pages happens on ONE background step
    thread; the client-facing surface is confined to operations that are
    safe from other threads:

      submit_stream()  validates + queues through ``Engine.submit``
                       (scheduler append is single-consumer-safe, rid
                       allocation is locked) and returns a :class:`Stream`
                       fed straight from the engine's ``on_token`` hook —
                       tokens arrive mid-step, not at step boundaries.
                       Every rejection (oversize, backpressure past
                       ``max_pending`` live requests) comes back as a
                       Stream already ended with ``status="rejected"`` —
                       never an exception that could kill a socket
                       handler's loop.
      cancel(rid)      enqueues the rid into an inbox the step loop drains
                       BETWEEN steps, where ``Engine.cancel`` retires it
                       from any lifecycle state with allocator invariants
                       intact (pages + shared-prefix pins unref'd).
      shutdown()       stops the loop — ``drain=True`` serves all pending
                       work first, ``drain=False`` (or a drain timeout)
                       cancels everything live so no pages leak — and
                       re-raises any exception the step loop died on.

    A step-loop exception does not vanish into the thread: it is captured,
    every live request is cancelled (pages unref'd), open streams end with
    ``status="aborted"``, and the exception re-raises at ``shutdown()``
    (or the next ``submit_stream``). ``step_cb(engine)``, if given, runs
    after every step on the step thread — the fuzz harness hangs allocator
    invariant checks there.
    """

    def __init__(self, engine: Engine, *, max_pending: int = 64,
                 step_cb: Optional[Callable] = None,
                 retain_results: bool = True):
        if engine.on_token is not None or engine.on_finish is not None:
            raise ValueError("engine already has emission hooks installed "
                             "(wrapped by another AsyncEngine?)")
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.engine = engine
        self.max_pending = int(max_pending)
        self.step_cb = step_cb
        # retain_results=False drops each terminal request from
        # engine.finished once its stream has the result — the memory
        # knob for a long-running server (stats percentiles then cover
        # only retained requests; the scalar counters keep counting)
        self.retain_results = bool(retain_results)
        # RLock on purpose: _on_finish re-enters under submit_stream's hold
        # when engine.submit rejects inline (see _expect_early)
        self._lock = threading.RLock()
        self._streams: dict[int, Stream] = {}    # guarded-by: _lock
        self._live: set[int] = set()             # guarded-by: _lock
        self._early_end: dict[int, tuple] = {}   # guarded-by: _lock
        # True only while submit_stream's own engine.submit call is on
        # this stack (under _lock): the ONLY legitimate window in which a
        # terminal _on_finish may precede stream registration. Gating the
        # _early_end stash on it keeps terminals of requests submitted
        # OUTSIDE submit_stream (engine.submit / direct Scheduler.submit
        # on a wrapped engine) from accumulating stashes forever.
        self._expect_early = False               # guarded-by: _lock
        self._cancels: deque = deque()
        self._wake = threading.Event()
        self._stop = False
        self._dead = False      # teardown's last act # guarded-by: _lock
        self._drain_on_stop = True
        self._exc: Optional[BaseException] = None
        engine.on_token = self._on_token
        engine.on_finish = self._on_finish
        # wake the idle loop on ANY servable submission — including a
        # DIRECT engine.submit() on the wrapped engine, which otherwise
        # sits queued until an unrelated wake (submit_stream sets _wake
        # itself, so this is belt-and-braces there)
        engine.on_submit = lambda req: self._wake.set()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="nbl-engine-step-loop")
        self._thread.start()

    # ------------------------------------------------------ client surface

    def submit_stream(self, prompt, max_new: int, *, enc=None,
                      spec_gamma: int = 0,
                      draft_m: Optional[int] = None) -> Stream:
        """Queue a request and return its live token :class:`Stream`.
        Thread-safe. ``spec_gamma``/``draft_m`` opt the request into
        speculative decoding (see :meth:`Engine.submit`). Unservable or
        over-capacity submissions return a
        stream already ended with ``status="rejected"`` (reject-with-error
        backpressure; ``stream.error`` says why)."""
        if self._stop:
            raise RuntimeError("AsyncEngine is shut down")
        if self._exc is not None:
            raise RuntimeError("engine step loop died") from self._exc
        with self._lock:
            self._expect_early = True
            try:
                if len(self._live) >= self.max_pending:
                    rid = self.engine._submit_rejected(
                        np.asarray(prompt, np.int32).reshape(-1), max_new,
                        f"engine at capacity "
                        f"(max_pending={self.max_pending} requests live)",
                        enc=enc)
                else:
                    rid = self.engine.submit(prompt, max_new, enc=enc,
                                             spec_gamma=spec_gamma,
                                             draft_m=draft_m)
            finally:
                self._expect_early = False
            s = Stream(rid)
            if rid in self._early_end:      # rejected inside submit()
                s._end(*self._early_end.pop(rid))
                # rejections never retain engine-side: sustained overload
                # is exactly what max_pending bounds, and pinning every
                # rejected prompt in engine.finished would unbound it
                self.engine._drop_finished(rid)
            elif self._dead:
                # lost the race with shutdown: the step thread already tore
                # down (its final act, under this lock, was _dead = True),
                # so nothing will ever serve or end this stream — end it
                # here rather than leave result()/iteration hanging forever
                s._end("aborted", "engine shut down before admission")
            else:
                # only LIVE streams are registered: a terminal stream is
                # never looked up again, and leaving it in _streams would
                # grow the wrapper by one entry per rejection — exactly
                # the overload path backpressure exists for
                self._streams[rid] = s
                self._live.add(rid)
        self._wake.set()
        return s

    def cancel(self, rid: int) -> None:
        """Request cancellation of ``rid``, whatever its state (queued /
        chunking mid-prompt / decoding). Applied by the step loop between
        steps so allocator invariants hold; a no-op if the request is
        already terminal. The stream ends with ``status="cancelled"`` and
        keeps its partial tokens."""
        self._cancels.append(rid)
        self._wake.set()

    def stats(self) -> dict:
        return self.engine.stats()

    def shutdown(self, *, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the step loop. ``drain=True`` finishes all queued and
        in-flight work first; ``drain=False`` — or a drain that outlives
        ``timeout`` — cancels everything still live (pages unref'd,
        streams ended) before stopping. Idempotent. Re-raises the step
        loop's exception if it died."""
        self._drain_on_stop = drain
        self._stop = True
        self._wake.set()
        self._thread.join(timeout)
        if self._thread.is_alive():         # drain overran: abort the rest
            self._drain_on_stop = False
            self._wake.set()
            self._thread.join()
        if self._exc is not None:
            raise RuntimeError("engine step loop died") from self._exc

    def __enter__(self) -> "AsyncEngine":
        return self

    def __exit__(self, etype, evalue, tb) -> None:
        # on a client-side error, abort rather than serve out the backlog
        self.shutdown(drain=etype is None)

    # ---------------------------------------------------------- step loop

    def _loop(self) -> None:
        eng = self.engine
        try:
            while True:
                while self._cancels:
                    eng.cancel(self._cancels.popleft())
                if self._stop and (not self._drain_on_stop
                                   or not eng.has_work):
                    break
                if eng.has_work:
                    eng.step()
                    if self.step_cb is not None:
                        self.step_cb(eng)
                else:
                    # purely event-driven idle: every producer mutates its
                    # state (scheduler append / cancel inbox / stop flags)
                    # BEFORE setting the wake event, and the loop re-derives
                    # everything from that state after clear() — so a set
                    # raced away by clear() is never a lost wakeup, and an
                    # idle server burns zero CPU instead of polling
                    self._wake.wait()
                    self._wake.clear()
        except BaseException as e:          # surfaced at shutdown/submit
            self._exc = e
        finally:
            self._teardown()

    def _teardown(self) -> None:
        """Last act of the step thread: cancel whatever is still live (so
        pages/pins are released even on abort or a step crash), close any
        stream that survived that, and uninstall the engine hooks."""
        with self._lock:
            live = list(self._live)
        for rid in live:
            try:
                self.engine.cancel(rid)     # ends its stream "cancelled"
            except BaseException:
                pass                        # engine already broken: below
        msg = (f"engine step loop died: {self._exc!r}"
               if self._exc is not None else "shutdown before completion")
        with self._lock:
            leftovers = [self._streams[r] for r in self._live]
            self._live.clear()
            self._dead = True   # submit_stream self-ends from here on
        for s in leftovers:
            s._end("aborted", msg)
        self.engine.on_token = None
        self.engine.on_finish = None
        self.engine.on_submit = None

    # ------------------------------------------------------- engine hooks

    def _on_token(self, req: Request, tok: int) -> None:
        with self._lock:
            s = self._streams.get(req.rid)
        if s is not None:
            s._push(tok, len(req.tokens) - 1)

    def _on_finish(self, req: Request) -> None:
        status = ("cancelled" if req.cancelled
                  else "rejected" if req.error is not None else "finished")
        with self._lock:
            self._live.discard(req.rid)
            # a terminal stream is never looked up again (no further
            # tokens, teardown walks _live only) — drop it here or a
            # long-running server grows O(total requests)
            s = self._streams.pop(req.rid, None)
            if s is None:
                if self._expect_early:
                    # terminal before the stream registered (rejection
                    # inside submit_stream's own engine.submit call, which
                    # holds _lock around us): hand the end state back
                    self._early_end[req.rid] = (status, req.error)
                # else: a request submitted outside submit_stream (direct
                # engine/scheduler use on a wrapped engine) — no stream
                # will ever claim it; its record lives in engine.finished
                return
        s._end(status, req.error, final_tokens=req.tokens)
        if not self.retain_results or req.error is not None:
            # the stream carries the result to its consumer; the engine's
            # finished dict (and with it latency_stats history) would
            # otherwise also grow without bound under continuous traffic.
            # Rejections are dropped UNCONDITIONALLY — overload must not
            # grow memory per rejected request (see submit_stream)
            self.engine._drop_finished(req.rid)
