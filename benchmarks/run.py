"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

  table_compression   Tables 2/3/4 analog: NBL vs DROP vs SLEB at equal m
                      (perplexity + successor-probe accuracy on a trained
                      small model, offline stand-in for the HF suites)
  table_calibration   Tables 1/7: Algorithm-2 calibration runtime vs d
  fig3_prefill        Figure 3: analytic prefill speed-up vs context length
  table21_kv_cache    Table 21: KV-cache bytes vs context × NBL-m
  criterion_ablation  Appendix F.3: CCA-bound vs cosine selection
  serving_throughput  throughput under load: continuous-batching engine at a
                      FIXED cache byte budget — requests/s and p50/p99
                      latency vs number of NBL-linearized layers (the freed
                      KV budget converts into concurrent slots)
  paged_throughput    paged vs ring KV management at EQUAL HBM budget on a
                      short-prompt-heavy mix: the paged engine bills pages
                      actually used instead of max_len rings, so it admits
                      more concurrent requests — requests/s, decode sweeps
                      (deterministic), pool utilization, p99 TTFT vs NBL-m
  prefix_throughput   prefix sharing (copy-on-write paged KV) vs plain
                      paged at EQUAL HBM budget on a shared-system-prompt
                      workload: suffix-only prefill (n_prefill_tokens and
                      p50 TTFT strictly lower), shared pages billed once
                      (admitted concurrency up, monotone in NBL-m), exact
                      token parity vs generate()
  chunked_throughput  chunked prefill (page-aligned prefill-decode
                      interleaving) vs non-chunked paged at EQUAL HBM
                      budget while a long prompt is admitted next to
                      active decodes: p99 inter-token latency of the
                      in-flight decodes strictly below non-chunked, long-
                      prompt TTFT within 1.2x, exact token parity, decodes
                      provably emitting BETWEEN chunks
  fused_throughput    fused one-dispatch step pipeline vs the legacy
                      two-dispatch path (docs/architecture.md) at EQUAL
                      HBM budget on the chunked-admission workload: p99
                      inter-token latency during long-prompt admission
                      strictly below legacy, one fused launch per step
                      (dispatch counters), exact token parity
  async_throughput    AsyncEngine host loop under concurrent streamed
                      submission at a FIXED HBM budget: streamed tokens/s
                      and p50/p99 queue delay (submit->admission) vs
                      NBL-m, token-exact parity of the streamed tokens vs
                      generate(), zero leaked pages after shutdown
  speculative_throughput  engine-native self-speculative decoding (Table 6
                      analog): calibrated NBL drafter sharing the target's
                      page table vs non-spec paged decode at EQUAL HBM
                      budget on single streams — tokens/s, tokens/burst,
                      acceptance vs (draft-m, γ); token-exact greedy
                      parity + zero leaked pages every pass
  kernels             µs/call of the three Pallas kernels (interpret mode —
                      CPU-emulated, structural check only)

Prints ``name,value,derived`` CSV rows; writes benchmarks/out.json plus a
stable per-scenario artifact benchmarks/out/<scenario>.json (one sorted
rows list per scenario — the trajectory-tracking unit across PRs).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.jitcache import enable_compile_cache
from repro.obs import clock

ROWS: list[tuple[str, object, str]] = []

# Every TIMED scenario runs >= this many measured passes after its warmup
# and minimizes EACH metric independently across them (latencies/runtimes:
# min; rates: computed from the min elapsed time). A single descheduling
# blip on a loaded CI box inflates a summed latency one-sidedly — best-of-3
# was observed flaking where best-of-4 with per-metric minima holds — and a
# lexicographic best-of-tuple can keep a bad TTFT because another pass had
# a lower p99. Structural metrics (slot counts, decode sweeps, prefill
# tokens) are deterministic per pass and taken from the first one.
TIMED_REPEATS = 4


def emit(name: str, value, derived: str = "") -> None:
    ROWS.append((name, value, derived))
    print(f"{name},{value},{derived}", flush=True)


# ---------------------------------------------------------------------------
def bench_compression(fast: bool) -> None:
    """Train a small LM, compress with each method, compare quality."""
    from repro.configs import get_config
    from repro.core import drop_compress, nbl_compress, sleb_compress
    from repro.data import ZipfMarkov, calib_factory
    from repro.eval import eval_suite
    from repro.launch.train import train

    cfg = get_config("tiny-dense")
    steps = 120 if fast else 300
    out = train(cfg, steps=steps, global_batch=16, seq=64, peak_lr=3e-3,
                log_every=max(steps // 3, 1), log_fn=lambda s: None)
    params = out["params"]
    proc = ZipfMarkov(cfg.vocab_size, seed=0)
    fac = calib_factory(cfg, batch=4, seq=64, n_batches=4 if fast else 8)
    evalfac = calib_factory(cfg, batch=4, seq=64, n_batches=4, seed=999)

    base = eval_suite(cfg, params, evalfac, proc.succ)
    emit("compression/baseline/ppl", round(base["ppl"], 3))
    emit("compression/baseline/succ_acc", round(base["succ_acc"], 4))

    ms = [1, 2] if fast else [1, 2, 3]
    for m in ms:
        ncfg, nparams, _ = nbl_compress(cfg, params, fac, m)
        e = eval_suite(ncfg, nparams, evalfac, proc.succ)
        emit(f"compression/attn_nbl-{m}/ppl", round(e["ppl"], 3))
        emit(f"compression/attn_nbl-{m}/succ_acc", round(e["succ_acc"], 4))

        dcfg, dparams, _ = drop_compress(cfg, params, fac, m)
        e = eval_suite(dcfg, dparams, evalfac, proc.succ)
        emit(f"compression/attn_drop-{m}/ppl", round(e["ppl"], 3))
        emit(f"compression/attn_drop-{m}/succ_acc", round(e["succ_acc"], 4))

        bcfg, bparams, _ = nbl_compress(cfg, params, fac, m, block=True)
        e = eval_suite(bcfg, bparams, evalfac, proc.succ)
        emit(f"compression/block_nbl-{m}/ppl", round(e["ppl"], 3))

    if not fast:
        scfg, sparams, _ = sleb_compress(cfg, params, fac, 2)
        e = eval_suite(scfg, sparams, evalfac, proc.succ)
        emit("compression/sleb-2/ppl", round(e["ppl"], 3))


# ---------------------------------------------------------------------------
def bench_calibration_runtime(fast: bool) -> None:
    """Algorithm-2 cost (moments→eigh→SVD→solve) vs embedding dim; the paper
    reports 26 s/layer @ d=4096 on A100 (Tables 1/7). O(d³+s·t·d²) scaling
    is asserted by the cubic fit in tests."""
    from repro.core.cca import cca_bound_from_moments
    from repro.core.lmmse import lmmse_from_moments
    from repro.core.moments import finalize, init_moments, update_moments

    dims = (256, 512) if fast else (256, 512, 1024)
    tokens = 4096
    for d in dims:
        rng = np.random.default_rng(0)
        x = rng.standard_normal((tokens, d)).astype(np.float32)
        y = (x @ (rng.standard_normal((d, d)).astype(np.float32) * 0.1))
        ts = []
        for _ in range(TIMED_REPEATS):       # min-over-repeats (see top)
            t0 = clock()
            mom = init_moments(d, d)
            for i in range(0, tokens, 1024):
                mom = update_moments(mom, x[i:i + 1024], y[i:i + 1024])
            jax.block_until_ready(mom["sxx"])
            fin = finalize(mom)
            cca_bound_from_moments(fin)
            lmmse_from_moments(fin)
            ts.append(clock() - t0)
        emit(f"calibration/layer_runtime_d{d}", round(min(ts) * 1e6, 1),
             "us_per_layer")


# ---------------------------------------------------------------------------
def bench_fig3_prefill(fast: bool) -> None:
    """Analytic prefill speed-up (K−m)·n²d + m·nd vs K·n²d (paper §4.2);
    reproduces the Fig. 3 shape: gains grow with context length."""
    K, d = 32, 4096
    for n in (2048, 8192, 32_768, 131_072):
        base = K * n * n * d
        for m in (4, 8, 12, 16):
            sped = (K - m) * n * n * d + m * n * d
            emit(f"prefill_speedup/n{n}/nbl-{m}", round(base / sped, 4),
                 "analytic")


# ---------------------------------------------------------------------------
def bench_kv_cache(fast: bool) -> None:
    """Paper Table 21: KV-cache GB for Llama-3.1-8B-class GQA at batch 64,
    half precision, vs context × NBL-m — the structural cache_bytes() is
    asserted equal to the analytic 2·bs·n·d·(g/h)·((K−m)/K) formula."""
    from repro.configs import get_config
    from repro.core.surgery import compress_config
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("llama-3.1-8b").replace(compute_dtype="bfloat16")
    K = cfg.n_blocks
    for n in ((512, 4096) if fast else (512, 1024, 2048, 4096)):
        for m in (0, 4, 8, 12, 16):
            c = compress_config(cfg, cfg.attn_layer_indices()[-m:], "nbl") \
                if m else cfg
            got = cache_bytes(c, 64, n) - 4 * (K - m) * n   # minus kpos i32
            want = 2 * 64 * n * cfg.n_kv_heads * cfg.head_dim * 2 * (K - m)
            assert got == want, (got, want)
            emit(f"kv_cache/n{n}/nbl-{m}_GB", round(got / 2**30, 3),
                 "structural==analytic")


# ---------------------------------------------------------------------------
def bench_criterion_ablation(fast: bool) -> None:
    """Appendix F.3: CCA-bound vs cosine-distance selection overlap."""
    from repro.configs import get_config
    from repro.core import calibrate, rank_layers
    from repro.data import calib_factory
    from repro.models import init_params

    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    fac = calib_factory(cfg, batch=4, seq=64, n_batches=4)
    calib = calibrate(cfg, params, fac)
    cca = rank_layers(calib, "cca")
    cos = rank_layers(calib, "cosine")
    k = 3
    overlap = len(set(cca[:k]) & set(cos[:k])) / k
    emit("criterion/cca_vs_cosine_top3_overlap", round(overlap, 3))
    emit("criterion/cca_ranking", "|".join(map(str, cca)))


# ---------------------------------------------------------------------------
def bench_serving(fast: bool) -> None:
    """Throughput under load (ROADMAP north-star scenario): the continuous-
    batching engine serves a ragged request stream at a FIXED cache byte
    budget while m attention layers are NBL-linearized. Linearized layers
    carry no KV cache, so the same budget admits ~K/(K−m)× more slots
    (launch/scheduler.nbl_slot_budget) and requests/s rises with m.
    Reported per m: slots, requests/s, tokens/s, p50/p99 latency, and the
    (deterministic) number of batched decode sweeps."""
    from repro.configs import get_config
    from repro.core.surgery import nbl_variant
    from repro.launch.engine import Engine
    from repro.launch.scheduler import latency_stats
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("tiny-dense")
    max_len = 64
    budget = 2 * cache_bytes(cfg, 1, max_len)      # 2 slots uncompressed
    n_req = 8 if fast else 16
    max_new = 8
    rng = np.random.default_rng(0)
    lens = rng.integers(8, 25, n_req)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    for m in (0, 1, 2, 3):
        c = nbl_variant(cfg, m)
        params = init_params(jax.random.PRNGKey(0), c)
        eng = Engine(c, params, max_len=max_len, cache_budget_bytes=budget)
        # warmup pass: compiles every prompt-length prefill + the decode jit
        for p in prompts:
            eng.submit(p, max_new)
        eng.run()
        # timed passes on warm jits: per-metric min over TIMED_REPEATS
        dts, p50s, p99s, toks, sweeps = [], [], [], [], []
        for _ in range(TIMED_REPEATS):
            steps0 = eng.n_decode_steps
            t0 = clock()
            rids = [eng.submit(p, max_new) for p in prompts]
            eng.run()
            dts.append(clock() - t0)
            timed = [eng.finished[r] for r in rids]
            s = latency_stats(timed)
            p50s.append(s["p50_latency_s"])
            p99s.append(s["p99_latency_s"])
            toks.append(sum(len(r.tokens) for r in timed))
            sweeps.append(eng.n_decode_steps - steps0)
        emit(f"serving/nbl-{m}/n_slots", eng.n_slots, "fixed_budget")
        emit(f"serving/nbl-{m}/requests_per_s", round(n_req / min(dts), 2))
        emit(f"serving/nbl-{m}/tokens_per_s", round(toks[0] / min(dts), 1))
        emit(f"serving/nbl-{m}/p50_latency_ms", round(min(p50s) * 1e3, 1))
        emit(f"serving/nbl-{m}/p99_latency_ms", round(min(p99s) * 1e3, 1))
        assert len(set(sweeps)) == 1, sweeps     # same work every pass
        emit(f"serving/nbl-{m}/decode_sweeps", sweeps[0], "deterministic")


# ---------------------------------------------------------------------------
def bench_paged(fast: bool) -> None:
    """Paged vs ring engine at EQUAL HBM budget (tentpole scenario): a
    short-prompt-heavy mix where per-slot max_len rings strand most of their
    reservation. The paged engine converts the stranded bytes into admitted
    requests (requests/s up, decode sweeps down — the sweeps count is
    deterministic) and composes with NBL: linearized layers carry no page
    pool, so concurrency is monotone in m in BOTH engines but the paged one
    starts from page-granular accounting."""
    from repro.configs import get_config
    from repro.core.surgery import nbl_variant
    from repro.launch.engine import Engine
    from repro.launch.scheduler import latency_stats
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("tiny-dense")
    max_len = 64
    page_size = 8
    budget = 2 * cache_bytes(cfg, 1, max_len)      # 2 full rings
    n_req = 8 if fast else 16
    max_new = 6
    rng = np.random.default_rng(0)
    lens = rng.integers(4, 13, n_req)              # short prompts: ~18 toks
    expected = int(np.percentile(lens, 90)) + max_new
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    for m in (0, 1, 2, 3):
        c = nbl_variant(cfg, m)
        params = init_params(jax.random.PRNGKey(0), c)
        row = {}
        for mode in ("ring", "paged"):
            kw = dict(paged=True, page_size=page_size,
                      expected_len=expected) if mode == "paged" else {}
            eng = Engine(c, params, max_len=max_len,
                         cache_budget_bytes=budget, **kw)
            for p in prompts:                      # warmup: compile jits
                eng.submit(p, max_new)
            eng.run()
            # per-metric min over TIMED_REPEATS passes on warm jits
            dts, p99s, sweeps = [], [], []
            for _ in range(TIMED_REPEATS):
                steps0 = eng.n_decode_steps
                t0 = clock()
                rids = [eng.submit(p, max_new) for p in prompts]
                eng.run()
                dts.append(clock() - t0)
                s = latency_stats([eng.finished[r] for r in rids])
                p99s.append(s["p99_ttft_s"])
                sweeps.append(eng.n_decode_steps - steps0)
            assert len(set(sweeps)) == 1, sweeps   # same work every pass
            row[mode] = (eng, sweeps[0])
            emit(f"paged/nbl-{m}/{mode}/concurrency", eng.n_slots,
                 "equal_budget")
            emit(f"paged/nbl-{m}/{mode}/requests_per_s",
                 round(n_req / min(dts), 2))
            emit(f"paged/nbl-{m}/{mode}/decode_sweeps",
                 sweeps[0], "deterministic")
            emit(f"paged/nbl-{m}/{mode}/p99_ttft_ms",
                 round(min(p99s) * 1e3, 1))
        eng_p = row["paged"][0]
        emit(f"paged/nbl-{m}/pool_utilization",
             round(eng_p.stats()["pool_utilization"], 3))
        emit(f"paged/nbl-{m}/preemptions", eng_p.n_preemptions)
        # structural claim, timing-free: page-granular admission never does
        # WORSE than ring admission on the same budget
        assert row["paged"][0].n_slots >= row["ring"][0].n_slots, \
            (m, row["paged"][0].n_slots, row["ring"][0].n_slots)
        assert row["paged"][1] <= row["ring"][1], "paged needs more sweeps"


# ---------------------------------------------------------------------------
def bench_prefix(fast: bool) -> None:
    """Prefix sharing (copy-on-write paged KV) vs plain paged at EQUAL HBM
    budget on the dominant serving pattern: every request carries the same
    system prompt plus a short unique tail. The sharing engine prefills
    each prompt's suffix only (shared pages are referenced, not recomputed
    — n_prefill_tokens drops), admits more concurrent requests (shared
    pages billed once: scheduler.nbl_page_budget) and cuts p50 TTFT, while
    emitting tokens EXACTLY equal to generate(). Composes with NBL:
    linearized layers carry no pool, so admitted concurrency stays monotone
    in m with sharing on."""
    from repro.configs import get_config
    from repro.core.surgery import nbl_variant
    from repro.launch.engine import Engine
    from repro.launch.scheduler import latency_stats
    from repro.launch.serve import generate
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("tiny-dense")
    max_len = 64
    page_size = 8
    budget = 2 * cache_bytes(cfg, 1, max_len)      # 2 full rings
    n_req = 12 if fast else 24
    max_new = 6
    rng = np.random.default_rng(0)
    sys_len = 32                                   # 4 shared pages
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len)
    tails = rng.integers(2, 9, n_req)
    prompts = [np.concatenate([sys_prompt,
                               rng.integers(0, cfg.vocab_size, t)])
               .astype(np.int32) for t in tails]
    expected = sys_len + int(np.percentile(tails, 90)) + max_new

    shared_slots = []
    # per-request TTFTs pooled across m, kept SEPARATE per timed repeat so
    # the final claim can take the min of the pooled p50s (per-metric
    # minima over >= 4 repeats — a single-shot pooled comparison still
    # flakes when one whole pass lands on a descheduling blip)
    ttfts = {"paged": [[] for _ in range(TIMED_REPEATS)],
             "shared": [[] for _ in range(TIMED_REPEATS)]}
    for m in (0, 1, 2, 3):
        c = nbl_variant(cfg, m)
        params = init_params(jax.random.PRNGKey(0), c)
        refs = [np.asarray(generate(c, params, jnp.asarray(p)[None],
                                    max_new=max_new))[0] for p in prompts]
        row = {}
        for mode in ("paged", "shared"):
            kw = dict(paged=True, page_size=page_size, expected_len=expected)
            if mode == "shared":
                kw.update(prefix_sharing=True, shared_prefix_len=sys_len)
            eng = Engine(c, params, max_len=max_len,
                         cache_budget_bytes=budget, **kw)
            for p in prompts:                      # warmup: compile jits and
                eng.submit(p, max_new)             # (shared) seed the index
            eng.run()
            hit0, shr0 = eng.n_prefix_hits, eng.n_shared_prompt_tokens
            dts, p50s, ptoks_reps = [], [], []
            for rep in range(TIMED_REPEATS):
                tok0 = eng.n_prefill_tokens
                t0 = clock()
                rids = [eng.submit(p, max_new) for p in prompts]
                out = eng.run()
                dts.append(clock() - t0)
                for rid, want in zip(rids, refs):  # exact parity, both modes
                    np.testing.assert_array_equal(out[rid], want)
                s = latency_stats([eng.finished[r] for r in rids])
                p50s.append(s["p50_ttft_s"])
                ttfts[mode][rep] += [eng.finished[r].ttft for r in rids]
                ptoks_reps.append(eng.n_prefill_tokens - tok0)
            assert len(set(ptoks_reps)) == 1, ptoks_reps  # deterministic
            ptoks = ptoks_reps[0]
            row[mode] = (eng, ptoks)
            emit(f"prefix/nbl-{m}/{mode}/concurrency", eng.n_slots,
                 "equal_budget")
            emit(f"prefix/nbl-{m}/{mode}/n_prefill_tokens", ptoks,
                 "deterministic")
            emit(f"prefix/nbl-{m}/{mode}/requests_per_s",
                 round(n_req / min(dts), 2))
            emit(f"prefix/nbl-{m}/{mode}/p50_ttft_ms",
                 round(min(p50s) * 1e3, 2))
        eng_s = row["shared"][0]
        emit(f"prefix/nbl-{m}/prefix_hits",
             (eng_s.n_prefix_hits - hit0) // TIMED_REPEATS, "per_pass")
        emit(f"prefix/nbl-{m}/shared_prompt_tokens",
             (eng_s.n_shared_prompt_tokens - shr0) // TIMED_REPEATS,
             "per_pass")
        shared_slots.append(eng_s.n_slots)
        # structural claims, exact-token-parity already asserted above:
        # sharing prefills strictly fewer tokens and never admits less
        assert row["shared"][1] < row["paged"][1], \
            (m, row["shared"][1], row["paged"][1])
        assert row["shared"][0].n_slots >= row["paged"][0].n_slots
    assert shared_slots == sorted(shared_slots), shared_slots
    # timing claim, gated on the per-request TTFTs POOLED across every m
    # (a per-m p50 comparison is load-sensitive on a shared CI box; the
    # pooled median is dominated by queueing structure, not noise) with the
    # pooled p50 minimized over the timed repeats per mode
    p50_s = min(float(np.percentile(t, 50)) for t in ttfts["shared"])
    p50_p = min(float(np.percentile(t, 50)) for t in ttfts["paged"])
    assert p50_s < p50_p, (p50_s, p50_p)
    emit("prefix/pooled_p50_ttft_ms/shared", round(p50_s * 1e3, 2))
    emit("prefix/pooled_p50_ttft_ms/paged", round(p50_p * 1e3, 2))
    emit("prefix/shared_concurrency_monotone_in_m", 1, "assert")


# ---------------------------------------------------------------------------
def bench_chunked(fast: bool) -> None:
    """Chunked prefill vs non-chunked paged at EQUAL HBM budget (the
    prefill-decode interleaving claim): two short requests are mid-decode
    when a long prompt arrives. Non-chunked, the admission step runs the
    whole prompt's prefill serially — every active decode stalls for it,
    and that stall IS the decodes' inter-token latency spike. Chunked, at
    most one page-aligned chunk runs per step, so decodes keep emitting
    between chunks: p99 inter-token latency during the admission window
    must be STRICTLY below non-chunked, the long prompt's TTFT within
    1.2x, and every request's tokens exactly equal generate()'s."""
    from repro.configs import get_config
    from repro.launch.engine import Engine
    from repro.launch.serve import generate
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("tiny-dense")
    max_len, page_size = 1024, 64
    # the long prompt must be big enough that prefill COMPUTE (not
    # per-step dispatch overhead) dominates, or the TTFT comparison
    # measures the host loop: at 768 tokens the 3 chunks skip the full
    # prefill's masked upper triangle and chunked TTFT lands ~0.5-0.6x
    # non-chunked; --fast only trims the timed repetitions
    long_len, chunk = 768, 256
    short_len, short_new, long_new = 16, 40, 8
    budget = 3 * cache_bytes(cfg, 1, max_len)      # 3 full reservations
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, cfg.vocab_size, short_len).astype(np.int32)
              for _ in range(2)]
    longp = rng.integers(0, cfg.vocab_size, long_len).astype(np.int32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                max_new=n))[0]
            for p, n in [(shorts[0], short_new), (shorts[1], short_new),
                         (longp, long_new)]]

    def run_once(chunked: bool):
        kw = dict(paged=True, page_size=page_size, expected_len=max_len)
        if chunked:
            kw.update(chunked_prefill=True, prefill_chunk_tokens=chunk)
        eng = Engine(cfg, params, max_len=max_len,
                     cache_budget_bytes=budget, **kw)
        sids = [eng.submit(p, short_new) for p in shorts]
        for _ in range(3):                         # shorts mid-decode
            eng.step()
        lid = eng.submit(longp, long_new)
        gaps = []
        long_first = None
        while eng.has_work:
            t0 = clock()
            eng.step()
            dt = clock() - t0
            req = eng.finished.get(lid) or next(
                (r for r in eng.slot_req
                 if r is not None and r.rid == lid), None)
            if long_first is None:
                # the admission window: every step until the long prompt's
                # first token is a decode gap the short requests ate
                gaps.append(dt)
                if req is not None and req.t_first:
                    long_first = req.t_first - req.t_submit
        outs = {rid: np.asarray(eng.finished[rid].tokens, np.int32)
                for rid in sids + [lid]}
        for got, want in zip([outs[sids[0]], outs[sids[1]], outs[lid]],
                             refs):                # exact parity, each mode
            np.testing.assert_array_equal(got, want)
        interleaved = eng.n_interleaved_decode_steps
        return eng, gaps, long_first, interleaved

    rows = {}
    for mode, chunked in (("paged", False), ("chunked", True)):
        run_once(chunked)                          # warmup: compile jits
        # TIMED_REPEATS passes, with p99-ITL and TTFT minimized
        # INDEPENDENTLY: both are sums/maxima over steps, so a single
        # descheduling blip on a loaded CI box inflates them one-sidedly
        # — per-claim minima estimate the latency structure under test,
        # not the box's background load
        p99s, ttfts, inters = [], [], []
        for _ in range(TIMED_REPEATS):
            eng, gaps, ttft, interleaved = run_once(chunked)
            p99s.append(float(np.percentile(gaps, 99)))
            ttfts.append(ttft)
            inters.append(interleaved)
        p99, ttft, interleaved = min(p99s), min(ttfts), max(inters)
        rows[mode] = (p99, ttft, interleaved)
        emit(f"chunked/{mode}/concurrency", eng.n_slots, "equal_budget")
        emit(f"chunked/{mode}/p99_itl_ms", round(p99 * 1e3, 2),
             "long_admission_window")
        emit(f"chunked/{mode}/long_ttft_ms", round(ttft * 1e3, 2))
        if chunked:
            emit("chunked/n_chunks", eng.n_chunks, "deterministic")
            emit("chunked/interleaved_steps", interleaved, "deterministic")
    # structural + latency claims (parity already asserted per mode):
    # chunking strictly caps the decode stall, within 1.2x TTFT, and the
    # decodes demonstrably emitted between chunks
    assert rows["chunked"][0] < rows["paged"][0], rows
    assert rows["chunked"][1] <= 1.2 * rows["paged"][1], rows
    assert rows["chunked"][2] >= 1, rows
    emit("chunked/p99_itl_ratio",
         round(rows["paged"][0] / rows["chunked"][0], 2), "assert_gt_1")


# ---------------------------------------------------------------------------
def bench_fused(fast: bool) -> None:
    """Fused one-dispatch step vs the legacy two-dispatch path at EQUAL
    HBM budget (docs/architecture.md): both engines run chunked prefill
    over the same workload — two short requests mid-decode when a long
    prompt arrives — each paced by its own ONLY knob. Legacy runs
    prefill_chunk_tokens-sized chunks, launching the chunk's prefill jit
    AND the batched decode jit each interleaved step; the fused engine
    runs under a decode-priority step_tokens budget (decoders charged
    first, the remainder funding one page of chunk progress), folding
    chunk + decodes into ONE mixed dispatch whose width the budget keeps
    at a single page. Bounded per-step work + the dropped second launch
    and readback is exactly what the budget buys: p99 inter-token latency
    during the long prompt's admission window must be STRICTLY below the
    two-dispatch path, with exact generate() parity, and the dispatch
    counters prove the one-dispatch contract per step. The price is the
    long prompt's TTFT (more, smaller chunks) — reported, not asserted:
    the budget is the latency/TTFT dial."""
    from repro.configs import get_config
    from repro.launch.engine import Engine
    from repro.launch.serve import generate
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes

    cfg = get_config("tiny-dense")
    max_len, page_size = 1024, 32
    long_len, chunk = 768, 256
    short_len, short_new, long_new = 16, 40, 8
    budget = 3 * cache_bytes(cfg, 1, max_len)      # 3 full reservations
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, cfg.vocab_size, short_len).astype(np.int32)
              for _ in range(2)]
    longp = rng.integers(0, cfg.vocab_size, long_len).astype(np.int32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                max_new=n))[0]
            for p, n in [(shorts[0], short_new), (shorts[1], short_new),
                         (longp, long_new)]]

    # the fused engine's pacing knob: every decoder funded first, the
    # remainder grants the chunking row exactly one page per step
    step_tokens = page_size + len(shorts)

    def run_once(fused: bool):
        eng = Engine(cfg, params, max_len=max_len, paged=True,
                     page_size=page_size, expected_len=max_len,
                     chunked_prefill=True, prefill_chunk_tokens=chunk,
                     cache_budget_bytes=budget, fused_step=fused,
                     step_tokens=step_tokens if fused else None)
        assert eng.fused == fused
        sids = [eng.submit(p, short_new) for p in shorts]
        for _ in range(3):                         # shorts mid-decode
            eng.step()
        lid = eng.submit(longp, long_new)
        gaps = []
        long_first = None
        while eng.has_work:
            d0 = eng.n_fused_dispatches
            t0 = clock()
            eng.step()
            dt = clock() - t0
            # the one-dispatch contract, step by step (the PR 6 dispatch
            # counter machinery): never a second fused launch
            assert eng.n_fused_dispatches - d0 <= 1
            req = eng.finished.get(lid) or next(
                (r for r in eng.slot_req
                 if r is not None and r.rid == lid), None)
            if long_first is None:
                gaps.append(dt)
                if req is not None and req.t_first:
                    long_first = req.t_first - req.t_submit
        outs = {rid: np.asarray(eng.finished[rid].tokens, np.int32)
                for rid in sids + [lid]}
        for got, want in zip([outs[sids[0]], outs[sids[1]], outs[lid]],
                             refs):                # exact parity, each mode
            np.testing.assert_array_equal(got, want)
        if fused:
            assert eng.n_fused_dispatches > 0
            assert eng.n_legacy_dispatches == 0
        else:
            assert eng.n_fused_dispatches == 0
            assert eng.n_legacy_dispatches > 0
        return eng, gaps, long_first

    rows = {}
    for mode, fused in (("legacy", False), ("fused", True)):
        run_once(fused)                            # warmup: compile jits
        p99s, ttfts = [], []
        for _ in range(TIMED_REPEATS):             # per-claim minima, as
            eng, gaps, ttft = run_once(fused)      # in bench_chunked
            p99s.append(float(np.percentile(gaps, 99)))
            ttfts.append(ttft)
        p99, ttft = min(p99s), min(ttfts)
        rows[mode] = (p99, ttft)
        emit(f"fused/{mode}/p99_itl_ms", round(p99 * 1e3, 2),
             "long_admission_window")
        emit(f"fused/{mode}/long_ttft_ms", round(ttft * 1e3, 2))
        emit(f"fused/{mode}/dispatches",
             eng.n_fused_dispatches or eng.n_legacy_dispatches,
             "deterministic")
        if fused:
            emit("fused/interleaved_steps", eng.n_interleaved_decode_steps,
                 "deterministic")
            emit("fused/step_tokens", step_tokens, "deterministic")
            emit("fused/budget_utilization",
                 round(eng.stats()["step_budget_utilization"], 3))
    # the budget-bounded one-dispatch step strictly caps the legacy
    # chunk-step decode stall
    assert rows["fused"][0] < rows["legacy"][0], rows
    emit("fused/p99_itl_ratio",
         round(rows["legacy"][0] / rows["fused"][0], 2), "assert_gt_1")


# ---------------------------------------------------------------------------
def bench_async(fast: bool) -> None:
    """Async host loop under concurrent streamed traffic at a FIXED HBM
    budget vs NBL-m: client threads submit through AsyncEngine.submit_stream
    while the background step thread serves, measuring streamed tokens/s
    end-to-end (submission -> last stream closed) and p50/p99 QUEUE DELAY
    (submit -> admission wait — the metric backpressure acts on). Every
    pass asserts token-exact generate() parity on the streamed tokens and
    a zero-leak pool after shutdown; linearized layers carry no page pool,
    so admitted concurrency is monotone in m at equal budget and the queue
    drains wider.

    Runs with the observability registry ATTACHED: the artifact's token
    count is the registry's ``nbl_tokens_emitted_total`` (cross-validated
    against the hand count from the streams every pass), and at m=0 the
    scenario asserts the two obs acceptance bounds — streamed tok/s with
    the registry enabled within 3% of disabled (per-metric minima over
    TIMED_REPEATS on BOTH sides), and zero extra device dispatches on the
    step path (obs on/off produce identical deterministic sweep counts on
    a sync engine replay)."""
    import threading

    from repro.configs import get_config
    from repro.core.surgery import nbl_variant
    from repro.launch.engine import AsyncEngine, Engine
    from repro.launch.serve import generate
    from repro.models import init_params
    from repro.models.kv_cache import cache_bytes
    from repro.obs import Observability

    cfg = get_config("tiny-dense")
    max_len, page_size = 64, 8
    budget = 2 * cache_bytes(cfg, 1, max_len)      # 2 full rings
    n_req = 8 if fast else 16
    max_new = 8
    n_client_threads = 4
    rng = np.random.default_rng(0)
    lens = rng.integers(6, 21, n_req)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    expected = int(np.percentile(lens, 90)) + max_new

    slots_by_m = []
    for m in (0, 1, 2, 3):
        c = nbl_variant(cfg, m)
        params = init_params(jax.random.PRNGKey(0), c)
        refs = [np.asarray(generate(c, params, jnp.asarray(p)[None],
                                    max_new=max_new))[0] for p in prompts]

        def run_once(with_obs: bool = True):
            obs = Observability() if with_obs else None
            eng = Engine(c, params, max_len=max_len,
                         cache_budget_bytes=budget, paged=True,
                         page_size=page_size, expected_len=expected,
                         obs=obs)
            aeng = AsyncEngine(eng, max_pending=2 * n_req)
            streams = [None] * n_req
            t0 = clock()

            def client(tid):                 # round-robin request sharding
                for i in range(tid, n_req, n_client_threads):
                    streams[i] = aeng.submit_stream(prompts[i], max_new)
                for i in range(tid, n_req, n_client_threads):
                    streams[i].result(timeout=300)

            ts = [threading.Thread(target=client, args=(t,))
                  for t in range(n_client_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(300)
            dt = clock() - t0
            aeng.shutdown(drain=True)
            ntok = 0
            for s, want in zip(streams, refs):
                got = s.result(timeout=1)
                np.testing.assert_array_equal(got, want)  # streamed == ref
                ntok += len(got)
            assert eng.allocator.in_use == 0   # zero leaked pages
            if obs is not None:
                # the artifact's token count is the REGISTRY's view; the
                # hand count from the streams only cross-validates it
                assert obs.tokens.value == ntok, (obs.tokens.value, ntok)
                assert obs.finished.value == n_req
            qd = np.array([eng.finished[s.rid].t_admit
                           - eng.finished[s.rid].t_submit for s in streams])
            return eng, obs, dt, ntok, qd

        run_once()                             # warmup: compile jits
        n_slots, dts, p50s, p99s, ntok = None, [], [], [], 0
        for _ in range(TIMED_REPEATS):         # per-metric min (see top)
            eng, obs, dt, ntok, qd = run_once()
            n_slots = eng.n_slots
            dts.append(dt)
            p50s.append(float(np.percentile(qd, 50)))
            p99s.append(float(np.percentile(qd, 99)))
        slots_by_m.append(n_slots)
        emit(f"async/nbl-{m}/concurrency", n_slots, "equal_budget")
        emit(f"async/nbl-{m}/streamed_tokens_per_s",
             round(ntok / min(dts), 1), "registry")
        emit(f"async/nbl-{m}/p50_queue_delay_ms",
             round(min(p50s) * 1e3, 2))
        emit(f"async/nbl-{m}/p99_queue_delay_ms",
             round(min(p99s) * 1e3, 2))
        if m == 0:
            rate_on = ntok / min(dts)
            # overhead guard: same workload with obs=None, per-metric min
            off_dts = []
            for _ in range(TIMED_REPEATS):
                _, _, dt, ntok_off, _ = run_once(with_obs=False)
                off_dts.append(dt)
            assert ntok_off == ntok, (ntok_off, ntok)   # same tokens served
            rate_off = ntok_off / min(off_dts)
            over_pct = (rate_off - rate_on) / rate_off * 100.0
            assert rate_on >= 0.97 * rate_off, (rate_on, rate_off)
            emit("async/obs_overhead_pct", round(over_pct, 2), "assert_le_3")
            # dispatch guard: every obs hook is host-side, so a DETERMINISTIC
            # sync replay must do identical device work with obs on vs off —
            # sweep counts, prefill counts/tokens, and the tokens themselves
            sweep = {}
            for on in (True, False):
                o = Observability() if on else None
                e = Engine(c, params, max_len=max_len,
                           cache_budget_bytes=budget, paged=True,
                           page_size=page_size, expected_len=expected, obs=o)
                rids = [e.submit(p, max_new) for p in prompts]
                out = e.run()
                sweep[on] = (e.n_decode_steps, e.n_prefills,
                             e.n_prefill_tokens,
                             tuple(tuple(out[r]) for r in rids))
                if o is not None:
                    assert o.decode_steps.value == e.n_decode_steps
                    assert o.prefills.value == e.n_prefills
            assert sweep[True] == sweep[False], "obs changed device work"
            emit("async/obs_zero_extra_dispatches", 1, "assert")
    # structural claims (parity + zero-leak asserted inside every pass)
    assert slots_by_m == sorted(slots_by_m), slots_by_m
    emit("async/concurrency_monotone_in_m", 1, "assert")
    # the scenario artifact carries the last pass's full registry snapshot
    # (obs here is the TIMED_REPEATS loop's last binding, from run_once()
    # with the with_obs=True default — never None on this path)
    return {"registry": obs.snapshot()}  # nbl: disable=obs-hygiene -- bound by run_once(with_obs=True)


# ---------------------------------------------------------------------------
def bench_kernels(fast: bool) -> None:
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 256, 64))
    k = jax.random.normal(key, (1, 2, 256, 64))
    v = jax.random.normal(key, (1, 2, 256, 64))
    x = jax.random.normal(key, (1, 512, 256))
    w = jax.random.normal(key, (256, 256)) * 0.05
    b = jnp.zeros((256,))
    acc = jnp.zeros((256, 256))

    for name, fn in [
        ("flash_attention", lambda: ops.attention(q, k, v)),
        ("nbl_linear", lambda: ops.nbl_apply(x, w, b)),
        ("cov_accum", lambda: ops.cov_update(acc, x[0])),
    ]:
        fn()  # compile
        ts = []
        for _ in range(TIMED_REPEATS):       # min-over-repeats (see top)
            t0 = clock()
            jax.block_until_ready(fn())
            ts.append(clock() - t0)
        emit(f"kernels/{name}", round(min(ts) * 1e6, 1),
             "us_per_call_interpret")


# ---------------------------------------------------------------------------
def bench_spec_throughput(fast: bool) -> None:
    """Table 6 analog, engine-native: self-speculative decoding (the SAME
    trained params under a deeper NBL plan drafting through the target's
    own page table) vs non-spec paged decode at EQUAL HBM budget on a
    single-stream workload — the latency scenario speculation targets.
    The drafter's linear maps are CALIBRATED (core.calibrate on the
    deepest-m attention layers), because acceptance is what converts the
    2-dispatch burst (one scanned γ-token draft + one batched verify)
    into >1 token per step. Reported per (draft-m, γ): tokens/s,
    tokens/burst, acceptance; draft-m=0 (the target drafting for itself,
    acceptance 1) bounds the machinery's ceiling. Every timed pass
    asserts token-exact generate() parity and a drained, zero-leak pool;
    the headline asserts a CALIBRATED draft (m >= 1) emits > 1
    token/burst and beats the non-spec engine's tokens/s."""
    from repro.configs import get_config
    from repro.core import calibrate
    from repro.data import ZipfMarkov, calib_factory
    from repro.launch.engine import Engine
    from repro.launch.serve import generate
    from repro.launch.speculative import make_nbl_draft
    from repro.launch.train import train

    cfg = get_config("tiny-dense")
    params = train(cfg, steps=120 if fast else 200, global_batch=16, seq=64,
                   peak_lr=3e-3, log_fn=lambda s: None)["params"]
    fac = calib_factory(cfg, batch=4, seq=64, n_batches=4)
    calib = calibrate(cfg, params, fac)

    from repro.models.kv_cache import cache_bytes

    max_len, page_size = 64, 8
    budget = 2 * cache_bytes(cfg, 1, max_len)      # 2 full rings, both sides
    n_req = 4 if fast else 8
    max_new = 24                       # decode-dominated single streams
    proc = ZipfMarkov(cfg.vocab_size, seed=0)
    prompts = [np.asarray(p, np.int32) for p in proc.sample(n_req, 12,
                                                            seed=3)]
    refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                max_new=max_new))[0] for p in prompts]

    def run_sweep(eng, gamma, draft_m):
        """One sequential pass over the stream; asserts parity + zero
        leak. Sequential submit->drain is the single-stream latency
        shape: batched decode cannot hide the per-token dispatch."""
        t0 = clock()
        for p, want in zip(prompts, refs):
            rid = eng.submit(p, max_new, spec_gamma=gamma,
                             draft_m=draft_m)
            out = eng.run()
            np.testing.assert_array_equal(out[rid], want)
        dt = clock() - t0
        assert eng.allocator.in_use == 0
        return dt

    ms = ((0, 1, 2) if fast else (0, 1, 2, 3))
    gammas = (4,) if fast else (2, 4)
    drafts = {m: make_nbl_draft(
        cfg, params, m,
        linear_maps={i: calib[i].linear
                     for i in cfg.attn_layer_indices()[-m:]} if m else None)
        for m in ms}

    # non-spec baseline: same budget, same stream, plain paged decode
    eng = Engine(cfg, params, max_len=max_len, cache_budget_bytes=budget,
                 paged=True, page_size=page_size)
    run_sweep(eng, 0, None)                       # warmup: compile jits
    dts = [run_sweep(eng, 0, None) for _ in range(TIMED_REPEATS)]
    ntok = n_req * max_new
    base_rate = ntok / min(dts)
    emit("spec/baseline/tokens_per_s", round(base_rate, 1), "equal_budget")

    best = {}                                     # m -> best tok/s
    tpb = {}                                      # m -> tokens/burst at best
    for m in ms:
        for gamma in gammas:
            eng = Engine(cfg, params, max_len=max_len,
                         cache_budget_bytes=budget, paged=True,
                         page_size=page_size, drafts={m: drafts[m]})
            run_sweep(eng, gamma, m)              # warmup: compile jits
            b0, t0 = eng.n_spec_bursts, eng.n_spec_tokens
            a0, d0 = eng.n_spec_accepted_tokens, eng.n_spec_draft_tokens
            dts, bursts = [], []
            for _ in range(TIMED_REPEATS):
                s0 = eng.n_spec_bursts
                dts.append(run_sweep(eng, gamma, m))
                bursts.append(eng.n_spec_bursts - s0)
            assert len(set(bursts)) == 1, bursts  # same work every pass
            rate = ntok / min(dts)
            per_burst = (eng.n_spec_tokens - t0) / max(eng.n_spec_bursts
                                                       - b0, 1)
            acc = (eng.n_spec_accepted_tokens - a0) / max(
                eng.n_spec_draft_tokens - d0, 1)
            emit(f"spec/nbl-{m}/gamma-{gamma}/tokens_per_s",
                 round(rate, 1), "equal_budget")
            emit(f"spec/nbl-{m}/gamma-{gamma}/tokens_per_burst",
                 round(per_burst, 2), "deterministic")
            emit(f"spec/nbl-{m}/gamma-{gamma}/acceptance",
                 round(acc, 3), "deterministic")
            if rate > best.get(m, 0.0):
                best[m], tpb[m] = rate, per_burst
    # headline: a CALIBRATED self-draft multiplies tokens per step AND
    # converts it into throughput over the non-spec engine (parity and
    # zero-leak already asserted inside every pass)
    winner = max((m for m in best if m >= 1), key=lambda m: best[m])
    assert tpb[winner] > 1.0, (winner, tpb)
    assert best[winner] > base_rate, (winner, best[winner], base_rate)
    emit("spec/best_calibrated_m", winner, "assert_beats_baseline")
    emit("spec/speedup_vs_baseline",
         round(best[winner] / base_rate, 2), "assert_gt_1")


def bench_quant_compose(fast: bool) -> None:
    """Table 5 analog (§4.3): NBL on a weight-quantized model. Reports the
    byte compression and the ppl of fp / int8 / int8+NBL (int4 in full
    mode, matching the paper's AWQ-4bit 70B setup)."""
    from repro.configs import get_config
    from repro.core import nbl_compress
    from repro.data import calib_factory
    from repro.eval import perplexity
    from repro.launch.train import train
    from repro.quant import quantize_model

    cfg = get_config("tiny-dense")
    params = train(cfg, steps=120, global_batch=16, seq=64, peak_lr=3e-3,
                   log_fn=lambda s: None)["params"]
    fac = calib_factory(cfg, batch=4, seq=64, n_batches=4)
    evalfac = calib_factory(cfg, batch=4, seq=64, n_batches=2, seed=77)
    emit("quant/fp/ppl", round(perplexity(cfg, params, evalfac), 3))
    for bits in ((8,) if fast else (8, 4)):
        qp, rep = quantize_model(cfg, params, bits=bits)
        emit(f"quant/int{bits}/ppl",
             round(perplexity(cfg, qp, evalfac), 3))
        emit(f"quant/int{bits}/compression",
             round(rep.fp_bytes / max(rep.q_bytes, 1), 2))
        ncfg, np_, _ = nbl_compress(cfg, qp, fac, 2)
        emit(f"quant/int{bits}+nbl-2/ppl",
             round(perplexity(ncfg, np_, evalfac), 3))


def bench_lora(fast: bool) -> None:
    """Appendix F.2: LoRA refinement of NBL layers — marginal by design."""
    from repro.configs import get_config
    from repro.core import nbl_compress
    from repro.core.lora import lora_finetune
    from repro.data import calib_factory
    from repro.eval import perplexity
    from repro.launch.train import train

    cfg = get_config("tiny-dense")
    params = train(cfg, steps=120, global_batch=16, seq=64, peak_lr=3e-3,
                   log_fn=lambda s: None)["params"]
    fac = calib_factory(cfg, batch=4, seq=64, n_batches=4)
    ncfg, nparams, _ = nbl_compress(cfg, params, fac, 2)
    evalfac = calib_factory(ncfg, batch=4, seq=64, n_batches=2, seed=99)
    emit("lora/nbl-2/ppl", round(perplexity(ncfg, nparams, evalfac), 3))
    tuned = lora_finetune(ncfg, nparams, fac, steps=15 if fast else 30,
                          rank=4, lr=5e-4)
    emit("lora/nbl-2+lora/ppl", round(perplexity(ncfg, tuned, evalfac), 3))


BENCHES = {
    "table_compression": bench_compression,
    "table_calibration": bench_calibration_runtime,
    "fig3_prefill": bench_fig3_prefill,
    "table21_kv_cache": bench_kv_cache,
    "criterion_ablation": bench_criterion_ablation,
    "serving_throughput": bench_serving,
    "paged_throughput": bench_paged,
    "prefix_throughput": bench_prefix,
    "chunked_throughput": bench_chunked,
    "fused_throughput": bench_fused,
    "async_throughput": bench_async,
    "speculative_throughput": bench_spec_throughput,
    "quant_compose": bench_quant_compose,
    "lora": bench_lora,
    "kernels": bench_kernels,
}


def _provenance() -> dict:
    """Where this artifact came from: git SHA (best-effort — "unknown"
    outside a checkout), UTC timestamp, and the repeat count every timed
    metric was minimized over."""
    import datetime
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    ts = datetime.datetime.now(datetime.timezone.utc)
    return {"git_sha": sha,
            "timestamp_utc": ts.isoformat(timespec="seconds"),
            "timed_repeats": TIMED_REPEATS}


def write_scenario_artifact(name: str, rows: list, extra: dict = None) -> str:
    """One stable JSON artifact per scenario under benchmarks/out/ — a
    sorted rows list with a fixed schema, so successive PRs can diff the
    same file path for trajectory tracking. Schema v2 adds provenance
    (git SHA, timestamp, repeats) and lets a scenario attach extra
    derived views (e.g. the observability registry snapshot)."""
    outdir = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"{name}.json")
    payload = {"schema_version": 2,
               "scenario": name,
               "provenance": _provenance(),
               "rows": sorted(({"name": n, "value": v, "derived": d}
                               for n, v, d in rows), key=lambda r: r["name"])}
    for k, v in (extra or {}).items():
        payload.setdefault(k, v)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    print("name,value,derived")
    for name in names:
        start = len(ROWS)
        extra = BENCHES[name](args.fast)
        write_scenario_artifact(name, ROWS[start:], extra)
    out = os.path.join(os.path.dirname(__file__), "out.json")
    with open(out, "w") as f:
        json.dump([{"name": n, "value": v, "derived": d}
                   for n, v, d in ROWS], f, indent=1)


if __name__ == "__main__":
    main()
