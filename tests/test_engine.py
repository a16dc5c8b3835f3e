"""Continuous-batching engine: parity with generate(), ragged admission,
slot recycling, NBL-aware admission budget."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.surgery import compress_config, nbl_variant
from repro.launch.engine import Engine
from repro.launch.scheduler import Scheduler, nbl_slot_budget
from repro.launch.serve import generate, serve_requests
from repro.models import init_params
from repro.models.kv_cache import cache_bytes


def _setup(arch="tiny-dense", seed=0):
    cfg = get_config(arch)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _ref(cfg, params, prompt, max_new):
    """Single-request greedy reference via the fixed-batch loop."""
    out = generate(cfg, params, jnp.asarray(prompt)[None], max_new=max_new)
    return np.asarray(out)[0]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ------------------------------------------------------------- parity ------

@pytest.mark.parametrize("arch", ["tiny-dense", "tiny-swa", "tiny-mamba"])
def test_engine_parity_matches_generate(arch):
    """Greedy tokens from the continuous-batching engine match the
    single-request generate() loop, per request, across cache families
    (global attn / sliding-window ring / SSM state)."""
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, [6, 10, 8])
    refs = [_ref(cfg, params, p, 5) for p in prompts]

    eng = Engine(cfg, params, max_len=20, n_slots=2)
    rids = [eng.submit(p, 5) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i], err_msg=f"req {i}")


def test_engine_parity_nbl_compressed():
    """The engine serves an NBL-compressed stack (linearized layers carry
    no cache slots) with exact parity to generate()."""
    cfg, _ = _setup()
    ncfg = compress_config(cfg, cfg.attn_layer_indices()[-2:], "nbl")
    params = init_params(jax.random.PRNGKey(1), ncfg)
    prompts = _prompts(ncfg, [7, 9])
    refs = [_ref(ncfg, params, p, 4) for p in prompts]

    eng = Engine(ncfg, params, max_len=16, n_slots=2)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i])


# -------------------------------------------- ragged admission / stream ----

def test_ragged_admission_mid_stream():
    """More requests than slots, mixed prompt lengths: requests are admitted
    as slots free up mid-stream, every request completes, and concurrency
    never exceeds the slot pool."""
    cfg, params = _setup()
    lens = [4, 12, 6, 9, 5]
    prompts = _prompts(cfg, lens, seed=3)
    refs = [_ref(cfg, params, p, 4) for p in prompts]

    eng = Engine(cfg, params, max_len=20, n_slots=2)
    rids = [eng.submit(p, 4) for p in prompts]
    max_active = 0
    while eng.has_work:
        eng.step()
        max_active = max(max_active, len(eng.active_slots))
    out = {rid: np.asarray(r.tokens) for rid, r in eng.finished.items()}

    assert len(out) == len(prompts)          # all retired
    assert max_active <= 2
    assert eng.n_prefills == len(prompts)    # each admitted exactly once
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(out[rid], refs[i], err_msg=f"req {i}")


def test_late_submission_joins_running_batch():
    """A request submitted while the engine is mid-decode is admitted on a
    later step and still decodes correctly next to in-flight requests."""
    cfg, params = _setup()
    p1, p2 = _prompts(cfg, [8, 5], seed=7)
    r1 = _ref(cfg, params, p1, 6)
    r2 = _ref(cfg, params, p2, 4)

    eng = Engine(cfg, params, max_len=16, n_slots=2)
    rid1 = eng.submit(p1, 6)
    eng.step()                               # p1 prefilled + 1 decode
    eng.step()
    rid2 = eng.submit(p2, 4)                 # joins mid-stream
    out = eng.run()
    np.testing.assert_array_equal(out[rid1], r1)
    np.testing.assert_array_equal(out[rid2], r2)


# ----------------------------------------------------- slot recycling ------

def test_slot_recycling_no_stale_kv():
    """One slot, sequential tenancy: the second request's tokens must be
    identical to a fresh engine's — any stale KV/state left by the first
    tenant (longer prompt, fully filled cache) would corrupt them."""
    cfg, params = _setup()
    long_p, short_p = _prompts(cfg, [14, 4], seed=11)

    eng = Engine(cfg, params, max_len=20, n_slots=1)
    rid_a = eng.submit(long_p, 6)
    rid_b = eng.submit(short_p, 6)
    out = eng.run()
    assert len(out[rid_a]) == 6

    fresh = Engine(cfg, params, max_len=20, n_slots=1)
    rid_f = fresh.submit(short_p, 6)
    np.testing.assert_array_equal(out[rid_b], fresh.run()[rid_f])
    np.testing.assert_array_equal(out[rid_b],
                                  _ref(cfg, params, short_p, 6))


def test_eos_retires_early_and_slot_is_reused():
    cfg, params = _setup()
    p1, p2 = _prompts(cfg, [6, 9], seed=5)
    ref1 = _ref(cfg, params, p1, 8)
    eos = int(ref1[2])                       # some token generate() emits
    stop = int(np.argmax(ref1 == eos)) + 1   # engine must stop at FIRST hit

    eng = Engine(cfg, params, max_len=20, n_slots=1, eos_id=eos)
    rid1 = eng.submit(p1, 8)
    rid2 = eng.submit(p2, 3)
    out = eng.run()
    assert list(out[rid1]) == list(ref1[:stop])   # eos inclusive, early
    assert len(out[rid1]) < 8
    assert len(out[rid2]) <= 3               # second tenant ran after


# ------------------------------------------------- NBL-aware admission -----

def test_reset_slot_scrubs_one_row():
    """reset_slot invalidates exactly the given slot: kpos -> -1, state
    leaves -> 0; other slots untouched."""
    import jax.tree_util as jtu
    from repro.models import prefill
    from repro.models.kv_cache import assign_slot, init_slot_cache, reset_slot

    cfg, params = _setup()
    prompts = _prompts(cfg, [6, 6], seed=21)
    slot_cache = init_slot_cache(cfg, 2, 12)
    for slot, p in enumerate(prompts):
        _, pc = prefill(cfg, params, jnp.asarray(p)[None], cache_len=12)
        slot_cache = assign_slot(slot_cache, pc, jnp.int32(slot))
    scrubbed = reset_slot(slot_cache, jnp.int32(0))
    for (path, got), (_, was) in zip(
            jtu.tree_flatten_with_path(scrubbed)[0],
            jtu.tree_flatten_with_path(slot_cache)[0]):
        name = str(getattr(path[-1], "key", ""))
        want0 = -1 if name == "kpos" else 0
        assert (np.asarray(got[:, 0]) == want0).all(), (path, "row 0")
        np.testing.assert_array_equal(np.asarray(got[:, 1]),
                                      np.asarray(was[:, 1]))  # row 1 intact


def test_engine_budget_clamps_explicit_n_slots():
    """cache_budget_bytes is a ceiling even when n_slots is also given."""
    cfg, params = _setup()
    budget = 2 * cache_bytes(cfg, 1, 16)
    eng = Engine(cfg, params, max_len=16, n_slots=64,
                 cache_budget_bytes=budget)
    assert eng.n_slots == 2
    with pytest.raises(ValueError):
        Engine(cfg, params, max_len=16, n_slots=0)


def test_nbl_slot_budget_monotone_in_m():
    """Fixed byte budget: linearizing more layers -> more concurrent slots
    (the paper's (K-m)/K cache saving, converted to admission)."""
    cfg, _ = _setup()
    max_len = 128
    budget = 4 * cache_bytes(cfg, 1, max_len)   # 4 slots at m=0
    slots = []
    for m in range(0, 4):
        slots.append(nbl_slot_budget(nbl_variant(cfg, m), budget, max_len))
    assert slots[0] == 4
    assert slots == sorted(slots)               # monotone non-decreasing
    assert slots[-1] > slots[0]                 # strictly more by m=3 (K=6)


def test_more_slots_fewer_decode_sweeps():
    """The throughput mechanism: at fixed work, a bigger slot pool drains
    the queue in fewer batched decode steps."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [5, 5, 5, 5], seed=9)
    steps = {}
    for n_slots in (1, 4):
        eng = Engine(cfg, params, max_len=12, n_slots=n_slots)
        for p in prompts:
            eng.submit(p, 4)
        eng.run()
        steps[n_slots] = eng.n_decode_steps
    assert steps[4] < steps[1]


def test_serve_requests_wrapper():
    cfg, params = _setup()
    prompts = _prompts(cfg, [6, 10], seed=13)
    refs = [_ref(cfg, params, p, 4) for p in prompts]
    outs, stats = serve_requests(cfg, params, prompts, max_new=4, n_slots=2)
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    assert stats["n"] == 2 and stats["n_slots"] == 2


def test_engine_sharded_parity(subproc):
    """The engine under a (data, model) mesh — params/caches sharded with
    their production specs — emits the same greedy tokens as the unmeshed
    single-request reference."""
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.engine import Engine
from repro.launch.serve import generate
from repro.models import init_params

cfg = get_config('tiny-dense')
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (6, 9, 7)]
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=4))[0] for p in prompts]
with jax.set_mesh(make_mesh((2, 2), ('data', 'model'))):
    eng = Engine(cfg, params, max_len=16, n_slots=2)
    rids = [eng.submit(p, 4) for p in prompts]
    out = eng.run()
for i, r in enumerate(rids):
    np.testing.assert_array_equal(out[r], refs[i])
print('OK')
""", n_devices=4)


def test_paged_engine_sharded_async_parity(subproc):
    """The multi-chip serving path: a PAGED engine built under a (data,
    model) mesh, stepped by AsyncEngine's own thread (the mesh is
    per-thread, so the engine must carry it), with attention routed
    through the Pallas kernel (interpret mode here) — which a mesh can
    only partition inside a shard_map over KV heads. Tokens match the
    unmeshed generate() reference."""
    subproc("""
import functools, warnings; warnings.filterwarnings('ignore')
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.kernels import paged_attention as pa
from repro.launch.mesh import make_mesh
from repro.launch.engine import AsyncEngine, Engine
from repro.launch.serve import generate
from repro.models import init_params

pa.paged_attention = functools.partial(pa.paged_attention, interpret=True)
pa.paged_decode = functools.partial(pa.paged_decode, use_kernel=True)
pa.paged_mixed = functools.partial(pa.paged_mixed, use_kernel=True)
cfg = get_config('tiny-dense')
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (6, 9, 7)]
with jax.set_mesh(make_mesh((2, 2), ('data', 'model'))):
    eng = Engine(cfg, params, max_len=16, n_slots=2, paged=True,
                 page_size=4)
with AsyncEngine(eng) as aeng:
    streams = [aeng.submit_stream(p, 4) for p in prompts]
    outs = [s.result(timeout=300) for s in streams]
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=4))[0] for p in prompts]
for got, want in zip(outs, refs):
    np.testing.assert_array_equal(got, want)
assert eng.allocator.in_use == 0
print('OK')
""", n_devices=4)


@pytest.mark.parametrize("paged", [False, True])
def test_direct_scheduler_submit_overflow_rejected(paged):
    """Regression: a request submitted DIRECTLY to the scheduler (the
    benchmark construction path) with prompt + max_new > max_len used to
    bypass Engine.submit's guard and decode past max_len — a host-side
    IndexError into the page table mid-serve. The engine now validates at
    admission: the oversize request is rejected (error set, excluded from
    latency percentiles) and every other request still completes."""
    from repro.launch.scheduler import latency_stats

    cfg, params = _setup()
    good, big = _prompts(cfg, [5, 14], seed=17)
    ref = _ref(cfg, params, good, 4)

    kw = dict(paged=True, page_size=4) if paged else {}
    eng = Engine(cfg, params, max_len=16, n_slots=1, **kw)
    rid_bad = eng.scheduler.submit(big, 10)      # 14 + 10 > 16
    rid_ok = eng.submit(good, 4)
    out = eng.run(max_steps=200)                 # must not raise
    np.testing.assert_array_equal(out[rid_ok], ref)
    assert eng.n_rejected == 1
    bad = eng.finished[rid_bad]
    assert bad.error is not None and "max_len" in bad.error
    assert len(bad.tokens) == 0
    s = latency_stats(list(eng.finished.values()))
    assert s["n"] == 1 and s["n_rejected"] == 1  # percentiles exclude it
    # Engine.submit shares the same reject-with-error surface (PR 5): the
    # oversize submission is RECORDED with a rid instead of raising, so a
    # serving host loop never dies on it; strict=True keeps the raise.
    rid_eager = eng.submit(big, 10)
    assert eng.finished[rid_eager].error is not None
    assert eng.n_rejected == 2
    with pytest.raises(ValueError):
        eng.submit(big, 10, strict=True)


def test_scheduler_fifo_and_prefill_cap():
    sched = Scheduler(max_prefill_per_step=2)
    for i in range(5):
        sched.submit(np.array([1, 2, 3]), 4)
    got = sched.admit(free_slots=4)
    assert [r.rid for r in got] == [0, 1]     # capped at 2 despite 4 free
    got = sched.admit(free_slots=1)
    assert [r.rid for r in got] == [2]
    assert len(sched) == 2


def test_scheduler_token_budget_paces_admission():
    """Regression: the request-count cap admits several long prompts into
    one step (their serial prefills stall every in-flight decode); the
    TOKEN budget stops admission before the step's prompt tokens exceed it
    — while the queue HEAD always admits, so an over-budget prompt can
    never starve the queue."""
    sched = Scheduler(max_prefill_per_step=4,
                      max_prefill_tokens_per_step=10)
    for n in (8, 8, 3, 2):
        sched.submit(np.arange(n), 4)
    got = sched.admit(free_slots=4)
    assert [r.rid for r in got] == [0]        # 8 + 8 > 10: stop after head
    got = sched.admit(free_slots=4)
    assert [r.rid for r in got] == [1]        # 8 + 3 > 10
    got = sched.admit(free_slots=4)
    assert [r.rid for r in got] == [2, 3]     # 3 + 2 <= 10
    # an over-budget head request still admits (no starvation)
    sched.submit(np.arange(64), 1)
    assert [r.rid for r in sched.admit(free_slots=4)] == [4]
    # request-count cap still binds under an ample token budget
    loose = Scheduler(max_prefill_per_step=2,
                      max_prefill_tokens_per_step=1000)
    for _ in range(4):
        loose.submit(np.array([1, 2]), 1)
    assert len(loose.admit(free_slots=4)) == 2
    with pytest.raises(ValueError):
        Scheduler(max_prefill_tokens_per_step=0)
