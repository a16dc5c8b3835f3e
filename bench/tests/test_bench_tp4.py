"""The four-chip Mistral-7B cell: its collectives reader on hand-made
normalized traces, the reference's key layout for its 20-attention +
12-NBL stack, and its fused step sharded over a (data, model) = (1, 4)
mesh, compiled for a described TPU v5e:2x2 without a chip.

The compile keeps the names the benchmark's trace patterns match (the
step module, the paged kernel as ``%shard_map.N``), holds collectives
that the reader takes every one of, and fits a chip's memory. The
topology is described inside a fixture, never while the module is
imported (only one process may load the TPU library at a time, and under
pytest-xdist every worker imports every test file). Nothing runs."""
import functools
import json
import re
import sys

import pytest

import bench_tiny_cell as tiny

sys.path.insert(0, str(tiny.ROOT))
sys.path.insert(0, str(tiny.ROOT / "bench"))

import serve_cell  # noqa: E402
from bench.lib import names  # noqa: E402
from bench.lib.spec import Bench, load_module, read_metric  # noqa: E402

CONFIG = "mistral-7b-tp4-nbl12"
METRIC = "collective_ms_per_step.tp4"
MS = 1_000_000                                 # ns per millisecond
HBM_BYTES = 16e9                               # one v5e chip
# instruction heads as the compiled step names them
ALL_REDUCE = "%all-reduce.11 = bf16[48,1,4096] all-reduce(a)"
AR_FUSION = ("%fusion.179 = bf16[48,1024] fusion(a), kind=kCustom, "
             "calls=%all-reduce-scatter.clone.clone")
AG_START = "%all-gather-start.2 = (bf16[48,1,1024]) all-gather-start(a)"
AG_DONE = "%all-gather-done.2 = bf16[48,1,4096] all-gather-done(a)"
COPY = "%copy.2 = copy(a)"


@pytest.fixture(scope="module")
def bench():
    return Bench(str(tiny.ROOT))


@pytest.fixture(scope="module")
def reader():
    return load_module(str(tiny.ROOT / "bench" / "layer_metrics"
                           / "collective_ms_per_step.py"))


def span(name, t0, t1):
    return [name, int(t0 * MS), int((t1 - t0) * MS)]


def steps_trace(chips: int, ops) -> dict:
    """Four fused steps of 20 ms on each chip; ``ops(t)`` gives a step's
    device ops from its start ``t`` (ms)."""
    dev, mods = {}, {}
    for c in range(chips):
        plane = f"/device:TPU:{c}"
        mods[plane] = [span("jit__fused(7)", 25.0 * i, 25.0 * i + 20)
                       for i in range(4)]
        dev[plane] = [e for i in range(4) for e in ops(25.0 * i)]
    return {"device": dev, "modules": mods, "host": []}


def read(bench, norm):
    return read_metric(bench, METRIC, {"trace": norm, "obs": {},
                                       "chips": 4, "window_s": 0.1})


def test_async_pair_counts_start_to_done(bench):
    """A 0.1 ms start and a 0.1 ms done 2 ms apart: 2 ms moved data, not
    the markers' 0.2 ms."""
    norm = steps_trace(4, lambda t: [
        span(AG_START, t + 1, t + 1.1), span(COPY, t + 1.5, t + 2.5),
        span(AG_DONE, t + 2.9, t + 3.0)])
    assert read(bench, norm) == pytest.approx(2.0)


def test_interleaved_permute_pairs_close_in_order(bench):
    """The chip's trace names a reduce-scatter's permutes without a
    number and then ``.1``; two pairs open before either closes."""
    norm = steps_trace(4, lambda t: [
        span("%collective-permute-start = (bf16[48,1024]) "
             "collective-permute-start(a)", t + 1, t + 1.1),
        span("%collective-permute-start.1 = (bf16[48,1024]) "
             "collective-permute-start(b)", t + 1.2, t + 1.3),
        span("%collective-permute-done = bf16[48,1024] "
             "collective-permute-done(a)", t + 2, t + 2.01),
        span("%collective-permute-done.1 = bf16[48,1024] "
             "collective-permute-done(b)", t + 4, t + 4.01)])
    assert read(bench, norm) == pytest.approx(3.01)


def test_sync_collective_and_fusion_count_by_duration(bench):
    norm = steps_trace(4, lambda t: [
        span(ALL_REDUCE, t + 1, t + 1.5), span(COPY, t + 2, t + 9),
        span(AR_FUSION, t + 10, t + 10.25)])
    assert read(bench, norm) == pytest.approx(0.75)


def test_collectives_outside_whole_steps_are_left_out(bench):
    """The first and last steps may be cut by the window's edges, and an
    op between steps is no part of the step: only the two middle steps'
    0.5 ms count."""
    norm = steps_trace(4, lambda t: [
        span(ALL_REDUCE, t + 1, t + (9 if t in (0, 75) else 1.5))])
    for evs in norm["device"].values():
        evs.append(span(ALL_REDUCE, 21, 24))     # between two steps
    assert read(bench, norm) == pytest.approx(0.5)


def test_one_chip_without_collectives_reads_none(bench):
    norm = steps_trace(1, lambda t: [span(COPY, t + 1, t + 9)])
    assert read(bench, norm) is None


def test_overlapping_collectives_count_once(reader):
    evs = [span(AG_START, 1, 1.1), span(ALL_REDUCE, 1.5, 2.5),
           span(AG_DONE, 2.0, 2.2)]
    spans = reader.intervals(evs)
    assert sorted(spans) == [(1 * MS, int(2.2 * MS)),
                             (int(1.5 * MS), int(2.5 * MS))]


def test_reference_key_layout_matches_the_program_stack(bench):
    """The plain reference draws one key per scan group of equal
    consecutive layers: for the tp4 stack that is the program's own two
    groups, 20 attention layers then 12 NBL maps."""
    ref = load_module(str(tiny.ROOT / "bench" / "references"
                          / "dense_gqa.py"))
    cfg = bench.config(CONFIG)
    mc = serve_cell.program_config(cfg)
    program = [(tuple(b.kind for b in g.unit), g.repeat) for g in mc.stack]
    assert ref.groups(ref.Shapes(cfg).kinds) == program
    assert program == [(("attn",), 20), (("nbl",), 12)]


@pytest.fixture(scope="module")
def sharded_step():
    """The compiled fused step of the tp4 configuration at its engine's
    slots and length, with parameters and cache laid out by the program's
    specs over model = 4 and the kernel route on."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import cache_specs, param_specs
    from repro.kernels import paged_attention as pa
    from repro.models import fused_step, init_params
    from repro.models.paging import init_paged_cache, pages_per_seq

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = json.loads((tiny.ROOT / "bench" / "configs" / f"{CONFIG}.json")
                     .read_text())
    mc = serve_cell.program_config(cfg)
    e = cfg["engine"]
    b, max_len, ps = e["n_slots"], e["max_len"], e["page_size"]
    pshape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc))
    cshape = jax.eval_shape(lambda: init_paged_cache(mc, b, max_len,
                                                     page_size=ps))
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(1, 4),
                             ("data", "model"))
    rep = NamedSharding(mesh, P())
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)  # noqa: E731

    def named(tree, specs):
        return jax.tree.map(lambda x, sp: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, sp)), tree, specs)

    def _fused(p, t, c, rp, rl, tb):
        return fused_step(mc, p, t, c, rp, rl, tb)

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp, jax.set_mesh(mesh):
            mp.setattr(pa, "paged_mixed",
                       functools.partial(pa.paged_mixed, use_kernel=True))
            args = [named(pshape, param_specs(pshape)), i32(b, 1),
                    named(cshape, cache_specs(cshape)), i32(b), i32(b),
                    i32(b, pages_per_seq(max_len, ps))]
            return jax.jit(  # nbl: disable=jit-discipline -- AOT compile for a described chip, never called
                _fused, donate_argnums=(2,)).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_tp4_step_keeps_the_module_and_kernel_names(sharded_step):
    hlo = sharded_step.as_text()
    module = re.search(r"^HloModule ([^,\s]+)", hlo, re.M).group(1)
    # a trace names a program "<module>(<program id>)"
    assert re.search(names.STEP_MODULE, f"{module}(0)"), module
    kernels = [ln.strip() for ln in hlo.splitlines()
               if "tpu_custom_call" in ln]
    assert kernels, "no Mosaic call in the sharded step"
    assert all(re.search(names.PAGED_KERNEL, k) for k in kernels), \
        [k[:40] for k in kernels]
    assert any(k.startswith("%shard_map.") for k in kernels), \
        [k[:40] for k in kernels]


def test_tp4_step_collectives_match_the_reader(sharded_step, reader):
    """Every instruction of the step that moves data between chips (a
    collective, its async halves, or a fusion calling one) is one the
    reader counts: the all-reduces after ``wo`` and ``w_down``, the
    all-gathers after the column-parallel NBL maps, and the rest."""
    opcode = re.compile(rf"= [^=]*? {reader.KIND}(?:-start|-done)?\(")
    lines = [ln.strip().removeprefix("ROOT ")
             for ln in sharded_step.as_text().splitlines()]
    moving = [ln for ln in lines
              if opcode.search(ln.split(", metadata=")[0])
              or re.search(rf"calls=%{reader.KIND}", ln)]
    assert len(moving) >= 4, "no collectives in the sharded step"
    missed = [ln[:80] for ln in moving if not reader.COLLECTIVE.search(ln)]
    assert not missed, missed
    kinds = {m.group(0) for ln in moving
             for m in [re.search(reader.KIND, ln)] if m}
    assert {"all-reduce", "all-gather"} <= kinds, kinds


def test_tp4_step_fits_a_chip(sharded_step):
    """Arguments (a chip's weights and KV pool) and temporaries of the
    step, per device, under one v5e's 16 GB."""
    m = sharded_step.memory_analysis()
    if m is None:
        pytest.skip("the compiler gives no memory analysis here")
    held = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert held < HBM_BYTES, (m.argument_size_in_bytes,
                              m.temp_size_in_bytes)
