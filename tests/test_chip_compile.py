"""Compile the serving path for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached, so these tests catch what interpret mode
cannot: Mosaic tiling and VMEM limits, device-memory fit, and kernels that
GSPMD cannot partition. Nothing runs, so they say nothing about results or
speed.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and under
pytest-xdist every worker imports every test file. The compiles happen in
this process for the same reason. ``jax.default_backend()`` still reads
``cpu`` here, so each test steers the attention dispatch onto the Mosaic
kernel itself.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import dense_stack
from repro.kernels import paged_attention as pa
from repro.models import fused_step, init_params
from repro.models.paging import init_paged_cache, pages_per_seq

HBM_BYTES = 16 * 10**9                  # one v5e chip
# decode widths of mistral-7b: 8 KV heads, 4 query heads each, head_dim 128
B, KV, REP, HD, MAX_LEN = 8, 8, 4, 128, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_route(monkeypatch):
    monkeypatch.setattr(pa, "paged_mixed",
                        functools.partial(pa.paged_mixed, use_kernel=True))


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fused_args(cfg, page_size: int, width: int):
    """Shapes of one engine fused step: params, (B, W) tokens, the paged
    cache, row positions/lengths and the page table."""
    pshape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cshape = jax.eval_shape(lambda: init_paged_cache(
        cfg, B, MAX_LEN, page_size=page_size))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    pps = pages_per_seq(MAX_LEN, page_size)
    return pshape, i32(B, width), cshape, i32(B), i32(B), i32(B, pps)


def _fused(cfg):
    return jax.jit(  # nbl: disable=jit-discipline -- AOT compile for a described chip, never called
        lambda p, t, c, rp, rl, tb: fused_step(cfg, p, t, c, rp, rl, tb),
        donate_argnums=(2,))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("page_size", [64, 8])
def test_paged_attention_kernel_compiles(one_chip, page_size):
    """The decode kernel at Mistral widths in bf16, at the engine's page
    size and at the server's smaller default (a page block below bf16's
    16-row sublane tile)."""
    n_lpages = MAX_LEN // page_size
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = (B * n_lpages, KV, page_size, HD)
    compiled = jax.jit(  # nbl: disable=jit-discipline -- AOT compile for a described chip, never called
        functools.partial(pa.paged_attention, window=4096)).lower(
        s((B, KV, REP, HD), jnp.bfloat16), s(pool, jnp.bfloat16),
        s(pool, jnp.bfloat16), s((B, n_lpages), jnp.int32),
        s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_mixed_kernel_compiles_wide_rows(one_chip):
    """The mixed-row route at W=128: B*W virtual decode rows through the
    kernel."""
    w, ps = 128, 64
    n_lpages = MAX_LEN // ps
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = (B * n_lpages, KV, ps, HD)
    compiled = jax.jit(  # nbl: disable=jit-discipline -- AOT compile for a described chip, never called
        functools.partial(pa.paged_mixed, use_kernel=True)).lower(
        s((B, KV, REP, w, HD), jnp.bfloat16), s(pool, jnp.bfloat16),
        s(pool, jnp.bfloat16), s((B, n_lpages), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_fits_one_chip(one_chip, monkeypatch):
    """One engine fused step of mistral-7b at published widths, 2 layers,
    compiles for one chip with the kernel in it and fits its HBM."""
    _kernel_route(monkeypatch)
    cfg = get_config("mistral-7b", stack=dense_stack(2, window=4096))
    args = [_on(a, one_chip) for a in _fused_args(cfg, 64, 1)]
    compiled = _fused(cfg).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    used = _device_bytes(compiled)
    assert 0 < used < HBM_BYTES, used


def test_fused_step_sharded_over_model_axis(topo, monkeypatch):
    """The same step with params and cache sharded over model=4: the
    kernel runs per shard over the KV heads (a bare pallas_call cannot be
    partitioned), so each chip holds about a quarter of the step."""
    from repro.distributed.sharding import cache_specs, param_specs
    _kernel_route(monkeypatch)
    cfg = get_config("mistral-7b", stack=dense_stack(2, window=4096))
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(1, 4),
                             ("data", "model"))
    pshape, tok, cshape, rp, rl, tbl = _fused_args(cfg, 64, 1)
    with jax.set_mesh(mesh):
        named = lambda tree, specs: jax.tree.map(  # noqa: E731
            lambda x, sp: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, sp)),
            tree, specs)
        rep = NamedSharding(mesh, P())
        args = [named(pshape, param_specs(pshape)), _on(tok, rep),
                named(cshape, cache_specs(cshape)), _on(rp, rep),
                _on(rl, rep), _on(tbl, rep)]
        compiled = _fused(cfg).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    whole = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((pshape, cshape)))
    assert 0 < _device_bytes(compiled) < whole / 2
