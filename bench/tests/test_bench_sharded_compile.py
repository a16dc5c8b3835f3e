"""The fused step sharded over a (data, model) = (1, 4) mesh, compiled for
a described TPU v5e:2x2 without a chip, keeps the names the benchmark's
trace patterns match: a device trace names programs and operations after
the compiled HLO, and inside the step's ``shard_map`` the paged kernel's
call takes another name than on one chip.

The topology is described inside a fixture, never while the module is
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

CONFIG = "mistral-7b-8L-nbl3"


@pytest.fixture(scope="module")
def sharded_step_hlo():
    """Compiled HLO of the engine's fused step of the 8-layer Mistral
    configuration at its engine's slots and length, with parameters and
    cache laid out by the program's specs over model = 4 and the kernel
    route on."""
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
                _fused, donate_argnums=(2,)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_sharded_step_keeps_the_module_name(sharded_step_hlo):
    module = re.search(r"^HloModule ([^,\s]+)", sharded_step_hlo,
                       re.M).group(1)
    # a trace names a program "<module>(<program id>)"
    assert re.search(names.STEP_MODULE, f"{module}(0)"), module


def test_sharded_step_kernel_matches_the_kernel_pattern(sharded_step_hlo):
    """Every Mosaic call of the sharded step is the paged kernel, named
    ``%shard_map.N`` inside the ``shard_map``, and the pattern takes it."""
    kernels = [ln.strip() for ln in sharded_step_hlo.splitlines()
               if "tpu_custom_call" in ln]
    assert kernels, "no Mosaic call in the sharded step"
    assert all(re.search(names.PAGED_KERNEL, k) for k in kernels), \
        [k[:40] for k in kernels]
    assert any(k.startswith("%shard_map.") for k in kernels), \
        [k[:40] for k in kernels]
