"""Multi-device semantics (subprocesses with forced host-platform devices;
the main pytest process keeps the real 1-CPU view)."""
from __future__ import annotations

import pytest


def test_sharded_train_step_matches_single_device(subproc):
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.train import make_train_step, init_state
from repro.data import ShardedLoader
from repro.optim import get_schedule

cfg = get_config('tiny-dense')
sched = get_schedule('cosine', 1e-3, 5, 50)
loader = ShardedLoader(cfg.vocab_size, 8, 32, seed=4)

# single device reference
_, sf, _, _ = make_train_step(cfg, schedule=sched, zero1=False)
params, opt = init_state(cfg, 0, zero1=False)
step = sf(jax.eval_shape(lambda: jax.tree.map(jnp.asarray, loader.batch(0))))
losses_1 = []
for i in range(3):
    params, opt, m = step(params, opt, loader.batch(i), i)
    losses_1.append(float(m['loss']))

# 8-device (2 data x 4 model) mesh
mesh = make_mesh((2, 4), ('data', 'model'))
with jax.set_mesh(mesh):
    _, sf, _, _ = make_train_step(cfg, schedule=sched, zero1=True)
    params, opt = init_state(cfg, 0)
    step = sf(jax.eval_shape(lambda: jax.tree.map(jnp.asarray, loader.batch(0))))
    losses_8 = []
    for i in range(3):
        params, opt, m = step(params, opt, loader.batch(i), i)
        losses_8.append(float(m['loss']))

np.testing.assert_allclose(losses_1, losses_8, rtol=2e-4, atol=2e-4)
print('OK', losses_1, losses_8)
""", n_devices=8)


def test_pipeline_parallel_exact(subproc):
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ('pipe',))
L, B, D = 8, 8, 16
key = jax.random.PRNGKey(0)
params = {'w': jax.random.normal(key, (L, D, D)) * 0.2,
          'b': jax.random.normal(key, (L, D)) * 0.1}
def block(p, x):
    return jnp.tanh(x @ p['w'] + p['b'])
x = jax.random.normal(key, (B, D))
def ref(params, x):
    def body(c, p):
        return block(p, c), None
    return jax.lax.scan(body, x, params)[0]
want = ref(params, x)
for n_micro in (2, 4, 8):
    got = pipeline_apply(block, params, x, mesh=mesh, n_micro=n_micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
print('OK')
""", n_devices=4)


def test_compressed_psum_close_to_exact(subproc):
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim import compressed_psum, ef_init

mesh = make_mesh((4,), ('data',))
key = jax.random.PRNGKey(0)
g = jax.random.normal(key, (4, 64))          # per-shard gradients

def fn(g_local, err):
    mean, new_err = compressed_psum({'g': g_local}, {'g': err}, ('data',))
    return mean['g'], new_err['g']

sharded = jax.shard_map(fn, mesh=mesh, in_specs=(P('data'), P('data')),
                        out_specs=(P(), P('data')), check_vma=False)
got, err = sharded(g.reshape(4, 64), jnp.zeros((4, 64)))
want = g.mean(0)
err_inf = float(jnp.abs(got[0] - want).max())
scale = float(jnp.abs(g).max()) / 127.0
assert err_inf <= scale + 1e-6, (err_inf, scale)
print('OK', err_inf, scale)
""", n_devices=4)


def test_ef_compressed_training_converges(subproc):
    """EF-int8 DP training converges on a toy problem (within noise of
    exact all-reduce)."""
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim import compressed_psum

mesh = make_mesh((4,), ('data',))
key = jax.random.PRNGKey(0)
X = jax.random.normal(key, (64, 16))
true_w = jax.random.normal(jax.random.PRNGKey(1), (16,))
y = X @ true_w

def local_grad(w, Xl, yl):
    return jax.grad(lambda w: jnp.mean((Xl @ w - yl) ** 2))(w)

def train(compressed):
    w = jnp.zeros(16)
    err = jnp.zeros((4, 16))
    for i in range(150):
        def step(Xl, yl, errl):
            g = local_grad(w, Xl, yl)
            if compressed:
                m, ne = compressed_psum({'g': g}, {'g': errl}, ('data',))
                return m['g'], ne['g']
            return jax.lax.pmean(g, 'data'), errl
        sm = jax.shard_map(step, mesh=mesh,
                           in_specs=(P('data'), P('data'), P('data')),
                           out_specs=(P(), P('data')), check_vma=False)
        g, err = sm(X, y, err)
        w = w - 0.1 * g[0] if g.ndim > 1 else w - 0.1 * g
    return float(jnp.mean((X @ w - y) ** 2))

exact = train(False)
comp = train(True)
assert comp < 1e-2, (exact, comp)
print('OK', exact, comp)
""", n_devices=4)


def test_moments_match_under_data_parallel(subproc):
    """Calibration moments accumulated from sharded activations equal the
    host computation (the psum-merge property, via XLA auto-reduction)."""
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.moments import init_moments, update_moments, finalize

mesh = make_mesh((4,), ('data',))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (64, 16))
y = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
host = finalize(update_moments(init_moments(16, 16), x, y))
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P('data')))
    ys = jax.device_put(y, NamedSharding(mesh, P('data')))
    mom = jax.jit(lambda a, b: update_moments(init_moments(16, 16), a, b))(xs, ys)
    dist = finalize(jax.device_get(mom))
for k in ('cxx', 'cyx', 'cypyp'):
    np.testing.assert_allclose(host[k], dist[k], rtol=1e-4, atol=1e-4)
print('OK')
""", n_devices=4)


def test_elastic_checkpoint_reshard(subproc):
    """Save params on a (2,4) mesh, restore onto (4,2) and (1,) — elastic."""
    subproc("""
import warnings; warnings.filterwarnings('ignore')
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.launch.mesh import make_mesh
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.distributed.sharding import param_specs, named
from repro.models import init_params

cfg = get_config('tiny-dense')
params = init_params(jax.random.PRNGKey(0), cfg)
with tempfile.TemporaryDirectory() as d:
    m1 = make_mesh((2, 4), ('data', 'model'))
    with jax.set_mesh(m1):
        sh = named(param_specs(params), m1)
        p1 = jax.tree.map(jax.device_put, params, sh)
        mgr = CheckpointManager(d)
        mgr.save(1, p1)
    m2 = make_mesh((4, 2), ('data', 'model'))
    with jax.set_mesh(m2):
        sh2 = named(param_specs(params), m2)
        flatsh = {}
        paths = jax.tree_util.tree_flatten_with_path(sh2)[0]
        for path, s in paths:
            key = '/'.join(str(getattr(p, 'key', getattr(p, 'idx', p))) for p in path)
            flatsh[key] = s
        step, p2 = mgr.restore_latest(params, sharding_fn=lambda k, l: flatsh[k])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
""", n_devices=8)
