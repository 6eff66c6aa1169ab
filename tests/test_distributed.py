"""Multi-device tests: spawned subprocesses with fake host devices (the main
pytest process keeps the default 1 device)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


DIST_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import RobustConfig, robust_aggregate_dist, aggregate_matrix
from jax.flatten_util import ravel_pytree

mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
key = jax.random.PRNGKey(1)
base = 2.0 + 0.1*jax.random.normal(key, (4, 67))
base = base.at[3].set(50.0)
grads = {'w': base[:, :64], 'b': base[:, 64:]}
mat = np.stack([ravel_pytree(jax.tree.map(lambda x: x[i], grads))[0]
                for i in range(4)])
results = {}
for rule in ['trmean','phocas','mean','median','krum','multikrum','geomedian']:
    ref = aggregate_matrix(jnp.asarray(mat), RobustConfig(rule=rule, b=1, q=1))
    for layout in ['replicated','sharded']:
        cfg = RobustConfig(rule=rule, b=1, q=1, layout=layout)
        @partial(jax.shard_map, mesh=mesh, in_specs=(P('data'),),
                 out_specs=P(), check_vma=False)
        def f(g):
            local = jax.tree.map(lambda x: x[0], g)
            return robust_aggregate_dist(local, cfg, worker_axes=('data',),
                                         model_axes=('model',))
        flat = ravel_pytree(f(grads))[0]
        results[f'{rule}/{layout}'] = bool(
            np.allclose(np.asarray(flat), np.asarray(ref), atol=1e-4))
print(json.dumps(results))
"""


def test_distributed_aggregation_equivalence():
    """Both collective layouts reproduce the single-host oracle for every
    rule (incl. Krum's psum'd distances and distributed Weiszfeld)."""
    out = run_sub(DIST_EQUIV)
    results = json.loads(out.strip().splitlines()[-1])
    bad = [k for k, v in results.items() if not v]
    assert not bad, bad


DIST_TRAIN = r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_arch
from repro.models import build_model
from repro.core import RobustConfig, AttackConfig
from repro.optim import OptConfig, init_opt_state
from repro.data import TokenStream, make_worker_batches
from repro.train import make_train_step, step as step_mod
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=4, model=2)
cfg = get_arch('granite-8b-reduced')
model = build_model(cfg)
key = jax.random.PRNGKey(0)
params = model.init(key)
params = step_mod.shard_params(params, mesh) if hasattr(step_mod, 'shard_params') else params
opt_cfg = OptConfig(name='sgd', lr=0.2)
rob = RobustConfig(rule='phocas', b=1, layout='sharded',
                   attack=AttackConfig(name='gaussian', num_byzantine=1))
step = make_train_step(model, robust_cfg=rob, opt_cfg=opt_cfg,
                       num_workers=4, mesh=mesh, donate=False)
opt_state = init_opt_state(opt_cfg, params)
ds = TokenStream(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8, seed=0)
losses = []
for i in range(8):
    batch = make_worker_batches(ds.batch(i), 4)
    params, opt_state, mt = step(params, opt_state, batch,
                                 jax.random.fold_in(key, i))
    losses.append(float(mt['loss']))
print(json.dumps({'first': losses[0], 'last': losses[-1],
                  'finite': all(np.isfinite(losses))}))
"""


def test_distributed_train_step_on_mesh():
    """Full train step on a 4×2 (data, model) mesh with attack injection:
    loss finite and decreasing."""
    out = run_sub(DIST_TRAIN.replace("from repro.train import make_train_step, step as step_mod",
                                     "from repro.train import make_train_step\nfrom repro.train import step as step_mod"))
    res = json.loads(out.strip().splitlines()[-1])
    assert res["finite"]
    assert res["last"] < res["first"], res


SYNC_PS_ONE_COMPILE = r"""
import jax, json
from repro.core import AttackConfig, RobustConfig
from repro.defense import DefenseConfig
from repro.experiment import DataSpec, ModelSpec, ScenarioSpec, run_experiment
from repro.train import step as step_mod

made = []
def recording(*a, **kw):
    made.append(real(*a, **kw))
    return made[-1]
real, step_mod.make_train_step = step_mod.make_train_step, recording
spec = ScenarioSpec(
    name='mesh-one-compile', topology='sync_ps',
    model=ModelSpec(kind='arch', arch='granite-8b-reduced'),
    data=DataSpec(kind='tokens', seq_len=16, batch_per_worker=1),
    robust=RobustConfig(rule='phocas', b=1, layout='sharded'),
    attack=AttackConfig(name='gaussian', num_byzantine=1),
    defense=DefenseConfig(), num_workers=4, steps=3, mesh='4x1')
run_experiment(spec)
print(json.dumps([f._cache_size() for f in made]))
"""


def test_sync_ps_mesh_step_compiles_once():
    """The mesh loop feeds its first step the optimizer and defense state
    laid out as the step returns them, so every step reuses one program."""
    out = run_sub(SYNC_PS_ONE_COMPILE, devices=4)
    assert json.loads(out.strip().splitlines()[-1]) == [1]


MULTIPOD = r"""
import os
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import RobustConfig, aggregate_matrix, robust_aggregate_dist
from jax.flatten_util import ravel_pytree

mesh = jax.make_mesh((2, 4, 2), ('pod', 'data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
key = jax.random.PRNGKey(1)
m = 8
mat_tree = {'w': jax.random.normal(key, (m, 48)),
            'b': jnp.arange(m*4, dtype=jnp.float32).reshape(m, 4)}
mat = np.stack([ravel_pytree(jax.tree.map(lambda x: x[i], mat_tree))[0]
                for i in range(m)])
ok = {}
for layout in ['replicated', 'sharded']:
    cfg = RobustConfig(rule='trmean', b=2, layout=layout)
    ref = aggregate_matrix(jnp.asarray(mat), cfg)
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(('pod','data')),),
             out_specs=P(), check_vma=False)
    def f(g):
        local = jax.tree.map(lambda x: x[0], g)
        return robust_aggregate_dist(local, cfg,
                                     worker_axes=('pod', 'data'),
                                     model_axes=('model',))
    flat = ravel_pytree(f(mat_tree))[0]
    ok[layout] = bool(np.allclose(np.asarray(flat), np.asarray(ref), atol=1e-4))
print(json.dumps(ok))
"""


def test_multipod_worker_axes():
    """Robust aggregation over the joint (pod, data) worker axes — proves the
    pod axis participates in both layouts (incl. the 2-stage all_to_all)."""
    out = run_sub(MULTIPOD, devices=16)
    res = json.loads(out.strip().splitlines()[-1])
    assert res == {"replicated": True, "sharded": True}, res


@pytest.mark.slow
def test_dryrun_one_pair_compiles():
    """The dry-run entry point itself (512 fake devices, production mesh)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma2-2b",
         "--shape", "decode_32k", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
