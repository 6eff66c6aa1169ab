"""Launcher-level tests: Trainer loop, train/serve CLIs, HLO collective
accounting on a real multi-device program."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def test_trainer_loop_end_to_end(tmp_path):
    from repro.configs import get_arch
    from repro.core import AttackConfig, RobustConfig
    from repro.data import TokenStream
    from repro.models import build_model
    from repro.optim import OptConfig
    from repro.train import Trainer, TrainerConfig

    cfg = get_arch("granite-8b-reduced")
    model = build_model(cfg)
    ds = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    ckpt = str(tmp_path / "ck")
    tcfg = TrainerConfig(num_workers=4, steps=12, log_every=4,
                         checkpoint_path=ckpt, checkpoint_every=10)
    rob = RobustConfig(rule="trmean", b=1,
                       attack=AttackConfig(name="zero", num_byzantine=1))
    trainer = Trainer(model, ds.batch, tcfg, rob, OptConfig(lr=0.3))
    hist = trainer.run(verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert os.path.exists(ckpt + ".npz")        # checkpoint written
    # restore round-trips
    from repro.checkpoint import load_checkpoint
    restored, step = load_checkpoint(
        ckpt, {"params": trainer.params, "opt": trainer.opt_state})
    assert step == 10


@pytest.mark.slow
def test_train_cli(tmp_path):
    env = dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "gemma2-2b-reduced", "--steps", "6", "--global-batch", "8",
         "--seq-len", "16", "--workers", "4", "--rule", "phocas", "--b",
         "1", "--attack", "gaussian", "--q", "1"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[train] done" in out.stdout


@pytest.mark.slow
def test_serve_cli(tmp_path):
    env = dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "granite-8b-reduced", "--batch", "2", "--prompt-len", "4",
         "--new-tokens", "4"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tok/s" in out.stdout


def test_compile_cache_dir(monkeypatch):
    """The CLIs' compile cache: JAX_COMPILATION_CACHE_DIR wins; otherwise
    the fixed, git-ignored .jax_cache/ at the checkout root."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_hlo_collectives_accounting_multidevice():
    """The analyzer's collective bytes match hand-computed values for a
    known 8-device psum program."""
    code = r"""
import jax, jax.numpy as jnp, json
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo
mesh = jax.make_mesh((8,), ('d',), axis_types=(jax.sharding.AxisType.Auto,))
@partial(jax.shard_map, mesh=mesh, in_specs=P('d'), out_specs=P())
def f(x):
    return jax.lax.psum(x, 'd')
x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
hlo = jax.jit(f).lower(x).compile().as_text()
t = analyze_hlo(hlo)
print(json.dumps({'ar': t['collective_bytes']['all-reduce'],
                  'total': t['collective_total_bytes']}))
"""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # psum of a (1, 1024) f32 shard -> all-reduce output 4096 B per device
    assert res["ar"] == 4096, res
