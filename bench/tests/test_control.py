"""The control of ``correct`` at a size the CPU holds: the reference
computed in float8 (scaled per tensor, the step below the bfloat16 the
configurations state) put in the program's place reads well above what the
program reads, on every seed, so a limit set between the two readings
fails it.  On the chip, at the cells' sizes, ``bench/calibrate.py`` reads
the same numbers."""
import jax
import numpy as np
import pytest

from bench import gen
from bench.drivers import serve, train
from bench.tests.small import harness

TRAIN = ["granite-8b-train.phocas-gauss", "granite-8b-train.mean"]


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("seed", [5, 6])
def test_training_control_reads_above_the_program(cell, seed):
    h = harness(cell, seed=seed, seconds=0.0)
    cfg, job = h.config, h.traffic
    _, prog, _, _ = train.run_program(h, cfg, job)
    b = gen.token_pool(seed, batch=job["workers"] * job["seqs_per_worker"],
                       seq_len=job["seq_len"], pool=job["pool"],
                       modes=job["modes"], active_vocab=job["active_vocab"])
    ref = train.reference_readings(cfg, job, seed, b)
    ctl = train.reference_readings(cfg, job, seed, b, "fp8")
    p, c = train.compare(prog, ref), train.compare(ctl, ref)
    assert max(c[k] / p[k] for k in p) >= 3.0, (p, c)


def test_serving_control_reads_above_the_program():
    h = harness("granite-8b-serve.robust-k3-poisson", seed=5, seconds=1.0)
    cfg, mix = h.config, h.traffic
    model = serve.common.program_model(cfg)
    honest, bad = serve.serve_weights(jax.random.fold_in(gen.seed_key(5), 1),
                                      cfg, mix["corrupt_scale"])
    engine = serve.make_engine(model, honest, bad, mix)
    serve.warm_up(engine, mix, cfg["vocab_size"])
    tr, _, _ = serve.drive(h, engine, gen.request_schedule(
        mix, 5, cfg["vocab_size"], 1.0))
    picked = serve.sample(tr, np.random.default_rng(5), 30, 3)
    prog = max(serve.logit_gaps(honest, cfg, picked, mix["max_seq_len"]))
    ctl = max(serve.logit_gaps(honest, cfg, picked, mix["max_seq_len"],
                               "fp8"))
    assert ctl >= 3.0 * prog, (prog, ctl)
