#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip, in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--fault-seeds 1,2,3] [--seconds 10]

For each seed of ``--seeds`` the program's numbers against the reference
(training: its first steps, no window needed; serving: a window of
``--seconds`` at the cell's load).  For each control seed the control's
numbers: the reference in float8 put in the program's place (serving: the
token the float8 reference puts first at each position of the served
sample).  Training fault seeds: the reference with half of each worker's
batch left out.  One JSON line per reading on standard output.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()

    import gc

    import jax
    import numpy as np

    from bench import gen, harness
    from bench.drivers import serve, train

    def make(seed):
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        h = harness.Harness(args, time.perf_counter())
        h.start()
        return h

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    h = make(0)
    cfg, job = h.config, h.traffic
    if job["driver"] == "train":
        h.seconds = 0.0

        def batches(seed):
            return gen.token_pool(
                seed, batch=job["workers"] * job["seqs_per_worker"],
                seq_len=job["seq_len"], pool=job["pool"],
                modes=job["modes"], active_vocab=job["active_vocab"]
            )[:job["checked_steps"]]

        for s in a.seeds:
            h = make(s)
            h.seconds = 0.0
            feed, prog, _, _ = train.run_program(h, cfg, job)
            del feed
            gc.collect()
            ref = train.reference_readings(cfg, job, s, batches(s))
            emit(kind="program", seed=s, **train.compare(prog, ref),
                 losses=prog[0], ref_losses=ref[0])
        for s in sorted(set(a.control_seeds) | set(a.fault_seeds)):
            b = batches(s)
            ref = train.reference_readings(cfg, job, s, b)
            if s in a.control_seeds:
                ctl = train.reference_readings(cfg, job, s, b, "fp8")
                emit(kind="control", seed=s, **train.compare(ctl, ref))
            if s in a.fault_seeds:
                half = train.reference_readings(cfg, job, s, b,
                                                half_batch=True)
                emit(kind="half_batch", seed=s, **train.compare(half, ref))
        return
    key = gen.seed_key
    for s in a.seeds:
        h = make(s)
        model = serve.common.program_model(cfg)
        honest, bad = serve.serve_weights(jax.random.fold_in(key(s), 1),
                                          cfg, job["corrupt_scale"])
        engine = serve.make_engine(model, honest, bad, job)
        del bad
        serve.warm_up(engine, job, cfg["vocab_size"])
        sched = gen.request_schedule(job, s, cfg["vocab_size"], a.seconds)
        tr, _, _ = serve.drive(h, engine, sched)
        del engine
        gc.collect()
        picked = serve.sample(tr, np.random.default_rng(s),
                              job["check_tokens"], job["check_requests"])
        emit(kind="program", seed=s, logit_gap=max(serve.logit_gaps(
            honest, cfg, picked, job["max_seq_len"])),
             requests=len(picked), tokens=sum(len(x) for _, x in picked))
        if s in a.control_seeds:
            emit(kind="control", seed=s, logit_gap=max(serve.logit_gaps(
                honest, cfg, picked, job["max_seq_len"], "fp8")))
        del honest, tr, picked
        gc.collect()


if __name__ == "__main__":
    main()
