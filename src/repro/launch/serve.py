"""Serving launcher (DESIGN.md §11).

Two paths behind one CLI:

* dense (default): static-batch greedy ``generate`` with batched prefill —
  ``--mesh DxM`` serves over the local device set with the ``repro.dist``
  layout (requests sharded over the data axis, KV heads over the model
  axis);
* engine (``--engine``, or implied by ``--replicas > 1``): the
  continuous-batching paged ``ServeEngine`` — ``--replicas k`` decodes
  with k model replicas aggregated per step by ``--robust-rule`` (any
  registered rule), ``--corrupt n`` replaces n replicas with garbage
  parameters to demonstrate the defense, and ``--telemetry`` streams the
  per-replica suspicion scores / reputation / ejection mask alongside the
  engine's queue-depth records (shared ``repro.defense.telemetry`` JSONL).

  python -m repro.launch.serve --arch granite-8b-reduced --batch 4 \
      --prompt-len 8 --new-tokens 16
  python -m repro.launch.serve --arch granite-8b-reduced --engine \
      --replicas 3 --robust-rule phocas --corrupt 1 --max-batch 8 \
      --telemetry results/serve.jsonl
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import generate


def _obs_config(args):
    """ObsConfig from the --metrics/--profile-dir flags, or None."""
    if not (args.metrics or args.profile_dir):
        return None
    from repro.obs import ObsConfig
    return ObsConfig(enabled=bool(args.metrics), trace=True,
                     metrics_path=args.metrics or None,
                     profile_dir=args.profile_dir or None)


def _run_dense(args, model, params, key):
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 model.cfg.vocab_size)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh(data=d, model=m)
    from repro.obs.profile import profile_trace
    t0 = time.time()
    with profile_trace(args.profile_dir or None):
        out = generate(model, params, prompts, args.new_tokens, mesh=mesh)
        jax.block_until_ready(out)
    dt = time.time() - t0
    tok_s = args.batch * args.new_tokens / dt
    print(f"[serve] {args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({tok_s:.1f} tok/s)")
    if args.telemetry or args.metrics:
        from repro.obs import make_recorder
        with make_recorder(args.telemetry or None, _obs_config(args)) as rec:
            rec.log("serve", 0, arch=args.arch, batch=args.batch,
                    prompt_len=args.prompt_len,
                    new_tokens=args.new_tokens, wall_s=dt, tok_s=tok_s,
                    mesh=args.mesh or "none")
            rec.gauge("serve_tokens_per_sec", tok_s)
        if args.metrics:
            print(f"[serve] wrote metrics snapshot {args.metrics}")
    print(out[:, args.prompt_len:])


def _run_engine(args, model, params, key):
    import numpy as np
    from repro.obs import make_recorder
    from repro.serve import (RobustDecoder, ServeEngine, corrupt_replica,
                             make_replicas)

    decoder = None
    if args.replicas > 1:
        params = make_replicas(params, args.replicas)
        for i in range(args.corrupt):
            params = corrupt_replica(params, args.replicas - 1 - i,
                                     jax.random.fold_in(key, 1000 + i))
        decoder = RobustDecoder(rule=args.robust_rule, k=args.replicas)
    elif args.corrupt:
        raise SystemExit("--corrupt needs --replicas > 1")

    max_seq_len = args.prompt_len + args.new_tokens
    rng = np.random.default_rng(args.seed)
    with make_recorder(args.telemetry or None, _obs_config(args)) as rec:
        engine = ServeEngine(model, params, max_slots=args.max_batch,
                             max_seq_len=max_seq_len, decoder=decoder,
                             telemetry=rec)
        for _ in range(args.batch):
            engine.submit(
                rng.integers(0, model.cfg.vocab_size,
                             (args.prompt_len,)).tolist(),
                args.new_tokens)
        t0 = time.time()
        from repro.obs.profile import profile_trace
        with profile_trace(args.profile_dir or None):
            done = engine.run()
        dt = time.time() - t0
        rec.gauge("serve_tokens_per_sec",
                  sum(len(r.generated) for r in done) / max(dt, 1e-9))
    if args.metrics:
        print(f"[serve] wrote metrics snapshot {args.metrics}")
    toks = sum(len(r.generated) for r in done)
    lat = sorted(r.latency_ms() for r in done)
    mode = (f"robust k={args.replicas} {args.robust_rule}"
            if decoder is not None else "single")
    print(f"[serve] {args.arch} engine ({mode}): {len(done)} requests, "
          f"{toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s, "
          f"p50 latency {lat[len(lat) // 2]:.0f}ms, "
          f"{engine.steps_run} engine steps)")
    if decoder is not None:
        print(f"[serve] replica reputation: "
              f"{np.asarray(decoder.rep_state['reputation']).round(3)} "
              f"ejected: {decoder.ejected_replicas()}")
    for r in done[: min(4, len(done))]:
        print(f"  rid={r.rid} -> {r.generated}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="request count (dense: static batch)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="data×model, e.g. 4x2; empty = single device "
                         "(dense path only)")
    ap.add_argument("--engine", action="store_true",
                    help="use the continuous-batching paged ServeEngine")
    ap.add_argument("--replicas", type=int, default=1,
                    help="k model replicas per decode step (> 1 implies "
                         "--engine and robust aggregation)")
    ap.add_argument("--robust-rule", default="phocas",
                    help="aggregation rule for replicated decode (any "
                         "registered rule)")
    ap.add_argument("--corrupt", type=int, default=0,
                    help="corrupt this many replicas with garbage params")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="engine slot count (concurrent requests)")
    ap.add_argument("--telemetry", default="",
                    help="JSONL path for serve + robust-decode score "
                         "telemetry (shared repro.defense.telemetry "
                         "format)")
    ap.add_argument("--metrics", default="",
                    help="arm the obs layer: write a Prometheus-style "
                         "exposition snapshot to this path at run end "
                         "(implies span tracing; see repro.obs)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the run into "
                         "this directory (view with TensorBoard)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    if args.engine or args.replicas > 1:
        _run_engine(args, model, params, key)
    else:
        _run_dense(args, model, params, key)


if __name__ == "__main__":
    main()
