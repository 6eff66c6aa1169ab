"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
against the production mesh and extract roofline inputs.

MUST set the fake-device flag before ANY jax import (jax locks the device
count on first init) — hence the first two lines.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--layout sharded] ...
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_arch, get_shape, list_archs
from repro.core.robust import RobustConfig
from repro.dist.sharding import cache_pspec, tree_pspecs, worker_axes_of
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import OptConfig, init_opt_state
from repro.train.step import make_train_step

def _with_sharding(spec_tree, pspec_tree, mesh):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        spec_tree, pspec_tree)


def _active_params(cfg, params_shapes) -> tuple:
    """(total, active) param counts; active discounts un-routed experts."""
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        name = "/".join(str(getattr(p, "key", "")) for p in path)
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if cfg.is_moe and "moe_w" in name and "shared" not in name:
            active += n * cfg.num_experts_per_tok / cfg.num_experts
        else:
            active += n
    return total, int(active)


def build_lowerable(arch: str, shape_name: str, mesh, *, layout: str,
                    rule: str, b: int, remat: str, mode: str = "vmap"):
    """Returns (fn, arg_specs) ready for jit(...).lower(*arg_specs).

    mode: "vmap" (default — worker groups parallel over the data axis) or
    "streaming" (sequential workers, FSDP params over data+model; the
    O(b)-memory beyond-paper mode for 1T-scale archs)."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg, remat=remat)
    wa = worker_axes_of(mesh)
    m = 1
    for a in wa:
        m *= mesh.shape[a]

    from repro.dist.sharding import param_pspec_fsdp
    leaf_rule = param_pspec_fsdp if mode == "streaming" else None
    params_shapes = jax.eval_shape(
        # eval_shape is abstract: only shapes flow out, no value is drawn
        lambda: model.init(jax.random.PRNGKey(0)))  # repro: noqa[PRNG004]
    pspecs = (tree_pspecs(params_shapes, mesh, leaf_rule=leaf_rule)
              if leaf_rule else tree_pspecs(params_shapes, mesh))
    params_sds = _with_sharding(params_shapes, pspecs, mesh)

    if shape.kind == "train":
        robust = RobustConfig(rule=rule, b=b, q=b, layout=layout)
        opt_cfg = OptConfig(name="sgd", lr=0.01)
        if mode == "streaming":
            from repro.train.streaming import make_streaming_train_step
            step = make_streaming_train_step(
                model, robust_cfg=robust, opt_cfg=opt_cfg, num_workers=m)
        else:
            step = make_train_step(model, robust_cfg=robust, opt_cfg=opt_cfg,
                                   num_workers=m, mesh=mesh)
        opt_shapes = jax.eval_shape(
            lambda p: init_opt_state(opt_cfg, p), params_shapes)
        opt_sds = _with_sharding(
            opt_shapes,
            tree_pspecs(opt_shapes, mesh, leaf_rule=leaf_rule)
            if leaf_rule else tree_pspecs(opt_shapes, mesh), mesh)
        bspecs = model.input_specs(shape)
        batch_sds = {}
        for k, s in bspecs.items():
            B = s.shape[0]
            assert B % m == 0, f"{arch}/{shape_name}: batch {B} % m={m}"
            stacked = jax.ShapeDtypeStruct((m, B // m) + s.shape[1:], s.dtype)
            # streaming: worker axis scanned, per-worker batch data-sharded
            bspec = P(None, "data") if mode == "streaming" else P(wa)
            batch_sds[k] = jax.ShapeDtypeStruct(
                stacked.shape, stacked.dtype,
                sharding=NamedSharding(mesh, bspec))
        key_sds = jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
        fn = step
        args = (params_sds, opt_sds, batch_sds, key_sds)
    elif shape.kind == "prefill":
        def fwd(params, batch):
            logits, _ = model.forward(params, batch)
            return logits
        fn = jax.jit(fwd)
        bspecs = model.input_specs(shape)
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=NamedSharding(
                    mesh, P(wa) if s.shape[0] % m == 0 else P()))
            for k, s in bspecs.items()}
        args = (params_sds, batch_sds)
    else:                                              # decode
        B = shape.global_batch
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(B, shape.seq_len))
        cspecs = jax.tree_util.tree_map_with_path(
            lambda path, leaf: cache_pspec(path, leaf, mesh), cache_shapes)
        cache_sds = _with_sharding(cache_shapes, cspecs, mesh)
        tok_spec = P(wa) if B % m == 0 else P()
        tok_sds = jax.ShapeDtypeStruct(
            (B, 1), jnp.int32, sharding=NamedSharding(mesh, tok_spec))
        pos_sds = jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P()))

        def decode(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)
        fn = jax.jit(decode, donate_argnums=(1,))
        args = (params_sds, cache_sds, tok_sds, pos_sds)

    meta = {"total_params": None, "active_params": None}
    meta["total_params"], meta["active_params"] = _active_params(
        cfg, params_shapes)
    return fn, args, meta


def run_one(arch: str, shape_name: str, *, multi_pod: bool, layout: str,
            rule: str, b: int, remat: str, outdir: str,
            skip_existing: bool = False, mode: str = "vmap") -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}__{layout}__{rule}__{remat}"
    if mode != "vmap":
        tag += f"__{mode}"
    path = os.path.join(outdir, tag + ".json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    os.makedirs(outdir, exist_ok=True)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "layout": layout, "rule": rule, "remat": remat, "mode": mode,
           "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with jax.set_mesh(mesh):       # activates shard_hint constraints
            fn, args, meta = build_lowerable(arch, shape_name, mesh,
                                             layout=layout, rule=rule, b=b,
                                             remat=remat, mode=mode)
            rec.update(meta)
            lowered = fn.lower(*args)
            rec["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1
        ca = compiled.cost_analysis() or {}
        rec["xla_flops_raw"] = float(ca.get("flops", -1.0))
        rec["xla_bytes_raw"] = float(ca.get("bytes accessed", -1.0))
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes"):
                rec[attr] = getattr(ma, attr, None)
        # Loop-aware per-device costs (XLA's cost_analysis counts while
        # bodies once — see hlo_analysis docstring).
        hlo = compiled.as_text()
        an = analyze_hlo(hlo)
        rec["dot_flops"] = an["dot_flops"]
        rec["write_bytes"] = an["write_bytes"]
        rec["collectives"] = {
            "bytes": an["collective_bytes"],
            "counts": an["collective_counts"],
            "total_bytes": an["collective_total_bytes"],
        }
        rec["loops"] = an["loops"][:40]
        rec["num_devices"] = mesh.size
        rec["ok"] = True
    except Exception as e:                             # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[dryrun] {tag}: {status}  ({rec['total_s']:.1f}s)", flush=True)
    return rec


# long_500k skips: pure full-attention archs (DESIGN.md §4)
LONG_SKIP = {"granite-8b", "kimi-k2-1t-a32b", "internvl2-26b",
             "whisper-large-v3", "deepseek-v2-lite-16b"}


def pairs():
    for arch in list_archs():
        for shape in INPUT_SHAPES:
            if shape == "long_500k" and arch in LONG_SKIP:
                continue
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--layout", default="sharded",
                    choices=["replicated", "sharded"])
    from repro.core import registry
    ap.add_argument("--rule", default="phocas",
                    choices=registry.available_rules())
    ap.add_argument("--b", type=int, default=2)
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--mode", default="vmap", choices=["vmap", "streaming"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    todo = list(pairs()) if args.all else [(args.arch, args.shape)]
    n_ok = 0
    for arch, shape in todo:
        rec = run_one(arch, shape, multi_pod=args.multi_pod,
                      layout=args.layout, rule=args.rule, b=args.b,
                      remat=args.remat, outdir=args.out,
                      skip_existing=args.skip_existing, mode=args.mode)
        n_ok += bool(rec.get("ok"))
    print(f"[dryrun] {n_ok}/{len(todo)} OK")
    if n_ok != len(todo):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
