"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The dry-run compiles against 512 placeholder host devices, so this
module pins JAX to the CPU at import (``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count=512``), and so does every
per-cell child that ``--all`` starts: none of them ever opens an
accelerator that another process holds.

For each cell:
  * build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * eval_shape the model init -> parameter ShapeDtypeStructs (+ logical
    axes -> PartitionSpecs via the plan),
  * lower the hot-path step for the shape kind:
      - train:   inner train step (fwd+bwd+AdamW)    [+ DiLoCo sync step]
      - prefill: prefill (full prompt -> cache)
      - decode:  one serve_step token against a seq_len cache
  * ``.lower().compile()`` and record memory_analysis / cost_analysis /
    per-collective wire bytes -> JSON under experiments/dryrun/.

Run a single cell:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-130m \
        --shape train_4k --mesh single
Run everything (spawns one subprocess per cell for memory isolation):
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import pathlib
import subprocess
import sys
import time

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" \
    / "dryrun"


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             sync_too: bool = True, quant: str = "int8",
             out_dir: pathlib.Path = OUT_DIR) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.analysis import roofline
    from repro.configs import SHAPES, get_config
    from repro.core.diloco import DiLoCoConfig
    from repro.launch.mesh import make_production_mesh
    from repro.models import common
    from repro.models.registry import get_model
    from repro.optim.adamw import AdamW
    from repro.sharding import make_plan, partition
    from repro.train import step as step_lib
    from repro.train.state import TrainState
    from repro.optim.adamw import AdamWState

    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not cfg.supports(shape):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "skipped": "no sub-quadratic path for 500k dense attn"}
        out = out_dir / mesh_kind / arch
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{shape_name}.json").write_text(
            json.dumps(result, indent=1))
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axes_map = step_lib.mesh_axes(mesh)
    n_chips = int(mesh.devices.size)
    plan = make_plan(cfg, shape, axes_map)
    model = get_model(cfg)
    pshapes, paxes = common.eval_axes(model.init, jax.random.PRNGKey(0))
    pspecs = partition.param_pspecs(paxes, pshapes, plan, axes_map)

    def named(tree):
        return partition.to_named(tree, mesh)

    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "plan": {"diloco_axis": plan.diloco_axis,
                       "n_workers": plan.n_workers,
                       "batch_axes": plan.batch_axes,
                       "remat": plan.remat,
                       "seq_axis": plan.seq_axis},
              "n_chips": n_chips}

    def record(tag, lowered, model_flops):
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        rl = roofline.analyze(compiled, n_chips=n_chips,
                              model_flops=model_flops, hlo=hlo)
        from repro.analysis.hlo_cost import analyze_hlo
        coll_by_kind = analyze_hlo(hlo).collective_bytes
        xla_ca = compiled.cost_analysis() or {}
        mem = {}
        if ma is not None:
            mem = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_device_bytes": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
            }
        result[tag] = {"roofline": rl.as_dict(),
                       "collectives": coll_by_kind,
                       "memory": mem,
                       "xla_cost": {
                           "flops_1iter": float(
                               xla_ca.get("flops", 0.0)),
                           "bytes_1iter": float(
                               xla_ca.get("bytes accessed", 0.0))}}
        print(f"[{arch}/{shape_name}/{mesh_kind}/{tag}] "
              f"flops/dev={rl.flops:.3e} hbm/dev={rl.hbm_bytes:.3e} "
              f"wire/dev={rl.wire_bytes:.3e} bottleneck={rl.bottleneck} "
              f"mfu_bound={rl.mfu:.3f} "
              f"peakmem={mem.get('peak_device_bytes', 0)/2**30:.2f}GiB",
              flush=True)

    with mesh:
        if shape.kind == "train":
            train_step, state_specs = step_lib.build_train_step(
                model, plan, mesh, AdamW(lr=7.5e-5))
            k = plan.n_workers
            stack = (lambda t: jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((k,) + s.shape, s.dtype),
                t)) if plan.diloco_axis else (lambda t: t)
            params_s = stack(pshapes)
            f32 = lambda t: jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), t)
            opt_s = AdamWState(
                jax.ShapeDtypeStruct((k,) if plan.diloco_axis else (),
                                     jnp.int32),
                f32(params_s), f32(params_s))
            state_s = TrainState(params_s, opt_s)
            ispecs = model.input_specs(shape)
            bsp = step_lib.batch_pspecs(model, shape, plan, mesh,
                                        stacked=True)
            if plan.diloco_axis:
                per_w = {kk: jax.ShapeDtypeStruct(
                    (k, v.shape[0] // k) + v.shape[1:], v.dtype)
                    for kk, v in ispecs.items()}
            else:
                per_w = ispecs
            lowered = jax.jit(
                train_step,
                in_shardings=(named(state_specs), named(bsp)),
                out_shardings=(named(state_specs), None),
                donate_argnums=0,
            ).lower(state_s, per_w)
            record("train_step", lowered,
                   roofline.model_flops_for(cfg, shape))

            if sync_too:
                dcfg = DiLoCoConfig(quant=quant, quant_impl="jnp")
                sync, outer_specs = step_lib.build_outer_sync(
                    model, plan, mesh, dcfg)
                anchor_s = f32(pshapes)
                from repro.optim.nesterov import NesterovState
                from repro.core.diloco import OuterState
                outer_s = OuterState(
                    anchor_s, NesterovState(f32(pshapes)),
                    jax.ShapeDtypeStruct(
                        (k, 0) if plan.diloco_axis else (0,),
                        jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.int32))
                if outer_specs.anchor_flat is not None:
                    # every DiLoCo plan threads the persistent flat
                    # fp32 anchor through the sync step: replicated
                    # plans the full flatten, sharded plans the
                    # per-shard concat view (device-major, opaque)
                    nflat = step_lib.flat_anchor_len(model, plan,
                                                     mesh)
                    outer_s = outer_s._replace(
                        anchor_flat=jax.ShapeDtypeStruct(
                            (nflat,), jnp.float32))
                w_s = jax.ShapeDtypeStruct((k,), jnp.float32)
                wspec = NamedSharding(
                    mesh, P(plan.diloco_axis) if plan.diloco_axis
                    else P())
                lowered2 = jax.jit(
                    sync,
                    in_shardings=(named(partition.with_leading(
                        pspecs, plan.diloco_axis)),
                        named(outer_specs), wspec),
                    donate_argnums=(0, 1),
                ).lower(params_s, outer_s, w_s)
                # sync moves 1 byte/param int8 over the ring; "useful
                # flops" isn't meaningful here -> use param count
                record("sync_step", lowered2,
                       float(cfg.param_count()))
        else:
            kind = "prefill" if shape.kind == "prefill" else "decode"
            fn, _ = step_lib.build_serve_step(model, plan, mesh, kind)
            cache_s = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape))
            cache_specs = model.cache_pspecs(cache_s, plan, axes_map)
            ispecs = model.input_specs(shape)
            bsp = step_lib.batch_pspecs(model, shape, plan, mesh,
                                        stacked=False)
            if kind == "prefill":
                lowered = jax.jit(
                    fn, in_shardings=(named(pspecs), named(bsp),
                                      named(cache_specs)),
                    out_shardings=(None, named(cache_specs)),
                    donate_argnums=2,
                ).lower(pshapes, ispecs, cache_s)
            else:
                # decode: cache length reflects seq_len tokens present
                lowered = jax.jit(
                    fn, in_shardings=(named(pspecs),
                                      named(bsp["token"]),
                                      named(cache_specs)),
                    out_shardings=(None, named(cache_specs)),
                    donate_argnums=2,
                ).lower(pshapes, ispecs["token"], cache_s)
            record("serve_step", lowered,
                   roofline.model_flops_for(cfg, shape))

    result["elapsed_s"] = round(time.time() - t0, 1)
    out = out_dir / mesh_kind / arch
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{shape_name}.json").write_text(json.dumps(result, indent=1))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-sync", action="store_true")
    ap.add_argument("--quant", default="int8")
    args = ap.parse_args()

    if not args.all:
        res = run_cell(args.arch, args.shape, args.mesh,
                       sync_too=not args.no_sync, quant=args.quant)
        print(json.dumps(
            {k: v for k, v in res.items() if k != "plan"} | {
                "plan": res.get("plan")}, default=str)[:2000])
        return

    from repro.configs import ASSIGNED, SHAPES
    failures = []
    for mesh_kind in ("single", "multi"):
        for arch in ASSIGNED:
            for shape_name in SHAPES:
                tgt = OUT_DIR / mesh_kind / arch / f"{shape_name}.json"
                if args.skip_existing and tgt.exists():
                    print(f"skip {tgt}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--mesh", mesh_kind]
                if args.no_sync:
                    cmd.append("--no-sync")
                print(">>", " ".join(cmd), flush=True)
                p = subprocess.run(
                    cmd, timeout=3600,
                    env={**os.environ, "JAX_PLATFORMS": "cpu"})
                if p.returncode != 0:
                    failures.append((mesh_kind, arch, shape_name))
    print("FAILURES:", failures)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
