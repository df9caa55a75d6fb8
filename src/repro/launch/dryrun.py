import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init, and the production meshes need 512 placeholder
# devices (2 pods x 16 x 16). Everything else imports below.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell and mesh, lower + compile the
appropriate step (train_step / prefill_step / serve_step) with
ShapeDtypeStruct stand-ins (no allocation), print memory/cost analysis, and
record the roofline terms (deliverable g) to a JSONL file (``--out``;
default ``dryrun_<singlepod|multipod>.jsonl`` in the working directory).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all                 # 34 cells, single-pod
  python -m repro.launch.dryrun --all --multi-pod     # 34 cells, 2 pods
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k \
      --optimizer cholesky_precond                    # paper-technique cell
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.optim as optim
from repro.configs import ARCHS, SHAPES_BY_NAME, cells, get_config
from repro.launch import steps as St
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as RA
from repro.sharding import rules

def _local_bytes(shapes_tree, specs_tree, mesh) -> float:
    """Per-device bytes of a sharded ShapeDtypeStruct tree."""
    from jax.sharding import PartitionSpec as P

    total = 0.0
    flat_shapes = jax.tree.leaves(shapes_tree)
    flat_specs = jax.tree.leaves(specs_tree, is_leaf=lambda x: isinstance(x, P))
    for x, s in zip(flat_shapes, flat_specs):
        denom = 1
        for entry in (s or ()):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                denom *= mesh.shape[a]
        total += x.size * jnp.dtype(x.dtype).itemsize / denom
    return total


def default_optimizer(cfg, name="adamw"):
    state_dtype = jnp.dtype(cfg.opt_state_dtype)
    if name == "adamw":
        return optim.adamw(3e-4, state_dtype=state_dtype)
    if name == "cholesky_precond":
        return optim.cholesky_precond(3e-4, rank=16, block_size=1024)
    if name == "sgd":
        return optim.sgd(3e-4)
    raise ValueError(name)


def lower_cell(arch: str, shape: str, mesh, *, optimizer="adamw", verbose=True,
               unroll_layers=False, config_patch=None, grad_accum=4,
               policy="tp"):
    """Lower + compile one cell. Returns a result record dict.

    ``unroll_layers`` lowers with the layer loop unrolled so cost_analysis
    counts every layer (XLA does not multiply while-loop bodies); the scanned
    variant stays the memory-proof artifact.
    """
    import dataclasses as _dc

    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(arch)
    if unroll_layers:
        cfg = _dc.replace(cfg, scan_layers=False)
    if config_patch:
        cfg = _dc.replace(cfg, **config_patch)
    cell = SHAPES_BY_NAME[shape]
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    t0 = time.time()

    batch_axes = rules.data_axes(mesh)
    if policy == "dp":
        batch_axes = batch_axes + rules.model_axes(mesh)
    rules.set_batch_axes(batch_axes)

    values_shapes, axes = St.param_shapes_and_axes(cfg)
    pspecs, notes = rules.param_specs(axes, values_shapes, mesh, fsdp=cfg.fsdp,
                                      policy=policy)
    psh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    ins = St.input_specs(cfg, cell)

    analysis_text = None
    with mesh:
        if cell.kind == "train":
            opt = default_optimizer(cfg, optimizer)
            opt_shapes = jax.eval_shape(opt.init, values_shapes)
            ospecs = St.opt_state_specs(opt_shapes, pspecs, mesh)
            osh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), ospecs,
                is_leaf=lambda x: isinstance(x, P),
            )
            bspecs = St.batch_specs(ins, mesh, policy=policy)
            bsh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), bspecs,
                is_leaf=lambda x: isinstance(x, P),
            )

            def jit_step(accum):
                step = St.make_train_step(cfg, opt, grad_accum=accum)
                return jax.jit(
                    step,
                    in_shardings=(psh, osh, bsh),
                    out_shardings=(psh, osh, None),
                    donate_argnums=(0, 1),
                ).lower(values_shapes, opt_shapes, ins)

            lowered = jit_step(grad_accum)
            if grad_accum != 1:
                # FLOPs/collective analysis artifact: accumulation-free
                # (identical totals; avoids XLA loop-fission double counts
                # in the text parser).
                analysis_text = jit_step(1).compile().as_text()
        elif cell.kind == "prefill":
            bspecs = St.batch_specs(ins, mesh, policy=policy)
            bsh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), bspecs,
                is_leaf=lambda x: isinstance(x, P),
            )
            step = St.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(psh, bsh))
            lowered = jitted.lower(values_shapes, ins)
        else:  # decode
            csh_specs = rules.cache_specs(ins["cache"], cfg, mesh)
            csh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), csh_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            tspec = St.batch_specs({"tokens": ins["tokens"]}, mesh, policy=policy)["tokens"]
            step = St.make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(psh, csh, NamedSharding(mesh, tspec)),
                out_shardings=(None, csh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(values_shapes, ins["cache"], ins["tokens"])
        compiled = lowered.compile()

    t_compile = time.time() - t0
    p_local = _local_bytes(values_shapes, pspecs, mesh)
    o_local = 0.0
    if cell.kind == "train":
        o_local = _local_bytes(opt_shapes, ospecs, mesh)
    roof = RA.analyze(
        compiled, cfg, cell, n_chips, hlo_text=analysis_text,
        params_local_bytes=p_local, opt_local_bytes=o_local,
    )
    mem = compiled.memory_analysis()
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": dict(mesh.shape),
        "chips": n_chips,
        "optimizer": optimizer if cell.kind == "train" else None,
        "policy": policy,
        "kind": cell.kind,
        "compile_s": round(t_compile, 1),
        "flops_per_device": roof.flops,
        "bytes_per_device": roof.bytes_accessed,
        "collective_bytes_per_device": roof.collective_bytes,
        "collectives": roof.collectives,
        "compute_s": roof.compute_s,
        "memory_s": roof.memory_s,
        "collective_s": roof.collective_s,
        "bottleneck": roof.bottleneck,
        "model_flops": roof.model_flops,
        "useful_ratio": roof.useful_ratio,
        "memory_analysis": roof.per_device_memory,
        "replication_notes": [
            {"axis": a, "dim": d, "mesh_size": s} for a, d, s in notes
        ],
    }
    if verbose:
        print(f"== {arch} x {shape} on {dict(mesh.shape)} "
              f"({cell.kind}, compile {t_compile:.1f}s)")
        print("   memory_analysis:", mem)
        print(f"   cost: flops/dev={roof.flops:.3e} bytes/dev={roof.bytes_accessed:.3e} "
              f"coll/dev={roof.collective_bytes:.3e}")
        print(f"   roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms collective={roof.collective_s*1e3:.2f}ms "
              f"-> {roof.bottleneck}-bound; useful_ratio={roof.useful_ratio:.2f}")
        if notes:
            print(f"   replicated (indivisible): {rec['replication_notes']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimizer", type=str, default="adamw")
    ap.add_argument("--policy", type=str, default="tp", choices=["tp", "dp"])
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "singlepod"
    out_path = Path(args.out) if args.out else Path(f"dryrun_{tag}.jsonl")

    if args.all:
        todo = cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    n_fail = 0
    with open(out_path, "a") as f:
        for arch, shape in todo:
            try:
                rec = lower_cell(arch, shape, mesh, optimizer=args.optimizer,
                                 policy=args.policy)
                f.write(json.dumps(rec) + "\n")
                f.flush()
            except Exception as e:  # a failure here is a bug in the system
                n_fail += 1
                print(f"!! FAILED {arch} x {shape}: {e}")
                traceback.print_exc()
                f.write(json.dumps({"arch": arch, "shape": shape,
                                    "mesh": dict(mesh.shape),
                                    "error": str(e)}) + "\n")
                f.flush()
    print(f"done: {len(todo) - n_fail}/{len(todo)} cells OK -> {out_path}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
