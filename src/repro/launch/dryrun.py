import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
backend initialization, and the production meshes need 512 placeholder
host devices. Nothing else in the repo sets this flag — smoke tests and
benchmarks see the real single CPU device.

For every cell this driver:
  1. builds abstract params / optimizer state / caches (eval_shape only);
  2. derives shardings from distribution.sharding rules;
  3. jit(step).lower(...).compile() under the production mesh;
  4. records memory_analysis(), cost_analysis(), and the HLO collective
     traffic (roofline.hlo_parse);
  5. separately lowers ONE superblock (fwd, and fwd+bwd for train) with
     the same shardings — cost_analysis counts while-loop bodies once, so
     roofline totals compose as full + (n_superblocks-1) * block;
  6. writes a JSON record to --out.

Usage:
  python -m repro.launch.dryrun --arch stablelm-12b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out experiments/dryrun
"""

import argparse
import dataclasses
import functools
import json
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_arch, shapes_for
from repro.configs.base import SHAPES, ArchConfig, ShapeConfig
from repro.distribution.sharding import (
    batch_shardings, batch_spec, cache_shardings, make_spec,
    opt_state_shardings, param_shardings)
from repro.core.messages import Task
from repro.launch.mesh import make_production_mesh
from repro.launch import steps
from repro.models import model as M
from repro.roofline.hlo_parse import collective_bytes
from repro.runtime import run_job
from repro.train.optimizer import OptimizerConfig, init_opt_state

F32 = jnp.float32


def _j(obj):
    """JSON-safe."""
    if isinstance(obj, dict):
        return {k: _j(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_j(v) for v in obj]
    if isinstance(obj, (int, float, str, bool)) or obj is None:
        return obj
    return str(obj)


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6*N*D (train) / 2*N*D (serve), N = active params, D = tokens."""
    n = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch * 1.0      # decode: one token


_COST_KEYS = ("flops", "bytes accessed", "transcendentals")


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        return {k: float(ca[k]) for k in _COST_KEYS if k in ca}
    except Exception as e:   # pragma: no cover
        return {"error": repr(e)}


def _sharded_bytes(specs, shardings, mesh) -> int:
    """Analytic per-chip bytes for a sharded pytree of ShapeDtypeStructs."""
    leaves = jax.tree_util.tree_leaves(specs)
    shs = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    total = 0
    for leaf, sh in zip(leaves, shs):
        n = 1
        for d in leaf.shape:
            n *= d
        div = 1
        for axes in sh.spec:
            if axes is None:
                continue
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                div *= mesh.shape[a]
        total += (n // max(div, 1)) * leaf.dtype.itemsize
    return total


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        keys = ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")
        return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}
    except Exception as e:   # pragma: no cover
        return {"error": repr(e)}


def _block_shardings(cfg: ArchConfig, mesh, params_specs):
    """Shardings for ONE superblock's params (drop the stacked dim)."""
    full = param_shardings(params_specs, mesh)
    blocks_sh = full["blocks"]

    def strip(sh):
        return NamedSharding(mesh, P(*tuple(sh.spec)[1:]))
    return jax.tree_util.tree_map(strip, blocks_sh)


def _one_superblock_specs(params_specs):
    def strip(leaf):
        return jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
    return jax.tree_util.tree_map(strip, params_specs["blocks"])


def run_cell(cfg: ArchConfig, shape: ShapeConfig, multi_pod: bool,
             opt_cfg: Optional[OptimizerConfig] = None,
             measure_block: bool = True,
             remat: bool = True) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict[str, Any] = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "kind": shape.kind, "ok": False,
        "n_superblocks": cfg.n_superblocks,
        "params": cfg.param_count(),
        "active_params": cfg.param_count(active_only=True),
        "model_flops": model_flops(cfg, shape),
    }
    opt_cfg = opt_cfg or OptimizerConfig(state_dtype="int8")
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["chips"] = mesh.devices.size
    try:
        with jax.set_mesh(mesh):
            pspecs = steps.param_specs(cfg)
            psh = param_shardings(pspecs, mesh)
            batch = steps.input_specs(cfg, shape)
            bsh = batch_shardings(mesh, batch)
            rec["param_bytes_per_chip"] = _sharded_bytes(pspecs, psh, mesh)

            if shape.kind == "train":
                ospecs = jax.eval_shape(
                    functools.partial(init_opt_state, cfg=opt_cfg), pspecs)
                osh = opt_state_shardings(ospecs, pspecs, psh, mesh)
                rec["opt_bytes_per_chip"] = _sharded_bytes(
                    ospecs, osh, mesh)
                fn = steps.make_train_step(cfg, opt_cfg, remat=remat)
                jitted = jax.jit(
                    fn, in_shardings=(psh, osh, bsh),
                    out_shardings=(psh, osh, NamedSharding(mesh, P())))
                lowered = jitted.lower(pspecs, ospecs, batch)
            elif shape.kind == "prefill":
                cspecs = steps.cache_specs(cfg, shape)
                csh = cache_shardings(cspecs, mesh)
                rec["cache_bytes_per_chip"] = _sharded_bytes(
                    cspecs, csh, mesh)
                lsh = NamedSharding(mesh, batch_spec(
                    mesh, shape.global_batch, 2))
                fn = steps.make_prefill_step(cfg, shape.seq_len)
                jitted = jax.jit(fn, in_shardings=(psh, bsh),
                                 out_shardings=(lsh, csh))
                lowered = jitted.lower(pspecs, batch)
            else:  # decode
                cspecs = steps.cache_specs(cfg, shape)
                csh = cache_shardings(cspecs, mesh)
                rec["cache_bytes_per_chip"] = _sharded_bytes(
                    cspecs, csh, mesh)
                lsh = NamedSharding(mesh, batch_spec(
                    mesh, shape.global_batch, 2))
                fn = steps.make_decode_step(cfg)
                jitted = jax.jit(fn, in_shardings=(psh, csh, bsh),
                                 out_shardings=(lsh, csh))
                lowered = jitted.lower(pspecs, cspecs, batch)

            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 1)
            rec["memory"] = _memory_dict(compiled)
            rec["cost"] = _cost_dict(compiled)
            txt = compiled.as_text()
            st = collective_bytes(txt, mesh.devices.size)
            rec["collectives"] = {
                "operand_bytes": st.operand_bytes,
                "wire_bytes": st.wire_bytes,
                "wire_bytes_total": st.total_wire_bytes,
            }
            rec["hlo_bytes"] = len(txt)

            if measure_block and cfg.n_superblocks > 1:
                rec.update(_measure_block(cfg, shape, mesh, pspecs, psh))
            rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def _measure_block(cfg: ArchConfig, shape: ShapeConfig, mesh,
                   pspecs, psh) -> dict:
    """Lower one superblock under the same shardings; compose costs."""
    out: dict[str, Any] = {}
    bspecs = _one_superblock_specs(pspecs)
    bsh = _block_shardings(cfg, mesh, pspecs)
    B = shape.global_batch
    T = shape.seq_len if shape.kind != "decode" else 1
    adt = jnp.dtype(cfg.activation_dtype)
    xspec = jax.ShapeDtypeStruct((B, T, cfg.d_model), adt)
    xsh = NamedSharding(mesh, batch_spec(mesh, B, 2))

    if shape.kind == "decode":
        cspecs_full = steps.cache_specs(cfg, shape)
        csh_full = cache_shardings(cspecs_full, mesh)
        one_cache = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            cspecs_full)
        one_csh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, P(*tuple(s.spec)[1:])), csh_full)

        def blk(bp, cache, x):
            h = x
            ncs = {}
            for i, kind in enumerate(cfg.block_pattern):
                h, nc = M._apply_sublayer_decode(
                    cfg, kind, cfg.is_moe_layer(i), bp[f"s{i}"],
                    cache[f"s{i}"], h)
                ncs[f"s{i}"] = nc
            return h, ncs
        c = jax.jit(blk, in_shardings=(bsh, one_csh, xsh),
                    out_shardings=(xsh, one_csh)) \
            .lower(bspecs, one_cache, xspec).compile()
        out["block_cost"] = _cost_dict(c)
        st = collective_bytes(c.as_text(), mesh.devices.size)
        out["block_collectives"] = {"wire_bytes_total": st.total_wire_bytes}
        return out

    fwd = lambda bp, x: M.superblock_apply(cfg, bp, x)
    c_fwd = jax.jit(fwd, in_shardings=(bsh, xsh), out_shardings=xsh) \
        .lower(bspecs, xspec).compile()
    cost = _cost_dict(c_fwd)
    st = collective_bytes(c_fwd.as_text(), mesh.devices.size)
    wire = st.total_wire_bytes

    if shape.kind == "train":
        def vjp_fn(bp, x, ct):
            y = M.superblock_apply(cfg, bp, x)
            return jnp.sum(y.astype(F32) * ct.astype(F32))
        g = jax.jit(jax.grad(vjp_fn, argnums=(0, 1)),
                    in_shardings=(bsh, xsh, xsh),
                    out_shardings=(bsh, xsh))
        c_bwd = g.lower(bspecs, xspec, xspec).compile()
        bcost = _cost_dict(c_bwd)
        for k in set(cost) | set(bcost):
            if isinstance(cost.get(k, 0.0), float):
                cost[k] = cost.get(k, 0.0) + bcost.get(k, 0.0)
        st2 = collective_bytes(c_bwd.as_text(), mesh.devices.size)
        wire += st2.total_wire_bytes
    out["block_cost"] = cost
    out["block_collectives"] = {"wire_bytes_total": wire}
    return out


def _compile_cell(task: Task, *, opt_cfg: OptimizerConfig,
                  measure_block: bool) -> bool:
    """Worker fn for the self-scheduled cell dispatcher (module-level so
    it pickles under the multiprocessing spawn start method)."""
    a, s, mp, path = task.payload
    print(f"[run ] {task.task_id}", flush=True)
    rec = run_cell(get_arch(a), SHAPES[s], mp, opt_cfg,
                   measure_block=measure_block)
    with open(path, "w") as f:
        json.dump(_j(rec), f, indent=1)
    status = "ok" if rec["ok"] else f"FAIL: {rec.get('error')}"
    print(f"[done] {task.task_id}: {status} ({rec['total_s']}s)",
          flush=True)
    return bool(rec["ok"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    choices=sorted(ARCHS) + [None], nargs="?")
    ap.add_argument("--shape", default=None,
                    choices=sorted(SHAPES) + [None], nargs="?")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-block", action="store_true",
                    help="skip per-superblock roofline measurement")
    ap.add_argument("--opt-state", default="int8",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--jobs", type=int, default=1,
                    help="concurrent compile workers (self-scheduled)")
    ap.add_argument("--exec-backend", default="threads",
                    choices=["threads", "processes"],
                    help="execution backend for the cell dispatcher")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    for a in archs:
        cfg = get_arch(a)
        for sh in shapes_for(cfg):
            if args.shape and sh.name != args.shape:
                continue
            cells.append((a, sh.name))

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    opt_cfg = OptimizerConfig(state_dtype=args.opt_state)

    # Each (arch x shape x mesh) cell is one self-scheduled task; sized by
    # param count so largest-first compiles the heavyweight models first.
    cell_tasks: list[Task] = []
    for a, s in cells:
        for mp in meshes:
            tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            cell_tasks.append(Task(
                task_id=tag, size_bytes=get_arch(a).param_count(),
                payload=(a, s, mp, path)))

    if cell_tasks:
        run_job(cell_tasks,
                functools.partial(_compile_cell, opt_cfg=opt_cfg,
                                  measure_block=not args.no_block),
                backend=args.exec_backend, n_workers=args.jobs,
                organization="largest_first", poll_interval=0.05)


if __name__ == "__main__":
    main()
