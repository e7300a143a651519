import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The line above MUST run before any jax import (jax locks the device
count at first init). For each cell this driver:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract params/opt/cache/input ShapeDtypeStructs with
     NamedShardings (launch/specs.py) — no allocation anywhere,
  3. jits the real train/prefill/decode step and ``.lower().compile()``s it,
  4. prints ``memory_analysis()`` (fits-per-device proof) and
     ``cost_analysis()`` (FLOPs/bytes for the roofline),
  5. writes a JSON record consumed by EXPERIMENTS.md and the perf loop.

Usage:
  python -m repro.launch.dryrun --arch gemma3_27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out-dir artifacts/dryrun]
"""
import argparse
import json
import time
import traceback

import jax

import repro.api as falcon
from repro.configs import SHAPE_CELLS, get_config, list_archs
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SP
from repro.models import model as M
from repro.optim import AdamWConfig
from repro.roofline.analysis import analyze_compiled
from repro.roofline.analytic import analytic_costs
from repro.core.hardware import TPU_V5E
from repro.train import steps as ST


def build_step_fn(cfg: ModelConfig, cell, mesh, cs: SP.CellSpec,
                  opt_dtype: str = "float32", microbatches: int = 1):
    if cs.kind == "train":
        fn = ST.make_train_step(cfg, AdamWConfig(state_dtype=opt_dtype),
                                microbatches=microbatches)
        donate = (0, 1)
    elif cs.kind == "prefill":
        fn = ST.make_prefill_step(cfg, max_len=cell.seq_len)
        donate = ()
    else:
        fn = ST.make_decode_step(cfg)
        donate = (1,)
    return jax.jit(fn, donate_argnums=donate)


def model_flops_for(cs: SP.CellSpec, cell, cfg) -> float:
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    if cell.kind == "train":
        return 6.0 * cs.n_active_params * tokens
    return 2.0 * cs.n_active_params * tokens


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str | None,
             falcon_mode: str | None = None, fsdp: int | None = None,
             remat: int | None = None, parallel_style: str | None = None,
             parallel_block: int | None = None, opt_dtype: str | None = None,
             remat_policy: str | None = None, capacity_factor: float | None = None,
             microbatches: int = 1, batch_scale: int = 1,
             falcon_backend: str | None = None,
             tag: str = "", notes: str = "") -> dict:
    import dataclasses

    from repro.parallel import sharding as SHH

    cfg = get_config(arch)
    if falcon_mode is not None:
        cfg = dataclasses.replace(cfg, falcon_mode=falcon_mode,
                                  use_falcon=falcon_mode != "off")
    if fsdp is not None:
        cfg = dataclasses.replace(cfg, fsdp=bool(fsdp))
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=bool(remat))
    if parallel_style is not None:
        cfg = dataclasses.replace(cfg, parallel_style=parallel_style)
    if parallel_block is not None:
        cfg = dataclasses.replace(cfg, parallel_block=bool(parallel_block))
    if remat_policy is not None:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if capacity_factor is not None:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    if falcon_backend is not None:
        cfg = dataclasses.replace(cfg, falcon_backend=falcon_backend)
    SHH.set_parallel_style(cfg.parallel_style)
    cell = SHAPE_CELLS[shape]
    if batch_scale != 1:
        import dataclasses as _dc
        cell = _dc.replace(cell, global_batch=cell.global_batch * batch_scale)
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag,
                 "opt_dtype": opt_dtype or "float32",
                 "falcon_mode": cfg.falcon_mode if cfg.use_falcon else "off"}
    ok, why = SP.cell_applicable(cfg, cell)
    if not ok:
        rec.update(status="skipped", reason=why)
        _emit(rec, out_dir)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = int(len(mesh.devices.reshape(-1)))
    t0 = time.time()
    try:
        cs = SP.input_specs(cfg, cell, mesh, opt_dtype=opt_dtype or "float32")
        step = build_step_fn(cfg, cell, mesh, cs, opt_dtype=opt_dtype or "float32",
                             microbatches=microbatches)
        with jax.set_mesh(mesh), \
                falcon.use(M.falcon_config_for(cfg, dict(mesh.shape))):
            lowered = step.lower(*cs.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            print(f"[{arch} x {shape} x {mesh_name}] memory_analysis:", ma)
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            print(f"[{arch} x {shape} x {mesh_name}] cost_analysis: "
                  f"flops={ca.get('flops', 0):.3e} bytes={ca.get('bytes accessed', 0):.3e}")
            rep = analyze_compiled(
                compiled, arch=arch, shape=shape, mesh_name=mesh_name,
                chips=chips, model_flops=model_flops_for(cs, cell, cfg),
                notes=notes)
        # analytic roofline terms (primary: corrects while-body-once counting)
        ac = analytic_costs(cfg, cell, dict(mesh.shape), cs.n_params,
                            cs.n_active_params,
                            opt_dtype=opt_dtype or "float32")
        t_c, t_m, t_l = ac.terms(TPU_V5E, cfg.dtype)
        terms = {"compute": t_c, "memory": t_m, "collective": t_l}
        bott = max(terms, key=terms.get)
        step_time = max(terms.values())
        mf = model_flops_for(cs, cell, cfg)
        rec["analytic"] = {
            "flops_dev": ac.flops, "hbm_bytes_dev": ac.hbm_bytes,
            "coll_bytes_dev": ac.coll_bytes,
            "t_compute": t_c, "t_memory": t_m, "t_collective": t_l,
            "bottleneck": bott, "step_time": step_time,
            "model_flops": mf,
            "useful_ratio": mf / (ac.flops * chips) if ac.flops else 0.0,
            "roofline_fraction": (mf / chips) / step_time / TPU_V5E.flops_for(cfg.dtype)
                                 if step_time > 0 else 0.0,
            "detail": ac.detail,
        }
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1),
                   n_params=cs.n_params, n_active_params=cs.n_active_params,
                   argument_bytes=int(ma.argument_size_in_bytes),
                   temp_bytes=int(ma.temp_size_in_bytes),
                   output_bytes=int(ma.output_size_in_bytes),
                   roofline=rep.to_dict())
    except Exception as e:  # noqa: BLE001 - record the failure verbatim
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[{arch} x {shape} x {mesh_name}] FAILED: {e}")
    _emit(rec, out_dir)
    return rec


def _emit(rec: dict, out_dir: str | None):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{rec['tag']}" if rec.get("tag") else (
        f"_{rec['falcon_mode']}" if rec.get("falcon_mode") not in (None, "auto") else "")
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="artifacts/dryrun")
    ap.add_argument("--falcon-mode", default=None,
                    help="override: off|auto|<scheme> (perf experiments)")
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--remat", type=int, default=None)
    ap.add_argument("--parallel-style", default=None, choices=["tp", "fsdp_only"])
    ap.add_argument("--parallel-block", type=int, default=None)
    ap.add_argument("--opt-dtype", default=None)
    ap.add_argument("--remat-policy", default=None, choices=["full", "dots"])
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--batch-scale", type=int, default=1)
    ap.add_argument("--falcon-backend", default=None)
    ap.add_argument("--tag", default="", help="suffix for the output record")
    ap.add_argument("--notes", default="")
    args = ap.parse_args()
    enable_compile_cache()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPE_CELLS) if (args.all or not args.shape) else [args.shape]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                rec = run_cell(arch, shape, mesh_name, args.out_dir,
                               falcon_mode=args.falcon_mode, fsdp=args.fsdp,
                               remat=args.remat,
                               parallel_style=args.parallel_style,
                               parallel_block=args.parallel_block,
                               opt_dtype=args.opt_dtype,
                               remat_policy=args.remat_policy,
                               capacity_factor=args.capacity_factor,
                               microbatches=args.microbatches,
                               batch_scale=args.batch_scale,
                               falcon_backend=args.falcon_backend,
                               tag=args.tag, notes=args.notes)
                if rec["status"] == "error":
                    failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
