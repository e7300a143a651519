"""Training launcher: ``python -m repro.launch.train --arch granite_3_2b ...``

Runs a real (reduced or full) training job on the available devices with the
fault-tolerant loop, checkpointing, and optional compressed-DP gradients.
On the CPU container this runs the reduced configs; on a TPU slice the same
entrypoint runs the full configs against the production mesh (the per-host
data feeding hook is in repro.data.pipeline).
"""
from __future__ import annotations

import argparse
import logging

import jax

import repro.api as falcon
from repro.configs import get_config, smoke_config
from repro.data import DataConfig, SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init
from repro.parallel import sharding as SH
from repro.train import (TrainLoop, TrainLoopConfig, make_train_step, steps)
from jax.sharding import PartitionSpec as P


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced same-family config (CPU default)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--compressed-dp", action="store_true",
                    help="int8-compressed data-parallel gradient all-reduce")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(message)s")

    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh else make_local_mesh())
    rules = SH.make_rules(mesh, fsdp=cfg.fsdp)
    fcfg = M.falcon_config_for(cfg, dict(mesh.shape))

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_cfg = AdamWConfig(lr=args.lr)
    opt_state = adamw_init(params, opt_cfg)
    with jax.set_mesh(mesh), falcon.use(fcfg):
        psh = SH.param_sharding(params, mesh, rules)
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(opt_state, {
            "m": psh, "v": psh, "step": SH.named_sharding(mesh)})

        data = SyntheticLMData(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed,
                       num_codebooks=cfg.num_codebooks
                       if cfg.frontend == "audio_codebooks" else 0),
            mesh=mesh, batch_spec=P(rules.batch))
        if args.compressed_dp:
            step = steps.make_compressed_dp_train_step(
                cfg, opt_cfg, mesh, total_steps=args.steps)
        else:
            step = make_train_step(cfg, opt_cfg, total_steps=args.steps)
        step = jax.jit(step, donate_argnums=(0, 1))

        loop = TrainLoop(
            TrainLoopConfig(total_steps=args.steps,
                            checkpoint_every=args.checkpoint_every,
                            checkpoint_dir=args.checkpoint_dir,
                            handle_sigterm=True),
            step, data, params, opt_state, shardings=None)
        out = loop.run()
    print(f"done: {out['final_step']} steps, "
          f"loss {out['history'][0]['loss']:.4f} -> {out['history'][-1]['loss']:.4f}, "
          f"stragglers={out['stragglers']} restarts={out['restarts']}")


if __name__ == "__main__":
    main()
