"""Training launcher.

CPU-scale driver for the same code path the pod runs: pick an architecture
(full or smoke), build the mesh (production placeholder grid or the local
device set), and run the fault-tolerant loop.

Examples:
  # ~100M-class end-to-end run on this container (examples/train_lm.py
  # wraps this with a fixed recipe):
  python -m repro.launch.train --arch granite-3-2b --smoke --steps 200
"""
from __future__ import annotations

import argparse

import jax

from repro.config import MeshConfig, OptimizerConfig, RunConfig, ShapeConfig
from repro.configs import get_arch
from repro.configs.shapes import SMOKE_TRAIN, get_shape
from repro.launch.mesh import make_local_mesh
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    model_cfg = get_arch(args.arch, smoke=args.smoke)
    shape = SMOKE_TRAIN if args.smoke else get_shape("train_4k")
    if args.batch or args.seq:
        shape = ShapeConfig(
            name="custom",
            seq_len=args.seq or shape.seq_len,
            global_batch=args.batch or shape.global_batch,
            kind="train")

    mesh = make_local_mesh()
    run = RunConfig(
        model=model_cfg, shape=shape,
        mesh=MeshConfig(shape=tuple(mesh.devices.shape),
                        axes=tuple(mesh.axis_names)),
        optimizer=OptimizerConfig(
            name=args.optimizer, lr=args.lr, warmup_steps=args.steps // 20,
            total_steps=args.steps, compress_grads=args.compress_grads),
        microbatches=args.microbatches, seed=args.seed)

    loop = TrainLoop(run, mesh, TrainLoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir))
    with mesh:
        res = loop.run_loop(resume=args.resume)
    print(f"[train] done at step {res.final_step}; "
          f"loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f}; "
          f"skipped {res.skipped_steps}, rewinds {res.rewinds}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
