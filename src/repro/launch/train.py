"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \\
      --steps 50 --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

On real hardware the same entry point runs the production mesh; on this
container use --reduced (small config) and the host's devices. Supports
resume-from-checkpoint, preemption-safe saves, and the compressed cross-pod
gradient exchange when the mesh has a pod axis.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch, reduced as reduce_cfg, ShapeConfig
from repro.data.tokens import SyntheticTokenStream
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.api import build_model
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import init_error_feedback
from repro.runtime import steps as S
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="", help="e.g. '1x1' data x model")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--compress-pod-grads", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        names = ("pod", "data", "model")[-len(dims):] if args.multi_pod \
            else ("data", "model")[-len(dims):]
        mesh = make_mesh(dims, names)
    else:
        mesh = make_mesh((1, 1), ("data", "model"))

    api = build_model(cfg, max_seq=args.seq)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)

    params = api.init(jax.random.PRNGKey(0))
    opt_state = adamw.init(params)
    data = SyntheticTokenStream(cfg.vocab_size, args.batch, args.seq)

    with jax.set_mesh(mesh):
        step = S.make_train_step(api, mesh, opt_cfg, shape,
                                 compress_pod_grads=args.compress_pod_grads)
        # place state on its training shardings (required on multi-device
        # meshes: freshly-initialized arrays are committed replicated)
        params = jax.device_put(params, S.param_shardings(api, mesh))
        opt_state = jax.device_put(opt_state, S.opt_shardings(api, mesh))
        extra = ()
        if args.compress_pod_grads and "pod" in mesh.axis_names:
            extra = (jax.device_put(init_error_feedback(params),
                                    S.param_shardings(api, mesh)),)

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        shardings = None
        if ckpt:
            shardings = {"params": S.param_shardings(api, mesh),
                         "opt": S.opt_shardings(api, mesh)}
        loop = TrainLoop(train_step=step, params=params, opt_state=opt_state,
                         data=data, ckpt=ckpt,
                         cfg=TrainLoopConfig(total_steps=args.steps,
                                             ckpt_every=args.ckpt_every),
                         shardings=shardings, extra_step_args=extra)
        loop.install_signal_handler()
        resumed = loop.try_restore()
        if resumed:
            print(f"resumed from step {loop.step}")
        result = loop.run(args.steps - loop.step)

    losses = result["losses"]
    print(f"arch={cfg.name} steps={result['step']} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"stragglers={len(result['stragglers'])}")
    return result


if __name__ == "__main__":
    main()
