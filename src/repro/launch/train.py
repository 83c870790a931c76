"""End-to-end training driver (fault-tolerant).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 300 --batch 8 --seq 256 --smoke --ckpt-dir /tmp/ckpt

Fault tolerance:
 * periodic atomic checkpoints (params + opt state + step);
 * resume: picks up from LATEST automatically, data pipeline is a pure
   function of step → exact stream continuation;
 * SIGTERM/SIGINT (preemption) → checkpoint now → exit 0;
 * elastic: restart with a different device count / mesh reshapes the
   checkpoint onto the new topology (shardings recomputed at load).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)

    from ..configs import get_config, get_smoke_config, input_specs
    from ..configs.base import ShapeConfig
    from ..launch.mesh import make_host_mesh
    from ..train import checkpoint as ckpt
    from ..train.data import DataConfig, SyntheticLM
    from ..train.optimizer import AdamWConfig, init_opt_state
    from ..train.train_step import (
        TrainOptions, abstract_params, init_sharded, make_train_step,
    )

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(model=args.model_axis)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    batch_shape = input_specs(cfg, shape)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 5))

    with jax.sharding.set_mesh(mesh):
        step_fn, p_sh, o_sh, b_sh = make_train_step(
            cfg, opt_cfg, mesh,
            TrainOptions(remat=True, q_chunk=0, loss_chunk=0,
                         accum_steps=args.accum),
            batch_shape,
        )
        start = 0
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            p_shape = abstract_params(cfg)
            o_shape = jax.eval_shape(init_opt_state, p_shape)
            (params, opt_state), start = _restore(
                args.ckpt_dir, p_shape, o_shape, p_sh, o_sh
            )
            print(f"[train] resumed from step {start}")
        else:
            params, opt_state, _, _ = init_sharded(cfg, mesh)

        data = SyntheticLM(
            DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch), cfg
        )

        stop = {"now": False}

        def _sig(_s, _f):
            stop["now"] = True

        signal.signal(signal.SIGTERM, _sig)
        signal.signal(signal.SIGINT, _sig)

        t0 = time.time()
        tokens_done = 0
        for s in range(start, args.steps):
            batch = jax.device_put(data.batch(s), b_sh)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            tokens_done += args.batch * args.seq
            if (s + 1) % args.log_every == 0:
                dt = time.time() - t0
                print(
                    f"step {s+1}/{args.steps} loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e} "
                    f"tok/s={tokens_done/dt:.0f}"
                )
            want_ckpt = args.ckpt_dir and (
                (s + 1) % args.ckpt_every == 0 or stop["now"]
                or s + 1 == args.steps
            )
            if want_ckpt:
                ckpt.save(args.ckpt_dir, s + 1,
                          {"p": params, "o": opt_state})
                if stop["now"]:
                    print(f"[train] preempted at step {s+1}; "
                          "checkpointed, exiting cleanly")
                    return 0
    print("[train] done")
    return 0


def _restore(ckpt_dir, p_shape, o_shape, p_sh, o_sh):
    from ..train import checkpoint as ckpt

    tree, step = ckpt.restore(
        ckpt_dir, {"p": p_shape, "o": o_shape},
        shardings={"p": p_sh, "o": o_sh},
    )
    return (tree["p"], tree["o"]), step


if __name__ == "__main__":
    sys.exit(main())
