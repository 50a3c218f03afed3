"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 8 --seq 128 [--smoke] [--ckpt-dir DIR] \
        [--resume] [--microbatches 2]

On this CPU container ``--smoke`` (default) reduces the config to the
same-family smoke scale. On a TPU slice, drop ``--smoke`` and pass
``--mesh data,model`` sizes that match the slice; the step function,
shardings, checkpointing and data pipeline are the production ones either
way — tests/test_dryrun_small.py and the multi-pod dry-run prove the full
configs compile for the production meshes.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduce_for_smoke
from repro.data.pipeline import (DataPipeline, SyntheticCorpus,
                                 SyntheticCorpusConfig)
from repro.dist import sharding as SH
from repro.ft.checkpoint import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.training.optimizer import OptConfig
from repro.training.train_loop import (TrainConfig, init_train_state,
                                       make_train_step)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduce config for CPU (default on)")
    ap.add_argument("--mesh", default=None,
                    help="comma data,model sizes, e.g. 16,16 (TPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"))
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = jax.make_mesh((d, m), ("data", "model"))
    model = build_model(cfg, mesh)
    print(f"[train] {cfg.arch_id} ({cfg.param_count()/1e6:.1f}M params) "
          f"steps={args.steps} batch={args.batch}x{args.seq} "
          f"mesh={mesh.shape if mesh else '1x1'}")

    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps),
        optimizer=args.optimizer, num_microbatches=args.microbatches)
    corpus = SyntheticCorpus(SyntheticCorpusConfig(
        vocab_size=cfg.vocab_size))
    pipe = DataPipeline(corpus, batch=args.batch, seq=args.seq)

    params = model.init(jax.random.key(0))
    state = init_train_state(params, tcfg)
    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume and mgr.latest_step() is not None:
            tree, manifest = mgr.restore()
            params = jax.tree_util.tree_map(jnp.asarray, tree["params"])
            state = jax.tree_util.tree_map(jnp.asarray, tree["opt"])
            pipe.restore(manifest["extra"]["pipe"])
            start = manifest["extra"]["step"]
            print(f"[train] resumed from step {start}")

    if mesh is not None:
        p_sh = SH.param_shardings(cfg, mesh, params)
        params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
        step_fn = jax.jit(make_train_step(model.loss_fn, tcfg))
    else:
        step_fn = jax.jit(make_train_step(model.loss_fn, tcfg))

    t0 = time.perf_counter()
    tokens = 0
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        params, state, metrics = step_fn(params, state, batch)
        tokens += args.batch * args.seq
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"  step {step:5d} nll={float(metrics['nll']):.4f} "
                  f"gnorm={float(metrics.get('grad_norm', 0)):.2f} "
                  f"tok/s={tokens/max(dt, 1e-9):,.0f}")
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": state},
                     extra={"pipe": pipe.state(), "step": step + 1})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": state},
                 extra={"pipe": pipe.state(), "step": args.steps},
                 block=True)
        print(f"[train] final checkpoint at step {args.steps}")


if __name__ == "__main__":
    main()
