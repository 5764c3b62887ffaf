"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \
        --steps 50 --ckpt /tmp/ckpt

``--smoke`` swaps in the reduced config (CPU-sized); without it the full
config is used (requires the production mesh / real accelerators — on this
container use dryrun.py for full-size validation). The persistent compile
cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import base as cfgs
from repro.data import pipeline
from repro.launch import compile_cache
from repro.nn import transformer as tfm
from repro.train import ft as ft_mod
from repro.train import optimizer as opt_mod
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (FT demo)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = cfgs.get_arch(args.arch)
    if cfg.family != "lm":
        raise SystemExit("train.py drives LM archs; see examples/ for others")
    if args.smoke:
        cfg = cfgs.reduced(cfg)
    shape = cfgs.LMShape("cli", "train", args.seq, args.batch)

    def loss(params, batch):
        return tfm.loss_fn(params, cfg, batch)

    def init_params():
        return tfm.init(jax.random.PRNGKey(0), cfg)

    trainer = Trainer(
        loss_fn=loss,
        init_params=init_params,
        opt_cfg=opt_mod.OptConfig(name="adamw", lr=args.lr),
        tcfg=TrainerConfig(
            num_steps=args.steps,
            ckpt_dir=args.ckpt,
            ckpt_every=max(args.steps // 5, 1),
            log_every=max(args.steps // 20, 1),
        ),
    )
    batch_fn = pipeline.make_batch_fn("lm", cfg, shape, seed=0)
    injector = ft_mod.FailureInjector(fail_at=tuple(args.fail_at))
    state = trainer.fit(batch_fn, injector=injector if args.fail_at else None)
    losses = [h["loss"] for h in trainer.history]
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    if trainer.watchdog.events:
        print(f"[train] straggler events: {trainer.watchdog.events}")
    return state


if __name__ == "__main__":
    main()
