"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch opt-mini \
        --steps 300 --global-batch 32 --seq 128 --ckpt-dir checkpoints/opt-mini

Any registered arch works (``--arch phi4-mini-3.8b --reduced`` smoke-trains
the reduced config on CPU; full configs need the production mesh). The loop
provides checkpoint/restart, NaN-skip, and straggler flagging — kill and
relaunch the command to watch it resume.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs import get_config
from repro.data import MarkovCorpus, make_batch_fn
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.optim import AdamConfig
from repro.train.loop import LoopConfig, train_loop
from repro.train.step import init_train_state, make_train_step
from repro.utils import human_count, logger


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-mini")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced smoke config of --arch")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--mesh", default="host", choices=["host", "production",
                                                       "multipod"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.microbatches > 1 and args.global_batch % cfg.microbatches:
        cfg = dataclasses.replace(cfg, microbatches=1)
    model = build_model(cfg)

    if args.mesh == "host":
        mesh = make_host_mesh()
        rules = sharding.make_rules()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
        rules = sharding.make_rules(multi_pod=args.mesh == "multipod")

    corpus = MarkovCorpus(vocab=cfg.vocab_size, seed=args.seed)
    batch_fn_np = make_batch_fn(corpus, args.global_batch, args.seq)

    adam = AdamConfig(lr=args.lr, state_dtype=cfg.opt_state_dtype)
    with sharding.use_mesh(mesh, rules):
        state = init_train_state(model, jax.random.PRNGKey(args.seed), adam)
        logger.info("arch=%s params=%s devices=%d", cfg.name,
                    human_count(cfg.param_count()), mesh.size)
        step_fn = jax.jit(make_train_step(model, adam,
                                          total_steps=args.steps,
                                          warmup=max(args.steps // 20, 5)),
                          donate_argnums=(0,))

        def batch_fn(step):
            b = batch_fn_np(step)
            return {"tokens": jnp.asarray(b["tokens"])}

        loop_cfg = LoopConfig(total_steps=args.steps,
                              ckpt_every=args.ckpt_every,
                              ckpt_dir=args.ckpt_dir)
        state = train_loop(state, step_fn, batch_fn, loop_cfg)
        final_loss = float(step_fn(state, batch_fn(args.steps))[1]["loss"])
    logger.info("done: final loss %.4f (ppl %.2f); corpus entropy floor "
                "%.4f nats", final_loss, jnp.exp(final_loss),
                corpus.entropy_floor())
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
