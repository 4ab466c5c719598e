"""End-to-end LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Runs on whatever devices exist (CPU here, pod in production: the same
code path; only the mesh shape changes).  Demonstrates the full stack:
deterministic sharded data pipeline -> jitted train step with doubly
distributed sharding -> AdamW -> fault-tolerant trainer (async ckpt,
NaN rollback, preemption save, straggler log).
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data.tokens import synthetic_token_batch
from ..models import Transformer, reduced
from ..optim import AdamWConfig, adamw_init, warmup_cosine
from ..runtime import Trainer, TrainerConfig
from ..sharding.rules import batch_axes
from .compile_cache import use_compile_cache
from .mesh import make_mesh
from .steps import make_train_step, param_shardings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '4,2' for a 4x2 (data, model) mesh")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])
    else:
        mesh = make_mesh((jax.device_count(), 1), ("data", "model"))

    model = Transformer(cfg, mesh=mesh)
    opt_cfg = AdamWConfig(lr=warmup_cosine(args.lr, 20, args.steps))

    with jax.set_mesh(mesh):
        pstructs, _, pspecs = param_shardings(model, mesh)
        params = jax.jit(
            lambda k: model.init(k)[0],
            out_shardings=jax.tree.map(lambda s: s.sharding, pstructs),
        )(jax.random.PRNGKey(0))
        opt_state = jax.jit(adamw_init)(params)
        step_fn = jax.jit(make_train_step(model, opt_cfg),
                          donate_argnums=(0, 1))

        def make_batch(step):
            b = synthetic_token_batch(step, batch=args.batch, seq=args.seq,
                                      vocab=cfg.vocab)
            if cfg.embed_input != "tokens":
                rng = np.random.default_rng(step)
                b = {"embeds": rng.normal(size=(args.batch, args.seq,
                                                cfg.d_model)).astype("float32"),
                     "labels": b["labels"]}
            if cfg.encoder_len:
                rng = np.random.default_rng(10_000 + step)
                b["encoder"] = rng.normal(
                    size=(args.batch, cfg.encoder_len, cfg.d_model)
                ).astype("float32")
            return b

        trainer = Trainer(
            TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
            step_fn, make_batch, params, opt_state)
        if args.resume:
            print("resumed at step", trainer.restore())
        history = trainer.run(args.steps)

    losses = [h["loss"] for h in history]
    print(f"steps={len(history)} first_loss={losses[0]:.4f} "
          f"last_loss={losses[-1]:.4f} stragglers={trainer.stragglers[:5]}")
    return history


if __name__ == "__main__":
    main()
