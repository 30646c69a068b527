"""Training launcher.

On real hardware this runs the production mesh; on the CPU container it
trains REDUCED variants of the assigned architectures on the synthetic
token stream (host mesh), demonstrating the full path: config -> model ->
sharded train step -> checkpoint -> restore.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.core.trainer import make_train_step
from repro.data.tokens import make_stream
from repro.models import frontend as fe
from repro.models.api import Model
from repro.optim import adamw, cosine_warmup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="CPU-smoke widths (default); --no-reduced builds "
                         "the published widths")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    print(f"arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")

    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")

    opt = adamw(cosine_warmup(args.lr, args.steps // 10 + 1, args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(
        lambda p, b: model.loss(p, b), opt, clip=1.0), donate_argnums=(0, 1))

    stream = make_stream(cfg.vocab_size, args.seq, args.batch, args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt:
        restored = ckpt.restore_latest({"params": params, "opt": opt_state})
        if restored[0] is not None:
            start = restored[0]
            params = restored[1]["params"]
            opt_state = restored[1]["opt"]
            print(f"restored step {start}")

    t0 = time.time()
    losses = []
    for step in range(start, args.steps):
        np_batch = stream.batch(step)
        batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
        if cfg.frontend != "none":
            batch["embeds"] = fe.fake_embeds(cfg, args.batch, cfg.dtype,
                                             seed=step)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss={losses[-1]:.4f} "
                  f"xent={float(metrics['xent']):.4f} ({dt:.1f}s)", flush=True)
        if ckpt and (step + 1) % max(args.steps // 4, 1) == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})

    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'LEARNING' if last < first - 0.2 else 'check hyperparams'})")


if __name__ == "__main__":
    main()
