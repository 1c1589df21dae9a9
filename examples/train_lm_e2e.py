"""End-to-end training driver: a ~100M-parameter qwen-family decoder
trained on the synthetic Markov LM stream with warmup+cosine schedule,
gradient clipping, periodic eval, and checkpointing -- the full
substrate stack in one script.

Defaults are CPU-budget friendly (~20M params, 60 steps). --preset 100m
trains the full ~100M model for 300 steps (hours on 1 CPU core; the
config is the point on this container, the wall time is not).

  PYTHONPATH=src python examples/train_lm_e2e.py
  PYTHONPATH=src python examples/train_lm_e2e.py --preset 100m --steps 300
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint, latest_step, save_checkpoint
from repro.compile_cache import setup_compile_cache
from repro.configs import get_config
from repro.data import markov_lm_batches
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import adam, linear_warmup_cosine

PRESETS = {
    # ~20M params: CI-fast
    "20m": dict(num_layers=6, d_model=384, num_heads=6, num_kv_heads=2,
                head_dim=64, d_ff=1536, vocab_size=8192),
    # ~100M params (the deliverable-b scale)
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 head_dim=64, d_ff=3072, vocab_size=16384),
}


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config("qwen1.5-0.5b").replace(
        remat=False, dtype="float32", **PRESETS[args.preset])
    model = build_model(cfg)
    n_params = None
    opt = adam(linear_warmup_cosine(args.lr, 20, args.steps))
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))

    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params "
          f"({cfg.num_layers}L d={cfg.d_model} vocab={cfg.vocab_size})")

    start = 0
    if latest_step(args.ckpt_dir) is not None:
        start = latest_step(args.ckpt_dir)
        params = load_checkpoint(args.ckpt_dir, start, params)
        print(f"resumed from checkpoint at step {start}")

    it = markov_lm_batches(cfg.vocab_size, args.batch, args.seq, seed=1)
    step = jnp.asarray(start, jnp.int32)
    t0 = time.time()
    first_loss = None
    for i in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        params, opt_state, step, m = step_fn(params, opt_state, step, batch)
        loss = float(m["loss"])
        if first_loss is None:
            first_loss = loss
        if i % 10 == 0 or i == args.steps - 1:
            tput = args.batch * args.seq * (i - start + 1) / \
                (time.time() - t0)
            print(f"step {i:4d}  loss {loss:.4f}  {tput:,.0f} tok/s")
        if args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            path = save_checkpoint(args.ckpt_dir, i + 1, params)
            print(f"  checkpoint -> {path}")

    print(f"loss: {first_loss:.3f} -> {loss:.3f} "
          f"(uniform would be {jnp.log(cfg.vocab_size):.2f})")
    assert loss < first_loss, "training must reduce loss"


if __name__ == "__main__":
    main()
