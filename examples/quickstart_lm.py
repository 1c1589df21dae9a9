"""Quickstart: build an assigned architecture (reduced), train a few
steps on the synthetic LM stream, then decode with a KV cache.

  PYTHONPATH=src python examples/quickstart_lm.py --arch gemma2-2b
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.reduced import reduced_config
from repro.compile_cache import setup_compile_cache
from repro.data import markov_lm_batches
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import adam


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    print(f"arch={cfg.name} family={cfg.family} "
          f"(reduced: {cfg.num_layers}L d={cfg.d_model})")
    model = build_model(cfg)
    opt = adam(1e-3)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))

    it = markov_lm_batches(cfg.vocab_size, 4, 64)
    step = jnp.zeros((), jnp.int32)
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        if cfg.modality != "text":
            batch["prefix_emb"] = jnp.zeros(
                (4, cfg.num_prefix_embeddings, cfg.d_model))
        params, opt_state, step, m = step_fn(params, opt_state, step, batch)
        if i % 5 == 0:
            print(f"  step {i:3d}  loss {float(m['loss']):.4f}")

    # decode 8 tokens
    state = model.init_decode_state(2, 32)
    if cfg.is_encoder_decoder:
        state["enc"] = jnp.zeros((2, cfg.num_prefix_embeddings,
                                  cfg.d_model), model.dtype)
    toks = jnp.zeros((2, 1), jnp.int32)
    out = []
    dec = jax.jit(model.decode_step)
    for _ in range(8):
        logits, state = dec(params, state, toks)
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(int(toks[0, 0]))
    print("greedy decode:", out)


if __name__ == "__main__":
    main()
