#!/usr/bin/env python3
"""Decompose the GPT-2-small step time: fwd / fwd+bwd / optimizer, and
flash vs dense attention inside the full model.

CAVEAT: each timing below carries one host fetch amortised over its
iterations — fine for the relative comparisons this tool exists for, but
use bench.py's two-length-difference numbers for absolute claims."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters=20, warmup=3):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    np.asarray(jax.tree.leaves(out)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0])
    return (time.perf_counter() - t0) / iters * 1000


def main():
    from dataclasses import replace

    from distributed_compute_pytorch_tpu.core.mesh import (
        batch_sharding, make_mesh)
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    mesh = make_mesh("data=-1", devices=jax.devices())
    B, T = 8, 1024
    cfg = GPT2Config(dropout_rate=0.0)
    model = GPT2(cfg)
    tx = build_optimizer("adamw", lr=3e-4, gamma=1.0, steps_per_epoch=100,
                         warmup_steps=10, total_steps=1000)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, 50257, jnp.int32),
        batch_sharding(mesh, 2))

    def time_step(step, st):
        for _ in range(3):
            st, m = step(st, x, x)
        float(np.asarray(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(20):
            st, m = step(st, x, x)
        np.asarray(m["loss"])
        return (time.perf_counter() - t0) / 20 * 1000, st

    full, state = time_step(train_step, state)
    print(f"full step (flash):      {full:.2f} ms")

    params_bf16 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), state.params)

    @jax.jit
    def fwd_loss(params, x):
        logits, _ = model.apply(params, {}, x, train=False)
        return model.loss_fn(logits, x)

    print(f"fwd only (bf16 params): {timeit(fwd_loss, params_bf16, x):.2f} ms")

    @jax.jit
    def fwd_bwd(params, x):
        return jax.grad(lambda p: fwd_loss(p, x))(params)

    print(f"fwd+bwd (bf16 params):  {timeit(fwd_bwd, params_bf16, x):.2f} ms")

    grads = fwd_bwd(params_bf16, x)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    import optax

    @jax.jit
    def opt_only(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def time_opt():
        p, o = state.params, state.opt_state
        for _ in range(3):
            p, o = opt_only(p, o, grads)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for _ in range(20):
            p, o = opt_only(p, o, grads)
        np.asarray(jax.tree.leaves(p)[0])
        return (time.perf_counter() - t0) / 20 * 1000

    print(f"optimizer update only:  {time_opt():.2f} ms")

    # dense-attention variant of the full model
    class DenseBlockGPT2(GPT2):
        def _block(self):
            b = super()._block()
            return replace(b, attn_impl="xla")

    dmodel = DenseBlockGPT2(cfg)
    dinit, dstep, _ = make_step_fns(dmodel, tx, mesh,
                                    compute_dtype=jnp.bfloat16)
    dstate = dinit(jax.random.key(0))
    dfull, _ = time_step(dstep, dstate)
    print(f"full step (dense attn): {dfull:.2f} ms")


if __name__ == "__main__":
    main()
