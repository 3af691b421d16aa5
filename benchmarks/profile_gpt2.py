#!/usr/bin/env python3
"""Ad-hoc perf probe for the GPT-2 MFU push (VERDICT r2 next-round #2).

Times flash fwd and fwd+bwd vs dense, then the full GPT-2-small train step,
on the attached TPU. Not part of bench.py — a working tool for relative
comparisons only.

CAVEAT: every number here carries one host fetch amortised over its
iterations — use bench.py's two-length-difference numbers for absolute
claims.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def scan_time(fn, *args, iters=50):
    @jax.jit
    def run(*args):
        def body(c, _):
            o = fn(*(a + c.astype(a.dtype) * 0 if i == 0 else a
                     for i, a in enumerate(args)))
            return o.mean().astype(jnp.float32), None
        c, _ = lax.scan(body, jnp.float32(0), None, length=iters)
        return c
    float(np.asarray(run(*args)))
    t0 = time.perf_counter()
    float(np.asarray(run(*args)))
    return (time.perf_counter() - t0) / iters * 1000


def main():
    from distributed_compute_pytorch_tpu.ops.attention import (
        _pick_block, dot_product_attention)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)

    for T in (1024, 4096):
        B, H, D = 4, 8, 64
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
                   for kk in ks)
        blk = _pick_block(T)

        def fl(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   block_q=blk, block_k=blk)

        def de(q, k, v):
            return dot_product_attention(q, k, v, causal=True)

        fwd_fl = scan_time(fl, q, k, v)
        fwd_de = scan_time(de, q, k, v)

        def g(attn):
            def f(q, k, v):
                return jax.grad(
                    lambda q: attn(q, k, v).astype(jnp.float32).sum())(q)
            return f

        bwd_fl = scan_time(g(fl), q, k, v)
        bwd_de = scan_time(g(de), q, k, v)
        print(f"T={T}: fwd flash {fwd_fl:.3f}ms dense {fwd_de:.3f}ms "
              f"({fwd_de/fwd_fl:.2f}x) | fwd+bwd flash {bwd_fl:.3f}ms "
              f"dense {bwd_de:.3f}ms ({bwd_de/bwd_fl:.2f}x)")

    # full GPT-2-small step
    from distributed_compute_pytorch_tpu.core.mesh import (
        batch_sharding, make_mesh)
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    mesh = make_mesh("data=-1", devices=jax.devices())
    B, T = 8, 1024
    model = GPT2(GPT2Config(dropout_rate=0.0))
    tx = build_optimizer("adamw", lr=3e-4, gamma=1.0, steps_per_epoch=100,
                         warmup_steps=10, total_steps=1000)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, 50257, jnp.int32),
        batch_sharding(mesh, 2))
    for _ in range(4):
        state, m = train_step(state, x, x)
    float(np.asarray(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(20):
        state, m = train_step(state, x, x)
    np.asarray(m["loss"])
    dt = (time.perf_counter() - t0) / 20
    n_params = sum(l.size for l in jax.tree.leaves(state.params))
    flops_per_token = 6 * n_params + 12 * 12 * T * 768
    mfu = B * T / dt * flops_per_token / 197e12
    print(f"gpt2-small B={B} T={T}: step {dt*1000:.2f}ms  mfu {mfu:.4f}")


if __name__ == "__main__":
    main()
