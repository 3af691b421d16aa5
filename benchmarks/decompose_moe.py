#!/usr/bin/env python3
"""MoE train-rung component decomposition (VERDICT r4 weak #4).

Where does the 8-expert rung's active-MFU (~0.42) lose its ~28% to the
dense Llama rung (~0.58)? One fwd+bwd LAYER at the exact bench shapes
(B=8, T=1024, d=768, f=3072, E=8, top-2, group 512, cf 1.0, bf16,
sinkhorn selection), measured in isolation:

- ``moe-layer``: the full MoELayer (router -> sinkhorn -> one-hots ->
  dispatch einsum -> expert FFNs -> combine einsum) fwd+bwd.
- ``experts-only``: the expert FFN einsums alone on a pre-dispatched
  [G, E, C, d] block — the only FLOPs the active-MFU convention counts.
- ``dispatch+combine``: routing + one-hot build + dispatch/combine
  einsums with the expert compute replaced by identity — the overhead
  the GShard formulation pays to stay static-shaped.
- ``dense-mlp``: a dense d->4d->d MLP on the same tokens — what the
  same MLP slot costs a dense model.
- ``attention``: the shared attention sublayer at the same shapes (the
  non-MoE half of the block, for the full-step cross-check).

Each probe is a jitted grad step on its component, timed by the
two-length scan discipline with a final host fetch.

Usage: python benchmarks/decompose_moe.py
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402


def two_length(time_n, iters, repeats=4):
    best = lambda n: min(time_n(n) for _ in range(repeats))
    b1, b2 = best(iters), best(2 * iters)
    d = b2 - b1
    return d / iters if d > 0.02 * b2 else b2 / (2 * iters)


def main():
    from distributed_compute_pytorch_tpu.utils.compilation_cache import (
        enable as enable_compile_cache)
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from distributed_compute_pytorch_tpu.models import layers as L
    from distributed_compute_pytorch_tpu.models.moe import MoELayer

    B, T, d, f, E = 8, 1024, 768, 3072, 8
    Ng, cf, topk = 512, 1.0, 2
    N = B * T
    G, C = N // Ng, int(cf * topk * Ng / E)
    PEAK = 197e12

    moe = MoELayer(d, f, E, cf, top_k=topk, group_size=Ng,
                   router_balance="sinkhorn")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          moe.init(jax.random.key(0)))
    x0 = jax.random.normal(jax.random.key(1), (B, T, d), jnp.bfloat16)

    def probe(name, loss_fn, args, flops):
        """fwd+bwd time of loss_fn via two-length chained scans; the grad
        wrt args[0] feeds the carry so nothing is dead."""
        g = jax.grad(lambda a, *r: loss_fn(a, *r).astype(jnp.float32))

        def make_run(length):
            @jax.jit
            def run(a, *r):
                def body(c, _):
                    return c - 1e-9 * g(c, *r), None
                out, _ = lax.scan(body, a, None, length=length)
                return out.astype(jnp.float32).mean()
            return run
        runs = {m: make_run(m) for m in (30, 60)}
        for r_ in runs.values():
            float(np.asarray(r_(*args)))

        def t_n(m):
            t0 = time.perf_counter()
            float(np.asarray(runs[m](*args)))
            return time.perf_counter() - t0
        ms = two_length(t_n, 30) * 1e3
        mfu = flops / (ms * 1e-3) / PEAK if flops else 0
        print(f"{name:18s} {ms:8.3f} ms   flops={flops/1e9:7.1f} G  "
              f"mfu={mfu:.3f}", flush=True)
        return ms

    # expert FFN FLOPs actually executed (full capacity slots, fwd+bwd):
    # 2 matmuls x G*E*C*d*f MACs x 2 flops, x3 for fwd+bwd
    expert_flops = 3 * 2 * 2 * G * E * C * d * f
    # dispatch+combine one-hot contractions: 2 einsums x G*Ng*E*C*d MACs
    disp_flops = 3 * 2 * 2 * G * Ng * E * C * d

    t_moe = probe("moe-layer",
                  lambda x: moe.apply(params, x)[0].sum(), (x0,),
                  expert_flops + disp_flops)

    ein0 = jax.random.normal(jax.random.key(2), (G, E, C, d), jnp.bfloat16)

    def experts_only(ein):
        h = jnp.einsum("gecd,edf->gecf", ein, params["w_in"])
        h = jax.nn.gelu(h + params["b_in"][None, :, None, :])
        out = jnp.einsum("gecf,efd->gecd", h, params["w_out"])
        return out.sum()
    t_exp = probe("experts-only", experts_only, (ein0,), expert_flops)

    def dispatch_combine(x):
        # full routing path, expert compute replaced by identity
        xg = x.reshape(G, Ng, d)
        logits = jnp.einsum("gnd,de->gne", xg,
                            params["router"]["kernel"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        sel = probs
        for _ in range(3):
            sel = sel / jnp.maximum(sel.sum(1, keepdims=True), 1e-9) \
                * (topk * Ng / E)
            sel = sel / jnp.maximum(sel.sum(2, keepdims=True), 1e-9)
        sel = jax.lax.stop_gradient(sel)
        idx = jnp.argmax(sel, -1)
        oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        pos = (jnp.cumsum(oh, axis=1) - oh) * oh
        keep = (pos < C) * oh
        gate = jnp.sum(probs * oh, -1)
        pos_oh = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), C,
                                dtype=jnp.float32)
        piece = keep[..., None] * pos_oh[:, :, None, :]
        dispatch = piece.astype(x.dtype)
        combine = (piece * gate[..., None, None]).astype(x.dtype)
        ein = jnp.einsum("gnec,gnd->gecd", dispatch, xg)
        y = jnp.einsum("gnec,gecd->gnd", combine, ein)
        return y.sum()
    t_disp = probe("dispatch+combine", dispatch_combine, (x0,), disp_flops)

    wi = jax.random.normal(jax.random.key(3), (d, 4 * d), jnp.bfloat16)
    wo = jax.random.normal(jax.random.key(4), (4 * d, d), jnp.bfloat16)

    def dense_mlp(x):
        return jnp.einsum("btf,fd->btd",
                          jax.nn.gelu(jnp.einsum("btd,df->btf", x, wi)),
                          wo).sum()
    probe("dense-mlp", dense_mlp, (x0,), 3 * 2 * 2 * N * d * 4 * d)

    from distributed_compute_pytorch_tpu.models.transformer import (
        attention_sublayer)
    ap = jax.tree.map(lambda a: a.astype(jnp.bfloat16), {
        "qkv": L.Dense(d, 3 * d).init(jax.random.key(5)),
        "attn_out": L.Dense(d, d).init(jax.random.key(6))})
    probe("attention",
          lambda x: attention_sublayer(ap, x, num_heads=12,
                                       causal=True).sum(), (x0,),
          3 * 2 * 2 * N * d * 4 * d + 3 * 2 * 2 * B * 12 * T * T * 64)

    print(f"\nmoe-layer {t_moe:.2f} = experts {t_exp:.2f} + routing"
          f"/dispatch {t_disp:.2f} (+ interaction "
          f"{t_moe - t_exp - t_disp:+.2f})")


if __name__ == "__main__":
    main()
