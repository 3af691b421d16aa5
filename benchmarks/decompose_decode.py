#!/usr/bin/env python3
"""Decode-tick component decomposition (VERDICT r4 weak #1-3).

Measures, in isolation but with the production shapes, each component of
one KV-cache decode tick for GPT-2-small / Llama-125M at B=16 (and the
B=64 throughput point), bf16 and int8 weights:

- ``weights``: the per-layer matmul stack alone (qkv/attn_out/mlp or
  q/k/v/o/gate/up/down) over a [B, 1, d] activation — the weight-stream
  component, measured bf16 vs int8 to see what the mixed dot actually
  pays back end-to-end-free.
- ``cache``: ``cached_attention`` over a full [B, Hk, t_max, hd] cache
  x layers — the cache-stream component (plus the in-place insert).
- ``readout``: final norm + vocab matmul (GPT-2's tied 50257x768 attend
  is 77 MB bf16 — a meaningful slice of the tick).
- ``embed+sample``: token embed + argmax.

Every wall ends in a host fetch and uses the K-batched two-length
discipline (bench.py::_two_length_dt); per-component rooflines come from
the component's actual HBM bytes. The table this prints is the
attribution record for closing (or bounding) the gap between the decode
stages' measured ticks and their weights+cache floors.

Usage: python benchmarks/decompose_decode.py [gpt2|llama] [B]
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
    which = sys.argv[1] if len(sys.argv) > 1 else "gpt2"
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    quant = "--int8" in sys.argv

    from distributed_compute_pytorch_tpu.utils.compilation_cache import (
        enable as enable_compile_cache)
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from distributed_compute_pytorch_tpu.models import layers as L
    from distributed_compute_pytorch_tpu.ops import attention as A

    if which == "llama":
        from distributed_compute_pytorch_tpu.models.llama import (
            LlamaConfig, LlamaLM)
        cfg = LlamaConfig()
        model = LlamaLM(cfg)
        hk = cfg.num_kv_heads
    else:
        from distributed_compute_pytorch_tpu.models.gpt2 import (
            GPT2, GPT2Config)
        cfg = GPT2Config(dropout_rate=0.0)
        model = GPT2(cfg)
        hk = cfg.num_heads
    d, nl, hd = cfg.d_model, cfg.num_layers, cfg.d_model // cfg.num_heads
    t_max = 384
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params)
    if quant:
        from distributed_compute_pytorch_tpu.utils.quantize import (
            quantize_params_int8)
        params = jax.jit(quantize_params_int8)(params)
    blocks = params["blocks"]
    leaf_bytes = lambda t: sum(l.size * l.dtype.itemsize
                               for l in jax.tree.leaves(t))
    HBM = 819e9
    x0 = jax.random.normal(jax.random.key(1), (B, 1, d), jnp.bfloat16)

    def scan_probe(step, init, n):
        """Chain ``step`` n times (output feeds input) inside one jit;
        both probe lengths are built+warmed ONCE up front (a fresh
        closure per repeat would retrace/recompile every time)."""
        def make_run(length):
            @jax.jit
            def run(z):
                def body(c, _):
                    return step(c), None
                out, _ = lax.scan(body, z, None, length=length)
                return jax.tree.leaves(out)[0].astype(jnp.float32).mean()
            return run
        runs = {m: make_run(m) for m in (n, 2 * n)}
        for r in runs.values():
            float(np.asarray(r(init)))       # compile + warm

        def t_n(m):
            t0 = time.perf_counter()
            float(np.asarray(runs[m](init)))
            return time.perf_counter() - t0
        return two_length(t_n, n)

    rows = []

    def row(name, ms, byts):
        roof = byts / HBM * 1e3
        rows.append((name, ms * 1e3, byts / 1e6, roof,
                     roof / (ms * 1e3) if ms else 0))
        print(f"  .. {name}: {ms * 1e3:.3f} ms", flush=True)

    # ---- weights stack: all layers' matmuls on [B, 1, d] ----
    def weights_tick(x):
        for i in range(nl):
            p = jax.tree.map(lambda a: a[i], blocks)
            if which == "llama":
                x_ = x
                qo = L.Dense(d, d, use_bias=False).apply(p["q"], x_)
                ko = L.Dense(d, hk * hd, use_bias=False).apply(p["k"], x_)
                vo = L.Dense(d, hk * hd, use_bias=False).apply(p["v"], x_)
                x_ = x_ + L.Dense(d, d, use_bias=False).apply(
                    p["o"], qo + jnp.pad(ko, ((0, 0), (0, 0),
                                              (0, d - hk * hd)))
                    + jnp.pad(vo, ((0, 0), (0, 0), (0, d - hk * hd))))
                g = L.Dense(d, cfg.d_ff, use_bias=False).apply(p["gate"], x_)
                u = L.Dense(d, cfg.d_ff, use_bias=False).apply(p["up"], x_)
                x = x_ + L.Dense(cfg.d_ff, d, use_bias=False).apply(
                    p["down"], jax.nn.silu(g) * u)
            else:
                qkv = L.Dense(d, 3 * d).apply(p["qkv"], x)
                q_, k_, v_ = jnp.split(qkv, 3, axis=-1)
                # all three projections feed the carry: a sliced
                # qkv[..., :d] would let XLA narrow the matmul and DCE
                # the k/v columns, under-measuring the weight stream
                x = x + L.Dense(d, d).apply(p["attn_out"],
                                            q_ + k_ + v_)
                h = L.Dense(d, cfg.d_ff).apply(p["mlp_in"], x)
                x = x + L.Dense(cfg.d_ff, d).apply(
                    p["mlp_out"], jax.nn.gelu(h))
        return x
    w_bytes = leaf_bytes(blocks)
    row("weights-stack", scan_probe(weights_tick, x0, 200), w_bytes)

    # ---- cache stream: cached attention over full windows, all layers ----
    cache = {"k": jax.random.normal(jax.random.key(2),
                                    (B, hk, t_max, hd), jnp.bfloat16),
             "v": jax.random.normal(jax.random.key(3),
                                    (B, hk, t_max, hd), jnp.bfloat16)}
    q0 = jax.random.normal(jax.random.key(4), (B, cfg.num_heads, 1, hd),
                           jnp.bfloat16)

    def cache_tick(q):
        o = q
        for _ in range(nl):
            o = A.cached_attention(o, cache["k"], cache["v"], t_max - 2)
        return o
    c_bytes = 2 * B * hk * t_max * hd * 2 * nl
    row("cache-read", scan_probe(cache_tick, q0, 200), c_bytes)

    # ---- cache insert: the PRODUCTION kv-pair one-window write ----
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_insert_all)
    pair = {"kv": jnp.stack([cache["k"], cache["v"]])}
    upd = {"kv": jax.random.normal(jax.random.key(5),
                                   (2, B, hk, 1, hd), jnp.bfloat16)}

    def insert_tick(c):
        for _ in range(nl):
            c = kv_insert_all(c, upd, 37)
        return c
    row("cache-insert", scan_probe(insert_tick, pair, 200),
        2 * nl * 2 * B * hk * 8 * hd * 2)

    # ---- readout: final norm + vocab matmul ----
    def readout_tick(x):
        # the carry depends on the MEAN over the FULL vocab so XLA
        # cannot sink a slice into the matmul and read one column
        # (verified failure mode: [:, :, :1] compiles to a 1-column dot)
        lg = model.readout(params, x)
        return x + (lg.mean(axis=-1, keepdims=True) * 1e-6).astype(x.dtype)
    ro_bytes = leaf_bytes(
        params["wte"] if which == "gpt2" else params["lm_head"])
    row("readout", scan_probe(readout_tick, x0, 200), ro_bytes)

    # ---- embed + sample ----
    tok0 = jnp.zeros((B, 1), jnp.int32)

    def emb_tick(t):
        lg = model.readout(params, model.embed(params, t, jnp.arange(1)))
        return jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    # embed gather is tiny; this mostly re-measures readout — reported
    # as embed+readout+sample for the overlap check
    row("embed+readout+sample", scan_probe(emb_tick, tok0, 200),
        ro_bytes)

    # ---- the real full tick, for the cross-check ----
    from distributed_compute_pytorch_tpu.infer import make_generate_fn
    gen = {n: make_generate_fn(model, n, t_max=t_max)
           for n in (128, 256)}
    prompt = jax.random.randint(jax.random.key(6), (B, 128), 0,
                                cfg.vocab_size, jnp.int32)
    for g in gen.values():
        int(np.asarray(g(params, prompt))[0, -1])
    K = 8

    def t_n(n):
        g = gen[n // K]
        t0 = time.perf_counter()
        out = None
        for _ in range(K):
            out = g(params, prompt)
        np.asarray(out[0, -1])
        return time.perf_counter() - t0
    full = two_length(t_n, K * 128, repeats=5)
    total_bytes = leaf_bytes(params) + c_bytes
    row("FULL-tick", full, total_bytes)

    print(f"\n== {which} B={B} t_max={t_max} "
          f"{'int8' if quant else 'bf16'} ==")
    print(f"{'component':24s} {'ms':>8s} {'MB':>8s} {'roof_ms':>8s} "
          f"{'eff':>6s}")
    comp_sum = 0.0
    for name, ms, mb, roof, eff in rows:
        if name != "FULL-tick":
            comp_sum += ms if name != "embed+readout+sample" else 0
        print(f"{name:24s} {ms:8.3f} {mb:8.1f} {roof:8.3f} {eff:6.3f}")
    print(f"{'sum(components)':24s} {comp_sum:8.3f}   "
          f"(vs FULL-tick {rows[-1][1]:.3f} -> "
          f"unattributed {rows[-1][1] - comp_sum:+.3f} ms)")


if __name__ == "__main__":
    main()
