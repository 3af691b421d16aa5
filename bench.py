#!/usr/bin/env python3
"""Headline benchmark — run by the driver on real TPU hardware.

The headline stage (BASELINE.json north star): samples/sec/chip training
the reference's default model (the MNIST ConvNet of
``/root/reference/main.py:20-45``) at the reference's default global
batch (128, ``main.py:139``) with the reference optimizer stack.
``vs_baseline`` compares against the measured torch-CPU number in
``benchmarks/baseline_measured.json`` (the reference publishes none).

Then the ladder, grown round by round: GPT-2-small / Llama-125M /
BERT-base / ResNet-18 / ResNet-50 / 8-expert MoE train steps in bf16
with MFU (per-token FLOPs = 6N + 12·L·T·d for the LMs; XLA cost
analysis for the convnets, with roofline attribution where HBM binds),
an eval-pass stage, KV-cache decode for the causal families (GPT-2 and
Llama in bf16 and weight-only int8, latency B=16 and throughput B=64
points; the 8-expert MoE in bf16 — every tick streams all experts'
weights — each with a weights+cache HBM byte model and achieved
fraction), and flash-vs-dense attention at T=1k/4k/8k.

Runs on a TPU only: with no chip it exits non-zero instead of printing a
CPU number under a device metric's name, a stage that raises makes the
exit code non-zero, and a device_kind missing from the peak tables is an
error. Prints exactly ONE compact JSON line: {"metric", "value", "unit",
"vs_baseline", "extra": {...}} (the full per-stage record goes to
benchmarks/bench_details_latest.json — the printed line must stay small
enough for the driver to capture and parse).

Timing discipline: completion is forced by a device->host fetch of a value
that depends on the last step. All stages time by a TWO-LENGTH DIFFERENCE
— wall(2n) - wall(n) — so the constant dispatch+fetch cost of a timed
call cancels instead of inflating the per-step time.
"""

import json
import os
import sys
import time

# every printed bench record (headline and smokes) carries this stamp
# and a stable stage-key layout, so obs/regress.py's bench-diff can
# compare any two records — including historical BENCH_r*.json files —
# without per-era heuristics. Bump only on layout-breaking changes;
# key ADDITIONS are compatible (the diff reports them as only_new).
SCHEMA_VERSION = 1


def _print_record(rec: dict) -> None:
    """The one output contract: stamp and print a bench record as a
    single JSON line (what the driver captures and bench-diff loads)."""
    rec.setdefault("schema_version", SCHEMA_VERSION)
    print(json.dumps(rec))


def _two_length_dt(time_n, iters, repeats=3):
    """Per-iteration time from a two-length difference, with a recorded
    spread (the variance discipline: every headline number is
    best-of-K, K >= 3 walls).

    ``time_n(n)`` runs an n-iteration workload to completion (host fetch
    included) and returns its wall seconds. The difference wall(2n)-wall(n)
    cancels the constant dispatch+fetch overhead of a timed call. When
    jitter swamps the device work and the difference is not comfortably
    positive, fall back to the overhead-inflated wall(2n)/2n — a
    conservative (slower-than-true) number rather than a fabricated one.

    Returns ``(dt, spread)``: the headline is best-of-``repeats`` per
    wall, and ``spread`` = (max-min)/min over the 2n-wall repeats — the
    run-to-run variability of the exact workload the headline came
    from. Stages whose spread exceeds 5% are flagged in the record.
    """
    def best(n):
        return min(time_n(n) for _ in range(repeats))

    b1 = best(iters)
    walls2 = [time_n(2 * iters) for _ in range(repeats)]
    b2 = min(walls2)
    spread = round((max(walls2) - b2) / b2, 4) if b2 > 0 else 0.0
    d = b2 - b1
    if d > 0.02 * b2:
        return d / iters, spread
    return b2 / (2 * iters), spread


# chip peak dense bf16 FLOP/s by jax device_kind (public spec sheets)
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # Trillium
}

# chip HBM bandwidth (bytes/s), same sources — decode-roofline attribution
_PEAK_HBM = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
}


def _peak(table: dict, device_kind: str) -> float:
    """A device that is not in the table is an error, not a default: a
    utilization against a guessed peak is not a measurement."""
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in bench.py's peak "
            f"tables ({sorted(table)}); add it with its source")
    return table[device_kind]


def _bench_convnet(jax, jnp, np, mesh, n_chips):
    """Samples/sec/chip for the reference ConvNet train step.

    The steps are folded into one compiled program (lax.scan over the
    jitted step, which inlines), so one dispatch times ``iters`` real
    optimization steps on device. A per-step python loop would measure
    host dispatch, not the chip — the step itself is ~0.1 ms of device
    work.
    """
    from jax import lax

    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.convnet import ConvNet
    from distributed_compute_pytorch_tpu.train.optim import adadelta_steplr
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    batch = 128  # reference default (main.py:139)
    model = ConvNet()
    tx = adadelta_steplr(lr=1e-3, gamma=0.7, steps_per_epoch=469)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh, donate=False)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (batch, 28, 28, 1), jnp.float32),
        batch_sharding(mesh, 4))
    y = jax.device_put(
        jax.random.randint(jax.random.key(2), (batch,), 0, 10, jnp.int32),
        batch_sharding(mesh, 1))

    # ~0.1 ms of device work per step: 2000 iters puts ~200/400 ms of real
    # work behind the two-length difference, well above dispatch jitter
    iters = 2000

    runs = {}
    for n in (iters, 2 * iters):
        @jax.jit
        def run(state, x, y, n=n):
            def body(s, _):
                s2, m = train_step(s, x, y)
                return s2, m["loss"]
            s, losses = lax.scan(body, state, None, length=n)
            return s, losses[-1]
        _, loss = run(state, x, y)     # compile + warm
        float(np.asarray(loss))
        runs[n] = run

    def time_n(n):
        t0 = time.perf_counter()
        _, loss = runs[n](state, x, y)
        np.asarray(loss)               # device->host fetch = true completion
        return time.perf_counter() - t0

    dt, spread = _two_length_dt(time_n, iters)
    return batch / dt / n_chips, spread


def _bench_causal_lm(jax, jnp, np, mesh, n_chips, peak_flops, model):
    """Shared harness for the decoder-LM train rungs (GPT-2, Llama):
    bf16 train step at T=1024, 16 sequences/chip (the measured single-chip
    MFU sweet spot on v5e: B=8 0.46, B=16 0.49, B=24 0.48, B=32
    OOM-pressure 0.44), MFU via the 6N + 12*L*T*d analytic convention."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    cfg = model.config
    B, T = 16 * n_chips, 1024
    tx = build_optimizer("adamw", lr=3e-4, gamma=1.0, steps_per_epoch=100,
                         warmup_steps=10, total_steps=1000)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))
    dt, finite, spread = _time_steps(np, train_step, state, x, x)
    tokens_per_sec = B * T / dt
    n_params = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * T * cfg.d_model
    mfu = (tokens_per_sec * flops_per_token / (peak_flops * n_chips)
           if peak_flops else None)
    return {
        "batch": B, "seq_len": T, "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "peak_bf16_flops_assumed": peak_flops,
        "n_params": int(n_params), "loss_finite": finite,
        "spread": spread,
    }


def _bench_gpt2(jax, jnp, np, mesh, n_chips, peak_flops):
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config

    # GPT-2-small: 12L/12H/768d, 50257v
    return _bench_causal_lm(jax, jnp, np, mesh, n_chips, peak_flops,
                            GPT2(GPT2Config(dropout_rate=0.0)))


def _compile_step(train_step, *args):
    """AOT-compile once; returns (compiled, xla_flops, xla_bytes) with the
    counts None when unavailable.

    One lower().compile() serves both the cost analysis and the timed
    calls — calling the jitted wrapper after an AOT compile would compile
    the identical program a second time. "bytes accessed" is XLA's
    op-level count, an upper bound on true HBM traffic (fusion keeps some
    of it on-chip) — useful for roofline attribution, not an exact meter."""
    compiled = train_step.lower(*args).compile()
    flops = bytes_acc = None
    try:
        cost = compiled.cost_analysis()
        f = cost.get("flops")
        flops = float(f) if f and f > 0 else None
        b = cost.get("bytes accessed")
        bytes_acc = float(b) if b and b > 0 else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        pass
    return compiled, flops, bytes_acc


def _time_steps(np, train_step, state, x, y, iters=20, warmup=4):
    """Wall-time chained train steps; completion forced by a host fetch.

    Per-step time via ``_two_length_dt``, cancelling the constant per-fetch
    overhead. Returns ``(dt, loss_finite, spread)``
    (the best-of-3 variance discipline)."""
    st = {"state": state, "m": None}
    for _ in range(warmup):
        st["state"], st["m"] = train_step(st["state"], x, y)
    float(np.asarray(st["m"]["loss"]))

    def time_n(n):
        t0 = time.perf_counter()
        for _ in range(n):
            st["state"], st["m"] = train_step(st["state"], x, y)
        np.asarray(st["m"]["loss"])
        return time.perf_counter() - t0

    dt, spread = _two_length_dt(time_n, iters, repeats=3)
    return dt, bool(np.isfinite(np.asarray(st["m"]["loss"]))), spread


def _bench_llama(jax, jnp, np, mesh, n_chips, peak_flops):
    """Llama-family rung: default config (12L/768d, GQA 12:4, SwiGLU,
    RoPE, 32k vocab — ~125M params, GPT-2-small class)."""
    from distributed_compute_pytorch_tpu.models.llama import (
        LlamaConfig, LlamaLM)

    return _bench_causal_lm(jax, jnp, np, mesh, n_chips, peak_flops,
                            LlamaLM(LlamaConfig()))


def _bench_resnet18(jax, jnp, np, mesh, n_chips, peak_flops):
    """BASELINE.md rung 1: ResNet-18 / CIFAR-10-shaped data, bf16 train
    step, samples/sec/chip (+MFU from XLA's own FLOP count)."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.resnet import ResNet
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    B = 512 * n_chips
    model = ResNet.build("resnet18", num_classes=10, in_channels=3)
    tx = build_optimizer("sgd", lr=0.1, gamma=0.97, steps_per_epoch=100)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (B, 32, 32, 3), jnp.float32),
        batch_sharding(mesh, 4))
    y = jax.device_put(
        jax.random.randint(jax.random.key(2), (B,), 0, 10, jnp.int32),
        batch_sharding(mesh, 1))
    compiled, flops, _ = _compile_step(train_step, state, x, y)
    dt, finite, spread = _time_steps(np, compiled, state, x, y)
    mfu = (flops / dt / (peak_flops * n_chips)
           if (flops and peak_flops) else None)
    return {
        "batch": B, "image": "32x32x3", "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "xla_flops_per_step": flops, "loss_finite": finite,
        "spread": spread,
    }


def _bench_resnet50(jax, jnp, np, mesh, n_chips, peak_flops):
    """BASELINE.md rung 2 (configs[2]): ResNet-50 at ImageNet geometry
    (224x224x3), bf16 train step, samples/sec/chip + MFU from XLA's own
    FLOP count. The input pipeline half of this rung is the streaming
    sharded dataset (data/shards.py), exercised in tests; this stage pins
    the compute half on real hardware.

    Why MFU sits near 0.30 on v5e and why that is close to the ceiling:
    this model/geometry is HBM-BANDWIDTH-bound, not MXU-bound. Measured
    r5 (B=128): forward alone is ~13.4 ms of the ~51.5 ms step; the
    PROVABLE conv traffic from the forward jaxpr (each conv's
    input+output+kernel bytes in bf16 — a lower bound, since residual
    adds, bn stats and backward-saved tensors also move) floors it at
    ~6.9 ms, and XLA's op-level count (which double-counts fused
    elementwise traffic) tops it at an impossible >819 GB/s. The truth
    sits between: the forward achieves ~420 GB/s against the provable
    bytes — about half of spec — consistent with the low
    FLOPs-per-byte of the early-stage convs (56x56x64..256 on a 240
    flops/byte machine). The C_in=3 stem is NOT the story (0.59 ms
    fwd, ~1% of step; a space-to-depth stem measured only 1.9x faster
    on that op).

    Attribution discipline (VERDICT r4 weak #5): the stage MEASURES the
    forward and derives its byte model from the forward jaxpr — the sum
    of every conv's input+output+kernel bytes, which is what actually
    crosses HBM (elementwise bn/relu fuse into the conv epilogues, so
    their traffic IS the conv output write already counted). XLA's
    op-level byte count is also recorded, but explicitly as an UPPER
    BOUND that double-counts fused elementwise traffic — dividing it by
    the step time yields >819 GB/s, which is physically impossible and
    therefore not reported as achieved bandwidth."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.resnet import ResNet
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    B = 128 * n_chips    # measured best on v5e (0.29 vs 0.28 at 64/256)
    model = ResNet.build("resnet50", num_classes=1000, in_channels=3)
    tx = build_optimizer("sgd", lr=0.1, gamma=0.97, steps_per_epoch=100)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (B, 224, 224, 3), jnp.float32),
        batch_sharding(mesh, 4))
    y = jax.device_put(
        jax.random.randint(jax.random.key(2), (B,), 0, 1000, jnp.int32),
        batch_sharding(mesh, 1))
    compiled, flops, bytes_acc = _compile_step(train_step, state, x, y)

    # --- forward-only measurement + jaxpr conv-traffic byte model ---
    # (the docstring's roofline decomposition, now IN the record).
    # MUST run BEFORE the timed train steps: those donate the state
    # buffers, after which state.params is deleted.
    def fwd(params, xin):
        bf = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a,
                          params)
        out, _ = model.apply(bf, state.model_state, xin.astype(jnp.bfloat16),
                             train=False)
        return out.astype(jnp.float32).sum()

    conv_bytes = 0
    for eqn in jax.make_jaxpr(fwd)(state.params, x).jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            conv_bytes += sum(v.aval.size * v.aval.dtype.itemsize
                              for v in (*eqn.invars, *eqn.outvars))
    fwd_c = jax.jit(fwd)
    float(np.asarray(fwd_c(state.params, x)))    # compile + warm

    def fwd_time_n(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fwd_c(state.params, x)
        float(np.asarray(out))
        return time.perf_counter() - t0

    fwd_dt, _fwd_spread = _two_length_dt(fwd_time_n, 10)
    hbm_bw = _peak(_PEAK_HBM, jax.devices()[0].device_kind)
    fwd_roof_ms = conv_bytes / n_chips / hbm_bw * 1e3

    dt, finite, spread = _time_steps(np, compiled, state, x, y)
    mfu = (flops / dt / (peak_flops * n_chips)
           if (flops and peak_flops) else None)
    return {
        "batch": B, "image": "224x224x3", "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "xla_flops_per_step": flops,
        # UPPER BOUND: op-level counts double-count fused elementwise
        # traffic (dividing by step time would exceed the 819 GB/s spec —
        # physically impossible, so NOT reported as achieved bandwidth)
        "xla_op_bytes_per_step_upper_bound": bytes_acc,
        # forward roofline: measured fwd wall vs the jaxpr conv-traffic
        # floor (conv in+out+kernel bytes; bn/relu ride the conv
        # epilogues). achieved_gbps = provable bytes / measured time,
        # <= spec by construction when the claim "fwd runs at the HBM
        # roofline" is true
        "fwd_ms": round(fwd_dt * 1000, 2),
        "fwd_conv_traffic_gb": round(conv_bytes / n_chips / 1e9, 2),
        "fwd_hbm_roofline_ms": (round(fwd_roof_ms, 2)
                                if fwd_roof_ms else None),
        "fwd_roofline_fraction": (round(fwd_roof_ms / (fwd_dt * 1e3), 3)
                                  if fwd_roof_ms else None),
        "achieved_gbps": round(conv_bytes / n_chips / fwd_dt / 1e9, 1),
        "bound": "hbm_bandwidth",
        "loss_finite": finite,
        "spread": spread,
    }


def _bench_bert(jax, jnp, np, mesh, n_chips, peak_flops):
    """BASELINE.md rung 3: BERT-base MLM train step in bf16 at T=512,
    samples/sec/chip, tokens/sec/chip and MFU.

    Why BERT reads ~0.49 while GPT-2 reads ~0.52 (VERDICT r3 weak #7,
    measured 2026-07-30): it is the ACCOUNTING, not the chip. The shared
    12*L*T*d convention credits FULL T^2 attention FLOPs; GPT-2's causal
    flash kernel executes only ~half of them (skipped upper-triangle
    blocks) while BERT's bidirectional attention executes all — so
    GPT-2's number is flattered by ~ its credited attention fraction / 2
    (~6% at T=1024), i.e. 0.519/1.06 ~= 0.49 == BERT. Sequence length is
    a second-order term: the same model at B=16/T=1024 measures 0.499 vs
    0.487 at B=32/T=512. The record carries this as ``mfu_note``."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.bert import BertConfig, BertMLM
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    # 32/chip measured best on v5e (0.496 vs 0.489 at 16, 0.484 at 48)
    B, T = 32 * n_chips, 512
    cfg = BertConfig(dropout_rate=0.0)     # BERT-base: 12L/12H/768d, 30522v
    model = BertMLM(cfg)
    tx = build_optimizer("adamw", lr=1e-4, gamma=1.0, steps_per_epoch=100,
                         warmup_steps=10, total_steps=1000)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))
    compiled, xla_flops, _ = _compile_step(train_step, state, x, x)
    dt, finite, spread = _time_steps(np, compiled, state, x, x)
    tokens_per_sec = B * T / dt
    # MFU from the same analytic convention as the GPT-2 stage (6N fwd+bwd
    # + attention term). XLA's cost analysis undercounts here — the Pallas
    # attention custom call is opaque to it — so it is reported for
    # reference, not used for MFU. N is the actual parameter count so the
    # number tracks BertConfig instead of a hardcoded 110e6.
    n_params = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    flops = (6 * n_params + 12 * cfg.num_layers * T * cfg.d_model) * B * T
    mfu = flops / dt / (peak_flops * n_chips) if peak_flops else None
    return {
        "batch": B, "seq_len": T, "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "xla_flops_per_step": xla_flops, "loss_finite": finite,
        # bidirectional attention EXECUTES the full credited T^2 FLOPs;
        # causal rungs (gpt2/llama) execute ~half of theirs — adjusting
        # for that, BERT matches GPT-2's real efficiency (see docstring)
        "mfu_note": "bidirectional attention executes full credited T^2; "
                    "causal rungs execute ~half — convention, not a "
                    "kernel gap (T=1024 measures 0.499)",
        "spread": spread,
    }


def _bench_moe(jax, jnp, np, mesh, n_chips, peak_flops,
               dispatch_mode="einsum", remat="dots"):
    """Switch/GShard MoE rung: GPT-2-small-geometry blocks with an 8-expert
    top-2 grouped-routing MoE MLP, bf16 train step. Surfaces the
    dropped-token fraction (VERDICT r2 #8) alongside throughput."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.moe import (
        MoETransformerConfig, MoETransformerLM)
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    B, T = 8 * n_chips, 1024
    # remat="dots": the 8-expert model is ~453M params; with remat OFF the
    # step's activations overflow a single v5e's 16G HBM at B=8 (measured:
    # 19.7G needed), but FULL per-block remat re-runs every expert matmul
    # in the backward. Selective remat saves the named matmul outputs
    # (~150 MB/layer) and recomputes only routing/gelu — measured r4 on
    # v5e: 144.4 ms (block remat+scan) -> 134.6 (dots+scan) -> 118.2
    # (dots+unrolled layers), active-MFU 0.346 -> 0.422.
    # group 512 measured best on v5e (2026-07-30 sweep): 158 ms vs 169 at
    # 1024, 182 at 2048, 261 global — smaller [G, E, C] dispatch tensors
    # beat fewer-larger groups until capacity granularity bites.
    # capacity_factor 1.0 + SINKHORN-balanced selection (r4): the
    # measured cf frontier with raw argmax was drop/MFU = 13.5%/0.316 at
    # cf 1.25, 6.6%/0.285 at 1.5, 2.7%/0.244 at 2.0 — capacity padding
    # buys drop reduction ONLY by burning active-MFU. Balancing the
    # SELECTION instead (models/moe.py router_balance) collapses drops
    # without the padding: measured 2.1%/0.342 at cf=1.0, 0.0%/0.317 at
    # cf=1.25. The once-suspected "next step up" — gather-based dispatch
    # replacing the one-hot einsums (models/moe.py dispatch_mode="gather")
    # — was implemented and measured-REJECTED: the row gathers XLA emits
    # run ~7x slower than the dispatch einsum's MXU one-hot matmuls
    # (5.6 vs 0.8 ms/layer fwd), and the full rung drops 144 -> 164 ms.
    # What actually closed the gap was the backward: full block remat was
    # re-running every expert matmul; remat="dots" + unrolled layers
    # measured 144.4 -> 118.2 ms (active-MFU 0.346 -> 0.422). The
    # remaining gap to ~0.5 is the dispatch/combine einsums' non-expert
    # FLOPs (~17%) and the routing recompute (saving the one-hots too
    # measured flat, 119.7 — not worth 0.8 GB). Re-swept under dots
    # (2026-07-31): group 256 measures 114.6 ms but drops 2.8% vs 512's
    # 2.1% — the 1.4% speed is not worth the quality tax; B=12 is
    # per-token slower (69.7k vs 71.5k tok/s) and B=16 OOMs.
    cfg = MoETransformerConfig(num_experts=8, top_k=2, moe_group_size=512,
                               capacity_factor=1.0, dropout_rate=0.0,
                               remat=remat, dispatch_mode=dispatch_mode)
    model = MoETransformerLM(cfg)
    tx = build_optimizer("adamw", lr=3e-4, gamma=1.0, steps_per_epoch=100,
                         warmup_steps=10, total_steps=1000)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh,
                                           compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))
    n_params = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    # ACTIVE params per token (the MoE MFU convention): expert FFNs
    # count top_k/E-ths; everything else is dense. Keyed by the expert
    # leaf NAMES (w_in/w_out/b_in/b_out, same convention as
    # optim.decay_mask) — a shape[1]==num_experts test would also catch
    # the always-active router bias [L, E] and could misfire if a dense
    # dim ever equalled num_experts (ADVICE r3)
    _expert_leaf = {"w_in", "w_out", "b_in", "b_out"}
    expert_params = sum(
        leaf.size for path, leaf in
        jax.tree_util.tree_flatten_with_path(state.params)[0]
        if any(getattr(k, "key", None) == "moe" for k in path)
        and getattr(path[-1], "key", None) in _expert_leaf)
    n_active = (n_params - expert_params
                + expert_params * cfg.top_k // cfg.num_experts)
    # dropped-token fraction from a fresh apply, BEFORE the timed steps
    # donate the state buffers
    (_, aux), _ = jax.jit(
        lambda s, x: model.apply(
            jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                         if jnp.issubdtype(p.dtype, jnp.floating) else p,
                         s.params), {}, x))(state, x)
    aux = {k: float(v) for k, v in aux.items()}
    dt, finite, spread = _time_steps(np, train_step, state, x, x)
    flops_per_token = (6 * n_active
                       + 12 * cfg.num_layers * T * cfg.d_model)
    mfu = (B * T / dt * flops_per_token / (peak_flops * n_chips)
           if peak_flops else None)
    return {
        "batch": B, "seq_len": T, "experts": cfg.num_experts,
        "top_k": cfg.top_k, "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 2),
        "tokens_per_sec_per_chip": round(B * T / dt / n_chips, 1),
        "n_params": int(n_params), "n_active_params": int(n_active),
        # MFU against ACTIVE flops — the honest MoE convention (dense MFU
        # would credit compute the routing deliberately skips)
        "mfu_active": round(mfu, 4) if mfu is not None else None,
        "dropped_token_fraction": round(float(aux["dropped_fraction"]), 4),
        # the dense-vs-MoE MFU gap, attributed (VERDICT r4 weak #4;
        # measured r5, benchmarks/decompose_moe.py, per-layer fwd+bwd at
        # these shapes): the expert matmuls themselves run at 0.91 MFU —
        # the gap is the GShard dispatch/combine ONE-HOT einsums, 1.73
        # ms/layer at 0.23 MFU (bandwidth-bound [G, Ng, E, C] one-hot
        # streams, ~cf*top_k*N*Ng elements). Group-size and gather-based
        # alternatives were swept/measured-rejected in r4; this is the
        # formulation's known static-shape tax.
        "bound_breakdown": {
            "expert_matmul_mfu": 0.91,
            "dispatch_combine_mfu": 0.23,
            "dispatch_combine_ms_per_layer_fwd_bwd": 1.73,
            "note": "measured v5e (decompose_moe.py); the one-hot "
                    "dispatch/combine streams bind, not the experts",
        },
        "loss_finite": finite,
        "spread": spread,
    }


def _opt_hbm_bytes_per_chip(jax, state, mesh):
    """Resident optimizer-state bytes on ONE chip: each leaf's per-device
    shard size (replicated leaves count in full — that is the point of
    the comparison)."""
    import numpy as _np

    del mesh
    total = 0
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        shard = leaf.sharding.shard_shape(leaf.shape)
        total += int(_np.prod(shard)) * leaf.dtype.itemsize
    return total


def _bench_zero1(jax, jnp, np, mesh, n_chips, peak_flops, tiny=False):
    """ZeRO-1 weight-update sharding A/B (train/step.py ``shard_update``,
    parallel/collectives.py): the SAME GPT-2 AdamW train step with the
    replicated update vs the RS -> shard-local-update -> AG one, reporting
    ``step_ms`` and per-chip resident opt-state bytes for both modes plus
    the measured ratios. The expected shape of the result on a dp=N mesh:
    opt bytes drop ~N x (AdamW's mu/nu dominate; small leaves stay
    replicated) at ~flat step time — an all-reduce IS a reduce-scatter +
    all-gather, so the transform trades no comm volume for the memory.
    On one chip (dp=1) the mode is a no-op and the stage reports that.

    ``tiny=True`` is the CPU-sized `make bench-smoke` shape: a 2-layer
    GPT-2 at T=64 on whatever devices exist — it exercises the whole
    plumbing (sharded init, both step programs, the byte meter) inside
    tier-1 time budgets, not a performance claim."""
    import dataclasses

    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    if tiny:
        cfg = dataclasses.replace(GPT2Config.tiny(), dropout_rate=0.0)
        B, T = 8 * max(n_chips, 1), 64
        iters = 4
    else:
        cfg = GPT2Config(dropout_rate=0.0)          # GPT-2-small
        B, T = 16 * n_chips, 1024
        iters = 20
    model = GPT2(cfg)
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))

    out = {"batch": B, "seq_len": T, "dp": n_chips, "optimizer": "adamw"}
    for mode, su in (("replicated", False), ("shard_update", True)):
        tx = build_optimizer("adamw", lr=3e-4, gamma=1.0,
                             steps_per_epoch=100, warmup_steps=10,
                             total_steps=1000)
        init_fn, train_step, _ = make_step_fns(
            model, tx, mesh, shard_update=su,
            compute_dtype=None if tiny else jnp.bfloat16)
        state = init_fn(jax.random.key(0))
        opt_bytes = _opt_hbm_bytes_per_chip(jax, state, mesh)
        if tiny:
            st, m = state, None
            import time as _t
            for _ in range(2):                       # compile + warm
                st, m = train_step(st, x, x)
            float(np.asarray(m["loss"]))
            t0 = _t.perf_counter()
            for _ in range(iters):
                st, m = train_step(st, x, x)
            loss = float(np.asarray(m["loss"]))
            dt = (_t.perf_counter() - t0) / iters
            finite = bool(np.isfinite(loss))
            spread = None
        else:
            dt, finite, spread = _time_steps(np, train_step, state, x, x,
                                             iters=iters)
        out[mode] = {
            "step_ms": round(dt * 1000, 2),
            "spread": spread,
            "opt_hbm_bytes_per_chip": int(opt_bytes),
            "opt_hbm_mb_per_chip": round(opt_bytes / 1e6, 2),
            "loss_finite": finite,
        }
    out["opt_bytes_ratio"] = round(
        out["replicated"]["opt_hbm_bytes_per_chip"]
        / max(out["shard_update"]["opt_hbm_bytes_per_chip"], 1), 2)
    out["step_ms_ratio"] = round(
        out["shard_update"]["step_ms"]
        / max(out["replicated"]["step_ms"], 1e-9), 3)
    if n_chips <= 1:
        out["note"] = ("dp=1: shard_update is a no-op (nothing to shard "
                       "across); ratios are expected ~1.0")
    return out


def _bench_grad_accum(jax, jnp, np, mesh, n_chips, peak_flops,
                      tiny=False):
    """Gradient-accumulation A/B (train/step.py ``accum_steps``): the
    SAME GPT-2 AdamW workload — effective batch B, N=4 microbatches —
    three ways:

    - ``legacy``: optax.MultiSteps, N host ``train_step`` dispatches per
      update, each paying a FULL dp gradient all-reduce (N x the wire
      bytes per update);
    - ``boundary``: step-level accumulation, one compiled step whose
      microbatch scan accumulates local grads and reduces ONCE at the
      boundary (single-shot: all leaves reduce before the update);
    - ``bucketed``: same, boundary pipelined over parameter buckets so
      bucket k's reduce-scatter overlaps bucket k-1's optimizer update
      and all-gather (DDP bucket_cap_mb; bit-identical to ``boundary``).

    Records ``step_ms`` per UPDATE, the gradient wire bytes per update
    (boundary: counted from the jaxpr's explicit collectives via
    ``collectives.grad_collective_stats``; legacy: N x the same leaves,
    reduced once per microbatch by the partitioner), and best-effort
    peak-HBM from XLA's memory analysis. ``tiny=True`` is the CPU-sized
    `make bench-smoke` shape (2-layer GPT-2, T=64, faked 4-device mesh)
    asserting the structural claims: zero in-scan collectives, an
    N-independent boundary count, >= N x byte reduction, and a step_ms
    no worse than the legacy path's N dispatches."""
    import dataclasses
    import warnings

    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.parallel import collectives as coll
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    N = 4
    if tiny:
        cfg = dataclasses.replace(GPT2Config.tiny(), dropout_rate=0.0)
        B, T = 8 * max(n_chips, 1), 64
        iters, compute_dtype = 4, None
    else:
        cfg = GPT2Config(dropout_rate=0.0)          # GPT-2-small
        B, T = 16 * n_chips, 1024
        iters, compute_dtype = 20, jnp.bfloat16
    model = GPT2(cfg)
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))
    # the legacy path consumes the same B rows as N separate microbatches
    x_micro = jax.device_put(x[:B // N], batch_sharding(mesh, 2))

    def adamw(grad_accum=1):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return build_optimizer("adamw", lr=3e-4, gamma=1.0,
                                   steps_per_epoch=100, warmup_steps=10,
                                   total_steps=1000, grad_accum=grad_accum)

    def measure(train_step, state, xx, calls_per_update):
        st = {"s": state, "m": None}

        def one_update():
            for _ in range(calls_per_update):
                st["s"], st["m"] = train_step(st["s"], xx, xx)

        for _ in range(2):
            one_update()                                # compile + warm
        float(np.asarray(st["m"]["loss"]))
        t0 = time.perf_counter()
        for _ in range(iters):
            one_update()
        loss = float(np.asarray(st["m"]["loss"]))
        return ((time.perf_counter() - t0) / iters,
                bool(np.isfinite(loss)))

    def peak_hbm(train_step, state, xx):
        try:
            mem = train_step.lower(state, xx, xx).compile() \
                .memory_analysis()
            return int(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                       + mem.output_size_in_bytes)
        except Exception:  # noqa: BLE001 — best-effort (CPU backends)
            return None

    out = {"batch_effective": B, "seq_len": T, "accum_steps": N,
           "dp": n_chips, "optimizer": "adamw"}
    # grad wire bytes per update, counted from the step-level path's
    # explicit jaxpr collectives; the legacy path reduces the same
    # leaves once per microbatch (partitioner-inserted, not visible in
    # its jaxpr) -> N x the boundary bytes
    stats = {}
    for mode, kw, calls in (
            ("legacy", None, N),
            ("boundary", {"accum_steps": N, "accum_bucket_mb": 0}, 1),
            ("bucketed", {"accum_steps": N,
                          "accum_bucket_mb": 0.25 if tiny else None}, 1)):
        if mode == "legacy":
            init_fn, train_step, _ = make_step_fns(
                model, adamw(grad_accum=N), mesh, donate=False,
                compute_dtype=compute_dtype)
            xx = x_micro
        else:
            init_fn, train_step, _ = make_step_fns(
                model, adamw(), mesh, donate=False,
                compute_dtype=compute_dtype, **kw)
            xx = x
        state = init_fn(jax.random.key(0))
        if mode != "legacy":
            stats[mode] = coll.grad_collective_stats(
                train_step, state, xx, xx, dp_axes=coll.dp_axes(mesh))
        dt, finite = measure(train_step, state, xx, calls)
        out[mode] = {
            "step_ms_per_update": round(dt * 1000, 2),
            "dispatches_per_update": calls,
            "peak_hbm_bytes": peak_hbm(train_step, init_fn(
                jax.random.key(0)), xx),
            "loss_finite": finite,
        }
    boundary_bytes = stats["boundary"]["bytes"]
    out["boundary"]["grad_collectives_per_update"] = \
        stats["boundary"]["boundary"]
    out["boundary"]["grad_collectives_in_scan"] = \
        stats["boundary"]["in_loop"]
    out["boundary"]["grad_wire_bytes_per_update"] = boundary_bytes
    out["bucketed"]["grad_wire_bytes_per_update"] = \
        stats["bucketed"]["bytes"]
    out["legacy"]["grad_wire_bytes_per_update"] = boundary_bytes * N
    out["step_ms_ratio_boundary_vs_legacy"] = round(
        out["boundary"]["step_ms_per_update"]
        / max(out["legacy"]["step_ms_per_update"], 1e-9), 3)
    out["step_ms_ratio_bucketed_vs_boundary"] = round(
        out["bucketed"]["step_ms_per_update"]
        / max(out["boundary"]["step_ms_per_update"], 1e-9), 3)
    out["wire_bytes_reduction"] = float(N) if boundary_bytes else None
    if n_chips <= 1:
        out["note"] = ("dp=1: no cross-replica reduction exists; the A/B "
                       "still measures the dispatch fusion (N calls -> 1)")
    return out


def _bench_real_mnist(jax, jnp, np, mesh, n_chips):
    """Real-pixel accuracy rung (VERDICT r4 missing #4): when actual
    MNIST idx files are present locally (``$DCP_MNIST_DIR`` or ./data —
    this environment has no egress, so nothing is downloaded), train the
    reference ConvNet on the real 60k training images for 2 epochs with
    the reference optimizer stack and record TEST-set accuracy next to
    throughput — the one observable of ``/root/reference/main.py`` the
    synthetic stages cannot reproduce. Reference behavior note: the
    reference evaluates on its TRAIN set (SURVEY §A.1, fixed here) and
    reaches ~98-99% test accuracy in a couple of epochs at lr 1e-3
    Adadelta + StepLR(0.7)."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.data.datasets import load_mnist
    from distributed_compute_pytorch_tpu.data.loader import DeviceFeeder
    from distributed_compute_pytorch_tpu.models.convnet import ConvNet
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    data_dir = os.environ.get("DCP_MNIST_DIR", "./data")
    try:
        # synthetic_fallback=False is load-bearing: the loader's default
        # quietly substitutes synthetic images, which would record
        # fabricated "real-pixel" accuracy here
        train = load_mnist(data_dir, "train", synthetic_fallback=False)
        test = load_mnist(data_dir, "test", synthetic_fallback=False)
    except FileNotFoundError:
        return {"skipped": f"no MNIST idx files under {data_dir} "
                           f"(zero-egress environment; set DCP_MNIST_DIR)"}

    B = 128
    model = ConvNet()
    tx = build_optimizer("adadelta", lr=1e-3, gamma=0.7,
                         steps_per_epoch=len(train) // B)
    init_fn, train_step, eval_step = make_step_fns(model, tx, mesh)
    state = init_fn(jax.random.key(0))
    feed = DeviceFeeder(train, mesh, B, shuffle=True)
    # warm trace+compile OUTSIDE the timed wall (train_step donates its
    # state, so re-init after the throwaway step)
    for xw, yw in feed.epoch(0):
        _s, _m = train_step(state, xw, yw)
        float(np.asarray(_m["loss"]))
        break
    state = init_fn(jax.random.key(0))
    t0 = time.perf_counter()
    epochs = 2
    for ep in range(epochs):
        for x, y in feed.epoch(ep):
            state, metrics = train_step(state, x, y)
    float(np.asarray(metrics["loss"]))     # force completion
    wall = time.perf_counter() - t0

    eval_feed = DeviceFeeder(test, mesh, B, shuffle=False)
    acc = None
    # with_valid: 10000 % 128 != 0, so the feeder's wraparound rows carry
    # valid=0 and the counts are exact (reference double-counts, §A)
    for x, y, valid in eval_feed.epoch(0, with_valid=True):
        acc = eval_step(state, x, y, acc, valid=valid)
    correct = int(np.asarray(acc["correct"]))
    count = int(np.asarray(acc["count"]))
    return {
        "dataset": "mnist_real_idx", "epochs": epochs, "batch": B,
        "test_accuracy": round(correct / count, 4),
        "test_correct": f"{correct}/{count}",
        "train_samples_per_sec_per_chip":
            round(epochs * len(train) / wall / n_chips, 1),
        "note": "reference main.py evaluates on its train set (SURVEY "
                "§A.1); this rung reports honest TEST accuracy",
    }


def _bench_serve(jax, jnp, np, mesh, n_chips):
    """Continuous batching vs gang-scheduled static batching on ONE
    mixed-length request stream (VERDICT r4 missing #2).

    Workload: 96 seeded requests, prompts 16-96 tokens, budgets 24-96
    new tokens, Llama-125M int8 weights, 64 slots. Two schedules through
    the SAME ``serve.ContinuousBatcher`` harness (identical compiled
    ticks, identical per-segment host harvests — the comparison isolates
    the SCHEDULING):

    - ``continuous``: one session; a finished row's slot takes the next
      request at the pool's live position.
    - ``static``: requests ganged into batches of 64; each batch is a
      fresh session that admits everything at t=0 and runs until its
      LONGEST request finishes (classic static batching: short rows burn
      ticks to the batch max).

    Both schedules run on ONE ContinuousBatcher each, built at the SAME
    t_max (identical compiled tick programs, identical per-tick cache
    stream), warmed with a throwaway session and reset() before timing —
    so neither wall pays compile and the only difference between them is
    the scheduling.

    Primary metric: device-tick efficiency — useful tokens / (ticks x
    slots) — which does not depend on the host. Wall tok/s is also
    reported; it carries one device->host fetch per segment on both
    schedules (the two-length-diff decode stages carry the clean
    per-tick numbers)."""
    from distributed_compute_pytorch_tpu.models.llama import (
        LlamaConfig, LlamaLM)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)

    cfg = LlamaConfig()
    model = LlamaLM(cfg)
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params)
    params = jax.jit(quantize_params_int8)(params)

    rng = np.random.default_rng(0)
    reqs = [Request(tokens=[int(t) for t in
                            rng.integers(0, cfg.vocab_size,
                                         rng.integers(16, 97))],
                    max_new=int(rng.integers(24, 97)))
            for _ in range(96)]
    SLOTS, TB, SEG, TMAX = 64, 96, 24, 768

    def one_wall(cb, schedule):
        cb.reset()
        t0 = time.perf_counter()
        useful = ticks = 0
        if schedule == "continuous":
            outs = cb.serve([Request(list(r.tokens), r.max_new)
                             for r in reqs])
            useful = sum(len(o) for o in outs)
            ticks = cb.ticks
        else:
            for lo in range(0, len(reqs), SLOTS):
                cb.reset()
                outs = cb.serve([Request(list(r.tokens), r.max_new)
                                 for r in reqs[lo:lo + SLOTS]])
                useful += sum(len(o) for o in outs)
                ticks += cb.ticks
        return time.perf_counter() - t0, useful, ticks

    def run(cb, schedule, k=3):
        # best-of-K walls (variance discipline); tokens/ticks are
        # scheduling-deterministic, so only the wall varies. Wall 0 is
        # a discarded warmup: admission waves compile per wave size and
        # only a full session surfaces them all
        walls = []
        for i in range(k + 1):
            wall, useful, ticks = one_wall(cb, schedule)
            if i:
                walls.append(wall)
        best = min(walls)
        return {"useful_tokens": useful, "device_ticks": ticks,
                "tick_efficiency": round(useful / (ticks * SLOTS), 3),
                "wall_s": round(best, 2),
                "spread": round((max(walls) - best) / best, 4),
                "useful_tokens_per_sec_per_chip":
                    round(useful / best / n_chips, 1)}

    # ONE batcher per schedule, identical t_max (identical compiled tick
    # programs); run()'s discarded first session warms each, reset()
    # rewinds without recompiling — the timed walls pay zero
    # trace/compile
    smesh = mesh if n_chips > 1 else None
    cbs = {s: ContinuousBatcher(model, params, slots=SLOTS, t_max=TMAX,
                                prompt_buf=TB, segment=SEG, mesh=smesh)
           for s in ("continuous", "static")}

    cont = run(cbs["continuous"], "continuous")
    stat = run(cbs["static"], "static")
    # the unified telemetry view of the last continuous session (ISSUE 8):
    # legacy stats/waste plus the SLO histogram digests, one block
    cont["snapshot"] = cbs["continuous"].stats_snapshot()
    return {
        "model": "llama_125m_int8", "slots": SLOTS, "requests": len(reqs),
        "prompt_len": "16-96", "max_new": "24-96", "segment": SEG,
        "t_max": TMAX,
        "mesh": dict(smesh.shape) if smesh is not None else None,
        "continuous": cont, "static_gang": stat,
        "efficiency_gain": round(cont["tick_efficiency"]
                                 / stat["tick_efficiency"], 2),
        "spread": max(cont["spread"], stat["spread"]),
        "note": "one warmed+reset batcher per schedule at equal t_max — "
                "identical compiled ticks, zero compile in the walls; "
                "per-segment harvest fetch "
                "overlaps the next segment's execution on both "
                "schedules; best-of-3 walls",
    }


def _bench_serve_long_stream(jax, jnp, np, mesh, n_chips):
    """Per-row-horizon serving (the lockstep-horizon fix): ONE session
    over a mixed-length stream whose total decode ticks exceed what the
    old shared-position design could hold in its cache at all.

    Workload: 192 seeded requests, prompts 16-96 tokens, budgets 24-96
    new tokens, Llama-125M int8 weights, 32 slots, t_max=192 — the old
    design needed t_max >= prompt_buf + total segment-rounded ticks
    (tens of thousands of slots here) or it raised mid-run; per-row
    positions recycle each row in place, so the same stream completes
    in a 192-slot cache. Reports useful tok/s (``serve_tok_s``) and the
    slot-utilization fraction useful/(ticks x slots); per-tick decode
    cost comparability with the lockstep baseline is covered by the
    decode stages above (identical compiled tick math)."""
    from distributed_compute_pytorch_tpu.models.llama import (
        LlamaConfig, LlamaLM)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)

    cfg = LlamaConfig()
    model = LlamaLM(cfg)
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params)
    params = jax.jit(quantize_params_int8)(params)

    rng = np.random.default_rng(1)
    reqs = [Request(tokens=[int(t) for t in
                            rng.integers(0, cfg.vocab_size,
                                         rng.integers(16, 97))],
                    max_new=int(rng.integers(24, 97)))
            for _ in range(192)]
    SLOTS, TB, TMAX = 32, 96, 192
    smesh = mesh if n_chips > 1 else None

    def run_at_segment(seg, walls_k):
        """Best-of-K timed sessions at one segment length, with the
        waste attribution from the (deterministic) schedule."""
        cb = ContinuousBatcher(model, params, slots=SLOTS, t_max=TMAX,
                               prompt_buf=TB, segment=seg, mesh=smesh)
        # warm with ONE FULL session, not a single request: admission
        # waves compile per wave SIZE, and the stream's wave sizes only
        # all appear across a whole session — without this the first
        # timed wall absorbs those compiles and the spread lies
        walls = []
        for i in range(walls_k + 1):
            cb.reset()
            t0 = time.perf_counter()
            outs = cb.serve([Request(list(r.tokens), r.max_new)
                             for r in reqs])
            if i:                       # wall 0 is the compile warmup
                walls.append(time.perf_counter() - t0)
        best = min(walls)
        useful = sum(len(o) for o in outs)
        total_row_ticks = cb.ticks * SLOTS
        # waste attribution (the old prose knob guidance, replaced by
        # numbers): tail = ticks planned for live rows that produced no
        # kept token (segment rounding + post-eos overlap lag);
        # admission_lag/drain = parked row-ticks with/without work left
        tail = cb.waste["planned_ticks"] - useful
        return {
            "segment": seg,
            "useful_tokens": useful,
            "session_ticks": cb.ticks,
            "slot_utilization": round(useful / total_row_ticks, 3),
            "serve_tok_s": round(useful / best, 1),
            "serve_tok_s_per_chip": round(useful / best / n_chips, 1),
            "wall_s": round(best, 2),
            "spread": round((max(walls) - best) / best, 4),
            "waste_breakdown": {
                "post_eos_budget_tail": round(tail / total_row_ticks, 3),
                "admission_lag": round(
                    cb.waste["parked_admission_lag"] / total_row_ticks, 3),
                "final_drain": round(
                    cb.waste["parked_drain"] / total_row_ticks, 3),
            },
            "transport": dict(cb.stats),
            "snapshot": cb.stats_snapshot(),
        }

    SEG = 24
    head = run_at_segment(SEG, walls_k=3)        # the headline point
    # 3-point segment sweep (1 wall each): the admission-granularity vs
    # host-round-trip trade, measured instead of prose
    sweep = {f"seg{s}": {k: v for k, v in
                         run_at_segment(s, walls_k=1).items()
                         if k != "snapshot"}     # headline carries it
             for s in (12, 48)}
    sweep[f"seg{SEG}"] = {k: head[k] for k in
                          ("serve_tok_s", "slot_utilization",
                           "waste_breakdown")}
    old_horizon_ticks = TMAX - TB   # all the old design could ever tick
    return {
        "model": "llama_125m_int8", "slots": SLOTS, "requests": len(reqs),
        "prompt_len": "16-96", "max_new": "24-96", "segment": SEG,
        "t_max": TMAX,
        "mesh": dict(smesh.shape) if smesh is not None else None,
        **{k: v for k, v in head.items() if k != "segment"},
        "ticks_vs_old_horizon": round(head["session_ticks"]
                                      / old_horizon_ticks, 1),
        "segment_sweep": sweep,
        # the ROADMAP hardware goal this stage tracks: >= 3x the r05
        # 3,374 useful tok/s/chip measured when every segment's harvest
        # serialised a ~130 ms fetch between dispatches
        "target_tok_s_per_chip": 10000,
        "note": "best-of-3 walls; overlapped dispatch/harvest (segment "
                "N+1 dispatched before N's fetch) + batched admission "
                f"waves; the stream needs {head['session_ticks']} ticks "
                f"vs the {old_horizon_ticks}-tick shared horizon the "
                "same cache allowed under lockstep positions",
    }


def _bench_eval(jax, jnp, np, mesh, n_chips):
    """Eval-pass throughput (the reference's test() role, main.py:70-95):
    GPT-2-small bf16 eval steps chained through the device-side metrics
    accumulator, samples/sec/chip."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    B, T = 16 * n_chips, 1024
    cfg = GPT2Config(dropout_rate=0.0)
    model = GPT2(cfg)
    tx = build_optimizer("adamw", lr=3e-4, gamma=1.0, steps_per_epoch=100)
    init_fn, _, eval_step = make_step_fns(model, tx, mesh,
                                          compute_dtype=jnp.bfloat16)
    state = init_fn(jax.random.key(0))
    x = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size,
                           jnp.int32),
        batch_sharding(mesh, 2))
    acc = None
    for _ in range(3):
        acc = eval_step(state, x, x, acc)
    float(np.asarray(acc["loss_sum"]))

    def time_n(n):
        nonlocal acc
        t0 = time.perf_counter()
        for _ in range(n):
            acc = eval_step(state, x, x, acc)
        np.asarray(acc["loss_sum"])
        return time.perf_counter() - t0

    dt, spread = _two_length_dt(time_n, 20, repeats=3)
    return {
        "batch": B, "seq_len": T, "step_ms": round(dt * 1000, 2),
        "samples_per_sec_per_chip": round(B / dt / n_chips, 2),
        "tokens_per_sec_per_chip": round(B * T / dt / n_chips, 1),
        "spread": spread,
    }


def _bench_decode(jax, jnp, np, mesh, n_chips, which: str = "gpt2",
                  quantize: bool = False, b_per_chip: int = 16):
    """KV-cache decode throughput (the inference path the reference never
    had): ``b_per_chip`` sequences/chip (default 16; the B=64 stage is
    the throughput-serving point), prompt 128, greedy, bf16 params, batch
    sharded over the data axis so every chip decodes. ``which`` picks the
    family — the Llama entry shows what GQA buys at decode time (4 kv
    heads vs GPT-2's 12 = a third of the cache bandwidth per tick).

    Timed as wall(prompt+256 new) - wall(prompt+128 new) over the extra
    128 ticks — the difference cancels BOTH the prefill cost and the
    constant dispatch+fetch overhead, leaving pure per-tick decode
    time.

    Roofline attribution (VERDICT r3 #2): decode is HBM-bound; a tick
    must stream every parameter (bf16) plus the K/V cache the masked
    attention reads (full ``t_max`` window, all layers). The record
    reports that byte model, the implied floor, and the achieved
    fraction. The old ~2.6x gap to the weights-only floor was the KV
    cache being COPIED every tick by XLA's non-aliased
    dynamic-update-slice — fixed by the in-place Pallas slot write
    (``ops/pallas/cache_update.py``).

    Component attribution (VERDICT r4 weak #1-3; measured r5 via
    benchmarks/decompose_decode.py + targeted A/B probes, v5e B=16
    t_max=384 — the ``bound_breakdown`` in the record): the remaining
    gap between tick and floor decomposes into (1) the cache-window
    stream achieving ~0.74 of spec bandwidth (gpt2's 226 MB MHA cache
    dominates its floor, hence its lower overall fraction vs GQA
    llama's 75 MB), (2) the B=16 vocab readout matmul at ~0.44 of its
    byte floor for gpt2's tied 77 MB table (llama's untied 49 MB head
    reaches ~0.88; pre-transposing the tied table and padding 50257 ->
    50304/50432 were probed and measured FLAT — it is a small-batch
    matmul effect, not layout), and (3) per-layer small-op latency.
    The weight stream itself runs at ~0.93 of spec, which is why int8
    (halving only the weight slice) shrinks the FLOOR faster than the
    TICK and the efficiency FRACTION drops even as absolute tok/s
    improves — the int8 win is real but bounded by the int8-independent
    components. The kv-pair one-window insert (cache_update.py)
    replaced a 0.19-0.27 ms/tick per-array write path; most of that
    overhead was overlapped with compute in situ, so the end-to-end
    gain is ~0.02-0.05 ms (llama 0.709 -> ~0.74 efficiency), and the
    whole-model-stacked deferred-write variant measured-REGRESSED
    (aliasing loss -> full cache copy; see cache_update.py)."""
    from distributed_compute_pytorch_tpu.core.mesh import batch_sharding
    from distributed_compute_pytorch_tpu.infer import make_generate_fn

    B, T0 = b_per_chip * n_chips, 128
    if which == "llama":
        from distributed_compute_pytorch_tpu.models.llama import (
            LlamaConfig, LlamaLM)
        cfg = LlamaConfig()
        model = LlamaLM(cfg)
    elif which == "moe":
        # the train rung's 8-expert geometry (453M params). Every tick's
        # dispatch einsum touches ALL experts' FFN weights (static
        # shapes), so the per-tick weight stream is the full 8-expert
        # set — the measured cost of serving MoE on one chip, and the
        # bytes EP sharding divides by the expert-axis size on a pod
        # (tests/test_moe_generate.py pins the sharded layout). Decode
        # ticks are full-capacity/no-drop by construction;
        # eval_capacity_factor 2.0 governs the prefill
        # (models/moe.py::MoEBlock docstring).
        from distributed_compute_pytorch_tpu.models.moe import (
            MoETransformerConfig, MoETransformerLM)
        cfg = MoETransformerConfig(num_experts=8, top_k=2,
                                   moe_group_size=512, capacity_factor=1.0,
                                   eval_capacity_factor=2.0,
                                   dropout_rate=0.0)
        model = MoETransformerLM(cfg)
    else:
        from distributed_compute_pytorch_tpu.models.gpt2 import (
            GPT2, GPT2Config)
        cfg = GPT2Config(dropout_rate=0.0)
        model = GPT2(cfg)
    params, _ = model.init(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params)
    if quantize:
        # weight-only int8 (utils/quantize.py): halves the per-tick
        # weight stream; the mixed-dtype dot consumes int8 directly
        # (ops/int8_matmul.py docstring has the formulation A/B)
        from distributed_compute_pytorch_tpu.utils.quantize import (
            quantize_params_int8)
        params = jax.jit(quantize_params_int8)(params)
    prompt = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, T0), 0,
                           cfg.vocab_size, jnp.int32),
        batch_sharding(mesh, 2))
    # probe lengths derived from ONE constant so the runs keys and the
    # time_n lookups can't drift apart (both walls share t_max: the cache
    # size must be identical or the two-length diff stops cancelling)
    BASE = 128
    runs = {}
    for n in (BASE, 2 * BASE):
        gen = make_generate_fn(model, n, t_max=T0 + 2 * BASE)
        int(np.asarray(gen(params, prompt))[0, -1])   # compile + warm
        runs[n] = gen

    # K back-to-back generate calls per timed wall, one fetch at the end
    # (the device executes submitted programs in order, so the single
    # fetch forces all K). Rationale (r4 reconciliation): a single
    # wall(256)-wall(128) diff is ~65 ms of device time against
    # +-20-25 ms of per-call jitter — at that SNR the min-of-repeats
    # estimator can land anywhere in 0.26-0.81 ms/tick, including BELOW
    # the 0.40 ms HBM floor (measured r4: llama 0.257/0.504/0.793/0.808
    # across process restarts — the first is physically impossible, so
    # the estimator, not the device, was moving). With K=8 the diff
    # carries ~8x the device signal while per-call dispatch overhead
    # appears K times in BOTH walls and still cancels.
    K = 8

    def time_n(n):
        gen = runs[n // K]     # n is K*(generated tokens); keys come from
                               # the same BASE the probe below uses
        t0 = time.perf_counter()
        out = None
        for _ in range(K):
            out = gen(params, prompt)
        np.asarray(out[0, -1])
        return time.perf_counter() - t0

    per_tok, spread = _two_length_dt(time_n, K * BASE, repeats=5)

    # HBM byte model per tick: all params (bf16, or int8+scales when
    # quantized — counted from the actual leaf bytes) + the k+v cache
    # window the masked attention reads (t_max slots, kv-heads, all layers)
    n_weight_bytes = sum(l.size * l.dtype.itemsize
                         for l in jax.tree.leaves(params))
    hk, hd = model.kv_cache_spec()
    t_max = T0 + 2 * BASE
    # PER-CHIP bytes: the batch (and so the cache) shards over data;
    # weights are replicated — every chip streams all of them
    cache_bytes = 2 * (B // n_chips) * hk * t_max * hd * 2 * cfg.num_layers
    # the in-place Pallas slot write engages off-mesh only (a Mosaic
    # call cannot be partitioned — ops/pallas/cache_update.py); under
    # this stage's multi-chip mesh XLA's DUS COPIES the cache every
    # tick, so the honest floor must charge that read+write traffic too
    inplace = n_chips == 1
    copy_bytes = 0 if inplace else 2 * cache_bytes
    hbm_bw = _peak(_PEAK_HBM, jax.devices()[0].device_kind)
    floor_ms = (n_weight_bytes + cache_bytes + copy_bytes) / hbm_bw * 1e3
    return {
        "batch": B, "prompt_len": T0, "new_tokens": BASE,
        "per_tick_ms": round(per_tok * 1000, 3),
        "spread": spread,
        "decode_tokens_per_sec_per_chip": round(B / per_tok / n_chips, 1),
        "bound": "hbm_weights+kv_cache",
        "cache_write": "pallas_inplace" if inplace else "xla_dus_copy",
        "weights_mb": round(n_weight_bytes / 1e6, 1),
        "kv_cache_mb": round(cache_bytes / 1e6, 1),
        "roofline_ms": round(floor_ms, 3) if floor_ms else None,
        "hbm_efficiency": (round(floor_ms / (per_tok * 1e3), 3)
                           if floor_ms else None),
        # measured component bounds (docstring; decompose_decode.py) —
        # attached ONLY to the configuration they were measured at, so
        # a record from other hardware or batch never carries another
        # machine's constants as if they were part of the measurement
        "bound_breakdown": (
            {"weights_stream_eff": 0.93,
             "cache_window_stream_eff": 0.74,
             "vocab_readout_eff": 0.44 if which == "gpt2" else 0.88,
             "note": "measured v5e bf16 B=16 (decompose_decode.py); "
                     "small-batch vocab matmul and cache stream are "
                     "int8-independent, so int8 shrinks the floor "
                     "faster than the tick"}
            if (jax.devices()[0].device_kind == "TPU v5 lite"
                and b_per_chip == 16 and which in ("gpt2", "llama"))
            else {"note": "see benchmarks/decompose_decode.py for the "
                          "per-component attribution method"}),
    }


def _bench_attention(jax, jnp, np):
    """On-device flash-vs-dense timing: the python loop is folded into the
    compiled program (lax.scan, output chained into the next query), and the
    per-iteration time is the two-scan-length difference, so the single
    host fetch's constant cost never lands in a per-iteration number."""
    from jax import lax

    from distributed_compute_pytorch_tpu.ops.attention import (
        dot_product_attention)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)

    def scan_time(attn, q, k, v, ITERS):
        runs = {}
        for n in (ITERS, 2 * ITERS):
            @jax.jit
            def run(q, k, v, n=n):
                def body(qc, _):
                    return attn(qc, k, v), None   # output feeds next query
                o, _ = lax.scan(body, q, None, length=n)
                return o.mean().astype(jnp.float32)
            float(np.asarray(run(q, k, v)))       # compile + warm
            runs[n] = run

        def time_n(n):
            t0 = time.perf_counter()
            float(np.asarray(runs[n](q, k, v)))
            return time.perf_counter() - t0

        dt, spread = _two_length_dt(time_n, ITERS)
        return dt * 1000, spread

    out = {}
    # iters scaled so each workload carries >= ~50 ms of device work into
    # the two-length difference (flash T=1024 is ~0.1 ms/iter); the T=8192
    # rung is the long-context case where the dense path's [T, T] logits
    # (2.1 GB at B=1) start crowding HBM
    for T, B, iters in ((1024, 4, 500), (4096, 4, 100), (8192, 1, 40)):
        H, D = 8, 64
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
                   for kk in ks)
        from distributed_compute_pytorch_tpu.ops.attention import _pick_block
        blk = _pick_block(T)
        fl_ms, fl_spread = scan_time(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=blk, block_k=blk), q, k, v, iters)
        de_ms, de_spread = scan_time(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True), q, k, v, iters)
        out[f"t{T}"] = {"batch": B, "heads": H, "head_dim": D,
                        "flash_ms": round(fl_ms, 4),
                        "dense_ms": round(de_ms, 4),
                        "speedup": round(de_ms / fl_ms, 2),
                        "spread": max(fl_spread, de_spread)}
    return out


def zero1_smoke():
    """CPU-sized end-to-end run of the ZeRO-1 bench stage (`make
    bench-smoke`): tiny GPT-2, faked multi-device CPU mesh, both update
    modes, printed as one JSON line — exercises the bench plumbing (and
    asserts the ~N x opt-byte reduction) inside tier-1 time budgets."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_compute_pytorch_tpu.core.mesh import make_mesh

    n_chips = len(jax.devices())
    mesh = make_mesh("data=-1")
    rec = _bench_zero1(jax, jnp, np, mesh, n_chips, None, tiny=True)
    _print_record({"metric": "zero1_update_sharding_smoke",
                   "n_chips": n_chips, **rec})
    ratio = rec["opt_bytes_ratio"]
    if n_chips > 1 and not ratio > 1.5:
        raise SystemExit(f"opt_bytes_ratio {ratio} — update sharding did "
                         f"not shrink per-chip optimizer state")
    return 0


def grad_accum_smoke():
    """CPU-sized end-to-end run of the grad-accum bench stage (`make
    bench-smoke`): tiny GPT-2, faked 4-device CPU mesh, N=4. Asserts the
    structural contract the TPU numbers ride on — the compiled update
    holds ZERO grad-sized dp collectives inside the microbatch scan and
    an N-independent boundary count (one per leaf), the gradient wire
    bytes per update drop N x vs the per-micro-step legacy path, and
    one fused dispatch is no slower than the legacy path's N."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_compute_pytorch_tpu.core.mesh import make_mesh

    n_chips = len(jax.devices())
    mesh = make_mesh("data=-1")
    rec = _bench_grad_accum(jax, jnp, np, mesh, n_chips, None, tiny=True)
    _print_record({"metric": "grad_accum_boundary_smoke",
                   "n_chips": n_chips, **rec})
    checks = {
        "no_collectives_in_scan":
            rec["boundary"]["grad_collectives_in_scan"] == 0,
        "boundary_reduction_exists":
            rec["boundary"]["grad_collectives_per_update"] > 0,
        "wire_bytes_reduction_is_n":
            rec["legacy"]["grad_wire_bytes_per_update"]
            >= 4 * rec["boundary"]["grad_wire_bytes_per_update"] > 0,
        "bucketed_same_wire_bytes":
            rec["bucketed"]["grad_wire_bytes_per_update"]
            == rec["boundary"]["grad_wire_bytes_per_update"],
        # one fused dispatch vs N host dispatches: the step-level path
        # must not be slower (generous slack for CPU smoke jitter)
        "step_no_worse_than_legacy":
            rec["step_ms_ratio_boundary_vs_legacy"] <= 1.2,
        "losses_finite": all(rec[m]["loss_finite"]
                             for m in ("legacy", "boundary", "bucketed")),
    }
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"grad-accum smoke failed: {bad}")
    return 0


def serve_smoke():
    """CPU-sized end-to-end check of the serving loop's transport
    discipline (`make bench-smoke`): faked 4-device data x tensor mesh,
    tiny GPT-2, one long request pinning the pool live plus short
    requests churning admission waves. Asserts the overlap + batched
    admission invariants via the batcher's instrumented counters —
    exactly ONE device->host fetch per segment, every fetch except the
    final drain issued AFTER the next segment's dispatch, one multi-row
    prefill call per admission wave (3 calls for 9 requests here) — and
    that the KV cache actually lands sharded (rows over data, kv heads
    over tensor), inside tier-1 time budgets."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import dataclasses

    import jax
    import numpy as np

    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2,tensor=2")
    sharded = shard_pytree(params, pick_strategy(mesh, model), mesh)
    cb = ContinuousBatcher(model, sharded, slots=4, t_max=64,
                           prompt_buf=16, segment=4, mesh=mesh)
    rng = np.random.default_rng(0)

    def toks():
        return [int(t) for t in rng.integers(0, 256, 5)]

    reqs = [Request(toks(), 40)] + [Request(toks(), 4) for _ in range(8)]
    outs = cb.serve(reqs)
    assert all(len(o) == r.max_new for o, r in zip(outs, reqs))
    s, w = cb.stats, cb.waste
    useful = sum(len(o) for o in outs)
    checks = {
        # one harvest fetch per compiled segment, nothing else reads back
        "one_fetch_per_segment": s["fetches"] == s["segments"],
        # the overlap: every fetch except the terminal one was issued
        # with the NEXT segment already dispatched
        "dispatch_before_fetch":
            s["fetches_overlapped"] == s["fetches"] - 1,
        # batched admission: the 4-token heads share the ladder's 8-token
        # rung, one row a device in each dispatch, not one per request
        "batched_admission": (s["prefill_rows"] == len(reqs)
                              and s["prefill_calls"] < len(reqs)),
        "cache_sharded":
            not cb._caches[0]["kv"].sharding.is_fully_replicated,
        # every row-tick is attributed exactly once
        "waste_accounting": (
            w["planned_ticks"] + w["parked_admission_lag"]
            + w["parked_drain"] == cb.ticks * cb.B
            and w["planned_ticks"] >= useful),
    }
    _print_record({"metric": "serve_overlap_smoke",
                   "snapshot": cb.stats_snapshot(),
                   "stats": s, "waste": w, "useful_tokens": useful,
                   "cache_spec": str(cb._caches[0]["kv"].sharding.spec),
                   "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve smoke failed: {bad}")
    return 0


def serve_chaos_smoke():
    """CPU-sized chaos drill for the serve fault-tolerance subsystem
    (`make serve-chaos-smoke`, wired into `make bench-smoke`): tiny
    GPT-2, a 1-fault schedule (injected harvest exception at segment 2
    — where a real dead chip surfaces). Asserts the recovery contract:
    every request completes ok, the recovered streams are TOKEN-
    IDENTICAL to a fault-free run of the same workload (greedy and
    sampled rows — host-tracked prefixes + (seed, tokens-so-far)
    sampling keys make reconstruction exact), goodput under the fault
    stays > 0, and no slot leaks. Records recovery time and the
    goodput ratio vs the clean run."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.serve_lifecycle import (
        ChaosInjector)

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=4, t_max=64,
                           prompt_buf=8, segment=4)
    rng = np.random.default_rng(0)

    def reqs():
        out = []
        for i in range(10):
            r = Request([int(t) for t in rng.integers(0, 256, 5)], 12)
            if i % 5 == 4:            # sampled rows ride along
                r.temperature = 0.8
                r.seed = 100 + i
            out.append(r)
        return out

    workload = reqs()

    def clone():
        return [dataclasses.replace(r) for r in workload]

    # fault-free baseline (also warms the compile cache so both timed
    # walls measure serving, not tracing)
    cb.serve_detailed(clone())
    cb.reset()
    t0 = time.perf_counter()
    clean = cb.serve_detailed(clone())
    clean_wall = time.perf_counter() - t0
    cb.reset()
    chaos = ChaosInjector(fault_at_segment=2, fault_mode="raise")
    t0 = time.perf_counter()
    faulted = cb.serve_detailed(clone(), chaos=chaos)
    fault_wall = time.perf_counter() - t0
    useful = sum(len(r.tokens) for r in faulted if r.ok)
    goodput = useful / fault_wall
    checks = {
        "recovery_completes": all(r.ok for r in faulted),
        "one_fault_one_reconstruction":
            cb.stats["faults"] == 1 and cb.stats["reconstructions"] == 1,
        "token_parity_through_fault":
            [r.tokens for r in faulted] == [r.tokens for r in clean],
        "goodput_positive": goodput > 0,
        "zero_slot_leaks": cb.last_slot_leaks == 0,
        "recovery_time_recorded": cb.stats["recovery_s"] > 0,
    }
    _print_record({
        "metric": "serve_chaos_smoke",
        "useful_tokens": useful,
        "goodput_tok_s": round(goodput, 2),
        "goodput_ratio_vs_clean": round(
            goodput / (sum(len(r.tokens) for r in clean) / clean_wall),
            3),
        "recovery_s": round(cb.stats["recovery_s"], 4),
        "reconstruction_rows": cb.stats["reconstruction_rows"],
        "stats": cb.stats, "snapshot": cb.stats_snapshot(),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve chaos smoke failed: {bad}")
    return 0


def serve_prefix_smoke():
    """CPU-sized end-to-end check of the paged-KV prefix cache
    (`make serve-prefix-smoke`, wired into `make bench-smoke`): tiny
    GPT-2 serving a ZIPF-SHARED prompt stream — a few hot system
    prompts carrying most of the traffic mass, cold random tails — with
    the radix prefix cache ON vs OFF over the same block-pool engine.

    Asserts the acceptance contract: hit rate > 0 on the Zipf stream,
    served tokens TOKEN-IDENTICAL to the cache-off path, zero block and
    slot leaks after drain, prefill_tokens_saved > 0, and a
    time-to-first-token proxy (an admission-heavy warm-cache follow-up
    wave, best-of-3) that is not degraded vs always-prefill admission.
    Records prefill-bytes-saved (the K/V bytes the cache produced by
    lookup instead of compute) and the stream walls. The TTFT assert
    keeps generous CPU-smoke slack — the decisive wins are the
    deterministic counters; real TTFT numbers need the TPU bench."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=256))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    # Zipf-shared stream: 3 hot system prompts (21 tokens each — the
    # shared span deliberately ends MID-BLOCK so copy-on-write runs),
    # rank-weighted 1/k, plus a cold tail of one-off prompts
    hot = [[int(t) for t in rng.integers(0, 256, 21)] for _ in range(3)]
    zipf = np.array([1.0, 0.5, 1 / 3.0])
    zipf /= zipf.sum()
    reqs = []
    for _ in range(24):
        head = (hot[int(rng.choice(3, p=zipf))] if rng.random() < 0.85
                else [int(t) for t in rng.integers(0, 256, 21)])
        tail = [int(t)
                for t in rng.integers(0, 256, int(rng.integers(1, 4)))]
        reqs.append(Request(head + tail, 4))

    def clone(rs):
        return [dataclasses.replace(r) for r in rs]

    kw = dict(slots=4, t_max=64, prompt_buf=24, segment=4)
    off = ContinuousBatcher(model, params, **kw)
    on = ContinuousBatcher(model, params, prefix_cache=True, **kw)
    # warm every compile (incl. the attach-wave shapes) out of the walls
    off.serve(clone(reqs))
    on.serve(clone(reqs))

    def best_wall(cb, k=3):
        best, outs = None, None
        for _ in range(k):
            cb.reset()
            t0 = time.perf_counter()
            outs = cb.serve(clone(reqs))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best, outs

    wall_off, out_off = best_wall(off)
    wall_on, out_on = best_wall(on)
    s = dict(on.stats)
    leaks = (on.last_block_leaks, on.last_slot_leaks,
             off.last_block_leaks, off.last_slot_leaks)

    # TTFT proxy: one admission wave of hot-prefix requests + one
    # segment, against a WARM cache (no reset — the radix persists
    # across serve calls, the long-running-server shape). The cache-on
    # path admits by block lookup; cache-off re-prefills every prompt.
    follow = [Request(hot[0] + [7, i % 7], 4) for i in range(4)]

    def best_ttft(cb, k=3):
        best = None
        for _ in range(k):
            t0 = time.perf_counter()
            cb.serve(clone(follow))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    ttft_off = best_ttft(off)
    ttft_on = best_ttft(on)
    hk, hd = model.kv_cache_spec()
    n_layers = model.config.num_layers
    bytes_per_tok = n_layers * 2 * hk * hd * np.dtype(np.float32).itemsize
    checks = {
        "hit_rate_positive": s["prefix_hits"] > 0,
        "prefill_tokens_saved_positive": s["prefill_tokens_saved"] > 0,
        "token_parity_vs_cache_off": out_on == out_off,
        "zero_leaks": leaks == (0, 0, 0, 0),
        "cow_exercised": s["cow_copies"] > 0,
        # generous CPU slack: the counters above are the deterministic
        # contract; wall clocks on a contended CPU smoke only guard
        # against gross regression
        "ttft_not_degraded": ttft_on <= ttft_off * 2.0,
    }
    _print_record({
        "metric": "serve_prefix_smoke",
        "requests": len(reqs),
        "prefix_hits": s["prefix_hits"],
        "cached_prefix_tokens": s["cached_prefix_tokens"],
        "prefill_tokens_saved": s["prefill_tokens_saved"],
        "prefill_bytes_saved": s["prefill_tokens_saved"] * bytes_per_tok,
        "cow_copies": s["cow_copies"],
        "block_pool_occupancy": round(s["block_pool_occupancy"], 4),
        "stream_wall_s": {"cache_off": round(wall_off, 4),
                          "cache_on": round(wall_on, 4)},
        "ttft_proxy_s": {"cache_off": round(ttft_off, 4),
                         "cache_on": round(ttft_on, 4)},
        "snapshot": on.stats_snapshot(),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve prefix smoke failed: {bad}")
    return 0


def serve_tier_smoke():
    """CPU-sized end-to-end check of the hierarchical KV spill tier
    (`make serve-tier-smoke`, wired into `make bench-smoke`): tiny
    GPT-2 on a deliberately STARVED device pool serving the Zipf
    working set's adversarial schedule — 3 hot prefixes cycled
    round-robin, so the hot set always exceeds device capacity and
    plain LRU discards every head before its rehit — with the
    host+disk tier ON vs OFF (kv_tier.py, `--host_cache_mb` /
    `--disk_cache_dir`).

    Asserts the acceptance contract: spill-ON achieves prefix hits
    where spill-OFF gets exactly none, outputs token-identical to
    tier-off, the tier hit counters (host + disk) are positive with
    the host pool genuinely absorbing the overflow (occupancy > 0)
    while device-pool occupancy stays flat vs tier-off, warm-TTFT on
    a demoted prefix is not degraded vs cold prefill (generous CPU
    slack — the deterministic counters are the decisive contract; real
    TTFT numbers need the TPU bench), and zero slot/device-block/
    host-block leaks end to end."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import tempfile

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    # 3 hot 17-token prefixes (ending mid-block so COW attaches run),
    # cycled round-robin: the LRU-adversarial schedule of a Zipf hot
    # set that is 3x too big for the pool — 8 blocks hold at most one
    # cached head (3 blocks) next to a live row (4 blocks)
    hot = [[int(t) for t in rng.integers(0, 256, 17)] for _ in range(3)]
    reqs = [Request(hot[i % 3]
                    + [int(t) for t in rng.integers(0, 256, 2)], 4)
            for i in range(12)]

    def clone(rs):
        return [dataclasses.replace(r) for r in rs]

    kw = dict(slots=1, t_max=32, prompt_buf=24, segment=4,
              prefix_cache=True, pool_blocks=8)
    off = ContinuousBatcher(model, params, **kw)
    disk_dir = tempfile.mkdtemp(prefix="dcp_tier_smoke_")
    # host pool of 6 = two demoted heads: the third demotion must
    # cascade to disk, so the smoke crosses every tier edge
    on = ContinuousBatcher(model, params, **kw, host_cache_blocks=6,
                           disk_cache_dir=disk_dir)
    # warm every compile (incl. the promote program) out of the walls
    off.serve(clone(reqs[:4]))
    on.serve(clone(reqs[:4]))

    def best_wall(cb, k=2):
        best, outs = None, None
        for _ in range(k):
            cb.reset()
            t0 = time.perf_counter()
            outs = cb.serve(clone(reqs))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best, outs

    wall_off, out_off = best_wall(off)
    wall_on, out_on = best_wall(on)
    s_off, s_on = dict(off.stats), dict(on.stats)
    t = dict(on.tier)
    leaks = (on.last_slot_leaks, on.last_block_leaks,
             on.last_host_block_leaks,
             off.last_slot_leaks, off.last_block_leaks)

    # TTFT proxy: one hot-prefix request against the engines as the
    # stream left them — tier-on promotes the demoted head (one H2D
    # copy), tier-off re-prefills it cold. Serve calls include the
    # 4-token decode on both sides, so the delta is pure admission.
    follow = [Request(hot[0] + [7, 3], 4)]

    def best_ttft(cb, k=3):
        best = None
        for _ in range(k):
            t0 = time.perf_counter()
            cb.serve(clone(follow))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    ttft_off = best_ttft(off)
    ttft_on = best_ttft(on)
    checks = {
        "tier_off_gets_no_hits": s_off["prefix_hits"] == 0,
        "tier_on_gets_hits": s_on["prefix_hits"] > 0,
        "tier_hit_rate_positive": t["host_hits"] + t["disk_hits"] > 0,
        "disk_tier_crossed": t["disk_spills"] > 0,
        "token_parity_vs_tier_off": out_on == out_off,
        "host_absorbs_overflow": 0 < t["host_pool_occupancy"] <= 1,
        # the device pool is a FIXED allocation the tier never grows:
        # occupancy stays bounded at <= 1 of the configured pool while
        # the 3x-oversized working set lives in the spill tiers
        "device_occupancy_bounded": (
            0 < s_on["block_pool_occupancy"] <= 1.0),
        "zero_leaks": leaks == (0, 0, 0, 0, 0),
        # generous CPU slack (see docstring): counters are the contract
        "warm_ttft_not_degraded": ttft_on <= ttft_off * 2.0,
    }
    _print_record({
        "metric": "serve_tier_smoke",
        "requests": len(reqs),
        "prefix_hits": {"tier_off": s_off["prefix_hits"],
                        "tier_on": s_on["prefix_hits"]},
        "tier": t,
        "block_pool_occupancy": {
            "tier_off": round(s_off["block_pool_occupancy"], 4),
            "tier_on": round(s_on["block_pool_occupancy"], 4)},
        "stream_wall_s": {"tier_off": round(wall_off, 4),
                          "tier_on": round(wall_on, 4)},
        "ttft_proxy_s": {"cold_prefill": round(ttft_off, 4),
                         "warm_promote": round(ttft_on, 4)},
        "snapshot": on.stats_snapshot(),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve tier smoke failed: {bad}")
    return 0


def serve_spec_smoke():
    """CPU-sized end-to-end check of speculative decoding
    (`make serve-spec-smoke`, wired into `make bench-smoke`): tiny
    GPT-2 serving a REPETITIVE stream — looped token periods, the
    self-drafting n-gram proposer's best case — with ``speculate`` ON
    vs OFF on the same paged-pool engine.

    Asserts the acceptance contract: served tokens TOKEN-IDENTICAL to
    spec-off (the accept/reject rule is exact — this is the whole
    bargain), acceptance_rate > 0 on the repetitive stream, USEFUL
    tokens per verify window > 1 (each window costs one weight stream,
    like one plain tick, so >1 emitted/window is the throughput win
    mechanism), and zero slot/block leaks after drain. Records the
    stream walls with their best-of-3 spread for `bench-diff`. Wall
    SPEEDUP is deliberately not asserted here: a tiny CPU model is
    latency- not HBM-bound, so the verify window's arithmetic isn't
    free the way it is on hardware — the >1.5x useful-tok/s target on
    ``serve_long_stream`` (ISSUE 12) is a TPU bench number; this smoke
    pins the mechanism (emitted/window) that produces it."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.spec_decode import SpecConfig

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=256))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    # repetitive stream: looped periods (code/JSON-shaped decodes) plus
    # a few random prompts so the reject path runs in the same walls
    reqs = []
    for i in range(12):
        if i % 4 == 3:
            head = [int(t) for t in rng.integers(0, 256, 8)]
        else:
            period = [int(t) for t in rng.integers(0, 256, 3)]
            head = period * 4
        reqs.append(Request(head, 16))

    def clone(rs):
        return [dataclasses.replace(r) for r in rs]

    kw = dict(slots=4, t_max=64, prompt_buf=16, segment=4)
    off = ContinuousBatcher(model, params, **kw)
    on = ContinuousBatcher(model, params,
                           speculate=SpecConfig(k=4), **kw)
    off.serve(clone(reqs))        # warm every compile out of the walls
    on.serve(clone(reqs))

    def best_wall(cb, k=3):
        walls, outs = [], None
        for _ in range(k):
            cb.reset()
            t0 = time.perf_counter()
            outs = cb.serve(clone(reqs))
            walls.append(time.perf_counter() - t0)
        best = min(walls)
        spread = round((max(walls) - best) / best, 4) if best > 0 else 0.0
        return best, spread, outs

    wall_off, spread_off, out_off = best_wall(off)
    wall_on, spread_on, out_on = best_wall(on)
    s = dict(on.spec)
    row_verifies = s["proposed"] / 4            # k drafts per window
    tok_per_window = (s["emitted_tokens"] / row_verifies
                      if row_verifies else 0.0)
    leaks = (on.last_block_leaks, on.last_slot_leaks,
             off.last_block_leaks, off.last_slot_leaks)
    checks = {
        "token_parity_vs_spec_off": out_on == out_off,
        "acceptance_rate_positive": s["acceptance_rate"] > 0,
        "useful_tokens_per_window_gt_1": tok_per_window > 1.0,
        "zero_leaks": leaks == (0, 0, 0, 0),
        "never_autodisabled": s["autodisabled"] == 0,
    }
    _print_record({
        "metric": "serve_spec_smoke",
        "requests": len(reqs),
        "speculate_k": 4,
        "proposed": s["proposed"],
        "accepted": s["accepted"],
        "acceptance_rate": round(s["acceptance_rate"], 4),
        "wasted_verify_tokens": s["wasted_verify_tokens"],
        "verify_segments": s["verify_segments"],
        "emitted_tokens": s["emitted_tokens"],
        "useful_tokens_per_window": round(tok_per_window, 3),
        "stream_wall_s": {"spec_off": round(wall_off, 4),
                          "spec_on": round(wall_on, 4)},
        "spread": max(spread_off, spread_on),
        "target": ("useful tok/s > 1.5x spec-off on serve_long_stream "
                   "(TPU hardware bench; see DESIGN.md)"),
        "snapshot": on.stats_snapshot(),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve spec smoke failed: {bad}")
    return 0


def serve_kvq_smoke():
    """CPU-sized bf16-vs-int8 A/B of the quantized KV pool
    (`make serve-kvq-smoke`, wired into `make bench-smoke`): the same
    Poisson-bursty hot-prefix stream served by two engines that differ
    only in ``--kv_dtype``, then every serving drill repeated UNDER
    int8 — speculative decode, host+disk tier spill, prefix handoff
    (plus its corrupt-scale and dtype-stamp declines), and
    crash-restart recovery (reconstruction + journal replay).

    Asserts the relaxed parity contract of DESIGN.md "Quantized KV":
    greedy token match >= 99% vs bf16 on the stream (every mismatch is
    flight-recorded via ``record_greedy_mismatch``), per-position KL
    finite and small on a shared probe prefix, and >= 1.8x resident
    prefix tokens per pool byte — measured from the live cache arrays,
    with float KV slabs normalized to the 2-byte dtype they ship as on
    hardware (CPU runs hold f32 stand-ins; scales count at their full
    f32 width). The head geometry matters for that headline: int8
    costs hd+4 bytes per cached token-head (the +4 is the per-block
    f32 scale) vs 2*hd for bf16, so the ratio 2*hd/(hd+4) only clears
    1.8x at hd >= 40 — the smoke uses a production-shaped hd=64
    (1.88x) rather than tiny()'s hd=16 (1.6x), which would fail by
    geometry, not by implementation. Zero slot/block/host-block leaks
    across all engines; what stays EXACT under int8: radix keys, CRC
    stamps, journal replay."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from distributed_compute_pytorch_tpu import serve_journal
    from distributed_compute_pytorch_tpu.kv_pool import TIER_DEVICE
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.serve_lifecycle import (
        ChaosInjector)
    from distributed_compute_pytorch_tpu.spec_decode import SpecConfig

    cfg = dataclasses.replace(GPT2Config.tiny(), d_model=128,
                              num_heads=2, max_seq_len=256)
    model = GPT2(cfg)
    params, _ = model.init(jax.random.key(1))
    rng = np.random.default_rng(0)

    # one Poisson stream: burst sizes ~ Poisson(3), each request a hot
    # 33-token prefix (ending mid-block, so COW attaches run) plus a
    # random 2-token tail — the arrival process of a shared-prompt
    # serving fleet, replayed identically on both engines
    hot = [[int(t) for t in rng.integers(0, 256, 33)] for _ in range(3)]
    waves, i = [], 0
    while i < 30:
        k = max(1, int(rng.poisson(3.0)))
        waves.append([Request(hot[(i + j) % 3]
                              + [int(t) for t in rng.integers(0, 256, 2)],
                              6) for j in range(k)])
        i += k

    def clone(rs):
        return [dataclasses.replace(r) for r in rs]

    kw = dict(slots=2, t_max=96, prompt_buf=48, segment=4,
              prefix_cache=True, pool_blocks=24, kv_block_tokens=32)
    bf = ContinuousBatcher(model, params, **kw)
    q8 = ContinuousBatcher(model, params, **kw, kv_dtype="int8")
    bf.serve(clone(waves[0]))     # warm every compile out of the walls
    q8.serve(clone(waves[0]))

    def run(cb, k=2):
        best, outs = None, None
        for _ in range(k):
            cb.reset()
            outs = []
            t0 = time.perf_counter()
            for w in waves:
                outs.extend(cb.serve(clone(w)))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best, outs

    wall_bf, out_bf = run(bf)
    wall_q8, out_q8 = run(q8)
    # divergence-aware match accounting: compare each request's stream
    # up to and including its FIRST mismatch — tokens after a flip are
    # conditioned on a different prefix, so counting the cascaded
    # suffix would charge one near-tie argmax flip many times over
    total = match = 0
    for si, (ws, gs) in enumerate(zip(out_bf, out_q8)):
        for pos, (a, b) in enumerate(zip(ws, gs)):
            total += 1
            if a == b:
                match += 1
            else:
                q8.record_greedy_mismatch(pos, a, b, stream=f"req{si}")
                break
    match_rate = match / total

    # capacity headline: resident prefix tokens per pool byte, from the
    # engines as the stream left them (same stream + same block
    # geometry -> same resident entries; only the bytes differ)
    def tokens_per_byte(cb):
        ents = [e for e in cb._radix.entries if e.tier == TIER_DEVICE]
        toks = sum(e.n_tokens for e in ents)
        blocks = sum(len(e.blocks) for e in ents)
        per_block = 0
        for c in cb._caches:
            for name, leaf in c.items():
                els = int(np.prod(leaf.shape)) // leaf.shape[1]
                if (name == "kv"
                        and jnp.issubdtype(leaf.dtype, jnp.floating)):
                    itemsize = 2   # f32 CPU stand-in ships as bf16
                else:
                    itemsize = np.dtype(leaf.dtype).itemsize
                per_block += els * itemsize
        return toks, blocks * per_block, toks / (blocks * per_block)

    toks_bf, bytes_bf, tpb_bf = tokens_per_byte(bf)
    toks_q8, bytes_q8, tpb_q8 = tokens_per_byte(q8)
    capacity_ratio = tpb_q8 / tpb_bf

    # per-position KL on a shared probe prefix (the recorded A/B the
    # parity contract asks for — bounded error, not bit equality)
    lb = bf.logit_probe(hot[0][:12])
    lq = q8.logit_probe(hot[0][:12])
    p = jax.nn.softmax(jnp.asarray(lb), axis=-1)
    kl = np.asarray((p * (jax.nn.log_softmax(jnp.asarray(lb), axis=-1)
                          - jax.nn.log_softmax(jnp.asarray(lq),
                                               axis=-1))).sum(-1))

    # ---- drills, all under int8 ----
    # handoff: export from the warm int8 engine, import into a fresh
    # peer, then serve the handed-off prefix on both and compare
    h_req = [Request(hot[0] + [9, 1], 6)]
    pay = q8.export_prefix(hot[0] + [9])
    dst = ContinuousBatcher(model, params, **kw, kv_dtype="int8")
    imported = pay is not None and dst.import_prefix(pay)
    h_got = dst.serve(clone(h_req))
    h_want = q8.serve(clone(h_req))
    handoff_ok = (imported and h_got == h_want
                  and dst.stats["prefix_hits"] >= 1)

    # speculative decode under int8: repetitive stream (the n-gram
    # proposer's best case), spec engine vs the plain int8 engine
    sreqs = []
    for j in range(6):
        period = [int(t) for t in rng.integers(0, 256, 3)]
        sreqs.append(Request(period * 4, 16))
    spec = ContinuousBatcher(model, params, **kw, kv_dtype="int8",
                             speculate=SpecConfig(k=4))
    spec_want = q8.serve(clone(sreqs))
    spec_got = spec.serve(clone(sreqs))

    # declines must never raise: a flipped scale byte fails the CRC
    # stamp (satellite: scale arrays are CRC-covered end to end), and a
    # dtype-stamp mismatch is refused with its own counter
    pay2 = q8.export_prefix(hot[0] + [9])
    sc = np.array(pay2["scale"])
    sc.flat[0] += 1.0
    corrupt_declined = not spec.import_prefix({**pay2, "scale": sc})
    dtype_declined = not bf.import_prefix(q8.export_prefix(hot[0] + [9]))

    # host+disk tier spill under int8: starved device pool (5 blocks)
    # + 2-block host cache force demotions to cascade to disk AND
    # promote back; outputs must match the unspilled int8 engine
    tkw = dict(kw, slots=1, pool_blocks=5)
    tier = ContinuousBatcher(model, params, **tkw, kv_dtype="int8",
                             host_cache_blocks=2,
                             disk_cache_dir=tempfile.mkdtemp(
                                 prefix="dcp_kvq_smoke_"))
    treqs = [Request(hot[j % 3] + [int(t)
                                   for t in rng.integers(0, 256, 2)], 6)
             for j in range(6)]
    tier_got = [tier.serve(clone([r])) for r in treqs]
    tier_want = [q8.serve(clone([r])) for r in treqs]
    tt = dict(tier.tier)

    # crash-restart under int8: a mid-stream device fault reconstructs
    # from the journaled token streams; then a "restarted process"
    # recovers the WAL (config frame stamped with the pool dtype, the
    # satellite contract) and dedups the completed sessions
    jd = tempfile.mkdtemp(prefix="dcp_kvq_wal_")
    rec = ContinuousBatcher(model, params, **kw, kv_dtype="int8",
                            journal_dir=jd)
    rec._journal.config({"kv_dtype": "int8"})
    rreqs = clone(waves[0])
    for j, r in enumerate(rreqs):
        r.request_id = f"kvq-{j:02d}"
    res = rec.serve_detailed(
        clone(rreqs), chaos=ChaosInjector(fault_at_segment=2,
                                          fault_mode="raise"))
    rec_want = q8.serve(clone(rreqs))
    rec._journal.close()
    man = serve_journal.recover(jd)
    replay = dst.serve_detailed(clone(rreqs), recovery=man)
    rec_ok = ([r.tokens for r in res] == rec_want
              and rec.stats["reconstructions"] >= 1
              and [r.tokens for r in replay] == rec_want)

    leaks = tuple(v for cb in (bf, q8, dst, spec, tier, rec)
                  for v in (cb.last_slot_leaks, cb.last_block_leaks,
                            cb.last_host_block_leaks))
    checks = {
        "greedy_match_ge_99pct": match_rate >= 0.99,
        "capacity_ratio_ge_1p8": capacity_ratio >= 1.8,
        "kl_finite_and_small": bool(np.isfinite(kl).all()
                                    and float(kl.max()) < 0.5),
        "hbm_bytes_saved_positive": q8.kvq["bytes_saved_hbm"] > 0,
        "quantized_blocks_positive": q8.kvq["quantized_blocks"] > 0,
        "spec_token_parity_int8": spec_got == spec_want,
        "spec_verify_ran": spec.spec["verify_segments"] >= 1,
        "tier_token_parity_int8": tier_got == tier_want,
        "tier_disk_crossed": tt["disk_spills"] > 0
                             and tt["disk_hits"] > 0,
        "tier_crc_clean": tt["disk_crc_miss"] == 0,
        "d2h_bytes_halved": tier.kvq["bytes_saved_d2h"] > 0,
        "handoff_roundtrip": handoff_ok,
        "handoff_bytes_saved": q8.kvq["bytes_saved_handoff"] > 0,
        "handoff_corrupt_scale_declines": corrupt_declined
            and spec.prefill["handoff_declined"] >= 1,
        "handoff_dtype_declines": dtype_declined
            and bf.kvq["handoff_dtype_declined"] >= 1,
        "crash_restart_recovery_int8": rec_ok,
        "journal_dtype_stamped": (man.config or {}).get(
            "kv_dtype") == "int8",
        "journal_replay_deduped": dst.journal["deduped_completions"] > 0,
        "zero_leaks": not any(leaks),
    }
    _print_record({
        "metric": "serve_kvq_smoke",
        "requests": len(out_q8),
        "greedy_decisions": total,
        "greedy_match_rate": round(match_rate, 4),
        "greedy_mismatches": int(q8.kvq["greedy_mismatches"]),
        "kl_per_position": {"mean": round(float(kl.mean()), 6),
                            "max": round(float(kl.max()), 6)},
        "resident_tokens_per_pool_byte": {
            "bf16": round(tpb_bf, 6), "int8": round(tpb_q8, 6),
            "ratio": round(capacity_ratio, 4)},
        "resident_prefix_tokens": {"bf16": toks_bf, "int8": toks_q8},
        "resident_pool_bytes": {"bf16": bytes_bf, "int8": bytes_q8},
        "kvq": dict(q8.kvq),
        "tier": tt,
        "stream_wall_s": {"bf16": round(wall_bf, 4),
                          "int8": round(wall_q8, 4)},
        "target": (">= 1.8x resident prefix tokens per HBM byte at "
                   "equal pool bytes (hd=64: 2*64/(64+4) = 1.88x)"),
        "snapshot": q8.stats_snapshot(),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve kvq smoke failed: {bad}")
    return 0


def serve_load_smoke():
    """Open-loop Poisson load drill for the telemetry subsystem
    (`make serve-load-smoke`, wired into `make bench-smoke`): tiny
    GPT-2, 16 requests offered at 8 req/s (obs.loadgen), spans traced
    through the serve loop. Asserts the ISSUE 8 acceptance contract:
    goodput > 0 with finite p99 TTFT, every request's tokens IDENTICAL
    to the same workload served without load shaping (arrival gating
    must never change outputs), zero slot/block leaks after drain, the
    span trace written during the drill validates as Chrome-trace JSON
    (matched B/E, monotonic timestamps), and the DISABLED-telemetry
    record path costs < 1% of a segment wall — computed from the
    measured no-op call cost times a generous per-segment call-site
    census, not a flaky timing A/B."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import math
    import tempfile

    import numpy as np  # noqa: F401 — loadgen pulls it; fail early here

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs import loadgen
    from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer, span, validate_chrome_trace)
    from distributed_compute_pytorch_tpu.serve import ContinuousBatcher

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=4, t_max=64,
                           prompt_buf=16, segment=4)

    spec = loadgen.LoadSpec(n_requests=16, rate_rps=8.0, seed=0,
                            prompt_len=(2, 10), max_new=(4, 12))
    load = loadgen.offered_load(spec)

    def clone(rs, zero_arrival=False):
        return [dataclasses.replace(
            r, arrival_s=0.0 if zero_arrival else r.arrival_s)
            for r in rs]

    # unloaded parity baseline — also warms every compile out of the
    # timed drill (greedy decode: tokens must not depend on arrivals)
    base = cb.serve_detailed(clone(load, zero_arrival=True))
    cb.reset()

    tracer = Tracer()
    prev = configure_tracer(tracer)
    try:
        report = loadgen.run_load(cb, clone(load))
    finally:
        configure_tracer(prev)
    trace_path = os.path.join(tempfile.gettempdir(),
                              "dcp_serve_load_trace.json")
    tracer.dump(trace_path)
    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    trace_errors = validate_chrome_trace(events)

    slo = report["slo"]
    p99_ttft = float(slo.get("ttft_s", {}).get("p99", float("nan")))

    # disabled-path overhead, deterministically: cost of one gated no-op
    # (histogram record + span enter/exit) times a generous per-segment
    # call-site census, as a fraction of the drill's measured segment wall
    obs_metrics.set_enabled(False)
    try:
        h = obs_metrics.Histogram("overhead_probe")
        N = 20000
        t0 = time.perf_counter()
        for _ in range(N):
            h.record(1.0)
            with span("noop"):
                pass
        per_call = (time.perf_counter() - t0) / N
    finally:
        obs_metrics.set_enabled(True)
    segments = max(1, report["snapshot"]["stats"]["segments"])
    seg_wall = report["wall_s"] / segments
    # census: ~8 span/instant sites per segment + 4 SLO records per
    # request amortised over the session's segments
    calls_per_segment = 8 + 4 * len(load) / segments
    overhead_frac = per_call * calls_per_segment / seg_wall

    checks = {
        "goodput_positive": report["goodput_tok_s"] > 0,
        "all_ok": report["ok"] == len(load),
        "p99_ttft_finite": math.isfinite(p99_ttft),
        "token_parity_with_unloaded":
            [r.tokens for r in report["results"]]
            == [r.tokens for r in base],
        "zero_slot_leaks": report["snapshot"]["slot_leaks"] == 0,
        "zero_block_leaks": report["snapshot"]["block_leaks"] == 0,
        "valid_chrome_trace": not trace_errors and len(events) > 0,
        "disabled_overhead_lt_1pct": overhead_frac < 0.01,
    }
    pct = {name: {k: slo.get(name, {}).get(k) for k in
                  ("count", "p50", "p95", "p99")}
           for name in ("queue_wait_s", "ttft_s", "tpot_s", "e2e_s")}
    _print_record({
        "metric": "serve_load_smoke",
        "offered_rate_rps": spec.rate_rps, "requests": len(load),
        "wall_s": round(report["wall_s"], 3),
        "goodput_tok_s": round(report["goodput_tok_s"], 2),
        "statuses": report["statuses"],
        "slo": pct,
        "trace_events": len(events),
        "trace_errors": trace_errors[:4],
        "disabled_overhead_frac": round(overhead_frac, 6),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve load smoke failed: {bad}")
    return 0


def serve_router_smoke():
    """Replica-set goodput + failover drill for the serve router
    (`make serve-router-smoke`, wired into `make bench-smoke`): tiny
    GPT-2, the obs.loadgen open-loop Poisson stream offered to a
    1-replica and a 3-replica ServeRouter, then to 3 replicas with one
    killed mid-stream. Every segment harvest carries an injected 80 ms
    `slow` chaos sleep standing in for real device latency (this
    container is a single CPU core: compute serialises across replica
    threads, but the sleeps — like real device waits — overlap, which
    is exactly the throughput a replica set buys). Asserts the ISSUE 11
    acceptance contract: 3-replica goodput scales > 1.5x over 1
    replica on the same offered load, goodput stays > 0 through a
    replica kill with every request completing token-identical to the
    unloaded single-replica reference, sessions actually migrate, and
    no survivor leaks a slot or block."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs import loadgen
    from distributed_compute_pytorch_tpu.serve import ContinuousBatcher
    from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
    from distributed_compute_pytorch_tpu.serve_router import ServeRouter

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    replicas = [ContinuousBatcher(model, params, slots=2, t_max=64,
                                  prompt_buf=12, segment=3,
                                  prefix_cache=True, max_recoveries=0)
                for _ in range(3)]

    spec = loadgen.LoadSpec(n_requests=18, rate_rps=50.0, seed=0,
                            prompt_len=(2, 10), max_new=(4, 12))
    load = loadgen.offered_load(spec)

    def clone(rs, zero_arrival=False):
        return [dataclasses.replace(
            r, arrival_s=0.0 if zero_arrival else r.arrival_s)
            for r in rs]

    SLOW_S = 0.08

    def slow():
        # every harvest sleeps SLOW_S: the simulated device latency the
        # replica threads overlap (fault_count bounds never bind)
        return ChaosInjector(fault_at_segment=0, fault_mode="slow",
                             slow_s=SLOW_S, fault_count=1_000_000)

    def reset():
        for r in replicas:
            r.reset()

    # unloaded, chaos-free parity reference — run on EVERY replica so
    # each one's jitted programs (per-batcher closures, not shared)
    # compile outside the timed runs
    base = None
    for rep in replicas:
        out = rep.serve_detailed(clone(load, zero_arrival=True))
        base = out if base is None else base
    reset()

    def run(router, chaos):
        t0 = time.monotonic()
        results = router.route(clone(load), chaos=chaos)
        wall = time.monotonic() - t0
        ok_tokens = sum(len(r.tokens) for r in results if r.ok)
        return {"wall_s": wall,
                "goodput_tok_s": ok_tokens / wall if wall > 0 else 0.0,
                "results": results}

    one = run(ServeRouter([replicas[0]]), {0: slow()})
    reset()
    three = run(ServeRouter(replicas), {i: slow() for i in range(3)})
    reset()
    # 3 replicas, one killed mid-stream (the survivors keep their
    # simulated device latency — failover is measured under load)
    killer = ServeRouter(replicas, jitter_seed=17)
    chaos = {0: slow(), 2: slow(),
             1: ChaosInjector(fault_at_segment=3, fault_mode="raise")}
    fail = run(killer, chaos)

    leaks = [(r.last_slot_leaks, r.last_block_leaks) for r in replicas]
    ratio = (three["goodput_tok_s"] / one["goodput_tok_s"]
             if one["goodput_tok_s"] > 0 else 0.0)
    checks = {
        "goodput_scales_gt_1p5x": ratio > 1.5,
        "goodput_positive_during_failover": fail["goodput_tok_s"] > 0,
        "all_ok_during_failover": all(r.ok for r in fail["results"]),
        "token_parity_during_failover":
            [r.tokens for r in fail["results"]]
            == [r.tokens for r in base],
        "sessions_migrated": killer.stats["migrations"] > 0,
        "zero_leaks": leaks == [(0, 0)] * 3,
    }
    _print_record({
        "metric": "serve_router_smoke",
        "replicas": 3, "requests": len(load),
        "offered_rate_rps": spec.rate_rps,
        "injected_harvest_latency_s": SLOW_S,
        "goodput_tok_s": {"one_replica": round(one["goodput_tok_s"], 2),
                          "three_replicas":
                              round(three["goodput_tok_s"], 2),
                          "three_with_kill":
                              round(fail["goodput_tok_s"], 2)},
        "wall_s": {"one_replica": round(one["wall_s"], 3),
                   "three_replicas": round(three["wall_s"], 3),
                   "three_with_kill": round(fail["wall_s"], 3)},
        "scaling_ratio": round(ratio, 3),
        "router": killer.stats_snapshot()["router"],
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve router smoke failed: {bad}")
    return 0


def serve_elastic_smoke():
    """Elastic-fleet drill (`make serve-elastic-smoke`, wired into
    `make bench-smoke`): an offered-load ramp hits a 1-replica fleet
    under serve_fleet.ElasticFleetController (max 3), with the same
    injected 80 ms per-harvest `slow` chaos the router smoke uses as
    stand-in device latency. The controller must scale up at its FIRST
    control step (goodput tracks the ramp within one scale period —
    asserted both ways: the decision fires immediately, and elastic
    goodput beats the fixed 1-replica fleet on the identical load),
    and a same-value weight push lands mid-ramp via the rolling
    upgrade walk with ZERO failed requests and exact token parity
    against the unloaded reference. Every member — original, added,
    retired — must end slot/block/host-leak-free, and the scale/
    upgrade events must be visible in the flight recorder."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs import flight, loadgen
    from distributed_compute_pytorch_tpu.serve import ContinuousBatcher
    from distributed_compute_pytorch_tpu.serve_fleet import (
        ElasticFleetController, ScalePolicy)
    from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
    from distributed_compute_pytorch_tpu.serve_router import ServeRouter

    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    kw = dict(slots=2, t_max=64, prompt_buf=12, segment=3,
              prefix_cache=True, max_recoveries=0)

    def build(p, wv, slot):
        return ContinuousBatcher(model, p, weights_version=wv, **kw)

    spec = loadgen.LoadSpec(n_requests=24, rate_rps=60.0, seed=3,
                            prompt_len=(2, 10), max_new=(4, 12))
    load = loadgen.offered_load(spec)

    def clone(rs, zero_arrival=False):
        return [dataclasses.replace(
            r, arrival_s=0.0 if zero_arrival else r.arrival_s)
            for r in rs]

    SLOW_S = 0.08

    def slow_chaos():
        # simulated device latency for every replica slot the fleet
        # could ever grow into (route ignores absent indices)
        return {i: ChaosInjector(fault_at_segment=0, fault_mode="slow",
                                 slow_s=SLOW_S, fault_count=1_000_000)
                for i in range(8)}

    # unloaded, chaos-free parity reference (also the program warmup —
    # replicas added later share the compiled-program cache)
    ref_engine = build(params, 0, 0)
    base = ref_engine.serve_detailed(clone(load, zero_arrival=True))
    ref_engine.reset()

    # fixed 1-replica fleet on the ramp: the goodput baseline
    t0 = time.monotonic()
    fixed_res = ServeRouter([ref_engine]).route(clone(load),
                                                chaos=slow_chaos())
    fixed_wall = time.monotonic() - t0
    fixed_good = (sum(len(r.tokens) for r in fixed_res if r.ok)
                  / fixed_wall)

    # the elastic run: same ramp, controller live, weight push after
    # the first window (same param VALUES, new version stamp — the
    # push must be invisible in tokens)
    rec = flight.FlightRecorder(capacity=512)
    prev = flight.configure_flight(rec)
    try:
        router = ServeRouter([build(params, 0, 0)])
        ctl = ElasticFleetController(
            router, build, params=params,
            policy=ScalePolicy(min_replicas=1, max_replicas=3,
                               up_after=1, down_after=99))
        steps = []
        orig_step = ctl.control_step

        def logged_step(queued=0):
            d = orig_step(queued)
            steps.append((queued, d, ctl.fleet["current_replicas"]))
            return d

        ctl.control_step = logged_step
        t0 = time.monotonic()
        res = ctl.serve_stream(clone(load), window=6,
                               chaos=slow_chaos(),
                               upgrade_to=(params, 1))
        wall = time.monotonic() - t0
        kinds = {ev["kind"] for ev in rec.events()}
    finally:
        flight.configure_flight(prev)
    goodput = sum(len(r.tokens) for r in res if r.ok) / wall

    leaks = [(r.last_slot_leaks, r.last_block_leaks,
              r.last_host_block_leaks) for r in router.replicas]
    ratio = goodput / fixed_good if fixed_good > 0 else 0.0
    active_wv = [router.replicas[i].weights_version
                 for i in router.active_replicas()]
    checks = {
        "scaled_up_within_one_period":
            bool(steps) and steps[0][1] == "up",
        "goodput_tracks_ramp": ratio > 1.3,
        "zero_failed_through_push": all(r.ok for r in res),
        "token_parity_through_push":
            [r.tokens for r in res] == [r.tokens for r in base],
        "fleet_on_new_version":
            ctl.fleet["upgrades"] == 1 and active_wv
            and all(v == 1 for v in active_wv),
        "zero_leaks": leaks == [(0, 0, 0)] * len(router.replicas),
        "scale_events_in_flight_recorder":
            "fleet_scale_up" in kinds and "fleet_upgrade_step" in kinds,
    }
    _print_record({
        "metric": "serve_elastic_smoke",
        "requests": len(load), "offered_rate_rps": spec.rate_rps,
        "injected_harvest_latency_s": SLOW_S,
        "goodput_tok_s": {"fixed_one_replica": round(fixed_good, 2),
                          "elastic": round(goodput, 2)},
        "wall_s": {"fixed_one_replica": round(fixed_wall, 3),
                   "elastic": round(wall, 3)},
        "scaling_ratio": round(ratio, 3),
        "control_steps": [{"queued": q, "decision": d, "replicas": n}
                          for q, d, n in steps],
        "fleet": dict(ctl.fleet),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve elastic smoke failed: {bad}")
    return 0


def serve_disagg_smoke():
    """Long-prompt storm + disaggregated-fleet drill for chunked
    prefill (`make serve-disagg-smoke`, wired into `make bench-smoke`).

    Stage 1 — decode-tick flatness. A mixed open-loop Poisson stream
    (short chatty requests + ~200-token prompts) is offered to a
    long-prompt batcher with chunking OFF and ON, against a
    no-long-prompt BASELINE batcher whose admission window is
    naturally narrow (small ``prompt_buf``, shorts only). Decode-tick
    latency comes from the span trace: the gap between consecutive
    ``harvest`` span ends, divided by the segment length. Asserts the
    ISSUE 14 acceptance contract: the chunked p99 tick stays within a
    FIXED multiple (3x) of the baseline while the unchunked p99 blows
    past it — every unchunked admission wave pays the full
    ``prompt_buf``-wide compiled prefill, chunking bounds it to the
    chunk — with TTFT finite under load, tokens IDENTICAL chunked vs
    unchunked, and zero slot/block/host-block leaks.

    Stage 2 — prefill/decode tier split. A 3-replica prefix-cache
    fleet serves the same style of mix as one unified pool and as a
    1-prefill + 2-decode split (``prefill_replicas=1``). Asserts at
    least one session's finished KV blocks rode the export/import
    handoff (not token replay), split tokens stay identical to the
    unloaded single-replica reference, zero leaks on every replica;
    records TTFT p99 unified vs split for the hardware A/B."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import math
    import tempfile

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs import loadgen
    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    from distributed_compute_pytorch_tpu.serve_router import ServeRouter

    def clone(rs, zero_arrival=False):
        return [dataclasses.replace(
            r, arrival_s=0.0 if zero_arrival else r.arrival_s)
            for r in rs]

    def mixed(short_spec, long_spec):
        # two Poisson processes interleaved by arrival (FIFO contract)
        rs = (loadgen.offered_load(short_spec)
              + loadgen.offered_load(long_spec))
        return sorted(rs, key=lambda r: r.arrival_s)

    def traced_ticks(run_fn, segment):
        """Run under a fresh tracer; return (result, per-tick gaps in
        seconds between consecutive harvest-span ends)."""
        tracer = Tracer()
        prev = configure_tracer(tracer)
        try:
            out = run_fn()
        finally:
            configure_tracer(prev)
        path = os.path.join(tempfile.gettempdir(),
                            "dcp_serve_disagg_trace.json")
        tracer.dump(path)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        ends = sorted(e["ts"] for e in events
                      if e.get("name") == "harvest" and e.get("ph") == "E")
        gaps = [(b - a) / 1e6 / segment for a, b in zip(ends, ends[1:])]
        return out, gaps

    def p99(xs):
        return float(np.percentile(xs, 99)) if xs else float("nan")

    # ---- stage 1: decode-tick flatness under a long-prompt storm ----
    # the contrast the gates measure is STRUCTURAL, so the workload is
    # sized where it actually lives: every unchunked admission wave in
    # the storm batcher compiles at the FULL prompt_buf width (~1.8k
    # tokens of matmul + quadratic attention, ~100 ms on CPU even for
    # pure padding), while a chunked wave is CHUNK-wide (~15 ms) and a
    # decode tick single-digit — chunking's win grows with prompt
    # length, and at short prompt_buf the CPU's flat small-matmul cost
    # curve would drown the spike in per-wave overhead.
    # CHUNK sizing: total long-prompt suffix demand (~4 x 1.8k tokens)
    # divided by the shared per-wave budget must FIT inside the anchor
    # streams' harvest-gap count (160 segments at max_new=320, SEG=2)
    # or chunk waves pile up back-to-back after the anchors drain and
    # the tail gaps absorb many waves each.
    # SEG is deliberately SHORT: per-tick gap cost is roughly
    # tick + wave/SEG, so a long segment would amortise the very
    # admission spike the contrast gates measure
    SEG, CHUNK, LONG_BUF = 2, 64, 1856
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=2304,
                                     d_model=256, d_ff=1024))
    params, _ = model.init(jax.random.key(0))

    # t_max must clear prompt_buf + the anchors' segment-rounded budget
    # (the conservative per-row horizon check), and is held EQUAL
    # across baseline and storm batchers so decode ticks cost the same
    # — only the admission window differs
    def batcher(prompt_buf, chunk=None):
        return ContinuousBatcher(model, params, slots=4, t_max=2304,
                                 prompt_buf=prompt_buf, segment=SEG,
                                 prefill_chunk_tokens=chunk)

    base_cb = batcher(16)                    # shorts only: narrow waves
    off_cb = batcher(LONG_BUF)
    on_cb = batcher(LONG_BUF, chunk=CHUNK)

    # the mix: two long-lived ANCHOR streams that decode for the whole
    # drill (tick gaps measure RESIDENT streams' experience — with no
    # decode-phase row there is no tick to stall), a burst of short
    # chatty requests, and four ~1.8k-token prompts arriving in a
    # bunch once the shorts occupy the pool. The shared chunk budget
    # holds every chunked wave at <= CHUNK suffix tokens no matter how
    # many rows it admits. Rates are high enough that the queue never
    # drains mid-drill: an idle batcher waiting on the next Poisson
    # arrival would pollute the gap percentiles with think-time, not
    # service time.
    anchors = [Request(tokens=[7, 11, 13], max_new=320),
               Request(tokens=[5, 3, 2, 9], max_new=320)]
    shorts = loadgen.LoadSpec(n_requests=10, rate_rps=400.0, seed=3,
                              prompt_len=(2, 10), max_new=(8, 14))
    longs = loadgen.LoadSpec(n_requests=4, rate_rps=2000.0, seed=7,
                             prompt_len=(1780, 1850), max_new=(4, 6))
    storm = sorted(
        anchors + loadgen.offered_load(shorts)
        + [dataclasses.replace(r, arrival_s=r.arrival_s + 0.1)
           for r in loadgen.offered_load(longs)],
        key=lambda r: r.arrival_s)
    short_only = sorted(anchors + loadgen.offered_load(shorts),
                        key=lambda r: r.arrival_s)

    # the unchunked zero-arrival pass is the token-parity reference
    # (greedy decode: arrivals and chunking must never change tokens)
    ref = off_cb.serve_detailed(clone(storm, zero_arrival=True))
    off_cb.reset()

    def timed(cb, load):
        # warm pass with IDENTICAL arrivals first: admission-wave row
        # counts depend on the arrival pattern, so a zero-arrival warm
        # would leave wave shapes to compile inside the timed drill
        cb.serve_detailed(clone(load))
        cb.reset()
        return traced_ticks(lambda: loadgen.run_load(cb, clone(load)),
                            SEG)

    base_rep, base_ticks = timed(base_cb, short_only)
    off_rep, off_ticks = timed(off_cb, storm)
    on_rep, on_ticks = timed(on_cb, storm)

    K = 4.0                                  # the fixed multiple
    p99_base, p99_off, p99_on = p99(base_ticks), p99(off_ticks), \
        p99(on_ticks)
    ttft_on = float(on_rep["slo"].get("ttft_s", {})
                    .get("p99", float("nan")))

    def leaks(snap):
        return (snap["slot_leaks"], snap["block_leaks"],
                snap["host_block_leaks"])

    # ---- stage 2: unified pool vs 1-prefill + 2-decode split --------
    tiny = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    tparams, _ = tiny.init(jax.random.key(1))
    fleet = [ContinuousBatcher(tiny, tparams, slots=2, t_max=64,
                               prompt_buf=32, segment=3,
                               prefix_cache=True, prefill_chunk_tokens=8,
                               max_recoveries=0)
             for _ in range(3)]
    fload = mixed(
        loadgen.LoadSpec(n_requests=10, rate_rps=50.0, seed=11,
                         prompt_len=(2, 10), max_new=(4, 10)),
        loadgen.LoadSpec(n_requests=6, rate_rps=30.0, seed=13,
                         prompt_len=(20, 28), max_new=(4, 8)))

    # warm every replica's programs + the unloaded parity reference
    fbase = None
    for rep in fleet:
        out = rep.serve_detailed(clone(fload, zero_arrival=True))
        fbase = out if fbase is None else fbase
        rep.reset()

    def run_router(router):
        t0 = time.monotonic()
        results = router.route(clone(fload))
        wall = time.monotonic() - t0
        ttfts = [r.ttft_s for r in results if r.ttft_s is not None]
        for rep in fleet:
            rep.reset()
        return {"wall_s": wall, "results": results,
                "ttft_p99_s": p99(ttfts)}

    unified = run_router(ServeRouter(fleet))
    split_router = ServeRouter(fleet, prefill_replicas=1)
    split = run_router(split_router)
    rstats = split_router.stats_snapshot()["router"]

    checks = {
        "chunked_p99_tick_bounded": p99_on <= K * p99_base,
        "unchunked_p99_tick_blows_past": p99_off > K * p99_base,
        "ttft_p99_finite_under_storm": math.isfinite(ttft_on),
        "token_parity_chunked_vs_unchunked":
            [r.tokens for r in on_rep["results"]]
            == [r.tokens for r in ref],
        "chunking_engaged":
            on_rep["snapshot"]["prefill"]["chunked_admissions"] > 0,
        "zero_leaks_storm":
            [leaks(r["snapshot"]) for r in (base_rep, off_rep, on_rep)]
            == [(0, 0, 0)] * 3,
        "handoff_rode_blocks_not_replay": rstats["handoffs"] >= 1,
        "token_parity_unified": [r.tokens for r in unified["results"]]
            == [r.tokens for r in fbase],
        "token_parity_split": [r.tokens for r in split["results"]]
            == [r.tokens for r in fbase],
        "zero_leaks_fleet":
            [(r.last_slot_leaks, r.last_block_leaks,
              r.last_host_block_leaks) for r in fleet] == [(0, 0, 0)] * 3,
    }
    _print_record({
        "metric": "serve_disagg_smoke",
        "storm": {"requests": len(storm),
                  "long_prompts": longs.n_requests,
                  "prompt_buf": LONG_BUF, "chunk_tokens": CHUNK},
        "p99_tick_s": {"baseline_no_longs": round(p99_base, 5),
                       "storm_unchunked": round(p99_off, 5),
                       "storm_chunked": round(p99_on, 5)},
        "tick_samples": {"baseline": len(base_ticks),
                         "unchunked": len(off_ticks),
                         "chunked": len(on_ticks)},
        "fixed_multiple_K": K,
        "ttft_p99_s_chunked_storm": round(ttft_on, 4),
        "prefill": on_rep["snapshot"]["prefill"],
        # the hardware A/B the split tier exists for — recorded, not
        # gated (CPU walls say nothing about HBM-bound prefill)
        "ttft_p99_s": {"unified": round(unified["ttft_p99_s"], 4),
                       "split_1p2d": round(split["ttft_p99_s"], 4)},
        "router": rstats,
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve disagg smoke failed: {bad}")
    return 0


def serve_width_smoke():
    """Width-bucketed paged-decode drill (`make serve-width-smoke`,
    wired into `make bench-smoke`).

    A mixed open-loop Poisson stream — a burst of short chatty
    sessions plus one long ANCHOR session that decodes deep into the
    horizon — is offered to the same engine with width bucketing OFF
    (``decode_width_buckets=1``: every tick gathers the full
    ``nb``-block horizon, the pre-ISSUE-19 traffic model) and ON (the
    full geometric ladder: each tick's tables are sliced to the
    smallest rung covering the live rows). The anchor starts near
    position 0 and climbs through every rung, so the stream exercises
    bucket growth end to end while the shorts keep early ticks cheap.

    Asserts the ISSUE 19 acceptance contract: tokens IDENTICAL on vs
    off (greedy and sampled rows both ride the stream), the bucketed
    run's own full-width-equivalent read counter at least 2x its
    gathered reads (per-tick KV traffic tracked live tokens, not the
    horizon), decode p99 tick not degraded (<= 1.25x the off run,
    measured from harvest-span gaps, best of 3 passes after a warm
    pass — arrival jitter can shift an admission wave onto a prefill
    shape the warm pass never compiled, and one XLA compile inside a
    ~30-tick run IS the p99), compiled programs bounded by the ladder,
    at least one
    bucket growth observed, and zero slot/block/host-block leaks on
    both engines."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import tempfile

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs import loadgen
    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)

    def clone(rs):
        return [dataclasses.replace(r) for r in rs]

    def traced_ticks(run_fn, segment):
        """Run under a fresh tracer; return (result, per-tick gaps in
        seconds between consecutive harvest-span ends)."""
        tracer = Tracer()
        prev = configure_tracer(tracer)
        try:
            out = run_fn()
        finally:
            configure_tracer(prev)
        path = os.path.join(tempfile.gettempdir(),
                            "dcp_serve_width_trace.json")
        tracer.dump(path)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        ends = sorted(e["ts"] for e in events
                      if e.get("name") == "harvest" and e.get("ph") == "E")
        gaps = [(b - a) / 1e6 / segment for a, b in zip(ends, ends[1:])]
        return out, gaps

    def p99(xs):
        return float(np.percentile(xs, 99)) if xs else float("nan")

    # t_max is deliberately DEEP relative to the mix (nb=32 blocks of
    # horizon, anchor peaks around rung 16): the >= 2x read contrast
    # is exactly the over-provisioned-horizon waste the ladder exists
    # to strip, and a horizon sized to the anchor would hide it
    SEG = 4
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=256))
    params, _ = model.init(jax.random.key(0))

    def batcher(width_buckets):
        return ContinuousBatcher(model, params, slots=4, t_max=256,
                                 prompt_buf=16, segment=SEG,
                                 decode_width_buckets=width_buckets)

    off_cb = batcher(1)            # single full-horizon rung = old model
    on_cb = batcher(None)          # full geometric ladder

    # every 5th short samples (temperature > 0): sampled parity rides
    # the same stream — seeds default to the request's index, so the
    # two engines draw identical streams
    anchor = Request(tokens=[7, 11, 13], max_new=96)
    shorts = loadgen.offered_load(
        loadgen.LoadSpec(n_requests=14, rate_rps=60.0, seed=5,
                         prompt_len=(2, 8), max_new=(4, 12)))
    for i, r in enumerate(shorts):
        if i % 5 == 3:
            r.temperature = 0.8
    stream = sorted([anchor] + shorts, key=lambda r: r.arrival_s)

    def timed(cb, load, repeats=3):
        # warm pass with IDENTICAL arrivals first: the bucketed engine
        # compiles one program per rung it crosses, and a growth-time
        # compile inside the timed drill would charge XLA wall time to
        # the very tick percentile the gate measures. Best-of-N on top
        # (the serve-journal-smoke convention): arrival jitter can
        # still land an admission wave on a (suffix, prefix-rung)
        # prefill shape the warm pass never saw, and that one compile
        # dominates a ~30-tick p99 — by the second pass it's cached
        cb.serve_detailed(clone(load))
        cb.reset()
        rep, best, n = None, float("inf"), 0
        for i in range(repeats):
            if i:
                cb.reset()
            rep, ticks = traced_ticks(
                lambda: loadgen.run_load(cb, clone(load)), SEG)
            best, n = min(best, p99(ticks)), len(ticks)
        return rep, best, n

    off_rep, p99_off, n_off = timed(off_cb, stream)
    on_rep, p99_on, n_on = timed(on_cb, stream)
    w_on = on_rep["snapshot"]["width"]
    w_off = off_rep["snapshot"]["width"]

    def leaks(snap):
        return (snap["slot_leaks"], snap["block_leaks"],
                snap["host_block_leaks"])

    checks = {
        "token_parity_on_vs_off":
            [r.tokens for r in on_rep["results"]]
            == [r.tokens for r in off_rep["results"]],
        "reads_at_least_halved":
            w_on["full_width_block_reads"]
            >= 2 * w_on["gathered_block_reads"] > 0,
        "decode_p99_not_degraded": p99_on <= 1.25 * p99_off,
        "bucket_growth_observed": w_on["bucket_growths"] >= 1,
        "programs_bounded_by_ladder":
            set(on_cb._widths_dispatched) <= set(on_cb._width_ladder)
            and len(on_cb._widths_dispatched) <= len(on_cb._width_ladder),
        "off_engine_pinned_full_width":
            set(off_cb._widths_dispatched) == {off_cb.nb}
            and w_off["gathered_block_reads"]
            == w_off["full_width_block_reads"],
        "zero_leaks":
            [leaks(r["snapshot"]) for r in (off_rep, on_rep)]
            == [(0, 0, 0)] * 2,
    }
    _print_record({
        "metric": "serve_width_smoke",
        "stream": {"requests": len(stream), "anchor_max_new": 96,
                   "t_max": 256, "segment": SEG},
        "ladder_blocks": list(on_cb._width_ladder),
        "widths_dispatched": sorted(int(w) for w in
                                    on_cb._widths_dispatched),
        "block_reads": {
            "gathered": int(w_on["gathered_block_reads"]),
            "full_width_equivalent": int(w_on["full_width_block_reads"]),
            "saved_bytes": int(w_on["bytes_saved_vs_full"])},
        "bucket_growths": int(w_on["bucket_growths"]),
        "p99_tick_s": {"full_width": round(p99_off, 5),
                       "bucketed": round(p99_on, 5)},
        "tick_samples": {"full_width": n_off, "bucketed": n_on},
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve width smoke failed: {bad}")
    return 0


# the crash-durability driver run in REAL subprocesses by
# serve_journal_smoke: a Poisson stream through a journaling batcher.
# argv = [journal_dir ('' = journal off), out_json]. Deterministic
# (fixed init key + LoadSpec seed) so three processes — reference,
# killed, restarted — build the identical workload.
_JOURNAL_DRIVER = r"""
import dataclasses, json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
from distributed_compute_pytorch_tpu.utils.compilation_cache import (
    enable as enable_compile_cache)
enable_compile_cache()
from distributed_compute_pytorch_tpu import serve_journal as sj
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.obs.loadgen import (
    LoadSpec, offered_load)
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher

jd, out = sys.argv[1], sys.argv[2]
model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
params, _ = model.init(jax.random.key(0))
reqs = offered_load(LoadSpec(n_requests=24, rate_rps=50.0, seed=11,
                             prompt_len=(2, 8), max_new=(32, 64)))
for i, r in enumerate(reqs):
    r.request_id = f"req-{i:03d}"
    if i % 4 == 3:                    # sampled rows ride along: their
        r.temperature = 0.8           # materialized seeds are journaled
recovery, kw = None, {}
if jd:
    recovery = sj.recover(jd)
    kw = dict(journal_dir=jd, journal_fsync="os")
cb = ContinuousBatcher(model, params, slots=4, t_max=128, prompt_buf=10,
                       segment=4, **kw)
res = cb.serve_detailed(reqs, recovery=recovery)
with open(out, "w") as f:
    json.dump({"ids": [r.request_id for r in res],
               "status": [r.status for r in res],
               "tokens": [r.tokens for r in res],
               "recovered": int(cb.journal["recovered_sessions"]),
               "deduped": int(cb.journal["deduped_completions"]),
               "leaks": cb.last_slot_leaks + cb.last_block_leaks
                        + cb.last_host_block_leaks}, f)
"""


def serve_journal_smoke():
    """Crash-durability drill for the write-ahead session journal
    (`make serve-journal-smoke`, wired into `make bench-smoke`).

    Stage 1 — the drill the journal exists for, with a REAL SIGKILL:
    a Poisson stream serves in a journaling subprocess (fsync=os — the
    survives-process-death tier); the parent waits until the WAL shows
    harvested deltas, then SIGKILLs it mid-stream. A restarted process
    recovers from the journal and must finish every request with
    token streams IDENTICAL to an unkilled reference process, at least
    one session resuming from journaled state, and zero leaks.

    Stage 2 — the price: decode-tick p99 (harvest-span gaps from the
    tracer, the serve_disagg technique) with the journal ON (fsync=os)
    must stay within 1.25x of journal OFF, best-of-3 trials (the os
    policy buys SIGKILL durability for buffered appends only — it must
    not cost a visible slice of the tick)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    import signal
    import subprocess
    import tempfile

    import numpy as np

    import jax
    from distributed_compute_pytorch_tpu.models.gpt2 import (
        GPT2, GPT2Config)
    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)

    work = tempfile.mkdtemp(prefix="dcp_journal_smoke_")
    driver = os.path.join(work, "driver.py")
    with open(driver, "w") as f:
        f.write(_JOURNAL_DRIVER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the driver lives in a tempdir: put this repo on its import path
    # (its compile cache follows the package, not the driver file)
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def run(jd, out):
        return subprocess.run([sys.executable, driver, jd, out],
                              env=env, timeout=600)

    # unkilled reference (also warms the shared compile cache, so the
    # killed run spends its life SERVING, not compiling)
    ref_out = os.path.join(work, "ref.json")
    assert run("", ref_out).returncode == 0
    with open(ref_out) as f:
        ref = json.load(f)

    # the kill run: SIGKILL once the journal shows harvest deltas
    jd = os.path.join(work, "wal")
    wal = os.path.join(jd, "serve.wal")
    proc = subprocess.Popen([sys.executable, driver, jd,
                             os.path.join(work, "never.json")], env=env)
    deadline = time.time() + 300
    killed = False
    while time.time() < deadline and proc.poll() is None:
        try:
            with open(wal, "rb") as f:
                seen_delta = b'"kind":"delta"' in f.read()
        except OSError:
            seen_delta = False
        if seen_delta:
            proc.send_signal(signal.SIGKILL)
            killed = True
            break
        time.sleep(0.03)
    proc.wait(timeout=60)
    kill_rc = proc.returncode

    # the restarted process: recover + finish
    res_out = os.path.join(work, "restart.json")
    restart_rc = run(jd, res_out).returncode
    with open(res_out) as f:
        res = json.load(f)

    # ---- stage 2: decode-tick p99, journal on vs off ----
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = [Request([int(t) for t in rng.integers(1, 256, 6)], 32)
             for _ in range(12)]

    def clone():
        return [dataclasses.replace(r) for r in batch]

    def traced_p99(cb):
        tracer = Tracer()
        prev = configure_tracer(tracer)
        try:
            out = cb.serve_detailed(clone())
        finally:
            configure_tracer(prev)
        path = os.path.join(work, "trace.json")
        tracer.dump(path)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        ends = sorted(e["ts"] for e in events
                      if e.get("name") == "harvest"
                      and e.get("ph") == "E")
        gaps = [(b - a) / 1e6 for a, b in zip(ends, ends[1:])]
        return out, float(np.percentile(gaps, 99))

    warm = ContinuousBatcher(model, params, slots=4, t_max=64,
                             prompt_buf=8, segment=4)
    warm.serve_detailed(clone())      # compile outside the timed trials
    ratios, p99s = [], []
    for trial in range(3):
        cb_off = ContinuousBatcher(model, params, slots=4, t_max=64,
                                   prompt_buf=8, segment=4)
        off_res, p99_off = traced_p99(cb_off)
        cb_on = ContinuousBatcher(
            model, params, slots=4, t_max=64, prompt_buf=8, segment=4,
            journal_dir=os.path.join(work, f"twal{trial}"),
            journal_fsync="os")
        on_res, p99_on = traced_p99(cb_on)
        assert [r.tokens for r in on_res] == [r.tokens for r in off_res]
        ratios.append(p99_on / p99_off)
        p99s.append((p99_off, p99_on))
    best_ratio = min(ratios)

    ref_by_id = dict(zip(ref["ids"], ref["tokens"]))
    checks = {
        "reference_all_ok": all(s == "ok" for s in ref["status"]),
        "kill_landed_mid_stream": killed and kill_rc != 0,
        "restart_completed": restart_rc == 0
            and all(s == "ok" for s in res["status"]),
        "token_parity_through_sigkill":
            {i: t for i, t in zip(res["ids"], res["tokens"])} == ref_by_id,
        "recovered_from_journal": res["recovered"] >= 1,
        "zero_leaks": res["leaks"] == 0,
        "tick_p99_overhead_bounded": best_ratio <= 1.25,
    }
    _print_record({
        "metric": "serve_journal_smoke",
        "requests": len(ref["ids"]),
        "kill_rc": kill_rc,
        "recovered_sessions": res["recovered"],
        "deduped_completions": res["deduped"],
        "tick_p99_s": [{"off": round(a, 5), "on": round(b, 5)}
                       for a, b in p99s],
        "tick_p99_ratio_best_of_3": round(best_ratio, 3),
        "checks": checks})
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"serve journal smoke failed: {bad}")
    return 0


def _max_spread(rec):
    """Deepest ``spread`` field in a (nested) stage record, or None."""
    if not isinstance(rec, dict):
        return None
    best = None
    for k, v in rec.items():
        s = (v if (k == "spread" and isinstance(v, (int, float)))
             else _max_spread(v))
        if s is not None:
            best = s if best is None else max(best, s)
    return best


def main():
    if "--diff" in sys.argv:
        # bench-diff: compare two bench records stage-by-stage using
        # each stage's recorded spread as the noise floor; exit 1 on
        # regression (obs/regress.py; `make bench-diff`)
        from distributed_compute_pytorch_tpu.obs.regress import (
            main as diff_main)
        return diff_main(sys.argv[sys.argv.index("--diff") + 1:])
    if "--zero1-smoke" in sys.argv:
        return zero1_smoke()
    if "--serve-smoke" in sys.argv:
        return serve_smoke()
    if "--serve-chaos-smoke" in sys.argv:
        return serve_chaos_smoke()
    if "--serve-prefix-smoke" in sys.argv:
        return serve_prefix_smoke()
    if "--serve-tier-smoke" in sys.argv:
        return serve_tier_smoke()
    if "--serve-spec-smoke" in sys.argv:
        return serve_spec_smoke()
    if "--serve-kvq-smoke" in sys.argv:
        return serve_kvq_smoke()
    if "--serve-load-smoke" in sys.argv:
        return serve_load_smoke()
    if "--serve-router-smoke" in sys.argv:
        return serve_router_smoke()
    if "--serve-elastic-smoke" in sys.argv:
        return serve_elastic_smoke()
    if "--serve-disagg-smoke" in sys.argv:
        return serve_disagg_smoke()
    if "--serve-journal-smoke" in sys.argv:
        return serve_journal_smoke()
    if "--serve-width-smoke" in sys.argv:
        return serve_width_smoke()
    if "--grad-accum-smoke" in sys.argv:
        return grad_accum_smoke()
    from distributed_compute_pytorch_tpu.utils.compilation_cache import (
        enable as enable_compile_cache)

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_compute_pytorch_tpu.core.mesh import make_mesh

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        # a CPU timing is never printed under a device metric's name
        print(f"bench.py measures on the chip: platform is "
              f"{devices[0].platform!r} ({device_kind}), no TPU — nothing "
              f"measured", file=sys.stderr)
        return 1
    peak = _peak(_PEAK_BF16, device_kind)
    mesh = make_mesh("data=-1", devices=devices)

    sps_per_chip, headline_spread = _bench_convnet(jax, jnp, np, mesh,
                                                   n_chips)

    # a failing stage must not cost the other stages their numbers: it
    # reports its error in place, and the exit code says a stage failed
    failed: list = []

    def _stage(fn, *args):
        try:
            return fn(*args)
        except Exception as e:  # noqa: BLE001 — report, keep measuring
            failed.append(fn.__name__)
            return {"error": f"{type(e).__name__}: {e}"[:300]}

    # decode FIRST: its per-tick time is HBM-placement-sensitive, and
    # running it after the big training stages measures allocator
    # fragmentation, not the decode loop (llama 0.76 ms after the full
    # ladder vs 0.51 in a fresh process, 5-repeat stable either way)
    dec = _stage(_bench_decode, jax, jnp, np, mesh, n_chips)
    dec_ll = _stage(_bench_decode, jax, jnp, np, mesh, n_chips, "llama")
    dec_q = _stage(_bench_decode, jax, jnp, np, mesh, n_chips, "gpt2",
                   True)
    dec_ll_q = _stage(_bench_decode, jax, jnp, np, mesh, n_chips, "llama",
                      True)
    # throughput-serving operating point: 4x the sequences amortise the
    # per-tick weight stream (the latency stages above are B=16)
    dec_ll_q64 = _stage(_bench_decode, jax, jnp, np, mesh, n_chips, "llama",
                        True, 64)
    # MoE decode (VERDICT r4 missing #1): bf16 only — quantize_params_int8
    # keys on 'kernel'/'embedding' leaf names, so the expert FFN stacks
    # (w_in/w_out, ~88% of this model's bytes) stay float and int8 would
    # shave only the attention/embedding sliver
    dec_moe = _stage(_bench_decode, jax, jnp, np, mesh, n_chips, "moe")
    serve = _stage(_bench_serve, jax, jnp, np, mesh, n_chips)
    serve_long = _stage(_bench_serve_long_stream, jax, jnp, np, mesh,
                        n_chips)
    real_mnist = _stage(_bench_real_mnist, jax, jnp, np, mesh, n_chips)
    gpt2 = _stage(_bench_gpt2, jax, jnp, np, mesh, n_chips, peak)
    zero1 = _stage(_bench_zero1, jax, jnp, np, mesh, n_chips, peak)
    gaccum = _stage(_bench_grad_accum, jax, jnp, np, mesh, n_chips, peak)
    llama = _stage(_bench_llama, jax, jnp, np, mesh, n_chips, peak)
    resnet = _stage(_bench_resnet18, jax, jnp, np, mesh, n_chips, peak)
    resnet50 = _stage(_bench_resnet50, jax, jnp, np, mesh, n_chips, peak)
    bert = _stage(_bench_bert, jax, jnp, np, mesh, n_chips, peak)
    moe = _stage(_bench_moe, jax, jnp, np, mesh, n_chips, peak)
    ev = _stage(_bench_eval, jax, jnp, np, mesh, n_chips)
    attn = _stage(_bench_attention, jax, jnp, np)

    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "benchmarks", "baseline_measured.json")
    with open(base_path) as f:
        base = json.load(f)["mnist_convnet_train_samples_per_sec"]["value"]

    result = {
        "schema_version": SCHEMA_VERSION,
        "metric": "mnist_convnet_train_samples_per_sec_per_chip",
        "value": round(sps_per_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_per_chip / base, 3),
        "extra": {
            "device_kind": device_kind,
            "n_chips": n_chips,
            "headline_spread": headline_spread,
            "gpt2_small_bf16_t1024": gpt2,
            "zero1_update_sharding_gpt2_adamw": zero1,
            "grad_accum_boundary_gpt2_adamw": gaccum,
            "llama_125m_gqa_bf16_t1024": llama,
            "resnet18_cifar32_bf16": resnet,
            "resnet50_imagenet224_bf16": resnet50,
            "bert_base_mlm_bf16_t512": bert,
            "moe_8e_top2_bf16_t1024": moe,
            "gpt2_eval_bf16_t1024": ev,
            "gpt2_decode_kvcache_bf16": dec,
            "llama_decode_kvcache_gqa_bf16": dec_ll,
            "gpt2_decode_kvcache_int8": dec_q,
            "llama_decode_kvcache_gqa_int8": dec_ll_q,
            "llama_decode_kvcache_gqa_int8_b64": dec_ll_q64,
            "moe_8e_decode_kvcache_bf16": dec_moe,
            "serve_continuous_vs_static_llama_int8": serve,
            "serve_long_stream_llama_int8": serve_long,
            "mnist_real_idx_accuracy": real_mnist,
            "flash_vs_dense_attention_bf16": attn,
            # pipeline parallelism needs >1 device; its bubble is
            # quantified on the faked 8-device mesh in
            # tests/test_pipeline.py::test_more_microbatches_shrink_bubble
            "pipeline": {
                "skipped": f"needs >1 device (have {n_chips}); bubble "
                           f"quantified in tests/test_pipeline.py::"
                           f"test_more_microbatches_shrink_bubble"},
        },
    }
    # variance discipline: stages whose best-of-K spread exceeds 5% are
    # flagged — their headline numbers moved >5% across the K walls and
    # should be read with that error bar
    high_variance = {
        name: s for name, rec in result["extra"].items()
        if isinstance(rec, dict)
        for s in [_max_spread(rec)] if s is not None and s > 0.05}
    if headline_spread and headline_spread > 0.05:
        high_variance["mnist_convnet_headline"] = headline_spread
    result["extra"]["high_variance"] = high_variance

    details = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "bench_details_latest.json")
    try:
        with open(details, "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass

    # The PRINTED line must stay small enough for the driver to capture and
    # parse (r03's full record exceeded the capture window -> parsed: null).
    # Print a compact headline + per-rung key numbers; the full record is in
    # benchmarks/bench_details_latest.json.
    def _pick(d, *keys):
        if not isinstance(d, dict):
            return None
        if "skipped" in d:
            return "skipped"
        if "error" in d:
            return "error"
        for k in keys:
            if d.get(k) is not None:
                return d[k]
        return None

    compact = {
        "metric": result["metric"],
        "value": result["value"],
        "unit": result["unit"],
        "vs_baseline": result["vs_baseline"],
        "extra": {
            "device_kind": device_kind,
            "n_chips": n_chips,
            "mfu": {
                "gpt2": _pick(gpt2, "mfu"),
                "llama": _pick(llama, "mfu"),
                "resnet18": _pick(resnet, "mfu"),
                "resnet50": _pick(resnet50, "mfu"),
                "bert": _pick(bert, "mfu"),
                "moe_active": _pick(moe, "mfu_active"),
            },
            "moe_dropped_fraction": _pick(moe, "dropped_token_fraction"),
            "zero1": {
                "opt_bytes_ratio": _pick(zero1, "opt_bytes_ratio"),
                "step_ms_ratio": _pick(zero1, "step_ms_ratio"),
            },
            "grad_accum": {
                "step_ms_boundary_vs_legacy": _pick(
                    gaccum, "step_ms_ratio_boundary_vs_legacy"),
                "step_ms_bucketed_vs_boundary": _pick(
                    gaccum, "step_ms_ratio_bucketed_vs_boundary"),
                "wire_bytes_reduction": _pick(gaccum,
                                              "wire_bytes_reduction"),
            },
            "decode_per_tick_ms": {
                "gpt2": _pick(dec, "per_tick_ms"),
                "llama": _pick(dec_ll, "per_tick_ms"),
                "gpt2_int8": _pick(dec_q, "per_tick_ms"),
                "llama_int8": _pick(dec_ll_q, "per_tick_ms"),
                "llama_int8_b64_tok_s": _pick(
                    dec_ll_q64, "decode_tokens_per_sec_per_chip"),
            },
            "serve_long_stream": {
                "serve_tok_s": _pick(serve_long, "serve_tok_s"),
                "serve_tok_s_per_chip": _pick(serve_long,
                                              "serve_tok_s_per_chip"),
                "target_tok_s_per_chip": _pick(serve_long,
                                               "target_tok_s_per_chip"),
                "slot_utilization": _pick(serve_long, "slot_utilization"),
                "waste_breakdown": _pick(serve_long, "waste_breakdown"),
                "ticks_vs_old_horizon": _pick(serve_long,
                                              "ticks_vs_old_horizon"),
            },
            "high_variance": high_variance,
            "flash_speedup": {
                k: (v.get("speedup") if isinstance(v, dict) else None)
                for k, v in attn.items()
            } if isinstance(attn, dict) and "skipped" not in attn
              and "error" not in attn else _pick(attn),
            "details_file": "benchmarks/bench_details_latest.json",
        },
    }
    _print_record(compact)
    if failed:
        print(f"bench.py: {len(failed)} stage(s) raised: {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
