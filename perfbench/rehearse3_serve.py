"""Rehearsal 3 for a serving cell (called from ``rehearse3.py``): builds
the program's ``ContinuousBatcher`` on the CPU at the cell's real sizes
with all-zero weights, then compiles its admission program for each wave
size asked and its decode segment at the widest and a middle width rung
for a described v5e. Prints the compiler's memory per program: the pool
and the weights are arguments, so "temp" is what a wave adds."""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding


def serve(cell, cfg, traffic, waves):
    from distributed_compute_pytorch_tpu.serve import ContinuousBatcher
    from jax.experimental import topologies
    from perfbench import families, weights
    from perfbench.rehearse3 import report
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    run_kw = cell["run"]
    ref = families.reference_module(cfg)
    model = families.build_program_model(
        cfg, dict(run_kw, max_seq_len=run_kw["t_max"]))
    spec = ref.param_spec(cfg)
    dtypes = ref.param_dtypes(cfg, run_kw["param_dtype"])
    params = jax.tree.map(lambda s, d: jnp.zeros(s[0], d), spec, dtypes,
                          is_leaf=weights._is_leaf)
    cb = ContinuousBatcher(model, params, slots=run_kw["slots"],
                           t_max=run_kw["t_max"],
                           prompt_buf=run_kw["prompt_buf"],
                           kv_dtype=run_kw.get("kv_dtype", "bf16"))
    print(f"REHEARSAL3 batcher: block {cb.bt} tokens, {cb.nb} blocks a row, "
          f"ladder {cb._width_ladder}, pallas pool write {cb._pallas_write}, "
          f"pool {sum(c['kv'].nbytes for c in cb._caches) / 1e9:.2f} GB, "
          f"weights {sum(a.nbytes for a in jax.tree.leaves(params)) / 1e9:.2f} GB",
          flush=True)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    p_s = jax.tree.map(sds, params)
    c_s = jax.tree.map(sds, cb._caches)
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    B, W = cb.B, cb.Tb
    for K in waves:
        args = (p_s, c_s, arr((K, cb.nb), jnp.int32), arr((K, W), jnp.int32),
                arr((K, W), jnp.float32), arr((K, W), jnp.int32),
                arr((K, 0), jnp.float32), arr((K, W), jnp.int32),
                arr((K, W), jnp.int32))
        t0 = time.time()
        try:
            report(f"admit wave K={K} window={W}",
                   cb._admit_c.lower(*args).compile(), t0)
        except Exception as e:   # noqa: BLE001 — the finding is the message
            print(f"REHEARSAL3 admit wave K={K}: REFUSED: "
                  f"{str(e)[:600]}", flush=True)
    for w in sorted({cb._width_ladder[-1],
                     cb._width_ladder[len(cb._width_ladder) // 2]}):
        args = (p_s, c_s, arr((B, w), jnp.int32), arr((B,), jnp.int32),
                arr((B,), jnp.int32), arr((B,), jnp.int32),
                arr((B,), jnp.float32), arr((B,), jnp.int32),
                arr((B,), jnp.float32), arr((B,), jnp.uint32))
        t0 = time.time()
        try:
            report(f"decode segment width={w} blocks",
                   cb._segment_c.lower(*args, sampling=False).compile(), t0)
        except Exception as e:   # noqa: BLE001
            print(f"REHEARSAL3 decode segment width={w}: REFUSED: "
                  f"{str(e)[:600]}", flush=True)
