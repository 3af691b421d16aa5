"""Rehearsal 3: compile a cell's programs at the real size for a DESCRIBED
v5e (no chip attached) and print what the compiler says: whether it
compiles at all, the bytes it needs on each device, which Pallas kernels
and collectives are in the program. Nothing runs; nothing printed here is
a measurement.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse3.py --workload <cell> [--remat 0|1]

``jax.default_backend`` is patched to say "tpu" for the length of this
script, so that the program's own dispatch takes its TPU branch (the flash
kernels, the Pallas pool write) while lowering for the described devices.
"""

import argparse
import json
import os
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    kernels = sorted(set(re.findall(r"dcp_[a-z0-9_]+", txt)))
    calls = len(re.findall(r"tpu_custom_call", txt))
    coll = {k: len(re.findall(rf"\b{k}(?:-start)?\(", txt))
            for k in ("all-reduce", "all-gather", "reduce-scatter")}
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"REHEARSAL3 {name}: compiled in {time.time() - t0:.0f} s | "
          f"args {ma.argument_size_in_bytes / 1e9:.2f} GB, temp "
          f"{ma.temp_size_in_bytes / 1e9:.2f} GB, out "
          f"{ma.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{ma.alias_size_in_bytes / 1e9:.2f} GB => {total / 1e9:.2f} GB a "
          f"device | Mosaic calls {calls} {kernels} | collectives {coll}",
          flush=True)
    return total


def train(env_cell, cfg, traffic, chips, remat):
    from distributed_compute_pytorch_tpu.parallel import collectives as coll
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns
    from jax.experimental import topologies
    from perfbench import families
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    run_kw = dict(env_cell["run"], remat=remat)
    model = families.build_program_model(cfg, run_kw)
    opt = traffic["optimizer"]
    spe = traffic["steps_per_epoch"]
    tx = build_optimizer("adamw", opt["lr"], 0.7, steps_per_epoch=spe,
                         total_steps=spe * opt["schedule_epochs"],
                         weight_decay=opt.get("weight_decay", 0.0))
    _, train_step, _ = make_step_fns(
        model, tx, mesh, None, compute_dtype=jnp.dtype(run_kw["compute_dtype"]))
    repl = NamedSharding(mesh, P())

    def init(key):
        params, ms = model.init(key)
        return params, ms, tx.init(params)

    p, ms, o = jax.eval_shape(init, jax.random.key(0))
    zero1 = chips > 1
    o_sh = (coll.tree_update_shardings(o, mesh) if zero1
            else jax.tree.map(lambda _: repl, o))
    from distributed_compute_pytorch_tpu.train.step import TrainState
    sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state = TrainState(
        step=sds(jax.ShapeDtypeStruct((), jnp.int32), repl),
        params=jax.tree.map(lambda a: sds(a, repl), p),
        model_state=jax.tree.map(lambda a: sds(a, repl), ms),
        opt_state=jax.tree.map(sds, o, o_sh),
        rng=sds(jax.eval_shape(lambda: jax.random.key(0)), repl))
    B = traffic["sequences_per_chip"] * chips
    x = jax.ShapeDtypeStruct((B, traffic["seq_len"]), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    t0 = time.time()
    compiled = train_step.lower(state, x, x).compile()
    return report(f"train_step chips={chips} remat={remat} batch={B}",
                  compiled, t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--waves", default="1,2,4,8,12,16")
    args = ap.parse_args()
    here = ROOT / "perfbench"
    cell = json.load(open(here / "workloads" / f"{args.workload}.json"))
    cfg = json.load(open(here / "configs" / f"{cell['config']}.json"))
    traffic = json.load(open(here / "traffic" / f"{cell['traffic']}.json"))
    jax.default_backend = lambda: "tpu"      # see the module docstring
    if traffic["kind"] == "train_job":
        remat = cell["run"]["remat"] if args.remat is None else (
            args.remat not in ("0", "false", "False"))
        train(cell, cfg, traffic, cell["chips"], remat)
    else:
        from perfbench import rehearse3_serve
        rehearse3_serve.serve(cell, cfg, traffic,
                              [int(k) for k in args.waves.split(",")])


if __name__ == "__main__":
    main()
