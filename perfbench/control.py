"""The control of "How `correct` is decided": the plain reference put in
the program's place and computed one precision below the one the
configuration states (int8 operands for a bfloat16 cell; fp8 is read
beside it). Run by hand on the chip at a cell's own size, on several seeds;
the benchmark's own runs never run it. It prints, per seed, each number of
the cell's check as the control gives it, beside the limit: at least one
has to be over. For a train cell the program's own numbers on the same
seeds and rows are read first, in the same process: the two readings a
limit is set from.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def _numbers(side, base, spec):
    """Every number of the train check, of ``side`` against ``base``."""
    from perfbench import check as chk
    g, gw = chk.worst_leaf_gap(side["grad_norms"], base["grad_norms"])
    d, dw = chk.worst_leaf_gap(side["delta_norms"], base["delta_norms"])
    rel = chk.sampled_rel_diffs(side["grad_sample"], base["grad_sample"])
    e, ew = chk.worst(rel)
    return {
        "loss_gap": max(abs(x - y)
                        for x, y in zip(side["losses"], base["losses"])),
        "grad_norm_gap": g, "grad_where": gw, "delta_norm_gap": d,
        "delta_where": dw, "grad_elem_diff": e, "elem_where": ew,
        "grad_vector_pooled": chk.pooled(rel, chk.vector_leaves(spec)),
        "grad_all_pooled": chk.pooled(rel),
        "pooled_by_leaf": {k: chk.pooled(rel, {k}) for k in rel}}


def train_reference(cfg, traffic, seed, chips, precisions, batches=None):
    """The reference's three steps at each of ``precisions`` on the rows
    ``batches`` (default: the dataset's first rows in order)."""
    import jax
    import jax.numpy as jnp

    from perfbench import families, weights
    from perfbench.runners.train import make_dataset
    ref = families.reference_module(cfg)
    cfg = families.without_dropout(cfg)
    gb = traffic["sequences_per_chip"] * chips
    if batches is None:
        toks = make_dataset(traffic, cfg["vocab_size"], seed, gb)
        batches = [toks[k * gb:(k + 1) * gb] for k in range(3)]
    batches = [jnp.asarray(b) for b in batches]
    opt = dict(traffic["optimizer"])
    opt["total_steps"] = traffic["steps_per_epoch"] * opt["schedule_epochs"]
    spec = ref.param_spec(cfg)
    rows = traffic.get("reference_rows_per_block", 4)
    draw = lambda: weights.make_params(spec, seed, jnp.float32,
                                       device=jax.devices()[0])
    out = {}
    for prec in precisions:
        t0 = time.monotonic()
        out[prec] = ref.train_steps(draw(), batches, cfg, opt, prec, rows,
                                    devices=jax.devices()[:chips],
                                    make_p0=draw)
        out[prec]["s"] = time.monotonic() - t0
    return out


def train_control(cell, cfg, traffic, seed, chips, program=None,
                  controls=("int8", "fp8")):
    """The controls' numbers on one seed (and the program's, where
    ``program`` holds what ``train_program`` recorded for the seed)."""
    out = train_reference(cfg, traffic, seed, chips, ("f32",) + controls,
                          program and program["compared"]["batches"])
    from perfbench import families
    spec = families.reference_module(cfg).param_spec(cfg)
    b = out["f32"]
    res = {"seed": seed, "f32_s": b["s"], "losses_f32": b["losses"]}
    for prec in controls:
        res[prec] = dict(_numbers(out[prec], b, spec), s=out[prec]["s"])
    if program:
        res["program"] = _numbers(program["compared"], b, spec)
        res["timed_loss_gap"] = max(
            abs(x - y) for x, y in zip(program["timed_losses"], b["losses"]))
        res["timed_losses"] = program["timed_losses"]
    return res


def train_program(env, seeds):
    """What the check records of the PROGRAM, on every seed in one
    process: the compared form (dropout off) and then the timed form are
    each built once and handed every seed's weights and rows in turn. Also
    times two epochs of each form (host clock, fenced)."""
    import jax
    import numpy as np

    from perfbench import families
    from perfbench.runners.train import Program, make_dataset
    cfg, traffic = env.config, env.traffic
    gb = traffic["sequences_per_chip"] * env.chips
    spe = traffic["steps_per_epoch"]
    sets = {s: make_dataset(traffic, cfg["vocab_size"], s, gb) for s in seeds}
    got = {s: {} for s in seeds}
    rate = {}
    for form, c in (("compared", families.without_dropout(cfg)),
                    ("timed", cfg)):
        prog = Program(env, c, sets[seeds[0]])
        like = next(iter(prog.trainer.train_feed.epoch(0)))[0]

        def three(toks):
            st = prog.trainer.state
            for k in range(Program.N_CHECK):
                x = jax.device_put(toks[k * gb:(k + 1) * gb], like.sharding)
                st, _ = prog.trainer.train_step(st, x, x)
            prog.trainer.state = st
        for s in seeds:
            prog.hand_over(s)
            rec = prog.recorded(s, lambda: three(sets[s]))
            del rec["x"]
            got[s][form] = rec
        t0 = time.monotonic()
        for e in (1, 2):
            prog.trainer.train_epoch(e)
        rate[form] = 2 * spe * gb * traffic["seq_len"] / (time.monotonic() - t0)
        prog.free()
        del prog, like
    print("PROGRAM tokens/s over two epochs, no window, host clock:",
          json.dumps(rate), flush=True)
    return {s: {"compared": got[s]["compared"],
                "timed_losses": got[s]["timed"]["losses"]} for s in seeds}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from distributed_compute_pytorch_tpu.utils import compilation_cache
    from perfbench import run as prun
    from perfbench.compilewatch import CompileWatch
    compilation_cache.enable()
    seeds = [int(s) for s in args.seeds.split(",")]
    env = prun.Env(argparse.Namespace(
        workload=args.workload, seed=seeds[0], seconds=args.seconds,
        trace=0, rehearse=args.rehearse),
        prun.load_json(ROOT / "BENCHMARK.json"))
    env.watch = CompileWatch()
    env.devices = jax.devices()[:env.chips]
    cell = env.cell
    if env.traffic["kind"] == "train_job":
        program = train_program(env, seeds)
        # int8 is the control of a bfloat16 cell; fp8 is read beside it
        # on the first three seeds
        rows = (train_control(cell, env.config, env.traffic, seed, env.chips,
                              program.pop(seed, None),
                              ("int8", "fp8") if i < 3 else ("int8",))
                for i, seed in enumerate(seeds))
    else:
        from perfbench.runners import serve
        rows = serve.control(env, seeds, args.seconds)
    for r in rows:
        print("CONTROL", json.dumps(r), "limits", json.dumps(cell["limits"]),
              flush=True)


if __name__ == "__main__":
    main()
