"""Runner for traffic of kind ``train_job``: drives the program's
``Trainer`` through its Python API on a synthetic in-memory dataset drawn
from the seed, measures tokens per second over the window, and afterwards
follows the first steps with the plain reference.

Order of a run: dataset and Trainer (compiles or fetches the step) ->
weights from the seed handed to the trainer -> one warm-up epoch through
``Trainer.train_epoch`` whose first three steps are recorded for the check
-> the measured window on the SAME trainer object -> the trainer's state is
freed -> the reference follows the three steps -> numbers are compared.

A configuration with dropout (GPT-2's published 0.1) is TIMED with it, and
its masks come from the program's own key stream, which a plain reference
cannot follow. Such a cell therefore builds the program twice, one after
the other: first with the family's dropout keys at 0, driven through the
same call and feed for the three compared steps and then freed; then as
published, warmed up and timed. The compared program differs from the
timed one in dropout only; the timed object's own first losses, the rows
it was fed and its loss after the window are still held to limits."""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench import check as chk
from perfbench import families, tracewin, weights


def make_dataset(traffic: dict, vocab: int, seed: int, global_batch: int):
    """``steps_per_epoch * global_batch`` packed rows of ``seq_len`` tokens
    over the whole vocabulary, Zipf-distributed (exponent ``zipf_a``) over
    a seeded permutation of the ids, so that the loss can fall. Rows are
    independent draws: all differ."""
    rng = np.random.default_rng([seed, 0x7261696E])
    n = traffic["steps_per_epoch"] * global_batch
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / (ranks + traffic.get("zipf_shift", 10.0)) ** traffic.get("zipf_a", 1.0)
    p /= p.sum()
    ids = rng.permutation(vocab)
    toks = ids[rng.choice(vocab, size=(n, traffic["seq_len"]), p=p)]
    return toks.astype(np.int32)


def _find_mu(opt_state):
    """The first-moment tree inside an optax state."""
    import jax
    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise ValueError("no Adam first moment in the optimizer state")


class Program:
    """The program's ``Trainer`` on the cell's dataset, with the weights of
    a seed handed over, and a record of the first steps it takes."""

    N_CHECK = 3

    def __init__(self, env, cfg, toks):
        import jax
        import jax.numpy as jnp

        from distributed_compute_pytorch_tpu.core.config import Config
        from distributed_compute_pytorch_tpu.data.datasets import ArrayDataset
        from distributed_compute_pytorch_tpu.train.trainer import Trainer

        traffic, run_kw = env.traffic, env.cell["run"]
        self.ref = families.reference_module(cfg)
        self.spec = self.ref.param_spec(cfg)
        self.pdtype = jnp.dtype(run_kw["param_dtype"])
        self.opt = dict(traffic["optimizer"])
        spe = traffic["steps_per_epoch"]
        self.opt["total_steps"] = spe * self.opt["schedule_epochs"]
        gb = traffic["sequences_per_chip"] * env.chips
        data = ArrayDataset(toks, toks, name=traffic["name"],
                            num_classes_override=cfg["vocab_size"])
        config = Config(
            batch_size=gb, lr=self.opt["lr"],
            epochs=self.opt["schedule_epochs"], mesh=f"data={env.chips}",
            model=families.family(cfg).BUILD_MODEL, dataset="synthetic-lm",
            optimizer="adamw",
            weight_decay=self.opt.get("weight_decay", 0.0),
            warmup_steps=self.opt.get("warmup_steps", 0), log_every=spe,
            seed=env.seed & 0x7FFFFFFF, compute_dtype=run_kw["compute_dtype"],
            param_dtype=run_kw["param_dtype"], force_cpu=env.rehearse,
            ckpt_path=str(env.scratch / "unused_checkpoint.npz"))
        model = families.build_program_model(cfg, run_kw)
        self.trainer = Trainer(config, model=model, train_data=data,
                               eval_data=data)
        assert self.trainer.train_feed.steps_per_epoch == spe
        shapes = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                              self.trainer.state.params)
        want = jax.tree.map(lambda s: (s[0], run_kw["param_dtype"]),
                            self.spec, is_leaf=weights._is_leaf)
        if shapes != want:
            raise SystemExit(f"the program's parameter tree differs from "
                             f"the reference's spec:\n{shapes}\n{want}")
        self.step = self.trainer.train_step
        ref, gen = self.ref, weights.gen_fn(self.spec, self.pdtype)
        self._norms = jax.jit(lambda t: (ref.leaf_norms(t),
                                         ref.sample_elems(t)))

        @jax.jit
        def delta_norms(p, key):
            p0 = gen(key)
            return (ref.leaf_norms(jax.tree.map(jnp.subtract, p, p0)),
                    ref.leaf_norms(p0))
        self._delta_norms = delta_norms

    def hand_over(self, seed):
        """Weights from ``seed`` into a state that has taken no step."""
        import jax
        import jax.numpy as jnp
        st = self.trainer.state
        shardings = jax.tree.map(lambda a: a.sharding, st.params)
        params = weights.make_params(self.spec, seed, self.pdtype,
                                     shardings=shardings)
        self.trainer.state = st.replace(
            step=jnp.zeros_like(st.step), params=params,
            opt_state=jax.tree.map(jnp.zeros_like, st.opt_state))

    def recorded(self, seed, take_steps):
        """``take_steps()`` with the step spied on: the rows and loss of
        its first ``N_CHECK`` steps, the first gradient as AdamW's first
        moment holds it, the parameters' change after the last."""
        import jax
        rec = {"x": [], "loss": [], "mu": None, "delta": None}

        def spy(state, x, y):
            k = len(rec["loss"])
            new_state, metrics = self.step(state, x, y)
            if k < self.N_CHECK:
                rec["x"].append(x)
                rec["loss"].append(metrics["loss"])
                if k == 0:
                    rec["mu"] = self._norms(_find_mu(new_state.opt_state))
                if k == self.N_CHECK - 1:
                    rec["delta"] = self._delta_norms(
                        new_state.params, weights.seed_key(seed))
            return new_state, metrics

        self.trainer.train_step = spy
        try:
            take_steps()
        finally:
            self.trainer.train_step = self.step  # the window drives the step itself
        jax.block_until_ready(rec["delta"])
        scale = 1.0 / (1.0 - self.opt.get("b1", 0.9))
        mu_norms, mu_sample = jax.device_get(rec["mu"])
        delta, p0 = jax.device_get(rec["delta"])
        return {
            "x": rec["x"], "batches": [np.asarray(x) for x in rec["x"]],
            "losses": [float(l) for l in rec["loss"]],
            "grad_norms": {k: np.asarray(v) * scale
                           for k, v in mu_norms.items()},
            "grad_sample": {k: np.asarray(v) * scale
                            for k, v in mu_sample.items()},
            "delta_norms": delta, "p0_norms": p0}

    def free(self):
        self.trainer = self.step = self._norms = self._delta_norms = None
        gc.collect()


def rows_fed(checks, toks, batches, prefix=""):
    """Which dataset rows the recorded steps were fed: each must be a row
    of the dataset, none twice. Returns the rows' indices per step."""
    index = {row.tobytes(): i for i, row in enumerate(toks)}
    ids = [[index.get(r.tobytes(), -1) for r in b] for b in batches]
    flat = [i for b in ids for i in b]
    checks.add(prefix + "rows_fed_not_in_dataset", sum(i < 0 for i in flat),
               0, "==")
    checks.add(prefix + "rows_fed_twice_in_first_steps",
               len(flat) - len(set(flat)), 0, "==")
    return ids


def compare(checks, prog, out, lim, spec):
    """The program's recorded first steps against the reference's."""
    for k, (a, b) in enumerate(zip(prog["losses"], out["losses"])):
        checks.add(f"loss_step{k}_gap", abs(a - b), lim["loss_gap"])
    g, gw = chk.worst_leaf_gap(prog["grad_norms"], out["grad_norms"])
    checks.add(f"first_grad_norm_worst_leaf_gap[{gw}]", g, lim["grad_norm_gap"])
    rel = chk.sampled_rel_diffs(prog["grad_sample"], out["grad_sample"])
    # the gradients of the biases and norm parameters are sums of the
    # back-propagated signal over tokens: they carry the rounding of the
    # forward and backward passes and not that of the weight-gradient
    # matmul itself, and pooled over all layers they are steady to a few
    # per cent from seed to seed: the number that holds int8 out
    checks.add("first_grad_vector_leaves_pooled_rel_diff",
               chk.pooled(rel, chk.vector_leaves(spec)),
               lim["grad_vector_pooled"])
    e, ew = chk.worst(rel)
    checks.add(f"first_grad_sampled_elements_worst_rel_diff[{ew}]", e,
               lim["grad_elem_diff"])
    d, dw = chk.worst_leaf_gap(prog["delta_norms"], out["delta_norms"])
    checks.add(f"param_change_norm_worst_leaf_gap[{dw}]", d,
               lim["delta_norm_gap"])


def run(env) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_compute_pytorch_tpu.obs.tracing import (
        Tracer, configure_tracer)

    cfg, traffic, cell = env.config, env.traffic, env.cell
    chips = env.chips
    seq = traffic["seq_len"]
    global_batch = traffic["sequences_per_chip"] * chips
    spe = traffic["steps_per_epoch"]
    lim = cell["limits"]
    checks = chk.Checks()

    # ---- set-up -----------------------------------------------------
    toks = make_dataset(traffic, cfg["vocab_size"], env.seed, global_batch)
    plain = families.without_dropout(cfg)
    compared = None
    if plain != cfg:
        # the compared program: dropout off, otherwise the timed one
        prog_c = Program(env, plain, toks)
        prog_c.hand_over(env.seed)
        compared = prog_c.recorded(env.seed,
                                   lambda: prog_c.trainer.train_epoch(0))
        del compared["x"]
        prog_c.free()
        del prog_c
    prog = Program(env, cfg, toks)
    prog.hand_over(env.seed)
    trainer = prog.trainer
    first = prog.recorded(env.seed, lambda: trainer.train_epoch(0))

    tracer = None
    if env.trace:
        tracer = Tracer()
        configure_tracer(tracer)
    win = tracewin.TraceWindow(env, start_frac=0.3)

    # ---- the measured window ---------------------------------------
    env.window_opens()
    t0 = time.monotonic()
    win.arm(t0)
    epochs, epoch_s = 0, []
    while time.monotonic() - t0 < env.seconds:
        epochs += 1
        t_e = time.monotonic()
        trainer.train_epoch(epochs)
        epoch_s.append(round(time.monotonic() - t_e, 3))
    t1 = time.monotonic()
    win.close()
    built = env.watch.between(t0, t1)
    # ---- after the window ------------------------------------------
    steps = epochs * spe
    tokens_per_s = steps * global_batch * seq / (t1 - t0)
    peak = env.memory_peak()
    spans = tracer.events() if tracer is not None else []
    if tracer is not None:
        configure_tracer(None)
    # one more step on the first recorded batch: its loss against the
    # loss the same rows had at step 0
    x0 = first.pop("x")[0]
    state, m_end = prog.step(trainer.state, x0, x0)
    loss_end = float(m_end["loss"])
    ref, spec, opt = prog.ref, prog.spec, prog.opt
    del state, m_end, x0, trainer
    prog.free()
    del prog

    # ---- the reference follows the recorded steps -------------------
    ids = rows_fed(checks, toks, first["batches"])
    if compared is None:
        compared = first
    elif rows_fed(checks, toks, compared["batches"], "compared_") != ids:
        checks.add("compared_and_timed_programs_fed_other_rows", 1, 0, "==")
    t_ref = time.monotonic()
    dev0 = jax.devices()[0]
    p_ref = weights.make_params(spec, env.seed, jnp.float32, device=dev0)
    ref_p0 = jax.device_get(ref.leaf_norms(p_ref))
    sides = [("", compared)] + ([("timed_", first)]
                                if compared is not first else [])
    for name, side in sides:
        same, _ = chk.worst_leaf_gap(side["p0_norms"], ref_p0)
        checks.add(name + "weights_handed_over_vs_reference_gap", same,
                   lim["weights_gap"])
    ref_batches = [jnp.asarray(toks[[i for i in b if i >= 0]]) for b in ids]
    out = ref.train_steps(
        p_ref, ref_batches, plain, opt, "f32",
        traffic.get("reference_rows_per_block", 4), devices=env.devices,
        make_p0=lambda: weights.make_params(spec, env.seed, jnp.float32,
                                            device=dev0))
    del p_ref
    ref_s = time.monotonic() - t_ref
    compare(checks, compared, out, lim, spec)
    if compared is not first:
        # the timed object itself (dropout on): its losses on the same
        # rows stay beside the reference's
        for k, (a, b) in enumerate(zip(first["losses"], out["losses"])):
            checks.add(f"timed_loss_step{k}_gap", abs(a - b),
                       lim["timed_loss_gap"])
    checks.add("loss_end_minus_loss_step0", loss_end - first["losses"][0],
               0.0, "<")
    checks.add("programs_built_in_window", len(built), 0, "==")
    print(f"INFO seconds per epoch of {spe} steps: {epoch_s}")
    print(f"INFO reference: {ref_s:.1f} s for {Program.N_CHECK} steps; "
          f"compared program's losses {compared['losses']} timed program's "
          f"{first['losses']} reference {out['losses']}; loss after the "
          f"window {loss_end}")

    facts = {"steps": steps, "tokens_per_step": global_batch * seq,
             "seq_len": seq, "global_batch": global_batch,
             "train_tokens_per_s": tokens_per_s, "window_s": t1 - t0,
             "reference_s": ref_s}
    return {"checks": checks, "attempted": steps, "failed": 0,
            "e2e": {"train_tokens_per_s": tokens_per_s},
            "memory_peak_bytes": peak, "spans": spans, "counters": facts,
            "trace": win.result(), "requests": None}
