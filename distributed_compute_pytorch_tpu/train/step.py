"""The compiled SPMD step functions.

This single module replaces four reference components at once (SURVEY.md §7
layer 4): the train loop body (``/root/reference/main.py:55-68``), the eval
loop body (``main.py:70-95``), the DDP gradient sync (``main.py:122``) and the
explicit metric all-reduces (``main.py:65,90,91``). Everything is one jitted
function over the mesh:

- the batch arrives sharded over the batch axes; params live wherever the
  partition strategy put them;
- gradients of replicated params are globally summed by XLA (the DDP
  all-reduce, now fused into the compiled step and riding ICI);
- metric outputs are unsharded scalars, so XLA inserts the cross-shard
  reductions the reference did with ``dist.all_reduce(SUM)``.

Host<->device discipline: step functions return device scalars that are only
*read* at the logging cadence (every ``log_every`` steps, reference
``main.py:64``), so the hot loop never blocks on transfers (SURVEY §7 hard
part c).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import (
    batch_sharding, use_manual_axes, use_mesh)
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.parallel import collectives as coll
from distributed_compute_pytorch_tpu.parallel.api import (
    DataParallel, tree_shardings)

PyTree = Any


@partial(jax.tree_util.register_dataclass,
         data_fields=["step", "params", "model_state", "opt_state", "rng"],
         meta_fields=[])
@dataclass
class TrainState:
    """Everything that evolves during training, as one pytree.

    The reference splits this across the DDP-wrapped module, the torch
    optimizer and the scheduler (``main.py:118-125``); here it is a single
    donated pytree so each step updates in place on device.
    """

    step: jax.Array          # global step counter (drives the LR schedule)
    params: PyTree
    model_state: PyTree      # e.g. BatchNorm running stats
    opt_state: PyTree
    rng: jax.Array           # base key; per-step keys are fold_in(rng, step)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


def _grad_sumsq(tree):
    """f32 sum of squares over every leaf — the global-gradient-norm
    proxy the non-finite guard checks (NaN/Inf anywhere surfaces here;
    the square can only ADD an overflow-to-Inf, never hide one)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)


def make_step_fns(model, tx: optax.GradientTransformation, mesh: Mesh,
                  strategy=None, donate: bool = True, compute_dtype=None,
                  augment=None, shard_update: bool | None = None,
                  quant_collectives: bool = False, accum_steps: int = 1,
                  accum_dtype=None, accum_bucket_mb: float | None = None,
                  nonfinite_policy: str = "raise",
                  sentinel: bool = False):
    """Build ``(init_fn, train_step, eval_step)`` for ``model`` on ``mesh``.

    ``strategy`` decides parameter layout (default pure DP = replicated,
    reference parity). ``compute_dtype`` (e.g. ``jnp.bfloat16``) casts
    floating-point inputs before the forward pass — the TPU fast path; params
    stay in their own dtype and are cast inside the layers. ``augment`` is an
    optional ``(x, rng) -> x`` transform (``ops/augment.py``) traced into the
    TRAIN step only — device-side augmentation, eval untouched. The returned
    functions are jit-compiled; train_step donates the state buffers.

    ``shard_update`` — ZeRO-1 cross-replica weight-update sharding
    (``parallel/collectives.py``; default ON when the strategy is
    ``DataParallel`` and the dp world size > 1): instead of every replica
    all-reducing full gradients and redundantly running the whole
    O(params) update on fully replicated ``opt_state``, each gradient
    leaf is reduce-scattered into a 1/N shard, the optimizer update runs
    shard-local inside a ``shard_map`` over the dp axes (which is also
    what lets ``adamw_fused``'s Pallas kernel run on the shard instead
    of being replicated-only), and the updated params are all-gathered
    back. ``opt_state`` is BORN sharded via ``init_fn``'s out_shardings
    and stays sharded for the life of the run — per-chip optimizer HBM
    drops by the dp-axis size. Param trajectories match the replicated
    update to f32 reduction-order tolerance. Leaves too small or
    indivisible stay replicated and pay the old update (byte-budget
    rounding error). Pass ``False`` to force the replicated update.

    ``quant_collectives`` — opt-in block-scaled int8 GRADIENT collectives
    (EQuARX-motivated): the whole loss+grad+update runs inside one
    shard_map manual over the dp axis, so the gradient cross-replica
    reduction IS ``collectives.quantized_reduce_scatter`` (int8 wire
    bytes, f32 accumulate) rather than the partitioner's exact psum.
    Requires ``shard_update``, a single dp axis, a stateless model (no
    BatchNorm-style cross-batch state — its stats would turn shard-local
    inside the manual region) and no ``augment``; losses that are means
    over fixed-size shards reproduce the exact-path loss, and gradients
    differ by the collective's bounded quantization error
    (tests/test_collectives.py).

    ``accum_steps`` — STEP-LEVEL gradient accumulation (the SPMD analog
    of DDP ``no_sync``, arXiv:1810.11112): the global batch ``[B, ...]``
    is split into ``accum_steps`` microbatches and a ``lax.scan`` inside
    the compiled step accumulates **local, un-reduced** gradients in
    ``accum_dtype`` (f32 default, bf16 opt-in), paying exactly ONE dp
    gradient reduction per optimizer update at the scan boundary instead
    of one per microbatch. Under the ``DataParallel`` strategy with
    dp > 1 the whole step runs inside a dp-manual shard_map so the
    boundary reduction is explicit — plain psum, ZeRO-1 reduce-scatter
    (``shard_update``), or ``quantized_reduce_scatter``
    (``quant_collectives``) — and provable at the jaxpr level
    (``collectives.grad_collective_stats``); the boundary is pipelined
    over parameter buckets (``accum_bucket_mb``, DDP's bucket_cap_mb
    move: bucket k's reduce-scatter overlaps bucket k-1's optimizer
    update + all-gather; 0 disables). Activation memory stays at ONE
    microbatch (composes with remat'd models); ``adamw_fused`` composes
    (accumulation no longer lives in the optax chain); BatchNorm models
    keep sync-BN statistics, updated once per microbatch
    (``models/layers.py::BatchNorm``, ``tests/test_batchnorm.py``).
    Other strategies (FSDP/TP, or dp == 1) take an automatic-partitioner
    scan: same one-compiled-step / one-microbatch-activations contract,
    but the collective placement is the partitioner's.

    ``nonfinite_policy`` — divergence containment. ``"raise"`` (default)
    compiles nothing extra: the trainer aborts when a non-finite loss
    shows up at its log-cadence fetch. ``"skip"`` compiles a guard INTO
    the step: the update is applied only when the loss AND the global
    gradient sum-of-squares are finite; otherwise params, opt_state and
    model_state come back BIT-UNTOUCHED (a ``where`` select against the
    incoming state — one bad batch cannot poison the trajectory), the
    step counter still advances (the rng stream moves on, so the next
    attempt draws fresh masks), and ``metrics["skipped"]`` reports 1.0
    so the trainer can count and give up after K consecutive skips.
    Incompatible with ``quant_collectives`` (the gradients live inside
    its manual region with quantized wire values; guard there would
    check the wrong numbers).

    ``sentinel`` — adds ``metrics["grad_sumsq"]`` (the same f32 global
    gradient sum-of-squares the skip guard checks) to every step's
    metrics, feeding the trainer's per-step loss/grad-norm hash chain
    (``obs/sentinel.py``) for bitwise run diffing. Free when the skip
    guard is on (the scalar already exists); one extra fused reduction
    per leaf otherwise. Not available under ``quant_collectives``
    (same reason as the guard) — the chain falls back to loss-only.
    """
    if nonfinite_policy not in ("raise", "skip"):
        raise ValueError(f"nonfinite_policy must be 'raise' or 'skip', "
                         f"got {nonfinite_policy!r}")
    skip_guard = nonfinite_policy == "skip"
    # the sentinel's grad_sumsq metric rides the skip guard's scalar
    # when both are on; quant_collectives cannot surface it (gradients
    # exist only quantized inside the manual region)
    need_gn2 = skip_guard or (sentinel and not quant_collectives)
    if skip_guard and quant_collectives:
        raise ValueError(
            "nonfinite_policy 'skip' does not compose with "
            "quant_collectives (gradients only exist quantized inside "
            "the manual region); use nonfinite_policy 'raise'")
    strategy = strategy or DataParallel()
    fused_opt = hasattr(tx, "fused_apply")
    dp_ax = coll.dp_axes(mesh)
    dp_n = coll.dp_size(mesh)
    elementwise = getattr(tx, "elementwise_update", True)
    if shard_update is None:
        zero1 = (isinstance(strategy, DataParallel) and dp_n > 1
                 and elementwise)
    else:
        zero1 = bool(shard_update)
        if zero1 and not elementwise:
            # global-norm clip computes over EVERY element of every leaf;
            # on shards it would clip against a shard-local norm
            raise ValueError(
                "shard_update cannot run a non-elementwise optimizer "
                "chain (global-norm clip) on per-leaf shards; drop "
                "--clip_norm or --shard_update")
        if zero1 and not isinstance(strategy, DataParallel):
            # FSDP/TP opt_state is already sharded by the parameter
            # layout; ZeRO-1 is specifically the fix for REPLICATED
            # parameter training
            raise ValueError(
                "shard_update applies to the DataParallel strategy only "
                "(FSDP/ShardingRules already shard opt_state with the "
                "params)")
        if zero1 and dp_n <= 1:
            zero1 = False
    accum_steps = int(accum_steps or 1)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    accum_dtype = jnp.dtype(accum_dtype if accum_dtype is not None
                            else jnp.float32)
    if accum_dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"accum_dtype must be float32 or bfloat16, got {accum_dtype}")
    # the boundary-reduction (manual) accumulation path: pure DP with a
    # real dp axis — elsewhere (FSDP/TP layouts, dp=1) the automatic
    # partitioner owns collective placement and accumulation is a plain
    # scan (see _accum_auto_step)
    accum_manual = (accum_steps > 1 and isinstance(strategy, DataParallel)
                    and dp_n > 1)
    bucket_bytes = ((coll.DEFAULT_BUCKET_MB if accum_bucket_mb is None
                     else accum_bucket_mb) * 1e6)
    if not elementwise:
        # a global-norm clip couples every leaf: the boundary update must
        # see the whole gradient at once (single bucket; still one
        # reduction per update — only the overlap pipelining is off)
        bucket_bytes = 0
    if quant_collectives:
        if not zero1:
            raise ValueError(
                "quant_collectives requires shard_update (DataParallel, "
                "dp world size > 1)")
        if len(dp_ax) != 1:
            raise ValueError(
                f"quant_collectives needs a single dp axis for its "
                f"all_to_all exchange; mesh has {dp_ax}")
        if augment is not None:
            raise ValueError(
                "quant_collectives runs the step inside a dp-manual "
                "shard_map where device-side augmentation would draw "
                "shard-local masks; drop --augment or the quantized mode")
    # Interleaved layer STORAGE (parallel/pipeline.py): when the model
    # wants the Megatron interleaved schedule (virtual_stages > 1) on a
    # pipe mesh, the live TrainState keeps its blocks permuted into the
    # strided per-device layout for the whole run — init permutes once,
    # the steps announce it via `interleaved_layout` so pipeline_blocks
    # consumes the storage in place, and the per-step cross-pipe
    # all-to-all re-gather (plus its backward scatter) vanishes from the
    # compiled program. Checkpoints stay LOGICAL: the trainer converts
    # at its save/restore boundaries via state_layout_transforms.
    _v = getattr(getattr(model, "config", None), "virtual_stages", 1)
    _pipe = (mesh.shape["pipe"] if "pipe" in mesh.axis_names else 1)
    interleave = (_v > 1 and _pipe > 1)
    if interleave:
        from distributed_compute_pytorch_tpu.parallel.pipeline import (
            interleave_blocks, interleaved_layout)
        _layout_ctx = lambda: interleaved_layout(_pipe, _v)
    else:
        import contextlib
        _layout_ctx = contextlib.nullcontext
    if fused_opt and not isinstance(strategy, DataParallel):
        # a pallas custom call is opaque to the GSPMD partitioner: under a
        # sharded parameter layout XLA would replicate (all-gather) every
        # leaf into the kernel, silently defeating FSDP/TP memory savings
        # or OOMing — refuse loudly instead. (Under DataParallel +
        # shard_update the kernel is no longer replicated-only: the
        # ZeRO-1 shard_map body hands it explicit per-shard LOCAL arrays,
        # so the partitioner never sees the custom call at all.)
        raise ValueError(
            "fused optimizers (adamw_fused) support replicated parameters "
            "(DataParallel) only; use --optimizer adamw with sharded "
            "parameter layouts")

    def _cast(x):
        if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(compute_dtype)
        return x

    def _cast_params(params):
        """Mixed precision: compute in ``compute_dtype`` while master params
        (and optimizer state) stay in their own dtype — the cast is inside
        the grad closure, so gradients flow back to the master dtype. This is
        what makes ``compute_dtype=bfloat16`` effective for token models too,
        whose int inputs pass ``_cast`` untouched."""
        if compute_dtype is None:
            return params
        return jax.tree.map(_cast, params)

    def _state_shardings(state_shapes: TrainState) -> TrainState:
        repl = NamedSharding(mesh, P())
        # ZeRO-1: opt_state is BORN in the update-shard layout (and stays
        # there — the sharded update's out_specs keep it), so the 2x-params
        # AdamW moments never exist replicated on any chip
        opt = (coll.tree_update_shardings(state_shapes.opt_state, mesh)
               if zero1 else
               tree_shardings(strategy, state_shapes.opt_state, mesh))
        return TrainState(
            step=repl,
            params=tree_shardings(strategy, state_shapes.params, mesh),
            model_state=jax.tree.map(lambda _: repl, state_shapes.model_state),
            opt_state=opt,
            rng=repl,
        )

    def _init(key) -> TrainState:
        params, model_state = model.init(key)
        if interleave:
            # one-time permutation into interleaved storage; tx.init on
            # the permuted tree means the optimizer state is BORN in the
            # same layout (momentum rows travel with their params)
            params = {**params,
                      "blocks": interleave_blocks(params["blocks"],
                                                  _pipe, _v)}
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            model_state=model_state,
            opt_state=tx.init(params),
            rng=jax.random.key(0) if key is None else key,
        )

    def init_fn(key) -> TrainState:
        """Initialise the train state directly into its mesh layout.

        jit-with-out_shardings means FSDP params are *born sharded* — no
        host-side full copy, which is what lets models larger than one chip's
        HBM initialise at all.
        """
        shapes = jax.eval_shape(_init, key)
        shardings = _state_shardings(shapes)
        return jax.jit(_init, out_shardings=shardings)(key)

    # NOTE: train/eval steps take their shardings from the *arrays* — init_fn
    # commits the state to the strategy's layout and the DeviceFeeder commits
    # batches to the batch axes, so jit sees fully-specified layouts and the
    # SPMD partitioner inserts the implied collectives.

    def _update(g, o, p):
        """Apply the optimizer to one (gradient, opt_state, params)
        triple. On the replicated path these are full arrays; inside the
        ZeRO-1 shard_map body they are the per-shard LOCAL arrays — every
        transform in the supported chains is elementwise over leaves, so
        the same code serves both (clip_by_global_norm is the known
        non-elementwise exception; the trainer gates it off)."""
        if fused_opt:
            # single-pass fused optimizers produce new params directly —
            # the update->apply_updates contract would cost one extra
            # O(params) pass just to materialise deltas
            return tx.fused_apply(g, o, p)
        updates, new_o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), new_o

    def _local_update(g, o, p):
        with scope("optimizer"):
            return _update(g, o, p)

    def _zero1_update(grads, opt_state, params):
        """RS -> shard-local update -> AG (the weight-update-sharding
        paper's transform, annotation-driven): the shard_map's in_specs
        mark each leaf's 1/N update layout, so the partitioner
        materialises the gradients' pending cross-replica psum AS a
        reduce-scatter at the region boundary; the body updates the
        shard (this is where ``adamw_fused``'s Pallas kernel runs
        per-shard-local); the closing replicated constraint is the param
        all-gather. ``opt_state`` goes in sharded and comes out sharded
        — it never exists replicated."""
        p_specs = coll.tree_update_specs(params, dp_n, dp_ax)
        o_specs = coll.tree_update_specs(opt_state, dp_n, dp_ax)
        body = jax.shard_map(_update, mesh=mesh,
                             in_specs=(p_specs, o_specs, p_specs),
                             out_specs=(p_specs, o_specs),
                             axis_names=set(dp_ax))
        with scope("optimizer"):
            new_p, new_o = body(grads, opt_state, params)
            repl = NamedSharding(mesh, P())
            new_p = jax.tree.map(
                lambda a: lax.with_sharding_constraint(a, repl), new_p)
        return new_p, new_o

    def _quant_step(state: TrainState, x, y, step_rng):
        """Opt-in quantized-gradient ZeRO-1 step: loss, backward and
        update all inside ONE shard_map manual over the single dp axis,
        so each rank holds its honest per-shard gradient and the
        cross-replica reduction IS the block-scaled int8
        ``quantized_reduce_scatter`` (int8 + per-block f32 scales on the
        wire, f32 accumulate; bf16 for tiny chunks; exact psum for
        leaves that stay replicated). Params enter replicated (no comm),
        updated shards all-gather back inside the region."""
        ax = dp_ax[0]
        params, opt_state = state.params, state.opt_state
        p_specs = coll.tree_update_specs(params, dp_n, dp_ax)
        o_specs = coll.tree_update_specs(opt_state, dp_n, dp_ax)
        rng_data = jax.random.key_data(step_rng)

        def body(p, o, xs, ys, rd):
            rng = jax.random.wrap_key_data(rd)
            if hasattr(model, "train_loss"):
                def local_loss(pp):
                    return model.train_loss(_cast_params(pp),
                                            state.model_state, xs, ys,
                                            rng=rng)
            else:
                def local_loss(pp):
                    out, _ = model.apply(_cast_params(pp),
                                         state.model_state, xs,
                                         train=True, rng=rng)
                    return model.loss_fn(out, ys), None
            (loss, _), g = jax.value_and_grad(local_loss,
                                              has_aux=True)(p)
            # global-mean loss/grads = mean of the per-shard means (the
            # feeder guarantees equal-size shards)
            loss = lax.psum(loss, ax) / dp_n

            def reduce_leaf(gl, spec):
                d = coll.spec_shard_dim(spec)
                if d is None:
                    return lax.psum(gl, ax) / dp_n
                return coll.quantized_reduce_scatter(gl, ax, dp_n,
                                                     dim=d) / dp_n

            with scope("grad_reduce"):
                g = jax.tree.map(reduce_leaf, g, p_specs)

            def slice_leaf(pl, spec):
                # params entered the region replicated (full local
                # copies, zero comm); the update consumes the shard
                d = coll.spec_shard_dim(spec)
                return pl if d is None else coll.shard_slice(pl, ax, dp_n,
                                                             dim=d)

            new_p, new_o = _local_update(g, o,
                                         jax.tree.map(slice_leaf, p,
                                                      p_specs))

            def gather_leaf(pl, spec):
                d = coll.spec_shard_dim(spec)
                return pl if d is None else coll.all_gather(pl, ax, dim=d)

            new_p = jax.tree.map(gather_leaf, new_p, p_specs)
            return new_p, new_o, loss

        repl_p = jax.tree.map(lambda _: P(), params)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(repl_p, o_specs, P(ax), P(ax), P()),
                           out_specs=(repl_p, o_specs, P()),
                           axis_names={ax})
        # use_manual_axes: the model's internal layout pins (constrain /
        # constrain_activations) must drop the now-manual dp axis
        with use_mesh(mesh), use_manual_axes((ax,)), _layout_ctx():
            return fn(params, opt_state, x, y, rng_data)

    def _micro_loss_fn(p, ms, xm, ym, k):
        """One microbatch's loss closure over fixed params ``p`` —
        shared by both accumulation paths. Returns ``(loss, new_ms)``."""
        xm = _cast(xm)
        if augment is not None:
            # same dedicated-key discipline as the non-accum step
            xm = augment(xm, jax.random.fold_in(k, 0x41554747))
        if hasattr(model, "train_loss"):
            return model.train_loss(_cast_params(p), ms, xm, ym, rng=k)
        out, new_ms = model.apply(_cast_params(p), ms, xm, train=True,
                                  rng=k)
        return model.loss_fn(out, ym), new_ms

    def _micro_scan(params, mstate, xs, ys, rng):
        """``lax.scan`` over the microbatches: accumulate local
        (un-reduced on the manual path) gradients in ``accum_dtype``,
        thread ``model_state`` so BatchNorm statistics see every
        microbatch in sequence (N reference steps' worth of running-stat
        updates), and fold the microbatch index into the rng so each
        microbatch draws its own dropout/augment masks."""

        def micro(carry, inp):
            acc, ms = carry
            xm, ym, i = inp
            k = jax.random.fold_in(rng, i)
            (loss, new_ms), g = jax.value_and_grad(
                _micro_loss_fn, has_aux=True)(params, ms, xm, ym, k)
            acc = jax.tree.map(lambda a, gl: a + gl.astype(a.dtype),
                               acc, g)
            return (acc, new_ms), loss

        acc0 = jax.tree.map(lambda l: jnp.zeros(l.shape, accum_dtype),
                            params)
        (gsum, new_ms), losses = lax.scan(
            micro, (acc0, mstate), (xs, ys, jnp.arange(accum_steps)))
        return gsum, new_ms, losses

    def _accum_manual_step(state: TrainState, x, y, step_rng):
        """Step-level accumulation under pure DP: the whole step runs in
        ONE shard_map manual over the dp axes. Each rank scans its local
        microbatch shards accumulating honest per-rank gradients with NO
        cross-replica traffic (DDP ``no_sync``); the scan boundary then
        pays the update's single reduction per leaf — psum for
        replicated leaves, reduce-scatter into the ZeRO-1 update shard
        for sharded ones, the block-scaled int8 exchange under
        ``quant_collectives`` — pipelined over parameter buckets so
        bucket k's collective rides under bucket k-1's optimizer update
        and param all-gather. The jaxpr therefore contains zero
        grad-sized dp collectives inside the scan and exactly one per
        leaf at the boundary, for any N
        (``collectives.grad_collective_stats``)."""
        params, opt_state = state.params, state.opt_state
        if zero1:
            p_specs = coll.tree_update_specs(params, dp_n, dp_ax)
            o_specs = coll.tree_update_specs(opt_state, dp_n, dp_ax)
        else:
            p_specs = jax.tree.map(lambda _: P(), params)
            o_specs = jax.tree.map(lambda _: P(), opt_state)
        ax_spec = dp_ax if len(dp_ax) > 1 else dp_ax[0]
        buckets = coll.bucketize(params, bucket_bytes)
        rng_data = jax.random.key_data(step_rng)
        mstate = state.model_state
        repl_ms = jax.tree.map(lambda _: P(), mstate)

        def body(p, o, ms, xs, ys, rd):
            rng = jax.random.wrap_key_data(rd)
            # per-rank streams: the auto partitioner slices ONE global
            # dropout/augment mask across ranks; inside the manual
            # region each rank draws its own, so fold the rank in
            for a in dp_ax:
                rng = jax.random.fold_in(rng, lax.axis_index(a))
            xs = xs.reshape((accum_steps, xs.shape[0] // accum_steps)
                            + xs.shape[1:])
            ys = ys.reshape((accum_steps, ys.shape[0] // accum_steps)
                            + ys.shape[1:])
            gsum, new_ms, losses = _micro_scan(p, ms, xs, ys, rng)
            # global mean loss = mean of the equal-size per-rank,
            # per-microbatch means
            loss = lax.psum(jnp.mean(losses), dp_ax) / dp_n
            scale = 1.0 / (accum_steps * dp_n)

            def reduce_leaf(gl, spec, pl):
                d = coll.spec_shard_dim(spec)
                with scope("grad_reduce"):
                    if d is None:
                        red = lax.psum(gl, dp_ax)
                    elif quant_collectives:
                        red = coll.quantized_reduce_scatter(gl, dp_ax[0],
                                                            dp_n, dim=d)
                    else:
                        red = coll.reduce_scatter(gl, ax_spec, dim=d)
                    return (red.astype(jnp.float32) * scale).astype(
                        pl.dtype)

            def slice_leaf(pl, spec):
                d = coll.spec_shard_dim(spec)
                return pl if d is None else coll.shard_slice(
                    pl, ax_spec, dp_n, dim=d)

            def gather_leaf(pl, spec):
                d = coll.spec_shard_dim(spec)
                return pl if d is None else coll.all_gather(pl, ax_spec,
                                                            dim=d)

            new_p, new_o = coll.bucketed_update(
                gsum, o, p, p_specs, buckets,
                reduce_leaf=reduce_leaf, slice_leaf=slice_leaf,
                gather_leaf=gather_leaf, update_fn=_local_update)
            if need_gn2:
                # per-rank LOCAL grad sum-of-squares, psum'd: non-finite
                # on any rank => non-finite here (the reduced gradient
                # inherits it), so the outer guard sees every divergence
                gn2 = lax.psum(_grad_sumsq(gsum), dp_ax)
                return new_p, new_o, new_ms, loss, gn2
            return new_p, new_o, new_ms, loss

        repl_p = jax.tree.map(lambda _: P(), params)
        out_specs = (repl_p, o_specs, repl_ms, P())
        if need_gn2:
            out_specs = out_specs + (P(),)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(repl_p, o_specs, repl_ms,
                                     P(ax_spec), P(ax_spec), P()),
                           out_specs=out_specs,
                           axis_names=set(dp_ax))
        # use_manual_axes: constrain() pins AND BatchNorm's sync-stat
        # pmean (models/layers.py) key off the declared manual dp axes
        with use_mesh(mesh), use_manual_axes(dp_ax), _layout_ctx():
            new_p, new_o, new_ms, loss, *rest = fn(params, opt_state,
                                                   mstate, x, y, rng_data)
        if zero1:
            repl = NamedSharding(mesh, P())
            new_p = jax.tree.map(
                lambda a: lax.with_sharding_constraint(a, repl), new_p)
        return new_p, new_o, new_ms, loss, (rest[0] if rest else None)

    def _accum_auto_step(state: TrainState, x, y, step_rng):
        """Step-level accumulation under the automatic partitioner
        (FSDP/TP layouts, or dp == 1): one compiled step, activation
        memory of one microbatch, schedules advancing per UPDATE — but
        collective placement belongs to the partitioner, so the
        one-boundary-reduction guarantee is NOT made here (under FSDP
        the per-microbatch reduce-scatter is structural: gradients must
        land in the parameter shards the backward produces them for)."""
        B = x.shape[0]
        xs = x.reshape((accum_steps, B // accum_steps) + x.shape[1:])
        ys = y.reshape((accum_steps, B // accum_steps) + y.shape[1:])
        bspec = batch_sharding(mesh, 1).spec[0]
        if bspec is not None:
            # keep each microbatch batch-sharded: the reshape must not
            # gather microbatch rows onto one device
            xs = lax.with_sharding_constraint(xs, NamedSharding(
                mesh, P(None, bspec, *([None] * (xs.ndim - 2)))))
            ys = lax.with_sharding_constraint(ys, NamedSharding(
                mesh, P(None, bspec, *([None] * (ys.ndim - 2)))))
        with use_mesh(mesh), _layout_ctx():
            gsum, new_ms, losses = _micro_scan(state.params,
                                               state.model_state,
                                               xs, ys, step_rng)
        grads = jax.tree.map(
            lambda g, pl: (g.astype(jnp.float32)
                           / accum_steps).astype(pl.dtype),
            gsum, state.params)
        new_p, new_o = _local_update(grads, state.opt_state, state.params)
        gn2 = _grad_sumsq(gsum) if need_gn2 else None
        return new_p, new_o, new_ms, jnp.mean(losses), gn2

    def _guarded(state: TrainState, new_params, new_opt_state,
                 new_mstate, loss, gn2, metrics):
        """The non-finite skip: keep the UPDATED state only when loss
        and the gradient sum-of-squares are finite; a bad batch leaves
        params/opt_state/model_state bit-identical to the incoming
        state (the scalar-pred ``where`` preserves shardings — ZeRO-1
        opt shards select shard-locally). ``step`` always advances so
        the rng stream (and the skip's visibility in metrics) moves."""
        ok = jnp.isfinite(loss) & jnp.isfinite(gn2)
        sel = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), new, old)
        new_state = state.replace(
            step=state.step + 1,
            params=sel(new_params, state.params),
            model_state=sel(new_mstate, state.model_state),
            opt_state=sel(new_opt_state, state.opt_state))
        metrics["skipped"] = (~ok).astype(jnp.float32)
        return new_state, metrics

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(state: TrainState, x, y):
        """One optimization step == reference ``train`` body (``main.py:57-63``)."""
        step_rng = jax.random.fold_in(state.rng, state.step)
        if accum_steps > 1:
            div = accum_steps * (dp_n if accum_manual else 1)
            if x.shape[0] % div:
                raise ValueError(
                    f"grad accumulation needs the global batch "
                    f"({x.shape[0]}) divisible by accum_steps"
                    f"{' x dp world size' if accum_manual else ''} "
                    f"({div}); pick a batch/accum combination that "
                    f"divides evenly")
            step_fn = (_accum_manual_step if accum_manual
                       else _accum_auto_step)
            new_params, new_opt_state, new_mstate, loss, gn2 = step_fn(
                state, x, y, step_rng)
            metrics = {"loss": loss.astype(jnp.float32)}
            if sentinel and gn2 is not None:
                metrics["grad_sumsq"] = gn2.astype(jnp.float32)
            if skip_guard:
                return _guarded(state, new_params, new_opt_state,
                                new_mstate, loss, gn2, metrics)
            new_state = state.replace(
                step=state.step + 1, params=new_params,
                model_state=new_mstate, opt_state=new_opt_state)
            return new_state, metrics
        x = _cast(x)
        if augment is not None:
            # dedicated key: the model's rng stream is unchanged whether or
            # not augmentation is on
            x = augment(x, jax.random.fold_in(step_rng, 0x41554747))

        if hasattr(model, "train_loss"):
            # models owning their objective end-to-end (e.g. BERT's MLM
            # masking needs the step rng before the forward pass)
            def loss_fn(params):
                return model.train_loss(_cast_params(params),
                                        state.model_state, x, y,
                                        rng=step_rng)
        else:
            def loss_fn(params):
                out, new_mstate = model.apply(_cast_params(params),
                                              state.model_state, x,
                                              train=True, rng=step_rng)
                loss = model.loss_fn(out, y)
                return loss, new_mstate

        if quant_collectives:
            if jax.tree_util.tree_leaves(state.model_state):
                raise ValueError(
                    "quant_collectives requires a stateless model: "
                    "cross-batch statistics (BatchNorm) would become "
                    "shard-local inside the dp-manual region")
            new_params, new_opt_state, loss = _quant_step(state, x, y,
                                                          step_rng)
            new_mstate = state.model_state
        else:
            # trace-time mesh context: lets layers (ring attention) find
            # the mesh; the layout context tells pipeline_blocks the
            # blocks are stored pre-interleaved (no-op otherwise)
            with use_mesh(mesh), _layout_ctx():
                (loss, new_mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params)
            if zero1:
                new_params, new_opt_state = _zero1_update(
                    grads, state.opt_state, state.params)
            else:
                new_params, new_opt_state = _local_update(
                    grads, state.opt_state, state.params)
            gn2 = _grad_sumsq(grads) if need_gn2 else None
            if skip_guard:
                metrics = {"loss": loss.astype(jnp.float32)}
                if sentinel:
                    metrics["grad_sumsq"] = gn2.astype(jnp.float32)
                return _guarded(state, new_params, new_opt_state,
                                new_mstate, loss, gn2, metrics)
        new_state = state.replace(
            step=state.step + 1, params=new_params,
            model_state=new_mstate, opt_state=new_opt_state)
        # global mean loss (the reference logs the SUM over ranks, a
        # world-size-scaled number — SURVEY §A.4; we fix to the mean)
        metrics = {"loss": loss.astype(jnp.float32)}
        if sentinel and not quant_collectives:
            metrics["grad_sumsq"] = gn2.astype(jnp.float32)
        return new_state, metrics

    @jax.jit
    def eval_step(state: TrainState, x, y, acc=None, valid=None):
        """Eval-batch metrics == reference ``test`` body (``main.py:78-86``).

        Returns device-side sums; the cross-replica ``all_reduce(SUM)`` of
        ``main.py:90-91`` is implicit in producing unsharded outputs.

        ``acc``: optional metrics pytree from the previous batch, added into
        the result *inside* the compiled step. Passing the running total back
        in makes consecutive eval executions dataflow-dependent, which (a)
        keeps the whole eval pass on device with one host fetch at the end
        and (b) serialises the programs' collectives — independent eval
        batches dispatched async can otherwise run concurrently and deadlock
        the CPU backend's in-process rendezvous (XLA CPU collectives assume
        one program at a time over the faked device set).

        ``valid``: optional float ``[batch]`` mask weighting each example's
        contribution (0.0 for the feeder's wraparound-padded rows), making
        eval exact where the reference double-counts padding.
        """
        with use_mesh(mesh), _layout_ctx():
            out, _ = model.apply(_cast_params(state.params),
                                 state.model_state, _cast(x), train=False)
        if hasattr(model, "eval_metrics"):
            metrics = model.eval_metrics(out, y, valid=valid)
        elif valid is None:
            loss_sum = model.loss_sum(out, y) if hasattr(model, "loss_sum") \
                else model.loss_fn(out, y) * x.shape[0]
            pred = jnp.argmax(out, axis=-1)
            correct = jnp.sum((pred == y).astype(jnp.int32))
            metrics = {"loss_sum": loss_sum.astype(jnp.float32),
                       "correct": correct,
                       "count": jnp.asarray(x.shape[0], jnp.int32)}
        else:
            # generic classifier path ([B, C] outputs): per-example NLL so
            # the mask can weight it. log_softmax first — correct for raw
            # logits (resnet) and idempotent on log-probs (convnet)
            log_probs = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
            per_ex = -jnp.take_along_axis(log_probs, y[:, None], axis=-1)[:, 0]
            pred = jnp.argmax(out, axis=-1)
            metrics = {
                "loss_sum": jnp.sum(per_ex * valid),
                "correct": jnp.sum(((pred == y).astype(jnp.float32)
                                    * valid)).astype(jnp.int32),
                "count": jnp.sum(valid).astype(jnp.int32),
            }
        if acc is not None:
            metrics = jax.tree.map(jnp.add, metrics, acc)
        return metrics

    return init_fn, train_step, eval_step


def state_layout_transforms(model, tx, mesh: Mesh):
    """``(to_logical, to_storage)`` converters between the live training
    state's layer layout and the persistent LOGICAL layout — or ``None``
    when they coincide (no interleaved storage in play).

    ZeRO-1 update sharding needs no VALUE transform here: the sharded
    ``opt_state`` is a device LAYOUT of the same logical arrays, so the
    checkpoint layer round-trips it by construction — the v1 save
    gathers leaves to their logical form, the v2 sharded save writes
    per-shard spans reassembled under any target layout, and restore
    places leaves straight into whatever shardings the template carries
    (sharded -> replicated and back; pinned in tests/test_zero1.py).
    When interleaved storage IS in play, the converters below preserve
    each leaf's live sharding — including ZeRO-1-sharded optimizer
    leaves — via the memoized ``out_shardings``.

    The trainer calls ``to_logical`` on the state it hands to checkpoint
    saves and ``to_storage`` on what restore returns, so every artifact
    on disk keeps logical layer order (generation, interop and
    cross-layout elastic restores never see the strided storage). Both
    transforms permute the ``blocks`` subtree of params AND of every
    params-shaped tree inside the optimizer state
    (``optax.tree_map_params``), and preserve each leaf's sharding.
    """
    v = getattr(getattr(model, "config", None), "virtual_stages", 1)
    pipe = (mesh.shape["pipe"] if "pipe" in mesh.axis_names else 1)
    if v <= 1 or pipe <= 1:
        return None
    import optax as _optax

    from distributed_compute_pytorch_tpu.parallel.pipeline import (
        deinterleave_blocks, interleave_blocks)

    _memo: dict = {}

    def _convert(state: TrainState, fn) -> TrainState:
        def params_fn(p):
            if not (isinstance(p, dict) and "blocks" in p):
                return p
            return {**p, "blocks": fn(p["blocks"], pipe, v)}

        # mask tree marking the blocks leaves, mapped through the
        # optimizer state so momentum/second-moment rows move with
        # their params; non-params leaves (counts) pass through
        mask = jax.tree.map(lambda _: False, state.params)
        if isinstance(mask, dict) and "blocks" in mask:
            mask = {**mask, "blocks": jax.tree.map(lambda _: True,
                                                   mask["blocks"])}

        perm_one = lambda a, m: fn(a, pipe, v) if m else a
        if fn not in _memo:
            # built ONCE per direction (a fresh jit closure per save
            # would retrace the permutation program every checkpoint);
            # shardings are stable for the life of the run
            out_shardings = jax.tree.map(lambda a: a.sharding, state)
            _memo[fn] = jax.jit(
                lambda s: TrainState(
                    step=s.step,
                    params=params_fn(s.params),
                    model_state=s.model_state,
                    opt_state=_optax.tree_map_params(tx, perm_one,
                                                     s.opt_state, mask),
                    rng=s.rng),
                out_shardings=out_shardings)
        return _memo[fn](state)

    return (lambda s: _convert(s, deinterleave_blocks),
            lambda s: _convert(s, interleave_blocks))
