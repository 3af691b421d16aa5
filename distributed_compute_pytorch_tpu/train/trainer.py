"""The trainer loop — reference ``proc()`` (``main.py:98-134``) reimagined.

One process per host drives: epoch loop -> jitted train steps at full device
occupancy -> jitted eval -> LR schedule (compiled into the optimizer) ->
epoch timing -> coordinator checkpoint. Observable behaviour matches the
reference's contract (flags, print cadence and format, metrics, checkpoint
file), with the SURVEY §A bug ledger consciously fixed:

- eval runs on the test split (§A.1) unless ``eval_on_train`` replicates the
  reference's train-set eval;
- gradient sync always on (§A.3) — it's structural under SPMD;
- logged losses are proper means, eval loss properly normalised (§A.4-5);
- one logical checkpoint writer + restore support (§A.6);
- epoch-keyed shuffling (§A.9).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from distributed_compute_pytorch_tpu.core.config import Config
from distributed_compute_pytorch_tpu.core.mesh import (
    initialize_distributed, is_coordinator, make_mesh, dp_world_size)
from distributed_compute_pytorch_tpu.data.datasets import load_dataset
from distributed_compute_pytorch_tpu.data.loader import (
    DeviceFeeder, StreamingDeviceFeeder)
from distributed_compute_pytorch_tpu.data.shards import ShardedFileDataset
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.obs import flight
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
from distributed_compute_pytorch_tpu.obs.tracing import (
    Tracer, configure_tracer, span)
from distributed_compute_pytorch_tpu.train import checkpoint
from distributed_compute_pytorch_tpu.train.elastic import (
    ClusterPreemption, Heartbeat, Preempted, PreemptionGuard, restart_count)
from distributed_compute_pytorch_tpu.train.optim import build_optimizer
from distributed_compute_pytorch_tpu.train.step import (
    make_step_fns, state_layout_transforms)
from distributed_compute_pytorch_tpu.utils.compilation_cache import (
    enable as enable_compile_cache)
from distributed_compute_pytorch_tpu.utils.logging import (
    MetricLogger, device_banner, log0)
from distributed_compute_pytorch_tpu.utils.timing import Timer, maybe_profile

# nonfinite_policy=skip: abort after this many CONSECUTIVE skipped
# updates — scattered skips are survivable (params stay untouched), an
# unbroken run means the run has genuinely diverged
NONFINITE_SKIP_LIMIT = 10


class Trainer:
    """End-to-end training run from a :class:`Config`."""

    def __init__(self, config: Config, model=None, train_data=None,
                 eval_data=None, strategy=None):
        self.config = config
        initialize_distributed(config.coordinator, config.num_processes,
                               config.process_id)
        if config.force_cpu:
            # fixed --no-cuda (reference main.py:142, SURVEY §A.7): an actual
            # boolean that pins the run to host CPU devices; works as long
            # as no backend has initialised yet. Pair with
            # XLA_FLAGS=--xla_force_host_platform_device_count=N for an
            # N-device CPU mesh.
            jax.config.update("jax_platforms", "cpu")
        enable_compile_cache()
        # first line of every run: a trainer that landed on the CPU
        # because the accelerator runtime failed to load must say so
        log0(f"dcp-train | {device_banner()}")
        self.mesh = make_mesh(config.mesh)

        fallback_ok = not config.require_real_data
        data_kw = ({"seq_len": config.seq_len,
                    "tokenizer": config.tokenizer}
                   if config.dataset == "text" else {})
        self.train_data = train_data if train_data is not None else \
            load_dataset(config.dataset, config.data_dir, "train",
                         synthetic_fallback=fallback_ok,
                         download=config.download, **data_kw)
        self.eval_data = eval_data if eval_data is not None else \
            (self.train_data if config.eval_on_train
             else load_dataset(config.dataset, config.data_dir, "test",
                               synthetic_fallback=fallback_ok,
                               download=config.download, **data_kw))

        def _feeder(data, shuffle, batch):
            """In-memory datasets fancy-index through DeviceFeeder; sharded
            on-disk datasets stream with bounded RAM (VERDICT r2 missing #1:
            the ResNet-50/ImageNet rung needs data larger than host memory)."""
            cls = (StreamingDeviceFeeder
                   if isinstance(data, ShardedFileDataset) else DeviceFeeder)
            return cls(data, self.mesh, batch, shuffle=shuffle,
                       seed=config.seed, prefetch=config.prefetch)

        # STEP-LEVEL gradient accumulation (train/step.py accum_steps):
        # the feeder delivers the full EFFECTIVE batch (micro x accum) and
        # the compiled step splits it into microbatches — one train_step
        # dispatch AND one gradient reduction per update, vs the legacy
        # optax-MultiSteps path's N of each. --batch_size keeps its
        # meaning as the microbatch (activation-memory) size, so the
        # effective batch is still N x batch_size; step counts
        # (log_every, checkpoint_every, steps_per_epoch) now tick per
        # UPDATE, which is also what the LR schedules index.
        self.accum = max(1, int(config.grad_accum))
        self.train_feed = _feeder(self.train_data, True,
                                  config.batch_size * self.accum)
        self.eval_feed = _feeder(self.eval_data, False, config.batch_size)
        if self.accum > 1:
            log0(f"grad_accum={self.accum}: step-level accumulation — "
                 f"effective batch {config.batch_size * self.accum} "
                 f"({self.accum} x {config.batch_size} microbatches, one "
                 f"gradient reduction per update); steps count updates")

        self.model = model if model is not None else build_model(
            config.model, **self._model_kwargs())
        self.strategy = (strategy if strategy is not None
                         else self._pick_strategy())

        # grad_accum is NOT passed down: schedules already tick per
        # update (the feeder batch is the effective batch), and the
        # legacy MultiSteps wrapper is superseded by accum_steps below
        self.tx = build_optimizer(
            config.optimizer, config.lr, config.gamma,
            steps_per_epoch=self.train_feed.steps_per_epoch,
            total_steps=self.train_feed.steps_per_epoch * config.epochs,
            weight_decay=config.weight_decay, clip_norm=config.clip_norm,
            warmup_steps=config.warmup_steps)
        compute_dtype = (None if config.compute_dtype in (None, "float32")
                         else jnp.dtype(config.compute_dtype))
        augment = None
        if config.augment not in (None, "none"):
            from distributed_compute_pytorch_tpu.ops.augment import (
                build_augment)
            if self.train_data.inputs.ndim == 4:   # [B, H, W, C] images
                augment = build_augment(config.augment)
            else:
                log0(f"WARNING: --augment {config.augment} needs image "
                     f"(rank-4) inputs; {config.dataset!r} provides rank "
                     f"{self.train_data.inputs.ndim} — ignored")
        accum_dtype = {"float32": jnp.float32, "f32": jnp.float32,
                       "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16}.get(
                           config.accum_dtype)
        if accum_dtype is None:
            raise ValueError(f"--accum_dtype must be float32|bfloat16, "
                             f"got {config.accum_dtype!r}")
        self.init_fn, self.train_step, self.eval_step = make_step_fns(
            self.model, self.tx, self.mesh, self.strategy,
            donate=config.donate, compute_dtype=compute_dtype,
            augment=augment, shard_update=self._resolve_shard_update(),
            quant_collectives=config.quant_collectives,
            accum_steps=self.accum, accum_dtype=accum_dtype,
            accum_bucket_mb=config.accum_bucket_mb,
            nonfinite_policy=config.nonfinite_policy,
            sentinel=(config.divergence_check
                      and not config.quant_collectives))
        # non-finite guard bookkeeping (train/step.py nonfinite_policy):
        # per-step skip flags queue as DEVICE scalars and are only read
        # at the log cadence — no per-step host sync on the hot path
        self._skip_hist: list = []
        self._skips_total = 0
        self._skips_consec = 0
        # hash-chain scalars queue the same way (obs/sentinel.py): per
        # step (loss, grad_sumsq) device scalars, folded at log cadence
        self._chain_pending: list = []
        # interleaved-pipeline runs keep the LIVE state's blocks in the
        # strided storage layout; checkpoints stay logical — these
        # converters sit at the save/restore boundaries (None otherwise)
        self._layout = state_layout_transforms(self.model, self.tx,
                                               self.mesh)

        self.state = self.init_fn(jax.random.key(config.seed))
        self.start_epoch = 0
        self.start_step = 0            # step within start_epoch (mid-epoch resume)
        self._pending_eval_epoch = None  # epoch trained but not yet evaluated
        self._resumed = False
        if (config.resume and os.path.exists(config.ckpt_path)
                and not checkpoint.exists(config.ckpt_path)):
            # a sharded directory without a committed manifest: a save
            # crashed before its commit point — start fresh, don't wedge
            log0(f"WARNING: {config.ckpt_path} exists but holds no "
                 f"committed checkpoint (interrupted save?); starting fresh")
        if config.resume and checkpoint.exists(config.ckpt_path):
            # restore each leaf straight into its strategy layout — the
            # freshly-initialised state already carries the right
            # shardings. Integrity: every read is CRC-verified, and a
            # corrupted newest checkpoint falls back to the most recent
            # retained good one (--keep_last), resuming at ITS manifest
            self.state, manifest = checkpoint.restore_with_fallback(
                config.ckpt_path, self.state,
                shardings=jax.tree.map(lambda a: a.sharding, self.state))
            if self._layout is not None:
                # checkpoint content is logical; the live state runs in
                # interleaved storage
                self.state = self._layout[1](self.state)
            self._resumed = True
            epoch = int(manifest["epoch"])
            step_in_epoch = int(manifest.get("extra", {})
                                .get("step_in_epoch", -1))
            if 0 <= step_in_epoch < self.train_feed.steps_per_epoch:
                # a --checkpoint_every / preemption checkpoint: land on the
                # exact next batch of the deterministic epoch order
                self.start_epoch, self.start_step = epoch, step_in_epoch
                log0(f"resumed from {config.ckpt_path} at epoch {epoch} "
                     f"step {step_in_epoch}")
            else:
                self.start_epoch = epoch + 1
                extra = manifest.get("extra", {})
                if (not extra.get("eval_done", True)
                        or step_in_epoch >= self.train_feed.steps_per_epoch):
                    # eval never ran for this epoch: either preempted during
                    # the eval pass (eval_done False) or preempted on the
                    # epoch's last training step (step_in_epoch == steps).
                    # fit() backfills the eval before continuing.
                    self._pending_eval_epoch = epoch
                log0(f"resumed from {config.ckpt_path} at epoch "
                     f"{self.start_epoch}")
        if config.import_torch and self._resumed:
            # a restart (supervisor or manual --resume) must keep the
            # restored progress, not reset to the imported weights
            log0(f"resume checkpoint found; skipping --import_torch "
                 f"{config.import_torch}")
        elif config.import_torch:
            # migration path for reference users: start from their mnist.pt
            # (main.py:133) instead of a fresh init
            from distributed_compute_pytorch_tpu import interop
            if config.model != "convnet":
                raise ValueError("--import_torch supports the reference "
                                 "ConvNet checkpoint schema (model=convnet)")
            params, mstate = interop.load_reference_checkpoint(
                config.import_torch, self.model)
            params = jax.tree.map(lambda p, a: jax.device_put(p, a.sharding),
                                  params, self.state.params)
            mstate = jax.tree.map(lambda p, a: jax.device_put(p, a.sharding),
                                  mstate, self.state.model_state)
            self.state = self.state.replace(params=params, model_state=mstate)
            log0(f"imported torch checkpoint {config.import_torch}")
        multi_host = jax.process_count() > 1
        if config.heartbeat_path and multi_host and is_coordinator():
            # previous-incarnation beats (possibly from a LARGER world —
            # elastic resize) would keep the aggregate permanently stale
            Heartbeat.clear_dir(config.heartbeat_path)
        self.heartbeat = (Heartbeat(config.heartbeat_path,
                                    host_index=(jax.process_index()
                                                if multi_host else None))
                          if config.heartbeat_path else None)
        self.cluster = (ClusterPreemption(config.preempt_flag)
                        if config.preempt_flag else None)
        if self.cluster is not None:
            if is_coordinator():
                # a stale stop flag from the previous incarnation must not
                # stop the resumed run
                self.cluster.reset()
            if multi_host:
                # BARRIER the reset: jax dispatch is async, so without it
                # a non-coordinator's first host-side poll can read the
                # stale flags before the coordinator deletes them (the
                # train-step collective does NOT order host code)
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("dcp:preempt-reset")
        self.checkpointer = (checkpoint.AsyncCheckpointer(
            sharded=config.ckpt_sharded, keep_last=config.keep_last)
            if config.async_checkpoint else None)

        # telemetry (ISSUE 8, obs/): JSONL metric sink + host span tracer.
        # The logger closes on EVERY fit() exit path (its try/finally) and
        # the tracer dumps a Perfetto-loadable Chrome trace there too.
        self.logger = MetricLogger(config.metrics_jsonl)
        self._tracer = (Tracer() if (config.trace_path
                                     and is_coordinator()) else None)
        if self._tracer is not None:
            configure_tracer(self._tracer)
        # flight recorder (ISSUE 10, obs/flight.py): bounded ring of the
        # span/instant event stream, dumped to --flight_recorder PATH on
        # every failure path; the crash hook covers unhandled exceptions
        self._flight = None
        if config.flight_recorder:
            self._flight = flight.FlightRecorder(
                path=config.flight_recorder)
            flight.configure_flight(self._flight)
            flight.install_crash_hook()
        # divergence sentinel (obs/sentinel.py): compiled cross-replica
        # fingerprint check + per-step hash chain, both at log cadence;
        # None when the mesh has no dp replication to check
        self._div_check = None
        self._hash_chain = None
        if config.divergence_check:
            from distributed_compute_pytorch_tpu.obs import sentinel
            self._div_check = sentinel.make_divergence_check(self.mesh)
            self._hash_chain = sentinel.HashChain()
        # --collective_stats: census the step's gradient collectives ONCE,
        # at the first batch (needs concrete args to trace against)
        self._collective_stats_done = not config.collective_stats
        leaves = jax.tree.leaves(self.state.params)
        n_params = sum(int(l.size) for l in leaves)
        p_dtype = checkpoint.dominant_float_dtype(
            (l.shape, l.dtype) for l in leaves)
        log0(f"mesh: {dict(self.mesh.shape)} | dp world size: "
             f"{dp_world_size(self.mesh)} | devices: {len(self.mesh.devices.flat)}"
             f" | model: {config.model} | dataset: {self.train_data.name}"
             f" | params: {n_params} ({p_dtype})")
        dev0 = self.mesh.devices.flat[0]
        model_cfg = getattr(self.model, "config", None)
        self.logger.telemetry("run", {
            "platform": dev0.platform, "device_kind": dev0.device_kind,
            "devices": len(self.mesh.devices.flat),
            "mesh": dict(self.mesh.shape), "model": config.model,
            "model_config": ({k: (v if isinstance(v, (int, float, bool,
                                                     str, type(None)))
                                  else str(v))
                              for k, v in vars(model_cfg).items()}
                             if model_cfg is not None else None),
            "param_count": n_params, "param_dtype": str(p_dtype),
            "compute_dtype": config.compute_dtype,
            "steps_per_epoch": self.train_feed.steps_per_epoch})

    # ------------------------------------------------------------------

    def _resolve_shard_update(self):
        """Map the config's 'auto'/'on'/'off' knob to make_step_fns'
        tri-state, with the known non-elementwise gate: the ZeRO-1 body
        runs the optimizer on per-leaf SHARDS, and clip_by_global_norm
        would compute a shard-local norm there — silently wrong — so a
        clip-bearing chain falls back to the replicated update."""
        cfg = self.config
        mode = cfg.shard_update
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"--shard_update must be auto|on|off, "
                             f"got {mode!r}")
        if mode == "off":
            return False
        if cfg.clip_norm > 0:
            if mode == "on":
                raise ValueError(
                    "--shard_update on is incompatible with --clip_norm: "
                    "the global-gradient-norm clip is not elementwise "
                    "over shards")
            from distributed_compute_pytorch_tpu.parallel import (
                collectives)
            from distributed_compute_pytorch_tpu.parallel.api import (
                DataParallel)
            if (isinstance(self.strategy, DataParallel)
                    and collectives.dp_size(self.mesh) > 1):
                log0("NOTE: --clip_norm > 0 disables ZeRO-1 update "
                     "sharding (global-norm clip is not shard-local); "
                     "running the replicated update")
            return False
        from distributed_compute_pytorch_tpu.parallel.api import (
            DataParallel)
        if mode == "on" and not isinstance(self.strategy, DataParallel):
            raise ValueError(
                "--shard_update on requires the DataParallel strategy "
                "(FSDP/TP layouts already shard opt_state)")
        return True if mode == "on" else None

    def _pick_strategy(self):
        """Parameter-layout strategy from the mesh spec — the one-knob
        parallelism the reference gets from ``--gpus`` (``main.py:144``):
        ``--mesh`` alone decides DP / FSDP / TP and their compositions.

        - ``fsdp`` axis > 1         -> FSDP parameter sharding
        - ``tensor``/``pipe`` > 1   -> the model's ``partition_rules()``
          (Megatron TP layout + stacked-layer dim over pipe), stacked on
          the FSDP/DP fallback

        Shared with ``dcp-generate`` via ``parallel.api.pick_strategy`` so
        a checkpoint restores under the same layout it trained with.
        """
        from distributed_compute_pytorch_tpu.parallel.api import pick_strategy
        return pick_strategy(self.mesh, self.model,
                             warn=lambda m: log0(f"WARNING: {m}"))

    def _model_kwargs(self) -> dict:
        """Dataset-derived model construction kwargs, so every (model,
        dataset) pairing the CLI can express actually builds."""
        cfg = self.config
        kw: dict = {}
        inputs = self.train_data.inputs
        if cfg.model in ("convnet", "resnet18", "resnet50"):
            kw["num_classes"] = self.train_data.num_classes
            kw["in_channels"] = int(inputs.shape[-1])
            if cfg.model == "convnet":
                kw["image_size"] = tuple(int(s) for s in inputs.shape[1:3])
        if cfg.model in ("bert", "gpt2", "moe", "llama"):
            kw["preset"] = cfg.model_preset
            if (cfg.model_preset == "tiny"
                    or cfg.dataset.startswith("synthetic")
                    or cfg.dataset == "text"):
                # text: vocab must match the tokenizer exactly (ids outside
                # the embedding would clamp-gather silently)
                kw["vocab_size"] = max(self.train_data.num_classes, 4)
                kw["max_seq_len"] = int(inputs.shape[1])
        if (cfg.model in ("bert", "gpt2", "llama", "moe")
                and cfg.microbatches):
            kw["pipeline_microbatches"] = cfg.microbatches
        if (cfg.model in ("bert", "gpt2", "llama", "moe")
                and cfg.virtual_stages > 1):
            kw["virtual_stages"] = cfg.virtual_stages
        if (cfg.model in ("bert", "gpt2", "llama", "moe")
                and cfg.num_layers is not None):
            kw["num_layers"] = cfg.num_layers
        if cfg.seq_shard_activations:
            if cfg.model in ("bert", "gpt2", "llama"):
                kw["seq_shard_activations"] = True
            else:
                log0(f"WARNING: --seq_shard_activations is not supported "
                     f"by model {cfg.model!r} and will be ignored")
        if cfg.remat:
            if cfg.model in ("bert", "gpt2", "moe", "llama"):
                stage_ok = (cfg.remat_mode == "stage"
                            and dict(self.mesh.shape).get("pipe", 1) > 1)
                if cfg.remat_mode == "stage" and not stage_ok:
                    log0("WARNING: --remat_mode stage needs a pipe>1 mesh; "
                         "falling back to per-block remat")
                kw["remat"] = ("stage" if stage_ok else
                               "dots" if cfg.remat_mode == "dots" else True)
            else:
                log0(f"WARNING: --remat is not supported by model "
                     f"{cfg.model!r} and will be ignored")
        if cfg.param_dtype not in (None, "float32"):
            kw["param_dtype"] = jnp.dtype(cfg.param_dtype)
        return kw

    def _save_ckpt(self, epoch: int, extra: dict | None = None) -> None:
        """One checkpoint write via the configured path: async (background
        thread), sharded (per-host shard files, no O(params) gather), or
        the default coordinator-written single file."""
        cfg = self.config
        # persistent layout is always LOGICAL: de-interleave the live
        # state's blocks first on interleaved-pipeline runs (a fresh
        # permuted copy — safe to hand to the async writer)
        state = (self.state if self._layout is None
                 else self._layout[0](self.state))
        with span("checkpoint", epoch=epoch):
            if self.checkpointer is not None:
                self.checkpointer.save(cfg.ckpt_path, state, epoch=epoch,
                                       extra=extra)
            elif cfg.ckpt_sharded:
                checkpoint.save_sharded(cfg.ckpt_path, state, epoch=epoch,
                                        extra=extra, keep_last=cfg.keep_last)
            else:
                checkpoint.save(cfg.ckpt_path, state, epoch=epoch,
                                extra=extra, keep_last=cfg.keep_last)

    def _finish(self) -> None:
        """Flush any in-flight async checkpoint write, dump the span
        trace, then close the logger. Runs on EVERY ``fit`` exit path
        (its try/finally), including preemption, and is idempotent."""
        if self.checkpointer is not None:
            self.checkpointer.close()
        if self._tracer is not None:
            try:
                self._tracer.dump(self.config.trace_path)
                log0(f"span trace written to {self.config.trace_path}")
            finally:
                configure_tracer(None)
                self._tracer = None
        if self._flight is not None and flight.current_flight() is self._flight:
            # uninstall OUR recorder (another run may install its own);
            # failure paths have already dumped by the time we get here
            flight.configure_flight(None)
        self.logger.close()

    def train_epoch(self, epoch: int, skip: int = 0,
                    guard: PreemptionGuard | None = None) -> float:
        """One epoch; returns mean wall-time-throughput (samples/s).

        ``skip`` resumes mid-epoch (first incarnation passes 0);
        ``guard`` polls for preemption between steps — on a signal the
        current position is checkpointed and :class:`Preempted` raised.
        """
        cfg = self.config
        timer = Timer()
        steps = self.train_feed.steps_per_epoch
        metrics = None
        # explicit iterator so the input-pipeline stall (host batch prep +
        # transfer) is its own span, distinct from train_step dispatch —
        # the first question a slow run asks is data-bound vs compute-bound
        it = enumerate(self.train_feed.epoch(epoch, skip=skip), start=skip)
        while True:
            with span("data_wait"):
                nxt = next(it, None)
            if nxt is None:
                break
            b, (x, y) = nxt
            self._maybe_inject_fault(epoch * steps + b)
            self._maybe_collective_stats(x, y)
            with span("train_step"):
                self.state, metrics = self.train_step(self.state, x, y)
            if "skipped" in metrics:
                # device scalar, queued unread: fetched at log cadence
                self._skip_hist.append(metrics["skipped"])
            if self._hash_chain is not None:
                # same discipline: queue the device scalars, fold at
                # cadence — the chain costs the hot path nothing
                self._chain_pending.append(
                    (metrics["loss"], metrics.get("grad_sumsq")))
            if b % cfg.log_every == 0:
                # read the device scalar only at the logging cadence
                # (reference cadence, main.py:64)
                with span("log_read"):
                    loss = float(metrics["loss"])
                self._poll_nonfinite(loss, epoch, b)
                self._poll_divergence(epoch, b)
                self.logger.train_line(epoch, b, steps, loss)
                mem = obs_metrics.device_memory_gauges(obs_metrics.REGISTRY)
                if mem:
                    self.logger.telemetry("memory", mem)
                if self.heartbeat is not None:
                    self.heartbeat.beat(epoch, epoch * steps + b)
            if self._should_preempt(guard, epoch * steps + b):
                self._save_ckpt(epoch, extra={"step_in_epoch": b + 1})
                log0(f"preempted at epoch {epoch} step {b}; "
                     f"checkpoint written to {cfg.ckpt_path}")
                raise Preempted()
            if (cfg.checkpoint_every
                    and (b + 1) % cfg.checkpoint_every == 0
                    and b + 1 < steps):
                self._save_ckpt(epoch, extra={"step_in_epoch": b + 1})
        # fence: fetch a value depending on the last step, so the epoch
        # timer stops after the device work and not after the enqueue
        if metrics is not None:
            with span("epoch_fence"):
                np.asarray(metrics["loss"])
            # drain the skip flags queued since the last log line, so an
            # epoch can't end with unexamined non-finite skips
            self._poll_nonfinite(float(metrics["loss"]), epoch, steps - 1)
            self._poll_divergence(epoch, steps - 1)
        secs = timer.elapsed()
        # each update consumes the full effective batch (micro x accum)
        return (steps - skip) * cfg.batch_size * self.accum / secs

    def _poll_nonfinite(self, loss: float, epoch: int, b: int) -> None:
        """Log-cadence divergence containment (``--nonfinite_policy``).

        ``skip``: drain the per-step skip flags the compiled guard
        produced (their values settled long ago — fetching here stalls
        nothing), log the running count, and give up after
        :data:`NONFINITE_SKIP_LIMIT` CONSECUTIVE skips — params are
        bit-untouched throughout, so delayed detection is harmless.
        ``raise``: a non-finite loss at the cadence fetch aborts (the
        params are already poisoned; fail fast and let the supervisor
        restart from the last checkpoint)."""
        import math
        if self.config.nonfinite_policy == "skip":
            new_skips = 0
            for s in self._skip_hist:
                if float(s) > 0.0:
                    self._skips_total += 1
                    self._skips_consec += 1
                    new_skips += 1
                else:
                    self._skips_consec = 0
            self._skip_hist.clear()
            if new_skips:
                flight.record("nonfinite_skip", epoch=epoch, step=b,
                              count=new_skips, total=self._skips_total)
                log0(f"nonfinite_policy=skip: skipped {new_skips} "
                     f"non-finite update(s) near epoch {epoch} step {b} "
                     f"(total {self._skips_total}, consecutive "
                     f"{self._skips_consec})")
            if self._skips_consec >= NONFINITE_SKIP_LIMIT:
                msg = (f"{self._skips_consec} consecutive non-finite "
                       f"updates skipped (epoch {epoch} step {b}): the "
                       f"run has diverged — params are still the last "
                       f"finite state; lower the lr or clip gradients")
                flight.record("nonfinite_abort", epoch=epoch, step=b,
                              consecutive=self._skips_consec)
                flight.dump_on_fault("trainer_nonfinite", fault=msg)
                raise RuntimeError(msg)
        elif not math.isfinite(loss):
            msg = (f"non-finite loss {loss} at epoch {epoch} step {b} "
                   f"(nonfinite_policy=raise); use --nonfinite_policy "
                   f"skip to drop bad updates instead of aborting")
            flight.record("nonfinite_abort", epoch=epoch, step=b,
                          loss=loss)
            flight.dump_on_fault("trainer_nonfinite", fault=msg)
            raise RuntimeError(msg)

    def _poll_divergence(self, epoch: int, b: int) -> None:
        """Log-cadence sentinel work (``--divergence_check``): fold the
        queued per-step (loss, grad_sumsq) scalars into the hash chain,
        emit the digest to the metrics JSONL, then run the compiled
        cross-replica fingerprint check. A nonzero spread means the dp
        replicas no longer hold bit-identical params — silent data
        corruption caught within one log interval instead of surfacing
        as an unexplained loss explosion later (obs/sentinel.py)."""
        if self._hash_chain is None:
            return
        for loss_d, gsq_d in self._chain_pending:
            vals = (float(loss_d),) + (
                (float(gsq_d),) if gsq_d is not None else ())
            self._hash_chain.update(*vals)
        self._chain_pending.clear()
        self.logger.telemetry("hash_chain", {
            "epoch": epoch, "step": b, "steps": self._hash_chain.steps,
            "digest": self._hash_chain.digest()})
        if self._div_check is None:
            return
        with span("divergence_check"):
            spread = self._div_check(self.state.params)
        if spread != 0:
            msg = (f"dp replicas diverged at epoch {epoch} step {b}: "
                   f"param fingerprint spread {spread} (expected 0) — "
                   f"silent corruption or a nondeterministic kernel; "
                   f"restore from the last checkpoint")
            flight.record("replica_divergence", epoch=epoch, step=b,
                          spread=int(spread))
            flight.dump_on_fault("replica_divergence", fault=msg)
            raise RuntimeError(msg)

    def _should_preempt(self, guard, global_step: int) -> bool:
        """Per-step preemption poll. Single-host: the local signal flag.
        Multi-host (``--preempt_flag`` on a shared fs): the coordinated
        protocol — ALL hosts stop at the same agreed global step, so the
        preemption checkpoint's collectives line up (elastic.py
        ``ClusterPreemption``)."""
        if guard is None:
            return False
        if self.cluster is not None:
            return self.cluster.check(guard.preempted, global_step)
        return guard.preempted

    def _maybe_inject_fault(self, global_step: int) -> None:
        """Fault injection for exercising the recovery path (elastic.py):
        trips once — never in a supervised restart (DCP_RESTART_COUNT) nor
        in a manual --resume, which would otherwise crash-loop."""
        cfg = self.config
        if cfg.fault_at_step is None or restart_count() > 0 or self._resumed:
            return
        if global_step == cfg.fault_at_step:
            if cfg.fault_mode == "hang":
                import time
                log0(f"injected hang at step {global_step} (--fault_at_step)")
                while True:                      # stuck-collective stand-in
                    time.sleep(1)
            raise RuntimeError(
                f"injected fault at step {global_step} (--fault_at_step)")

    def _maybe_collective_stats(self, x, y) -> None:
        """One-time gradient-collective census (``--collective_stats``):
        trace the compiled step against the first real batch and record
        the boundary/in-loop reduction counts and wire bytes per chip
        (``parallel.collectives.grad_collective_stats``) to the registry
        and the metrics JSONL. Tracing only — no device work, and the
        donated buffers are untouched."""
        if self._collective_stats_done:
            return
        self._collective_stats_done = True
        from distributed_compute_pytorch_tpu.parallel.collectives import (
            compiled_hlo_text, count_hlo_collectives, count_hlo_kernels,
            grad_collective_stats)
        try:
            stats = grad_collective_stats(self.train_step, self.state, x, y)
        except Exception as e:   # noqa: BLE001 — diagnostics must not kill a run
            log0(f"WARNING: --collective_stats trace failed: {e}")
            return
        for k, v in stats.items():
            obs_metrics.REGISTRY.gauge(f"collectives.grad.{k}").set(v)
        # post-compile HLO census: the jaxpr walk above reports 0 on the
        # pure SPMD-jit path (the partitioner inserts its collectives
        # DURING compilation); counting the compiled module's ops closes
        # that gap — and the same text says which Pallas kernels reached
        # the device as Mosaic calls. Guarded the same way — HLO text is
        # compiler-internal
        hlo = kernels = None
        try:
            txt = compiled_hlo_text(self.train_step, self.state, x, y)
            hlo = count_hlo_collectives(txt)
            kernels = count_hlo_kernels(txt)
        except Exception as e:   # noqa: BLE001
            log0(f"WARNING: --collective_stats HLO census failed: {e}")
        if hlo is not None:
            obs_metrics.REGISTRY.gauge("collectives.hlo.count").set(
                hlo["count"])
            obs_metrics.REGISTRY.gauge("collectives.hlo.bytes").set(
                hlo["bytes"])
        self.logger.telemetry("collectives", {"grad": stats, "hlo": hlo,
                                              "kernels": kernels})
        log0(f"grad collectives per update: {stats['boundary']} boundary, "
             f"{stats['in_loop']} in-loop, {stats['bytes']} bytes/chip"
             + (f" | compiled HLO: {hlo['count']} collective op(s), "
                f"{hlo['bytes']} bytes ({hlo['ops']}), "
                f"{kernels['count']} Pallas kernel call(s) "
                f"({kernels['kernels']})" if hlo else ""))

    def evaluate(self, epoch: int,
                 guard: PreemptionGuard | None = None) -> dict:
        """Full eval pass == reference ``test`` (``main.py:70-95``), with the
        loss math fixed (§A.5) and — unlike the reference's
        DistributedSampler padding, which double-counts wraparound rows —
        exact: the feeder marks padded rows and eval weights them out.

        Metrics accumulate *on device*, threaded through ``eval_step`` as a
        carry; the host fetches once at the end instead of blocking on three
        transfers per batch.

        On the CPU backend we additionally block per batch: eval executions
        are independent up to the final accumulate (params and batch are both
        ready), so async dispatch runs several collective-bearing programs
        concurrently — which deadlocks XLA:CPU's in-process rendezvous when
        the host is thread-starved (observed on a 1-core host with 8 faked
        devices; the train loop is immune because each step consumes the
        previous step's donated state). TPU executes programs in order, so
        the async pipeline is kept there."""
        serialize = self.mesh.devices.flat[0].platform == "cpu"
        dev_total = None
        for b, (x, y, valid) in enumerate(
                self.eval_feed.epoch(0, with_valid=True)):
            if self.heartbeat is not None and b % self.config.log_every == 0:
                self.heartbeat.beat(epoch, b)   # stay live through eval
            if guard is not None and guard.preempted and self.cluster:
                # multi-host: a mid-eval exit cannot be coordinated (hosts
                # would leave the eval collectives at different batches) —
                # record the request; the stop is honoured at the next
                # train-step boundary, where steps are globally lockstep
                self.cluster.request()
            if (guard is not None and guard.preempted
                    and self.cluster is None):
                # train state is unchanged during eval, so checkpointing the
                # finished epoch now (rather than after the full eval pass +
                # epoch save) keeps us inside a short preemption grace
                # window; eval_done=False makes the resume backfill the
                # interrupted eval so its metrics line is never lost
                self._save_ckpt(epoch, extra={"eval_done": False})
                log0(f"preempted during epoch {epoch} eval; checkpoint "
                     f"written to {self.config.ckpt_path}")
                raise Preempted()
            if dev_total is None:
                # zero-seed the carry so every batch hits the same compiled
                # program (an acc=None first call would compile eval twice)
                shapes = jax.eval_shape(self.eval_step, self.state, x, y,
                                        None, valid)
                dev_total = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            dev_total = self.eval_step(self.state, x, y, dev_total, valid)
            if serialize:
                jax.block_until_ready(dev_total)
        total = ({"loss_sum": 0.0, "correct": 0, "count": 0}
                 if dev_total is None else
                 {"loss_sum": float(dev_total["loss_sum"]),
                  "correct": int(dev_total["correct"]),
                  "count": int(dev_total["count"])})
        loss = total["loss_sum"] / max(total["count"], 1)
        self.logger.eval_line(epoch, loss, total["correct"], total["count"])
        return {"loss": loss,
                "accuracy": total["correct"] / max(total["count"], 1)}

    def fit(self) -> dict:
        """The reference's epoch loop (``main.py:127-133``): train -> eval ->
        (schedule is compiled in) -> timing print -> checkpoint at the end.

        Runs under a :class:`PreemptionGuard`: SIGTERM/SIGINT checkpoints
        mid-epoch and returns ``{"preempted": True}`` (the CLI exits with
        ``EXIT_PREEMPTED`` so a supervisor restarts-with-resume)."""
        cfg = self.config
        last_eval = {}
        # NOTE: no heartbeat before the first step — a pre-compile beat
        # would arm the supervisor's staleness timer and a long XLA compile
        # would then read as a hang.
        # The try/finally is the MetricLogger-lifecycle fix (ISSUE 8):
        # _finish (async-ckpt flush, trace dump, JSONL close) runs on
        # every exit path — normal completion, preemption, AND errors —
        # instead of being repeated at each return site.
        try:
            with maybe_profile(cfg.profile_dir), PreemptionGuard() as guard:
                if self._pending_eval_epoch is not None:
                    # previous incarnation was preempted during this epoch's
                    # eval (manifest eval_done=False): report its metrics now,
                    # then mark the checkpoint evaluated so another bounce
                    # doesn't repeat the pass
                    pending = self._pending_eval_epoch
                    try:
                        with span("eval", epoch=pending):
                            last_eval = self.evaluate(pending, guard=guard)
                    except Preempted:
                        return {"preempted": True, "epoch": pending}
                    self._save_ckpt(pending, extra={"eval_done": True})
                    self._pending_eval_epoch = None
                for epoch in range(self.start_epoch, cfg.epochs):
                    skip = self.start_step if epoch == self.start_epoch else 0
                    timer = Timer()
                    try:
                        throughput = self.train_epoch(epoch, skip=skip,
                                                      guard=guard)
                        with span("eval", epoch=epoch):
                            last_eval = self.evaluate(epoch, guard=guard)
                    except Preempted:
                        return {"preempted": True, "epoch": epoch}
                    self.logger.epoch_time(epoch, timer.elapsed(), throughput)
                    self._save_ckpt(epoch, extra={"eval_done": True})
                    if guard.preempted and self.cluster is not None:
                        # multi-host: record the request and keep going — the
                        # NEXT epoch's first train steps coordinate the stop
                        # (a unilateral exit here would leave the other hosts
                        # hanging in their next collective). A last-epoch
                        # signal simply lets the run complete.
                        self.cluster.request()
                    elif guard.preempted:
                        # signal arrived after eval (eval-time signals raise
                        # Preempted inside evaluate()): during the epoch-time
                        # print or the epoch-end save. The checkpoint just
                        # written is the resume point — exit now rather than
                        # starting another epoch.
                        log0(f"preempted during epoch {epoch} epoch-end "
                             f"save; checkpoint written to {cfg.ckpt_path}")
                        return {"preempted": True, "epoch": epoch}
            return last_eval
        finally:
            self._finish()
