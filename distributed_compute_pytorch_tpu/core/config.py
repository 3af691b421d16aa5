"""Run configuration.

The reference exposes exactly six CLI knobs via argparse
(``/root/reference/main.py:139-144``): ``--batch_size`` (128), ``--lr``
(0.001), ``--epochs`` (20), ``--no-cuda``, ``--gamma`` (0.7), ``--gpus`` (4).
Here the same knobs live in one dataclass; the device-count knob becomes a
mesh spec, and ``--no-cuda`` becomes a real boolean ``--force-cpu``
(the reference's flag is broken — it takes a value and truthy strings like
``'False'`` disable CUDA; see SURVEY.md §A.7. We fix it.)

Rendezvous configuration (reference hard-codes ``MASTER_ADDR=localhost``,
``MASTER_PORT=12355`` at ``main.py:48-49``) comes from flags/env instead, so
multi-host actually works.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any


def _env(name: str, default: str | None = None) -> str | None:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


@dataclass
class Config:
    """All knobs for a training run.

    The first block mirrors the reference CLI one-to-one
    (``main.py:139-144``); the rest are framework additions the reference
    either hard-codes or lacks.
    """

    # --- reference-parity knobs (main.py:139-144) ---
    batch_size: int = 128          # global batch size, like the reference's per-run bs
    lr: float = 1e-3               # Adadelta lr (reference default 0.001, main.py:140)
    epochs: int = 20               # main.py:141
    force_cpu: bool = False        # fixed --no-cuda (main.py:142, SURVEY §A.7)
    gamma: float = 0.7             # StepLR decay per epoch (main.py:143)
    mesh: str = "data=-1"          # replaces --gpus: mesh axes spec, e.g. "data=4",
                                   # "data=2,fsdp=4", "data=1,tensor=4,seq=2"; -1 = infer

    # --- model / task selection (the reference has one model; we have a zoo) ---
    model: str = "convnet"         # convnet | resnet18 | resnet50 | bert | gpt2 | moe | llama
    model_preset: str | None = None  # e.g. 'tiny' for test-scale transformers
    microbatches: int | None = None  # GPipe microbatches under a pipe axis
    virtual_stages: int = 1        # Megatron interleaved pipeline: v layer
                                   # chunks per device (needs M <= pipe)
    num_layers: int | None = None  # transformer depth override (e.g. a
                                   # 4-layer tiny model for pipe*virtual)
    dataset: str = "mnist"         # mnist | cifar10 | synthetic-images | synthetic-lm
    optimizer: str = "adadelta"    # adadelta (reference stack) | sgd | adamw
                                   # | adamw_fused (Pallas single-pass kernel)

    # --- logging / metrics (cadence matches main.py:64) ---
    log_every: int = 10            # print a loss line every N steps (main.py:64)
    seed: int = 0                  # torch.manual_seed(0) equivalent (main.py:103)

    # --- data / checkpoint paths ---
    data_dir: str = "./data"       # reference uses './data/' (main.py:107)
    # --- real-text LM corpus (--dataset text: data_dir is a .txt file) ---
    seq_len: int = 256             # training-window length for text corpora
    tokenizer: str = "byte"        # 'byte' or path to a tokenizer .json
                                   # (data/tokenizer.py; train a BPE with
                                   # dcp-tokenizer)
    prefetch: int = 2              # feeder prefetch depth (0 = synchronous);
                                   # the DataLoader-workers role (main.py:110)
    require_real_data: bool = False  # error (not warn) if real data missing
    download: bool = False         # fetch missing data (coordinator + barrier)
    ckpt_path: str = "checkpoint.npz"  # reference writes 'mnist.pt' (main.py:133)
    resume: bool = False           # restore path the reference lacks (SURVEY §5.4)
    import_torch: str | None = None  # start from a reference mnist.pt (interop.py)
    ckpt_sharded: bool = False     # v2 directory format: each host writes its
                                   # own shards, no O(params) gather (FSDP-scale)
    async_checkpoint: bool = False  # overlap the checkpoint write with training
    keep_last: int = 1             # checkpoint retention: keep the last N
                                   # checkpoints (v1: rotated .prev-K files;
                                   # v2: last N generations) — restore falls
                                   # back to the newest UNCORRUPTED one
                                   # (train/checkpoint.py integrity checksums)

    # --- elastic / fault tolerance (SURVEY §5.3; the reference has none) ---
    checkpoint_every: int = 0      # also checkpoint every N steps (0 = per-epoch
                                   # only); resume restarts mid-epoch exactly
    heartbeat_path: str | None = None  # liveness file, touched at log cadence
                                       # (multi-host: a shared dir; each host
                                       # beats into host-{i}.hb)
    preempt_flag: str | None = None    # shared dir for COORDINATED multi-host
                                       # preemption: any host's SIGTERM makes
                                       # every host checkpoint at one agreed
                                       # step (elastic.ClusterPreemption)
    supervise: bool = False        # run under the restart supervisor
    max_restarts: int = 3          # supervisor restart budget
    heartbeat_timeout: float = 300.0   # supervisor hang detection threshold (s)
    first_beat_timeout: float | None = None  # hang-before-first-beat window
                                             # (None = off; size for compiles)
    fault_at_step: int | None = None   # fault injection: trip at global step N
    fault_mode: str = "raise"      # 'raise' (crash) | 'hang' (stuck collective
                                   # stand-in); first incarnation only
    nonfinite_policy: str = "raise"  # NaN/Inf loss or grad norm: 'raise'
                                     # (abort at the log-cadence check) |
                                     # 'skip' (compiled guard skips the
                                     # update, params/opt_state stay
                                     # bit-untouched; raise after K=10
                                     # consecutive skips — train/step.py)

    # --- distributed rendezvous (replaces main.py:48-49 hard-coding) ---
    coordinator: str | None = field(
        default_factory=lambda: _env("DCP_COORDINATOR"))
    num_processes: int | None = field(
        default_factory=lambda: (lambda v: int(v) if v else None)(_env("DCP_NUM_PROCESSES")))
    process_id: int | None = field(
        default_factory=lambda: (lambda v: int(v) if v else None)(_env("DCP_PROCESS_ID")))

    # --- numerics / performance ---
    compute_dtype: str = "float32"   # bfloat16 for TPU speed; float32 for parity tests
    param_dtype: str = "float32"
    donate: bool = True              # donate train-state buffers to the jitted step
    # rematerialise transformer blocks on backward (jax.checkpoint): one
    # extra forward buys ~2-4x batch when HBM binds
    remat: bool = False
    # remat granularity: 'block' (each transformer block), 'dots' (save
    # the named matmul outputs, recompute only elementwise work — less
    # memory saved, no matmul runs twice), or 'stage' (each pipeline-stage
    # tick — the 1F1B memory profile; needs a pipe>1 mesh, see
    # parallel/pipeline.py)
    remat_mode: str = "block"
    # device-side train-time image augmentation (ops/augment.py), traced
    # into the jitted step: none | flip | flip-crop
    augment: str = "none"
    # --- optimizer extras (train/optim.py) ---
    weight_decay: float = 0.0      # AdamW decay (matrices only, masked)
    clip_norm: float = 0.0         # global-grad-norm clip (0 = off)
    grad_accum: int = 1            # microbatches accumulated per update,
                                   # STEP-LEVEL (train/step.py): effective
                                   # batch N x batch_size, one gradient
                                   # reduction + one dispatch per update
    accum_dtype: str = "float32"   # grad-accumulator dtype (float32 |
                                   # bfloat16 — half the accumulator HBM
                                   # and boundary wire bytes, bounded
                                   # rounding; tests pin the tolerance)
    accum_bucket_mb: float = 25.0  # boundary-reduction bucket size (MB,
                                   # DDP bucket_cap_mb analog): bucket k's
                                   # reduce-scatter overlaps bucket k-1's
                                   # optimizer update + gather; 0 = one
                                   # single-shot boundary (bit-identical)
    warmup_steps: int = 0          # LR warmup updates (adamw schedule)
    # ZeRO-1 cross-replica weight-update sharding (train/step.py,
    # parallel/collectives.py): reduce-scatter grads -> shard-local
    # optimizer update (opt_state born sharded, 1/N per chip) ->
    # all-gather params. 'auto' (default) = on when the strategy is pure
    # DataParallel and the dp world size > 1; 'on'/'off' force it.
    shard_update: str = "auto"
    # opt-in block-scaled int8 gradient collectives for the sharded
    # update (EQuARX-style): int8 + per-block f32 scales on the wire,
    # f32 accumulate; bounded quantization error on the gradients
    quant_collectives: bool = False
    # Megatron sequence-parallel activations on tensor>1 meshes: residual
    # stream's token dim sharded over `tensor` between blocks (transformer
    # models; numerics-transparent)
    seq_shard_activations: bool = False
    profile_dir: str | None = None   # opt-in XLA profiler traces (SURVEY §5.1)
    # --- telemetry (ISSUE 8, obs/): machine-readable metrics + host traces
    metrics_jsonl: str | None = None  # MetricLogger JSONL sink (train/eval/
                                      # epoch lines + telemetry records)
    trace_path: str | None = None     # host span trace: Chrome-trace JSON
                                      # written here at exit (obs/tracing.py;
                                      # data-wait/step/eval/checkpoint spans)
    collective_stats: bool = False    # one-time jaxpr census of the train
                                      # step's gradient collectives into the
                                      # registry + metrics_jsonl (reuses
                                      # parallel.collectives.
                                      # grad_collective_stats; costs one
                                      # extra trace at startup), plus the
                                      # post-compile HLO census (ISSUE 10)
    # --- forensics (ISSUE 10, obs/flight.py + obs/sentinel.py)
    flight_recorder: str | None = None  # dump path: ring-buffer of span/
                                        # instant events written here on any
                                        # failure (nonfinite raise, crash)
    divergence_check: bool = False    # log-cadence dp-replica fingerprint
                                      # check + per-step loss/grad-norm
                                      # hash chain in metrics_jsonl

    # --- eval behaviour: reference evaluates on the TRAIN set (main.py:130, bug §A.1).
    # We default to the test split but keep the knob for log-comparison runs.
    eval_on_train: bool = False

    def mesh_axes(self) -> dict[str, int]:
        """Parse the mesh spec string into an ordered ``{axis: size}`` dict
        (delegates to MeshSpec so axis-name validation happens in one place)."""
        from distributed_compute_pytorch_tpu.core.mesh import MeshSpec
        return dict(MeshSpec.parse(self.mesh).axes)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    # ---- CLI shim: same role as reference argparse block (main.py:137-145) ----
    @classmethod
    def parser(cls) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(
            description="TPU-native distributed trainer "
                        "(capability parity with reference main.py)",
            # no prefix abbreviation: an abbreviated '--superv' surviving the
            # supervisor's child-argv filter would recurse into supervisors
            allow_abbrev=False)
        p.add_argument("--batch_size", type=int, default=cls.batch_size,
                       help="global batch size of train and test")
        p.add_argument("--lr", type=float, default=cls.lr, help="LR of optimizer")
        p.add_argument("--epochs", type=int, default=cls.epochs, help="# of epochs")
        p.add_argument("--force-cpu", action="store_true", dest="force_cpu",
                       help="run on host CPU devices (fixed --no-cuda)")
        p.add_argument("--gamma", type=float, default=cls.gamma,
                       help="gamma value for lr update")
        p.add_argument("--mesh", type=str, default=cls.mesh,
                       help="device mesh spec, e.g. 'data=8' or 'data=2,fsdp=4'")
        p.add_argument("--model", type=str, default=cls.model)
        p.add_argument("--model_preset", type=str, default=None,
                       help="e.g. 'tiny' for test-scale transformers")
        p.add_argument("--microbatches", type=int, default=None,
                       help="GPipe microbatch count under a pipe mesh axis "
                            "(default: pipe size)")
        p.add_argument("--virtual_stages", type=int, default=cls.virtual_stages,
                       help="Megatron interleaved pipeline: v layer chunks "
                            "per device (needs microbatches <= pipe)")
        p.add_argument("--num_layers", type=int, default=None,
                       help="transformer depth override")
        p.add_argument("--dataset", type=str, default=cls.dataset)
        p.add_argument("--optimizer", type=str, default=cls.optimizer,
                       help="adadelta (reference stack) | sgd | adamw")
        p.add_argument("--log_every", type=int, default=cls.log_every)
        p.add_argument("--seed", type=int, default=cls.seed)
        p.add_argument("--data_dir", type=str, default=cls.data_dir)
        p.add_argument("--seq_len", type=int, default=cls.seq_len,
                       help="window length for --dataset text")
        p.add_argument("--tokenizer", type=str, default=cls.tokenizer,
                       help="'byte' or a tokenizer .json (dcp-tokenizer)")
        p.add_argument("--prefetch", type=int, default=cls.prefetch,
                       help="feeder prefetch depth (0 = synchronous)")
        p.add_argument("--require_real_data", action="store_true",
                       help="fail instead of substituting synthetic data")
        p.add_argument("--download", action="store_true",
                       help="download missing dataset files (coordinator-"
                            "only, like the reference's download=True)")
        p.add_argument("--ckpt_path", type=str, default=cls.ckpt_path)
        p.add_argument("--resume", action="store_true")
        p.add_argument("--ckpt_sharded", action="store_true",
                       help="sharded checkpoint directory: each host writes "
                            "its own shards (no O(params) gather)")
        p.add_argument("--async_checkpoint", action="store_true",
                       help="write checkpoints on a background thread")
        p.add_argument("--import_torch", type=str, default=None,
                       help="initialise from a reference torch checkpoint "
                            "(mnist.pt); convnet only")
        p.add_argument("--checkpoint_every", type=int,
                       default=cls.checkpoint_every,
                       help="also checkpoint every N steps (0 = per-epoch "
                            "only); resume restarts mid-epoch")
        p.add_argument("--heartbeat_path", type=str, default=None,
                       help="liveness file for external failure detection "
                            "(multi-host: shared dir, host-{i}.hb each)")
        p.add_argument("--preempt_flag", type=str, default=None,
                       help="shared dir for coordinated multi-host "
                            "preemption (all hosts checkpoint at one "
                            "agreed step)")
        p.add_argument("--supervise", action="store_true",
                       help="run under the restart supervisor (auto --resume "
                            "after crash/hang/preemption)")
        p.add_argument("--max_restarts", type=int, default=cls.max_restarts)
        p.add_argument("--first_beat_timeout", type=float, default=None,
                       help="supervisor: kill a child that never produces "
                            "its FIRST heartbeat within this window (off by "
                            "default; size generously for cold compiles)")
        p.add_argument("--heartbeat_timeout", type=float,
                       default=cls.heartbeat_timeout)
        p.add_argument("--fault_at_step", type=int, default=None,
                       help="fault injection (testing): trip at global step N "
                            "in the first incarnation")
        p.add_argument("--fault_mode", type=str, default=cls.fault_mode,
                       choices=("raise", "hang"))
        p.add_argument("--nonfinite_policy", type=str,
                       default=cls.nonfinite_policy,
                       choices=("raise", "skip"),
                       help="on NaN/Inf loss or gradient norm: 'raise' "
                            "aborts at the next log-cadence check; "
                            "'skip' compiles a guard that drops the bad "
                            "update (params/opt_state bit-untouched), "
                            "logs the skip count, and raises after 10 "
                            "consecutive skips")
        p.add_argument("--keep_last", type=int, default=cls.keep_last,
                       help="checkpoint retention: keep the last N "
                            "checkpoints and fall back to the newest "
                            "uncorrupted one on restore (v1 files "
                            "rotate to .prev-K; v2 directories keep N "
                            "generations)")
        p.add_argument("--coordinator", type=str, default=None,
                       help="host:port of process 0 (multi-host rendezvous)")
        p.add_argument("--num_processes", type=int, default=None)
        p.add_argument("--process_id", type=int, default=None)
        p.add_argument("--compute_dtype", type=str, default=cls.compute_dtype)
        p.add_argument("--param_dtype", type=str, default=cls.param_dtype)
        p.add_argument("--remat_mode", type=str, default=cls.remat_mode,
                       choices=("block", "dots", "stage"),
                       help="remat granularity: per-block, selective "
                            "(save matmul outputs only), or per-pipeline-"
                            "stage (1F1B memory profile; pipe meshes only)")
        p.add_argument("--remat", action="store_true",
                       help="rematerialise transformer blocks on backward "
                            "(bigger batches when HBM binds)")
        p.add_argument("--augment", type=str, default=cls.augment,
                       choices=("none", "flip", "flip-crop"),
                       help="device-side train-time image augmentation "
                            "(traced into the jitted step; image models)")
        p.add_argument("--weight_decay", type=float, default=cls.weight_decay,
                       help="AdamW weight decay (matrices only; biases and "
                            "norm scales are excluded)")
        p.add_argument("--clip_norm", type=float, default=cls.clip_norm,
                       help="clip gradients to this global norm (0 = off)")
        p.add_argument("--grad_accum", type=int, default=cls.grad_accum,
                       help="accumulate N microbatch gradients per "
                            "optimizer update INSIDE the compiled step "
                            "(effective batch N x batch_size at "
                            "one-microbatch activation memory; exactly "
                            "ONE gradient reduction per update — the DDP "
                            "no_sync analog — composing with "
                            "shard_update, quant_collectives, remat and "
                            "adamw_fused; step counts tick per update)")
        p.add_argument("--accum_dtype", type=str, default=cls.accum_dtype,
                       choices=("float32", "bfloat16", "f32", "bf16"),
                       help="gradient-accumulator dtype under "
                            "--grad_accum>1: bfloat16 halves the "
                            "accumulator HBM and the boundary psum wire "
                            "bytes at a bounded rounding cost")
        p.add_argument("--accum_bucket_mb", type=float,
                       default=cls.accum_bucket_mb,
                       help="bucket size (MB) for the accumulation "
                            "boundary's reduce->update->gather pipeline "
                            "(DDP bucket_cap_mb analog; overlap of "
                            "bucket k's collective with bucket k-1's "
                            "update; 0 = single-shot boundary, "
                            "bit-identical numerics)")
        p.add_argument("--warmup_steps", type=int, default=cls.warmup_steps,
                       help="LR warmup updates for the adamw "
                            "warmup-cosine schedule")
        p.add_argument("--shard_update", type=str, default=cls.shard_update,
                       choices=("auto", "on", "off"),
                       help="ZeRO-1 weight-update sharding over the dp "
                            "axes: reduce-scatter grads, shard-local "
                            "optimizer update (opt_state 1/N per chip), "
                            "all-gather params. auto = on for pure "
                            "DataParallel with dp world size > 1")
        p.add_argument("--quant_collectives", action="store_true",
                       help="opt-in block-scaled int8 gradient "
                            "collectives for the sharded update (int8 + "
                            "f32 scales on the wire, f32 accumulate; "
                            "bounded gradient quantization error; "
                            "stateless models, single dp axis)")
        p.add_argument("--seq_shard_activations", action="store_true",
                       help="Megatron sequence-parallel activations: shard "
                            "the residual stream's token dim over `tensor` "
                            "between transformer blocks (tensor>1 meshes)")
        p.add_argument("--profile_dir", type=str, default=None)
        p.add_argument("--metrics_jsonl", type=str, default=None,
                       help="append machine-readable metric records "
                            "(train/eval/epoch lines, device-memory and "
                            "collective telemetry) to this JSONL file")
        p.add_argument("--trace_path", type=str, default=None,
                       help="write a Chrome-trace JSON of host-side spans "
                            "(data-wait/train_step/eval/checkpoint) here "
                            "at exit; load in Perfetto")
        p.add_argument("--collective_stats", action="store_true",
                       help="trace the train step once at startup and "
                            "record its gradient-collective op/byte "
                            "census (jaxpr + compiled-HLO) and the "
                            "compiled step's Pallas kernel (Mosaic "
                            "custom call) census to the registry and "
                            "--metrics_jsonl")
        p.add_argument("--flight_recorder", type=str, default=None,
                       help="record span/instant events in a bounded ring "
                            "and dump them as JSON to this path on any "
                            "failure path (obs/flight.py)")
        p.add_argument("--divergence_check", action="store_true",
                       help="verify dp replicas hold bit-identical params "
                            "at every log interval (compiled fingerprint "
                            "pmax-pmin check) and emit a per-step "
                            "loss/grad-norm hash chain to --metrics_jsonl "
                            "for bitwise run diffing")
        p.add_argument("--eval_on_train", action="store_true",
                       help="replicate reference bug §A.1 (eval on train split)")
        return p

    @classmethod
    def from_argv(cls, argv: list[str] | None = None) -> "Config":
        ns = cls.parser().parse_args(argv)
        base = cls()
        kw = {f.name: getattr(ns, f.name) for f in dataclasses.fields(cls)
              if hasattr(ns, f.name)}
        # env-derived fields fall back to env when flags were not given
        for k in ("coordinator", "num_processes", "process_id"):
            if kw.get(k) is None:
                kw[k] = getattr(base, k)
        return cls(**kw)
