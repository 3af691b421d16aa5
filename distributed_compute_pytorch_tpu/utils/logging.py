"""Coordinator-guarded structured logging.

The reference prints aggregate lines under ``rank == 0`` guards
(``/root/reference/main.py:66-68,93-95``) but leaks unguarded per-rank prints
(``main.py:100,132``). Here every user-facing line goes through the
coordinator guard, and metrics can additionally stream to a JSONL file for
machine consumption (SURVEY §5.5).

ISSUE 8: :class:`MetricLogger` is a context manager (the JSONL handle
closes on EVERY trainer exit path, including preemption — ``Trainer.fit``
wraps its body in try/finally), ``close`` is idempotent, and every record
is mirrored into an ``obs.metrics.Registry`` (the process default unless
one is injected), so train lines and the telemetry layer share one sink:
``Registry.snapshot()`` carries the latest ``train.loss`` / ``eval.*`` /
``epoch.*`` next to whatever gauges/histograms other subsystems record.
"""

from __future__ import annotations

import json
import sys
import time

from distributed_compute_pytorch_tpu.core.mesh import is_coordinator
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics


def log0(*args, **kw) -> None:
    """``print`` from the coordinator only (reference's rank-0 guard)."""
    if is_coordinator():
        print(*args, **kw)
        sys.stdout.flush()


def device_banner() -> str:
    """Platform, device kind and device count as JAX reports them — the
    first line every CLI prints, so a run that fell back to the CPU
    (accelerator runtime failed to load) is visible at a glance."""
    import jax
    devs = jax.devices()
    return (f"platform: {devs[0].platform} | device_kind: "
            f"{devs[0].device_kind} | devices: {len(devs)}")


class MetricLogger:
    """stdout (reference cadence/format) + optional JSONL sink + the
    metrics registry (one record, three sinks)."""

    def __init__(self, jsonl_path: str | None = None,
                 registry: obs_metrics.Registry | None = None):
        self._f = (open(jsonl_path, "a")
                   if (jsonl_path and is_coordinator()) else None)
        self._reg = registry if registry is not None else obs_metrics.REGISTRY

    def __enter__(self) -> "MetricLogger":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def train_line(self, epoch: int, step: int, steps_per_epoch: int,
                   loss: float) -> None:
        # same shape as reference main.py:67-68
        pct = 100.0 * step / steps_per_epoch
        log0(f"epoch: {epoch} [{step}/{steps_per_epoch} ({pct:.0f}%)]\t "
             f"Loss:{loss:.6f}")
        self._reg.gauge("train.loss").set(loss)
        self._reg.gauge("train.step").set(epoch * steps_per_epoch + step)
        self._emit({"kind": "train", "epoch": epoch, "step": step,
                    "loss": loss})

    def eval_line(self, epoch: int, loss: float, correct: int, total: int) -> None:
        # same shape as reference main.py:94-95, with the loss actually
        # normalised (fixes SURVEY §A.5)
        acc = 100.0 * correct / max(total, 1)
        log0(f"\nTest set: Average loss: {loss:.4f}, "
             f"Accuracy: {correct}/{total} ({acc:.0f}%)\n")
        self._reg.gauge("eval.loss").set(loss)
        self._reg.gauge("eval.accuracy").set(acc / 100.0)
        self._emit({"kind": "eval", "epoch": epoch, "loss": loss,
                    "correct": correct, "total": total, "accuracy": acc})

    def epoch_time(self, epoch: int, seconds: float, samples_per_sec: float) -> None:
        # reference main.py:132 prints wall time; we add throughput (the
        # north-star metric, BASELINE.md)
        log0(f"time to complete this epoch: {seconds} seconds "
             f"({samples_per_sec:.1f} samples/s)")
        self._reg.gauge("epoch.seconds").set(seconds)
        self._reg.gauge("epoch.samples_per_sec").set(samples_per_sec)
        self._emit({"kind": "epoch", "epoch": epoch, "seconds": seconds,
                    "samples_per_sec": samples_per_sec})

    def telemetry(self, kind: str, record: dict) -> None:
        """Ship an arbitrary telemetry record (device-memory gauges,
        collective-byte stats) to the JSONL sink under its own
        ``kind`` — no stdout line; the registry was already updated by
        whoever measured."""
        self._emit({"kind": kind, **record})

    def _emit(self, rec: dict) -> None:
        if self._f is not None:
            rec["ts"] = time.time()
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None
