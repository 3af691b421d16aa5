"""The trace reduction on a hand-made trace (exact unions and sums) and on
an excerpt recorded on a v5e (PR 23: the start of one GPT-2-medium train
step), so that kernel and module names are matched as the chip writes them."""

import json
import pathlib

import pytest

from perfbench.trace_reduce import TraceSummary

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur, "stats": {}}


def test_busy_is_the_union_and_kernel_time_the_sum():
    events = [
        ev("XLA Ops", "%fusion.1 = f32[8] fusion(%p)", 0, 100),
        ev("XLA Ops", "%dcp_flash_fwd_.2 = bf16[8] custom-call(%fusion.1)", 50, 100),
        ev("XLA Ops", "%fusion.3 = f32[8] fusion(%dcp_flash_fwd_.2)", 300, 100),
        ev("XLA Ops", "%all-reduce.4 = f32[8] all-reduce(%fusion.3)", 350, 100),
        ev("XLA Modules", "jit_step(123)", 0, 450),
        ev("python", "host thing", 0, 1000, plane="/host:CPU"),
    ]
    t = TraceSummary(events, window_s=1000e-9)
    assert t.busy_s() == pytest.approx(300e-9)       # [0,150] + [300,450]
    assert t.op_time_s("dcp_flash_fwd") == pytest.approx(100e-9)
    # the consumer of the kernel's output names it as an operand: not a call
    assert t.op_count("dcp_flash_fwd") == 1
    assert t.op_union_s("all-reduce|all-gather") == pytest.approx(100e-9)
    assert t.module_time_s("^jit_step") == (pytest.approx(450e-9), 1)
    gaps = t.idle_gaps()
    assert gaps[0][0] == "before fusion" and gaps[0][1] == pytest.approx(150e-9)


def test_two_devices_are_averaged_and_idle_ones_left_out():
    events = [ev("XLA Ops", "%a.1 = f32[] x()", 0, 100),
              ev("XLA Ops", "%a.1 = f32[] x()", 0, 300, plane="/device:TPU:1"),
              ev("XLA Modules", "m(1)", 0, 1, plane="/device:TPU:2")]
    t = TraceSummary(events, window_s=1e-6)
    assert t.planes == ["/device:TPU:0", "/device:TPU:1"]
    assert t.busy_s() == pytest.approx(200e-9)


def test_trim_edges_leaves_out_cut_executions():
    events = [ev("XLA Modules", "jit_train_step(1)", s, d)
              for s, d in ((0, 50), (100, 180), (300, 180), (500, 20))]
    events.append(ev("XLA Ops", "%x.1 = f32[] x()", 0, 10))
    t = TraceSummary(events)
    assert t.module_time_s("train_step") == (pytest.approx(430e-9), 4)
    assert t.module_time_s("train_step", trim_edges=True) == (
        pytest.approx(360e-9), 2)


def test_recorded_v5e_excerpt():
    events = json.load(open(DATA / "trace_v5e_train_excerpt.json"))
    t = TraceSummary(events)
    assert not t.rehearsal and t.planes == ["/device:TPU:0"]
    # 400 operations of one partial step: back to back on the device
    assert 0.99 < t.busy_s() / t.window_s <= 1.0
    # the backward kernels appear as transpose_jvp_dcp_flash_bwd_*__.N
    assert t.op_count("dcp_flash_bwd_dq") == 4
    assert t.op_count("dcp_flash_bwd_dkv") == 4
    assert t.op_time_s("dcp_flash_bwd_dkv") == pytest.approx(3.307629e-3)
    assert t.module_time_s("^jit_train_step")[1] == 1
    assert t.top_ops(1)[0][0].startswith("dcp_flash_bwd_dkv")
