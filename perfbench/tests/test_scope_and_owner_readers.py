"""The two readers of ISSUE 24 on hand-made event lists (exact sums), the
wire decoder against ``jax.profiler.ProfileData`` on a trace made here, and
the loader of a run's own trace."""

import glob

import jax
import jax.numpy as jnp
import pytest

from perfbench import host_plane, xplane
from perfbench.readers import idle_owner_share as owner
from perfbench.readers import trace_scope_share as scoped

P = "jit(train_step)/jit(main)/"


def op(name, start, dur, path=None):
    return {"name": f"%{name} = f32[8] fusion()", "start": float(start),
            "dur": float(dur), "stats": {"tf_op": path} if path else {}}


def lines(ops, modules=(("jit_train_step(1)", 0, 1000),)):
    return {"/device:TPU:0": {
        "ops": ops,
        "modules": [{"name": n, "start": float(s), "dur": float(d),
                     "stats": {}} for n, s, d in modules]}}


def test_components_take_the_transformations_off():
    assert scoped.components(P + "transpose(jvp(attn))/dropout/div") == (
        ("jit(train_step)", "jit(main)", "attn", "dropout", "div"), True)
    assert scoped.components(P + "jvp(attn)/dot_general")[1] is False
    # a jitted helper that happens to be called like a scope is no scope
    names, _ = scoped.components("jit(f)/jit(dropout)/mul")
    assert "dropout" not in names
    assert scoped.op_path(op("x", 0, 1)) == ""
    assert scoped.op_path(op("x", 0, 1, "a/b:")) == "a/b"   # as the v5e has it


TRAIN = [
    op("fusion.1", 0, 100, P + "jvp(attn)/dot_general"),
    op("fusion.2", 100, 50, P + "jvp(attn)/dropout/jit(_where)/select_n"),
    # one fusion of an mlp matmul and the dropout after it: XLA names it
    # after its root, so all of it is dropout's
    op("fusion.3", 150, 200, P + "jvp(mlp)/dropout/div"),
    op("fusion.4", 350, 150, P + "transpose(jvp(attn))/dot_general"),
    op("fusion.5", 500, 100, P + "jvp(loss)/reduce_sum"),
    op("fusion.6", 600, 100, P + "jvp(head)/dot_general"),
    op("fusion.7", 700, 200, P + "optimizer/mul"),
    op("copy.8", 900, 100),                         # no path at all
    op("fusion.9", 1500, 400, P + "jvp(attn)/dot_general"),  # another module
]


def test_scope_shares_of_a_module():
    ls = lines(TRAIN, (("jit_train_step(1)", 0, 1000), ("jit_eval(2)", 1500, 400)))
    share = lambda **spec: scoped.share(ls, dict(of_module="^jit_train_step",
                                                 **spec))
    assert share(scope=["attn"]) == pytest.approx(30.0)       # 100+50+150
    assert share(scope=["attn"], **{"pass": "fwd"}) == pytest.approx(15.0)
    assert share(scope=["attn"], **{"pass": "bwd"}) == pytest.approx(15.0)
    assert share(scope=["attn"], **{"except": ["dropout"]}) == pytest.approx(25.0)
    assert share(scope=["dropout"]) == pytest.approx(25.0)    # 50+200
    assert share(scope=["head", "loss"]) == pytest.approx(20.0)
    assert share(scope=["optimizer"]) == pytest.approx(20.0)
    every = ["attn", "mlp", "dropout", "head", "loss", "optimizer"]
    assert share(**{"except": every}) == pytest.approx(10.0)  # the copy
    # over busy time: the other module's attention counts too
    assert scoped.share(ls, {"scope": ["attn"]}) == pytest.approx(
        100.0 * 700 / 1400)
    # a scope the slice never ran reads 0 while others are there ...
    assert share(scope=["kv_gather"], **{"except": ["attn"]}) == 0.0
    # ... and a trace without any of the names reads nothing
    assert share(scope=["kv_gather"]) is None
    bare = lines([op("fusion.1", 0, 100), op("copy.2", 100, 100)])
    assert scoped.share(bare, {"except": every, "of_module": "train"}) is None
    assert scoped.share({}, {"scope": ["attn"]}) is None


def test_a_loop_counts_for_what_its_body_leaves():
    S = "jit(_segment_impl)/jit(main)/"
    ops = [
        op("while.1", 0, 1000, S + "decode/while"),
        op("fusion.2", 0, 300, S + "decode/while/body/attn/kv_gather/gather"),
        op("fusion.3", 300, 400, S + "decode/while/body/attn/dot_general"),
        op("fusion.4", 700, 200, S + "decode/while/body/mlp/dot_general"),
        op("fusion.5", 1000, 100, S + "transpose"),
    ]
    assert scoped.self_times(ops) == [100.0, 300.0, 400.0, 200.0, 100.0]
    ls = lines(ops, (("jit__segment_impl(9)", 0, 1100),))
    share = lambda **spec: scoped.share(ls, dict(of_module="_segment_impl",
                                                 **spec))
    assert share(scope=["kv_gather"]) == pytest.approx(100 * 300 / 1100)
    assert share(scope=["attn"], **{"except": ["kv_gather"]}) == pytest.approx(
        100 * 400 / 1100)
    inner = ["attn", "mlp", "kv_gather"]
    # the loop's own 100 (under `decode` only) and the transpose outside it
    assert share(**{"except": inner}) == pytest.approx(100 * 200 / 1100)
    assert share(scope=["decode"]) == pytest.approx(100 * 1000 / 1100)


def unscoped_file(which):
    import json
    import pathlib
    here = pathlib.Path(__file__).resolve().parent.parent
    return json.load(open(here / "layer_metrics" / f"unscoped_share.{which}.json"))


DECODE = [
    op("while.1", 0, 1000, "jit(_segment_impl)/jit(main)/decode/while"),
    op("fusion.2", 0, 300, "jit(_segment_impl)/jit(main)/decode/while/body/attn/kv_gather/gather"),
    op("fusion.3", 300, 400, "jit(_segment_impl)/jit(main)/decode/while/body/attn/dot_general"),
    op("fusion.4", 700, 200, "jit(_segment_impl)/jit(main)/decode/while/body/mlp/dot_general"),
    op("fusion.5", 1000, 100, "jit(_segment_impl)/jit(main)/transpose"),
]


def test_unscoped_files_read_what_is_under_no_declared_scope():
    """The two files as they are, over the hand-made lines: the values the
    listed form gave (10.0 over the train lines, 100 x 200 / 1100 over the
    decode lines), from the vocabulary the run hands over alone."""
    from distributed_compute_pytorch_tpu.obs.tracing import SCOPES
    train = lines(TRAIN, (("jit_train_step(1)", 0, 1000),
                          ("jit_eval(2)", 1500, 400)))
    decode = lines(DECODE, (("jit__segment_impl(9)", 0, 1100),))
    for which, ls, want in (("train", train, 10.0),
                            ("decode", decode, 100 * 200 / 1100)):
        spec = unscoped_file(which)
        assert set(spec["wraps"]) <= {"admit", "decode"} and "scope" not in spec
        assert scoped.share(ls, spec, SCOPES) == pytest.approx(want)
        # the list the file still carries (tier-1's test reads it) adds
        # nothing to the vocabulary
        bare = {k: v for k, v in spec.items() if k != "except"}
        assert scoped.share(ls, bare, SCOPES) == pytest.approx(want)
        assert set(spec["except"]) <= set(SCOPES) - set(spec["wraps"])


def test_a_scope_declared_later_is_left_out_of_both_unscoped_shares():
    """One more name in the vocabulary the run hands over, no file
    edited: its operations leave both shares."""
    from distributed_compute_pytorch_tpu.obs.tracing import SCOPES
    assert "router" not in SCOPES
    train = lines(TRAIN + [op("fusion.10", 1000, 100, P + "jvp(router)/top_k")],
                  (("jit_train_step(1)", 0, 1100),))
    S = "jit(_segment_impl)/jit(main)/decode/while/body/"
    decode = lines(DECODE[:-1] + [op("fusion.6", 900, 100, S + "router/top_k"),
                                  DECODE[-1]],
                   (("jit__segment_impl(9)", 0, 1100),))
    later = tuple(SCOPES) + ("router",)
    for which, ls, without, with_ in (
            ("train", train, 100 * 200 / 1100, 100 * 100 / 1100),
            # the router's 100 come out of the loop's own self time either
            # way; declared, they no longer count as unscoped
            ("decode", decode, 100 * 200 / 1100, 100 * 100 / 1100)):
        spec = unscoped_file(which)
        assert scoped.share(ls, spec, SCOPES) == pytest.approx(without)
        assert scoped.share(ls, spec, later) == pytest.approx(with_)
    # a share that names its scope takes no notice of the vocabulary
    assert scoped.share(train, {"scope": ["attn"], "of_module": "^jit_train"},
                        later) == pytest.approx(100 * 300 / 1100)


def span(name, start, dur, thread="/host:CPU#0:python3", **args):
    return {"thread": thread, "name": name, "args": args,
            "start": float(start), "dur": float(dur)}


def test_idle_time_by_owner():
    us = 1e3                                         # the lists are in us
    ops = [op("a", 0, 100 * us), op("b", 150 * us, 50 * us),   # gap 100-150
           op("c", 400 * us, 100 * us),                        # gap 200-400
           op("d", 520 * us, 80 * us)]                         # gap 500-520
    other = "/host:CPU#1:python3"
    sp = lambda name, start, dur, **kw: span(name, start * us, dur * us, **kw)
    events = [
        sp("train_step", 90, 30),                  # 100-120 of gap 1
        sp("data_wait", 120, 20),                  # 120-140 of gap 1
        sp("epoch_fence", 190, 110),               # 200-300 of gap 2
        sp("data_wait", 320, 60),                  # 320-380 of gap 2
        sp("PjitFunction(train_step)", 325, 10),   # not one of the owners
        sp("data_wait", 500, 20, thread=other),    # another thread's
        sp("train_step", 510, 5, thread=other),
    ]
    spec = {"span": "data_wait",
            "owners": ["data_wait", "train_step", "epoch_fence"]}
    said = []
    got = owner.share(ops, events, spec, 1000 * us, report=said.append)
    assert got == pytest.approx(100.0 * (20 + 60) / 1000)
    assert owner.gaps(ops) == [(100 * us, 150 * us), (200 * us, 400 * us),
                               (500 * us, 520 * us)]
    assert said == [
        "INFO idle gap 0.200 ms in epoch_fence (epoch_fence 0.100, "
        "data_wait 0.060, unowned 0.040)",
        "INFO idle gap 0.050 ms in train_step (train_step 0.020, "
        "data_wait 0.020, unowned 0.010)",
        "INFO idle gap 0.020 ms in unowned (unowned 0.020)"]
    # innermost wins where spans nest
    nested = [span("admit_wave", 0, 100), span("prefill_wave", 20, 50)]
    assert owner.owned((10.0, 90.0), nested) == {"admit_wave": 30.0,
                                                 "prefill_wave": 50.0}
    # no span of the program in the profile: nothing to read
    assert owner.share(ops, events[4:5], spec, 1000 * us) is None


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation("dispatch_segment", rows=3,
                                          rids="req-0 req-1"):
            jax.jit(lambda x: jnp.tanh(x) @ x)(jnp.ones((64, 64))
                                               ).block_until_ready()
    return d


def test_decoder_agrees_with_profiledata(cpu_trace):
    path = xplane.newest(str(cpu_trace))
    assert path == sorted(glob.glob(f"{cpu_trace}/**/*.xplane.pb",
                                    recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    n = 0
    for p, q in zip(data.planes, xplane.load(path), strict=True):
        assert p.name == q["name"]
        for line, mine in zip(p.lines, q["lines"], strict=True):
            assert line.name == mine["name"]
            for e, g in zip(line.events, mine["events"], strict=True):
                assert e.name == g["name"]
                assert e.start_ns == pytest.approx(g["start"], abs=1e-3)
                assert e.duration_ns == pytest.approx(g["dur"], abs=1e-3)
                for k, v in e.stats:       # mine also holds the metadata's
                    assert g["stats"][k] == v
                n += 1
    assert n > 10
    skipped = xplane.load(path, lambda plane, line: False)
    assert sum(line["skipped"] for p in skipped for line in p["lines"]) == n


def test_metadata_stats_reach_the_event():
    """What ProfileData hides: a stat of the event's METADATA."""
    def varint(v):
        out = b""
        while True:
            b, v = v & 0x7F, v >> 7
            out += bytes([b | (0x80 if v else 0)])
            if not v:
                return out

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    stat_meta = field(5, field(1, 1) + field(2, field(1, 1) + field(2, b"tf_op")))
    meta_stat = field(1, 1) + field(5, b"jit(f)/attn/dot_general")
    event_meta = field(4, field(1, 7) + field(2, field(1, 7) + field(
        2, b"%fusion.1 = f32[] fusion()") + field(5, meta_stat)))
    event = field(1, 7) + field(2, 5000) + field(3, 7000)
    line = field(3, field(2, b"XLA Ops") + field(3, 1000) + field(4, event))
    plane = field(1, field(2, b"/device:TPU:0") + line + event_meta + stat_meta)
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
        f.write(plane)
        f.flush()
        got, = xplane.load(f.name)
    ev, = got["lines"][0]["events"]
    assert ev == {"name": "%fusion.1 = f32[] fusion()", "start": 1005.0,
                  "dur": 7.0, "stats": {"tf_op": "jit(f)/attn/dot_general"}}
    assert scoped.components(scoped.op_path(ev))[0] == (
        "jit(f)", "attn", "dot_general")


def test_host_plane_holds_the_spans_with_their_arguments(cpu_trace):
    events = host_plane.host_events(cpu_trace)
    seg, = [e for e in events if e["name"] == "dispatch_segment"]
    assert seg["args"] == {"rows": 3, "rids": "req-0 req-1"}
    assert seg["dur"] > 0 and seg["thread"].startswith("/host:CPU#")
    assert owner.dispatch_thread(events, {"dispatch_segment"}) == seg["thread"]
    assert host_plane.planes(cpu_trace) is host_plane.planes(cpu_trace)
    assert host_plane.device_lines(cpu_trace) == {}     # the CPU has none
    assert host_plane.planes(cpu_trace / "nothing_here") == []
