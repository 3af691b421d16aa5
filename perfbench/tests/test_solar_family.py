"""Family ``solar_open2`` (Solar-Open2-250B as one chip of the eight that
share each layer): the family as files, its counts against the integers
reckoned in ISSUE 44 and against the parameter tree's own bytes (the 6.62
GB), the state, the pool and the resident bytes of the cell, the decode
tick's floor, the catalog's keys, the draws of ``longgen_backlog``, the four
new per-layer metrics on hand-made traces and counters (a program without
the scope or the counter, as the parent, reads nothing), ``param_spec``
against the program's tree, and the rehearsal of the new cell."""

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import families, host_plane, run, trafficgen
from perfbench.family import solar_open2 as fam
from perfbench.readers import (counter_ratio, trace_scope_roofline,
                               trace_scope_share)
from perfbench.reference import solar_open2_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "solaropen2_longgen_backlog"
GLM = "glm53flash_longctx_backlog"
CONFIG = "solar-open2-250b-ep8-l4"
NEW = ["linear_scan_share.decode", "kda_step_roofline_share.decode",
       "attn_gate_share.decode", "state_rows_per_tick"]


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_the_family_is_files_found_by_name():
    assert families.family(CFG) is fam
    assert families.reference_module(CFG) is ref
    assert (fam.BUILD_MODEL, fam.DROPOUT_KEYS) == ("hybrid", ())
    assert families.without_dropout(CFG) == CFG
    for name in ("decode_tick_bytes", "kda_step_bytes"):
        assert families.count_fn(CFG, name) is getattr(fam, name)
    # the kernel's counts are the kernel's, not the family's
    from perfbench import bytes as nbytes
    assert families.count_fn(CFG, "paged_decode_attn_bytes") is (
        nbytes.paged_decode_attn_bytes)
    with pytest.raises(LookupError):
        families.family({"family": "solar_open3"})


def test_weights_are_the_reckoned_integers():
    # a KDA mixer: q, k, v, o; the two low-rank gates; beta; three convs
    assert fam.kda_params(CFG) == 137723904 == (
        4 * 33554432 + 2 * (524288 + 1048576) + 262144 + 3 * 4 * 8192)
    # a full mixer: q, o and the gate 33.55M each, k and v 4.19M each
    assert fam.full_params(CFG) == 109051904 == 3 * 33554432 + 2 * 4194304
    assert fam.expert_params(CFG) == 15728640 == 3 * 4096 * 1280
    assert fam.router_params(CFG) == 1310720
    ffn = 1310720 + 41 * 15728640                         # 40 held + shared
    layers = [109051904 + ffn, 137723904 + ffn]           # 755.2M, 783.9M
    assert layers == [755236864, 783908864]
    assert fam.solar_weight_params(CFG) == (
        layers[0] + 3 * layers[1] + 2 * 24576 * 4096) == 3308290048
    assert round(2 * fam.solar_weight_params(CFG) / 1e9, 2) == 6.62
    # the same number from the reference's own parameter spec: the matrices
    # in bfloat16; norm scales, rates, the selection bias and the decay
    # gate's bias in float32
    import jax
    from perfbench import weights
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    sizes = jax.tree.map(lambda s, d: (math.prod(s[0]), d), spec, dts,
                         is_leaf=weights._is_leaf)
    leaves = jax.tree.leaves(sizes, is_leaf=lambda x: isinstance(x, tuple))
    kda = 8192 + 64 + 128 + 2 * 4096     # dt_bias, A_log, o_norm, the norms
    assert sum(n for n, d in leaves if d == "float32") == (
        3 * kda + 2 * 4096 + 4 * 320 + 4096) == 63296
    assert sum(n for n, d in leaves if d == "bfloat16") == 3308290048
    per_layer = [sum(n for n, d in jax.tree.leaves(
        sizes["layers"][l], is_leaf=lambda x: isinstance(x, tuple))
        if d == "bfloat16") for l in range(4)]
    assert per_layer == [layers[0]] + [layers[1]] * 3
    held = 2 * 3308290048 + 4 * 63296
    assert CFG["bytes"]["weights"] == held == 6616833280
    assert CFG["bytes"]["parameters"] == 3308290048 + 63296


def test_state_pool_and_decode_tick_bytes():
    cell = load("workloads", CELL)["run"]
    assert cell == {"param_dtype": "bfloat16", "kv_dtype": "bf16",
                    "slots": 160, "t_max": 5120, "prompt_buf": 2048,
                    "warm_waves": 16}
    assert cell["t_max"] == 2048 + 3072
    assert cell["slots"] == CFG["serving"]["slots"]
    # a KDA layer's slot: 64 heads of 128 x 128 float32, and three tokens'
    # q^, k^, v^ in bfloat16: 4.19 MB + 0.15 MB; three layers 13.0 MB
    assert fam.kda_state_bytes_per_slot(CFG) == 4194304 + 147456
    assert 3 * fam.kda_state_bytes_per_slot(CFG) == (
        CFG["bytes"]["state_and_tails_per_slot"]) == 13025280
    # the full layer's token: K and V of 8 heads of 128 in bfloat16
    assert fam.kv_bytes_per_token(CFG) == 4096 == (
        CFG["bytes"]["pool_per_cached_token"])
    state = cell["slots"] * 3 * fam.kda_state_bytes_per_slot(CFG)
    pool = cell["slots"] * cell["t_max"] * 4096
    assert (state, pool) == (2084044800, 3355443200)      # 2.08 + 3.36 GB
    resident = CFG["bytes"]["weights"] + state + pool
    assert round(resident / 1e9, 2) == 12.06
    assert resident == CFG["bytes"]["resident_at_160_slots"]
    # a uniform router sends 160 rows of 8 over 320 to 98% of the experts:
    # the floor counts all 40 held
    assert 0.98 < 1 - (1 - 8 / 320) ** 160 < 0.99
    tick = families.count_fn(CFG, "decode_tick_bytes")
    matrices = 2 * (fam.solar_weight_params(CFG) - 24576 * 4096)
    assert fam.kda_step_bytes(CFG, 160) == 2 * state
    assert tick(CFG, 0) == matrices + 2 * state
    live = 160 * 1500.0
    assert tick(CFG, live) == pytest.approx(tick(CFG, 0) + live * 4096)
    # the state is a third of what a tick moves
    assert 0.35 < 2 * state / tick(CFG, live) < 0.37
    shape = families.kernel_shape(
        CFG, "decode", {"mean_live_context_tokens": live}, 1)
    assert shape == dict(live_context_tokens=live, q_heads=64, kv_heads=8,
                         head_dim=128, itemsize=2)
    assert families.kernel_shape(CFG, "decode", {"x": 1}, 1) is None
    assert families.kernel_shape(CFG, "admit_band", {}, 1) is None


def test_no_key_differs_from_the_catalogs_row_but_the_reduced():
    """Every key of the published config is in the file under its name;
    what differs is named in ``reduced`` and is no width."""
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 320, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"num_hidden_layers", "gqa_layers", "n_routed_experts",
                       "vocab_size"} == set(CFG["reduced"])
    m = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(CFG["reduced"])
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert CFG["gqa_layers"] == [0] and CFG["num_hidden_layers"] == 4
    assert (CFG["experts_held"], CFG["router_num_experts"],
            CFG["deployment_chips"]) == ([0, 40], 320, 8)
    assert CFG["vocab_size"] * 8 == 196608 and 40 * 8 == 320
    assert CFG["published"]["num_hidden_layers"] == 48
    for why in ("gqa_gate", "full_attention", "kda_gate", "kda_beta",
                "kda_heads", "kda_conv", "kda_output", "kda_draw",
                "shared_expert", "router", "norm_placement",
                "initializer_range", "stream_draw", "published_code"):
        assert why in CFG["assumed"]
    kw = fam.model_kwargs(CFG, {"max_seq_len": 5120})
    assert kw["layer_types"] == ("full_attention",) + (
        "linear_attention",) * 3
    assert kw["mlp_layer_types"] == ("sparse",) * 4
    assert (kw["num_experts"], kw["experts_held"], kw["top_k"],
            kw["shared_d_ff"], kw["moe_d_ff"], kw["routed_scale"]) == (
                320, (0, 40), 8, 1280, 1280, 1.0)
    assert (kw["attn_gate"], kw["qk_norm"], kw["kda_gate_lower_bound"],
            kw["kda_allow_neg_eigval"], kw["norm_placement"]) == (
                True, False, None, True, "pre")
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"],
            kw["kda_heads"], kw["kda_head_dim"], kw["kda_conv"],
            kw["kda_gate_rank"]) == (64, 8, 128, 64, 128, 4, 128)


def test_param_spec_is_the_programs_tree_at_the_rehearse_size():
    import jax
    from perfbench import weights
    tiny = run.overlay(CFG, CFG["rehearse"])
    model = families.build_program_model(
        tiny, {"max_seq_len": 128, "param_dtype": "bfloat16"})
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda s, d: (s[0], d), ref.param_spec(tiny),
                        ref.param_dtypes(tiny, "bfloat16"),
                        is_leaf=weights._is_leaf)
    assert have == want
    assert [model.layer_block(i).cache_kind for i in range(4)] == [
        "paged", "state", "state", "state"]


def test_longgen_backlog_draws():
    t = load("traffic", "longgen_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == 16 * 51
    cell = load("workloads", CELL)["run"]
    for r in a:
        assert 128 <= len(r["tokens"]) <= 2048 <= cell["prompt_buf"]
        assert 512 <= r["max_new"] <= 3072
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        assert len(r["tokens"]) + r["max_new"] <= cell["t_max"]
    # the issue's ramp: 160 requests 0.05 s apart, the rest due when it ends
    assert t["ramp"] == {"requests": 160, "gap_s": 0.05}
    due = [r["arrival_s"] for r in a]
    assert due[:160] == pytest.approx([0.05 * j for j in range(160)])
    assert due[160:] == pytest.approx([8.0] * (len(a) - 160))
    pairs = [(len(r["tokens"]), r["max_new"]) for r in a]
    assert len(set(pairs)) <= 48 == t["cycle"]
    assert sorted(pairs[:48]) == sorted(pairs[48:96]) != pairs[48:96]
    assert t["shape_seed"] not in {
        load("traffic", n)["shape_seed"]
        for n in ("chat_backlog", "chat_steady", "reason_backlog",
                  "longdoc_backlog", "longprompt_backlog",
                  "longctx_backlog")}
    assert (t["kind"], t["sampling"], t["shared_prefix"]) == (
        "backlog", "greedy", False)
    assert t["prompt_tokens"] == {"median": 768, "sigma": 0.6, "lo": 128,
                                  "hi": 2048}
    assert t["output_tokens"] == {"median": 1536, "sigma": 0.5, "lo": 512,
                                  "hi": 3072}
    # decode leads: two tokens served to every prompt token
    assert 1.8 * sum(len(r["tokens"]) for r in a) < sum(
        r["max_new"] for r in a)
    # every window of the ladder is drawn on
    rungs = [next(w for w in (256, 512, 1024, 2048) if w >= n - 1)
             for n, _ in pairs[:48]]
    assert [rungs.count(w) for w in (256, 512, 1024, 2048)] == [1, 10, 22, 15]


# ---- the four new metrics, on hand-made traces and counters ----------------

S = "jit(_segment_impl)/jit(main)/decode/while/body/"


def op(name, start, dur, path=None):
    return {"name": f"%{name} = f32[8] fusion()", "start": float(start),
            "dur": float(dur), "stats": {"tf_op": path} if path else {}}


def lines(ops, modules):
    return {"/device:TPU:0": {
        "ops": ops,
        "modules": [{"name": n, "start": float(s), "dur": float(d),
                     "stats": {}} for n, s, d in modules]}}


# two segments of 400 (time units), an admission between them
TICKS = lines([
    op("fusion.1", 0, 40, S + "attn/dot_general"),
    op("fusion.2", 40, 20, S + "attn/attn_gate/dot_general"),
    op("fusion.3", 60, 100, S + "attn/attn_linear/dot_general"),
    op("fusion.4", 160, 80, S + "attn/attn_linear/linear_scan/mul"),
    op("fusion.5", 240, 160, S + "mlp/experts/dot_general"),
    op("fusion.6", 400, 300,
       "jit(_admit_impl)/jit(main)/admit/attn/attn_linear/linear_scan/x"),
    op("fusion.7", 700, 320, S + "attn/attn_linear/linear_scan/mul"),
    op("fusion.8", 1020, 80, S + "attn/attn_gate/mul"),
], (("jit__segment_impl(3)", 0, 400), ("jit__admit_impl(5)", 400, 300),
    ("jit__segment_impl(3)", 700, 400)))
# a program from before the scopes and the counter: the parent
BEFORE = lines([op("fusion.1", 0, 400, S + "attn/dot_general")],
               (("jit__segment_impl(3)", 0, 400),))


class _Trace:
    """The two segments above as a trace summary: 0.8 s of two runs."""

    def module_time_s(self, pattern, trim_edges=False):
        return (0.8, 2.0) if pattern == "_segment_impl" else (0.0, 0.0)


def test_the_scope_shares_read_the_tick_only():
    scan = load("layer_metrics", "linear_scan_share.decode")
    gate = load("layer_metrics", "attn_gate_share.decode")
    assert scan == {"reader": "trace_scope_share", "scope": ["linear_scan"],
                    "of_module": "_segment_impl"}
    assert gate == dict(scan, scope=["attn_gate"])
    # the admission's 300 under linear_scan is no part of either
    assert trace_scope_share.share(TICKS, scan) == pytest.approx(
        100 * (80 + 320) / 800)
    assert trace_scope_share.share(TICKS, gate) == pytest.approx(
        100 * (20 + 80) / 800)
    # a program without the scopes reads nothing under either name
    assert trace_scope_share.share(BEFORE, scan) is None
    assert trace_scope_share.share(BEFORE, gate) is None


def test_the_steps_roofline_share_is_the_states_bytes_over_the_scopes_time(
        monkeypatch):
    spec = load("layer_metrics", "kda_step_roofline_share.decode")
    assert spec == {"reader": "trace_scope_roofline",
                    "scope": ["linear_scan"], "of_module": "_segment_impl",
                    "rows_counter": "state_rows_advanced",
                    "bytes_fn": "kda_step_bytes"}
    monkeypatch.setattr(host_plane, "device_lines", lambda root=None: TICKS)
    # the window: 2,000 ticks of which 150 rows a tick were in the plan; the
    # trace holds two segments of 16 ticks, half their time under the scope
    counters = {"state_rows_advanced": 300000, "ticks": 2000, "segment": 16}
    ctx = {"trace": _Trace(), "counters": counters, "config": CFG,
           "scopes": (), "device_kind": "TPU v5 lite"}
    got = trace_scope_roofline.read(spec, ctx)
    rows = 150 * 32
    by = 2 * rows * 3 * (4194304 + 147456)
    assert by == fam.kda_step_bytes(CFG, rows)
    assert got["value"] == pytest.approx(100 * (by / 819e9) / 0.4)
    assert "4800 slot-ticks in 32 ticks" in got["note"]
    # all the slots in every tick, at the bandwidth's own pace: 100, no more
    full = dict(counters, state_rows_advanced=160 * 2000)
    secs = fam.kda_step_bytes(CFG, 160 * 32) / 819e9
    monkeypatch.setattr(_Trace, "module_time_s",
                        lambda self, p, trim_edges=False: (2 * secs, 2.0))
    assert trace_scope_roofline.read(spec, dict(ctx, counters=full))[
        "value"] == pytest.approx(100.0)
    # what gives nothing, and does not raise: no trace; a program without
    # the counter (the parent); a family without the byte function; a
    # trace without the scope
    assert trace_scope_roofline.read(spec, dict(ctx, trace=None)) is None
    assert trace_scope_roofline.read(spec, dict(ctx, counters={
        "ticks": 2000, "segment": 16})) is None
    assert trace_scope_roofline.read(spec, dict(
        ctx, config=load("configs", "glm-5.3-flash-ep8-l5"))) is None
    monkeypatch.setattr(host_plane, "device_lines", lambda root=None: BEFORE)
    assert trace_scope_roofline.read(spec, ctx) is None


def test_state_rows_per_tick_is_a_ratio_of_running_counters():
    spec = load("layer_metrics", "state_rows_per_tick")
    assert spec == {"reader": "counter_ratio",
                    "numerator": ["state_rows_advanced"],
                    "denominator": ["ticks"]}
    read = lambda c: counter_ratio.read(spec, {"counters": c})
    assert read({"state_rows_advanced": 320000, "ticks": 2000}) == 160.0
    assert read({"state_rows_advanced": 0, "ticks": 2000}) == 0.0
    assert read({"ticks": 2000}) is None              # the parent's counters
    # the runner differences every number of stats over the window, so the
    # program keeps a running sum (a gauge would read 0)
    src = (ROOT / "distributed_compute_pytorch_tpu" / "serve.py").read_text()
    assert 'self.stats["state_rows_advanced"] += len(plan) * self.S' in src


def test_the_cell_is_in_the_manifest_after_what_was_there():
    """Found by NAME, after the entries PR 43 left last (a later PR appends
    after these, so nothing here says "last")."""
    m = run.load_json(ROOT / "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("glm-5.3-flash-ep8-l5")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index(GLM)
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "longgen_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    assert at > names.index("kda_scan_kernel_share.admit")
    from distributed_compute_pytorch_tpu.obs import tracing
    assert "attn_gate" in tracing.SCOPES
    for p in m["per_layer"][at:at + len(NEW)]:
        assert CELL in p["workloads"]
        assert p["moves"] == "serve_tokens_per_s"
        spec = load("layer_metrics", p["name"])
        assert (HERE / "readers" / f"{spec['reader']}.py").exists()
        assert set(spec.get("scope", [])) <= set(tracing.SCOPES)
        assert p["source"] == ("program_counter" if spec["reader"]
                               == "counter_ratio" else "device_trace")
    by_name = {p["name"]: p for p in m["per_layer"]}
    # GLM reports the scope and the counter; it has no byte function for
    # the step's floor and no gate
    assert by_name["linear_scan_share.decode"]["workloads"] == [GLM, CELL]
    assert by_name["state_rows_per_tick"]["workloads"] == [GLM, CELL]
    assert by_name["kda_step_roofline_share.decode"]["workloads"] == [CELL]
    assert by_name["attn_gate_share.decode"]["workloads"] == [CELL]
    assert by_name["kda_step_roofline_share.decode"]["unit"] == "%"
    # (a subset, not an equality: a later metric may list this cell)
    mine = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])}
    assert mine >= set(NEW) | {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog", "prefill_window_fill_share",
        "decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog",
        "decode_rows_parked_share.serve_backlog",
        "delivery_gap_p99_ms.serve_backlog",
        "delivery_gap_clear_ms.serve_backlog",
        "delivery_gap_behind_admission_ms.serve_backlog",
        "experts_share.decode", "experts_share.admit", "router_share.decode",
        "held_assignment_share", "expert_load_max_over_mean",
        "paged_attn_roofline_share.serve_backlog",
        "attn_linear_share.admit", "attn_linear_share.decode",
        "linear_scan_share.admit", "kda_scan_kernel_share.admit"}
    # no per-layer metric is left without a list, and one lists a cell only
    # if the metric it moves does too
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    for p in m["per_layer"]:
        assert p.get("workloads"), p["name"]
        for cell in p["workloads"]:
            assert cell in e2e[p["moves"]].get("workloads", [cell]), (
                p["name"], cell)
    for entry in m["configs"] + m["workloads"]:
        assert len(entry["why"]) <= 200
        assert len(entry.get("source", "")) <= 200


def test_the_cell_rehearses():
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, timeout=900, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL done: checks pass" in r.stdout
    assert f"perfbench | {CELL} |" in r.stdout


def test_the_reference_reads_out_the_rows_asked_and_reports_a_mean_gap():
    """``forward(rows=)`` gives the logits of those positions only;
    ``served_token_gaps`` gives every served token its request's mean gap;
    the reference's own greedy continuation has no gap at all."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench import weights
    tiny = run.overlay(CFG, CFG["rehearse"])
    params = weights.make_params(ref.param_spec(tiny), 3,
                                 ref.param_dtypes(tiny, "bfloat16"))
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, tiny["vocab_size"], 29)]
    whole = ref.forward(params, jnp.asarray(prompt), tiny)
    part = ref.forward(params, jnp.asarray(prompt), tiny, rows=(3, 4))
    assert part.shape == (4, tiny["vocab_size"])
    assert float(jnp.max(jnp.abs(part - whole[3:7]))) == 0.0
    served = []
    for _ in range(6):           # the float32 reference's greedy tokens
        logits = ref.forward(params, jnp.asarray(prompt + served), tiny)
        served.append(int(jnp.argmax(logits[-1])))
    raw = ref.raw_token_gaps(params, prompt, served, tiny, pad_to=8,
                             control=("int8",))
    assert len(raw["served"]) == 6 and float(raw["served"].max()) == 0.0
    other = [int(t) for t in rng.integers(1, tiny["vocab_size"], 6)]
    raw = ref.raw_token_gaps(params, prompt, other, tiny, pad_to=8,
                             control=("int8",))
    got = ref.served_token_gaps(params, prompt, other, tiny, pad_to=8,
                                control=("int8",))
    assert raw["served"].min() >= 0 and raw["served"].max() > 0
    for k in ("served", "int8"):
        assert got[k] == [pytest.approx(float(raw[k].mean()))] * 6
