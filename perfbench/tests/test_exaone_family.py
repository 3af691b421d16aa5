"""Family ``exaone_moe`` (K-EXAONE as one chip of eight): its counts
against the integers reckoned in ISSUE 28, the band's operations, the
catalog's widths, the draws of ``reason_backlog``, the new per-layer
metrics on a hand-made trace, and the rehearsal of the new cell."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import families, run, trafficgen
from perfbench.family import exaone_moe as fam
from perfbench.reference import exaone_moe_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "kexaone_reason_backlog"
CONFIG = "k-exaone-236b-a23b-ep8-l5"


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_weights_are_the_reckoned_integers():
    assert fam.attention_params(CFG) == 113246208            # 113.25M
    assert fam.dense_mlp_params(CFG) == 339738624            # + attn 453.0M
    assert fam.expert_params(CFG) == 37748736                # 37.75M
    assert fam.sparse_mlp_params(CFG) == 786432 + 17 * 37748736
    assert (fam.attention_params(CFG) + fam.sparse_mlp_params(CFG)
            == 755761152)                                    # 755.8M
    held = fam.exaone_weight_bytes(CFG)
    assert round(held / 1e9, 2) == 7.42
    # the same number from the reference's own parameter spec
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    import jax
    from perfbench import weights
    sizes = jax.tree.map(
        lambda s, d: int(__import__("math").prod(s[0]))
        * (4 if d == "float32" else 2), spec, dts, is_leaf=weights._is_leaf)
    assert sum(jax.tree.leaves(sizes)) == held


def test_decode_tick_bytes_is_weights_full_layer_and_rings():
    tick = families.count_fn(CFG, "decode_tick_bytes")
    weights_read = fam.exaone_matmul_params(CFG) * 2
    assert round(weights_read / 1e9, 2) == 7.19    # all but the embedding
    assert tick(CFG, 0) == weights_read
    # one full layer at 4096 B a token; four rings of at most 128 tokens a
    # slot: below 128 x 128 live tokens the rings hold them all
    assert tick(CFG, 10000) == weights_read + 10000 * 4096 * 5
    assert tick(CFG, 200000) == (weights_read + 200000 * 4096
                                 + 4 * 128 * 128 * 4096)
    experts = 4 * 16 * fam.expert_params(CFG) * 2
    assert round(experts / 1e9, 2) == 4.83
    assert 0.66 < experts / weights_read < 0.68


def test_band_counts_only_the_band():
    assert fam.band_pairs(2048, 128) == 128 * 129 / 2 + (2048 - 128) * 128
    assert fam.band_pairs(100, 128) == 100 * 101 / 2          # all causal
    shape = families.kernel_shape(
        CFG, "admit_band", {"prefill_calls": 10, "prefill_rows": 40}, 1)
    assert shape == dict(rows=4.0, q_heads=64, kv_heads=8, q_len=2048,
                         head_dim=128, window=128, itemsize=2)
    fl = families.count_fn(CFG, "flash_band_fwd_flops")(**shape)
    assert fl == 4.0 * 4 * 64 * fam.band_pairs(2048, 128) * 128
    full = 4.0 * 4 * 64 * (2048 * 2049 / 2) * 128
    assert 0.11 < fl / full < 0.13                 # an eighth of the causal
    by = families.count_fn(CFG, "flash_band_fwd_bytes")(**shape)
    assert by == 4 * 2048 * 128 * 2 * (2 * 64 + 2 * 8)
    assert families.kernel_shape(CFG, "admit_band", {}, 1) is None
    assert families.kernel_shape(CFG, "train", {}, 1) is None
    dec = families.kernel_shape(
        CFG, "decode", {"mean_live_context_tokens": 1e5}, 1)
    assert dec["q_heads"] == 64 and dec["kv_heads"] == 8


def test_no_width_differs_from_the_catalogs_row():
    """Every number of the published config is in the file under its key;
    what differs is named in ``reduced`` and is no width."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
        "intermediate_size": 18432, "max_position_embeddings": 262144,
        "moe_intermediate_size": 2048, "n_group": 1,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "sliding_window": 128,
        "topk_group": 1, "vocab_size": 153600}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size",
                       "num_nextn_predict_layers"} <= set(CFG["reduced"])
    assert CFG["published"]["num_experts"] == CFG["router_num_experts"] == 128
    assert CFG["experts_held"] == [0, CFG["num_experts"]]
    assert CFG["vocab_size"] * CFG["deployment_chips"] == 153600
    assert CFG["num_experts"] * CFG["deployment_chips"] == 128
    L = CFG["num_hidden_layers"]
    assert CFG["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention", "sliding_attention"])
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * (L - 1)
    assert CFG["sliding_windows"] == [128, 128, 128, 0, 128]
    cell = load("workloads", CELL)
    assert cell["run"]["slots"] == CFG["serving"]["slots"]
    assert cell["run"]["prompt_buf"] == CFG["serving"]["prefill_window"]
    for why in ("bias", "qk_norm", "rope_layers", "window", "norm_placement",
                "router_bias"):
        assert why in CFG["assumed"]


def test_reason_backlog_draws():
    t = load("traffic", "reason_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == 10 * 51
    for r in a:
        assert 64 <= len(r["tokens"]) <= 2048 and 256 <= r["max_new"] <= 3072
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        assert len(r["tokens"]) + r["max_new"] <= load(
            "workloads", CELL)["run"]["t_max"]
    ramp = t["ramp"]
    assert ramp["requests"] * ramp["gap_s"] == pytest.approx(12.8)
    assert a[0]["arrival_s"] == 0.0
    assert a[17]["arrival_s"] == pytest.approx(1.7)
    late = {r["arrival_s"] for r in a[128:]}
    assert len(late) == 1 and late.pop() == pytest.approx(12.8)
    # 48 pairs offered over and over; decode leads: output over prompt
    pairs = {(len(r["tokens"]), r["max_new"]) for r in a}
    assert len(pairs) <= 48
    assert sum(r["max_new"] for r in a) > 1.5 * sum(len(r["tokens"])
                                                    for r in a)


READ = f'''
import argparse, json
from perfbench import run
env = run.Env(argparse.Namespace(workload="{CELL}", seed=1, seconds=3.0,
                                 trace=1, rehearse=False),
              run.load_json(run.ROOT / "BENCHMARK.json"))
# the metrics that read counts of the family or counters of the program
# (the scope shares read a recorded trace: test_scope_and_owner_readers)
WANT = ("decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog", "held_assignment_share",
        "expert_load_max_over_mean",
        "paged_attn_roofline_share.serve_backlog",
        "flash_fwd_band_roofline_share.admit")
env.manifest["per_layer"] = [m for m in env.manifest["per_layer"]
                             if m["name"] in WANT]
class Trace:
    def module_time_s(self, pattern, trim_edges=False): return 0.64, 2.0
    def op_time_s(self, pattern): return 0.01
    def op_count(self, pattern): return 6.0
counters = {{"segment": 16, "mean_live_context_tokens": 150000.0,
            "prefill_calls": 4, "prefill_rows": 12,
            "expert_assignments": 8000, "expert_assignments_held": 1000,
            **{{f"expert_load_{{e}}": 50 + 5 * (e == 3) for e in range(16)}}}}
out = run.layer_metrics(env, {{"counters": counters, "trace": Trace(),
                              "e2e": {{}}}}, "TPU v5 lite")
print("READ " + json.dumps(out))
'''


def test_the_new_metrics_read_the_familys_counts():
    r = subprocess.run([sys.executable, "-c", READ], cwd=ROOT, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    got = json.loads(r.stdout.split("READ ", 1)[1])
    assert len(got) == 6
    tick = fam.decode_tick_bytes(CFG, 150000.0)
    assert got["decode_tick_ms.serve_backlog"]["value"] == pytest.approx(20.0)
    assert got["decode_tick_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (tick / 819e9) / 0.02)
    assert got["held_assignment_share"]["value"] == pytest.approx(12.5)
    assert got["expert_load_max_over_mean"]["value"] == pytest.approx(
        55 * 16 / (50 * 16 + 5))
    kv = 150000.0 * 2 * 8 * 128 * 2
    assert got["paged_attn_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (6 * kv / 819e9) / 0.01)
    fl = 6 * fam.flash_band_fwd_flops(3.0, 64, 8, 2048, 128, 128)
    by = 6 * fam.flash_band_fwd_bytes(3.0, 64, 8, 2048, 128, 128)
    assert got["flash_fwd_band_roofline_share.admit"][
        "value"] == pytest.approx(100 * max(fl / 197e12, by / 819e9) / 0.01)


def test_the_cell_is_in_the_manifest_as_appended_entries():
    m = run.load_json(ROOT / "BENCHMARK.json")
    assert m["configs"][-1]["name"] == CONFIG
    assert m["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "reason_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert set(mine) >= {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog",
        "decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog", "experts_share.decode",
        "router_share.decode", "attn_local_share.decode",
        "experts_share.admit", "held_assignment_share",
        "expert_load_max_over_mean",
        "paged_attn_roofline_share.serve_backlog",
        "flash_fwd_band_roofline_share.admit"}
    for p in m["per_layer"]:
        if p.get("workloads") == [CELL]:
            assert p["moves"] == "serve_tokens_per_s"


def test_the_cell_rehearses():
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, timeout=900, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL done: checks pass" in r.stdout
    assert f"perfbench | {CELL} |" in r.stdout


def test_the_reference_reports_a_requests_mean_gap():
    """``served_token_gaps`` gives every served token its request's mean
    gap (``raw_token_gaps`` has each token's own); the reference's own
    greedy continuation has no gap at all, and the int8 control's has."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench import weights
    tiny = run.overlay(CFG, CFG["rehearse"])
    params = weights.make_params(ref.param_spec(tiny), 3,
                                 ref.param_dtypes(tiny, "bfloat16"))
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, tiny["vocab_size"], 9)]
    served = []
    for _ in range(12):          # the float32 reference's greedy tokens
        logits = ref.forward(params, jnp.asarray(prompt + served), tiny)
        served.append(int(jnp.argmax(logits[-1])))
    raw = ref.raw_token_gaps(params, prompt, served, tiny, pad_to=8,
                             control=("int8",))
    assert len(raw["served"]) == 12 and float(raw["served"].max()) == 0.0
    other = [int(t) for t in rng.integers(1, tiny["vocab_size"], 12)]
    raw = ref.raw_token_gaps(params, prompt, other, tiny, pad_to=8,
                             control=("int8",))
    got = ref.served_token_gaps(params, prompt, other, tiny, pad_to=8,
                                control=("int8",))
    assert raw["served"].min() >= 0 and raw["served"].max() > 0
    for k in ("served", "int8"):
        assert got[k] == [pytest.approx(float(raw[k].mean()))] * 12
    rep = ref.routing_report(params, jnp.asarray(prompt + other), tiny)
    assert rep["margin"].shape == (4, 21) and (rep["margin"] >= 0).all()
    assert rep["flipped_held"].sum() <= rep["flipped"].sum()
