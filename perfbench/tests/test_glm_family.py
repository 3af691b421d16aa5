"""Family ``glm5_next`` (GLM-5.3-Flash as one chip of the eight that share
each layer): its counts against the integers reckoned in ISSUE 41 (a KDA
mixer, a sparse latent mixer, the hyper-connection maps, the layers, the
weights; the state, the pools and the resident bytes of the cell; the decode
tick's floor), the catalog's widths, the draws of ``longctx_backlog``, the
new counter metric on hand-made counters, ``param_spec`` against the
program's tree, and the rehearsal of the new cell."""

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import families, run, trafficgen
from perfbench.family import glm5_next as fam
from perfbench.reference import glm5_next_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "glm53flash_longctx_backlog"
CONFIG = "glm-5.3-flash-ep8-l5"
NEW = ["attn_linear_share.admit", "attn_linear_share.decode",
       "linear_scan_share.admit", "attn_sparse_share.admit",
       "attn_sparse_share.decode", "index_select_share.decode",
       "hyper_mix_share.admit", "hyper_mix_share.decode",
       "sparse_selected_share"]


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_weights_are_the_reckoned_integers():
    # a KDA mixer: q, k, v, o; the two low-rank gates; beta; three convs
    assert fam.kda_params(CFG) == 137723904 == (
        4 * 33554432 + 2 * (524288 + 1048576) + 262144 + 3 * 4 * 8192)
    # a sparse latent mixer: q 6.29 + 25.17M, kv 2.10 + 16.78M, o 67.11M,
    # the indexer 6.29 + 0.52 + 0.13M
    assert fam.sparse_latent_params(CFG) == 124387328 == (
        6291456 + 25165824 + 2097152 + 16777216 + 67108864
        + 6291456 + 524288 + 131072)
    assert fam.hyper_params(CFG) == 786432 == 2 * 16384 * 24
    assert fam.dense_mlp_params(CFG) == 150994944
    assert fam.expert_params(CFG) == 25165824
    assert fam.router_params(CFG) == 1179648
    sparse_ffn = 1179648 + 37 * 25165824                  # 36 held + shared
    layers = [137723904 + 786432 + 150994944,             # 289.5M
              124387328 + 786432 + sparse_ffn,            # 1,057.5M
              137723904 + 786432 + sparse_ffn]            # 1,070.8M
    assert layers == [289505280, 1057488896, 1070825472]
    assert fam.glm_weight_params(CFG) == (
        layers[0] + layers[1] + 3 * layers[2] + 2 * 19360 * 4096
    ) == 4718067712
    assert round(2 * fam.glm_weight_params(CFG) / 1e9, 2) == 9.44
    # the same number from the reference's own parameter spec: the matrices
    # in bfloat16; norm scales, gains and biases, the selection bias and
    # the decay gate's bias in float32
    import jax
    from perfbench import weights
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    sizes = jax.tree.map(lambda s, d: (math.prod(s[0]), d), spec, dts,
                         is_leaf=weights._is_leaf)
    leaves = jax.tree.leaves(sizes, is_leaf=lambda x: isinstance(x, tuple))
    hc = 2 * (3 + 4 + 4 + 16)                 # a layer's gains and biases
    kda = 8192 + 64 + 128 + hc + 2 * 4096     # dt_bias, A_log, o_norm, norms
    sparse = 1536 + 512 + 2 * 128 + hc + 2 * 4096
    assert sum(n for n, d in leaves if d == "float32") == (
        4 * kda + sparse + 4 * 288 + 4096) == 82318
    assert sum(n for n, d in leaves if d == "bfloat16") == 4718067712
    per_layer = [sum(n for n, d in jax.tree.leaves(
        sizes["layers"][l], is_leaf=lambda x: isinstance(x, tuple))
        if d == "bfloat16") for l in range(5)]
    assert per_layer == [layers[0], layers[1]] + [layers[2]] * 3


def test_state_pools_and_decode_tick_bytes():
    cell = load("workloads", CELL)["run"]
    assert cell == {"param_dtype": "bfloat16", "kv_dtype": "bf16",
                    "slots": 32, "t_max": 17184, "prompt_buf": 16384,
                    "warm_waves": 4}
    # the first multiple of the block of 32 at or above 16384 + 768 + 16
    assert cell["t_max"] == -(-(16384 + 768 + 16) // 32) * 32
    assert cell["slots"] == CFG["serving"]["slots"]
    # a KDA layer's slot: 64 heads of 128 x 128 float32, and three tokens'
    # q^, k^, v^ in bfloat16
    assert fam.kda_state_bytes_per_slot(CFG) == 4194304 + 147456
    # a sparse layer's token: 512 channels and a quarter of a key of 128
    assert fam.sparse_bytes_per_token(CFG) == 1024 + 64
    state = cell["slots"] * 4 * fam.kda_state_bytes_per_slot(CFG)
    pool = cell["slots"] * cell["t_max"] * 1088
    assert (state, pool) == (555745280, 598278144)        # 0.56 + 0.60 GB
    resident = 2 * fam.glm_weight_params(CFG) + state + pool
    assert round(resident / 1e9, 2) == 10.59
    assert 0.67 < resident / 15.75e9 < 0.68               # of 15.75
    share = fam.experts_touched_share(CFG, 32)
    assert share == pytest.approx(1 - (1 - 8 / 288) ** 32)
    assert 0.59 < share < 0.60                            # 59% at 32 rows
    tick = families.count_fn(CFG, "decode_tick_bytes")
    matrices = 2 * (fam.glm_weight_params(CFG) - 19360 * 4096
                    - (1 - share) * 4 * 36 * 25165824)
    assert tick(CFG, 0) == pytest.approx(matrices + 2 * state)
    assert tick(CFG, 0) < 2 * fam.glm_weight_params(CFG) + 2 * state
    # a context past the selection: the pooled keys of all of it, the
    # latent vectors of 2,048 + 2.5 tokens a row and no more
    live = 32 * 9000.0
    assert tick(CFG, live) == pytest.approx(
        tick(CFG, 0) + live * 64 + 32 * 2050.5 * 1024)
    assert tick(CFG, 32 * 1000.0) == pytest.approx(
        tick(CFG, 0) + 32000 * 64 + 32000 * 1024)
    assert families.kernel_shape(CFG, "decode", {"x": 1}, 1) is None


def test_no_width_differs_from_the_catalogs_row():
    """Every number of the published config is in the file under its key;
    what differs is named in ``reduced`` and is no width."""
    published = {
        "first_k_dense_replace": 3, "hc_eps": 1e-06, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "head_dim": 0, "hidden_size": 4096,
        "index_head_dim": 128, "index_kpool": 4, "index_n_heads": 32,
        "index_topk": 2048, "intermediate_size": 12288,
        "kv_lora_rank": 512, "max_position_embeddings": 1048576,
        "moe_intermediate_size": 2048, "n_group": 1,
        "n_routed_experts": 288, "n_shared_experts": 1,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 45, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 256, "qk_nope_head_dim": 256, "qk_rope_head_dim": 0,
        "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
        "swiglu_limit": 10, "topk_group": 1, "v_head_dim": 256,
        "vocab_size": 154880}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"first_k_dense_replace", "n_routed_experts",
                       "num_hidden_layers", "num_nextn_predict_layers",
                       "vocab_size"}
    lists = {"layer_types", "indexer_types", "mlp_layer_types",
             "linear_attn_config"}
    assert differs | lists == set(CFG["reduced"])
    m = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(CFG["reduced"])
    assert entry["source"] == CFG["source"] and len(entry["reduced"]) == 9
    # the group's widths are the published ones; its two lists are cut
    assert CFG["linear_attn_config"] == {
        "num_heads": 64, "gate_lower_bound": -5, "head_dim": 128,
        "short_conv_kernel_size": 4, "kda_layers": [0, 2, 3, 4],
        "full_attn_layers": [1]}
    assert CFG["layer_types"] == [
        "linear_attention", "deepseek_sparse_attention"
    ] + ["linear_attention"] * 3
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CFG["indexer_types"] == ["full"] * 5
    assert (CFG["experts_held"], CFG["router_num_experts"],
            CFG["deployment_chips"]) == ([0, 36], 288, 8)
    assert CFG["vocab_size"] * 8 == 154880 and 36 * 8 == 288
    assert CFG["model_type"] == "glm5_next_text" and CFG["mla_use_nope"]
    for why in ("hyper_connection", "stream_fan_out_and_fold",
                "hyper_connection_draw", "kda_gate", "kda_conv",
                "kda_output", "kda_draw", "index_pooling", "index_tail",
                "index_rope", "index_scores", "latent", "swiglu_limit",
                "router_bias", "norm_placement", "initializer_range",
                "published_code"):
        assert why in CFG["assumed"]
    assert "vision tower" in CFG["deployment"]
    kw = fam.model_kwargs(CFG, {"max_seq_len": 17184})
    assert kw["layer_types"] == (
        "linear_attention", "sparse_latent_attention") + (
            "linear_attention",) * 3
    assert (kw["num_experts"], kw["experts_held"], kw["top_k"],
            kw["shared_d_ff"], kw["hc_mult"], kw["index_topk"],
            kw["swiglu_limit"]) == (288, (0, 36), 8, 2048, 4, 2048, 10.0)


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
    assert [model.layer_block(i).cache_kind for i in range(5)] == [
        "state", "latent+index", "state", "state", "state"]


def test_longctx_backlog_draws():
    t = load("traffic", "longctx_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == 10 * 51
    cell = load("workloads", CELL)["run"]
    for r in a:
        assert 3072 <= len(r["tokens"]) <= 16384 <= cell["prompt_buf"]
        assert 64 <= r["max_new"] <= 768
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        assert len(r["tokens"]) + -(-r["max_new"] // 16) * 16 <= cell["t_max"]
        # every context is past the selection and its tail
        assert len(r["tokens"]) > CFG["index_topk"] + CFG["index_kpool"]
    # the issue's ramp: 32 requests 0.1 s apart, the rest due when it ends
    assert t["ramp"] == {"requests": 32, "gap_s": 0.1}
    due = [r["arrival_s"] for r in a]
    assert due[:32] == pytest.approx([0.1 * j for j in range(32)])
    assert due[32:] == pytest.approx([3.2] * (len(a) - 32))
    pairs = [(len(r["tokens"]), r["max_new"]) for r in a]
    assert len(set(pairs)) <= 48 == t["cycle"]
    assert sorted(pairs[:48]) == sorted(pairs[48:96]) != pairs[48:96]
    assert t["shape_seed"] not in {
        load("traffic", n)["shape_seed"]
        for n in ("chat_backlog", "chat_steady", "reason_backlog",
                  "longdoc_backlog", "longprompt_backlog")}
    assert t["prompt_tokens"] == {"median": 8192, "sigma": 0.5, "lo": 3072,
                                  "hi": 16384}
    assert t["output_tokens"] == {"median": 256, "sigma": 0.5, "lo": 64,
                                  "hi": 768}
    # admission leads: thirty prompt tokens to every token served
    assert sum(len(r["tokens"]) for r in a) > 25 * sum(r["max_new"] for r in a)
    # every window a prompt of the mix can take is drawn on
    rungs = [next(w for w in (4096, 8192, 16384) if w >= n - 1)
             for n, _ in pairs[:48]]
    assert [rungs.count(w) for w in (4096, 8192, 16384)] == [3, 22, 23]


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
        "expert_load_max_over_mean", "sparse_selected_share",
        "prefill_window_fill_share")
env.manifest["per_layer"] = [m for m in env.manifest["per_layer"]
                             if m["name"] in WANT]
class Trace:
    def module_time_s(self, pattern, trim_edges=False): return 0.48, 2.0
    def op_time_s(self, pattern): return 0.05
    def op_count(self, pattern): return 5.0
counters = {{"segment": 16, "mean_live_context_tokens": 288000.0,
            "prefill_calls": 10, "prefill_rows": 12, "prefill_tokens": 90000,
            "prefill_window_tokens": 122880,
            "sparse_tokens_attended": 2050 * 9000,
            "sparse_tokens_in_context": 9100 * 9000,
            "expert_assignments": 8000, "expert_assignments_held": 1000,
            **{{f"expert_load_{{e}}": 25 + 10 * (e == 3) for e in range(36)}}}}
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
    tick = fam.decode_tick_bytes(CFG, 288000.0)
    assert got["decode_tick_ms.serve_backlog"]["value"] == pytest.approx(15.0)
    assert got["decode_tick_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (tick / 819e9) / 0.015)
    assert got["held_assignment_share"]["value"] == pytest.approx(12.5)
    assert got["expert_load_max_over_mean"]["value"] == pytest.approx(
        35 * 36 / (25 * 36 + 10))
    assert got["sparse_selected_share"]["value"] == pytest.approx(
        100 * 2050 / 9100)
    assert got["prefill_window_fill_share"]["value"] == pytest.approx(
        100 * 90000 / 122880)


def test_the_glm_cell_is_in_the_manifest_after_what_was_there():
    """Found by NAME, after the entries PR 36 left last (a later PR appends
    after these, so nothing here says "last")."""
    m = run.load_json(ROOT / "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("zaya1-8b-pp4-l10")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index("zaya1_longprompt_backlog")
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "longctx_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    assert at > names.index("skip_assignment_share")
    from distributed_compute_pytorch_tpu.obs import tracing
    for p in m["per_layer"][at:at + len(NEW)]:
        assert CELL in p["workloads"] and p["unit"] == "%"
        assert p["moves"] == "serve_tokens_per_s" and p["layer"] == "Models"
        spec = load("layer_metrics", p["name"])
        assert (HERE / "readers" / f"{spec['reader']}.py").exists()
        assert set(spec.get("scope", [])) <= set(tracing.SCOPES)
        assert p["source"] == ("program_counter" if spec["reader"]
                               == "counter_ratio" else "device_trace")
    # (subsets, not equalities: a later cell of these mechanisms lists
    # itself on these metrics, and a later metric may list this cell)
    mine = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])}
    assert mine >= set(NEW) | {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog", "prefill_window_fill_share",
        "decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog", "experts_share.decode",
        "router_share.decode", "experts_share.admit",
        "held_assignment_share", "expert_load_max_over_mean",
        "decode_rows_parked_share.serve_backlog",
        "delivery_gap_p99_ms.serve_backlog",
        "delivery_gap_clear_ms.serve_backlog",
        "delivery_gap_behind_admission_ms.serve_backlog"}
    # not on it, each pinned to its own cell by the benchmark's test of the
    # cell that brought it (PERF.md section 7): ``router_share.admit``
    # (ISSUE 41 asked for it; test_zaya_family.py) and
    # ``latent_absorb_share.decode`` (test_joyai_family.py)
    # a per-layer metric lists a cell only if the metric it moves does too
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    for p in m["per_layer"]:
        for cell in p.get("workloads", []):
            assert cell in e2e[p["moves"]].get("workloads", [cell]), (
                p["name"], cell)
    for entry in m["configs"] + m["workloads"]:
        assert len(entry["why"]) <= 200
        assert len(entry.get("source", "")) <= 200


def test_the_cell_rehearses():
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, timeout=900, capture_output=True, text=True)
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
