"""Family ``joyai_llm_flash`` (JoyAI-LLM-Flash as one chip of eight): its
counts against the integers reckoned in ISSUE 32, both kernels' operations
and bytes, the catalog's widths, the draws of ``longdoc_backlog``, the new
per-layer metrics on a hand-made trace, ``param_spec`` against the
program's tree, and the rehearsal of the new cell."""

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import families, run, trafficgen
from perfbench.family import joyai_llm_flash as fam
from perfbench.reference import joyai_llm_flash_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "joyai_longdoc_backlog"
CONFIG = "joyai-llm-flash-ep8-l5"


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_weights_are_the_reckoned_integers():
    assert fam.attention_params(CFG) == 26345472 == (
        3145728 + 9437184 + 1179648 + 4194304 + 8388608)
    assert fam.attention_params(CFG) + fam.dense_mlp_params(CFG) == 70385664
    assert fam.expert_params(CFG) == 4718592 and fam.router_params(CFG) == 524288
    assert (fam.attention_params(CFG) + fam.router_params(CFG)
            + 33 * fam.expert_params(CFG)) == 182583296
    assert fam.joyai_weight_params(CFG) == 866910208         # 1.73 GB
    assert fam.joyai_matmul_params(CFG) == 866910208 - 16160 * 2048
    assert round(fam.joyai_matmul_params(CFG) / 1e6, 1) == 833.8
    # the same number from the reference's own parameter spec: the matrices
    # in bfloat16, the norm scales and selection biases in float32
    import jax
    from perfbench import weights
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    sizes = jax.tree.map(lambda s, d: (math.prod(s[0]), d), spec, dts,
                         is_leaf=weights._is_leaf)
    leaves = jax.tree.leaves(sizes, is_leaf=lambda x: isinstance(x, tuple))
    assert sum(n for n, d in leaves if d == "bfloat16") == 866910208
    f32 = 5 * (2 * 2048 + 1536 + 512) + 2048 + 4 * 256
    assert sum(n for n, d in leaves if d == "float32") == f32
    held = 2 * 866910208 + 4 * f32
    assert round(held / 1e9, 2) == 1.73


def test_decode_tick_bytes_is_weights_touched_and_latents():
    tick = families.count_fn(CFG, "decode_tick_bytes")
    share = fam.experts_touched_share(CFG, 64)
    assert share == pytest.approx(1 - (1 - 8 / 256) ** 64)
    assert 0.86 < share < 0.88                       # 87% at 64 rows
    weights_read = 2 * (833814528 - (1 - share) * 4 * 32 * 4718592)
    assert tick(CFG, 0) == pytest.approx(weights_read)
    assert 1.50e9 < tick(CFG, 0) < 1.67e9            # never over all weights
    # every layer keeps 576 channels a token: 1152 B at bfloat16
    assert fam.latent_width(CFG) == 576
    assert tick(CFG, 100000) == pytest.approx(
        weights_read + 5 * 100000 * 1152)
    # the cell's pool at its published width: 64 slots x 19456 x 5 layers
    assert round(64 * 19456 * 5 * 1152 / 1e9, 2) == 7.17


def test_both_kernels_counts():
    dec = families.kernel_shape(
        CFG, "decode_latent", {"mean_live_context_tokens": 5e5}, 1)
    assert dec == dict(live_context_tokens=5e5, q_heads=32,
                       latent_width=576, value_width=512, itemsize=2)
    fl = families.count_fn(CFG, "latent_decode_attn_flops")(**dec)
    by = families.count_fn(CFG, "latent_decode_attn_bytes")(**dec)
    assert fl == 2 * 5e5 * 32 * (576 + 512) and by == 5e5 * 1152
    assert 60 < fl / by < 61              # flops a byte: a quarter of 240
    # admission: the run's mean rows a dispatch over the mean window a row,
    # never a fixed window
    adm = families.kernel_shape(CFG, "admit_latent", {
        "prefill_calls": 10, "prefill_rows": 15,
        "prefill_window_tokens": 10 * 1.5 * 6144}, 1)
    assert adm == dict(rows=1.5, q_heads=32, q_len=6144.0, qk_head_dim=192,
                       v_head_dim=128, itemsize=2)
    fl = families.count_fn(CFG, "latent_flash_fwd_flops")(**adm)
    assert fl == 2 * 1.5 * 32 * (6144 * 6145 / 2) * (192 + 128)
    by = families.count_fn(CFG, "latent_flash_fwd_bytes")(**adm)
    assert by == 1.5 * 6144 * 32 * 2 * (192 + 192 + 128 + 128)
    # a dispatch of one short row is counted at its own window
    one = families.kernel_shape(CFG, "admit_latent", {
        "prefill_calls": 4, "prefill_rows": 3,
        "prefill_window_tokens": 4 * 2048}, 1)
    assert one["rows"] == 1.0 and one["q_len"] == 2048
    assert families.kernel_shape(CFG, "admit_latent", {}, 1) is None
    assert families.kernel_shape(CFG, "decode_latent", {}, 1) is None
    assert families.kernel_shape(CFG, "decode", {}, 1) is None


def test_no_width_differs_from_the_catalogs_row():
    """Every number of the published config is in the file under its key;
    what differs is named in ``reduced`` and is no width."""
    published = {
        "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "topk_group": 1, "v_head_dim": 128, "vocab_size": 129280}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"} == set(CFG["reduced"])
    for k in differs:
        assert CFG["published"][k] == published[k]
    assert CFG["rope_interleave"] is True and CFG["rope_scaling"] is None
    assert CFG["attention_bias"] is False and CFG["norm_topk_prob"] is True
    assert CFG["scoring_func"] == "sigmoid"
    assert CFG["topk_method"] == "noaux_tc"
    assert CFG["router_num_experts"] == 256
    assert CFG["experts_held"] == [0, CFG["n_routed_experts"]]
    assert CFG["vocab_size"] * CFG["deployment_chips"] == 129280
    assert CFG["n_routed_experts"] * CFG["deployment_chips"] == 256
    assert CFG["num_hidden_layers"] == CFG["first_k_dense_replace"] + 4
    cell = load("workloads", CELL)
    assert cell["run"]["slots"] == CFG["serving"]["slots"] == 64
    assert cell["run"] == {"param_dtype": "bfloat16", "kv_dtype": "bf16",
                           "slots": 64, "t_max": 19456, "prompt_buf": 16384,
                           "warm_waves": 8}
    for why in ("rope_order", "softmax_scale", "router_bias",
                "norm_placement", "initializer_range"):
        assert why in CFG["assumed"]
    m = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert entry["source"] == CFG["source"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_selection_bias_decides_near_ties_and_leaves_the_load_even(seed):
    """Every seed is to do the same work: over the published router (256
    wide, top-8, 32 held) and scores drawn as the weights are, a bias of
    ``BIAS_STD`` keeps the held experts' share of the assignments within a
    point and a half of an eighth (11.7-13.9% over eight seeds here, 7-20%
    at 0.1), and still changes which experts a token picks for more than a
    tenth of the tokens (for six in ten of them)."""
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    d, E, n = 256, CFG["router_num_experts"], 8192
    k = CFG["num_experts_per_tok"]
    y = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    # logits of the published scale: 0.02 * sqrt(2048) a unit of y
    kernel = jnp.asarray(rng.standard_normal((d, E)) * 0.02
                         * math.sqrt(2048 / d), jnp.float32)
    spec = ref.layer_spec(CFG, CFG["first_k_dense_replace"])["moe"]
    assert spec["router_bias"] == ((E,), ref.BIAS_STD) and ref.BIAS_STD == 0.01
    pick = lambda b: np.asarray(ref.route(
        y, {"router": {"kernel": kernel},
            "router_bias": jnp.asarray(b, jnp.float32)}, k, 2.5, True)[0])
    with_b = pick(rng.standard_normal(E) * ref.BIAS_STD)
    without = pick(np.zeros(E))
    first, count = CFG["experts_held"]
    share = np.mean((with_b >= first) & (with_b < first + count))
    assert abs(share - count / E) < 0.015
    moved = np.any(np.sort(with_b, -1) != np.sort(without, -1), -1)
    assert np.mean(moved) > 0.1


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
    assert [model.layer_block(i).cache_kind for i in range(5)] == ["latent"] * 5


def test_longdoc_backlog_draws():
    t = load("traffic", "longdoc_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == 8 * 51
    t_max = load("workloads", CELL)["run"]["t_max"]
    for r in a:
        assert 1024 <= len(r["tokens"]) <= 16384
        assert 512 <= r["max_new"] <= 3072
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        assert len(r["tokens"]) + r["max_new"] <= t_max
    # the issue's ramp: 64 requests 0.1 s apart, the rest due when it ends
    assert t["ramp"] == {"requests": 64, "gap_s": 0.1}
    due = [r["arrival_s"] for r in a]
    assert due[:64] == pytest.approx([0.1 * j for j in range(64)])
    assert due[64:] == pytest.approx([6.4] * (len(a) - 64))
    # 48 pairs offered over and over, each pass in its own order
    pairs = [(len(r["tokens"]), r["max_new"]) for r in a]
    assert len(set(pairs)) <= 48 == t["cycle"]
    assert sorted(pairs[:48]) == sorted(pairs[48:96]) != pairs[48:96]
    assert t["shape_seed"] not in {
        load("traffic", n)["shape_seed"]
        for n in ("chat_backlog", "chat_steady", "reason_backlog")}
    # prefill leads by tokens, decode by ticks
    assert sum(len(r["tokens"]) for r in a) > 3 * sum(r["max_new"] for r in a)


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
        "expert_load_max_over_mean", "latent_attn_roofline_share.decode",
        "flash_fwd_roofline_share.admit", "prefill_window_fill_share")
env.manifest["per_layer"] = [m for m in env.manifest["per_layer"]
                             if m["name"] in WANT]
class Trace:
    def module_time_s(self, pattern, trim_edges=False): return 0.32, 2.0
    def op_time_s(self, pattern): return 0.01
    def op_count(self, pattern): return 5.0
counters = {{"segment": 16, "mean_live_context_tokens": 500000.0,
            "prefill_calls": 10, "prefill_rows": 15, "prefill_tokens": 80000,
            "prefill_window_tokens": 92160,
            "expert_assignments": 8000, "expert_assignments_held": 1000,
            **{{f"expert_load_{{e}}": 25 + 5 * (e == 3) for e in range(32)}}}}
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
    assert len(got) == 7
    tick = fam.decode_tick_bytes(CFG, 500000.0)
    assert got["decode_tick_ms.serve_backlog"]["value"] == pytest.approx(10.0)
    assert got["decode_tick_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (tick / 819e9) / 0.01)
    assert got["held_assignment_share"]["value"] == pytest.approx(12.5)
    assert got["expert_load_max_over_mean"]["value"] == pytest.approx(
        30 * 32 / (25 * 32 + 5))
    # the latent read: memory bound (60 flops a byte against a ridge of 240)
    by = 5 * 500000.0 * 1152
    assert got["latent_attn_roofline_share.decode"][
        "value"] == pytest.approx(100 * (by / 819e9) / 0.01)
    # the prefill: compute bound
    fl = 5 * fam.latent_flash_fwd_flops(1.5, 32, 6144.0, 192, 128)
    assert got["flash_fwd_roofline_share.admit"][
        "value"] == pytest.approx(100 * (fl / 197e12) / 0.01)


def test_the_joyai_cell_is_in_the_manifest_after_what_was_there():
    """Found by NAME, after the entries PR 28 left last (a later PR appends
    after these, so nothing here says "last")."""
    m = run.load_json(ROOT / "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("k-exaone-236b-a23b-ep8-l5")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index("kexaone_reason_backlog")
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "longdoc_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    new = ["attn_latent_share.decode", "latent_absorb_share.decode",
           "attn_latent_share.admit", "latent_attn_roofline_share.decode",
           "flash_fwd_roofline_share.admit"]
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(new[0])
    assert names[at:at + 5] == new
    assert at > names.index("prefill_window_fill_share")
    for p in m["per_layer"][at:at + 5]:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_tokens_per_s"
        assert (HERE / "layer_metrics" / f"{p['name']}.json").exists()
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert set(mine) == set(new) | {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog", "prefill_window_fill_share",
        "decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog", "experts_share.decode",
        "router_share.decode", "experts_share.admit",
        "held_assignment_share", "expert_load_max_over_mean"}
    # a per-layer metric lists a cell only if the metric it moves does too
    e2e = {e["name"]: e for e in m["end_to_end"]}
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


def test_the_reference_reports_a_requests_mean_gap():
    """``served_token_gaps`` gives every served token its request's mean
    gap (``raw_token_gaps`` has each token's own); the reference's own
    greedy continuation has no gap at all, and other tokens have."""
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
    share = ref.near_tie_share(params, jnp.asarray(prompt + other), tiny,
                               margin=1e-2)
    assert 0.0 <= share <= 1.0
