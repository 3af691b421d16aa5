"""Family ``zaya`` (ZAYA1-8B as one stage of a four-stage pipeline): its
counts against the integers reckoned in ISSUE 36 (weights, pool, tail, the
decode tick's bytes, the flash call's operations and bytes at a given
window), the catalog's widths, the draws of ``longprompt_backlog``, the
new per-layer metrics on hand-made counters, ``param_spec`` against the
program's tree, and the rehearsal of the new cell."""

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import families, run, trafficgen
from perfbench.family import zaya as fam
from perfbench.reference import zaya_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "zaya1_longprompt_backlog"
CONFIG = "zaya1-8b-pp4-l10"


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_weights_are_the_reckoned_integers():
    assert fam.attention_params(CFG) == 5242880 == (
        2097152 + 524288 + 2 * 262144 + 2097152)
    assert fam.conv_params(CFG) == 2560 + 1280 + 327680 + 1280 + 2
    assert fam.router_params(CFG) == (524544 + 256 + 256 + 131584 + 4352
                                      + 17) == 661009
    assert fam.vector_params(CFG) == 20480
    assert fam.expert_params(CFG) == 12582912
    assert fam.layer_params(CFG) == 207583763            # 415 MB
    assert fam.zaya_weight_params(CFG) == (
        10 * 207583763 + 262272 * 2048 + 3 * 2048) == 2612976830
    assert round(2 * fam.zaya_weight_params(CFG) / 1e9, 2) == 5.23
    # the same number from the reference's own parameter spec: the
    # matrices in bfloat16; norm scales, the selection bias and the key
    # temperature in float32
    import jax
    from perfbench import weights
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    sizes = jax.tree.map(lambda s, d: (math.prod(s[0]), d), spec, dts,
                         is_leaf=weights._is_leaf)
    leaves = jax.tree.leaves(sizes, is_leaf=lambda x: isinstance(x, tuple))
    f32 = 10 * (2 * 2048 + 256 + 17 + 2) + 2048
    assert sum(n for n, d in leaves if d == "float32") == f32
    assert sum(n for n, d in leaves if d == "bfloat16") == 2612976830 - f32
    assert "lm_head" not in spec                 # tied: held once


def test_pool_tail_and_decode_tick_bytes():
    cell = load("workloads", CELL)["run"]
    assert fam.kv_bytes_per_token(CFG) == 1024
    assert fam.tail_width(CFG) == 2688 and fam.tail_bytes_per_slot(CFG) == 5376
    pool = cell["slots"] * cell["t_max"] * 10 * fam.kv_bytes_per_token(CFG)
    assert pool == 6815744000                            # 6.82 GB
    assert cell["slots"] * 10 * fam.tail_bytes_per_slot(CFG) == 1075200
    resident = 2 * fam.zaya_weight_params(CFG) + pool
    assert 0.76 < resident / 15.75e9 < 0.77              # 12.0 GB of 15.75
    tick = families.count_fn(CFG, "decode_tick_bytes")
    share = fam.experts_touched_share(CFG, 20)
    assert share == pytest.approx(1 - (16 / 17) ** 20)
    assert 0.70 < share < 0.71                           # 70% at 20 rows
    read = 2 * (10 * (207583763 - (1 - share) * 16 * 12582912)
                + 262272 * 2048) + 2 * 10 * 20 * 5376
    assert tick(CFG, 0) == pytest.approx(read)
    assert tick(CFG, 0) < 2 * fam.zaya_weight_params(CFG)   # never over all
    assert tick(CFG, 274000) == pytest.approx(read + 10 * 274000 * 1024)


def test_both_kernels_counts():
    dec = families.kernel_shape(
        CFG, "decode", {"mean_live_context_tokens": 2.7e5}, 1)
    assert dec == dict(live_context_tokens=2.7e5, q_heads=8, kv_heads=2,
                       head_dim=128, itemsize=2)
    fl = families.count_fn(CFG, "paged_decode_attn_flops")(**dec)
    by = families.count_fn(CFG, "paged_decode_attn_bytes")(**dec)
    assert fl == 4 * 2.7e5 * 8 * 128 and by == 2.7e5 * 1024
    # admission: the run's mean rows a dispatch over the mean window a row,
    # never a fixed window; the two key heads are repeated to eight
    adm = families.kernel_shape(CFG, "admit_cca", {
        "prefill_calls": 10, "prefill_rows": 15,
        "prefill_window_tokens": 10 * 1.5 * 16384}, 1)
    assert adm == dict(batch_heads=12.0, q_len=16384.0, kv_len=16384.0,
                       head_dim=128, causal=True)
    fl = families.count_fn(CFG, "flash_fwd_flops")(**adm)
    assert fl == 4 * 12 * (16384 * 16385 / 2) * 128
    by = families.count_fn(CFG, "flash_fwd_bytes")(**adm)
    assert by == 12 * 128 * 2 * 4 * 16384
    assert fl / by > 240                     # compute bound on a v5e
    one = families.kernel_shape(CFG, "admit_cca", {
        "prefill_calls": 4, "prefill_rows": 3,
        "prefill_window_tokens": 4 * 32768}, 1)
    assert one["batch_heads"] == 8.0 and one["q_len"] == 32768
    assert families.kernel_shape(CFG, "admit_cca", {}, 1) is None
    assert families.kernel_shape(CFG, "decode", {}, 1) is None
    assert families.kernel_shape(CFG, "decode_latent", {"x": 1}, 1) is None


def test_no_width_differs_from_the_catalogs_row():
    """Every number of the published config is in the file under its key;
    what differs is named in ``reduced`` and is no width."""
    published = {
        "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
        "hidden_size": 2048, "max_position_embeddings": 131072,
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "router_hidden_size": 256, "vocab_size": 262272}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"num_hidden_layers"} == set(CFG["reduced"])
    assert CFG["published"]["num_hidden_layers"] == 40
    # the nested group is copied whole; the family builds
    # num_hidden_layers layers of its one kind
    assert CFG["num_hidden_layers"] == 10
    assert CFG["layer_types"] == ["hybrid"] * 40
    assert CFG["rope_parameters"] == {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"}
    assert CFG["rope_theta"] == CFG["rope_parameters"]["hybrid"]["rope_theta"]
    assert CFG["attention_bias"] is False and CFG["lm_head_bias"] is False
    assert CFG["tie_word_embeddings"] is True and CFG["sliding_window"] is None
    assert CFG["model_type"] == "zaya" and CFG["hidden_act"] == "silu"
    assert CFG["num_hidden_layers"] * CFG["pipeline_stages"] == 40
    cell = load("workloads", CELL)
    assert cell["run"] == {"param_dtype": "bfloat16", "kv_dtype": "bf16",
                           "slots": 20, "t_max": 33280, "prompt_buf": 32768,
                           "warm_waves": 4}
    assert cell["run"]["slots"] == CFG["serving"]["slots"]
    assert cell["run"]["t_max"] < 2 * cell["run"]["prompt_buf"]
    for why in ("residual_merge", "scale_parameterisation", "conv_padding",
                "qk_mean", "key_temperature", "rotation", "value_shift",
                "router", "router_bias", "router_draw", "merge_draw",
                "stream_draw", "initializer_range"):
        assert why in CFG["assumed"]
    m = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CFG["source"]
    kw = fam.model_kwargs(CFG, {"max_seq_len": 33280})
    assert (kw["num_experts"], kw["experts_held"], kw["top_k"],
            kw["shared_d_ff"]) == (17, (0, 16), 1, 0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_routers_draw_leaves_the_skip_share_near_a_seventeenth(seed):
    """Every seed is to do about the same work: over the published router
    (2048 -> 256 -> 17) and inputs drawn as normed activations are, the
    skip choice takes 3-9% (a seventeenth is 5.9%) and no expert more than
    a fifth, with a state handed up from the layer below."""
    import jax
    import numpy as np
    from perfbench import weights
    p = weights.make_params(
        {k: v for k, v in ref.layer_spec(CFG)["moe"].items()
         if k != "experts"}, seed, "float32")
    y = jax.random.normal(jax.random.key(seed), (4096, 2048))
    below = jax.random.normal(jax.random.key(seed + 9), (4096, 256))
    probs, rs = ref.router_probs(y, below, p, CFG)
    assert probs.shape == (4096, 17) and rs.shape == (4096, 256)
    pick = np.asarray((probs + p["router_bias"]).argmax(-1))
    share = np.bincount(pick, minlength=17) / 4096
    assert 0.03 < share[16] < 0.09
    assert share.max() < 0.2


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
    assert [model.layer_block(i).cache_kind
            for i in range(3)] == ["paged+tail"] * 3
    assert model.tail_width == fam.tail_width(tiny)


def test_longprompt_backlog_draws():
    t = load("traffic", "longprompt_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == 10 * 51
    cell = load("workloads", CELL)["run"]
    for r in a:
        assert 4096 <= len(r["tokens"]) <= 32768 <= cell["prompt_buf"]
        assert 32 <= r["max_new"] <= 384
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        assert len(r["tokens"]) + -(-r["max_new"] // 16) * 16 <= cell["t_max"]
    # the issue's ramp: 20 requests 0.1 s apart, the rest due when it ends
    assert t["ramp"] == {"requests": 20, "gap_s": 0.1}
    due = [r["arrival_s"] for r in a]
    assert due[:20] == pytest.approx([0.1 * j for j in range(20)])
    assert due[20:] == pytest.approx([2.0] * (len(a) - 20))
    # 48 pairs offered over and over, each pass in its own order
    pairs = [(len(r["tokens"]), r["max_new"]) for r in a]
    assert len(set(pairs)) <= 48 == t["cycle"]
    assert sorted(pairs[:48]) == sorted(pairs[48:96]) != pairs[48:96]
    assert t["shape_seed"] not in {
        load("traffic", n)["shape_seed"]
        for n in ("chat_backlog", "chat_steady", "reason_backlog",
                  "longdoc_backlog")}
    # admission leads: a hundred prompt tokens to every token served
    assert sum(len(r["tokens"]) for r in a) > 50 * sum(r["max_new"] for r in a)
    # the ladder's four windows are all drawn on
    rungs = {next(w for w in (4096, 8192, 16384, 32768) if w >= n - 1)
             for n, _ in pairs[:48]}
    assert rungs == {4096, 8192, 16384, 32768}


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
        "skip_assignment_share", "expert_load_max_over_mean",
        "paged_attn_roofline_share.serve_backlog",
        "flash_fwd_roofline_share.admit_cca", "prefill_window_fill_share")
env.manifest["per_layer"] = [m for m in env.manifest["per_layer"]
                             if m["name"] in WANT]
class Trace:
    def module_time_s(self, pattern, trim_edges=False): return 0.32, 2.0
    def op_time_s(self, pattern): return 0.05
    def op_count(self, pattern): return 5.0
counters = {{"segment": 16, "mean_live_context_tokens": 270000.0,
            "prefill_calls": 10, "prefill_rows": 15, "prefill_tokens": 200000,
            "prefill_window_tokens": 245760,
            "expert_assignments": 8000, "expert_assignments_held": 7520,
            "expert_assignments_skipped": 480,
            **{{f"expert_load_{{e}}": 400 + 70 * (e == 3) for e in range(16)}}}}
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
    assert len(got) == 8
    tick = fam.decode_tick_bytes(CFG, 270000.0)
    assert got["decode_tick_ms.serve_backlog"]["value"] == pytest.approx(10.0)
    assert got["decode_tick_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (tick / 819e9) / 0.01)
    assert got["held_assignment_share"]["value"] == pytest.approx(94.0)
    assert got["skip_assignment_share"]["value"] == pytest.approx(6.0)
    assert got["expert_load_max_over_mean"]["value"] == pytest.approx(
        470 * 16 / (400 * 16 + 70))
    assert got["prefill_window_fill_share"]["value"] == pytest.approx(
        100 * 200000 / 245760)
    # the pool read: memory bound
    by = 5 * 270000.0 * 1024
    assert got["paged_attn_roofline_share.serve_backlog"][
        "value"] == pytest.approx(100 * (by / 819e9) / 0.05)
    # the prefill: compute bound, and it says so
    fl = 5 * 4 * 12 * (16384 * 16385 / 2) * 128
    assert got["flash_fwd_roofline_share.admit_cca"][
        "value"] == pytest.approx(100 * (fl / 197e12) / 0.05)
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("LAYER flash_fwd_roofline_share.admit_cca"))
    assert "compute bound" in line


def test_the_zaya_cell_is_in_the_manifest_after_what_was_there():
    """Found by NAME, after the entries PR 32 left last (a later PR appends
    after these, so nothing here says "last")."""
    m = run.load_json(ROOT / "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("joyai-llm-flash-ep8-l5")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index("joyai_longdoc_backlog")
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "longprompt_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    new = ["attn_cca_share.admit", "cca_mix_share.admit",
           "attn_cca_share.decode", "cca_mix_share.decode",
           "router_share.admit", "flash_fwd_roofline_share.admit_cca",
           "skip_assignment_share"]
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(new[0])
    assert names[at:at + 7] == new
    assert at > names.index("flash_fwd_roofline_share.admit")
    for p in m["per_layer"][at:at + 7]:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_tokens_per_s"
        spec = load("layer_metrics", p["name"])
        assert (HERE / "readers" / f"{spec['reader']}.py").exists()
    mine = [p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert set(mine) == set(new) | {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog", "prefill_window_fill_share",
        "decode_tick_ms.serve_backlog",
        "decode_tick_roofline_share.serve_backlog", "experts_share.decode",
        "router_share.decode", "experts_share.admit",
        "held_assignment_share", "expert_load_max_over_mean",
        "paged_attn_roofline_share.serve_backlog"}
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
    """``forward(rows=)`` gives the logits of those positions only (the
    whole vocabulary at every position of a 32k request would not fit);
    ``served_token_gaps`` gives every served token its request's mean gap;
    the reference's own greedy continuation has no gap at all."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench import weights
    tiny = run.overlay(CFG, CFG["rehearse"])
    params = weights.make_params(ref.param_spec(tiny), 3,
                                 ref.param_dtypes(tiny, "bfloat16"))
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, tiny["vocab_size"], 9)]
    whole = ref.forward(params, jnp.asarray(prompt), tiny)
    part = ref.forward(params, jnp.asarray(prompt), tiny, rows=(3, 4))
    assert part.shape == (4, tiny["vocab_size"])
    assert float(jnp.max(jnp.abs(part - whole[3:7]))) == 0.0
    served = []
    for _ in range(8):           # the float32 reference's greedy tokens
        logits = ref.forward(params, jnp.asarray(prompt + served), tiny)
        served.append(int(jnp.argmax(logits[-1])))
    raw = ref.raw_token_gaps(params, prompt, served, tiny, pad_to=8,
                             control=("int8",))
    assert len(raw["served"]) == 8 and float(raw["served"].max()) == 0.0
    other = [int(t) for t in rng.integers(1, tiny["vocab_size"], 8)]
    raw = ref.raw_token_gaps(params, prompt, other, tiny, pad_to=8,
                             control=("int8",))
    got = ref.served_token_gaps(params, prompt, other, tiny, pad_to=8,
                                control=("int8",))
    assert raw["served"].min() >= 0 and raw["served"].max() > 0
    for k in ("served", "int8"):
        assert got[k] == [pytest.approx(float(raw[k].mean()))] * 8
    share = ref.near_tie_share(params, jnp.asarray(prompt + other), tiny,
                               margin=1e-2)
    assert 0.0 <= share["skip"] <= share["any"] <= 1.0
