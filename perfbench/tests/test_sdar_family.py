"""Family ``sdar_moe`` (SDAR-30B-A3B-Chat as a pipeline stage of seven of its
48 layers): the family as files, its counts against the integers reckoned in
ISSUE 48 and against the parameter tree's own bytes (the 9.97 GB), the pool
and the resident bytes of the cell, a PASS's floor and the call shape of the
pool's kernel with a block's queries, the catalog's keys, the draws of
``blockgen_backlog``, the new per-layer metrics on hand-made counters and
traces (a program without the counters or the program, as the parent, reads
nothing), ``param_spec`` against the program's tree, the reference's noised
copies against its own generation loop, and the rehearsal of the new cell."""

import json
import math
import os
import pathlib
import subprocess
import sys

import pytest

from perfbench import bytes as nbytes
from perfbench import families, flops, run, trafficgen
from perfbench.family import sdar_moe as fam
from perfbench.readers import counter_ratio, trace_module_time
from perfbench.reference import sdar_moe_ref as ref

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent
CELL = "sdar_blockgen_backlog"
SOLAR = "solaropen2_longgen_backlog"
CONFIG = "sdar-30b-a3b-chat-l7"
NEW = ["block_pass_ms", "block_pass_roofline_share", "attn_share.block",
       "experts_share.block", "router_share.block", "unmask_share.block",
       "tokens_per_row_pass", "commit_pass_share",
       "paged_attn_roofline_share.block"]


def load(kind, name):
    return json.load(open(HERE / kind / f"{name}.json"))


CFG = load("configs", CONFIG)


def test_the_family_is_files_found_by_name():
    assert families.family(CFG) is fam
    assert families.reference_module(CFG) is ref
    assert (fam.BUILD_MODEL, fam.DROPOUT_KEYS) == ("hybrid", ())
    assert families.without_dropout(CFG) == CFG
    assert families.count_fn(CFG, "decode_tick_bytes") is fam.decode_tick_bytes
    # the kernel's counts are the kernel's, not the family's
    assert families.count_fn(CFG, "paged_decode_attn_bytes") is (
        nbytes.paged_decode_attn_bytes)
    assert families.count_fn(CFG, "paged_decode_attn_flops") is (
        flops.paged_decode_attn_flops)
    with pytest.raises(LookupError):
        families.family({"family": "sdar_dense"})


def test_weights_are_the_reckoned_integers():
    assert fam.expert_params(CFG) == 4718592 == 3 * 2048 * 768
    assert 128 * fam.expert_params(CFG) == 603979776
    # q 2048 x 4096, k and v 2048 x 512 each, o 4096 x 2048; router 2048 x 128
    assert fam.attn_params(CFG) + fam.router_params(CFG) == 19136512 == (
        2 * 8388608 + 2 * 1048576 + 262144)
    layer = 603979776 + 19136512
    assert layer == 623116288
    assert fam.sdar_weight_params(CFG) == (
        7 * layer + 2 * 151936 * 2048) == 4984143872
    assert round(2 * fam.sdar_weight_params(CFG) / 1e9, 2) == 9.97
    # the same number from the reference's own parameter spec: the matrices
    # in bfloat16, the norm scales (4,352 a layer, 2,048 at the end) float32
    import jax
    from perfbench import weights
    spec, dts = ref.param_spec(CFG), ref.param_dtypes(CFG, "bfloat16")
    sizes = jax.tree.map(lambda s, d: (math.prod(s[0]), d), spec, dts,
                         is_leaf=weights._is_leaf)
    leaves = jax.tree.leaves(sizes, is_leaf=lambda x: isinstance(x, tuple))
    f32 = sum(n for n, d in leaves if d == "float32")
    assert f32 == 7 * (2 * 128 + 2 * 2048) + 2048 == 32512
    assert sum(n for n, d in leaves if d == "bfloat16") == 4984143872
    assert CFG["bytes"]["parameters"] == 4984143872 + 32512
    assert CFG["bytes"]["weights"] == 2 * 4984143872 + 4 * 32512 == 9968417792


def test_pool_resident_and_a_passes_bytes():
    cell = load("workloads", CELL)["run"]
    assert fam.kv_bytes_per_token(CFG) == 2 * 4 * 128 * 2 == 2048
    assert CFG["bytes"]["pool_per_cached_token"] == 7 * 2048 == 14336
    # blocks of 8 tokens: 336 a row, 96 rows and the trash block
    blocks = cell["slots"] * (cell["t_max"] // 8) + 1
    assert blocks == 32257
    assert CFG["bytes"]["pool_at_96_slots"] == blocks * 8 * 14336 == 3699490816
    assert CFG["bytes"]["resident_at_96_slots"] == (
        CFG["bytes"]["weights"] + 3699490816) == 13667908608
    assert 12e9 < CFG["bytes"]["resident_at_96_slots"] < 15.75e9
    assert cell["slots"] == CFG["serving"]["slots"] == 96
    # a pass: every matrix but the embedding once, the live K/V once
    assert fam.sdar_matmul_params(CFG) == 4984143872 - 151936 * 2048
    assert fam.decode_tick_bytes(CFG, 0.0) == 2 * 4672978944 == 9345957888
    assert fam.decode_tick_bytes(CFG, 86400.0) == (
        9345957888 + 86400 * 14336)
    # the pool's kernel: a block's 4 positions of 32 heads a slot, one read
    shape = fam.kernel_shapes(CFG, "decode",
                              {"mean_live_context_tokens": 86400.0}, 1)
    assert shape == dict(live_context_tokens=86400.0, q_heads=128,
                         kv_heads=4, head_dim=128, itemsize=2)
    assert nbytes.paged_decode_attn_bytes(**shape) == 86400 * 2048
    assert flops.paged_decode_attn_flops(**shape) == 4 * 86400 * 128 * 128
    assert fam.kernel_shapes(CFG, "decode", {}, 1) is None
    assert fam.kernel_shapes(CFG, "admit", {"mean_live_context_tokens": 1}, 1
                             ) is None


def test_no_key_differs_from_the_catalogs_row_but_the_depth():
    """Every key of the published config is in the file under its name;
    what differs is ``num_hidden_layers`` alone, named in ``reduced``."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = {k for k, v in published.items() if CFG[k] != v}
    assert differs == {"num_hidden_layers"} == set(CFG["reduced"])
    m = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert CFG["num_hidden_layers"] == 7
    assert (CFG["experts_held"], CFG["deployment_chips"]) == ([0, 128], 1)
    assert CFG["published"]["num_hidden_layers"] == 48
    assert CFG["generation"] == {
        "block_length": 4, "denoising_steps": 2, "remasking": "sequential",
        "mask_token_id": 151669}
    for why in ("block_length", "generation", "block_mask", "mask_token_id",
                "attention", "experts", "norm_placement",
                "initializer_range", "stream_draw", "published_code"):
        assert why in CFG["assumed"]
    for key in ("source", "assumed", "deployment", "published", "rehearse"):
        assert CFG[key]
    cell = load("workloads", CELL)["run"]
    kw = fam.model_kwargs(CFG, dict(cell, max_seq_len=cell["t_max"]))
    assert kw["layer_types"] == ("full_attention",) * 7
    assert kw["mlp_layer_types"] == ("sparse",) * 7
    assert (kw["num_experts"], kw["experts_held"], kw["top_k"],
            kw["shared_d_ff"], kw["moe_d_ff"], kw["router"]) == (
                128, (0, 128), 8, 0, 768, "softmax")
    assert (kw["qk_norm"], kw["rope_sliding_only"], kw["rope_theta"],
            kw["norm_placement"]) == (True, False, 1e6, "pre")
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"],
            kw["d_model"], kw["vocab_size"]) == (32, 4, 128, 2048, 151936)
    assert (kw["block_length"], kw["denoising_steps"], kw["remasking"],
            kw["mask_token_id"]) == (4, 2, "sequential", 151669)
    # a cell's run repeats the configuration's generation, never contradicts
    with pytest.raises(ValueError, match="the reference reads the latter"):
        fam.model_kwargs(CFG, dict(cell, max_seq_len=64, denoising_steps=4))


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
    assert [model.layer_block(i).cache_kind for i in range(2)] == [
        "paged", "paged"]
    assert model.block_generation == (4, 2, "sequential", 500)


def test_blockgen_backlog_draws():
    t = load("traffic", "blockgen_backlog")
    a = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    b = trafficgen.requests(t, 51.0, 2**31 + 5, CFG["vocab_size"])
    assert a == b and len(a) == math.ceil(
        t["requests_per_second_offered"] * 51)
    cell = load("workloads", CELL)["run"]
    for r in a:
        assert 64 <= len(r["tokens"]) <= 1536 == cell["prompt_buf"]
        assert 128 <= r["max_new"] <= 1024
        assert all(1 <= x < CFG["vocab_size"] for x in r["tokens"])
        # whole blocks from the block the prompt's tail opens
        assert len(r["tokens"]) + r["max_new"] + 4 <= cell["t_max"]
    # the issue's ramp: 96 requests 0.05 s apart, the rest due when it ends
    assert t["ramp"] == {"requests": 96, "gap_s": 0.05}
    due = [r["arrival_s"] for r in a]
    assert due[:96] == pytest.approx([0.05 * j for j in range(96)])
    assert due[96:] == pytest.approx([4.8] * (len(a) - 96))
    pairs = [(len(r["tokens"]), r["max_new"]) for r in a]
    assert len(set(pairs)) <= 48 == t["cycle"]
    assert sorted(pairs[:48]) == sorted(pairs[48:96]) != pairs[48:96]
    assert t["shape_seed"] not in {
        load("traffic", n)["shape_seed"]
        for n in ("chat_backlog", "chat_steady", "reason_backlog",
                  "longdoc_backlog", "longprompt_backlog",
                  "longctx_backlog", "longgen_backlog")}
    assert (t["kind"], t["sampling"], t["shared_prefix"]) == (
        "backlog", "greedy", False)
    assert t["prompt_tokens"] == {"median": 512, "sigma": 0.7, "lo": 64,
                                  "hi": 1536}
    assert t["output_tokens"] == {"median": 512, "sigma": 0.5, "lo": 128,
                                  "hi": 1024}
    # outputs are NOT rounded to blocks, and prompts end anywhere in one
    assert {n % 4 for n, _ in pairs} == {m % 4 for _, m in pairs} == {
        0, 1, 2, 3}
    # every window of the admission ladder is drawn on (whole blocks of the
    # prompt are what a wave prefills)
    rungs = [next(w for w in (192, 384, 768, 1536) if w >= n // 4 * 4)
             for n, _ in pairs[:48]]
    assert [rungs.count(w) for w in (192, 384, 768, 1536)] == [6, 7, 21, 14]


# ---- the new metrics, on hand-made counters and traces ---------------------

def test_the_counter_metrics_are_ratios_of_the_block_counters():
    # 3 passes a block of 4 tokens: two denoise, one commit
    counters = {"tokens_emitted": 4000.0, "block_row_passes": 3000.0,
                "commit_row_passes": 1000.0, "denoise_row_passes": 2000.0}
    ctx = {"counters": counters}
    assert counter_ratio.read(load("layer_metrics", "tokens_per_row_pass"),
                              ctx) == pytest.approx(4 / 3)
    assert counter_ratio.read(load("layer_metrics", "commit_pass_share"),
                              ctx) == pytest.approx(100 / 3)
    # a program without the counters (the parent, a causal model)
    for name in ("tokens_per_row_pass", "commit_pass_share"):
        assert counter_ratio.read(load("layer_metrics", name), {
            "counters": {"tokens_emitted": 4000.0}}) is None


class _Trace:
    """Two block segments of 16 passes, 0.4 s together, and an admission
    dispatch beside them, which no block metric reads."""

    def module_time_s(self, pattern, trim_edges=False):
        import re
        names = {"jit__block_segment_impl(7)": (0.4, 2.0),
                 "jit__admit_impl(3)": (0.1, 1.0)}
        hit = [v for n, v in names.items() if re.search(pattern, n)]
        return (sum(s for s, _ in hit), sum(r for _, r in hit))


def test_a_passes_time_is_the_block_segments_over_its_passes():
    spec = load("layer_metrics", "block_pass_ms")
    assert spec["module"] == "_block_segment_impl"
    got = trace_module_time.read(spec, {"trace": _Trace(),
                                        "counters": {"segment": 16}})
    assert got == pytest.approx(1e3 * 0.4 / 32)
    assert trace_module_time.read(spec, {"trace": None, "counters": {}}) is None
    # the tick segment's own metrics match the block program's name too
    # (``_segment_impl`` is a part of it): no other cell compiles it, and
    # this cell is on no list of theirs
    m = run.load_json(ROOT / "BENCHMARK.json")
    for p in m["per_layer"]:
        spec = load("layer_metrics", p["name"])
        if "_segment_impl" in (spec.get("module", ""),
                               spec.get("of_module", "")):
            assert CELL not in p["workloads"], p["name"]


def test_the_cell_is_in_the_manifest_after_what_was_there():
    m = run.load_json(ROOT / "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("solar-open2-250b-ep8-l4")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index(SOLAR)
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "blockgen_backlog",
        "chips": 1, "why": load("workloads", CELL)["why"]}
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    assert at > names.index("held_experts_kernel_share.decode")
    from distributed_compute_pytorch_tpu.obs import tracing
    assert {"block_pass", "unmask"} <= set(tracing.SCOPES)
    for p in m["per_layer"][at:at + len(NEW)]:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "serve_tokens_per_s"
        spec = load("layer_metrics", p["name"])
        assert (HERE / "readers" / f"{spec['reader']}.py").exists()
        assert set(spec.get("scope", [])) <= set(tracing.SCOPES)
        assert p["source"] == ("program_counter" if spec["reader"]
                               == "counter_ratio" else "device_trace")
        if "roofline" in p["name"]:
            assert p["unit"] == "%"
    mine = {p["name"] for p in m["per_layer"] if CELL in p.get("workloads", [])}
    assert mine >= set(NEW) | {
        "slot_tick_yield", "serve_tokens_per_s_after_ramp",
        "device_idle_share.serve_backlog",
        "prefill_device_share.serve_backlog", "prefill_window_fill_share",
        "decode_rows_parked_share.serve_backlog",
        "delivery_gap_p99_ms.serve_backlog",
        "delivery_gap_clear_ms.serve_backlog",
        "delivery_gap_behind_admission_ms.serve_backlog",
        "experts_share.admit", "held_assignment_share",
        "expert_load_max_over_mean"}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL
    for p in m["per_layer"]:
        assert p.get("workloads"), p["name"]
        for cell in p["workloads"]:
            assert cell in e2e[p["moves"]].get("workloads", [cell]), (
                p["name"], cell)
    for entry in m["configs"] + m["workloads"]:
        assert len(entry["why"]) <= 200
        assert len(entry.get("source", "")) <= 200
    assert len(m["workloads"]) == 9 and len(m["configs"]) == 8
    cell = load("workloads", CELL)
    assert cell["run"] == {
        "param_dtype": "bfloat16", "kv_dtype": "bf16", "slots": 96,
        "t_max": 2688, "prompt_buf": 1536, "warm_waves": 16,
        "block_length": 4, "denoising_steps": 2, "remasking": "sequential"}
    assert set(cell["limits"]) == {"served_token_gap", "min_sampled_tokens"}


def test_the_cell_rehearses():
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, timeout=900, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL done: checks pass" in r.stdout
    assert f"perfbench | {CELL} |" in r.stdout


@pytest.mark.parametrize("n_prompt,n_served", [(8, 8), (7, 9), (5, 6),
                                               (10, 3)])
def test_the_noised_copies_rebuild_the_loops_passes(n_prompt, n_served):
    """The reference's own loop serves tokens with no gap at all in its own
    teacher-forced check, whose noised copies are the loop's inputs pass by
    pass; another token at one position has a gap there and nowhere before
    it; every served token gets its request's mean."""
    import numpy as np

    from perfbench import weights
    tiny = run.overlay(CFG, CFG["rehearse"])
    params = weights.make_params(ref.param_spec(tiny), 3,
                                 ref.param_dtypes(tiny, "float32"))
    rng = np.random.default_rng(n_prompt)
    prompt = [int(t) for t in rng.integers(1, tiny["vocab_size"], n_prompt)]
    trace: list = []
    served = ref.generate(params, prompt, n_served, tiny, trace=trace,
                          pad_to=16)
    streams, stream, at = ref.noised_streams(prompt, served, tiny, 16)
    assert streams.shape == (3, 16) or streams.shape == (3, 32)
    seq = prompt + served
    for start, toks, masked, _ in trace:
        # the pass's input is a copy's block: which copy is the pass's count
        done = int((~masked).sum()) - max(n_prompt - start, 0)
        copy = streams[1 + done // 2][start:start + 4]
        keep = start + np.arange(4) < len(seq)
        assert np.array_equal(copy[keep], toks[keep]), (start, done)
    assert np.array_equal(at, n_prompt + np.arange(n_served))
    raw = ref.raw_token_gaps(params, prompt, served, tiny, pad_to=16,
                             control=("int8",))
    assert len(raw["served"]) == n_served
    assert float(raw["served"].max()) < 1e-5
    other = list(served)
    other[-1] = (other[-1] + 1) % tiny["vocab_size"]
    raw = ref.raw_token_gaps(params, prompt, other, tiny, pad_to=16)
    assert raw["served"][-1] > 0 and float(raw["served"][:-1].max()) < 1e-5
    got = ref.served_token_gaps(params, prompt, other, tiny, pad_to=16)
    assert got["served"] == [pytest.approx(float(raw["served"].mean()))
                             ] * n_served
    with pytest.raises(ValueError, match="under 'sequential' only"):
        ref.noised_streams(prompt, served, dict(tiny, generation=dict(
            tiny["generation"], remasking="low_confidence_static")), 16)
