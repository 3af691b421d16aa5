"""The benchmark's own arithmetic against hand-worked values."""

import json
import math
import pathlib

import pytest

from perfbench import bytes as nbytes
from perfbench import flops, peaks
from perfbench.family import gpt2, mistral
from perfbench.percentiles import percentile, samples_beyond

CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "configs"


def cfg(name):
    return json.load(open(CONFIGS / f"{name}.json"))


def test_percentile_is_exact_nearest_rank():
    vals = [5, 1, 4, 2, 3, 9, 8, 7, 6, 10]
    assert percentile(vals, 90) == 9
    assert percentile(vals, 50) == 5
    assert percentile(vals, 100) == 10
    assert percentile(vals, 1) == 1
    assert samples_beyond(200, 90) == 20


def test_percentile_counts_a_missing_request_as_infinite():
    vals = [1.0] * 8 + [math.inf] * 2
    assert percentile(vals, 80) == 1.0
    assert percentile(vals, 90) == math.inf
    with pytest.raises(ValueError):
        percentile([], 90)


@pytest.mark.parametrize("name,matmul_params,gflop", [
    ("gpt2-medium", 24 * 12 * 1024 ** 2 + 50257 * 1024, 2.272),
    ("gpt2-large", 36 * 12 * 1280 ** 2 + 50257 * 1280, 4.916),
])
def test_gpt2_train_flops_per_token(name, matmul_params, gflop):
    c = cfg(name)
    assert gpt2.gpt2_matmul_params(c) == matmul_params
    per_token = gpt2.gpt2_train_flops_per_token(c, 1024)
    # 6 x matmul parameters + 6 x layers x T x d (causal half, fwd + bwd)
    assert per_token == 6 * matmul_params + 6 * c["n_layer"] * 1024 * c["n_embd"]
    assert per_token / 1e9 == pytest.approx(gflop, abs=5e-3)


def test_mistral_bytes():
    c = cfg("mistral-7b-v0.3-l16")
    # 2 (K,V) x 8 KV heads x 128 x 2 B x 16 layers = 64 KiB a token
    assert mistral.kv_bytes_per_token(c) == 65536
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert mistral.llama_matmul_params(c) == 16 * per_layer + 32768 * 4096
    # weights a tick reads: blocks + head + norms, bf16: 7.25 GB (the
    # embedding table, 0.27 GB more on the chip, is a gather of 32 rows)
    assert mistral.llama_weight_bytes(c) == 2 * (
        16 * per_layer + 32768 * 4096 + 33 * 4096)
    assert mistral.decode_tick_bytes(c, 10_000) == (
        mistral.llama_weight_bytes(c) + 10_000 * 65536)


def test_paged_decode_attention_counts_one_layers_live_context():
    c = cfg("mistral-7b-v0.3-l16")
    from perfbench import families
    shape = families.kernel_shape(c, "decode",
                                  {"mean_live_context_tokens": 3500.0}, 1)
    assert shape == dict(live_context_tokens=3500.0, q_heads=32, kv_heads=8,
                         head_dim=128, itemsize=2)
    # K and V of 3500 tokens of ONE layer: a sixteenth of the tick's K/V
    by = families.count_fn(c, "paged_decode_attn_bytes")(**shape)
    assert by == 3500 * 2 * 8 * 128 * 2 == 3500 * 65536 / 16
    fl = families.count_fn(c, "paged_decode_attn_flops")(**shape)
    assert fl == 4 * 3500 * 32 * 128
    r = peaks.roofline(fl, by, by / 819e9 / 0.25, "TPU v5 lite")
    assert r["bound"] == "memory" and r["share"] == pytest.approx(25.0)
    # no live contexts counted in the run: nothing to read
    assert families.kernel_shape(c, "decode", {}, 1) is None


def test_flash_counts_the_causal_half():
    full = flops.flash_fwd_flops(1, 1024, 1024, 64, causal=False)
    half = flops.flash_fwd_flops(1, 1024, 1024, 64, causal=True)
    assert full == 4 * 1024 * 1024 * 64
    assert half == 4 * (1024 * 1025 / 2) * 64
    assert flops.flash_bwd_flops(1, 1024, 1024, 64, True) == 2.5 * half
    assert nbytes.flash_fwd_bytes(2, 1024, 1024, 64) == 2 * 64 * 2 * 4096


def test_peaks_table_and_roofline_never_clip():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    r = peaks.roofline(197e12, 1.0, 0.5, "TPU v5 lite")
    assert r["bound"] == "compute" and r["share"] == pytest.approx(200.0)
    r = peaks.roofline(1.0, 819e9, 2.0, "TPU v5 lite")
    assert r["bound"] == "memory" and r["share"] == pytest.approx(50.0)


def test_kernel_call_shapes_come_from_the_family_module():
    from perfbench import families
    counters = {"global_batch": 32, "seq_len": 1024}
    got = families.kernel_shape(cfg("gpt2-large"), "train", counters, 4)
    assert got == dict(batch_heads=8 * 20, q_len=1024, kv_len=1024,
                       head_dim=64, causal=True)
    # the shape goes to the operation and the byte function as it comes
    assert flops.flash_fwd_flops(**got) == 4 * 160 * (1024 * 1025 / 2) * 64
    assert nbytes.flash_fwd_bytes(**got) == 160 * 64 * 2 * 4096
    assert families.kernel_shape(cfg("mistral-7b-v0.3-l16"), "train",
                                 counters, 1) is None
    assert families.kernel_shape(cfg("gpt2-large"), "decode", counters,
                                 1) is None


def test_an_unknown_family_or_count_fails_with_where_it_was_looked_for():
    from perfbench import families
    with pytest.raises(LookupError, match=r"perfbench/family/nosuch\.py"):
        families.family({"family": "nosuch"})
    with pytest.raises(LookupError, match="family/gpt2.py.*flops.py"):
        families.count_fn(cfg("gpt2-medium"), "no_such_count")
    # the family's module is asked first, the kernels' modules second
    assert families.count_fn(cfg("gpt2-medium"), "flash_fwd_flops") \
        is flops.flash_fwd_flops
    assert families.count_fn(cfg("mistral-7b-v0.3-l16"), "decode_tick_bytes") \
        is mistral.decode_tick_bytes


@pytest.mark.parametrize("name, changed", [
    ("gpt2-medium", {"attn_pdrop", "embd_pdrop", "resid_pdrop"}),
    ("mistral-7b-v0.3-l16", set())])
def test_without_dropout_zeroes_the_familys_keys_only(name, changed):
    from perfbench import families
    c = cfg(name)
    plain = families.without_dropout(c)
    assert {k for k in c if c[k] != plain[k]} == changed
    assert all(plain[k] == 0.0 for k in changed)


def test_pooled_and_worst_of_the_sampled_differences():
    import numpy as np

    from perfbench import check
    ref = {"a/kernel": np.ones((2, 4)), "b/bias": 2 * np.ones((1, 4))}
    prog = {"a/kernel": np.array([[1.1] * 4, [1.0] * 4]),
            "b/bias": 2 * np.ones((1, 4)) + 0.6}
    rel = check.sampled_rel_diffs(prog, ref)      # floor: median rms = 1
    assert rel["a/kernel"] == pytest.approx([0.1, 0.0])
    assert rel["b/bias"] == pytest.approx([0.3])
    assert check.worst(rel) == (pytest.approx(0.3), "b/bias[0]")
    assert check.pooled(rel) == pytest.approx(math.sqrt((0.01 + 0.09) / 3))
    assert check.pooled(rel, {"a/kernel"}) == pytest.approx(math.sqrt(0.005))


def test_vector_leaves_are_the_per_layer_biases_and_norm_parameters():
    from perfbench import check
    from perfbench.reference import gpt2_ref
    got = check.vector_leaves(gpt2_ref.param_spec(cfg("gpt2-medium")))
    assert got == {f"blocks/{a}/{b}" for a, b in [
        ("ln1", "scale"), ("ln1", "bias"), ("qkv", "bias"),
        ("attn_out", "bias"), ("ln2", "scale"), ("ln2", "bias"),
        ("mlp_in", "bias"), ("mlp_out", "bias")]}


def test_tokens_per_second_after_the_ramp():
    from perfbench.readers import request_rate_after
    row = lambda arrival, ttft, latency, n: {
        "arrival_s": arrival, "ttft_s": ttft, "latency_s": latency,
        "tokens": n}
    ctx = {"traffic": {"ramp": {"requests": 10, "gap_s": 0.5}},
           "counters": {"window_s": 15.0},
           "requests": [
               row(0.0, 1.0, 9.0, 9),     # tokens at 1..9 s: those at 6..9
               row(6.0, 1.0, 3.0, 7),     # all after the ramp
               row(1.0, 1.0, 3.0, 5),     # all before
               row(4.0, None, 0.0, 0)]}   # shed
    got = request_rate_after.read({}, ctx)
    assert got["value"] == pytest.approx((4 + 7) / 10.0)
    assert request_rate_after.read({}, dict(ctx, traffic={})) is None
