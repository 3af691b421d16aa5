"""A family is files. Two proofs:

- the counts that moved from ``flops.py`` / ``bytes.py`` into the family
  modules return the integers they returned before the move, for the
  three configurations the benchmark ships (the numbers below were read
  from the parent of the PR that moved them);
- a family the tree does not have is added to a COPY of the benchmark by
  adding files and appending manifest entries only -- a family module with
  keyword arguments, a decode-tick floor, a kernel shape and a count of
  its own, a reference module, a configuration, a traffic mix, a cell and
  a per-layer metric that names the count -- and the cell rehearses, its
  metrics read the family's own counts, and no file the copy started with
  has changed.
"""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from perfbench import bytes as nbytes
from perfbench import families, flops

HERE = pathlib.Path(__file__).resolve().parent.parent
ROOT = HERE.parent


def cfg(name):
    return json.load(open(HERE / "configs" / f"{name}.json"))


def test_moved_counts_return_the_parents_integers():
    mi = cfg("mistral-7b-v0.3-l16")
    tick = families.count_fn(mi, "decode_tick_bytes")
    assert [tick(mi, live) for live in (0, 3500, 10300)] == [
        7248027648, 7477403648, 7923048448]
    assert families.count_fn(mi, "llama_weight_bytes")(mi) == 7248027648
    assert families.count_fn(mi, "kv_bytes_per_token")(mi) == 65536
    assert families.count_fn(mi, "llama_matmul_params")(mi) == 3623878656
    assert families.count_fn(mi, "llama_forward_flops_per_token")(
        mi, 1000) == 7509901312.0
    counters = {"global_batch": 8, "seq_len": 1024}
    for name, params, per_token, batch_heads in (
            ("gpt2-medium", 353453056, 2271713280.0, 128),
            ("gpt2-large", 772117760, 4915822080.0, 160)):
        c = cfg(name)
        assert families.count_fn(c, "gpt2_matmul_params")(c) == params
        assert families.count_fn(c, "gpt2_train_flops_per_token")(
            c, 1024) == per_token
        assert families.kernel_shape(c, "train", counters, 1) == dict(
            batch_heads=batch_heads, q_len=1024, kv_len=1024, head_dim=64,
            causal=True)
    assert families.kernel_shape(mi, "train", counters, 1) is None


def test_kernel_counts_stayed_and_model_counts_left():
    for mod, kept in ((flops, {"flash_fwd_flops", "flash_bwd_flops",
                               "paged_decode_attn_flops"}),
                      (nbytes, {"flash_fwd_bytes", "flash_bwd_bytes",
                                "paged_decode_attn_bytes"})):
        have = {k for k, v in vars(mod).items()
                if callable(v) and not k.startswith("_")}
        assert have == kept
    assert not hasattr(families, "FAMILIES")


# ---- a new family, added to a copy of the benchmark as files ---------

FAMILY = '''"""A family the tree does not have (a test's): the program's ``llama``
model under keyword arguments of its own, a cache of which every second
layer keeps a window, and counts of its own."""

from perfbench.family import mistral

BUILD_MODEL = "llama"
REFERENCE = "perfbench.reference.halfwin_ref"
DROPOUT_KEYS = ()


def model_kwargs(cfg, run):
    import jax.numpy as jnp
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]),
        param_dtype=jnp.dtype(run["param_dtype"]))


def decode_tick_bytes(cfg, live_context_tokens):
    full = mistral.kv_bytes_per_token(cfg) / 2
    return (mistral.llama_weight_bytes(cfg)
            + live_context_tokens * full
            + min(live_context_tokens, cfg["window_tokens_live"]) * full)


def kernel_shapes(cfg, which, counters, chips):
    if which != "decode_window":
        return None
    return dict(tokens=min(counters["mean_live_context_tokens"],
                           cfg["window_tokens_live"]),
                kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"])


def halfwin_attn_flops(tokens, kv_heads, head_dim):
    return 4.0 * tokens * kv_heads * head_dim


def halfwin_attn_bytes(tokens, kv_heads, head_dim):
    return 4.0 * tokens * kv_heads * head_dim
'''

REFERENCE = '''"""The plain reference of the test's family: ``llama_ref``'s."""
from perfbench.reference.llama_ref import (  # noqa: F401
    param_dtypes, param_spec, served_token_gaps)
'''

CELL, CONFIG, TRAFFIC = "halfwin_chat", "halfwin-7b", "halfwin_chat"
METRIC = "halfwin_attn_roofline_share.decode"

# what ``run.layer_metrics`` reads in the copy, with a trace that ran two
# segments in 0.02 s and 40 kernel calls in 0.001 s
READ = f'''
import argparse, json
from perfbench import run
env = run.Env(argparse.Namespace(workload="{CELL}", seed=1, seconds=3.0,
                                 trace=1, rehearse=False),
              run.load_json(run.ROOT / "BENCHMARK.json"))
class Trace:
    def module_time_s(self, pattern, trim_edges=False): return 0.02, 2.0
    def op_time_s(self, pattern): return 0.001
    def op_count(self, pattern): return 40.0
out = run.layer_metrics(env, {{"counters": {{
    "segment": 16, "mean_live_context_tokens": 3000.0}}, "trace": Trace(),
    "e2e": {{}}}}, "TPU v5 lite")
print("READ " + json.dumps(out))
'''


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _add_the_family(root):
    """Files added and manifest entries appended; nothing else."""
    bench = root / "perfbench"
    (bench / "family" / "halfwin.py").write_text(FAMILY)
    (bench / "reference" / "halfwin_ref.py").write_text(REFERENCE)
    config = cfg("mistral-7b-v0.3-l16")
    config.update(family="halfwin", window_tokens_live=1000)
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(config))
    shutil.copy(bench / "traffic" / "chat_steady.json",
                bench / "traffic" / f"{TRAFFIC}.json")
    cell = json.load(open(bench / "workloads" / "mistral7b_chat_steady.json"))
    cell.update(config=CONFIG, traffic=TRAFFIC)
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(cell))
    (bench / "layer_metrics" / f"{METRIC}.json").write_text(json.dumps({
        "reader": "trace_kernel_roofline", "kernels": ["dcp_halfwin_attn"],
        "shape": "decode_window", "flops_fn": "halfwin_attn_flops",
        "bytes_fn": "halfwin_attn_bytes"}))
    m = json.load(open(root / "BENCHMARK.json"))
    m["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": f"perfbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": "a test's family"})
    m["workloads"].append({"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                           "chips": 1, "why": "a test's cell"})
    for group, names in (("end_to_end", ("ttft_p90_ms", "tpot_p90_ms")),
                         ("per_layer", ("decode_tick_roofline_share",))):
        for e in m[group]:
            if e["name"] in names:
                e["workloads"].append(CELL)
    m["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Pallas kernels",
        "moves": "tpot_p90_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=2))


def test_a_new_family_is_added_as_files_and_rehearses(tmp_path):
    shutil.copytree(HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = _digests(tmp_path)
    manifest = json.load(open(tmp_path / "BENCHMARK.json"))
    _add_the_family(tmp_path)

    env = dict(os.environ, PYTHONPATH=str(ROOT))   # the program, not perfbench
    run = lambda *argv: subprocess.run(
        [sys.executable, *argv], cwd=tmp_path, env=env, timeout=600,
        capture_output=True, text=True)
    r = run("perfbench/run.py", "--workload", CELL, "--seed", "1",
            "--seconds", "3", "--trace", "0", "--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL done: checks pass" in r.stdout
    assert f"perfbench | {CELL} |" in r.stdout

    # the metrics read the counts of the family's own module
    r = run("-c", "import sys; sys.path.insert(0, '.')\n" + READ)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    got = json.loads(r.stdout.split("READ ", 1)[1])
    assert set(got) == {"decode_tick_roofline_share", METRIC}
    tick_bytes = 7248027648 + 3000 * 32768 + 1000 * 32768
    assert got["decode_tick_roofline_share"]["value"] == pytest.approx(
        100 * (tick_bytes / 819e9) / (0.02 / 32))
    per_call = 4.0 * 1000 * 8 * 128
    assert got[METRIC]["value"] == pytest.approx(
        100 * max(40 * per_call / 197e12, 40 * per_call / 819e9) / 0.001)

    # the same configuration under the family it came from reads another
    # floor: the family, not the reader, states it
    mi = cfg("mistral-7b-v0.3-l16")
    assert families.count_fn(mi, "decode_tick_bytes")(mi, 3000) != tick_bytes

    # nothing the copy started with was edited; the manifest only grew
    after = _digests(tmp_path)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    new = json.load(open(tmp_path / "BENCHMARK.json"))
    for key, old in manifest.items():
        if not isinstance(old, list) or key in ("command", "paths"):
            assert new[key] == old
            continue
        assert len(new[key]) >= len(old)
        for a, b in zip(old, new[key]):
            assert {k: v for k, v in a.items() if k != "workloads"} == {
                k: v for k, v in b.items() if k != "workloads"}
            if "workloads" in a:
                assert b["workloads"][:len(a["workloads"])] == a["workloads"]
    added = sorted(k for k in after if k not in before
                   and not k.startswith(".perfbench_out")
                   and "__pycache__" not in k)
    assert added == sorted([
        "perfbench/family/halfwin.py", "perfbench/reference/halfwin_ref.py",
        f"perfbench/configs/{CONFIG}.json", f"perfbench/traffic/{TRAFFIC}.json",
        f"perfbench/workloads/{CELL}.json",
        f"perfbench/layer_metrics/{METRIC}.json"])
