"""What a CPU can pin of the chip bring-up (ISSUE 21): the CLIs stay off
the backend at import, the compile cache lives in one place, bf16
checkpoints round-trip through the entry points in the dtype they were
saved in, and replicas live on their own devices."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_imports_initialise_no_backend():
    """The --supervise parents (cli.py, cli_serve.py) import these
    modules and then only spawn children: a parent that touched a
    backend would hold the chip its child needs."""
    code = ("import distributed_compute_pytorch_tpu.cli, "
            "distributed_compute_pytorch_tpu.cli_serve, "
            "distributed_compute_pytorch_tpu.cli_generate\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_compile_cache_has_one_home(monkeypatch):
    from distributed_compute_pytorch_tpu.utils import compilation_cache

    # conftest already called enable(): the in-checkout default is live
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    home = os.path.join(REPO, ".jax_cache")
    assert compilation_cache.enable() == home
    assert jax.config.jax_compilation_cache_dir == home
    # with the variable set, JAX reads it itself: no directory is set here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compilation_cache.enable() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == home


@pytest.fixture(scope="module")
def bf16_run(tmp_path_factory):
    """One tiny GPT-2 trained with bf16 parameters, saved v1 and v2."""
    from distributed_compute_pytorch_tpu.core.config import Config
    from distributed_compute_pytorch_tpu.data.datasets import synthetic_lm
    from distributed_compute_pytorch_tpu.train.trainer import Trainer

    root = tmp_path_factory.mktemp("bf16")
    data = synthetic_lm(32, seq_len=16, vocab=256, seed=3)
    runs = {}
    for fmt, sharded in (("v1", False), ("v2", True)):
        cfg = Config(batch_size=16, lr=1e-3, epochs=1, mesh="data=1",
                     model="gpt2", model_preset="tiny",
                     dataset="synthetic-lm", optimizer="adamw",
                     param_dtype="bfloat16", ckpt_sharded=sharded,
                     ckpt_path=str(root / f"ck_{fmt}"))
        trainer = Trainer(cfg, train_data=data, eval_data=data)
        trainer.fit()
        runs[fmt] = (cfg, data, trainer.state)
    return runs


@pytest.mark.parametrize("fmt", ["v1", "v2"])
def test_bf16_checkpoint_resumes(bf16_run, fmt):
    from distributed_compute_pytorch_tpu.train.trainer import Trainer

    cfg, data, saved = bf16_run[fmt]
    resumed = Trainer(cfg.replace(resume=True, epochs=2), train_data=data,
                      eval_data=data)
    assert resumed.start_epoch == 1
    for a, b in zip(jax.tree.leaves(saved.params),
                    jax.tree.leaves(resumed.state.params)):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b)
    kernel = resumed.state.params["blocks"]["qkv"]["kernel"]
    assert kernel.dtype == jnp.bfloat16


@pytest.mark.parametrize("fmt", ["v1", "v2"])
def test_bf16_checkpoint_serves_in_saved_dtype(bf16_run, fmt):
    """No flag says bf16: the loader reads the dtype off the checkpoint,
    and the pool follows the weights."""
    from distributed_compute_pytorch_tpu.cli_generate import (
        load_model_and_params)
    from distributed_compute_pytorch_tpu.serve import ContinuousBatcher

    cfg, _, saved = bf16_run[fmt]
    model, params, _ = load_model_and_params("gpt2", "tiny", 256, 16,
                                             cfg.ckpt_path)
    assert model.config.param_dtype == jnp.bfloat16
    for a, b in zip(jax.tree.leaves(saved.params), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and jnp.array_equal(a, b)
    engine = ContinuousBatcher(model, params, slots=2, t_max=16,
                               prompt_buf=8).engine_info()
    assert engine["param_dtype"] == engine["pool_dtype"] == "bfloat16"


def test_replicas_live_on_their_own_devices(bf16_run, tmp_path, capsys,
                                            devices8):
    """dcp-serve --replicas 2: each replica's pool (and so its programs)
    on its own local device, answers unchanged."""
    from distributed_compute_pytorch_tpu.cli_serve import main as serve_main

    cfg, _, _ = bf16_run["v1"]
    reqs = tmp_path / "reqs.txt"
    reqs.write_text("5, 9, 12\n7\n1 2 3 4 5\n3 3\n")
    base = ["--ckpt_path", cfg.ckpt_path, "--model", "gpt2",
            "--model_preset", "tiny", "--max_seq_len", "16",
            "--requests", str(reqs), "--slots", "2", "--segment", "3",
            "--max_new_tokens", "4", "--heartbeat", "0"]
    outs = {}
    for n in (1, 2):
        metrics = tmp_path / f"m{n}.jsonl"
        capsys.readouterr()
        assert serve_main(base + ["--replicas", str(n),
                                  "--metrics_jsonl", str(metrics)]) == 0
        outs[n] = [json.loads(ln)["new"] for ln in
                   capsys.readouterr().out.strip().splitlines()]
        engines = [json.loads(ln) for ln in metrics.read_text().splitlines()
                   if '"serve_kernels"' in ln]
        assert len(engines) == n and all("programs" in e for e in engines)
        devices = [tuple(e["engine"]["devices"]) for e in engines]
        assert len(set(devices)) == n and all(len(d) == 1 for d in devices)
    assert outs[1] == outs[2]
