"""Mesh-sharded continuous batching (serve.py ``mesh=``): staggered
admissions through a SHARDED slot pool must reproduce sharded standalone
generation exactly, with the KV cache actually landing sharded — rows
over the batch axes, kv heads over ``tensor`` — not silently replicated.

The reference for every parity assert is ``infer.make_generate_fn``
under the SAME mesh (one left-padded batch): cross-LAYOUT equality is
only a logits-tolerance property (collective reduction order moves
argmax at random-init near-ties — see tests/test_generate.py), but
same-mesh serve-vs-generate is exact because both partition each row's
per-token math identically.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.core.mesh import (
    batch_sharding, make_mesh)
from distributed_compute_pytorch_tpu.infer import make_generate_fn
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.models.moe import (
    MoETransformerConfig, MoETransformerLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request)


def _sharded(model, params, mesh):
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    return shard_pytree(params, pick_strategy(mesh, model), mesh)


def _reqs(rng, n, max_len=8, min_new=3, max_new=6):
    return [Request([int(t) for t in
                     rng.integers(0, 256, rng.integers(2, max_len + 1))],
                    int(rng.integers(min_new, max_new + 1)))
            for _ in range(n)]


def _solo_batch(model, params, mesh, reqs):
    """Sharded standalone reference: ONE left-padded generate batch
    under the same mesh; request i's expected tokens are row i's first
    max_new continuations."""
    T0 = max(len(r.tokens) for r in reqs)
    N = max(r.max_new for r in reqs)
    prompt = np.zeros((len(reqs), T0), np.int32)
    mask = np.zeros((len(reqs), T0), np.int32)
    for i, r in enumerate(reqs):
        prompt[i, T0 - len(r.tokens):] = r.tokens
        mask[i, T0 - len(r.tokens):] = 1
    gen = make_generate_fn(model, N, mesh=mesh)
    out = np.asarray(gen(params,
                         jax.device_put(jnp.asarray(prompt),
                                        batch_sharding(mesh, 2)),
                         prompt_mask=jnp.asarray(mask)))
    return [[int(t) for t in out[i, T0:T0 + r.max_new]]
            for i, r in enumerate(reqs)]


def _assert_cache_sharded(cb, want_tensor: bool):
    kv = cb._caches[0]["kv"]          # kv-pair [2, B, hk, T, hd]
    assert not kv.sharding.is_fully_replicated, kv.sharding
    spec = kv.sharding.spec
    assert spec[1] in ("data", ("data",), ("data", "fsdp")), spec
    if want_tensor:
        assert spec[2] == "tensor", spec
    # the per-device shard must be a strict slice of the rows
    shard_rows = kv.addressable_shards[0].data.shape[1]
    assert shard_rows < kv.shape[1], (shard_rows, kv.shape)


@pytest.mark.parametrize("spec,slots", [
    ("data=2", 4),
    ("data=2,tensor=2", 4),
    ("data=2,fsdp=2,tensor=2", 4),
])
def test_mesh_serve_matches_sharded_generate(spec, slots, devices8):
    """The gold serving test, SHARDED: mixed-length staggered requests
    through a mesh-sharded pool equal the same-mesh standalone batch,
    token for token, and the cache rows/heads genuinely shard."""
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh(spec, devices=devices8)
    sharded = _sharded(model, params, mesh)
    rng = np.random.default_rng(3)
    reqs = _reqs(rng, 8)
    # prompt_buf 16: heads of up to 8 tokens share the ladder's lower
    # rung, as many rows a dispatch as the batch axes' product
    cb = ContinuousBatcher(model, sharded, slots=slots, t_max=64,
                           prompt_buf=16, segment=3, mesh=mesh)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    want = _solo_batch(model, sharded, mesh, reqs)
    for i, (out, w) in enumerate(zip(outs, want)):
        assert out == w, (spec, i, out, w)
    _assert_cache_sharded(cb, want_tensor="tensor" in spec)
    # batched admission + overlap survived the mesh: the rows of a rung
    # share a dispatch, one fetch/segment
    s = cb.stats
    assert s["prefill_rows"] == len(reqs) and s["prefill_calls"] < len(reqs)
    assert s["fetches"] == s["segments"]


def test_mesh_serve_int8_weights(devices8):
    """Weight-only int8 serving under dp x tensor: quantized leaves
    inherit the sharded layout (mixed-dtype dots partition) and serve
    token-identically to the same-mesh int8 generate."""
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)

    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2,tensor=2", devices=devices8)
    qp = jax.jit(quantize_params_int8)(_sharded(model, params, mesh))
    rng = np.random.default_rng(7)
    reqs = _reqs(rng, 6)
    cb = ContinuousBatcher(model, qp, slots=2, t_max=64, prompt_buf=10,
                           segment=3, mesh=mesh)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    want = _solo_batch(model, qp, mesh, reqs)
    assert outs == want
    _assert_cache_sharded(cb, want_tensor=True)


def test_mesh_serve_moe_expert_parallel(devices8):
    """The MoE family under data x expert: expert FFNs stay sharded,
    every admission wave routes each row as its own group, and served
    tokens equal the same-mesh standalone batch (generous eval capacity
    so the documented last-token no-drop boundary can't bind)."""
    cfg = dataclasses.replace(MoETransformerConfig.tiny(), top_k=2,
                              router_balance="aux", capacity_factor=2.0,
                              eval_capacity_factor=4.0, max_seq_len=128)
    model = MoETransformerLM(cfg)
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2,expert=2", devices=devices8)
    sharded = _sharded(model, params, mesh)
    rng = np.random.default_rng(11)
    reqs = _reqs(rng, 6)
    cb = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                           prompt_buf=10, segment=3, mesh=mesh)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    want = _solo_batch(model, sharded, mesh, reqs)
    for i, (out, w) in enumerate(zip(outs, want)):
        assert out == w, (i, out, w)
    _assert_cache_sharded(cb, want_tensor=False)
    # the expert FFN stacks really shard over the expert axis
    w_in = sharded["blocks"]["moe"]["w_in"]
    assert not w_in.sharding.is_fully_replicated, w_in.sharding


def test_mesh_serve_prefix_cache_parity(devices8):
    """The radix prefix cache under a sharded pool: attached blocks
    reshard into the row-sharded compute layout through the admission
    gather (the portable-redistribution move), and the cache-on stream
    stays token-identical to the same-mesh cache-off stream AND the
    same-mesh standalone batch — with real attaches, zero leaks, and
    the pool's BLOCK axis genuinely sharded."""
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2", devices=devices8)
    sharded = _sharded(model, params, mesh)
    rng = np.random.default_rng(13)
    shared = [int(t) for t in rng.integers(0, 256, 11)]
    reqs = [Request(shared + [int(t) for t in rng.integers(0, 256, 2)],
                    int(rng.integers(3, 6))) for _ in range(8)]
    off = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                            prompt_buf=14, segment=3, mesh=mesh)
    out_off = off.serve([Request(list(r.tokens), r.max_new)
                         for r in reqs])
    on = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                           prompt_buf=14, segment=3, mesh=mesh,
                           prefix_cache=True)
    out_on = on.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert out_on == out_off
    want = _solo_batch(model, sharded, mesh, reqs)
    for i, (out, w) in enumerate(zip(out_on, want)):
        assert out == w, (i, out, w)
    assert on.stats["prefix_hits"] > 0
    assert on.stats["cow_copies"] > 0      # 11-token prefix ends mid-block
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0
    _assert_cache_sharded(on, want_tensor=False)


def test_mesh_serve_validation(devices8):
    model = LlamaLM(LlamaConfig.tiny())       # 2 kv heads
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=1,tensor=8", devices=devices8)
    with pytest.raises(ValueError, match="num_kv_heads"):
        ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=8,
                          mesh=mesh)
    mesh = make_mesh("data=4,seq=2", devices=devices8)
    with pytest.raises(ValueError, match="seq"):
        ContinuousBatcher(model, params, slots=4, t_max=32, prompt_buf=8,
                          mesh=mesh)
    mesh = make_mesh("data=4,tensor=2", devices=devices8)
    with pytest.raises(ValueError, match="slots"):
        # 3 rows cannot divide over data=4
        ContinuousBatcher(model, params, slots=3, t_max=32, prompt_buf=8,
                          mesh=mesh)
