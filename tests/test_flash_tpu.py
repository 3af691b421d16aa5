"""TPU-gated flash-attention proof (VERDICT r1 weak #1 / next-round #2).

The rest of the suite forces interpret mode on the faked CPU mesh; Mosaic
compilation is exactly where Pallas kernels die, so this file compiles and
runs the kernels on a REAL TPU and pins numerics against the dense path.
Skipped automatically when no TPU is attached.

Run on hardware with ``DCP_TEST_TPU=1 python -m pytest tests/test_flash_tpu.py
tests/test_cache_update_tpu.py`` (the flag stops tests/conftest.py from
forcing the CPU backend; run only the two TPU files — the rest of the suite
expects the 8-device CPU mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="requires a real TPU (suite runs on the faked CPU mesh)")


def _qkv(T, B=2, H=4, D=64, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense_on_tpu(causal):
    from distributed_compute_pytorch_tpu.ops.attention import (
        dot_product_attention)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)

    q, k, v = _qkv(1024)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=512, block_k=512))(q, k, v)
    ref = jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)  # bf16 resolution


def test_flash_backward_matches_dense_on_tpu():
    from distributed_compute_pytorch_tpu.ops.attention import (
        dot_product_attention)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)

    q, k, v = _qkv(512)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=256,
                               block_k=256).astype(jnp.float32).sum()

    def loss_dense(q, k, v):
        return dot_product_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def test_masked_flash_matches_dense_on_tpu():
    """Padding-masked kernel (Mosaic-compiled) vs masked dense: fwd + grads."""
    import jax.numpy as jnp

    from distributed_compute_pytorch_tpu.ops.attention import (
        dot_product_attention)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)

    B, H, T, D = 2, 4, 1024, 64
    q, k, v = _qkv(T, B=B, H=H, D=D)
    lengths = [1024, 517]
    m = np.zeros((B, T), np.float32)
    for i, n in enumerate(lengths):
        m[i, :n] = 1.0
    kv_mask = jnp.asarray(m)
    g_mask = kv_mask[:, None, :, None]

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, kv_mask=kv_mask, block_q=512,
                            block_k=512)
        return jnp.sum(o.astype(jnp.float32) * g_mask)

    def loss_dense(q, k, v):
        o = dot_product_attention(
            q, k, v, mask=kv_mask[:, None, None, :].astype(bool))
        return jnp.sum(o.astype(jnp.float32) * g_mask)

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, kv_mask=kv_mask, block_q=512, block_k=512))(q, k, v)
    ref = jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, mask=kv_mask[:, None, None, :].astype(bool)))(q, k, v)
    valid = np.asarray(g_mask, bool) & np.ones_like(np.asarray(out), bool)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[valid[:, :1].repeat(H, 1)],
        np.asarray(ref, np.float32)[valid[:, :1].repeat(H, 1)],
        atol=3e-2, rtol=3e-2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def test_auto_impl_dispatches_to_flash_on_tpu():
    """attention(impl='auto') must pick the Pallas kernel on TPU for
    eligible shapes (the product path GPT-2/BERT take)."""
    from distributed_compute_pytorch_tpu.ops import attention as A

    q, k, v = _qkv(1024)
    auto = jax.jit(lambda q, k, v: A.attention(q, k, v, causal=True))(q, k, v)
    forced = jax.jit(lambda q, k, v: A.attention(
        q, k, v, causal=True, impl="pallas"))(q, k, v)
    np.testing.assert_array_equal(np.asarray(auto, np.float32),
                                  np.asarray(forced, np.float32))


def test_train_step_shape_1024_blocks_on_tpu():
    """The GPT-2-small train step's attention as the model dispatches it:
    T=1024 picks the 1024x1024 blocks (``ops/attention.py::_pick_block``)
    — forward AND both backward kernels at that block size."""
    from distributed_compute_pytorch_tpu.ops import attention as A

    q, k, v = _qkv(1024, B=2, H=12)

    def loss(impl):
        return lambda q, k, v: A.attention(
            q, k, v, causal=True, impl=impl).astype(jnp.float32).sum()

    gf = jax.jit(jax.grad(loss("auto"), argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss("xla"), argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def test_admission_prefill_shape_pad_and_mask_on_tpu():
    """Serving's admission prefill as the model dispatches it: a causal
    700-token window (no block divides it: padded to 768, 128 blocks) with
    a per-row key-validity mask — the masked forward kernel."""
    from distributed_compute_pytorch_tpu.ops import attention as A

    B, H, T = 4, 12, 700
    q, k, v = _qkv(T, B=B, H=H)
    m = np.zeros((B, T), np.float32)
    for i, n in enumerate([700, 512, 130, 16]):
        m[i, :n] = 1.0
    kv_mask = jnp.asarray(m)
    out = jax.jit(lambda q, k, v: A.attention(
        q, k, v, causal=True, kv_mask=kv_mask))(q, k, v)
    ref = jax.jit(lambda q, k, v: A.attention(
        q, k, v, causal=True, kv_mask=kv_mask, impl="xla"))(q, k, v)
    # rows past each prompt's length are padded queries: garbage by
    # contract on both paths, excluded here as they are from every loss
    valid = np.broadcast_to(m[:, None, :, None] > 0, out.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32)[valid],
                               np.asarray(ref, np.float32)[valid],
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("case", [
    "causal-2048-masked", "offset-q512-kv2048", "d192-dv128-4096",
    "band-w128-grouped", "band-w4096-grouped"])
def test_walked_blocks_on_tpu(case):
    """The serve cells' forward shapes, Mosaic-compiled, at blocks of 512
    and 1024: causal with a key mask, a static offset, unequal q/k and v
    head widths, and the band kernel over grouped heads."""
    from distributed_compute_pytorch_tpu.ops import attention as A

    ks = jax.random.split(jax.random.key(3), 3)
    window = kv_mask = None
    h, hk, t, tk, d, dv = 8, 8, 2048, 2048, 128, 128
    if case == "causal-2048-masked":
        m = np.zeros((2, tk), np.float32)
        m[0], m[1, :1300] = 1.0, 1.0
        kv_mask = jnp.asarray(m)
    elif case == "offset-q512-kv2048":
        t = 512
    elif case == "d192-dv128-4096":
        t = tk = 4096
        d, dv = 192, 128
    else:
        hk, window = 2, int(case.split("-")[1][1:])
        t = tk = 2048 if window == 128 else 6144
    q = jax.random.normal(ks[0], (2, h, t, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, hk, tk, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, hk, tk, dv), jnp.bfloat16)

    def run(impl):
        return jax.jit(lambda q, k, v: A.attention(
            q, k, v, causal=True, kv_mask=kv_mask, window=window,
            impl=impl))(q, k, v)

    out, ref = run("auto"), run("xla")
    assert out.shape == (2, h, t, dv)
    valid = np.ones(out.shape, bool)
    if kv_mask is not None:     # rows past a prompt's length are pads
        valid = np.broadcast_to(np.asarray(kv_mask)[:, None, :, None] > 0,
                                out.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32)[valid],
                               np.asarray(ref, np.float32)[valid],
                               atol=3e-2, rtol=3e-2)
