"""Pallas flash attention vs the dense XLA path — forward and backward, in
interpret mode on the CPU test mesh (the same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.ops.attention import (
    attention, dot_product_attention)
from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
    flash_attention)


def _qkv(key, b=1, h=2, t=64, d=32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, h, t, d)
    return (jax.random.normal(kq, shape), jax.random.normal(kk, shape),
            jax.random.normal(kv, shape))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv(jax.random.key(0))
    dense = dot_product_attention(q, k, v, causal=causal)
    flash = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(jax.random.key(1), t=32, d=16)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=16, block_k=16) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-6)


def test_flash_rectangular_blocks():
    q, k, v = _qkv(jax.random.key(2), t=64, d=16)
    dense = dot_product_attention(q, k, v, causal=True)
    flash = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_dispatcher_indivisible_lengths_still_correct():
    # t=50 not divisible by any block: on CPU 'auto' is the dense path;
    # on TPU it is now the flash kernel via internal pad-and-mask
    # (r5 — the forced-pallas tests below pin that path's numerics)
    q, k, v = _qkv(jax.random.key(3), t=50, d=16)
    out = attention(q, k, v, causal=True, impl="auto")
    dense = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,tk", [(50, 50), (33, 70), (70, 70)])
def test_flash_odd_lengths_pad_and_mask(causal, t, tk):
    """Non-block-multiple lengths run ON the flash path (VERDICT r4 weak
    #6): the wrapper zero-pads to the block grid, masks the padded keys,
    slices the padded query rows — numerics equal dense."""
    if causal and t > tk:
        pytest.skip("not a meaningful causal shape")
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, (2, 2, t, 16))
    k = jax.random.normal(kk, (2, 2, tk, 16))
    v = jax.random.normal(kv, (2, 2, tk, 16))
    dense = dot_product_attention(q, k, v, causal=causal)
    flash = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_flash_causal_cross_length_bottom_right():
    """Causal q_len < kv_len (masked decode prefill): bottom-right
    alignment — query row i attends kv slots <= i + (tk - t) — matching
    the dense path's convention exactly, block-multiple or not."""
    for t, tk in ((32, 64), (17, 50), (64, 65)):
        kq, kk, kv = jax.random.split(jax.random.key(5), 3)
        q = jax.random.normal(kq, (1, 2, t, 16))
        k = jax.random.normal(kk, (1, 2, tk, 16))
        v = jax.random.normal(kv, (1, 2, tk, 16))
        dense = dot_product_attention(q, k, v, causal=True)
        flash = flash_attention(q, k, v, causal=True,
                                block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                                   rtol=2e-5, atol=2e-6,
                                   err_msg=f"(t={t}, tk={tk})")
    with pytest.raises(ValueError, match="q_len <= kv_len"):
        flash_attention(jnp.zeros((1, 1, 8, 16)), jnp.zeros((1, 1, 4, 16)),
                        jnp.zeros((1, 1, 4, 16)), causal=True)


def test_flash_odd_lengths_masked_and_grads():
    """Odd lengths + a real kv padding mask + gradients: the padded-key
    mask composes with the user's mask and the backward matches dense."""
    t, tk = 21, 35
    kq, kk, kv = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(kq, (2, 2, t, 16))
    k = jax.random.normal(kk, (2, 2, tk, 16))
    v = jax.random.normal(kv, (2, 2, tk, 16))
    kv_mask = (jax.random.uniform(jax.random.key(7), (2, tk)) > 0.3)
    kv_mask = kv_mask.at[:, :2].set(True)   # no fully-masked rows

    def loss_dense(q, k, v):
        o = dot_product_attention(
            q, k, v, causal=True,
            mask=kv_mask[:, None, None, :])
        return jnp.sum(o ** 2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, kv_mask=kv_mask,
                            block_q=16, block_k=16)
        return jnp.sum(o ** 2)

    np.testing.assert_allclose(np.asarray(loss_flash(q, k, v)),
                               np.asarray(loss_dense(q, k, v)), rtol=2e-5)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-5, atol=5e-6)


def test_flash_under_jit_in_model_block():
    """The kernel must trace/jit inside a transformer block (interpret mode
    here; the same path compiles on TPU)."""
    from distributed_compute_pytorch_tpu.models.transformer import TransformerBlock
    block = TransformerBlock(d_model=32, num_heads=2, d_ff=64,
                             dropout_rate=0.0, causal=True,
                             attn_impl="pallas")
    params = block.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 128, 32))
    y = jax.jit(lambda p, x: block.apply(p, x))(params, x)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all())


# ---- blocks of 256 to 1024, and the walk of the diagonal block --------------
#
# At the blocks the dispatcher picks (256 / 512 / 1024) the backward kernels
# walk the block ON the diagonal in sub-tiles inside its grid step (tiles
# above the diagonal dropped, the tile on it masked, the rest run bare);
# every other block, and the forward, runs whole. Interpret mode, float32,
# against a dense masked softmax: what differs is the order of summation.

def _dense(q, k, v, *, window=None, kv_mask=None):
    """Bottom-right-aligned causal attention over grouped heads, dense."""
    t, tk = q.shape[2], k.shape[2]
    g = q.shape[1] // k.shape[1]
    if g > 1:
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    row = jnp.arange(t)[:, None] + (tk - t)
    col = jnp.arange(tk)[None, :]
    see = col <= row
    if window is not None:
        see = see & (col > row - window)
    see = see[None, None]
    if kv_mask is not None:
        see = see & kv_mask[:, None, None, :].astype(bool)
    return dot_product_attention(q, k, v, mask=see)


_CASES = {
    # name: (t, tk, block_q, block_k, extras)
    "b256-one-block": (256, 256, 256, 256, {}),
    "b256-several": (1024, 1024, 256, 256, {}),
    "b512-one-block": (512, 512, 512, 512, {}),
    "b512-several": (1024, 1024, 512, 512, {}),
    "b1024-one-block": (1024, 1024, 1024, 1024, {}),
    "b1024-several": (2048, 2048, 1024, 1024, {"grads": False}),
    "rect-512x256": (1024, 1024, 512, 256, {}),
    "rect-256x1024": (1024, 1024, 256, 1024, {}),
    "rect-1024x512": (1024, 1024, 1024, 512, {}),
    "offset-q512-kv2048": (512, 2048, 512, 1024, {}),
    "offset-q512-kv2048-b256x512": (512, 2048, 256, 512, {}),
    # the block on the diagonal is the LAST kv block of its row, not block qi
    "offset-q512-kv2048-b512": (512, 2048, 512, 512, {}),
    # the diagonal off every block's corner: no block is walked
    "offset-126": (386, 512, 256, 256, {}),
    "offset-129": (383, 512, 256, 256, {}),
    "kv-mask-odd-700": (700, 700, 512, 512, {"kv_mask": 0.3}),
    "kv-mask-odd-300x900": (300, 900, 256, 512, {"kv_mask": 0.3}),
    "kv-mask-head-all-masked": (512, 512, 512, 512, {"kv_mask": "head"}),
    "d192-dv128": (1024, 1024, 1024, 1024, {"d": 192, "dv": 128,
                                            "grads": False}),
    "band-w128-grouped": (1024, 1024, None, None, {"window": 128, "hk": 1}),
    "band-w128-grouped-masked": (900, 900, None, None, {
        "window": 128, "hk": 1, "kv_mask": 0.3}),
    "band-w4096-grouped": (6144, 6144, None, None, {"window": 4096,
                                                    "hk": 1}),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_causal_blocks_match_dense(case):
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention_band)
    t, tk, bq, bk, kw = _CASES[case]
    d, dv, h = kw.get("d", 16), kw.get("dv", kw.get("d", 16)), 2
    hk, window = kw.get("hk", h), kw.get("window")
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (1, h, t, d))
    k = jax.random.normal(ks[1], (1, hk, tk, d))
    v = jax.random.normal(ks[2], (1, hk, tk, dv))
    kv_mask, live = None, jnp.ones((t,), bool)
    if kw.get("kv_mask") == "head":
        # the first 200 keys are pads: rows 0..199 see no key at all
        kv_mask = (jnp.arange(tk) >= 200)[None, :]
        live = jnp.arange(t) >= 200
    elif "kv_mask" in kw:
        kv_mask = jax.random.uniform(ks[3], (1, tk)) > kw["kv_mask"]
        # every row keeps a key inside its window
        kv_mask = kv_mask | (jnp.arange(tk) % 64 == 0)[None, :]
        first = tk - t if window is None else 63
        kv_mask = kv_mask.at[:, :first + 1].set(True)
        if window is not None:
            live = jnp.arange(t) >= 63

    if window is None:
        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, kv_mask=kv_mask,
                                   block_q=bq, block_k=bk)
    else:
        def flash(q, k, v):
            return flash_attention_band(q, k, v, window=window,
                                        kv_mask=kv_mask)

    def dense(q, k, v):
        return _dense(q, k, v, window=window, kv_mask=kv_mask)

    got, want = flash(q, k, v), dense(q, k, v)
    assert got.shape == (1, h, t, dv)
    assert bool(jnp.isfinite(got).all())    # rows with no key included
    np.testing.assert_allclose(np.asarray(got[:, :, live]),
                               np.asarray(want[:, :, live]),
                               rtol=2e-5, atol=2e-6)
    if window is not None or not kw.get("grads", True):
        return      # forward only: the band, unequal head widths

    def loss(f):
        # rows that see no key are garbage on both sides and in no loss
        return lambda q, k, v: jnp.sum((f(q, k, v) ** 2)[:, :, live])

    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=1e-5, err_msg=name)


# ---- what a kernel costs to build ------------------------------------------
#
# A kernel's body is traced anew in every process, for every call site,
# before the compile cache is asked: a body written out sub-tile by sub-tile
# grows with the block and is paid in every set-up. The walk is loops of ONE
# tile body (unrolled only when the kernel is lowered), so a kernel's jaxpr
# must not depend on block, offset or window.

# equations and dot_generals of the kernels before the walk (one body each)
_BEFORE_THE_WALK = {
    ("dcp_flash_fwd", False): (70, 2), ("dcp_flash_fwd", True): (78, 2),
    ("dcp_flash_fwd_band", False): (74, 2),
    ("dcp_flash_fwd_band", True): (82, 2),
    ("dcp_flash_bwd_dq", False): (49, 3), ("dcp_flash_bwd_dq", True): (57, 3),
    ("dcp_flash_bwd_dkv", False): (57, 4),
    ("dcp_flash_bwd_dkv", True): (65, 4),
}


def _sub_jaxprs(eqn):
    for param in eqn.params.values():
        for x in (param if isinstance(param, (tuple, list)) else (param,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _size(jaxpr):
    """(equations, dot_generals) of a jaxpr, nested bodies included."""
    n = dots = 0
    for eqn in jaxpr.eqns:
        n += 1
        dots += eqn.primitive.name == "dot_general"
        for sub in _sub_jaxprs(eqn):
            a, b = _size(sub)
            n, dots = n + a, dots + b
    return n, dots


def _kernel_sizes(f, *args):
    """{kernel name: size of its body} over the pallas_calls ``f`` makes."""
    out = {}

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out[eqn.params["name"]] = _size(eqn.params["jaxpr"])
            else:
                for sub in _sub_jaxprs(eqn):
                    visit(sub)

    visit(jax.make_jaxpr(f)(*args).jaxpr)
    return out


@pytest.mark.parametrize("kernel,masked", list(_BEFORE_THE_WALK))
def test_kernel_body_does_not_grow_with_block_offset_or_window(kernel, masked):
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        _flash_bwd, _flash_fwd)
    sizes = set()
    if kernel == "dcp_flash_fwd_band":
        q, k = jnp.zeros((4, 8192, 64)), jnp.zeros((2, 8192, 64))
        mask = jnp.ones((1, 1, 8192)) if masked else None
        for window, block in ((128, 512), (512, 1024), (4096, 1024)):
            sizes.add(_kernel_sizes(
                lambda q, k, v: _flash_fwd(
                    q, k, v, mask, 4, 0.125, True, 0, block, block,
                    window=window, kv_heads=2), q, k, k)[kernel])
    else:
        for block in (256, 1024):
            for offset in (0, 2048):
                q = jnp.zeros((2, 1024, 64))
                k = jnp.zeros((2, 1024 + offset, 64))
                mask = jnp.ones((1, 1, 1024 + offset)) if masked else None
                o, lse = jnp.zeros_like(q), jnp.zeros((2, 1024, 1))

                def call(q, k, v):
                    if kernel == "dcp_flash_fwd":
                        return _flash_fwd(q, k, v, mask, 2, 0.125, True,
                                          offset, block, block)
                    return _flash_bwd((q, k, v, o, lse), o, mask, 2, 0.125,
                                      True, offset, block, block)

                sizes.add(_kernel_sizes(call, q, k, k)[kernel])
    assert len(sizes) == 1, sizes
    (eqns, dots), = sizes
    before_eqns, before_dots = _BEFORE_THE_WALK[kernel, masked]
    # backward: the whole block's body and one sub-tile's; forward: as it was
    assert dots == (2 if "bwd" in kernel else 1) * before_dots
    assert eqns <= 2 * before_eqns, (eqns, before_eqns)


# (equations, dot_generals) of the paged decode kernels before a grid step
# could pass a parked row by (PR 37's parent): the skip is a ``pl.when``
# round the one body, not a second chunk loop
_DECODE_BEFORE_THE_SKIP = {
    "dcp_paged_decode_attn.mistral_8_kv_heads": (655, 16),
    "dcp_paged_decode_attn.2_kv_heads": (457, 4),
    "dcp_paged_latent_decode_attn": (421, 3),
}


@pytest.mark.parametrize("case", list(_DECODE_BEFORE_THE_SKIP))
def test_decode_kernel_body_does_not_grow_with_the_skip_or_the_rows(case):
    """The decode kernels are traced in every set-up too: their bodies
    stay within a quarter of what they were before parked rows were
    skipped, with the same products, whatever the number of rows."""
    from distributed_compute_pytorch_tpu.ops.pallas import decode_attention
    kernel = case.split(".")[0]
    sizes = set()
    for B in (4, 32, 128):
        table, pos = jnp.zeros((B, 320), jnp.int32), jnp.zeros((B,), jnp.int32)
        if kernel == "dcp_paged_latent_decode_attn":
            args = (jnp.zeros((B, 32, 640), jnp.bfloat16),
                    jnp.zeros((1, 9, 1, 32, 640), jnp.bfloat16), table, pos)

            def call(*a):
                return decode_attention.paged_latent_decode_attention_pallas(
                    *a, v_width=512, scale=0.1)
        else:
            hk = 8 if "mistral" in case else 2
            args = (jnp.zeros((B, hk * 4, 1, 128), jnp.bfloat16),
                    jnp.zeros((2, 9, hk, 8, 128), jnp.bfloat16), table, pos)
            call = decode_attention.paged_decode_attention_pallas
        sizes.add(_kernel_sizes(call, *args)[kernel])
    assert len(sizes) == 1, sizes
    (eqns, dots), = sizes
    before_eqns, before_dots = _DECODE_BEFORE_THE_SKIP[case]
    assert dots == before_dots
    assert eqns <= 1.25 * before_eqns, (eqns, before_eqns)
