"""Fused AdamW Pallas kernel vs optax.adamw: step-for-step parity.

Interpret mode on CPU; tests/test_flash_tpu.py-style gating isn't
needed because the kernel is pure elementwise (no Mosaic-specific layout
hazards beyond the tiling rule, which interpret mode now mirrors for the
shapes used here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_compute_pytorch_tpu.ops.pallas.fused_adamw import fused_adamw


def _params(seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return {
        "w": jax.random.normal(ks[0], (48, 130)),      # non-128-multiple cols
        "b": jax.random.normal(ks[1], (130,)),         # 1-D leaf
        "scalar": jax.random.normal(ks[2], ()),        # 0-D leaf
        "deep": {"k": jax.random.normal(ks[3], (3, 5, 257))},  # odd dims
    }


@pytest.mark.parametrize("weight_decay", [0.0, 0.01])
def test_fused_matches_optax_adamw(weight_decay):
    sched = optax.warmup_cosine_decay_schedule(0.0, 1e-2, 3, 50)
    ref_tx = optax.adamw(sched, weight_decay=weight_decay)
    fus_tx = fused_adamw(sched, weight_decay=weight_decay)

    p_ref = _params()
    p_fus = _params()
    s_ref = ref_tx.init(p_ref)
    s_fus = fus_tx.init(p_fus)

    for i in range(5):
        g = jax.tree.map(
            lambda p: jax.random.normal(
                jax.random.fold_in(jax.random.key(100), i), p.shape),
            p_ref)
        upd, s_ref = ref_tx.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, upd)
        p_fus, s_fus = fus_tx.fused_apply(g, s_fus, p_fus)

    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_fus)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref[0].mu),
                    jax.tree_util.tree_leaves(s_fus.mu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)


def test_fused_update_contract_matches_fused_apply():
    """The optax-contract path (update -> apply_updates) must equal the
    direct fused_apply result."""
    tx = fused_adamw(1e-3, weight_decay=0.01)
    p = _params(1)
    s = tx.init(p)
    g = jax.tree.map(jnp.ones_like, p)
    upd, s2 = tx.update(g, s, p)
    via_updates = optax.apply_updates(p, upd)
    direct, s3 = tx.fused_apply(g, s, p)
    for a, b in zip(jax.tree_util.tree_leaves(via_updates),
                    jax.tree_util.tree_leaves(direct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert int(s2.count) == int(s3.count) == 1


def test_fused_adamw_rejects_sharded_layouts(devices8):
    """Pallas custom calls are opaque to GSPMD: sharded parameter layouts
    must be refused loudly, not silently replicated."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.models.convnet import ConvNet
    from distributed_compute_pytorch_tpu.parallel.api import FSDP
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    mesh = make_mesh("data=2,fsdp=4")
    tx = build_optimizer("adamw_fused", lr=1e-2, gamma=1.0,
                         steps_per_epoch=10)
    with pytest.raises(ValueError, match="replicated parameters"):
        make_step_fns(ConvNet(), tx, mesh, FSDP(min_size_to_shard=64))


def test_fused_adamw_trains_through_step_fns(devices8):
    """End-to-end: make_step_fns takes the fused path (no apply_updates)
    and the loss decreases."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.models.convnet import ConvNet
    from distributed_compute_pytorch_tpu.train.optim import build_optimizer
    from distributed_compute_pytorch_tpu.train.step import make_step_fns

    mesh = make_mesh("data=8")
    tx = build_optimizer("adamw_fused", lr=1e-2, gamma=1.0,
                         steps_per_epoch=10)
    assert hasattr(tx, "fused_apply")
    init_fn, train_step, _ = make_step_fns(ConvNet(), tx, mesh)
    state = init_fn(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (32, 28, 28, 1))
    y = jnp.zeros((32,), jnp.int32)
    losses = []
    for _ in range(8):
        state, m = train_step(state, x, y)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2
