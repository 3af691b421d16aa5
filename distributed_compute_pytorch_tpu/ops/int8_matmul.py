"""Weight-only int8 matmul for decode: stream weights at half the bytes.

KV-cache decode is weights-bandwidth-bound (every parameter is read
once per tick: PERF.md section 5, the serve cells' decode tick). Storing matmul weights as int8
with a per-output-channel scale halves that stream — IF the weights
actually cross HBM as int8. Three formulations were measured on v5e
(2026-07-31, decode-shaped scan, 12x[768,8192], B=16; bf16 weights
baseline 0.279 ms/tick):

1. ``wq.astype(bf16) * scale`` feeding a matmul: **0.338 ms** — slower
   than bf16. XLA materialises the dequantised copy each tick instead
   of fusing the convert into the dot.
2. A Pallas kernel (int8 tile DMA -> VMEM convert -> MXU dot -> scale
   the output tile): **0.174 ms** — the streaming win is real, but at
   the framework's shapes each tick makes ~84 small kernel launches
   (7 projections x 12 layers) and the fixed per-launch cost ate the
   win end-to-end (full Llama decode measured 0.560 vs 0.557 bf16).
3. ``lax.dot_general(x_bf16, wq_int8)`` — int8 passed DIRECTLY as the
   dot operand, scale applied to the output: **0.110 ms**. XLA:TPU
   consumes the mixed-dtype dot natively and streams the rhs as int8
   with none of the custom-call overhead. This is the implementation.

The per-output-channel scale commutes with the contraction
(``(x @ wq) * scale == x @ (wq * scale)``), which is what makes the
output-side dequant exact.

A plain native dot also keeps the op GSPMD-partitionable and
backend-portable (CPU tests run the same code path), unlike the
custom-call routes.

Capability beyond the reference (`/root/reference/main.py` has no
inference path at all); the quantization entry point is
``utils/quantize.py::quantize_params_int8`` and the consumer hooks are
``models/layers.py`` (Dense / Embedding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def int8_matmul(x, wq, scale, *, transpose: bool = False):
    """``x [..., K] @ dequant(wq)`` with weight-only int8 quantization.

    ``transpose=False``: ``wq [K, N]`` int8, ``scale [1, N]`` (or
    ``[N]``) per-output-channel -> ``[..., N]``.
    ``transpose=True``: ``wq [N, K]`` row-major (an embedding table),
    ``scale [N, 1]`` (or ``[N]``) per-row -> ``[..., N]`` — the readout
    ``x @ table.T`` without materialising a transposed copy.

    The int8 operand enters ``lax.dot_general`` directly (see module
    docstring for why that, and not a dequant or a Pallas kernel, is
    the fast path); accumulation in f32, output in ``x.dtype``.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = wq.shape[0] if transpose else wq.shape[1]
    x2 = x.reshape(-1, K)
    rhs_contract = 1 if transpose else 0
    out = lax.dot_general(
        x2, wq, dimension_numbers=(((1,), (rhs_contract,)), ((), ())),
        preferred_element_type=jnp.float32)
    out = out * scale.reshape(1, N).astype(jnp.float32)
    return out.astype(x.dtype).reshape(*lead, N)
