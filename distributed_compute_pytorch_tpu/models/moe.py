"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Capability beyond the reference (whose only model is a dense CNN,
``/root/reference/main.py:20-45``); makes the framework's declared
``expert`` axis real. The design is the TPU-idiomatic GShard/Switch
formulation rather than a gather/scatter one:

- **Einsum dispatch**: top-1 (Switch) or top-2 (GShard) routing builds a
  one-hot dispatch tensor ``[groups, group_tokens, experts, capacity]``;
  dispatch and combine are plain einsums, so the whole layer is
  static-shaped matmuls the MXU likes — no sorting, no dynamic shapes,
  fully differentiable (through the combine weights).
- **Routing groups**: the dispatch tensor over all N tokens at once costs
  ``capacity_factor * N^2`` elements (capacity scales as N/E, so E cancels
  — the known GShard wall). Routing within groups of ``group_size`` tokens
  (GShard's "groups") cuts that to ``capacity_factor * N * group_size``,
  linear in N, at the cost of per-group capacity boundaries.
- **Expert parallelism as sharding**: expert weights are stacked
  ``[E, ...]`` and sharded over ``expert``; a ``sharding_constraint`` pins
  the dispatched activations ``[E, C, d]`` to the same axis, and XLA's SPMD
  partitioner inserts the all-to-alls the layout implies — the same
  "layout, not message-passing" principle the framework uses for DP/FSDP/TP.
- **Load balancing**: the standard Switch auxiliary loss
  ``E * mean(fraction_tokens * fraction_probs)`` plus a router z-loss keep
  routing from collapsing; both are returned for the model to fold into its
  objective.

Tokens overflowing an expert's capacity are dropped (their combine weight
is zero — the residual path carries them), exactly as in Switch/GShard.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import current_mesh
from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.obs.tracing import scope


# sharding pin that composes with the pipeline's manual regions (moved to
# core/mesh.py when activation sharding grew more callers)
from distributed_compute_pytorch_tpu.core.mesh import constrain as _constrain  # noqa: E402,E501


@dataclass(frozen=True)
class MoELayer:
    """Top-1 (Switch) / top-2 (GShard) MoE MLP: router + E expert FFNs.

    ``group_size``: tokens per routing group (must divide the token count;
    None = one global group — exact Switch semantics, quadratic dispatch).
    ``top_k``: 1 or 2; with 2, the second expert's gate is renormalised
    against the first (GShard) and top-1 assignments take queue priority.
    """

    d_model: int
    d_ff: int
    num_experts: int
    capacity_factor: float = 1.25
    top_k: int = 1
    group_size: int | None = None
    # expert SELECTION scores: "sinkhorn" balances them with a few
    # row/column normalisations before the argmax, collapsing dropped
    # tokens (measured on the bench shapes: 7.8% -> ~0 at one iteration,
    # vs 13.5% raw) without the capacity_factor increase that costs
    # active-MFU (cf 2.0 measured 0.32 -> 0.24). Gates still come from
    # the raw softmax probs of the CHOSEN experts, so the differentiable
    # path and the aux losses are unchanged; "aux" is pure Switch/GShard
    # argmax selection. "auto" (default) = sinkhorn for top_k=2, aux for
    # top_k=1: the top-2 gate renormalises over the chosen pair, so a
    # balanced-away expert still combines with weight ~1; top-1's single
    # unnormalised gate would scale such tokens by its (near-zero) raw
    # prob — an uncounted drop — so sinkhorn+top_k=1 is rejected.
    router_balance: str = "auto"
    sinkhorn_iters: int = 3
    # "einsum": GShard one-hot contractions — dispatch/combine are
    # [G,Ng,E,C] matmuls (2*N*E*C*d extra MACs, ~17% of expert compute at
    # the bench shapes). "gather": same routing decisions expressed as row
    # gathers — the queue position already names each token's slot, so
    # dispatch is take_along_axis into [G,E*C,d] (sentinel -> a zero row
    # for unfilled slots / dropped tokens) and combine gathers each
    # token's expert output back and scales by the gate. Identical math
    # (one-hot contractions pick exactly one row), no contraction FLOPs;
    # both paths are differentiable (gather's transpose is scatter-add).
    # MEASURED (v5e, bench shapes, r4): einsum wins decisively — XLA's
    # row gathers run ~7x slower than the one-hot matmuls the MXU eats
    # (5.6 vs 0.8 ms/layer fwd; full rung 164 vs 144 ms) — so einsum
    # stays the default; "gather" is kept as the measured-rejected
    # alternative (it may win on backends with fast gathers).
    dispatch_mode: str = "einsum"
    # capacity == group token count: NO token can overflow (per expert
    # the worst-case queue is the whole group), so nothing drops. Used by
    # the decode tick, where the group is one position's B rows: the
    # [G, Ng, E, C] one-hots are tiny there and the tick is weight-
    # stream-bound, so the E/top_k x FLOP padding is free — while a
    # dropped LIVE token would silently zero a row's MLP output
    # mid-generation. Never for training/prefill shapes (C ~ N is the
    # quadratic dispatch wall).
    full_capacity: bool = False
    # explicit per-group capacity, overriding the capacity_factor formula.
    # The serving admission prefill uses this to route its fixed padded
    # window at the capacity the REAL (unpadded) token count implies —
    # pad tokens claim no queue slot (token_mask), so with the override
    # the real tokens see exactly the standalone prefill's queues
    # (serve.ContinuousBatcher, ADVICE r5's capacity divergence).
    capacity_override: int | None = None
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        kr, ki, ko = jax.random.split(key, 3)
        E, d, f = self.num_experts, self.d_model, self.d_ff
        s_in, s_out = d ** -0.5, f ** -0.5
        return {
            "router": {"kernel": s_in * jax.random.normal(
                kr, (d, E), self.param_dtype)},
            "w_in": s_in * jax.random.normal(ki, (E, d, f), self.param_dtype),
            "b_in": jnp.zeros((E, f), self.param_dtype),
            "w_out": s_out * jax.random.normal(ko, (E, f, d), self.param_dtype),
            "b_out": jnp.zeros((E, d), self.param_dtype),
        }

    def capacity(self, group_tokens: int) -> int:
        if self.full_capacity:
            return group_tokens
        if self.capacity_override is not None:
            return max(int(self.capacity_override), 1)
        c = int(self.capacity_factor * self.top_k * group_tokens
                / self.num_experts)
        return max(c, 1)

    def _dispatch_gather(self, xg, slots, C):
        """Routing decisions -> row gathers (no one-hot contractions).

        Each (token, slot) has a flat destination ``e*C + queue_pos``;
        dropped tokens go to a trash column past the real slots. A scatter
        of token indices inverts that map into ``src [G, E*C]`` (sentinel
        ``Ng`` -> an appended zero row, so unfilled capacity slots read
        zeros exactly like the einsum dispatch), and dispatch is one
        ``take_along_axis``. Returns the dispatched ``[G, E, C, d]`` block
        plus per-slot ``(dst, gate)`` for the combine-side gather. Queue
        positions are collision-free across slots (slot 2 starts after
        slot 1's per-expert assignment count), so one table serves both.
        """
        G, Ng, d = xg.shape
        E = self.num_experts
        tok = jnp.broadcast_to(
            jnp.arange(Ng, dtype=jnp.int32)[None], (G, Ng))
        g_idx = jnp.arange(G, dtype=jnp.int32)[:, None]
        src = jnp.full((G, E * C + 1), Ng, jnp.int32)
        picks = []
        for oh, keep, pos, gate in slots:
            e_n = jnp.argmax(oh, -1).astype(jnp.int32)          # [G, Ng]
            p_n = pos.sum(-1).astype(jnp.int32)                 # [G, Ng]
            kept = keep.sum(-1) > 0                             # [G, Ng]
            dst = jnp.where(kept, e_n * C + p_n, E * C)
            src = src.at[g_idx, dst].set(tok, mode="drop")
            picks.append((dst, gate * kept))
        xpad = jnp.concatenate(
            [xg, jnp.zeros((G, 1, d), xg.dtype)], axis=1)
        xdisp = jnp.take_along_axis(
            xpad, src[:, :E * C, None], axis=1)                 # [G, E*C, d]
        return xdisp.reshape(G, E, C, d), picks

    def apply(self, params, x, token_mask=None, capacity_rows=None):
        """``x [B, T, d]`` -> ``(y [B, T, d], aux)`` where ``aux`` carries
        the load-balancing and router-z losses (fold into the objective as
        ``loss + lb_weight*aux['lb_loss'] + z_weight*aux['z_loss']``).

        ``token_mask`` (``[B, T]``, 1 = real): masked tokens are excluded
        from routing entirely — they claim no expert-capacity queue slot
        (so left-pad tokens can never evict a REAL token when capacity
        binds) and their MoE output is zero (pure residual; pad
        positions' outputs are never consumed). The generation prefill
        passes its prompt mask here; masked tokens count as neither kept
        nor routed in the aux stats, so ``dropped_fraction`` under a mask
        is over-counted by the pad fraction (inference discards aux).

        ``capacity_rows`` (``[G]`` int32, traced): PER-GROUP queue
        capacities, each clamped by the static capacity ``C`` that shapes
        the dispatch one-hots. The serving loop's BATCHED admission
        (``serve.ContinuousBatcher``) routes each cache row as its own
        group with the capacity its REAL prompt length implies — one
        compiled multi-row prefill whose every row keeps exact parity
        with a standalone global-group prefill at that row's capacity
        (the static ``C`` is the wave's max; a row's excess one-hot
        columns past its own capacity are simply never kept)."""
        B, T, d = x.shape
        E = self.num_experts
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {self.top_k}")
        N = B * T
        Ng = self.group_size or N         # tokens per routing group
        if N % Ng:
            raise ValueError(f"group_size {Ng} does not divide {N} tokens")
        G = N // Ng
        C = self.capacity(Ng)
        # per-group effective capacity: keep-decisions use the row's own
        # capacity; the static C only shapes the one-hot queue axis
        cap_eff = (C if capacity_rows is None
                   else jnp.minimum(capacity_rows, C)[:, None, None])
        xg = x.reshape(G, Ng, d)
        mask_g = (None if token_mask is None
                  else token_mask.reshape(G, Ng).astype(jnp.float32))

        logits = jnp.einsum(
            "gnd,de->gne", xg,
            params["router"]["kernel"].astype(x.dtype)
        ).astype(jnp.float32)                                  # [G, Ng, E]
        probs = jax.nn.softmax(logits, -1)

        balance = self.router_balance
        if balance == "auto":
            balance = "sinkhorn" if self.top_k == 2 else "aux"
        elif balance == "sinkhorn" and self.top_k == 1:
            raise ValueError(
                "router_balance='sinkhorn' needs top_k=2: the top-1 gate "
                "is the raw prob of the selected expert, so balanced-away "
                "tokens would be scaled by ~0 (an uncounted drop); use "
                "'auto' or 'aux'")
        if balance == "sinkhorn":
            # balanced SELECTION scores: alternate expert-marginal and
            # token-marginal normalisation (Sinkhorn) so argmax spreads
            # tokens near-uniformly; a stop_gradient keeps the gate path
            # (raw probs of the chosen experts) the only gradient route,
            # same as plain argmax selection
            sel = probs
            target = self.top_k * Ng / E
            for _ in range(self.sinkhorn_iters):
                sel = sel / jnp.maximum(sel.sum(1, keepdims=True),
                                        1e-9) * target
                sel = sel / jnp.maximum(sel.sum(2, keepdims=True), 1e-9)
            sel = jax.lax.stop_gradient(sel)
        elif balance == "aux":
            sel = probs
        else:
            raise ValueError(f"router_balance must be 'auto', 'sinkhorn' "
                             f"or 'aux', got {self.router_balance!r}")

        def slot(scores, prio_count):
            """Route one top-k slot: (onehot, queue position, keep mask,
            gate) — selection by ``scores`` argmax, gate = raw prob of the
            SELECTED expert (differentiable path).

            ``prio_count [G, E]``: expert queue occupancy from higher-
            priority slots — this slot's positions start after it."""
            idx = jnp.argmax(scores, -1)                       # [G, Ng]
            oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [G, Ng, E]
            if mask_g is not None:
                # masked (pad) tokens route nowhere: no queue slot, no
                # gate — the cumsum below then skips them, so real
                # tokens' capacity positions are exactly the solo-run's
                oh = oh * mask_g[..., None]
            pos = (jnp.cumsum(oh, axis=1) - oh) * oh           # [G, Ng, E]
            pos = pos + prio_count[:, None, :] * oh
            keep = (pos < cap_eff) * oh
            gate = jnp.sum(probs * oh, -1)                     # [G, Ng]
            return oh, pos, keep, gate

        oh1, pos1, keep1, gate1 = slot(sel, jnp.zeros((G, E), jnp.float32))
        slots = [(oh1, keep1, pos1, gate1)]
        if self.top_k == 2:
            sel2 = sel * (1.0 - oh1)           # mask the chosen expert
            oh2, pos2, keep2, gate2 = slot(sel2, oh1.sum(axis=1))
            # GShard gate renormalisation over the two chosen experts
            denom = jnp.maximum(gate1 + gate2, 1e-9)
            slots = [(oh1, keep1, pos1, gate1 / denom),
                     (oh2, keep2, pos2, gate2 / denom)]

        if self.dispatch_mode == "gather":
            ein, picks = self._dispatch_gather(xg, slots, C)
        elif self.dispatch_mode == "einsum":
            # dispatch/combine as sums over slots — [G, Ng, E, C] one-hots;
            # memory capacity_factor*top_k*N*Ng (linear in N, fixed groups)
            dispatch = jnp.zeros((G, Ng, E, C), x.dtype)
            combine = jnp.zeros((G, Ng, E, C), x.dtype)
            for _, keep, pos, gate in slots:
                pos_oh = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), C,
                                        dtype=jnp.float32)     # [G, Ng, C]
                piece = keep[..., None] * pos_oh[:, :, None, :]
                dispatch = dispatch + piece.astype(x.dtype)
                combine = combine + (piece * gate[..., None, None]
                                     ).astype(x.dtype)
            ein = jnp.einsum("gnec,gnd->gecd", dispatch, xg)
        else:
            raise ValueError(f"dispatch_mode must be 'einsum' or 'gather', "
                             f"got {self.dispatch_mode!r}")

        # ---- expert compute, sharded over the expert axis ----
        # checkpoint_name tags: under remat="dots" these matmul outputs are
        # saved, so the backward recomputes only the routing one-hots and
        # gelu — no expert matmul runs twice (parallel/pipeline.py
        # SAVED_MATMUL_NAMES)
        from jax.ad_checkpoint import checkpoint_name
        ein = _constrain(ein, P(None, "expert", None, None))
        ein = checkpoint_name(ein, "moe_ein")
        h = jnp.einsum("gecd,edf->gecf", ein,
                       params["w_in"].astype(x.dtype))
        h = checkpoint_name(
            h + params["b_in"].astype(x.dtype)[None, :, None, :],
            "moe_hpre")
        h = jax.nn.gelu(h)
        out = jnp.einsum("gecf,efd->gecd", h,
                         params["w_out"].astype(x.dtype))
        out = out + params["b_out"].astype(x.dtype)[None, :, None, :]
        out = _constrain(out, P(None, "expert", None, None))
        out = checkpoint_name(out, "moe_out")

        if self.dispatch_mode == "gather":
            # EP caveat: reshape(G, E*C, d) COLLAPSES the 'expert'-
            # constrained axis before the per-token gathers, so under an
            # expert-sharded mesh the partitioner all-gathers every
            # expert's output to every device each layer — numerically
            # right (the EP test pins it) but it defeats expert-parallel
            # scaling. The einsum combine keeps the contraction on the
            # sharded axis (a psum-style all-to-all instead). Another
            # reason gather mode stays the measured-rejected alternative;
            # reshard explicitly here before ever enabling it on an EP
            # mesh.
            outp = jnp.concatenate(
                [out.reshape(G, E * C, d),
                 jnp.zeros((G, 1, d), x.dtype)], axis=1)
            y = jnp.zeros((G, Ng, d), x.dtype)
            for dst, gate in picks:
                pick = jnp.take_along_axis(outp, dst[..., None], axis=1)
                y = y + pick * gate.astype(x.dtype)[..., None]
        else:
            y = jnp.einsum("gnec,gecd->gnd", combine, out)

        # Switch aux losses over top-1 assignments (float32 for stability)
        frac_tokens = oh1.mean((0, 1))                         # [E]
        frac_probs = probs.mean((0, 1))                        # [E]
        lb_loss = E * jnp.sum(frac_tokens * frac_probs)
        z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
        kept = sum(keep.sum() for _, keep, _, _ in slots)
        dropped = 1.0 - kept / (N * len(slots))
        aux = {"lb_loss": lb_loss, "z_loss": z_loss,
               "dropped_fraction": dropped}
        return y.reshape(B, T, d), aux


# What ``dcp_held_experts`` (``ops/pallas/held_experts.py``) takes to stream
# the weights of EVERY held expert over what ``HeldExperts._dense`` takes, so
# the kernel is the faster form where a call's rows are expected to choose a
# smaller share of the experts than this. Alone on a v5e, every expert
# chosen, bfloat16: 36 experts of 4096 x 2048 over 32 rows 2.46-2.54 ms
# against 2.51 (0.98-1.01 over blocks of 1, 2 and 4 MB), 16 of 2048 x 2048
# over 20 rows 0.566 against 0.564 (1.00), 32 of 2048 x 768 over 64 rows
# 0.427 against 0.454 (0.94); with half chosen 0.52, 0.53, 0.50 of
# ``_dense`` (PERF.md, PR 46). Set at the low end of those readings: above
# it a tick's rows choose all but a few percent of the experts, and there is
# a reading's own spread to lose and nothing to win.
CHOSEN_STREAM_RATIO = 0.95


def _chosen_kernel_ok(d_model: int, d_ff: int) -> bool:
    """Whether a tick's held experts may run as the Pallas kernel over the
    chosen experts: one TPU (no mesh context, as the pool's kernels and the
    KDA kernels: ``ops/attention.py::_kda_kernel_ok``) and both widths a
    whole number of lane tiles."""
    return (jax.default_backend() == "tpu" and current_mesh() is None
            and d_model % 128 == 0 and d_ff % 128 == 0)


@dataclass(frozen=True)
class SigmoidRouter:
    """The router of :class:`HeldExperts` that is ONE matrix (the
    aux-loss-free form): ``s = sigmoid(W_r h)`` in float32 over all
    experts; the ``top_k`` with the largest ``s_e + b_e`` (``b`` a
    selection bias that does not enter the weights); ``w_e = routed_scale
    * s_e / sum_{e' in T} s_e'`` when ``norm_topk_prob``. It carries no
    state from layer to layer."""

    d_model: int
    num_experts: int
    top_k: int
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        d = self.d_model
        return {"router": {"kernel": jax.random.uniform(
                    key, (d, self.num_experts), self.param_dtype,
                    -d ** -0.5, d ** -0.5)},
                "router_bias": jnp.zeros((self.num_experts,), jnp.float32)}

    def route(self, params, x, state=None):
        logits = jnp.dot(x, params["router"]["kernel"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s + params["router_bias"].astype(jnp.float32), self.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.norm_topk_prob:
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx.astype(jnp.int32), w * self.routed_scale, state


@dataclass(frozen=True)
class SoftmaxRouter:
    """The router of :class:`HeldExperts` that is one matrix behind a
    SOFTMAX (the Qwen3-MoE form): ``p = softmax(W_r h)`` in float32 over
    ALL experts, the ``top_k`` largest, ``w_e = p_e / sum_{e' in T} p_e'``
    when ``norm_topk_prob`` (else the probabilities as they are), times
    ``routed_scale``. No selection bias, no state."""

    d_model: int
    num_experts: int
    top_k: int
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        d = self.d_model
        return {"router": {"kernel": jax.random.uniform(
            key, (d, self.num_experts), self.param_dtype,
            -d ** -0.5, d ** -0.5)}}

    def route(self, params, x, state=None):
        logits = jnp.dot(x, params["router"]["kernel"].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), self.top_k)
        if self.norm_topk_prob:
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx.astype(jnp.int32), w * self.routed_scale, state


@dataclass(frozen=True)
class MLPRouter:
    """A router that is a small NETWORK with state carried from layer to
    layer (the ZAYA1 recipe): ``rs = W_d h + c_d + gamma * rs_prev``
    (``hidden`` wide; ``rs_prev`` the state the layer below handed up,
    none into the first layer: depth averaging), ``z = RMSNorm(rs)``, ``l
    = W_3 gelu(W_2 gelu(W_1 z + c_1) + c_2)``, ``p = softmax(l)`` over
    ``num_choices``, the ONE choice ``argmax(p + b)`` (``b`` a selection
    bias) with the weight ``p`` of that choice, not renormalised. A choice
    no chip holds (a skip) is :class:`HeldExperts`' business, not the
    router's. ``gamma`` is stored as its offset from 0.5.

    ``W_d`` multiplies in the activations' type into float32; the state
    and everything after it stay in float32 at the highest matmul
    precision (three products ``hidden`` wide: the argmax over a softmax
    has near-ties that one bfloat16 pass would decide)."""

    d_model: int
    hidden: int
    num_choices: int
    eps: float = 1e-5
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        d, R, pd = self.d_model, self.hidden, self.param_dtype
        ks = jax.random.split(key, 4)
        u = lambda k, shape: jax.random.uniform(
            k, shape, pd, -shape[0] ** -0.5, shape[0] ** -0.5)
        lin = lambda k, i, o: {"kernel": u(k, (i, o)),
                               "bias": jnp.zeros((o,), pd)}
        return {"router": {"down": lin(ks[0], d, R),
                           "gamma": jnp.zeros((R,), pd),
                           "norm": {"scale": jnp.ones((R,), jnp.float32)},
                           "fc1": lin(ks[1], R, R), "fc2": lin(ks[2], R, R),
                           "out": {"kernel": u(ks[3], (R, self.num_choices))}},
                "router_bias": jnp.zeros((self.num_choices,), jnp.float32)}

    def route(self, params, x, state=None):
        r = params["router"]
        f32 = lambda a: a.astype(jnp.float32)
        mm = lambda a, w: jnp.dot(a, f32(w),
                                  precision=jax.lax.Precision.HIGHEST)
        rs = jnp.dot(x, r["down"]["kernel"].astype(x.dtype),
                     preferred_element_type=jnp.float32) + f32(
                         r["down"]["bias"])
        if state is not None:
            rs = rs + (0.5 + f32(r["gamma"])) * state
        z = rs * jax.lax.rsqrt(
            jnp.mean(rs * rs, -1, keepdims=True) + self.eps) * f32(
                r["norm"]["scale"])
        gelu = lambda a: jax.nn.gelu(a, approximate=True)
        z = gelu(mm(z, r["fc1"]["kernel"]) + f32(r["fc1"]["bias"]))
        z = gelu(mm(z, r["fc2"]["kernel"]) + f32(r["fc2"]["bias"]))
        probs = jax.nn.softmax(mm(z, r["out"]["kernel"]), axis=-1)
        idx = jnp.argmax(probs + f32(params["router_bias"]), axis=-1)
        w = jnp.take_along_axis(probs, idx[:, None], axis=-1)
        return idx[:, None].astype(jnp.int32), w, rs


@dataclass(frozen=True)
class HeldExperts:
    """Dropless routed experts with a shared expert, as ONE chip of an
    expert-parallel set sees them: the router is ``num_experts`` wide,
    this chip is told which experts it holds (``experts_held = (first,
    count)``) and computes only the assignments that fall on them, plus
    the shared expert. The result is this chip's partial sum; on one chip
    there is no exchange, and nothing here stands in for the other chips.

    Routing (the aux-loss-free form): ``s = sigmoid(W_r h)`` in float32
    over all experts; the ``top_k`` experts with the largest ``s_e + b_e``
    (``b`` a per-expert selection bias that does not enter the weights);
    ``w_e = routed_scale * s_e / sum_{e' in T} s_e'`` when
    ``norm_topk_prob``; every expert a SwiGLU. No capacity: no token is
    dropped whatever the skew. That router is the default
    (:class:`SigmoidRouter`, built from the fields below); ``router`` takes
    another part with its own parameters and its own ``route(params, x,
    state) -> (idx, w, state)`` (:class:`SoftmaxRouter`: a softmax over all
    experts, its top-k renormalised; :class:`MLPRouter`: a network with
    state carried from layer to layer, which :meth:`apply_with_state`
    threads).
    ``skip_index`` names a choice of the router that NO chip holds (a
    token sent there gets a zero output at no cost, which is what ``held``
    already means) so that the counts can tell it from an expert held
    elsewhere.

    One algorithm, three executions chosen by what is static at trace time
    (:meth:`form`: the tokens of the call, the router's shape, the
    backend; no option chooses). Up to ``dense_max_tokens`` tokens (a
    decode tick: a handful of tokens an expert, bound by the weight
    stream) every held expert runs over every token under its weight, zero
    where the token did not pick it: a batched product that reads every
    held expert's weights once (``_dense``), or, where the rows are
    expected to choose few enough of the held experts
    (:meth:`expected_chosen_share` under ``CHOSEN_STREAM_RATIO``) and a
    kernel may run (:func:`_chosen_kernel_ok`), ONE kernel that reads the
    weights of the experts some unmasked row chose and of no other
    (``_chosen``: ``ops/pallas/held_experts.py``). Above (an admission
    wave) the held assignments are sorted by expert and go through grouped
    matrix products (``lax.ragged_dot``) in windows of a static number of
    rows: one window of ``window_rows`` when the load is near uniform, and
    what it leaves, under skew, in windows of ``tail_rows`` (an eighth of
    it), so the cost follows the assignments and never the worst case
    (``_sorted``). ``token_mask`` (1 = real) keeps pad tokens and parked
    rows out: they route nowhere and choose no expert.
    """

    d_model: int
    d_ff: int                       # width of one routed expert
    num_experts: int                # the router's width
    top_k: int
    experts_held: tuple = None      # (first, count); None = all of them
    shared_d_ff: int = 0            # 0 = no shared expert
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    dense_max_tokens: int = 512
    param_dtype: jnp.dtype = jnp.float32
    router: object = None           # None = SigmoidRouter of the fields above
    skip_index: int | None = None   # a choice of the router no chip holds
    # every SwiGLU is silu(min(gate, l)) * clip(up, -l, l) (0: unclamped)
    swiglu_limit: float = 0.0

    def _router(self):
        return self.router or SigmoidRouter(
            self.d_model, self.num_experts, self.top_k, self.routed_scale,
            self.norm_topk_prob, self.param_dtype)

    @property
    def held(self) -> tuple:
        first, count = self.experts_held or (0, self.num_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"experts_held {self.experts_held} outside the "
                f"{self.num_experts} experts of the router")
        return int(first), int(count)

    def init(self, key):
        _, n = self.held
        d, f, pd = self.d_model, self.d_ff, self.param_dtype
        ks = jax.random.split(key, 8)
        u = lambda k, shape, fan_in: jax.random.uniform(
            k, shape, pd, -fan_in ** -0.5, fan_in ** -0.5)
        p = {**self._router().init(ks[0]),
             "experts": {"gate": u(ks[1], (n, d, f), d),
                         "up": u(ks[2], (n, d, f), d),
                         "down": u(ks[3], (n, f, d), f)}}
        if self.shared_d_ff:
            sf = self.shared_d_ff
            p["shared"] = {"gate": {"kernel": u(ks[4], (d, sf), d)},
                           "up": {"kernel": u(ks[5], (d, sf), d)},
                           "down": {"kernel": u(ks[6], (sf, d), sf)}}
        return p

    def route(self, params, x, state=None):
        """``x [N, d]`` -> (experts ``[N, k]`` int32, weights ``[N, k]``
        float32, the router's state to hand up), over the whole router."""
        with scope("router"):
            return self._router().route(params, x, state)

    def window_rows(self, n_tokens: int) -> int:
        """Rows of one window of the sorted form: an eighth more than a
        uniform router sends to the held experts, in whole 512s."""
        _, n = self.held
        expect = n_tokens * self.top_k * n / self.num_experts
        return int(-(-(1.125 * expect) // 512) * 512)

    def tail_rows(self, n_tokens: int) -> int:
        """Rows of the windows after the first: an eighth of it, in whole
        512s. The grouped products skip the row tiles no group covers, but
        a window's gather, activation and scatter-add run over all its
        rows: a layer whose held share lies just over the first window's
        edge paid for two whole windows (on the v5e half as much again for
        a 16,384-token dispatch: PERF.md, PR 32) where it now pays for an
        eighth more."""
        return max(self.window_rows(n_tokens) // 8 // 512 * 512, 512)

    def _dense(self, ex, x, local, w):
        """Every held expert over every token: ``[n, N, d]`` products
        under the token's weight for that expert (0 where not chosen)."""
        _, n = self.held
        onehot = (local[:, :, None] == jnp.arange(n)[None, None, :])
        we = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)  # [N, n]
        xe = jnp.broadcast_to(x[None], (n,) + x.shape)
        mm = lambda a, b: jnp.einsum("enk,ekf->enf", a, b.astype(a.dtype),
                                     preferred_element_type=jnp.float32)
        h = L.clamped_swiglu(mm(xe, ex["gate"]), lambda: mm(xe, ex["up"]),
                             self.swiglu_limit).astype(x.dtype)
        y = mm(h, ex["down"])                                    # [n, N, d]
        return jnp.einsum("end,ne->nd", y, we).astype(x.dtype)

    def _chosen(self, ex, x, local, w):
        """``_dense`` from the weights of the experts some assignment fell
        on, and of no other: one kernel over their compacted list."""
        from distributed_compute_pytorch_tpu.ops.pallas.held_experts import (
            held_experts_chosen)
        return held_experts_chosen(ex["gate"], ex["up"], ex["down"], x,
                                   local, w, swiglu_limit=self.swiglu_limit)

    def expected_chosen_share(self, n_tokens: int) -> float:
        """Share of the experts that ``n_tokens`` tokens send at least one
        assignment to under a uniform router: ``1 - (1 - k / E) ^ N``."""
        return 1.0 - (1.0 - self.top_k / self.num_experts) ** n_tokens

    def form(self, n_tokens: int) -> str:
        """The execution a call of ``n_tokens`` tokens takes: ``"sorted"``
        above ``dense_max_tokens``; below, ``"chosen"`` where the tokens
        are expected to choose a smaller share of the experts than the
        kernel's stream costs over ``_dense``'s and the kernel may run,
        else ``"dense"``."""
        if n_tokens > self.dense_max_tokens:
            return "sorted"
        if (self.expected_chosen_share(n_tokens) < CHOSEN_STREAM_RATIO
                and _chosen_kernel_ok(self.d_model, self.d_ff)):
            return "chosen"
        return "dense"

    def _sorted(self, ex, x, local, w):
        """Held assignments sorted by expert, grouped products over one
        window of ``window_rows`` sorted rows and, for what it leaves,
        windows of ``tail_rows``, scatter-added back."""
        _, n = self.held
        N, k = local.shape
        A = N * k
        C = min(self.window_rows(N), -(-A // 8) * 8)
        Ct = min(self.tail_rows(N), C)
        key = local.reshape(A)                 # n = not held / pad: last
        order = jnp.argsort(key, stable=True)
        tok = (order // k).astype(jnp.int32)
        ws = w.reshape(A)[order]
        sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        total = ends[-1]
        # the windows read [s, s + C) of the sorted order: pad it so the
        # last one never clamps back over rows already done
        tok = jnp.concatenate([tok, jnp.zeros((C,), jnp.int32)])
        ws = jnp.concatenate([ws, jnp.zeros((C,), ws.dtype)])
        rd = lambda a, b, g: jax.lax.ragged_dot(
            a, b.astype(a.dtype), g, preferred_element_type=jnp.float32)

        def window(rows):
            def body(carry):
                s, out = carry
                t = jax.lax.dynamic_slice(tok, (s,), (rows,))
                wt = jax.lax.dynamic_slice(ws, (s,), (rows,))
                live = (s + jnp.arange(rows)) < total
                g = (jnp.clip(ends - s, 0, rows)
                     - jnp.clip(ends - sizes - s, 0, rows)).astype(jnp.int32)
                xg = x[t]
                h = L.clamped_swiglu(
                    rd(xg, ex["gate"], g), lambda: rd(xg, ex["up"], g),
                    self.swiglu_limit).astype(x.dtype)
                y = rd(h, ex["down"], g) * jnp.where(live, wt, 0.0)[:, None]
                # rows past the window's groups are unspecified by
                # ragged_dot
                y = jnp.where(live[:, None], y, 0.0).astype(out.dtype)
                return s + rows, out.at[t].add(y)
            return body

        first = jax.lax.cond(total > 0, window(C), lambda c: c,
                             (jnp.int32(0), jnp.zeros_like(x)))
        _, out = jax.lax.while_loop(lambda c: c[0] < total, window(Ct),
                                    first)
        return out

    def apply(self, params, x, token_mask=None, counts_sink=None):
        """``x [..., d]`` -> this chip's partial ``m`` of the same shape
        (:meth:`apply_with_state` for a router without state)."""
        return self.apply_with_state(params, x, None, token_mask,
                                     counts_sink)[0]

    def apply_with_state(self, params, x, state, token_mask=None,
                         counts_sink=None):
        """``x [..., d]``, the router state ``[..., R]`` of the layer below
        (None: none, or the first layer) -> (this chip's partial ``m``, the
        state this layer's router hands up).
        ``counts_sink`` (a list) is handed one int32 vector ``[4 +
        count]``: the assignments of the unmasked tokens, those among
        them that fell on held experts, the held experts at least one of
        them fell on, the held experts (``count``: summed over layers and
        ticks, what the chosen are a share of), and the held experts'
        loads; with a ``skip_index``, ``[5 + count]``: those that fell on
        the skip choice come third."""
        first, n = self.held
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        if state is not None:
            state = state.reshape(x.shape[0], -1)
        idx, w, state = self.route(params, x, state)
        local = idx - first
        on = (local >= 0) & (local < n)
        if token_mask is not None:
            on = on & (token_mask.reshape(-1, 1) > 0.5)
        local = jnp.where(on, local, n)
        if counts_sink is not None:
            live = (jnp.ones((x.shape[0],), jnp.int32) if token_mask is None
                    else (token_mask.reshape(-1) > 0.5).astype(jnp.int32))
            load = jnp.bincount(local.reshape(-1), length=n + 1)[:n]
            head = [jnp.sum(live) * self.top_k, jnp.sum(load)]
            if self.skip_index is not None:
                head.append(jnp.sum((idx == self.skip_index)
                                    * live[:, None]))
            head += [jnp.sum(load > 0), n]
            counts_sink.append(jnp.concatenate(
                [jnp.stack(head), load]).astype(jnp.int32))
        with scope("experts"):
            form = getattr(self, "_" + self.form(x.shape[0]))
            y = form(params["experts"], x, local, w)
        if self.shared_d_ff:
            with scope("shared_expert"):
                sp = params["shared"]
                mm = lambda a, b: jnp.dot(a, b["kernel"].astype(a.dtype))
                y = y + mm(L.clamped_swiglu(
                    mm(x, sp["gate"]), lambda: mm(x, sp["up"]),
                    self.swiglu_limit), sp["down"])
        if state is not None:
            state = state.reshape(shape[:-1] + state.shape[-1:])
        return y.reshape(shape), state


@dataclass(frozen=True)
class MoETransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    num_experts: int = 8
    capacity_factor: float = 1.25
    # INFERENCE capacity factor for the generation PREFILL (decode ticks
    # are always full-capacity/no-drop — MoEBlock docstring). None =
    # max(2.0, capacity_factor), the GShard eval convention.
    eval_capacity_factor: float | None = None
    top_k: int = 1                 # 1 = Switch, 2 = GShard top-2
    moe_group_size: int | None = None  # routing group tokens (None = global)
    router_balance: str = "auto"       # balanced selection (see MoELayer)
    sinkhorn_iters: int = 3
    dispatch_mode: str = "einsum"      # einsum | gather (see MoELayer)
    lb_weight: float = 0.01
    z_weight: float = 1e-3
    dropout_rate: float = 0.0
    # rematerialise blocks on backward: True/"block" per-block, or "stage"
    # (per-pipeline-stage tick, the 1F1B memory profile — pipe meshes)
    remat: bool | str = False
    pipeline_microbatches: int | None = None   # GPipe M (None = pipe size)
    # Megatron interleaved schedule (parallel/pipeline.py)
    virtual_stages: int = 1
    unroll_layers: bool = True     # python-loop blocks (see GPT2Config)
    param_dtype: jnp.dtype = jnp.float32

    @classmethod
    def tiny(cls) -> "MoETransformerConfig":
        return cls(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                   d_model=64, d_ff=128, num_experts=4)


@dataclass(frozen=True)
class MoEBlock:
    """One MoE transformer block, serving BOTH step contracts.

    Training/scan/pipeline contract: ``apply(p, x, rng=, train=,
    manual_axes=) -> (x, aux)``. Generation contract (``infer.py:23-27``):
    ``apply(..., kv_sink=, kv_mask=)`` for prefill capture and
    ``decode_step(p, x, cache, pos, slot_mask=)`` for cached ticks.

    **Inference routing** (prefill — marked by ``kv_sink`` — and decode)
    selects experts by per-token argmax of the router probs:

    - Sinkhorn selection normalises scores ACROSS the routing group, so a
      token's expert assignment depends on the other tokens in its group —
      including FUTURE positions. That is a legitimate load-balancing
      device under teacher forcing (the gates, the only gradient path,
      stay per-token) but acausal for autoregressive decode, where future
      tokens don't exist yet. Per-token argmax is the standard
      Switch/GShard serving rule and is position-independent, so cached
      decode equals the full forward exactly for argmax-selection configs
      (``tests/test_moe_generate.py``); sinkhorn-trained models generate
      with argmax serving like everyone else's.
    - **Decode ticks never drop a token**: the tick's routing group is
      one position's B rows and capacity is the full group
      (``MoELayer.full_capacity`` — the one-hots are tiny there and the
      tick is weight-stream-bound, so the padding is free), because a
      capacity-dropped LIVE token would silently zero a row's MLP
      output mid-generation.
    - **Prefill keeps the config's routing groups** when
      ``moe_group_size`` divides the prompt tokens (otherwise one global
      group): a serving-scale prefill's dispatch one-hots are
      ``~cf*top_k*N*Ng`` elements, and a forced global group (Ng=N)
      would be the quadratic GShard wall the training path avoids.
      Capacity uses ``eval_capacity_factor`` (default: the larger of
      2.0 — the GShard eval convention — and the training factor).

    Expert parallelism at decode: the dispatched ``[1, E, C, d]`` tick
    block carries the same ``P(None, 'expert', None, None)`` pin as
    training, so on an ``expert``-sharded mesh the partitioner inserts
    the per-tick all-to-all and each device runs only its experts' FFNs.
    """

    config: MoETransformerConfig

    def _moe(self) -> MoELayer:
        c = self.config
        return MoELayer(c.d_model, c.d_ff, c.num_experts, c.capacity_factor,
                        top_k=c.top_k, group_size=c.moe_group_size,
                        router_balance=c.router_balance,
                        sinkhorn_iters=c.sinkhorn_iters,
                        dispatch_mode=c.dispatch_mode,
                        param_dtype=c.param_dtype)

    def _moe_infer(self, n_tokens: int, decode: bool,
                   capacity_override: int | None = None,
                   group_size: int | None = None) -> MoELayer:
        """Inference-routing layer (argmax selection; class docstring):
        full-capacity single group for decode ticks, grouped +
        eval-capacity for prefill. ``capacity_override`` (the serving
        admission path) pins the queue capacity explicitly — and, absent
        an explicit ``group_size``, forces a single global group, because
        the override expresses "route these ``n_real`` tokens as a
        standalone global-group prefill would" and per-group boundaries
        over a padded window cannot line up with the unpadded run's.
        The serving loop's BATCHED admission passes ``group_size`` = its
        prompt window so each cache row is its own group (with ITS
        capacity via ``MoELayer.apply(capacity_rows=…)``) — rows never
        share expert queues, which is what keeps every row's routing
        identical to its standalone prefill's."""
        c = self.config
        group = group_size
        if (group is None and capacity_override is None and not decode
                and c.moe_group_size and n_tokens % c.moe_group_size == 0):
            group = c.moe_group_size
        ecf = (c.eval_capacity_factor
               if c.eval_capacity_factor is not None
               else max(2.0, c.capacity_factor))
        return MoELayer(
            c.d_model, c.d_ff, c.num_experts, ecf,
            top_k=c.top_k, group_size=group, router_balance="aux",
            dispatch_mode=c.dispatch_mode, full_capacity=decode,
            capacity_override=capacity_override,
            param_dtype=c.param_dtype)

    def prefill_capacity(self, n_tokens: int) -> int:
        """Expert queue capacity a STANDALONE global-group prefill of
        ``n_tokens`` real tokens would use — what the serving admission
        passes back as ``moe_capacity`` so its fixed padded window routes
        at the real prompt's capacity (``serve.ContinuousBatcher``)."""
        return self._moe_infer(max(n_tokens, 1),
                               decode=False).capacity(max(n_tokens, 1))

    def init(self, key):
        c = self.config
        ks = jax.random.split(key, 4)
        pd = c.param_dtype
        d = c.d_model
        return {
            "ln1": L.LayerNorm(d).init(None),
            "qkv": L.Dense(d, 3 * d, param_dtype=pd).init(ks[0]),
            "attn_out": L.Dense(d, d, param_dtype=pd).init(ks[1]),
            "ln2": L.LayerNorm(d).init(None),
            "moe": self._moe().init(ks[2]),
        }

    def apply(self, p, x, *, rng=None, train: bool = False, kv_mask=None,
              manual_axes=(), kv_sink=None, moe_capacity=None,
              moe_capacity_rows=None, kv_prefix=None):
        from distributed_compute_pytorch_tpu.models.transformer import (
            attention_sublayer)
        c = self.config
        d = c.d_model
        h = L.LayerNorm(d).apply(p["ln1"], x)
        # shared attention half (flash kernel on TPU, ring attention on a
        # seq>1 mesh — same dispatch as the dense blocks). kv_prefix is
        # accepted for the shared prefill contract but the serving layer
        # refuses prefix caching for MoE models: routing is
        # group-dependent, so a suffix-only routing group cannot
        # reproduce the standalone full-prompt queues when capacity
        # binds (the attention math itself would be exact).
        a = attention_sublayer(p, h, num_heads=c.num_heads, causal=True,
                               dropout_rate=c.dropout_rate, rng=rng,
                               train=train, manual_axes=manual_axes,
                               kv_mask=kv_mask, kv_sink=kv_sink,
                               kv_prefix=kv_prefix)
        x = x + a
        h = L.LayerNorm(d).apply(p["ln2"], x)
        if kv_sink is not None:
            # generation-prefill pass -> inference routing (argmax
            # selection, eval capacity; see class docstring). The prompt
            # mask keeps left-pad tokens out of the routing queues so
            # they can never evict a real token when capacity binds.
            # ``moe_capacity`` (static int; the serving admission) pins
            # the queue capacity to the REAL token count's instead of
            # deriving it from the padded window size. A batched
            # admission wave (B > 1 rows) routes each row as its own
            # group at its own capacity (``moe_capacity_rows`` [B],
            # traced; the static value is the wave max) — for B == 1,
            # group_size == T is exactly the old single global group.
            B, T, _ = h.shape
            moe = self._moe_infer(
                B * T, decode=False, capacity_override=moe_capacity,
                group_size=(T if moe_capacity is not None else None))
            y, aux = moe.apply(p["moe"], h, token_mask=kv_mask,
                               capacity_rows=moe_capacity_rows)
        else:
            y, aux = self._moe().apply(p["moe"], h)
        return x + y, aux

    def decode_step(self, p, x, cache, pos, slot_mask=None):
        """One KV-cached decode tick, ``x [B, 1, d]`` at slot ``pos``
        (scalar, or ``[B]`` for per-row decode positions):
        the shared attention tick (``transformer.attention_decode_tick``)
        plus the tick's B tokens routed as one full-capacity group
        through the experts (no live token ever drops — class
        docstring)."""
        from distributed_compute_pytorch_tpu.models.transformer import (
            attention_decode_tick)
        c = self.config
        x, cache = attention_decode_tick(p, x, cache, pos,
                                         num_heads=c.num_heads,
                                         slot_mask=slot_mask)
        h = L.LayerNorm(c.d_model).apply(p["ln2"], x)
        y, _aux = self._moe_infer(x.shape[0], decode=True).apply(p["moe"], h)
        return x + y, cache


@dataclass(frozen=True)
class MoETransformerLM:
    """Decoder-only LM whose every block uses a Switch-MoE MLP.

    Same skeleton as GPT-2 (pre-LN, fused-QKV causal attention, tied
    readout) with the dense MLP swapped for :class:`MoELayer`; blocks are
    stacked and scanned with the aux losses accumulated through the scan
    carry — or pipelined over a ``pipe`` axis, where the GPipe schedule
    carries the aux sums (``pipeline_blocks(aux_init=...)``) and averages
    them over microbatches. Composes with data/fsdp/tensor/expert (and,
    through the manual-region attention dispatch, ``seq``); serves
    through ``infer.py`` like the dense families (expert-parallel decode,
    see :class:`MoEBlock`).
    """

    config: MoETransformerConfig = MoETransformerConfig()

    def _block(self) -> MoEBlock:
        return MoEBlock(self.config)

    def init(self, key):
        c = self.config
        from distributed_compute_pytorch_tpu.parallel.pipeline import (
            stacked_layers)
        ks = jax.random.split(key, c.num_layers + 2)
        wte = L.Embedding(c.vocab_size, c.d_model, param_dtype=c.param_dtype)
        wpe = L.Embedding(c.max_seq_len, c.d_model,
                          param_dtype=c.param_dtype, init_std=0.01)
        block = self._block()
        params = {
            "wte": wte.init(ks[0]),
            "wpe": wpe.init(ks[1]),
            "blocks": stacked_layers(
                [block.init(ks[2 + i]) for i in range(c.num_layers)]),
            "ln_f": L.LayerNorm(c.d_model).init(None),
        }
        return params, {}

    # --- generation contract (infer.py:23-27), same as GPT-2's ---

    def embed(self, params, tokens, positions=None):
        """Token + learned-position embeddings; ``positions`` defaults to
        ``arange(T)`` (decode passes the cache position, ``infer.py``)."""
        c = self.config
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        return (L.Embedding(c.vocab_size, c.d_model).apply(params["wte"],
                                                           tokens)
                + L.Embedding(c.max_seq_len, c.d_model).apply(params["wpe"],
                                                              positions))

    def readout(self, params, x):
        """Final LayerNorm + weight-tied readout (entry pin per
        ``core.mesh.constrain_activations`` block-boundary discipline)."""
        from distributed_compute_pytorch_tpu.core.mesh import (
            constrain_activations)
        c = self.config
        x = constrain_activations(x)
        x = L.LayerNorm(c.d_model).apply(params["ln_f"], x)
        return L.Embedding(c.vocab_size, c.d_model).attend(params["wte"], x)

    def kv_cache_spec(self):
        """(num_kv_heads, head_dim) a decode cache must hold per layer."""
        c = self.config
        return c.num_heads, c.d_model // c.num_heads

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        c = self.config
        x = self.embed(params, tokens)
        L_n = c.num_layers
        from distributed_compute_pytorch_tpu.core.mesh import current_mesh
        from distributed_compute_pytorch_tpu.parallel.pipeline import (
            pipeline_blocks, scan_blocks)

        block = self._block()
        mesh = current_mesh()
        zeros = {"lb_loss": 0.0, "z_loss": 0.0, "dropped_fraction": 0.0}
        if (mesh is not None and "pipe" in mesh.axis_names
                and mesh.shape["pipe"] > 1):
            # GPipe path: the pipeline sums aux over layers and averages
            # it over microbatches (exactly the scanned full-batch value
            # for these mean-based metrics when moe_group_size divides the
            # microbatch's tokens). MoEBlock.apply's signature already
            # fits the pipeline's block contract.
            x, aux = pipeline_blocks(
                block.apply, params["blocks"], x, mesh,
                num_microbatches=c.pipeline_microbatches, rng=rng,
                train=train, remat=c.remat, aux_init=zeros,
                virtual_stages=c.virtual_stages)
        else:
            x, aux = scan_blocks(
                block.apply, params["blocks"], x, rng=rng,
                train=train, remat=c.remat, unroll=c.unroll_layers,
                aux_init=zeros)
        lb, z, dr = (aux["lb_loss"], aux["z_loss"],
                     aux["dropped_fraction"])
        logits = self.readout(params, x)
        self_aux = {"lb_loss": lb / L_n, "z_loss": z / L_n,
                    "dropped_fraction": dr / L_n}
        return (logits, self_aux), state

    # --- step.py train protocol (owns its objective: aux losses) ---

    def train_loss(self, params, model_state, tokens, targets, rng,
                   train: bool = True):
        del targets
        (logits, aux), new_state = self.apply(params, model_state, tokens,
                                              train=train, rng=rng)
        c = self.config
        ce = L.cross_entropy_with_logits(logits[:, :-1], tokens[:, 1:],
                                         "mean")
        loss = ce + c.lb_weight * aux["lb_loss"] + c.z_weight * aux["z_loss"]
        return loss, new_state

    def eval_metrics(self, out, tokens, valid=None):
        logits, _ = out
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        tgt = tokens[:, 1:]
        per_tok = L.cross_entropy_with_logits(logits[:, :-1], tgt, "none")
        return L.token_eval_metrics(per_tok, pred == tgt, valid)

    def partition_rules(self):
        """Expert weights: layer dim (stacked) + expert dim over ``expert``;
        attention kernels follow the Megatron TP layout."""
        return (
            (r"blocks/moe/(w_in|w_out|b_in|b_out)$", P("pipe", "expert")),
            (r"blocks/moe/router/kernel$", P("pipe")),
            (r"blocks/qkv/kernel$", P("pipe", "fsdp", "tensor")),
            (r"blocks/qkv/bias$", P("pipe", "tensor")),
            (r"blocks/attn_out/kernel$", P("pipe", "tensor", "fsdp")),
            (r"blocks/", P("pipe")),
            (r"embedding$", P("fsdp", "tensor")),
        )
