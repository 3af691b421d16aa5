"""A decoder built from a list of layer kinds: layer ``i`` is a (mixer,
feed-forward) pair chosen by ``layer_types[i]`` and ``mlp_layer_types[i]``
(ROADMAP C6: a hybrid is a configuration, not a file of its own).

Mixers: ``"full_attention"`` (causal over the whole context),
``"sliding_attention"`` (causal over the last ``sliding_window`` tokens,
the token itself counted) and ``"latent_attention"`` (below).
Feed-forwards: ``"dense"`` (SwiGLU) and
``"sparse"`` (routed experts with a shared expert, of which this chip
holds ``experts_held``: ``models/moe.py::HeldExperts``). The rest is the
Llama recipe (``models/llama.py``: bias-free q/k/v/o at GQA width,
half-split RoPE, RMSNorm, untied head) with three switches a published
family sets: ``qk_norm`` (an RMSNorm over each head's channels of q and
of k, before any rotation), ``rope_sliding_only`` (full layers rotate
nothing) and ``norm_placement``: ``"post"`` norms each sublayer's OUTPUT
(``h = h + norm(sublayer(h))``, no norm on its input), ``"pre"`` its
INPUT (``h = h + sublayer(norm(h))``, the DeepSeek-V3 recipe).

LATENT attention (MLA, the DeepSeek-V2/V3 recipe): queries through a
low-rank bottleneck with its own norm (``q_down``, ``q_norm``, ``q_up``);
keys and values through ONE joint down-projection ``kv_down`` to
``kv_lora_rank`` compressed channels (normed, ``kv_norm``) plus a rotary
key of ``qk_rope_head_dim`` channels that all heads share; a head's q/k
are ``[nope, rope]`` (``qk_nope_head_dim + qk_rope_head_dim`` wide, the
rope part rotated as interleaved pairs), its v ``v_head_dim``. What the
cache keeps of a token is ``[c, k_rope]`` after norm and rotation, one
vector of ``latent_width`` channels with no heads and no K/V pair (cache
kind ``"latent"``). Two algebraically equal forms: ``apply`` (prefill)
EXPANDS ``c`` through ``kv_up`` into every head's ``k_nope`` and ``v``
and runs ordinary causal attention; ``decode_step`` ABSORBS ``kv_up``
into the query (``q~ = W_uk q_nope``) and the output (``o = W_uv^T
o~``), so the heads attend the cached vector itself
(``ops/attention.py::latent_write_and_attend``).

COMPRESSED CONVOLUTIONAL attention (CCA, the ZAYA1 recipe; mixer
``"cca_attention"``): queries and keys are projected DOWN to the heads'
width (``u = [W_q x; W_k x]``), mixed over the sequence by two short
causal convolutions (depth-wise over ``cca_time0`` tokens, then grouped by
head over ``cca_time1``), added to the query-key mean of the
pre-convolution heads, scaled to unit length (keys times a learned
temperature a head) and rotated over the first ``partial_rotary_factor``
of a head's channels; the second half of the value heads is of the token
BEFORE. Attention itself is the ordinary causal GQA product, so the pool
keeps a K/V pair a token; what is new is that the next token needs the
pre-convolution vectors of the last two and the shifted value of this
one: a fixed-size TAIL a row beside the pool (cache kind ``"paged+tail"``:
``[u_t, u_{t-1}, W_v2 x_t]``, zero for a row that holds nothing yet),
which the prefill form hands to the decode form at the last real token of
a row's window. Feed-forward ``"sparse_top1"``: the held experts behind a
router that is a small network with state carried from layer to layer and
a skip choice (``models/moe.py::MLPRouter``): the CARRY a block of this
kind takes from the block below and returns. ``scale_residual_merge``
makes every residual add the affine merge ``sr (h + br) + sy (f + by)``;
``tie_embeddings`` reads out through the embedding.

The layers differ in shape, so the parameters are a per-layer list
(``params["layers"][i]``), not one stacked tree, and the serving layer
asks for the block and the parameters of layer ``i`` (``layer_block`` /
``layer_params``) and the block for its ``cache_kind``: a full layer
keeps the paged pool, a sliding layer a ring of ``ring_tokens`` slots a
row that does not grow with the horizon (``ops/attention.py::
ring_write_and_attend``).

Served path only: ``apply`` (the whole forward, what the tests compare
with the reference) and the cache protocol work; ``loss_fn``, the
pipeline and the tensor-parallel rules raise ``NotImplementedError``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.models.moe import (
    HeldExperts, MLPRouter)
from distributed_compute_pytorch_tpu.models.transformer import (
    dispatch_attention)
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.rotary import (
    apply_rope, apply_rope_interleaved)

MIXERS = ("full_attention", "sliding_attention", "latent_attention",
          "cca_attention")
MLPS = ("dense", "sparse", "sparse_top1")


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    layer_types: tuple = ("sliding_attention", "full_attention")
    mlp_layer_types: tuple = ("dense", "sparse")
    sliding_window: int = 128
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 64
    d_model: int = 512
    d_ff: int = 1024               # the dense layers' SwiGLU width
    qk_norm: bool = True
    rope_sliding_only: bool = True
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # the sparse layers' experts (models/moe.py::HeldExperts)
    num_experts: int = 8           # the router's width
    experts_held: tuple = None     # (first, count); None = all
    top_k: int = 2
    moe_d_ff: int = 256
    shared_d_ff: int = 256         # 0 = no shared expert
    routed_scale: float = 1.0
    norm_topk_prob: bool = True
    param_dtype: jnp.dtype = jnp.float32
    # "post": norm each sublayer's output; "pre": its input
    norm_placement: str = "post"
    # the latent_attention layers' widths (num_heads heads; head_dim and
    # num_kv_heads are not theirs)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the cca_attention layers' convolution widths over the sequence, and
    # the share of a head's channels that rotates (full/sliding layers
    # rotate all of them)
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 1.0
    # the sparse_top1 layers' router: its hidden width (the choices are
    # num_experts, the LAST of them the skip)
    router_hidden: int = 0
    # every residual add is sr (h + br) + sy (f + by), the embedding
    # enters as s (Emb + b); needs norm_placement "pre"
    scale_residual_merge: bool = False
    # the head is the embedding, read out transposed
    tie_embeddings: bool = False

    def __post_init__(self):
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError(
                f"layer_types ({len(self.layer_types)}) and mlp_layer_types "
                f"({len(self.mlp_layer_types)}) name different depths")
        for kinds, known in ((self.layer_types, MIXERS),
                             (self.mlp_layer_types, MLPS)):
            for k in kinds:
                if k not in known:
                    raise ValueError(f"layer kind {k!r} is none of {known}")
        if self.norm_placement not in ("post", "pre"):
            raise ValueError(
                f"norm_placement {self.norm_placement!r} is neither 'post' "
                f"nor 'pre'")
        if "latent_attention" in self.layer_types and not all((
                self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim)):
            raise ValueError(
                "a latent_attention layer needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.scale_residual_merge and self.norm_placement != "pre":
            raise ValueError(
                "scale_residual_merge merges a sublayer's output into the "
                "stream: the norms are on the sublayers' inputs "
                "(norm_placement 'pre')")
        if "cca_attention" in self.layer_types and (
                self.num_kv_heads % 2
                or (self.cca_time0, self.cca_time1) != (2, 2)):
            raise ValueError(
                "a cca_attention layer shifts the second half of the value "
                "heads (num_kv_heads even) and its tail holds two tokens: "
                "cca_time0 = cca_time1 = 2")
        if "sparse_top1" in self.mlp_layer_types and not (
                self.router_hidden and self.top_k == 1):
            raise ValueError(
                "a sparse_top1 layer needs router_hidden and top_k 1")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} must be a multiple of "
                f"num_kv_heads={self.num_kv_heads}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def latent_width(self) -> int:
        """Channels a latent layer's cache keeps of a token."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cca_width(self) -> int:
        """Channels of a CCA layer's compressed query and key together."""
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @property
    def tail_width(self) -> int:
        """Channels of a CCA layer's per-row tail: ``u`` of the last two
        tokens and the half of the value heads the last leaves the next."""
        return 2 * self.cca_width + self.num_kv_heads * self.head_dim // 2

    @classmethod
    def tiny(cls) -> "HybridConfig":
        """Every kind at toy sizes: a leading dense layer, then a period
        of sliding/full layers over experts of which half are held."""
        return cls(vocab_size=256, max_seq_len=64,
                   layer_types=("sliding_attention",) * 3
                   + ("full_attention", "sliding_attention"),
                   mlp_layer_types=("dense",) + ("sparse",) * 4,
                   sliding_window=8, num_heads=4, num_kv_heads=2,
                   head_dim=16, d_model=64, d_ff=128, num_experts=8,
                   experts_held=(0, 4), top_k=2, moe_d_ff=32,
                   shared_d_ff=32, routed_scale=2.5)


def _dense(din, dout):
    return L.Dense(din, dout, use_bias=False)


@dataclass(frozen=True)
class HybridBlock:
    """One layer: its mixer and its feed-forward, each with its RMSNorm:
    on its output before the residual add (``norm_placement`` ``"post"``)
    or on its input (``"pre"``)."""

    config: HybridConfig
    mixer: str
    mlp: str

    @property
    def window(self) -> int | None:
        return (self.config.sliding_window
                if self.mixer == "sliding_attention" else None)

    @property
    def latent(self) -> bool:
        return self.mixer == "latent_attention"

    @property
    def cca(self) -> bool:
        return self.mixer == "cca_attention"

    @property
    def cache_kind(self) -> str:
        if self.latent:
            return "latent"
        if self.cca:
            return "paged+tail"
        return "ring" if self.window else "paged"

    @property
    def carries(self) -> bool:
        """Does this block take a carry from the block below and return
        one (its router's state)? Then ``apply`` returns ``(x, carry)``
        and ``decode_step`` ``(x, cache, carry)``."""
        return self.mlp == "sparse_top1"

    @property
    def _pre(self) -> bool:
        return self.config.norm_placement == "pre"

    def experts(self) -> HeldExperts:
        c = self.config
        if self.mlp == "sparse_top1":
            return HeldExperts(
                c.d_model, c.moe_d_ff, c.num_experts, 1,
                experts_held=c.experts_held, param_dtype=c.param_dtype,
                router=MLPRouter(c.d_model, c.router_hidden, c.num_experts,
                                 c.rms_eps, c.param_dtype),
                skip_index=c.num_experts - 1)
        return HeldExperts(c.d_model, c.moe_d_ff, c.num_experts, c.top_k,
                           experts_held=c.experts_held,
                           shared_d_ff=c.shared_d_ff,
                           routed_scale=c.routed_scale,
                           norm_topk_prob=c.norm_topk_prob,
                           param_dtype=c.param_dtype)

    def init(self, key):
        c = self.config
        ks = iter(jax.random.split(key, 8))
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False,
                                          param_dtype=c.param_dtype)
        norms = (("pre_attn_norm", "pre_mlp_norm") if self._pre
                 else ("post_attn_norm", "post_mlp_norm"))
        if self.latent:
            H, n, r = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
            p = {"q_down": dense(d, c.q_lora_rank).init(next(ks)),
                 "q_norm": L.RMSNorm(c.q_lora_rank, c.rms_eps).init(None),
                 "q_up": dense(c.q_lora_rank, H * (n + r)).init(next(ks)),
                 "kv_down": dense(d, c.latent_width).init(next(ks)),
                 "kv_norm": L.RMSNorm(c.kv_lora_rank, c.rms_eps).init(None),
                 "kv_up": dense(c.kv_lora_rank,
                                H * (n + c.v_head_dim)).init(next(ks)),
                 "o": dense(H * c.v_head_dim, d).init(next(ks))}
        elif self.cca:
            hq, hk, C, pd = c.num_heads, c.num_kv_heads, c.cca_width, \
                c.param_dtype
            k0, k1 = jax.random.split(next(ks))
            p = {"q": dense(d, hq * hd).init(next(ks)),
                 "k": dense(d, hk * hd).init(next(ks)),
                 "v_cur": dense(d, hk * hd // 2).init(next(ks)),
                 "v_prev": dense(d, hk * hd // 2).init(k0),
                 # taps over the sequence, the earlier token first
                 "conv0": {"kernel": jnp.full((c.cca_time0, C),
                                              1.0 / c.cca_time0, pd),
                           "bias": jnp.zeros((C,), pd)},
                 "conv1": {"kernel": (2 * hd) ** -0.5 * jax.random.normal(
                               k1, (c.cca_time1, hq + hk, hd, hd), pd),
                           "bias": jnp.zeros((C,), pd)},
                 # the key temperature, as its offset from 1
                 "k_temp": jnp.zeros((hk,), jnp.float32),
                 "o": dense(hq * hd, d).init(next(ks))}
        else:
            p = {"q": dense(d, c.num_heads * hd).init(next(ks)),
                 "k": dense(d, c.num_kv_heads * hd).init(next(ks)),
                 "v": dense(d, c.num_kv_heads * hd).init(next(ks)),
                 "o": dense(c.num_heads * hd, d).init(next(ks))}
        for name in norms:
            p[name] = L.RMSNorm(d, c.rms_eps).init(None)
        if c.scale_residual_merge:
            # scales as offsets from 1: a fresh merge is the plain add
            for name in ("attn_merge", "mlp_merge"):
                p[name] = {k: jnp.zeros((d,), c.param_dtype) for k in (
                    "res_scale", "res_bias", "out_scale", "out_bias")}
        if c.qk_norm and not (self.latent or self.cca):
            p["q_norm"] = L.RMSNorm(hd, c.rms_eps).init(None)
            p["k_norm"] = L.RMSNorm(hd, c.rms_eps).init(None)
        if self.mlp == "dense":
            p.update(gate=dense(d, c.d_ff).init(next(ks)),
                     up=dense(d, c.d_ff).init(next(ks)),
                     down=dense(c.d_ff, d).init(next(ks)))
        else:
            p["moe"] = self.experts().init(next(ks))
        return p

    def _norm(self, params, name, x):
        c = self.config
        return L.RMSNorm(c.d_model, c.rms_eps).apply(params[name], x)

    def _qkv(self, params, x, positions):
        """Projected q/k/v at GQA width; QK-norm, then rotation where
        this layer's kind rotates."""
        c = self.config
        d, hd = c.d_model, c.head_dim
        q = A.split_heads(_dense(d, c.num_heads * hd).apply(params["q"], x),
                          c.num_heads)
        k = A.split_heads(_dense(d, c.num_kv_heads * hd).apply(params["k"], x),
                          c.num_kv_heads)
        v = A.split_heads(_dense(d, c.num_kv_heads * hd).apply(params["v"], x),
                          c.num_kv_heads)
        if c.qk_norm:
            norm = L.RMSNorm(hd, c.rms_eps)
            q = norm.apply(params["q_norm"], q)
            k = norm.apply(params["k_norm"], k)
        if self.window or not c.rope_sliding_only:
            q = apply_rope(q, positions, c.rope_theta)
            k = apply_rope(k, positions, c.rope_theta)
        return q, k, v

    def _latent_q_and_token(self, params, x, positions):
        """A latent layer's queries ``[B, H, T, nope + rope]`` (the rope
        part rotated) and what its cache keeps of each token ``[B, T,
        latent_width]``: the normed compressed channels, then the rotated
        rotary key all heads share."""
        c = self.config
        d, r, kvl = c.d_model, c.qk_rope_head_dim, c.kv_lora_rank
        qw = c.num_heads * (c.qk_nope_head_dim + r)
        cq = L.RMSNorm(c.q_lora_rank, c.rms_eps).apply(
            params["q_norm"],
            _dense(d, c.q_lora_rank).apply(params["q_down"], x))
        q = A.split_heads(_dense(c.q_lora_rank, qw).apply(params["q_up"], cq),
                          c.num_heads)
        q = apply_rope_interleaved(q, positions, c.rope_theta, rotary_dim=r)
        ckv = _dense(d, c.latent_width).apply(params["kv_down"], x)
        comp = L.RMSNorm(kvl, c.rms_eps).apply(params["kv_norm"],
                                               ckv[..., :kvl])
        k_rope = apply_rope_interleaved(ckv[:, None, :, kvl:], positions,
                                        c.rope_theta)[:, 0]
        return q, jnp.concatenate([comp, k_rope], axis=-1)

    def _latent_prefill(self, params, x, positions, kv_mask, kv_sink):
        """The EXPANDED form: every head's ``k_nope`` and ``v`` from the
        compressed channels, ordinary causal attention at q/k width
        ``nope + rope`` and v width ``v_head_dim``."""
        c = self.config
        H, n, kvl = c.num_heads, c.qk_nope_head_dim, c.kv_lora_rank
        q, token = self._latent_q_and_token(params, x, positions)
        if kv_sink is not None:
            kv_sink.append((token,))
        with scope("latent_absorb"):
            kv = A.split_heads(
                _dense(kvl, H * (n + c.v_head_dim)).apply(
                    params["kv_up"], token[..., :kvl]), H)
        k_rope = jnp.broadcast_to(
            token[:, None, :, kvl:],
            kv.shape[:3] + (c.qk_rope_head_dim,))
        k = jnp.concatenate([kv[..., :n], k_rope], axis=-1)
        # the default scale is q's head width ** -0.5: (nope + rope)
        return dispatch_attention(q, k, kv[..., n:], causal=True,
                                  kv_mask=kv_mask)

    def _latent_decode(self, params, x, cache, pos):
        """The ABSORBED form: ``W_uk`` folded into the query and ``W_uv``
        into the output, so the heads attend the cached vectors
        themselves; equal to :meth:`_latent_prefill` in exact
        arithmetic."""
        c = self.config
        H, n, kvl = c.num_heads, c.qk_nope_head_dim, c.kv_lora_rank
        q, token = self._latent_q_and_token(params, x, pos[:, None])
        w = params["kv_up"]["kernel"].astype(x.dtype).reshape(
            kvl, H, n + c.v_head_dim)
        with scope("latent_absorb"):
            q_abs = jnp.einsum("bhn,chn->bhc", q[:, :, 0, :n], w[:, :, :n])
        o_lat, cache = A.latent_write_and_attend(
            jnp.concatenate([q_abs, q[:, :, 0, n:]], axis=-1), token[:, 0],
            cache, pos, v_width=kvl,
            scale=(n + c.qk_rope_head_dim) ** -0.5)
        with scope("latent_absorb"):
            o = jnp.einsum("bhc,chv->bhv", o_lat, w[:, :, n:])
        return o[:, :, None, :], cache

    def _cca_project(self, params, x):
        """The down-projections of a CCA layer: ``u = [W_q x; W_k x]``,
        ``W_v1 x`` and ``W_v2 x`` (the last one is of use to the NEXT
        token)."""
        c = self.config
        d, hd, hk = c.d_model, c.head_dim, c.num_kv_heads
        u = jnp.concatenate(
            [_dense(d, c.num_heads * hd).apply(params["q"], x),
             _dense(d, hk * hd).apply(params["k"], x)], axis=-1)
        half = hk * hd // 2
        return (u, _dense(d, half).apply(params["v_cur"], x),
                _dense(d, half).apply(params["v_prev"], x))

    def _cca_conv0(self, params, u, u_prev):
        """The depth-wise convolution: ``a`` of the tokens whose ``u`` is at
        hand, from the ``u`` of the token before each. Summed in float32
        and rounded to the activations' type in BOTH forms, so a tail kept
        in that type holds what the prefill form went on with."""
        f32 = lambda t: t.astype(jnp.float32)
        w0 = params["conv0"]
        return (f32(w0["kernel"][0]) * f32(u_prev) + f32(w0["kernel"][1])
                * f32(u) + f32(w0["bias"])).astype(u.dtype)

    def _cca_mix(self, params, u, a, a_prev, positions):
        """``u`` and ``a`` of the tokens at hand ``[B, T, C]`` with ``a`` of
        the token before each -> ``q [B, Hq, T, hd]``, ``k [B, Hk, T, hd]``:
        the convolution grouped by head, the query-key mean of the
        pre-convolution heads, unit length, the keys' temperature, the
        partial rotation; in float32 until the rotation is done."""
        c = self.config
        hq, hk, hd = c.num_heads, c.num_kv_heads, c.head_dim
        f32 = lambda t: t.astype(jnp.float32)
        w1 = params["conv1"]
        grouped = lambda t: t.reshape(t.shape[:2] + (hq + hk, hd))
        # float32 operands: on the TPU the default precision multiplies
        # them in one bfloat16 pass, which is exact for operands that are
        # bfloat16 values, and sums in float32 (the CPU backend has no
        # bfloat16 x bfloat16 -> float32 form of this grouped product)
        mm = lambda t, w: jnp.einsum("btgi,gio->btgo", f32(grouped(t)),
                                     f32(w))
        m = (mm(a_prev, w1["kernel"][0]) + mm(a, w1["kernel"][1])
             + f32(w1["bias"]).reshape(hq + hk, hd))
        uh = f32(grouped(u))
        qr, kr = uh[:, :, :hq], uh[:, :, hq:]
        G = hq // hk
        q = m[:, :, :hq] + (qr + jnp.repeat(kr, G, axis=2)) / 2
        k = m[:, :, hq:] + (qr.reshape(qr.shape[:2] + (hk, G, hd)).mean(3)
                            + kr) / 2
        unit = lambda t: t * (hd ** 0.5 * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + c.rms_eps))
        k = unit(k) * (1.0 + f32(params["k_temp"]))[:, None]
        rd = int(hd * c.partial_rotary_factor)
        rot = lambda t: apply_rope(t.transpose(0, 2, 1, 3), positions,
                                   c.rope_theta, rotary_dim=rd)
        return rot(unit(q)).astype(u.dtype), rot(k).astype(u.dtype)

    def _cca_prefill(self, params, x, positions, kv_mask, kv_sink):
        """The whole-window form: the convolutions and the value shift as
        shifts along the sequence. ``kv_sink`` is handed ``(k, v, tail)``:
        the pool's pair and, per row, the tail at its LAST REAL token
        (``kv_mask``): ``u`` of that token and of the one before it, and
        the value half it leaves the next (zero where the row has no such
        token: a zero tail is a row that holds nothing yet)."""
        c = self.config
        B, T = x.shape[:2]
        u, v_cur, v_next = self._cca_project(params, x)
        with scope("cca_mix"):
            shift = lambda t: jnp.pad(t, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            a = self._cca_conv0(params, u, shift(u))
            # a_{-1} is the first convolution of the two zero vectors the
            # sequence is padded with (its bias), not zero
            zero = jnp.zeros_like(u[:, :1])
            a_prev = jnp.concatenate(
                [self._cca_conv0(params, zero, zero), a[:, :-1]], axis=1)
            q, k = self._cca_mix(params, u, a, a_prev, positions)
            v = A.split_heads(
                jnp.concatenate([v_cur, shift(v_next)], axis=-1),
                c.num_kv_heads)
            if kv_sink is not None:
                n_tok = (jnp.full((B,), T, jnp.int32) if kv_mask is None
                         else jnp.sum(kv_mask > 0.5, axis=1).astype(
                             jnp.int32))

                def at(t, back):
                    i = n_tok - back
                    got = jnp.take_along_axis(
                        t, jnp.maximum(i, 0)[:, None, None], axis=1)[:, 0]
                    return jnp.where((i >= 0)[:, None], got, 0)

                kv_sink.append((k, v, jnp.concatenate(
                    [at(u, 1), at(u, 2), at(v_next, 1)], axis=-1)))
        return dispatch_attention(q, k, v, causal=True, kv_mask=kv_mask)

    def _cca_decode(self, params, x, cache, pos, slot_mask, live):
        """One token a row against the pool and the row's tail ``[u_{t-1},
        u_{t-2}, W_v2 x_{t-1}]``: the two ``u`` give ``a_{t-1}`` again
        (the same sum of the same rounded terms as the prefill form's, so
        a tail in the activations' type rounds nothing more), the last
        part the shifted value heads. The tail is rewritten for the rows
        in the plan (``live``; a parked row's tail does not advance).
        Equal to the prefill form token for token."""
        c = self.config
        C = c.cca_width
        tail = cache["tail"]
        u, v_cur, v_next = self._cca_project(params, x)
        with scope("cca_mix"):
            prev = tail.astype(x.dtype)[:, None]
            u1, u2 = prev[..., :C], prev[..., C:2 * C]
            q, k = self._cca_mix(
                params, u, self._cca_conv0(params, u, u1),
                self._cca_conv0(params, u1, u2),
                pos[:, None] if jnp.ndim(pos) == 1 else jnp.atleast_1d(pos))
            v = A.split_heads(
                jnp.concatenate([v_cur, prev[..., 2 * C:]], axis=-1),
                c.num_kv_heads)
            new = jnp.concatenate([u, u1, v_next], axis=-1)[:, 0].astype(
                tail.dtype)
            if live is not None:
                new = jnp.where(live[:, None] > 0.5, new, tail)
        o, pool = A.cache_write_and_attend(
            q, k, v, {n: leaf for n, leaf in cache.items() if n != "tail"},
            pos, slot_mask=slot_mask)
        return o, {**pool, "tail": new}

    def _merge(self, params, name, x, f):
        """The residual merge of a sublayer's output ``f`` into the stream
        ``x``: the plain add, or ``sr (x + br) + sy (f + by)`` in float32
        (the scales stored as offsets from 1)."""
        if not self.config.scale_residual_merge:
            return x + f
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params[name])
        x32, f32 = x.astype(jnp.float32), f.astype(jnp.float32)
        return ((1.0 + p["res_scale"]) * (x32 + p["res_bias"])
                + (1.0 + p["out_scale"]) * (f32 + p["out_bias"])).astype(
                    x.dtype)

    def _attn_out(self, params, x, o):
        c = self.config
        o = A.merge_heads(o)
        a = _dense(o.shape[-1], c.d_model).apply(params["o"], o)
        return self._merge(params, "attn_merge", x,
                           a if self._pre
                           else self._norm(params, "post_attn_norm", a))

    def _mlp(self, params, x, token_mask=None, counts_sink=None,
             carry=None):
        """The feed-forward sublayer; ``(x, carry)`` for a block that
        carries (``carry`` in: the state of the router below, None into
        the first layer)."""
        c = self.config
        with scope("mlp"):
            y = self._norm(params, "pre_mlp_norm", x) if self._pre else x
            if self.mlp == "dense":
                g = jax.nn.silu(_dense(c.d_model, c.d_ff).apply(
                    params["gate"], y))
                m = _dense(c.d_ff, c.d_model).apply(
                    params["down"],
                    g * _dense(c.d_model, c.d_ff).apply(params["up"], y))
            elif self.carries:
                m, carry = self.experts().apply_with_state(
                    params["moe"], y, carry, token_mask=token_mask,
                    counts_sink=counts_sink)
            else:
                m = self.experts().apply(params["moe"], y,
                                         token_mask=token_mask,
                                         counts_sink=counts_sink)
            x = self._merge(params, "mlp_merge", x,
                            m if self._pre
                            else self._norm(params, "post_mlp_norm", m))
            return (x, carry) if self.carries else x

    def apply(self, params, x, *, kv_mask=None, kv_sink=None,
              positions=None, counts_sink=None, carry=None):
        """The whole-sequence forward of one layer (prefill). ``kv_sink``
        captures what a cache stores: the K/V pair (after QK-norm and
        rotation, at kv-head width), or a latent layer's one token vector
        ``(token,)``, or a CCA layer's ``(k, v, tail)``; ``kv_mask`` (``[B,
        T]``, 1 = real) hides pad keys and keeps pad tokens out of the
        experts. A block that :attr:`carries` takes ``carry`` and returns
        ``(x, carry)``."""
        T = x.shape[1]
        with scope("attn"):
            pos = jnp.arange(T) if positions is None else positions
            y = self._norm(params, "pre_attn_norm", x) if self._pre else x
            if self.latent:
                with scope("attn_latent"):
                    x = self._attn_out(params, x, self._latent_prefill(
                        params, y, pos, kv_mask, kv_sink))
            elif self.cca:
                with scope("attn_cca"):
                    x = self._attn_out(params, x, self._cca_prefill(
                        params, y, pos, kv_mask, kv_sink))
            else:
                q, k, v = self._qkv(params, y, pos)
                if kv_sink is not None:
                    kv_sink.append((k, v))
                if self.window:
                    with scope("attn_local"):
                        o = A.attention(q, k, v, causal=True,
                                        kv_mask=kv_mask, window=self.window)
                else:
                    o = dispatch_attention(q, k, v, causal=True,
                                           kv_mask=kv_mask)
                x = self._attn_out(params, x, o)
        return self._mlp(params, x, token_mask=kv_mask,
                         counts_sink=counts_sink, carry=carry)

    def decode_step(self, params, x, cache, pos, slot_mask=None,
                    counts_sink=None, live=None, carry=None):
        """One cached decode tick, ``x [B, 1, d]`` at per-row slots ``pos
        [B]``. ``cache`` is this layer's kind: the paged pool with its
        table (K/V pairs, or a latent layer's token vectors), or a ring
        ``{"kv": [2, B, hk, R, hd]}``, or a CCA layer's pool with the
        rows' ``"tail" [B, tail_width]`` beside it. ``live`` (``[B]``, 1 =
        a row in the plan) keeps parked rows out of the experts and their
        tails where they are. A block that :attr:`carries` takes ``carry``
        and returns ``(x, cache, carry)``."""
        with scope("attn"):
            y = self._norm(params, "pre_attn_norm", x) if self._pre else x
            if self.latent:
                with scope("attn_latent"):
                    o, cache = self._latent_decode(
                        params, y, cache,
                        jnp.broadcast_to(jnp.atleast_1d(pos), x.shape[:1]))
                    x = self._attn_out(params, x, o)
            elif self.cca:
                with scope("attn_cca"):
                    o, cache = self._cca_decode(params, y, cache, pos,
                                                slot_mask, live)
                    x = self._attn_out(params, x, o)
            else:
                rope_pos = (pos[:, None] if jnp.ndim(pos) == 1
                            else jnp.atleast_1d(pos))
                q, k, v = self._qkv(params, y, rope_pos)
                if self.window:
                    with scope("attn_local"):
                        o, cache = A.ring_write_and_attend(
                            q, k, v, cache, pos, self.window)
                else:
                    o, cache = A.cache_write_and_attend(
                        q, k, v, cache, pos, slot_mask=slot_mask)
                x = self._attn_out(params, x, o)
        # a parked row (live 0) routes nowhere: its token is garbage, and
        # the experts' counts are of the rows in the plan
        out = self._mlp(params, x, token_mask=live,
                        counts_sink=counts_sink, carry=carry)
        return (out[0], cache, out[1]) if self.carries else (out, cache)


@dataclass(frozen=True)
class HybridLM:
    config: HybridConfig = HybridConfig()

    # --- what the serving layer asks, layer by layer ---

    @property
    def num_layers(self) -> int:
        return self.config.num_layers

    def layer_block(self, i: int) -> HybridBlock:
        c = self.config
        return HybridBlock(c, c.layer_types[i], c.mlp_layer_types[i])

    def layer_params(self, params, i: int):
        return params["layers"][i]

    @property
    def ring_tokens(self) -> int:
        """Slots of a sliding layer's ring: the window, in whole cache
        write windows (``ops/pallas/cache_update.py``)."""
        return -(-self.config.sliding_window // 16) * 16

    def counted_experts(self) -> int:
        """Held experts a sparse layer counts loads for (0: no sparse
        layer, no counters)."""
        c = self.config
        if not {"sparse", "sparse_top1"} & set(c.mlp_layer_types):
            return 0
        return (c.experts_held or (0, c.num_experts))[1]

    @property
    def counts_skips(self) -> bool:
        """Do the sparse layers' counts carry the skip choice's share?"""
        return "sparse_top1" in self.config.mlp_layer_types

    @property
    def tail_width(self) -> int:
        """Channels of a CCA layer's per-slot tail."""
        return self.config.tail_width

    def kv_cache_spec(self):
        return self.config.num_kv_heads, self.config.head_dim

    @property
    def latent_width(self) -> int:
        """Channels a token takes in a latent layer's pool."""
        return self.config.latent_width

    @property
    def cache_block_tokens(self) -> int | None:
        """Tokens to a pool block where the model implies one: a latent
        layer's token is one short vector (1152 bytes at 576 bf16
        channels), so a block of the default 8 tokens would be a 9 KB
        copy and a table entry for every 8 tokens of a long context; 32
        tokens make a block 40 KB, about a GQA block of 8 tokens at 8 KV
        heads of 128. Measured on the v5e (PERF.md, PR 32): the decode
        kernel's call takes 1.25 ms at 16, 1.02 at 32 and 1.01 at 64
        tokens a block, and a smaller block wastes less of a row's last
        one. A CCA layer's K/V pair of 2 heads of 128 is as short (1024
        bytes a token): at 8 tokens the decode kernel copies 8 KB a block,
        four times as many copies a byte as at 8 KV heads, and reached
        38% of its memory roofline where K-EXAONE's pool reaches 77%
        (PERF.md, PR 36); 32 tokens make a block the 32 KB of a GQA block
        of 8 tokens at 8 KV heads. None = the batcher's default."""
        short = {"latent_attention", "cca_attention"}
        return 32 if short & set(self.config.layer_types) else None

    def init(self, key):
        c = self.config
        ks = jax.random.split(key, c.num_layers + 2)
        p = {
            "wte": L.Embedding(c.vocab_size, c.d_model,
                               param_dtype=c.param_dtype).init(ks[0]),
            "layers": [self.layer_block(i).init(ks[1 + i])
                       for i in range(c.num_layers)],
            "norm_f": L.RMSNorm(c.d_model, c.rms_eps).init(None),
        }
        if c.scale_residual_merge:
            p["embed_merge"] = {k: jnp.zeros((c.d_model,), c.param_dtype)
                                for k in ("scale", "bias")}
        if not c.tie_embeddings:
            p["lm_head"] = L.Dense(c.d_model, c.vocab_size, use_bias=False,
                                   param_dtype=c.param_dtype).init(ks[-1])
        return p, {}

    def embed(self, params, tokens, positions=None):
        del positions          # rotation lives in the layers' mixers
        c = self.config
        with scope("embed"):
            x = L.Embedding(c.vocab_size, c.d_model).apply(params["wte"],
                                                           tokens)
            if c.scale_residual_merge:
                e = jax.tree.map(lambda t: t.astype(jnp.float32),
                                 params["embed_merge"])
                x = ((1.0 + e["scale"]) * (x.astype(jnp.float32)
                                           + e["bias"])).astype(x.dtype)
            return x

    def readout(self, params, x):
        c = self.config
        with scope("head"):
            x = L.RMSNorm(c.d_model, c.rms_eps).apply(params["norm_f"], x)
            if c.tie_embeddings:
                return jnp.einsum(
                    "...d,vd->...v", x,
                    params["wte"]["embedding"].astype(x.dtype))
            return L.Dense(c.d_model, c.vocab_size,
                           use_bias=False).apply(params["lm_head"], x)

    def apply(self, params, state, tokens, *, train: bool = False, rng=None,
              kv_mask=None):
        """``tokens [B, T]`` -> logits ``[B, T, vocab]``."""
        del rng
        if train:
            raise NotImplementedError(
                "HybridLM is served, not trained: its experts are one "
                "chip's share and have no backward path")
        x = self.embed(params, tokens)
        carry = None
        for i in range(self.num_layers):
            block = self.layer_block(i)
            if block.carries:
                x, carry = block.apply(params["layers"][i], x,
                                       kv_mask=kv_mask, carry=carry)
            else:
                x = block.apply(params["layers"][i], x, kv_mask=kv_mask)
        return self.readout(params, x), state

    def _untrained(self, what):
        raise NotImplementedError(
            f"HybridLM has no {what}: only the served path (apply without "
            f"train, ContinuousBatcher) is supported")

    def loss_fn(self, logits, tokens):
        self._untrained("loss_fn")

    def loss_sum(self, logits, tokens):
        self._untrained("loss_sum")

    def partition_rules(self):
        self._untrained("tensor-parallel partition rules")
