"""A decoder built from a list of layer kinds: layer ``i`` is a (mixer,
feed-forward) pair chosen by ``layer_types[i]`` and ``mlp_layer_types[i]``
(ROADMAP C6: a hybrid is a configuration, not a file of its own).

Mixers: ``"full_attention"`` (causal over the whole context),
``"sliding_attention"`` (causal over the last ``sliding_window`` tokens,
the token itself counted), ``"latent_attention"``, ``"cca_attention"``,
``"linear_attention"`` and ``"sparse_latent_attention"`` (all below).
Feed-forwards: ``"dense"`` (SwiGLU) and
``"sparse"`` (routed experts with a shared expert, of which this chip
holds ``experts_held``: ``models/moe.py::HeldExperts``). The rest is the
Llama recipe (``models/llama.py``: bias-free q/k/v/o at GQA width,
half-split RoPE, RMSNorm, untied head) with four switches a published
family sets: ``qk_norm`` (an RMSNorm over each head's channels of q and
of k, before any rotation), ``rope_sliding_only`` (full layers rotate
nothing), ``attn_gate`` (a full layer's heads, side by side, times the
sigmoid of a projection ``gate`` of the layer's input, one gate a channel,
before ``W_o``: arXiv:2505.06708) and ``norm_placement``: ``"post"`` norms
each sublayer's OUTPUT (``h = h + norm(sublayer(h))``, no norm on its
input), ``"pre"`` its INPUT (``h = h + sublayer(norm(h))``, the
DeepSeek-V3 recipe).

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

LINEAR attention (KDA, the Kimi Linear recipe; mixer
``"linear_attention"``): ``kda_heads`` heads of ``kda_head_dim`` (keys and
values alike); ``q^, k^, v^ = W x`` each through a depth-wise causal
convolution over ``kda_conv`` tokens and SiLU, ``q`` and ``k`` scaled to
unit length (``q`` times ``dk ** -0.5``); a log-decay a CHANNEL in one of
two forms: BOUNDED by a floor, ``g = kda_gate_lower_bound * sigmoid(exp(A_h)
(W_f2 W_f1 x + b_dt))``, or (``kda_gate_lower_bound`` None) Kimi Linear's
own with none, ``g = -exp(A_h) softplus(W_f2 W_f1 x + b_dt)``; a step size
a head, ``beta = sigmoid(W_b x)`` or (``kda_allow_neg_eigval``) ``2
sigmoid(W_b x)``, under which the transition ``I - beta k k^T`` has the
eigenvalue ``1 - beta`` in (-1, 1) along ``k`` (arXiv:2411.12537); the
delta rule ``S_t = (I - beta k k^T) Diag(e^g) S_{t-1} + beta k v^T``, ``o_t
= S_t^T q_t`` on a float32 state a head; ``y = W_o (RMSNorm_head(o) *
sigmoid(W_g2 W_g1 x))``. The cache keeps
NO tokens (cache kind ``"state"``): per row the state ``[H, dk, dv]`` and a
TAIL of the last ``kda_conv - 1`` tokens' ``[q^, k^, v^]``. ``apply``
(prefill) convolves the window, then runs the recurrence ``KDA_CHUNK``
tokens at a time (``ops/attention.py::kda_window``: one Pallas kernel on a
TPU, a scan of ``kda_chunk`` elsewhere); ``decode_step`` one step for the
rows in the plan (``kda_step_live``: one Pallas kernel on a TPU that moves
a live row's state once each way and no parked row's, ``kda_step`` and a
select elsewhere). A pad token leaves the state as it
was, so what prefill hands decode is the state after each row's last REAL
token. The chunked form divides a sub-chunk's decays out only under a
floor; without one its decay grams take the form no ``g <= 0`` can
overflow (``ops/attention.py::_decay_gram``), a static choice by the
configuration. A model may pair state layers with any pool kind: the
paged K/V pool of a full layer and the per-slot state stand side by side.

SPARSE LATENT attention (the DeepSeek-V3.2 recipe over NoPE latent
attention; mixer ``"sparse_latent_attention"``): the latent mixer with
``qk_rope_head_dim`` 0 behind an INDEXER: ``index_heads`` index queries a
token from the low-rank query, one index key a token (LayerNorm; both with
their first ``index_rope_dim`` channels rotated), the mean key of every
group of ``index_pool`` tokens; a token scores the complete groups before
its own (``sum_j w_j ReLU(q_j . K_g)``), attends the tokens of the
``index_topk / index_pool`` best and always its own group up to itself.
Cache kind ``"latent+index"``: the latent pool, the pooled keys on the same
block table, and per row the keys of its own group not yet pooled.
``apply`` computes it as dense attention under the selection's mask,
``decode_step`` gathers the chosen tokens; both call the latent mixer's
projections, pool write and absorbed products.

HYPER-CONNECTIONS (``hc_mult`` > 0): a token carries ``hc_mult`` streams
``[.., n, d]`` between layers; round each sublayer three maps read off the
token's normed ``n d`` channels (``H_pre``, ``H_post``, and ``H_res`` made
doubly stochastic by Sinkhorn rounds): the sublayer is fed ``RMSNorm(H_pre
x)`` and merged back as ``H_res x + H_post^T f`` (``_enter`` / ``_leave``).
``embed`` copies the embedding into every stream, ``readout`` sums them.
``swiglu_limit`` clamps every SwiGLU's gate and up.

BLOCK DIFFUSION (``block_length`` > 0; the SDAR recipe, full-attention
layers only): positions are cut into blocks of ``block_length`` from
position 0, prompt included, and position ``i`` attends ``j`` iff ``j //
block_length <= i // block_length``: every earlier block and ALL of its
own. ``apply`` runs a whole sequence under that mask
(``dispatch_attention(mask_block=)``); :meth:`HybridBlock.block_step` is the
cached form beside ``decode_step``: a block's positions of a row write their
K/V and all read slots ``0 .. block_end - 1`` of the pool, one range a row.
Position ``i``'s logits are token ``i``'s (no shift). How such a model
generates (a block's unknown positions hold ``mask_token_id`` and are
unmasked ``block_length / denoising_steps`` a pass by the rule
``remasking``) is ``HybridLM.block_generation``, which the serving layer
reads; the loop itself is ``serve.py``'s (``_row_passes``,
``_block_segment_impl``). The sparse layers' router is chosen by ``router``:
``"sigmoid"`` (the aux-loss-free form with a selection bias) or
``"softmax"`` (a softmax over all experts, its top-k renormalised:
``models/moe.py::SoftmaxRouter``).

The layers differ in shape, so the parameters are a per-layer list
(``params["layers"][i]``), not one stacked tree, and the serving layer
asks for the block and the parameters of layer ``i`` (``layer_block`` /
``layer_params``) and the block for what it keeps (``cache_leaves``: the
one declaration of a layer's cache format, leaf by leaf, each keyed by
block or by slot; ``apply`` hands admission the same leaves by name in
``kv_sink``, ``decode_step`` takes and returns them): a full layer keeps
the paged pool, a sliding layer a ring of ``ring_tokens`` slots a row
that does not grow with the horizon (``ops/attention.py::
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
    HeldExperts, MLPRouter, SoftmaxRouter)
from distributed_compute_pytorch_tpu.models.transformer import (
    dispatch_attention)
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.rotary import (
    apply_rope, apply_rope_interleaved)

MIXERS = ("full_attention", "sliding_attention", "latent_attention",
          "cca_attention", "linear_attention", "sparse_latent_attention")
# tokens of a sub-chunk of the chunked KDA form: under a floor the decays of
# a sub-chunk are divided out in float32, so 16 x |kda_gate_lower_bound| has
# to stay under its largest exponent (88)
KDA_SUB = 16
# tokens of a chunk of the prefill form: whole sub-chunks
KDA_CHUNK = 64
MLPS = ("dense", "sparse", "sparse_top1")
ROUTERS = ("sigmoid", "softmax")
# the rules by which a block-diffusion pass chooses which masked positions
# take their token; the last makes the yield depend on the data
REMASKINGS = ("sequential", "low_confidence_static", "low_confidence_dynamic")


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
    # a full_attention layer's output gate: sigmoid(W_g x) a channel of the
    # merged heads, before W_o
    attn_gate: bool = False
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
    # the sparse layers' router: "sigmoid" (scores a sigmoid, a selection
    # bias) or "softmax" (a softmax over all experts, its top-k renormalised)
    router: str = "sigmoid"
    param_dtype: jnp.dtype = jnp.float32
    # generation by BLOCK DIFFUSION (0: causal, one token a pass): positions
    # are cut into blocks of block_length from position 0 and a position
    # attends every earlier block and ALL of its own; a block's unknown
    # positions hold mask_token_id and are unmasked block_length /
    # denoising_steps a pass, chosen by the rule `remasking`
    block_length: int = 0
    denoising_steps: int = 1
    remasking: str = "sequential"
    mask_token_id: int = 0
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
    # the linear_attention (KDA) layers: heads and their width (key and
    # value alike), taps of the three convolutions, the rank of the two
    # low-rank gates, the floor of a token's log-decay (None: the softplus
    # gate, which has none), and whether beta runs to 2 and not to 1
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_rank: int = 0
    kda_gate_lower_bound: float | None = -5.0
    kda_allow_neg_eigval: bool = False
    # the sparse_latent_attention layers' indexer: heads and their width,
    # tokens a query attends (index_topk, in groups of index_pool tokens
    # with one pooled key a group), the channels of an index head that
    # rotate and at what base
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_pool: int = 4
    index_rope_dim: int = 0
    index_rope_theta: float = 10000.0
    # hyper-connections: what a token carries between layers is hc_mult
    # streams of d_model channels (0: the one residual vector)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # every SwiGLU is silu(min(gate, l)) * clip(up, -l, l) (0: unclamped)
    swiglu_limit: float = 0.0

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
        if {"latent_attention", "sparse_latent_attention"} & set(
                self.layer_types) and not all((
                    self.q_lora_rank, self.kv_lora_rank,
                    self.qk_nope_head_dim, self.v_head_dim)):
            raise ValueError(
                "a latent_attention layer needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim and v_head_dim (qk_rope_head_dim 0: no "
                "rotary key)")
        if "sparse_latent_attention" in self.layer_types and not (
                self.index_heads and self.index_head_dim and self.index_topk
                and self.index_topk % self.index_pool == 0
                and self.index_rope_dim % 2 == 0):
            raise ValueError(
                "a sparse_latent_attention layer needs index_heads, "
                "index_head_dim and an index_topk of whole groups of "
                "index_pool tokens")
        if "linear_attention" in self.layer_types and not (
                self.kda_heads and self.kda_head_dim and self.kda_gate_rank
                and self.kda_conv >= 2
                and (self.kda_gate_lower_bound is None
                     or -80.0 <= KDA_SUB * self.kda_gate_lower_bound < 0)):
            raise ValueError(
                "a linear_attention layer needs kda_heads, kda_head_dim, "
                "kda_gate_rank, kda_conv >= 2 and a kda_gate_lower_bound "
                f"in [-{80 // KDA_SUB}, 0) or None (a gate with no floor)")
        if self.hc_mult and (self.hc_mult < 2 or self.norm_placement != "pre"
                             or self.scale_residual_merge):
            raise ValueError(
                "hyper-connections carry hc_mult >= 2 streams, norm the one "
                "vector a sublayer is fed (norm_placement 'pre') and are "
                "the residual merge themselves (no scale_residual_merge)")
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
        if self.router not in ROUTERS:
            raise ValueError(f"router {self.router!r} is none of {ROUTERS}")
        b = self.block_length
        if b and not (b >= 2 and b & (b - 1) == 0
                      and set(self.layer_types) == {"full_attention"}
                      and self.denoising_steps >= 1
                      and b % self.denoising_steps == 0
                      and self.remasking in REMASKINGS
                      and 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                "a block-diffusion model has a block_length that is a power "
                "of two, full_attention layers only (the block mask is "
                "theirs), denoising_steps dividing block_length, a remasking "
                f"of {REMASKINGS} and a mask_token_id inside the vocabulary")

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

    @property
    def kda_width(self) -> int:
        """Channels of a KDA layer's projected q (k and v alike)."""
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_tail_width(self) -> int:
        """Channels of a KDA layer's per-row tail: the projected q, k and v
        of the last ``kda_conv - 1`` tokens, the earliest first."""
        return (self.kda_conv - 1) * 3 * self.kda_width

    @property
    def index_groups(self) -> int:
        """Groups of ``index_pool`` tokens a query attends at most."""
        return self.index_topk // self.index_pool

    @property
    def index_tail_width(self) -> int:
        """Channels of a sparse latent layer's per-row tail: the index keys
        of the row's own group that no pooled key holds yet."""
        return (self.index_pool - 1) * self.index_head_dim

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


def _real_tokens(kv_mask, B: int, T: int):
    """Real tokens a row of the window ``[B, T]`` holds (``kv_mask`` 1 =
    real, pads only trail; None: all of them), int32 ``[B]``."""
    if kv_mask is None:
        return jnp.full((B,), T, jnp.int32)
    return jnp.sum(kv_mask > 0.5, axis=1).astype(jnp.int32)


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
    def kda(self) -> bool:
        return self.mixer == "linear_attention"

    @property
    def gated(self) -> bool:
        """A full layer whose merged heads pass an output gate."""
        return self.config.attn_gate and self.mixer == "full_attention"

    @property
    def selects(self) -> bool:
        """Does this block attend a learned selection of its cache? Then
        ``decode_step`` takes ``select_sink`` and hands it the tick's
        ``[attended, in context]`` token counts of the rows in the plan."""
        return self.mixer == "sparse_latent_attention"

    @property
    def cache_kind(self) -> str:
        if self.latent:
            return "latent"
        if self.cca:
            return "paged+tail"
        if self.kda:
            return "state"
        if self.selects:
            return "latent+index"
        return "ring" if self.window else "paged"

    @property
    def ring_tokens(self) -> int:
        """Slots of a sliding layer's ring: the window, in whole cache
        write windows (``ops/pallas/cache_update.py``)."""
        return -(-self.config.sliding_window // 16) * 16

    def cache_leaves(self, slots: int, pool_blocks: int, block_tokens: int,
                     dtype, kv_dtype: str = "bf16") -> dict:
        """What this layer keeps, for an engine of ``slots`` rows over a
        pool of ``pool_blocks`` blocks of ``block_tokens`` tokens: ``{name:
        CacheLeaf}`` in the compute ``dtype``, each leaf keyed by block or
        by slot (``ops/attention.py::CacheLeaf``). The ONE place a layer's
        cache format is written: ``serve.py`` allocates, writes, copies and
        accounts by it, :meth:`apply` hands ``kv_sink`` the same names,
        :meth:`decode_step` takes and returns them (:attr:`cache_kind` is
        the label of the combination, for reports and refusals).

        - a full layer: the paged K/V pool (``kv_pool_leaves``);
        - a sliding layer: ``"kv" [2, slots, hk, ring_tokens, hd]`` by slot
          (axis 1), the last tokens of each row at ``pos % ring_tokens``;
        - a latent layer: ``"kv" [1, P, 1, bt, Wp]`` by block, a token one
          vector of ``latent_width`` channels in whole lane tiles (576 ->
          640), the pool's axes kept so that block writes and copies treat
          it as a K/V pool;
        - a CCA layer: the paged K/V pool and ``"tail" [slots,
          tail_width]`` by slot, what the next token needs of the last two
          (zero: a slot that holds nothing yet);
        - a KDA layer: no tokens; ``"state" [slots, H, dk, dv]`` float32
          and ``"tail" [slots, kda_tail_width]`` by slot;
        - a sparse latent layer: the latent pool, ``"idx" [1, P, 1, bt /
          index_pool, di]`` by block on the same table (one pooled index
          key to ``index_pool`` tokens), and ``"idx_tail" [slots,
          index_tail_width]`` by slot, the keys of the row's own group
          that no pooled key holds yet."""
        c, bt, Leaf = self.config, block_tokens, A.CacheLeaf
        if self.kda:
            return {"state": Leaf((slots, c.kda_heads, c.kda_head_dim,
                                   c.kda_head_dim), jnp.float32, 0),
                    "tail": Leaf((slots, c.kda_tail_width), dtype, 0)}
        if self.window:
            return {"kv": Leaf((2, slots, c.num_kv_heads, self.ring_tokens,
                                c.head_dim), dtype, 1, self.ring_tokens)}
        if self.latent or self.selects:
            leaves = {"kv": Leaf((1, pool_blocks, 1, bt, A.latent_pool_width(
                c.latent_width)), dtype, tokens=bt)}
            if self.selects:
                if bt % c.index_pool:
                    raise ValueError(
                        f"a pool block of {bt} tokens does not hold whole "
                        f"groups of index_pool={c.index_pool}")
                leaves["idx"] = Leaf((1, pool_blocks, 1, bt // c.index_pool,
                                      c.index_head_dim), dtype, tokens=bt)
                leaves["idx_tail"] = Leaf((slots, c.index_tail_width),
                                          dtype, 0)
            return leaves
        leaves = A.kv_pool_leaves(pool_blocks, c.num_kv_heads, bt,
                                  c.head_dim, dtype, kv_dtype)
        if self.cca:
            leaves["tail"] = Leaf((slots, c.tail_width), dtype, 0)
        return leaves

    def read_path(self, cache: dict) -> str:
        """Which engine reads this layer's block leaves in a decode tick
        (``stats_snapshot()["paged_read"]``): ``"selected"`` (a sparse
        latent layer gathers the tokens it chose, whatever the table's
        width), else the latent or the paged pool's own answer
        (``"kernel"`` / ``"gather"``)."""
        if self.selects:
            return "selected"
        if self.latent:
            return A.latent_read_path(cache)
        # (a block-diffusion model's pass: a block's queries, one range)
        b = self.config.block_length
        return A.paged_read_path(
            {n: leaf for n, leaf in cache.items() if n != "tail"}, b or 1,
            one_range=bool(b))

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
                           swiglu_limit=c.swiglu_limit,
                           param_dtype=c.param_dtype,
                           router=SoftmaxRouter(
                               c.d_model, c.num_experts, c.top_k,
                               c.routed_scale, c.norm_topk_prob,
                               c.param_dtype)
                           if c.router == "softmax" else None)

    def _hc_init(self, key):
        """One sublayer's hyper-connection: the three maps in the
        parameters' type, gains and biases in float32, drawn so that
        ``H_pre`` starts near ``1 / n``, ``H_post`` at 1 and ``H_res`` near
        the identity."""
        c = self.config
        n, nd = c.hc_mult, c.hc_mult * c.d_model
        ks = jax.random.split(key, 3)
        phi = lambda k, o: jax.random.uniform(
            k, (nd, o), c.param_dtype, -nd ** -0.5, nd ** -0.5)
        gain = lambda: jnp.full((), 0.1, jnp.float32)
        return {"phi_pre": phi(ks[0], n), "phi_post": phi(ks[1], n),
                "phi_res": phi(ks[2], n * n),
                "a_pre": gain(), "a_post": gain(), "a_res": gain(),
                "b_pre": jnp.full((n,), -jnp.log(n - 1.0), jnp.float32),
                "b_post": jnp.zeros((n,), jnp.float32),
                "b_res": 2.5 * jnp.eye(n, dtype=jnp.float32)}

    def init(self, key):
        c = self.config
        # (the kinds that were here first keep the keys they drew)
        ks = iter(jax.random.split(
            key, 24 if self.kda or self.selects or c.hc_mult else 8))
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False,
                                          param_dtype=c.param_dtype)
        norms = (("pre_attn_norm", "pre_mlp_norm") if self._pre
                 else ("post_attn_norm", "post_mlp_norm"))
        if self.latent or self.selects:
            H, n, r = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
            p = {"q_down": dense(d, c.q_lora_rank).init(next(ks)),
                 "q_norm": L.RMSNorm(c.q_lora_rank, c.rms_eps).init(None),
                 "q_up": dense(c.q_lora_rank, H * (n + r)).init(next(ks)),
                 "kv_down": dense(d, c.latent_width).init(next(ks)),
                 "kv_norm": L.RMSNorm(c.kv_lora_rank, c.rms_eps).init(None),
                 "kv_up": dense(c.kv_lora_rank,
                                H * (n + c.v_head_dim)).init(next(ks)),
                 "o": dense(H * c.v_head_dim, d).init(next(ks))}
            if self.selects:
                Hi, di = c.index_heads, c.index_head_dim
                p.update(
                    idx_q=dense(c.q_lora_rank, Hi * di).init(next(ks)),
                    idx_k=dense(d, di).init(next(ks)),
                    idx_k_norm=L.LayerNorm(di, c.rms_eps).init(None),
                    idx_w=dense(d, Hi).init(next(ks)))
        elif self.kda:
            H, C, R = c.kda_heads, c.kda_width, c.kda_gate_rank
            conv = lambda k: {"kernel": 0.5 * jax.random.normal(
                k, (c.kda_conv, C), c.param_dtype)}
            p = {"q": dense(d, C).init(next(ks)),
                 "k": dense(d, C).init(next(ks)),
                 "v": dense(d, C).init(next(ks)),
                 # taps over the sequence, the earliest token first
                 "conv_q": conv(next(ks)), "conv_k": conv(next(ks)),
                 "conv_v": conv(next(ks)),
                 # the decay gate (low-rank, a bias, a head's rate) ...
                 "f_down": dense(d, R).init(next(ks)),
                 "f_up": dense(R, C).init(next(ks)),
                 "dt_bias": 3.0 * jax.random.normal(next(ks), (C,),
                                                    jnp.float32),
                 "A_log": jnp.zeros((H,), jnp.float32),
                 "beta": dense(d, H).init(next(ks)),
                 # ... and the output gate
                 "g_down": dense(d, R).init(next(ks)),
                 "g_up": dense(R, C).init(next(ks)),
                 "o_norm": L.RMSNorm(c.kda_head_dim, c.rms_eps).init(None),
                 "o": dense(C, d).init(next(ks))}
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
            if self.gated:
                p["gate"] = dense(d, c.num_heads * hd).init(next(ks))
        for name in norms:
            p[name] = L.RMSNorm(d, c.rms_eps).init(None)
        if c.scale_residual_merge:
            # scales as offsets from 1: a fresh merge is the plain add
            for name in ("attn_merge", "mlp_merge"):
                p[name] = {k: jnp.zeros((d,), c.param_dtype) for k in (
                    "res_scale", "res_bias", "out_scale", "out_bias")}
        if c.hc_mult:
            p["attn_hc"] = self._hc_init(next(ks))
            p["mlp_hc"] = self._hc_init(next(ks))
        if c.qk_norm and not (self.latent or self.cca or self.kda
                              or self.selects):
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

    def _latent_cq(self, params, x):
        """The normed low-rank query of each token ``[B, T, q_lora_rank]``
        (an indexer reads it too)."""
        c = self.config
        return L.RMSNorm(c.q_lora_rank, c.rms_eps).apply(
            params["q_norm"],
            _dense(c.d_model, c.q_lora_rank).apply(params["q_down"], x))

    def _latent_q(self, params, cq, positions, heads=None):
        """A latent layer's queries ``[B, H, T, nope + rope]`` (the rope
        part rotated) from the low-rank query (``heads = (first, end)``:
        those heads only)."""
        c = self.config
        H, hw = c.num_heads, c.qk_nope_head_dim + c.qk_rope_head_dim
        up = params["q_up"]
        if heads is not None:
            h0, h1 = heads
            H = h1 - h0
            up = {"kernel": up["kernel"][:, h0 * hw:h1 * hw]}
        q = A.split_heads(_dense(c.q_lora_rank, H * hw).apply(up, cq), H)
        if not c.qk_rope_head_dim:
            return q
        return apply_rope_interleaved(q, positions, c.rope_theta,
                                      rotary_dim=c.qk_rope_head_dim)

    def _latent_token(self, params, x, positions):
        """What a latent layer's cache keeps of each token ``[B, T,
        latent_width]``: the normed compressed channels, then the rotated
        rotary key all heads share (none where ``qk_rope_head_dim`` is
        0)."""
        c = self.config
        kvl = c.kv_lora_rank
        ckv = _dense(c.d_model, c.latent_width).apply(params["kv_down"], x)
        comp = L.RMSNorm(kvl, c.rms_eps).apply(params["kv_norm"],
                                               ckv[..., :kvl])
        if not c.qk_rope_head_dim:
            return comp
        k_rope = apply_rope_interleaved(ckv[:, None, :, kvl:], positions,
                                        c.rope_theta)[:, 0]
        return jnp.concatenate([comp, k_rope], axis=-1)

    def _latent_q_and_token(self, params, x, positions):
        """Every head's queries and the tokens' cached vectors."""
        return (self._latent_q(params, self._latent_cq(params, x), positions),
                self._latent_token(params, x, positions))

    def _latent_planes(self, token):
        """Cached vectors ``token [K, T, latent_width]`` as admission
        writes them into the latent pool's blocks: ``[1, K, 1, T, Wp]``,
        zero-padded to the pool's lane width."""
        with scope("kv_write"):
            return A.pad_channels(token, A.latent_pool_width(
                self.config.latent_width))[None, :, None]

    def _latent_expand(self, params, token, heads=None):
        """The EXPANDED keys and values of cached vectors ``token [B, T,
        latent_width]``: every head's ``k = [k_nope, k_rope]`` and ``v``
        through the up-projection (``heads = (first, end)``: those heads
        only)."""
        c = self.config
        H, n, kvl = c.num_heads, c.qk_nope_head_dim, c.kv_lora_rank
        up = params["kv_up"]
        if heads is not None:
            h0, h1 = heads
            H = h1 - h0
            up = {"kernel": up["kernel"][
                :, h0 * (n + c.v_head_dim):h1 * (n + c.v_head_dim)]}
        with scope("latent_absorb"):
            kv = A.split_heads(
                _dense(kvl, H * (n + c.v_head_dim)).apply(
                    up, token[..., :kvl]), H)
        if not c.qk_rope_head_dim:
            return kv[..., :n], kv[..., n:]
        k_rope = jnp.broadcast_to(
            token[:, None, :, kvl:],
            kv.shape[:3] + (c.qk_rope_head_dim,))
        return jnp.concatenate([kv[..., :n], k_rope], axis=-1), kv[..., n:]

    def _latent_prefill(self, params, x, positions, kv_mask, kv_sink):
        """The EXPANDED form: every head's ``k_nope`` and ``v`` from the
        compressed channels, ordinary causal attention at q/k width
        ``nope + rope`` and v width ``v_head_dim``."""
        q, token = self._latent_q_and_token(params, x, positions)
        if kv_sink is not None:
            kv_sink.append({"kv": self._latent_planes(token)})
        k, v = self._latent_expand(params, token)
        # the default scale is q's head width ** -0.5: (nope + rope)
        return dispatch_attention(q, k, v, causal=True, kv_mask=kv_mask)

    def _latent_absorbed(self, params, q, attend):
        """The ABSORBED form round a read ``attend(q_abs [B, H, W]) -> o_lat
        [B, H, kv_lora_rank]`` of cached vectors: ``W_uk`` folded into the
        one-token queries ``q [B, H, 1, nope + rope]`` and ``W_uv`` into
        the output, so the heads attend the cached vectors themselves.
        Returns ``(o [B, H, 1, v_head_dim], what attend returned beside
        o_lat)``."""
        c = self.config
        H, n, kvl = c.num_heads, c.qk_nope_head_dim, c.kv_lora_rank
        w = params["kv_up"]["kernel"].astype(q.dtype).reshape(
            kvl, H, n + c.v_head_dim)
        with scope("latent_absorb"):
            q_abs = jnp.einsum("bhn,chn->bhc", q[:, :, 0, :n], w[:, :, :n])
        o_lat, *rest = attend(
            jnp.concatenate([q_abs, q[:, :, 0, n:]], axis=-1))
        with scope("latent_absorb"):
            o = jnp.einsum("bhc,chv->bhv", o_lat, w[:, :, n:])
        return (o[:, :, None, :], *rest)

    @property
    def _latent_scale(self) -> float:
        c = self.config
        return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

    def _latent_decode(self, params, x, cache, pos):
        """The absorbed form over the whole context; equal to
        :meth:`_latent_prefill` in exact arithmetic."""
        q, token = self._latent_q_and_token(params, x, pos[:, None])
        return self._latent_absorbed(
            params, q, lambda q_abs: A.latent_write_and_attend(
                q_abs, token[:, 0], cache, pos,
                v_width=self.config.kv_lora_rank, scale=self._latent_scale))

    # ---- the sparse latent mixer: the latent mixer behind an indexer ----

    def _index_parts(self, params, x, cq, positions):
        """The indexer's queries ``[B, T, Hi, di]`` and key ``[B, T, di]``
        of each token (both with their first ``index_rope_dim`` channels
        rotated as interleaved pairs) and its head weights ``[B, T, Hi]``
        (float32, the scale ``(Hi di) ** -0.5`` in them)."""
        c = self.config
        Hi, di, rd = c.index_heads, c.index_head_dim, c.index_rope_dim

        def rot(t):                              # [B, heads, T, di]
            if not rd:
                return t
            return jnp.concatenate([apply_rope_interleaved(
                t[..., :rd], positions, c.index_rope_theta), t[..., rd:]], -1)

        qi = rot(A.split_heads(
            _dense(c.q_lora_rank, Hi * di).apply(params["idx_q"], cq), Hi))
        ki = _dense(c.d_model, di).apply(params["idx_k"], x)
        ki = L.LayerNorm(di, c.rms_eps).apply(      # statistics in float32
            params["idx_k_norm"], ki.astype(jnp.float32)).astype(x.dtype)
        ki = rot(ki[:, None])[:, 0]
        w = jnp.dot(x, params["idx_w"]["kernel"].astype(x.dtype),
                    preferred_element_type=jnp.float32) * (Hi * di) ** -0.5
        return qi.transpose(0, 2, 1, 3), ki, w

    def _sparse_prefill(self, params, x, positions, kv_mask, kv_sink):
        """The whole-window form: the latent mixer's expanded form as dense
        causal attention under the SELECTION's mask (the same mathematics
        as the gathered read of :meth:`_sparse_decode`): token ``t`` attends
        the tokens of its ``index_groups`` best-scored earlier groups and
        its own group up to itself. ``kv_sink`` is handed ``"kv"`` (the
        latent vectors), ``"idx"`` (the pooled index key of every group of
        the window) and ``"idx_tail"`` (per row the index keys of its last
        real token's group that no pooled key holds yet)."""
        c = self.config
        B, T = x.shape[:2]
        P_, G = c.index_pool, -(-x.shape[1] // c.index_pool)
        cq = self._latent_cq(params, x)
        token = self._latent_token(params, x, positions)
        with scope("index_select"):
            qi, ki, w = self._index_parts(params, x, cq, positions)
            kp = jnp.pad(ki, ((0, 0), (0, G * P_ - T), (0, 0)))
            pooled = jnp.mean(kp.reshape(B, G, P_, -1).astype(jnp.float32),
                              axis=2).astype(x.dtype)
            if kv_sink is not None:
                n_tok = _real_tokens(kv_mask, B, T)
                at = n_tok[:, None] // P_ * P_ + jnp.arange(P_ - 1)[None, :]
                tail = jnp.take_along_axis(
                    ki, jnp.minimum(at, T - 1)[:, :, None], axis=1)
                tail = jnp.where((at < n_tok[:, None])[:, :, None], tail, 0)
                kv_sink.append({"kv": self._latent_planes(token),
                                "idx": pooled[None, :, None],
                                "idx_tail": tail.reshape(B, -1)})
            # whom each query may attend, a block of queries at a time
            bq = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1)
                      if T % b == 0)
            nblk = T // bq
            blocks = lambda t: t.reshape(
                (B, nblk, bq) + t.shape[2:]).swapaxes(0, 1)
            Gc, t_all = T // P_, jnp.arange(T)       # the complete groups

            def select(args):
                qib, wb, t0 = args
                t = t0 + jnp.arange(bq)                            # [bq]
                score = A.index_scores(qib, wb, pooled[:, :Gc])
                seen = jnp.arange(Gc)[None, :] < (t // P_)[:, None]
                chosen = A.topk_mask(jnp.where(seen[None], score, -jnp.inf),
                                     c.index_groups) & seen[None]
                see = jnp.pad(jnp.repeat(chosen, P_, axis=2),
                              ((0, 0), (0, 0), (0, T - Gc * P_)))
                own = ((t_all[None, :] // P_ == (t // P_)[:, None])
                       & (t_all[None, :] <= t[:, None]))
                return see | own[None]

            see = jax.lax.map(select, (blocks(qi), blocks(w),
                                       jnp.arange(nblk) * bq))
        # attention a group of heads at a time (their expanded keys and
        # values are 8 KB a token), each band of query blocks against the
        # keys up to its end
        H = c.num_heads
        hg = next(g for g in (16, 8, 4, 2, 1) if H % g == 0)
        bands = next(n for n in (4, 2, 1) if nblk % n == 0)
        per = nblk // bands                            # blocks to a band
        out = []
        for h0 in range(0, H, hg):
            q = self._latent_q(params, cq, positions, (h0, h0 + hg))
            qb = q.reshape(B, hg, nblk, bq, -1).transpose(2, 0, 1, 3, 4)
            k, v = self._latent_expand(params, token, (h0, h0 + hg))
            o_h = []
            for i in range(bands):
                Tk = (i + 1) * per * bq
                attend = lambda a, Tk=Tk: A.masked_attention(
                    a[0], k[:, :, :Tk], v[:, :, :Tk],
                    a[1][:, None, :, :Tk], self._latent_scale)
                o = jax.lax.map(attend, (
                    qb[i * per:(i + 1) * per],
                    see[i * per:(i + 1) * per]))     # [per, B, hg, bq, dv]
                o_h.append(o.transpose(1, 2, 0, 3, 4).reshape(
                    B, hg, per * bq, -1))
            out.append(jnp.concatenate(o_h, axis=2))
        return jnp.concatenate(out, axis=1)

    def _sparse_decode(self, params, x, cache, pos, live, select_sink):
        """One token a row: the latent mixer's projections, pool write and
        absorbed products round a read of the CHOSEN tokens only. The
        row's index key joins its group's tail; the group's running mean
        goes to the pooled-key pool at the group's slot every tick (a group
        is scored only once it is complete, so what an incomplete slot
        holds is never read); the index queries score every complete group
        before the row's own through the block table, and the
        ``index_groups`` best are gathered with the row's own group from
        the latent pool. ``select_sink`` is handed ``[attended, in
        context]``: the tokens the rows in the plan attended and could
        have."""
        c = self.config
        B = x.shape[0]
        P_, di = c.index_pool, c.index_head_dim
        table = cache["table"]
        cq = self._latent_cq(params, x)
        q = self._latent_q(params, cq, pos[:, None])
        token = self._latent_token(params, x, pos[:, None])
        kv = A.latent_write(token[:, 0], cache, pos)
        with scope("index_select"):
            qi, ki, w = self._index_parts(params, x, cq, pos[:, None])
            r = pos % P_                              # place in its group
            old = cache["idx_tail"].reshape(B, P_ - 1, di)
            slot = jnp.arange(P_ - 1)[None, :, None]
            held = jnp.where(slot < r[:, None, None], old, 0)
            mean = ((jnp.sum(held.astype(jnp.float32), 1)
                     + ki[:, 0].astype(jnp.float32)) / P_).astype(x.dtype)
            new = jnp.where(slot == r[:, None, None], ki, held)
            if live is not None:
                new = jnp.where(live[:, None, None] > 0.5, new, old)
            idx = cache["idx"]
            bt = kv.shape[3]
            blk = jnp.take_along_axis(table, (pos // bt)[:, None], 1)[:, 0]
            with scope("kv_write"):
                idx = idx.reshape(-1, di).at[
                    blk * (bt // P_) + (pos % bt) // P_].set(
                        mean.astype(idx.dtype)).reshape(idx.shape)
            with scope("kv_gather"):
                pooled = A.gather_kv_blocks(idx, table)[0, :, 0]  # [B, G, di]
            score = A.index_scores(qi, w, pooled)[:, 0]
            seen = (jnp.arange(pooled.shape[1])[None, :]
                    < (pos // P_)[:, None])
            kk = min(c.index_groups, pooled.shape[1])
            _, groups = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), kk)
            groups_ok = jnp.take_along_axis(seen, groups, axis=1)

        def attend(q_abs):
            return A.selected_latent_attention(
                A.pad_channels(q_abs, kv.shape[-1]), kv, table, pos, groups,
                groups_ok, group_tokens=P_, v_width=c.kv_lora_rank,
                scale=self._latent_scale)

        o, attended = self._latent_absorbed(params, q, attend)
        if select_sink is not None:
            on = (jnp.ones((B,), jnp.int32) if live is None
                  else (live > 0.5).astype(jnp.int32))
            select_sink.append(jnp.stack(
                [jnp.sum(attended * on), jnp.sum((pos + 1) * on)]).astype(
                    jnp.int32))
        return o, {"kv": kv, "idx": idx, "idx_tail": new.reshape(B, -1),
                   "table": table}

    # ---- the KDA mixer: the delta rule on a per-slot state ----

    def _kda_project(self, params, x):
        """``(W_q x, W_k x, W_v x)`` of each token, before the
        convolutions: what the next ``kda_conv - 1`` tokens need of it."""
        c = self.config
        return [_dense(c.d_model, c.kda_width).apply(params[n], x)
                for n in ("q", "k", "v")]

    def _kda_conv(self, params, name, window):
        """One of the three depth-wise convolutions (``name``: ``"q"``,
        ``"k"``, ``"v"``) and SiLU: ``window [..., K, C]`` (a token's own
        projection last) -> ``[..., C]``, summed in float32 tap by tap in
        BOTH forms and rounded to the activations' type, so a tail kept in
        that type rounds nothing more."""
        taps = params["conv_" + name]["kernel"].astype(jnp.float32)
        a = sum(taps[i] * window[..., i, :].astype(jnp.float32)
                for i in range(self.config.kda_conv))
        return jax.nn.silu(a).astype(window.dtype)

    def _kda_heads(self, params, a, f_low):
        """From the convolved ``a = (a_q, a_k, a_v)`` (``[..., C]`` each)
        and the decay gate's low-rank part ``f_low [..., R]``: ``q`` (unit
        length times ``dk ** -0.5``), ``k`` (unit length), ``v`` and the
        log-decay ``g`` (all ``[..., H, dk]`` float32)."""
        c = self.config
        H, dk = c.kda_heads, c.kda_head_dim
        # (in the order the tick's program has had these operations since
        # PR 41: its lowered text, and so its compiled program, is unchanged)
        heads = lambda t: t.astype(jnp.float32).reshape(
            t.shape[:-1] + (H, dk))
        q, k, v = (heads(t) for t in a)
        f = jnp.dot(f_low, params["f_up"]["kernel"].astype(f_low.dtype),
                    preferred_element_type=jnp.float32) + params["dt_bias"]
        rate = jnp.exp(params["A_log"].astype(jnp.float32))[:, None]
        return A.kda_heads(q, k, v, heads(f), rate, c.kda_gate_lower_bound)

    def _kda_gates(self, params, x):
        """The decay gate's low-rank part ``[..., R]`` and ``beta [...,
        H]`` (float32; in (0, 1), or (0, 2) under
        ``kda_allow_neg_eigval``)."""
        c = self.config
        f_low = _dense(c.d_model, c.kda_gate_rank).apply(params["f_down"], x)
        beta = jax.nn.sigmoid(jnp.dot(
            x, params["beta"]["kernel"].astype(x.dtype),
            preferred_element_type=jnp.float32))
        return f_low, 2.0 * beta if c.kda_allow_neg_eigval else beta

    def _kda_out(self, params, x, o):
        """``o [..., H, dv]`` float32 -> the mixer's output before ``W_o``,
        ``[..., C]``: each head normed, times the sigmoid of the output
        gate."""
        c = self.config
        o = L.RMSNorm(c.kda_head_dim, c.rms_eps).apply(
            params["o_norm"], o).astype(x.dtype)
        gate = jax.nn.sigmoid(_dense(c.kda_gate_rank, c.kda_width).apply(
            params["g_up"], _dense(c.d_model, c.kda_gate_rank).apply(
                params["g_down"], x)))
        return o.reshape(o.shape[:-2] + (c.kda_width,)) * gate

    def _kda_prefill(self, params, x, kv_mask, kv_sink):
        """The whole-window form: the three convolutions over the window,
        then the recurrence ``KDA_CHUNK`` tokens at a time
        (``ops/attention.py::kda_window``: one kernel on a TPU, a scan of
        ``kda_chunk`` elsewhere). A pad token (``kv_mask`` 0) gets ``beta =
        0`` and no decay, so the state the window ends with is the one
        after each row's LAST REAL token. ``kv_sink`` is handed ``"state"
        [B, H, dk, dv]`` float32 (that state) and ``"tail" [B,
        kda_tail_width]``: token by token, ``[q^, k^, v^]`` of the row's
        last ``kda_conv - 1`` real tokens (zero where it has fewer)."""
        c = self.config
        B, T = x.shape[:2]
        K = c.kda_conv
        u = self._kda_project(params, x)
        with scope("linear_scan"):
            f_low, beta = self._kda_gates(params, x)
            real = (jnp.ones((B, T), jnp.float32) if kv_mask is None
                    else (kv_mask > 0.5).astype(jnp.float32))
            # the projections of the row's last K - 1 REAL tokens (pads
            # only trail, so a real token's are the tokens before it),
            # zero where it has fewer; gathered from the projections
            # themselves, so that the padded copy below feeds the
            # convolution alone and is fused into it
            at = (jnp.sum(real, axis=1).astype(jnp.int32)[:, None, None]
                  + jnp.arange(1 - K, 0)[None, :, None])
            a, tails = [], []
            for name, u_i in zip("qkv", u):
                seq = jnp.pad(u_i, ((0, 0), (K - 1, 0), (0, 0)))
                a.append(self._kda_conv(params, name, jnp.stack(
                    [seq[:, i:i + T] for i in range(K)], axis=2)))
                tails.append(jnp.where(at >= 0, jnp.take_along_axis(
                    u_i, jnp.maximum(at, 0), axis=1), 0))
            o, S = A.kda_window(
                *a, f_low, params["f_up"]["kernel"], params["dt_bias"],
                jnp.exp(params["A_log"].astype(jnp.float32)), beta, real,
                lower_bound=c.kda_gate_lower_bound, chunk=KDA_CHUNK,
                sub=KDA_SUB)
            o = self._kda_out(params, x, o)
            if kv_sink is not None:
                kv_sink.append({"state": S, "tail": jnp.concatenate(
                    tails, -1).reshape(B, -1)})
        return o

    def _kda_decode(self, params, x, cache, live):
        """One token a row against the row's state and tail (the last
        ``kda_conv - 1`` tokens' projections): one step of the delta rule.
        Both are rewritten for the rows in the plan (``live``; a parked
        row's do not advance). Equal to the prefill form token for
        token."""
        c = self.config
        B = x.shape[0]
        K = c.kda_conv
        C = c.kda_width
        u = self._kda_project(params, x)                    # 3 x [B, 1, C]
        with scope("linear_scan"):
            tail = cache["tail"]
            window = jnp.concatenate(
                [tail.astype(x.dtype).reshape(B, K - 1, -1),
                 jnp.concatenate(u, axis=-1)], axis=1)      # [B, K, 3C]
            a = [self._kda_conv(params, name, window[..., i * C:(i + 1) * C])
                 for i, name in enumerate("qkv")]
            f_low, beta = self._kda_gates(params, x[:, 0])
            q, k, v, g = self._kda_heads(params, a, f_low)
            o, S = A.kda_step_live(cache["state"], q, k, v, g, beta, live)
            new_tail = window[:, 1:].reshape(B, -1).astype(tail.dtype)
            if live is not None:
                new_tail = jnp.where((live > 0.5)[:, None], new_tail, tail)
            o = self._kda_out(params, x[:, 0], o)[:, None]
        return o, {"state": S, "tail": new_tail}

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
        shifts along the sequence. ``kv_sink`` is handed ``"kv"`` (the
        pool's pair) and ``"tail"``: per row, the tail at its LAST REAL
        token (``kv_mask``): ``u`` of that token and of the one before it,
        and the value half it leaves the next (zero where the row has no
        such token: a zero tail is a row that holds nothing yet)."""
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
                n_tok = _real_tokens(kv_mask, B, T)

                def at(t, back):
                    i = n_tok - back
                    got = jnp.take_along_axis(
                        t, jnp.maximum(i, 0)[:, None, None], axis=1)[:, 0]
                    return jnp.where((i >= 0)[:, None], got, 0)

                with scope("kv_write"):
                    kv = jnp.stack([k, v])
                kv_sink.append({"kv": kv, "tail": jnp.concatenate(
                    [at(u, 1), at(u, 2), at(v_next, 1)], axis=-1)})
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

    def _enter(self, params, which, x):
        """What sublayer ``which`` (``"attn"`` or ``"mlp"``) is fed, and
        what :meth:`_leave` needs to merge its output back. The plain
        residual: ``x`` (normed where the norms are on the inputs), and
        nothing. Hyper-connections (``x [..., n, d]``): ``RMSNorm_in(H_pre
        x)``, one vector a token, and ``(H_post, H_res)``; the maps are read
        off the normed ``n d`` channels of the token in float32, ``H_res``
        through ``hc_sinkhorn_iters`` rounds of row then column
        normalisation."""
        c = self.config
        if not c.hc_mult:
            return (self._norm(params, f"pre_{which}_norm", x) if self._pre
                    else x), None
        hp, n = params[f"{which}_hc"], c.hc_mult
        f32 = lambda t: t.astype(jnp.float32)
        with scope("hyper_mix"):
            # a stream at a time, so that no float32 copy of all the
            # streams stands beside them while the sublayer runs
            xs = [x[..., i, :] for i in range(n)]
            inv = jax.lax.rsqrt(sum(
                jnp.sum(jnp.square(f32(xi)), -1, keepdims=True)
                for xi in xs) / (n * c.d_model) + c.rms_eps)
            flat = jnp.concatenate(
                [(f32(xi) * inv).astype(x.dtype) for xi in xs], axis=-1)
            mm = lambda w: jnp.dot(flat, w.astype(x.dtype),
                                   preferred_element_type=jnp.float32)
            pre = jax.nn.sigmoid(hp["a_pre"] * mm(hp["phi_pre"])
                                 + hp["b_pre"])
            post = 2.0 * jax.nn.sigmoid(hp["a_post"] * mm(hp["phi_post"])
                                        + hp["b_post"])
            res = jnp.exp(hp["a_res"] * mm(hp["phi_res"]).reshape(
                flat.shape[:-1] + (n, n)) + hp["b_res"])
            for _ in range(c.hc_sinkhorn_iters):
                res = res / (jnp.sum(res, -1, keepdims=True) + c.hc_eps)
                res = res / (jnp.sum(res, -2, keepdims=True) + c.hc_eps)
            u = sum(pre[..., i, None] * f32(xs[i])
                    for i in range(n)).astype(x.dtype)
        return self._norm(params, f"pre_{which}_norm", u), (post, res)

    def _leave(self, params, which, x, f, hc):
        """The merge of sublayer ``which``'s output ``f`` into the stream
        ``x``: the plain add, or ``sr (x + br) + sy (f + by)`` in float32
        (the scales stored as offsets from 1), or the hyper-connection's
        ``H_res x + H_post^T f``."""
        if hc is not None:
            post, res = hc
            n = self.config.hc_mult
            f32 = lambda t: t.astype(jnp.float32)
            with scope("hyper_mix"):
                return jnp.stack([
                    (sum(res[..., i, j, None] * f32(x[..., j, :])
                         for j in range(n))
                     + post[..., i, None] * f32(f)).astype(x.dtype)
                    for i in range(n)], axis=-2)
        if not self._pre:
            f = self._norm(params, f"post_{which}_norm", f)
        if not self.config.scale_residual_merge:
            return x + f
        p = jax.tree.map(lambda t: t.astype(jnp.float32),
                         params[f"{which}_merge"])
        x32, f32 = x.astype(jnp.float32), f.astype(jnp.float32)
        return ((1.0 + p["res_scale"]) * (x32 + p["res_bias"])
                + (1.0 + p["out_scale"]) * (f32 + p["out_bias"])).astype(
                    x.dtype)

    def _attn_out(self, params, x, o, hc=None, merged: bool = False,
                  gate_in=None):
        """``o`` (heads ``[B, H, T, hd]``, or already ``merged [B, T, H
        hd]``) through the output projection and into the stream; a
        :attr:`gated` layer's heads first times the sigmoid of the gate's
        projection of ``gate_in``, the mixer's input."""
        if not merged:
            o = A.merge_heads(o)
        if gate_in is not None:
            with scope("attn_gate"):
                o = o * jax.nn.sigmoid(_dense(
                    self.config.d_model, o.shape[-1]).apply(
                        params["gate"], gate_in))
        a = _dense(o.shape[-1], self.config.d_model).apply(params["o"], o)
        return self._leave(params, "attn", x, a, hc)

    def _mlp(self, params, x, token_mask=None, counts_sink=None,
             carry=None):
        """The feed-forward sublayer; ``(x, carry)`` for a block that
        carries (``carry`` in: the state of the router below, None into
        the first layer)."""
        c = self.config
        with scope("mlp"):
            y, hc = self._enter(params, "mlp", x)
            if self.mlp == "dense":
                m = _dense(c.d_ff, c.d_model).apply(
                    params["down"], L.clamped_swiglu(
                        _dense(c.d_model, c.d_ff).apply(params["gate"], y),
                        lambda: _dense(c.d_model, c.d_ff).apply(
                            params["up"], y), c.swiglu_limit))
            elif self.carries:
                m, carry = self.experts().apply_with_state(
                    params["moe"], y, carry, token_mask=token_mask,
                    counts_sink=counts_sink)
            else:
                m = self.experts().apply(params["moe"], y,
                                         token_mask=token_mask,
                                         counts_sink=counts_sink)
            x = self._leave(params, "mlp", x, m, hc)
            return (x, carry) if self.carries else x

    def apply(self, params, x, *, kv_mask=None, kv_sink=None,
              positions=None, counts_sink=None, carry=None):
        """The whole-sequence forward of one layer (prefill). ``kv_sink``
        is handed what the window leaves in the layer's cache: ONE mapping
        with the names of :meth:`cache_leaves`, each leaf's content in the
        form admission writes it. A leaf by block: the planes of its
        blocks, ``[s, B, heads, T, width]`` (the K/V pair after QK-norm and
        rotation, stacked; a latent token padded to the pool's lane width;
        the pooled index keys with as many rows as the window's blocks hold
        of them). A leaf by slot: the rows' entries where the declared
        leaf has its slots (a tail or a state ``[B, ...]``; a sliding
        layer's ring as the ticks will read it, ``[2, B, hk, ring_tokens,
        hd]``). ``kv_mask`` (``[B, T]``, 1 = real) hides pad keys and
        keeps pad tokens out of the experts and the state. A block that
        :attr:`carries` takes ``carry`` and returns ``(x, carry)``. With
        hyper-connections ``x`` is ``[B, T, n, d]``."""
        T = x.shape[1]
        with scope("attn"):
            pos = jnp.arange(T) if positions is None else positions
            y, hc = self._enter(params, "attn", x)
            if self.latent:
                with scope("attn_latent"):
                    x = self._attn_out(params, x, self._latent_prefill(
                        params, y, pos, kv_mask, kv_sink), hc)
            elif self.cca:
                with scope("attn_cca"):
                    x = self._attn_out(params, x, self._cca_prefill(
                        params, y, pos, kv_mask, kv_sink), hc)
            elif self.kda:
                with scope("attn_linear"):
                    x = self._attn_out(params, x, self._kda_prefill(
                        params, y, kv_mask, kv_sink), hc, merged=True)
            elif self.selects:
                with scope("attn_sparse"):
                    x = self._attn_out(params, x, self._sparse_prefill(
                        params, y, pos, kv_mask, kv_sink), hc)
            else:
                q, k, v = self._qkv(params, y, pos)
                if kv_sink is not None:
                    with scope("kv_write"):
                        kv_sink.append({"kv": A.ring_from_prefill(
                            k, v, _real_tokens(kv_mask, *x.shape[:2]),
                            self.ring_tokens) if self.window
                            else jnp.stack([k, v])})
                if self.window:
                    with scope("attn_local"):
                        o = A.attention(q, k, v, causal=True,
                                        kv_mask=kv_mask, window=self.window)
                else:
                    # (a block-diffusion model: under the block mask)
                    o = dispatch_attention(
                        q, k, v, causal=True, kv_mask=kv_mask,
                        **({"mask_block": self.config.block_length}
                           if self.config.block_length else {}))
                x = self._attn_out(params, x, o, hc,
                                   gate_in=y if self.gated else None)
        return self._mlp(params, x, token_mask=kv_mask,
                         counts_sink=counts_sink, carry=carry)

    def block_step(self, params, x, cache, pos0, live=None,
                   counts_sink=None):
        """One cached pass over a BLOCK of a block-diffusion model: ``x [B,
        L, d]``, the ``L = block_length`` positions ``pos0[b] .. pos0[b] + L
        - 1`` of each row (``pos0`` a multiple of ``L``). The block's K/V
        are written at its slots through the table (``cache`` as
        :meth:`decode_step` takes it), then every query of the block
        attends slots ``0 .. pos0 + L - 1``: the earlier blocks and all of
        its own, one range a row (``ops/attention.py::
        block_write_and_attend``). ``live`` as in :meth:`decode_step`.
        Returns ``(x, cache)``."""
        L_blk = x.shape[1]
        with scope("attn"):
            y, hc = self._enter(params, "attn", x)
            q, k, v = self._qkv(params, y,
                                pos0[:, None] + jnp.arange(L_blk)[None, :])
            o, cache = A.block_write_and_attend(q, k, v, cache, pos0)
            x = self._attn_out(params, x, o, hc,
                               gate_in=y if self.gated else None)
        mask = None if live is None else jnp.broadcast_to(
            live[:, None], x.shape[:2])
        return self._mlp(params, x, token_mask=mask,
                         counts_sink=counts_sink), cache

    def decode_step(self, params, x, cache, pos, slot_mask=None,
                    counts_sink=None, live=None, carry=None,
                    select_sink=None):
        """One cached decode tick, ``x [B, 1, d]`` at per-row slots ``pos
        [B]``. ``cache`` holds this layer's leaves (:meth:`cache_leaves`)
        and, where any of them is keyed by block, the rows' block
        ``"table"``. ``live`` (``[B]``, 1 = a row in the plan) keeps
        parked rows out of the experts and their per-slot leaves where
        they are. A block that :attr:`carries` takes ``carry`` and returns
        ``(x, cache, carry)``; one that :attr:`selects` takes
        ``select_sink``. With hyper-connections ``x`` is ``[B, 1, n,
        d]``."""
        with scope("attn"):
            y, hc = self._enter(params, "attn", x)
            rows = lambda: jnp.broadcast_to(jnp.atleast_1d(pos), x.shape[:1])
            if self.latent:
                with scope("attn_latent"):
                    o, cache = self._latent_decode(params, y, cache, rows())
                    x = self._attn_out(params, x, o, hc)
            elif self.cca:
                with scope("attn_cca"):
                    o, cache = self._cca_decode(params, y, cache, pos,
                                                slot_mask, live)
                    x = self._attn_out(params, x, o, hc)
            elif self.kda:
                with scope("attn_linear"):
                    o, cache = self._kda_decode(params, y, cache, live)
                    x = self._attn_out(params, x, o, hc, merged=True)
            elif self.selects:
                with scope("attn_sparse"):
                    o, cache = self._sparse_decode(params, y, cache, rows(),
                                                   live, select_sink)
                    x = self._attn_out(params, x, o, hc)
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
                x = self._attn_out(params, x, o, hc,
                                   gate_in=y if self.gated else None)
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
        """Channels of a CCA layer's per-slot tail (the benchmark's family
        test reads it off the model)."""
        return self.config.tail_width

    def kv_cache_spec(self):
        return self.config.num_kv_heads, self.config.head_dim

    @property
    def block_generation(self):
        """How this model generates, where it is by block diffusion:
        ``(block_length, denoising_steps, remasking, mask_token_id)``; None
        for a causal model (one token a row a pass). What
        ``serve.ContinuousBatcher`` reads to choose the pass it compiles and
        the step contract it plans by; ``infer.generate`` refuses such a
        model."""
        c = self.config
        return ((c.block_length, c.denoising_steps, c.remasking,
                 c.mask_token_id) if c.block_length else None)

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
        short = {"latent_attention", "cca_attention",
                 "sparse_latent_attention"}
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
            if c.hc_mult:
                # the token's embedding into every stream
                x = jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (
                    c.hc_mult, c.d_model))
            return x

    def readout(self, params, x):
        c = self.config
        with scope("head"):
            if c.hc_mult:
                # the streams fold by their sum
                x = jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)
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
