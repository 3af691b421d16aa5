"""Autoregressive KV-cache generation for the causal LMs (GPT-2, Llama,
Switch/GShard MoE — expert-parallel decode, ``models/moe.py::MoEBlock``).

The reference is a training-only example (``/root/reference/main.py`` has
no inference path at all); a complete framework needs one. TPU-idiomatic
design: everything is ONE compiled program with static shapes —

- **Prefill** runs the blocks' full-sequence forward over the prompt
  (python loop over the static layer count, MXU-batched over positions),
  capturing each layer's K/V into a preallocated KV-PAIR cache
  ``{"kv": [2, B, Hk, t_max, hd]}`` (kv-head width: under GQA the cache
  and its bandwidth scale with ``num_kv_heads``, not ``num_heads``).
- **Decode** is a ``lax.scan`` over ``max_new_tokens`` ticks; each tick
  embeds one token, runs every block's ``decode_step`` (one-window
  in-place pair write + masked attention over slots ``0..pos`` —
  insert+attend measured 0.101 vs 0.303 ms/tick for the old per-array
  form on v5e, ``ops/pallas/cache_update.py``), and samples the next
  token. No data-dependent python control flow, no per-token dispatch —
  the whole generation is a single device program.

Sampling: greedy at ``temperature=0``; else softmax sampling via
``jax.random.categorical``, optionally truncated to the ``top_k``
highest-probability tokens and/or the smallest set reaching ``top_p``
cumulative mass (nucleus). All deterministic given the rng key.

Model contract (``gpt2.py``/``llama.py``): ``embed(params, tokens,
positions)`` (positions may be per-row ``[B, T]``), ``readout(params,
x)``, ``kv_cache_spec()``, ``_block()`` with ``apply(..., kv_sink=...,
kv_mask=...)`` and ``decode_step(params, x, cache, pos,
slot_mask=None)`` — ``pos`` a scalar here (one-shot generation is
lockstep) or an int32 ``[B]`` vector (per-row decode positions, the
serving loop's contract — ``serve.ContinuousBatcher``); every family
honours both. Correctness is pinned by ``tests/test_generate.py``:
greedy cached generation must equal a full-forward re-run at every step,
and a left-padded batch must equal each prompt generated alone.
"""

from __future__ import annotations

import contextlib
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import constrain, use_mesh

# Decode-time mesh layout (engaged via ``constrain`` only when a mesh
# context is active — a no-op otherwise): batch over the batch axes, KV
# cache heads over ``tensor``. Each layer's cache is one KV-PAIR array
# [2(k/v), B, Hk, t_max, hd] (r5: the slot write costs one window DMA
# instead of two — insert+attend measured 0.101 vs 0.303 ms/tick,
# ops/pallas/cache_update.py); sharding Hk over tensor mirrors the
# Megatron column-parallel q/k/v training layout, so the per-head
# attention compute and the cache's HBM traffic split across the tensor
# group with no resharding against the params.
_CACHE_SPEC = P(None, ("data", "fsdp"), "tensor", None, None)

# Paged-pool layout (the serving block pool, ``serve.ContinuousBatcher``):
# per-layer ``{"kv": [2(k/v), P, Hk, bt, hd]}`` — P physical blocks of bt
# slots each, addressed through a per-row block table [B, nb]. Axis 1 is
# BLOCKS (not rows), sharded over the batch axes so the pool's HBM
# footprint splits across the data group like the dense rows did; kv
# heads stay on ``tensor``. A row's blocks may live on any device — the
# per-tick gather's output is constrained back to the row-sharded
# ``_CACHE_SPEC`` layout, so XLA inserts whatever collective the two
# layouts imply (the arXiv:2112.01075 portable-redistribution move; the
# same spec tuple serves both layouts since only the axis MEANING
# changes).
_POOL_SPEC = _CACHE_SPEC


def _constrain_cache(cache):
    # same layout pin for every cache leaf (the int8 form adds a paired
    # per-row scale array [2, B, Hk, T, 1] — sharded exactly like kv);
    # the paged form's host-built block table rides along unpinned (a
    # tiny int32 [B, nb] the partitioner replicates)
    return {name: (leaf if name == "table"
                   else constrain(leaf, _CACHE_SPEC))
            for name, leaf in cache.items()}


def paged_cache_view(cache):
    """Materialise the logical dense view of a PAGED cache dict
    (``{"kv": pool, "table": [B, nb], ...}``) — the per-row
    ``[2, B, Hk, nb*bt, hd]`` layout every dense cache consumer
    understands. Debug/inspection helper (checkpointing a paged session
    into the dense layout); the decode hot path gathers inside
    ``ops/attention.py::cache_write_and_attend`` instead."""
    from distributed_compute_pytorch_tpu.ops.attention import (
        gather_kv_blocks)
    table = cache["table"]
    return {name: gather_kv_blocks(leaf, table)
            for name, leaf in cache.items() if name != "table"}


def _per_layer(stacked, i: int):
    return jax.tree.map(lambda a: a[i], stacked)


def _num_layers(stacked) -> int:
    return int(jax.tree_util.tree_leaves(stacked)[0].shape[0])


def prefill(model, params, prompt, t_max: int, prompt_mask=None,
            kv_quant: bool = False):
    """Run the prompt through the blocks, filling fresh decode caches.

    ``prompt_mask`` (``[B, T0]``, 1 = real token) supports LEFT-padded
    variable-length prompts: pad slots are excluded from attention
    (``kv_mask``) and, for learned-position models, every row embeds its
    own logical positions (``max(slot - pad_count, 0)``). With left
    padding the last slot is every row's last real token, so the returned
    logits are valid for all rows.

    Returns ``(last_logits [B, vocab], caches)`` where ``caches`` is a
    list of per-layer kv-pair arrays ``{"kv": [2, B, Hk, t_max, hd]}``
    (dim 0 = k/v; prompt K/V written at positions ``0..T0-1``, rest
    zeros). ``kv_quant`` stores the INT8 form instead (``{"kv": int8,
    "scale": f32 [2, B, Hk, t_max, 1]}``, per-row scales — halves the
    decode tick's cache stream; see
    ``ops/attention.py::cached_attention_q8``). The prefill compute
    itself is untouched, so the first generated token is exactly the
    bf16-cache path's.
    """
    B, T0 = prompt.shape
    assert T0 <= t_max, (T0, t_max)
    hk, hd = model.kv_cache_spec()
    block = model._block()
    if prompt_mask is None:
        positions = jnp.arange(T0)
    else:
        pad_count = T0 - jnp.sum(prompt_mask.astype(jnp.int32), axis=1)
        positions = jnp.maximum(jnp.arange(T0)[None, :]
                                - pad_count[:, None], 0)
    x = constrain(model.embed(params, prompt, positions),
                  P(("data", "fsdp"), None, None))
    dtype = x.dtype
    caches = []
    for i in range(_num_layers(params["blocks"])):
        sink: list = []
        x = block.apply(_per_layer(params["blocks"], i), x, kv_sink=sink,
                        kv_mask=prompt_mask)
        if isinstance(x, tuple):
            # MoE blocks return (x, aux); the aux losses are a training
            # observable with no role at inference
            x = x[0]
        (k, v), = sink
        if kv_quant:
            from distributed_compute_pytorch_tpu.utils.quantize import (
                quantize_kv)
            pad = lambda a, w, dt: lax.dynamic_update_slice_in_dim(
                jnp.zeros((B, hk, t_max, w), dt), a, 0, axis=2)
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            caches.append(_constrain_cache(
                {"kv": jnp.stack([pad(kq, hd, jnp.int8),
                                  pad(vq, hd, jnp.int8)]),
                 "scale": jnp.stack([pad(ks, 1, jnp.float32),
                                     pad(vs, 1, jnp.float32)])}))
        else:
            pad = lambda a: lax.dynamic_update_slice_in_dim(
                jnp.zeros((B, hk, t_max, hd), dtype), a.astype(dtype), 0,
                axis=2)
            caches.append(_constrain_cache(
                {"kv": jnp.stack([pad(k), pad(v)])}))
    return model.readout(params, x)[:, -1], caches


def _sample(logits, temperature: float, rng, top_k: int | None = None,
            top_p: float | None = None):
    """Greedy at ``temperature=0``; else softmax sampling, optionally
    truncated to the ``top_k`` highest logits and/or the smallest-mass
    nucleus reaching ``top_p`` — both static-shape (mask, don't gather)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # keep the smallest prefix of the sorted distribution whose mass
        # reaches top_p (the first token always stays: shifted cumsum)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs
        cutoff_idx = jnp.sum((cum < top_p).astype(jnp.int32), axis=-1,
                             keepdims=True) - 1
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_rows(logits, temperature, top_k, top_p, keys):
    """Vectorised PER-ROW sampling — the serving loop's counterpart of
    :func:`_sample` (``serve.ContinuousBatcher`` mixes requests with
    different sampling settings in one compiled segment, so every knob
    is a ``[B]`` vector instead of a static scalar).

    Args:
      logits: ``[B, vocab]``.
      temperature: ``[B]`` float; 0 = greedy for that row (rng unused).
      top_k: ``[B]`` int32; 0 = no top-k truncation for that row.
      top_p: ``[B]`` float; >= 1 = no nucleus truncation for that row.
      keys: ``[B]`` PRNG keys (one independent stream per row).

    Static-shape like ``_sample`` (sort + mask, never a dynamic-size
    gather): per-row k/p cutoffs come from the row's sorted
    distribution via ``take_along_axis`` at a TRACED index, so one
    compiled program serves every combination of per-row settings.
    Greedy rows (``temperature == 0``) take the plain argmax — exactly
    ``_sample(…, 0.0)`` — so a greedy request served next to sampling
    requests keeps its standalone-parity tokens.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / jnp.maximum(temperature,
                                                  1e-6)[:, None]
    desc = jnp.sort(lg, axis=-1)[:, ::-1]
    # top-k: the row's k-th highest (scaled) logit is the cutoff
    kth = jnp.take_along_axis(desc, jnp.clip(top_k - 1, 0, V - 1)[:, None],
                              axis=-1)
    lg = jnp.where((top_k > 0)[:, None] & (lg < kth), -jnp.inf, lg)
    # nucleus over the (top-k-masked) distribution: keep the smallest
    # sorted prefix reaching p (first token always stays: shifted cumsum)
    desc = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    cut_idx = jnp.clip(
        jnp.sum((cum < top_p[:, None]).astype(jnp.int32), axis=-1,
                keepdims=True) - 1, 0, V - 1)
    cutoff = jnp.take_along_axis(desc, cut_idx, axis=-1)
    lg = jnp.where((top_p < 1.0)[:, None] & (lg < cutoff), -jnp.inf, lg)
    sampled = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
    return jnp.where(temperature == 0.0, greedy, sampled)


def verify_sample_rows(logits, temperature, top_k, top_p, keys):
    """:func:`sample_rows` over a verify WINDOW: ``logits [B, W, vocab]``
    and ``keys [B, W]`` -> ``[B, W]`` tokens, one :func:`sample_rows`
    call per window position (a Python loop — W is small and static).

    Position ``i`` draws with key ``keys[:, i]``, which the serving loop
    builds from the SAME (seed, tokens-generated) fold-in schedule plain
    decode uses at that logical position — so column ``i`` here is
    bit-identical to the token plain decode would sample after emitting
    ``i`` window tokens. That identity is the whole exactness argument
    for speculative accept/reject: the verify output at the first draft
    mismatch IS the deterministic rejection resample.
    """
    cols = [sample_rows(logits[:, i], temperature, top_k, top_p,
                        keys[:, i])
            for i in range(logits.shape[1])]
    return jnp.stack(cols, axis=1)


def make_generate_fn(model, max_new_tokens: int, *, t_max: int | None = None,
                     temperature: float = 0.0, eos_id: int | None = None,
                     top_k: int | None = None, top_p: float | None = None,
                     mesh=None, kv_quant: bool = False):
    """Build a jitted ``(params, prompt [B, T0], rng) -> tokens
    [B, T0 + max_new_tokens]`` generation function.

    ``t_max`` caps the cache length (default ``T0 + max_new_tokens`` at
    trace time); one compilation per (model, prompt-shape, max_new).
    ``eos_id``: rows that sample this token keep emitting it for the rest
    of the fixed-shape output (compiled loops cannot shrink; trim at the
    first eos).

    ``kv_quant``: store the KV cache as int8 with per-row scales —
    halves the cache's resident bytes (longer contexts per chip), but
    measured SLOWER per tick on v5e (see
    ``ops/attention.py::cached_attention_q8``); lossy past the first
    generated token.

    ``mesh``: optional ``jax.sharding.Mesh`` — SHARDED generation. The
    prompt/batch shards over the batch axes (``data``/``fsdp``), the KV
    caches and attention heads over ``tensor`` (GQA: the *kv*-head dim is
    what shards, so ``tensor`` must divide ``num_kv_heads``), and params
    keep whatever layout the caller committed them to (restore a
    checkpoint with ``parallel.api.shard_pytree`` under the training
    strategy). This is how a model that needed FSDP/TP to train also
    generates — nothing is gathered to one device.
    """
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if getattr(model, "block_generation", None) is not None:
        # (dcp-generate comes through here too)
        raise ValueError(
            "this model generates by block diffusion (block_length "
            f"{model.block_generation[0]}): a loop of one causal token a "
            "tick would print text the model never wrote; serve it through "
            "serve.ContinuousBatcher, whose block pass follows its mask and "
            "its denoising schedule")
    if mesh is not None:
        tp = dict(mesh.shape).get("tensor", 1)
        hk, _ = model.kv_cache_spec()
        if tp > 1 and hk % tp:
            # GQA shards the NARROW cache: an indivisible kv-head dim would
            # make XLA pad-and-replicate it, silently defeating the layout
            raise ValueError(
                f"tensor axis ({tp}) must divide num_kv_heads ({hk}) for "
                f"sharded generation — the KV cache shards on kv heads")
        if dict(mesh.shape).get("seq", 1) > 1:
            # decode is one position per tick; there is no sequence to
            # shard. Ring attention is a training/prefill concept.
            raise ValueError("generation does not compose with a seq>1 "
                             "mesh axis; fold those devices into data")
    vocab = getattr(model.config, "vocab_size", None)
    if top_k is not None and not 1 <= top_k <= (vocab or top_k):
        raise ValueError(f"top_k must be in [1, vocab={vocab}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        # top_p <= 0 would underflow the nucleus cutoff index and silently
        # sample the FULL vocabulary — the opposite of most-restrictive
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature < 0.0:
        # dividing logits by a negative temperature INVERTS the
        # distribution (samples the least likely tokens)
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature == 0.0 and (top_k is not None or top_p is not None):
        # greedy ignores truncation — silently returning greedy output
        # would mislead a caller who believes they sampled
        raise ValueError("top_k/top_p require temperature > 0 "
                         "(temperature 0 is greedy)")
    block = model._block()

    @partial(jax.jit, static_argnames=("_tmax", "_masked"))
    def _generate(params, prompt, rng, _tmax, _masked, prompt_mask):
        if max_new_tokens == 0:        # static: prefill-only no-op
            return prompt
        prompt = constrain(prompt, P(("data", "fsdp"), None))
        B, T0 = prompt.shape
        last_logits, caches = prefill(
            model, params, prompt, _tmax,
            prompt_mask=prompt_mask if _masked else None,
            kv_quant=kv_quant)
        if _masked:
            pad_count = T0 - jnp.sum(prompt_mask.astype(jnp.int32), axis=1)
            slot_mask = jnp.concatenate(
                [prompt_mask.astype(jnp.float32),
                 jnp.ones((B, _tmax - T0), jnp.float32)], axis=1)
        else:
            pad_count = slot_mask = None
        # Per-tick keys are PRE-SPLIT outside the loop: a jax.random.split
        # inside the scan body serialises a threefry chain through the
        # carry, measured at ~0.55 ms/tick on TPU v5e — more than the
        # whole 124M-param tick's math. One vectorised split here costs
        # one threefry call; greedy decoding (temperature 0) skips rng
        # entirely.
        if temperature == 0.0:
            first = _sample(last_logits, temperature, None, top_k, top_p)
            tick_keys = jnp.zeros((max(max_new_tokens - 1, 1),),
                                  jnp.uint32)     # unused scan xs
        else:
            keys = jax.random.split(rng, max_new_tokens)
            first = _sample(last_logits, temperature, keys[0], top_k, top_p)
            tick_keys = keys[1:] if max_new_tokens > 1 else keys[:1]
        done0 = (jnp.full((B,), False) if eos_id is None
                 else first == eos_id)

        def tick(carry, xs):
            i, sub = xs
            tok, caches, done = carry
            pos = T0 + i                       # cache slot being written
            # per-row LOGICAL position for the learned-position embed
            # (left-pads shift each row's indices down by its pad count).
            # Blocks keep SLOT positions for rotary embeddings: the cached
            # keys were roped at their slots, and RoPE scores depend only
            # on slot DIFFERENCES, which equal logical differences under
            # left padding — mixing logical q against slot-roped keys
            # would skew offsets by pad_count.
            positions = (jnp.atleast_1d(pos) if not _masked
                         else (pos - pad_count)[:, None])
            x = constrain(model.embed(params, tok[:, None], positions),
                          P(("data", "fsdp"), None, None))
            new_caches = []
            for li, c in enumerate(caches):
                x, c2 = block.decode_step(
                    _per_layer(params["blocks"], li), x, c, pos,
                    slot_mask=slot_mask)
                new_caches.append(_constrain_cache(c2))
            logits = model.readout(params, x)[:, -1]
            nxt = _sample(logits, temperature,
                          None if temperature == 0.0 else sub,
                          top_k, top_p)
            if eos_id is not None:
                # fixed-trip scan: finished rows keep emitting eos (the
                # compiled shape cannot shrink; callers trim at eos)
                nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                done = jnp.logical_or(done, nxt == eos_id)
            return (nxt, new_caches, done), nxt

        # tick i consumes the token at position T0+i and emits T0+i+1;
        # `first` (position T0) came from prefill, so N-1 ticks complete
        # the N new tokens with no wasted final iteration
        _, toks = lax.scan(tick, (first, caches, done0),
                           (jnp.arange(max_new_tokens - 1),
                            tick_keys[:max_new_tokens - 1]))
        return jnp.concatenate(
            [prompt, first[:, None], toks.transpose(1, 0)], axis=1)

    def generate(params, prompt, rng=None, prompt_mask=None):
        rng = jax.random.key(0) if rng is None else rng
        tm = t_max or (prompt.shape[1] + max_new_tokens)
        if prompt.shape[1] + max_new_tokens > tm:
            # validate the REQUESTED capacity (before alignment rounding:
            # a caller who asked for t_max=12 and generates 16 should
            # hear about it, not be silently saved by padding)
            raise ValueError(
                f"t_max={tm} can't hold prompt {prompt.shape[1]} + "
                f"{max_new_tokens} new tokens")
        # Align t_max to the in-place Pallas slot write's window
        # (cache_update.py ``_window``: 32 sublanes for int8 tiles, 8 for
        # bf16/f32 — read from the kernel so the two can't drift). A
        # misaligned t_max silently falls back to dynamic-update-slice,
        # which COPIES the whole cache every tick — the measured
        # 0.33 ms/tick cliff the kernel exists to avoid. Extra slots are
        # never attended (the position mask stops at ``pos``), so
        # rounding up is observationally free.
        from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
            _window)
        align = _window(jnp.dtype(jnp.int8) if kv_quant
                        else jnp.dtype(jnp.float32))
        tm = -(-tm // align) * align
        model_cap = getattr(model.config, "max_seq_len", None)
        final = prompt.shape[1] + max_new_tokens
        if model_cap is not None and final > model_cap:
            # past this, learned position tables would be indexed out of
            # range — and JAX gather CLAMPS instead of raising, so the
            # output would be silently wrong. (The cache may legitimately
            # be LARGER than the model capacity; only positions actually
            # reached matter.)
            raise ValueError(
                f"prompt ({prompt.shape[1]}) + {max_new_tokens} new tokens "
                f"exceeds the model's max_seq_len={model_cap}")
        if prompt_mask is not None:
            m = np.asarray(prompt_mask)
            if m.shape != tuple(prompt.shape):
                raise ValueError(f"prompt_mask shape {m.shape} != prompt "
                                 f"shape {tuple(prompt.shape)}")
            if not ((m == 0) | (m == 1)).all():
                # fractional values would split: int-cast pad_count counts
                # them as pads while the bool attention masks attend them
                raise ValueError("prompt_mask must be binary (0/1)")
            if not (m[:, 1:] >= m[:, :-1]).all():
                # pads-then-tokens per row: generation appends at the END,
                # so right-padded rows would interleave pads into the
                # decoded sequence
                raise ValueError("prompt_mask must be LEFT-padded "
                                 "(zeros before ones in every row)")
            if not (m[:, -1] == 1).all():
                raise ValueError("prompt_mask has fully-padded rows (or "
                                 "trailing pads); every row needs at "
                                 "least its final slot real")
        # trace-time mesh context: the constrain() pins inside _generate
        # engage only when the mesh is current (same pattern as
        # train.step.make_step_fns)
        ctx = use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
        with ctx:
            return _generate(params, prompt, rng, tm,
                             prompt_mask is not None, prompt_mask)

    generate._jitted = _generate   # exposed for cache/retrace inspection
    return generate


@lru_cache(maxsize=32)
def _cached_generate_fn(model, max_new_tokens, t_max, temperature, eos_id,
                        top_k, top_p, mesh, kv_quant=False):
    """Memoized builder behind the one-shot :func:`generate` — repeated
    one-shot calls with the same settings reuse one jit cache instead of
    retracing each time (models are frozen dataclasses, so hashable;
    ``Mesh`` is hashable too)."""
    return make_generate_fn(model, max_new_tokens, t_max=t_max,
                            temperature=temperature, eos_id=eos_id,
                            top_k=top_k, top_p=top_p, mesh=mesh,
                            kv_quant=kv_quant)


def generate(model, params, prompt, max_new_tokens: int, *,
             t_max: int | None = None, temperature: float = 0.0, rng=None,
             prompt_mask=None, eos_id: int | None = None,
             top_k: int | None = None, top_p: float | None = None,
             mesh=None, kv_quant: bool = False):
    """One-shot convenience wrapper around :func:`make_generate_fn`.

    ``prompt_mask`` (``[B, T0]``, 1 = real) enables LEFT-padded
    variable-length prompt batches; ``eos_id`` stops rows at that token
    (they pad the fixed-shape tail with it). ``mesh`` enables sharded
    generation and ``kv_quant`` the int8 KV-cache memory mode (see
    :func:`make_generate_fn`). The underlying generation function is
    memoized on all of these settings, so repeated one-shot calls do
    not retrace.
    """
    return _cached_generate_fn(model, max_new_tokens, t_max, temperature,
                               eos_id, top_k, top_p, mesh, kv_quant)(
        params, prompt, rng, prompt_mask=prompt_mask)
