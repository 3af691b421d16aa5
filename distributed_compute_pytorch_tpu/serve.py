"""Segment-wise continuous batching — the serving loop over the KV-cache
machinery (VERDICT r4 missing #2; the reference is training-only,
``/root/reference/main.py``).

One-shot ``infer.generate`` compiles a fixed batch to a fixed horizon:
fine for a single batch, wasteful for a STREAM of requests — short rows
finish early and their slots then burn ticks emitting garbage until the
longest row ends. This module keeps a fixed pool of ``slots`` busy
instead, with everything the TPU touches remaining static-shaped:

- **Paged block-pool KV cache**: each layer's cache is a POOL of
  fixed-size blocks ``{"kv": [2, pool_blocks, hk, kv_block_tokens,
  hd]}`` (block size a multiple of the Pallas cache window —
  ``ops/pallas/cache_update.py::_window`` — static shapes throughout),
  and each cache row maps its LOGICAL slot range ``[0, t_max)`` onto
  physical blocks through a per-row block table ``[slots, t_max // bt]``
  shipped with every dispatch. Admission allocates a request's blocks
  from a host-side refcounted free list (``kv_pool.BlockPool``); decode
  writes resolve ``pos -> (table[pos // bt], pos % bt)`` (one window
  DMA per row on the Pallas path — ``kv_pool_insert_rows_pallas``) and
  attention reads the row's logical slots through the same table: in
  place, by the block-table kernel ``dcp_paged_decode_attn``, where the
  pool is eligible (``ops/attention.py::paged_read_path``: unsharded on
  a TPU, float, heads of 128 lanes), else over a gathered logical view
  (``ops/attention.py::cache_write_and_attend``, paged format;
  ``stats_snapshot()["paged_read"]`` names which). Rows no
  longer own contiguous cache memory, which is what makes PREFIX
  SHARING possible at all. Parked/free rows point at the reserved
  trash block, where their per-tick garbage writes can never corrupt a
  live or cached block. Each dispatch ships the tables SLICED to the
  smallest rung of a geometric width-bucket ladder covering the live
  working set (``decode_width_buckets``; ISSUE 19), so the gathered
  read's per-tick KV traffic tracks live tokens, not the horizon (the
  kernel's follows each row's position whatever the rung) — one
  compiled program per rung, token-identical at every width.
- **Radix prefix cache** (``prefix_cache=True``): a host-side radix
  tree over prompt-HEAD tokens (``kv_pool.RadixCache``) maps a new
  request's longest cached prefix to already-prefilled blocks. The
  request ATTACHES: full blocks are shared read-only (refcount++), a
  prefix ending mid-block is COPY-ON-WRITE (the partial block is
  device-copied before the row may write into its span), and only the
  unshared suffix runs prefill — repeated prefill compute becomes a
  block lookup, the production traffic shape where thousands of
  requests share a long system prompt. Admission lays every prompt out
  from LOGICAL SLOT 0 (tokens-then-free, no left padding), so a shared
  token prefix always produces bit-identical K/V at identical
  positions — the invariant that makes attaching exact: learned
  positions embed the logical index, RoPE keys rotate at their own
  absolute slots, and the (seed, tokens-generated) sampling key
  schedule is position-based, so greedy AND sampled streams stay
  token-identical to the cache-off path. Eviction is LRU over tree
  entries, freeing refcount-0 blocks only. MoE models are refused:
  routing is group-dependent, so a suffix-only group cannot reproduce
  the standalone queues when capacity binds.
- **Decode segments**: one jitted ``lax.scan`` of ``segment`` ticks over
  all slots (the same per-tick math as ``infer.py`` — ``decode_step``
  per block, in-place pool writes, per-row sampling). Pool/tokens carry
  ACROSS calls as donated buffers, so consecutive segments reuse the
  same compiled program at zero re-trace cost.
- **Block passes** (a model that generates by BLOCK DIFFUSION,
  ``model.block_generation``: ``models/hybrid.py``): the segment is
  ``_block_segment_impl`` instead, ``segment`` PASSES in which a row takes
  a block of ``block_length`` positions and yields none to
  ``block_length`` tokens by its phase (denoise: some of the block's
  masked positions take their token, the position stays; commit: the
  finished block's K/V are final, its tokens are delivered, the position
  moves a block). The step contract is stated once, where a segment is
  planned (``ContinuousBatcher._row_passes``): a causal model is the case
  "every pass yields one token and moves one slot". Admission prefills the
  prompt's whole blocks under the block mask and the tail opens the first
  generated block; everything else below (tables, overlap, recovery from
  prompt + delivered tokens) is shared.
- **Per-row positions**: every row advances an INDEPENDENT write
  position (``decode_step`` takes a ``[B]`` position vector); a row's
  prompt head occupies logical slots ``[0, n-1)`` and decode continues
  at slot ``n-1`` — ``t_max`` is a PER-REQUEST length bound, rows
  recycle indefinitely on the same compiled programs and a session
  never exhausts.
- **Batched admission**: ALL pending prompts that fit free rows are
  admitted in ONE wave (of at most ``_WAVE_TOKENS`` of prefill window
  between two decode segments; the rest wait for the next). A wave costs
  what its prompts hold: with nothing attached each row takes the
  smallest window RUNG that covers its head (``admission_ladder``:
  ``prompt_buf``, a half, a quarter, an eighth of it, in whole blocks),
  and the rows of a rung go out together, as many as keep the dispatch
  at half of ``prompt_buf`` in window or under (a dispatch that size is
  already compute-bound), so a wave is SEVERAL compiled prefills back to
  back with no decode segment between them.
  The shapes are a small fixed set (seven at ``prompt_buf`` 2048) that the
  batcher builds once, at its first wave, by running each as a null
  dispatch: no later wave, whatever its lengths or row count, meets a
  program that is not already in ``jit``'s cache, and no dispatch needs
  more memory than the one-row, full-window case. Each prompt's
  tokens-but-the-last are prefilled (its SUFFIX past any cached prefix,
  attended against the gathered prefix K/V via the blocks'
  ``kv_prefix`` path); the LAST prompt token becomes the row's current
  token, consumed by the next segment's first tick exactly as standalone
  generation would — admission stays fetch-free. A window that starts at
  position 0 is written into the pool in WHOLE BLOCKS through one index
  on the block axis, in place; attach waves (one dispatch per
  (suffix-window, prefix-window) shape, both rounded to the block size so
  the recurring hot-prefix traffic reuses a handful of programs), chunk
  extensions and int8 pools keep the per-token scatter.
- **Mesh composition**: pass ``mesh=`` and the WHOLE serving session is
  sharded: pool BLOCKS over the batch axes (``data``/``fsdp``), KV
  heads over ``tensor`` (GQA: ``tensor`` must divide ``num_kv_heads``),
  expert FFNs over ``expert`` (``infer._POOL_SPEC``). A row's blocks
  may live on any device; the per-tick gather's output is constrained
  back to the row-sharded decode layout, so XLA inserts whatever
  collective the two layouts imply — the portable-redistribution move
  (arXiv:2112.01075) that resharded admission K/V in the dense design
  now reshards attached blocks.
- **A layer says what it keeps**: a model of layer kinds
  (``models/hybrid.py``) declares each layer's cache leaf by leaf
  (``HybridBlock.cache_leaves``, where each mixer documents its own), and
  every leaf has one of two PLACEMENTS (``ops/attention.py::CacheLeaf``).
  Keyed BY BLOCK: axis 1 is the pool's block axis, so the leaf is on the
  block table and the free list, written by admission in whole blocks,
  copied by copy-on-write and pinned to the pool's sharding (the K/V pool
  above, a latent layer's token vectors, pooled index keys). Keyed BY
  SLOT: one entry a slot, never on the table, written by admission at the
  wave's slots and by a tick for the rows in its plan, never copied (a
  window layer's ring, a tail, a linear-attention layer's state). The
  engine allocates, writes, copies and accounts by placement and compares
  no kind; ``cache_kind`` is a layer's label in
  ``stats_snapshot()["cache_kinds"]`` / ``["cache_bytes_per_token"]`` and
  in what such a model cannot be served with yet, refused at
  construction (``_refuse_for_layer_kinds``).
- **Overlapped host scheduler**: a plain queue, with the single
  device->host fetch per segment (the token harvest) OVERLAPPED with
  the next segment's execution: segment N+1 is dispatched BEFORE
  segment N's tokens are fetched. Sound because rows are
  computationally independent and budget completion is host-known;
  an eos'd row burns at most the one in-flight segment. A freed row's
  blocks return to the pool at harvest; the one in-flight segment may
  still write garbage through the row's OLD table, which is harmless
  by construction: any re-allocated block is fully overwritten by the
  (later-ordered) admission prefill over the slots it exposes, and
  slots beyond a row's live position are never attended.

**Admission fairness (the documented contract).** ``admit_policy=
"fifo"`` (default): requests are admitted strictly in arrival order —
a free row always takes the QUEUE HEAD, and no request is ever
leapfrogged by a later one. Because every row offers the same horizon,
a request whose segment-rounded budget can never fit (``prompt_buf +
ceil(max_new/segment)*segment > t_max``) would block the head FOREVER,
so infeasibility is resolved up front: such requests are set aside,
everything else is served to completion, then :class:`HorizonError` is
raised CARRYING the completed outputs (``.outputs``).
``admit_policy="skip_fit"`` opts out of the head-of-line guarantee
(class docstring).

**Sampling.** Each request carries its own ``temperature`` (0 =
greedy), ``top_k``, ``top_p`` and ``seed``; the compiled segment
samples every row from its own settings and its own counter-based key
stream (``infer.sample_rows``). The key for a row's t-th token depends
only on (seed, tokens-so-far), so sampled outputs are deterministic AND
invariant to ``slots``/``segment`` scheduling — and to prefix
attachment, which changes where K/V come from but not a single logical
position.

Correctness contract (``tests/test_serve.py``,
``tests/test_serve_mesh.py``, ``tests/test_kv_pool.py``):
greedy-served outputs of staggered admissions equal each prompt's
standalone ``infer.generate``, token for token, for GPT-2 (learned
positions), Llama (RoPE/GQA) and the MoE family — off-mesh and under
data/tensor/expert-sharded meshes — and prefix-cache-ON serving equals
prefix-cache-OFF serving token for token, greedy and sampled, with
zero block leaks after drain. MoE capacity: each admission-wave row is
its OWN routing group whose expert queue capacity derives from that
row's REAL prompt length (``moe_capacity_rows``); the documented
no-drop contract on the deferred last prompt token is unchanged.

**Fault tolerance (serve_detailed — the failure domain is ONE
request, never the process).** Per-request deadlines, thread-safe
:meth:`cancel`, bounded admission with load shedding (``max_pending``),
graceful drain off any ``.preempted`` flag, and DEVICE-FAILURE SESSION
RECONSTRUCTION: a raised segment/harvest or a harvest hung past the
``tick_timeout_s`` watchdog zeroes the untrusted device pool, resets
the host block accounting AND the radix cache (its content died with
the pool), and re-prefills every live row's ``prompt +
generated-so-far`` from host-tracked state — token-IDENTICAL resume
(``_reconstruct`` carries the soundness argument, DESIGN.md "Paged KV
and prefix reuse" / "Serving under failure" the long form). Every
request ends in a structured ``serve_lifecycle.RequestResult`` carrying
its cached-prefix length.

Instrumentation: ``stats`` counts segments, fetches, overlapped
fetches, prefill calls/rows, the fault-tolerance counters, and the
prefix-cache counters — ``prefix_hits`` (admissions that attached),
``cached_prefix_tokens`` / ``prefill_tokens_saved`` (tokens attached
instead of re-prefilled), ``cow_copies``, ``block_pool_occupancy``
(peak allocated fraction). ``last_block_leaks`` extends the PR 5
slot-leak discipline to blocks: after a serve call every pool
reference must be owned by the radix tree (or the pinned trash block)
— asserted by the tests alongside ``last_slot_leaks``.

Telemetry (ISSUE 8, ``obs/``): ``stats``/``waste`` are dict-compatible
VIEWS over a per-batcher ``obs.metrics.Registry``; per-request SLO
histograms (queue-wait, TTFT, TPOT, e2e — measurement points on
``serve_lifecycle.RequestResult``) accumulate beside them and
:meth:`ContinuousBatcher.stats_snapshot` serialises everything. The
scheduler's decision points — ``admit_wave`` > ``prefill_wave``,
``dispatch_segment``, ``harvest``, ``reconstruct``, ``await_arrival``,
drain/fault instants — run under ``obs.tracing.span``: each reaches
any running profile's host plane (``--profile_dir``,
``--profile_segments``) with the ids of the requests it handles
(``rids``; ``harvest`` says which got their ``first`` token and which
are ``done``), and is a Chrome-trace event when a tracer is configured
(``--trace_path``); with neither it is a shared null context. The
compiled programs name their parts for the same profile with
``obs.tracing.scope`` (the vocabulary is listed once, in ``obs/``):
``admit`` round the admission prefill, ``decode`` round the segment's
tick, and inside them the models' ``embed``/``attn``/``mlp``/``head``
and ``kv_gather`` (the paged view at the width rung, where the pool is
read through the gather), ``kv_write``,
``sample``. Open-loop
load rides in-band: ``Request.arrival_s`` delays admission to the
request's arrival instant and the scheduler idles across arrival gaps
(``obs/loadgen.py`` — the ROADMAP-3 Poisson load generator).
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import math
from bisect import bisect_right
import threading
import time
import weakref
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import (
    constrain, named_sharding, use_mesh)
from distributed_compute_pytorch_tpu.infer import (
    _CACHE_SPEC, _POOL_SPEC, sample_rows, verify_sample_rows)
from distributed_compute_pytorch_tpu.kv_pool import (
    TIER_DEVICE, TIER_DISK, TIER_HOST, BlockPool, PoolExhausted,
    RadixCache)
from distributed_compute_pytorch_tpu.kv_tier import (
    TIER_STATS, DiskTier, HostBlockPool, KVTierManager, _crc,
    host_blocks_for_mb)
from distributed_compute_pytorch_tpu.obs import flight
from distributed_compute_pytorch_tpu.obs import metrics as obs_metrics
from distributed_compute_pytorch_tpu.obs.metrics import device_memory_gauges
from distributed_compute_pytorch_tpu.obs.tracing import (
    instant, scope, span)
from distributed_compute_pytorch_tpu.serve_journal import JOURNAL_STATS
from distributed_compute_pytorch_tpu.serve_lifecycle import (
    CANCELLED, FAILED, OK, SHED, TIMEOUT, RequestResult)
from distributed_compute_pytorch_tpu.train.checkpoint import (
    dominant_float_dtype)
from distributed_compute_pytorch_tpu.train.elastic import call_with_timeout
from distributed_compute_pytorch_tpu.utils.quantize import quantize_kv

# (model class, model config, block tokens, segment, mesh devices+axes)
# -> weakref to the first live batcher that jitted programs for that
# shape family; later identical batchers borrow its bound jit objects
# instead of re-paying trace+compile (see the __init__ note).
_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_LOCK = threading.Lock()

# The most prefill window (rows x tokens, pad rows included: the sum of
# ``R x W`` over a wave's dispatches) that runs BETWEEN TWO DECODE
# SEGMENTS. Every decoding row waits while a wave runs, so this bounds
# what an admission storm may add to one gap between tokens; requests
# past it wait one decode segment for the next wave. It is no longer the
# chip's memory: no dispatch holds more than ``prompt_buf`` tokens of
# window (``admission_ladder``), so a wave of any number of rows fits
# where the one-row, full-window case fits. Windows this large are
# compute-bound on any chip: two waves cost the device what one of their
# sum would. Not tuned.
_WAVE_TOKENS = 32768

# The buckets of the gap between two deliveries of one request
# (``_run::count_gaps``): their upper edges in seconds, spaced as
# ``obs.metrics.Histogram`` spaces its own, 16 a decade from 10 ms to 10 s
# (a gap under 10 ms counts in the first), and the ``stats`` counter of
# each, one more than the edges for the gaps beyond the last.
# ``delivery_gap_upto_<ms>`` carries the edge in ms at three significant
# digits (``_inf`` where there is none), so a reader of the counters needs
# no copy of the edges.
_GAP_EDGES_S = tuple(1e-2 * 10.0 ** (i / 16) for i in range(1, 49))
_GAP_COUNTERS = (
    *(f"delivery_gap_upto_{float(f'{1e3 * e:.3g}'):g}" for e in _GAP_EDGES_S),
    "delivery_gap_upto_inf")


# The ``stats`` counters of a model that generates by block diffusion
# (``_run::dispatch_block_segment``): passes dispatched (segments x S); the
# row-passes of rows in a plan while their request is owed tokens, and of
# those the two phases; blocks committed; positions
# generated past a request's ``max_new`` (its last block is denoised whole);
# prompt tokens that filled no block and opened the first generated one.
_BLOCK_COUNTERS = ("block_passes", "block_row_passes",
                   "denoise_row_passes", "commit_row_passes",
                   "blocks_committed", "block_tokens_cut",
                   "prompt_tail_tokens")


@functools.partial(jax.jit, static_argnums=1)
def _unstack(leaf, sharding):
    """One stacked leaf ``[L, ...]`` as its ``L`` per-layer slices. Under a
    mesh ``sharding`` is the leaf's own on the axes that remain; off-mesh
    (``None``) a slice stays where the leaf is."""
    cut = tuple(leaf[i] for i in range(leaf.shape[0]))
    return cut if sharding is None else tuple(
        lax.with_sharding_constraint(c, sharding) for c in cut)


def admission_ladder(prompt_buf: int, block: int) -> tuple:
    """The shapes of an admission dispatch: ``((W, rows), ...)``, widest
    window first. ``W_i = prompt_buf / 2^i`` for ``i = 0..3``, rounded up
    to whole blocks, a rung under one block or equal to the one before it
    dropped; rows of rung ``i`` go out at most ``2^(i-1)`` at a time (one
    at a time at the two widest rungs), so no dispatch but the one-row,
    full-window case holds more than half of ``prompt_buf`` in window (a
    block's rounding apart). Half is where the v5e said a dispatch is
    already compute-bound (a row of 1024 tokens takes 43.4 ms, two such
    rows in one dispatch 81.9: PERF.md, PR 29), so wider dispatches would
    buy nothing and cost a program each to build. 2048 / 8 gives
    (2048, 1), (1024, 1), (512, 2), (256, 4): seven shapes."""
    rungs = []
    for i in range(4):
        if i and prompt_buf < block << i:
            break
        w = -(-prompt_buf // (block << i)) * block
        if not rungs or w < rungs[-1][0]:
            rungs.append((w, 1 << max(0, i - 1)))
    return tuple(rungs)


def ladder_shapes(ladder: tuple, dp: int = 1) -> list:
    """Every ``(R, W)`` a wave can dispatch: ``R`` is the batch-axes
    product ``dp`` (1 off-mesh) times a power of two, up to the rung's
    rows."""
    return [(dp << e, w) for w, rows in ladder
            for e in range((-(-rows // dp) - 1).bit_length() + 1)]


def wave_dispatches(heads: list, ladder: tuple, dp: int = 1) -> list:
    """Group a wave's rows into dispatches: ``[(W, R, [row indices])]``.
    A row's rung is the smallest that covers its head; the rows of a rung
    go out in arrival order, as many at a time as the rung takes (or as
    the batch axes do, if more: a device's share is then still one row),
    and each group is padded up to ``dp`` times a power of two with rows
    that hold and write nothing."""
    by_rung: dict = {}
    for j, n in enumerate(heads):
        w, rows = next(r for r in reversed(ladder) if r[0] >= n)
        by_rung.setdefault((w, max(rows, dp)), []).append(j)
    out = []
    for (w, rows), idx in sorted(by_rung.items(), reverse=True):
        for a in range(0, len(idx), rows):
            take = idx[a:a + rows]
            out.append(
                (w, dp << (-(-len(take) // dp) - 1).bit_length(), take))
    return out


@dataclass
class Request:
    """One generation request: ``tokens`` (prompt ids) in, up to
    ``max_new`` continuations out (fewer if ``eos_id`` fires).

    ``temperature`` 0 (default) decodes greedily; > 0 samples, with
    optional ``top_k``/``top_p`` truncation (both require temperature
    > 0, mirroring ``infer.generate``). ``seed`` fixes the request's
    sampling stream; ``None`` defaults to the request's index in the
    ``serve()`` call, so a whole call is deterministic by default.

    ``deadline_s`` is a WALL-CLOCK budget measured from submission
    (the ``serve_detailed`` call): a request still queued when it
    expires is finalised ``timeout`` with no device work; one
    in-flight is cut at the next segment boundary, returning the
    partial stream (so expiry can overshoot by up to one segment's
    wall time). ``None`` = no deadline (the legacy contract).

    ``arrival_s`` is the request's OPEN-LOOP arrival offset (seconds
    from the serve call's start): the scheduler will not admit the
    request before that wall-clock instant, and idles to the next
    arrival when the pool drains early — how ``obs.loadgen`` drives a
    Poisson arrival process through the synchronous engine. 0
    (default) is the legacy everything-arrives-at-submission shape.
    ``deadline_s`` still counts from SUBMISSION, so an offered-load
    deadline covers queue-wait too (the SLO a router cares about)."""

    tokens: list
    max_new: int
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    seed: int | None = None
    deadline_s: float | None = None
    arrival_s: float = 0.0
    # stable identity for journal recovery (ISSUE 15): dedup and
    # replay key on it across process restarts. ``None`` defaults to
    # the request's position in the serve call (``req-{i}``) — fine
    # inside one call, but resubmitters that reorder must set it.
    request_id: str | None = None


@dataclass
class _Slot:
    """Host-side bookkeeping for one cache row."""

    req_index: int = -1        # position in the request list (-1 = free)
    remaining: int = 0
    out: list = field(default_factory=list)
    admit_seq: int = -1        # admission order (poison-eviction heuristic)
    blocks: list = field(default_factory=list)   # owned pool block refs
    # chunked-prefill state (prefill_chunk_tokens): the full known
    # tokens of a row admitted mid-prompt, and how many logical head
    # tokens (attached prefix included) are prefilled so far. None =
    # fully prefilled — the only rows decode plans may include.
    pf_known: list | None = None
    pf_done: int = 0

    def free(self):
        self.req_index = -1
        self.remaining = 0
        self.out = []
        self.admit_seq = -1
        self.blocks = []
        self.pf_known = None
        self.pf_done = 0


class HorizonError(RuntimeError):
    """A request's segment-rounded budget can never fit the per-row
    horizon (``prompt_buf + ceil(max_new/segment)*segment > t_max``).

    Raised AFTER every admissible request has been served; ``outputs``
    holds the completed results (in request order, ``[]`` for the
    rejected requests) so finished work is never discarded."""

    def __init__(self, message: str, outputs: list):
        super().__init__(message)
        self.outputs = outputs


class ContinuousBatcher:
    """Fixed-pool continuous batching for one causal LM, over a paged
    block-table KV cache.

    Args:
      model: any ``infer.py``-contract model (GPT-2 / Llama / MoE).
      params: its (possibly quantized) parameters, in the form
        ``model.init`` and a checkpoint give them (a stacked family's
        ``params["blocks"]`` with a leading layer axis on every leaf) —
        already committed to the mesh layout when ``mesh`` is given. The
        caller's tree stays the caller's: the engine neither deletes nor
        donates it. The ENGINE's weights (``self.params``, what every
        compiled program takes) hold one tree a layer instead, cut from
        the stack once, a leaf at a time, at the first dispatch
        (:meth:`_cut_weights`; until then ``self.params`` is still the
        stack); a model of layer kinds hands over that form already. A
        caller whose weights fill the device drops its own reference
        once the constructor has returned (``dcp-serve`` does, and keeps
        a fleet's copy on the host): the engine is then each stacked
        leaf's last owner and the conversion never holds more than the
        weights and their largest leaf; a caller that keeps its tree on
        the engine's device keeps both forms there.
      slots: cache rows decoding concurrently (the static batch). Under
        a mesh it must divide over the batch axes
        (``data * fsdp | slots``).
      t_max: each ROW's logical length bound: one request needs
        ``prompt_buf + ceil(max_new/segment)*segment <= t_max``. Rounded
        up to the block size so every row's table covers whole blocks
        (the block size itself is window-aligned, so this subsumes the
        old Pallas-window rounding; extra slots are never attended).
      prompt_buf: static prompt window; prompts longer than this are
        rejected (size it to the workload's longest prompt).
      segment: ticks per compiled decode call.
      eos_id: optional stop token (rows stop early and free their slot).
      mesh: optional ``jax.sharding.Mesh`` — SHARDED serving (module
        docstring): pool blocks over the batch axes, KV heads over
        ``tensor`` (must divide ``num_kv_heads``), expert FFNs over
        ``expert``; ``seq`` is rejected.
      admit_policy: ``"fifo"`` (default) or ``"skip_fit"``.
      max_pending: bounded admission (``None`` = unbounded).
      tick_timeout_s: the tick watchdog (``None`` = no watchdog).
      max_recoveries: session reconstructions per ``serve_detailed``
        call before declaring the device lost.
      kv_block_tokens: logical slots per pool block (default: the
        Pallas cache window — 8 for bf16/f32 caches; rounded up to a
        window multiple otherwise). Smaller blocks share prefixes at a
        finer grain; larger blocks cut table length and per-wave
        compile variety.
      prefix_cache: enable the radix prefix cache (module docstring).
        Off by default — the paged pool alone is behaviour-identical to
        the old dense-window design. Refused for MoE models (routing is
        group-dependent).
      pool_blocks: physical blocks in the pool (default:
        ``slots * (t_max // bt) + 1`` — every row can always allocate
        its worst-case table after LRU eviction — plus 4 rows' worth of
        cache headroom when ``prefix_cache`` is on). Rounded up to a
        batch-axes multiple under a mesh.
      host_cache_mb: hierarchical KV (``kv_tier``, DESIGN.md
        "Hierarchical KV"): size of the host-RAM spill pool in MiB.
        LRU eviction then DEMOTES refcount-0 prefix entries D2H
        instead of discarding them, and a later match promotes them
        back with one async H2D copy — the radix working set outlives
        the device pool. Requires ``prefix_cache``. ``None`` = off
        (discard-on-evict, the pre-tier behaviour).
      host_cache_blocks: the same budget in blocks (tests/sizing by
        hand); wins over ``host_cache_mb``.
      disk_cache_dir: optional CRC-verified disk tier below the host
        pool (``part-NNNNN.npz`` + per-entry CRC-32, the v2 shard
        entry format): host-pool pressure spills LRU demoted entries
        there; a corrupt part degrades to a cache miss, never a
        failure. Requires a host tier.
      prefill_chunk_tokens: CHUNKED PREFILL (DESIGN.md "Disaggregated
        and chunked prefill"): bound every prefill wave to about this
        many suffix tokens (rounded up to the block size). A prompt
        longer than the budget admits its first chunk only, then
        extends chunk-by-chunk between decode segments through the
        same bottom-right-causal ``kv_prefix`` suffix-prefill path an
        attach wave rides — decode-tick latency stays flat under
        long-prompt admission storms. Positions are logical and
        sampling keys depend only on (seed, position), so chunked
        serving is TOKEN-IDENTICAL to unchunked, greedy or sampled.
        Refused for MoE models (chunking splits a prompt's routing
        group — the prefix-cache precedent). ``None`` = off (whole
        unshared suffixes in one wave, the legacy shape).
      heartbeat_s: emit a telemetry heartbeat every this many seconds
        of serving: ``on_heartbeat(stats_snapshot())`` runs in the
        scheduler thread between device calls (``dcp-serve`` prints it
        as one stderr JSON line). ``None`` = off.
      on_heartbeat: the heartbeat callback. Exceptions are swallowed —
        telemetry must never fail a request.
      speculate: speculative decoding (DESIGN.md "Speculative
        decoding"): an int ``k`` (draft k tokens per verify step with
        the self-drafting n-gram proposer) or a full
        ``spec_decode.SpecConfig``. Each verify step scores the row's
        current token plus its ``k`` drafts in ONE forward pass and
        emits the longest accepted prefix plus the model's own token at
        the first mismatch — the accept rule is EXACT, so outputs stay
        token-identical to ``speculate=None`` (greedy and sampled;
        proposer quality only moves throughput). Refused for MoE
        models (routing is group-dependent, the prefix-cache
        precedent). Sustained low acceptance auto-disables back to
        plain segment decode (``SpecConfig.autodisable_*``).
      kv_dtype: the POOL's storage dtype (DESIGN.md "Quantized KV").
        ``"bf16"`` (default) stores blocks in the params' activation
        dtype — the exact, token-identical path. ``"int8"`` stores each
        block as symmetric int8 with per-(position, head) f32 scales
        in a ``"scale"`` leaf beside ``"kv"`` (``utils/quantize.py::
        quantize_kv``): quantization fuses into every write (admission
        scatter, decode/verify tick — ``ops/attention.py`` branches on
        the scale leaf) and dequantization into every gathered read,
        roughly doubling resident prefix tokens per HBM/host/disk/
        handoff byte. Token-identical parity is SURRENDERED at int8;
        the replacement contract is bounded per-position logit error
        and ≥99% greedy match (``tests/test_kv_quant.py``).
        Radix keys, CRC stamps and journal replay stay dtype-agnostic
        (they key on token ids, not bytes); handoff payloads carry a
        dtype stamp and mixed-dtype imports decline to replay.

    Telemetry (ISSUE 8): every batcher owns a private
    ``obs.metrics.Registry`` (``self.obs``); ``stats``/``waste`` are
    dict-compatible views over it, the SLO histograms (queue-wait,
    TTFT, TPOT, e2e) live beside them, and :meth:`stats_snapshot`
    serialises the lot. :meth:`profile_next` arms on-demand XLA
    profiling of the next N dispatched segments.
    """

    def __init__(self, model, params, *, slots: int, t_max: int,
                 prompt_buf: int, segment: int = 16,
                 eos_id: int | None = None, mesh=None,
                 admit_policy: str = "fifo",
                 max_pending: int | None = None,
                 tick_timeout_s: float | None = None,
                 max_recoveries: int = 2,
                 kv_block_tokens: int | None = None,
                 prefix_cache: bool = False,
                 pool_blocks: int | None = None,
                 host_cache_mb: float | None = None,
                 host_cache_blocks: int | None = None,
                 disk_cache_dir: str | None = None,
                 prefill_chunk_tokens: int | None = None,
                 heartbeat_s: float | None = None,
                 on_heartbeat=None,
                 speculate=None,
                 journal=None,
                 journal_dir: str | None = None,
                 journal_fsync: str = "every_harvest",
                 kv_dtype: str = "bf16",
                 decode_width_buckets: int | None = None,
                 weights_version: int = 0):
        from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
            _pallas_ok, _window)
        from distributed_compute_pytorch_tpu.ops.attention import (
            paged_read_path)
        if prompt_buf > t_max:
            raise ValueError(f"prompt_buf {prompt_buf} > t_max {t_max}")
        if admit_policy not in ("fifo", "skip_fit"):
            raise ValueError(f"admit_policy must be 'fifo' or 'skip_fit', "
                             f"got {admit_policy!r}")
        if max_pending is not None and max_pending < 0:
            raise ValueError(f"max_pending must be >= 0, got {max_pending}")
        if tick_timeout_s is not None and tick_timeout_s <= 0:
            raise ValueError(
                f"tick_timeout_s must be > 0, got {tick_timeout_s}")
        if max_recoveries < 0:
            raise ValueError(
                f"max_recoveries must be >= 0, got {max_recoveries}")
        if kv_block_tokens is not None and kv_block_tokens < 1:
            raise ValueError(
                f"kv_block_tokens must be >= 1, got {kv_block_tokens}")
        if heartbeat_s is not None and heartbeat_s <= 0:
            raise ValueError(f"heartbeat_s must be > 0, got {heartbeat_s}")
        if host_cache_mb is not None and host_cache_mb <= 0:
            raise ValueError(
                f"host_cache_mb must be > 0, got {host_cache_mb}")
        if host_cache_blocks is not None and host_cache_blocks < 1:
            raise ValueError(
                f"host_cache_blocks must be >= 1, got {host_cache_blocks}")
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got "
                f"{prefill_chunk_tokens}")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}")
        if decode_width_buckets is not None and decode_width_buckets < 1:
            raise ValueError(
                f"decode_width_buckets must be >= 1, got "
                f"{decode_width_buckets} (1 = a single full-horizon "
                f"bucket, i.e. width bucketing off)")
        _tier_on = (host_cache_mb is not None
                    or host_cache_blocks is not None
                    or disk_cache_dir is not None)
        if _tier_on and not prefix_cache:
            raise ValueError(
                "host_cache_mb/host_cache_blocks/disk_cache_dir extend "
                "the radix prefix cache — they require prefix_cache=True")
        if (disk_cache_dir is not None and host_cache_mb is None
                and host_cache_blocks is None):
            raise ValueError(
                "disk_cache_dir needs a host tier to stage through "
                "(set host_cache_mb or host_cache_blocks)")
        self.max_pending = max_pending
        self.tick_timeout_s = tick_timeout_s
        self.max_recoveries = max_recoveries
        self.heartbeat_s = heartbeat_s
        self.on_heartbeat = on_heartbeat
        self._profile_req: dict | None = None
        self._cancel_mu = threading.Lock()
        self._cancelled: set[int] = set()
        self.model = model
        self.B = slots
        self.Tb = prompt_buf
        self.S = segment
        self.eos_id = eos_id
        self.admit_policy = admit_policy
        self._mesh = mesh
        # a model built from a list of layer kinds (models/hybrid.py) is
        # asked for the block, the parameters and the cache kind of layer
        # i; every other family stacks ONE block kind (model._block(),
        # params["blocks"] with a leading layer axis)
        self._layer_blocks = (
            [model.layer_block(i) for i in range(model.num_layers)]
            if hasattr(model, "layer_block") else None)
        # how the model generates is the model's own: None (causal, a pass
        # yields one token a row) or block diffusion's (block_length,
        # denoising_steps, remasking, mask_token_id): the STEP CONTRACT of
        # _row_passes, the pass of _block_segment_impl
        self._blockgen = getattr(model, "block_generation", None)
        if self._blockgen is not None:
            self._refuse_for_block_generation(
                self._blockgen[2], prefix_cache=prefix_cache,
                speculate=speculate, kv_dtype=kv_dtype, mesh=mesh,
                prefill_chunk_tokens=prefill_chunk_tokens)
        if self._layer_blocks is not None:
            self._refuse_for_layer_kinds(
                {b.cache_kind for b in self._layer_blocks},
                prefix_cache=prefix_cache, speculate=speculate,
                tiers=(host_cache_mb is not None
                       or host_cache_blocks is not None
                       or disk_cache_dir is not None),
                kv_dtype=kv_dtype, mesh=mesh,
                prefill_chunk_tokens=prefill_chunk_tokens)
        self._block = (self._layer_blocks[0] if self._layer_blocks
                       is not None else model._block())
        # does the block rope internally (needs absolute-slot positions
        # at admission)? Llama does; GPT-2/MoE embed positions instead.
        sig = inspect.signature(self._block.apply).parameters
        self._block_takes_positions = "positions" in sig
        self._block_takes_kv_prefix = "kv_prefix" in sig
        # MoE admission capacity (ADVICE r5): blocks whose prefill routing
        # accepts an explicit capacity get it derived from the REAL prompt
        # length, not the padded window (see _prefill_wave); the per-row
        # form carries each wave row's own capacity
        self._block_takes_moe_capacity = "moe_capacity" in sig
        self._block_takes_moe_capacity_rows = "moe_capacity_rows" in sig
        if prefix_cache and self._block_takes_moe_capacity:
            # MoE routing is group-dependent: a suffix-only admission
            # group cannot reproduce the standalone full-prompt expert
            # queues when capacity binds, so attached serving could
            # silently diverge from the cache-off path — refuse instead
            raise ValueError(
                "prefix_cache does not compose with MoE models (routing "
                "is group-dependent; a cached prefix cannot be skipped "
                "without changing the suffix's routing group)")
        if prefill_chunk_tokens is not None:
            if self._block_takes_moe_capacity:
                # same group-dependence as the prefix-cache refusal: a
                # chunk routes as its own group where the whole prompt
                # routed as one, so capacity-bound expert drops could
                # silently diverge from the unchunked path
                raise ValueError(
                    "prefill_chunk_tokens does not compose with MoE "
                    "models (routing is group-dependent; a chunked "
                    "prompt cannot reproduce the whole-prompt routing "
                    "group)")
            if not self._block_takes_kv_prefix:
                raise ValueError(
                    f"prefill_chunk_tokens needs a block family with "
                    f"kv_prefix suffix-prefill support; "
                    f"{type(self._block).__name__} has none")
        self.prefix_cache = prefix_cache
        if speculate is not None:
            from distributed_compute_pytorch_tpu.spec_decode import (
                SpecConfig, make_proposer)
            if not isinstance(speculate, SpecConfig):
                speculate = SpecConfig(k=int(speculate))
            if self._block_takes_moe_capacity:
                # MoE routing is group-dependent: a verify window routes
                # its k+1 positions as ONE group where tick-by-tick
                # decode routes them as k+1 groups, so capacity-bound
                # token drops could diverge from the plain path —
                # refuse, mirroring the prefix_cache precedent above
                raise ValueError(
                    "speculate does not compose with MoE models (routing "
                    "is group-dependent: a verify window's k+1 positions "
                    "route as one group, plain decode routes them "
                    "tick-by-tick, so capacity-bound drops could "
                    "silently diverge)")
            if not hasattr(self._block, "verify_step"):
                raise ValueError(
                    f"speculate needs a block family with verify_step; "
                    f"{type(self._block).__name__} has none")
            self._proposer = make_proposer(speculate)
        else:
            self._proposer = None
        self._spec = speculate
        self._spec_w = (speculate.k + 1) if speculate is not None else 0
        self._spec_on = speculate is not None
        self._spec_win = [0, 0]      # (proposed, accepted) this window
        hk, hd = model.kv_cache_spec()
        if mesh is not None:
            shape = dict(mesh.shape)
            tp = shape.get("tensor", 1)
            if tp > 1 and hk % tp:
                # GQA shards the NARROW cache: an indivisible kv-head dim
                # would make XLA pad-and-replicate it, silently defeating
                # the layout (same check as infer.make_generate_fn)
                raise ValueError(
                    f"tensor axis ({tp}) must divide num_kv_heads ({hk}) "
                    f"for sharded serving — the KV cache shards on kv "
                    f"heads")
            if shape.get("seq", 1) > 1:
                raise ValueError("serving does not compose with a seq>1 "
                                 "mesh axis; fold those devices into data")
            dp = shape.get("data", 1) * shape.get("fsdp", 1)
            if slots % dp:
                raise ValueError(
                    f"slots ({slots}) must divide over the batch axes "
                    f"(data*fsdp = {dp}) so every device owns whole "
                    f"cache rows")
            self._dp = dp
        else:
            self._dp = 1
        self._hold(params)
        n_layers = self._n_layers
        # compute dtype == the dtype most floating parameter elements are
        # in (bf16 serving params -> bf16 activations, whatever the f32
        # norm scales say; int8-quantized trees surface their float
        # embeddings, same outcome). kv_dtype="bf16" stores blocks in
        # that dtype; "int8" stores int8 blocks + a per-(position, head)
        # f32 scale leaf, quantized on write and dequantized on read
        self._cdtype = dominant_float_dtype(
            (l.shape, l.dtype) for l in jax.tree.leaves(params)
        ) or jnp.dtype(jnp.float32)
        self.kv_dtype = kv_dtype
        # weights-version stamp (ISSUE 20): every KV byte this engine
        # caches (radix entries, tier sidecars, handoff payloads) is
        # stamped with the version of the weights that computed it, so
        # an old-version prefix can never attach to new weights — a
        # mismatch anywhere DECLINES (serve.fleet.version_declined) and
        # falls back to token replay, never raises. reload_weights()
        # bumps it.
        self.weights_version = int(weights_version)
        dtype = jnp.int8 if kv_dtype == "int8" else self._cdtype
        # block size: a multiple of the in-place Pallas slot write's
        # window so the paged write keeps the one-window-DMA fast path
        # (int8 tiles need 32 sublanes — _window knows); t_max rounds up
        # to whole blocks (ADVICE r5's alignment move, now at block
        # granularity — observationally free, the per-row position mask
        # stops at each row's live position)
        align = _window(dtype)
        # a model of layer kinds whose cache implies a block size says so
        # (a latent layer's token is one short vector: models/hybrid.py)
        implied = (model.cache_block_tokens
                   if self._layer_blocks is not None else None)
        bt = kv_block_tokens if kv_block_tokens is not None else (
            implied or align)
        self.bt = -(-bt // align) * align
        if self._blockgen is not None and self.bt % self._blockgen[0]:
            raise ValueError(
                f"kv_block_tokens ({self.bt}) must be a multiple of the "
                f"model's block_length ({self._blockgen[0]}): a block of "
                f"positions never straddles two pool blocks")
        self.t_max = -(-t_max // self.bt) * self.bt
        self.nb = self.t_max // self.bt          # table entries per row
        # width-bucket ladder (ISSUE 19): every decode/verify dispatch
        # slices the shipped tables to the smallest rung (power-of-two
        # multiples of bt, capped at nb) covering the live working set,
        # so per-tick KV gather traffic tracks live tokens instead of
        # the horizon. All gathered views, validity masks, and slot
        # masks derive their width from the table argument
        # (ops/attention.py), so the slice needs no op-side plumbing;
        # the shared jit keys on the table aval, one compiled program
        # per rung. decode_width_buckets keeps only the WIDEST k rungs
        # (1 = full-horizon only, the pre-bucketing behaviour — the
        # on/off A/B lever; outputs are token-identical either way
        # because slots beyond a row's live extent are mask-invalid).
        self.decode_width_buckets = decode_width_buckets
        ladder, w = [], 1
        while w < self.nb:
            ladder.append(w)
            w *= 2
        ladder.append(self.nb)
        if decode_width_buckets is not None:
            ladder = ladder[-decode_width_buckets:]
        self._width_ladder = tuple(ladder)
        self._cur_width = self._width_ladder[0]
        self._widths_dispatched: set = set()
        # chunked prefill: block-rounded per-WAVE suffix budget (the
        # chunk is the wave's static window, so rounding keeps the
        # scatter whole-block and the program count at ~one per mode)
        self._chunk = (None if prefill_chunk_tokens is None else
                       -(-prefill_chunk_tokens // self.bt) * self.bt)
        # the shapes an admission dispatch takes when its window starts
        # at position 0 (nothing attached, no chunking): chosen from what
        # the wave's prompts hold, all built before the first request is
        # served (_warm_ladder)
        self._admit_ladder = admission_ladder(self.Tb, self.bt)
        self._ladder_warm = False
        min_blocks = slots * self.nb + 1         # + the trash block
        if pool_blocks is None:
            pool_blocks = min_blocks + (4 * self.nb if prefix_cache else 0)
        if pool_blocks < min_blocks:
            raise ValueError(
                f"pool_blocks={pool_blocks} < slots*blocks_per_row+1="
                f"{min_blocks}: a full pool could deadlock admission "
                f"(eviction frees only refcount-0 blocks)")
        # blocks shard over the batch axes: keep the axis divisible
        pool_blocks = -(-pool_blocks // self._dp) * self._dp

        # off-mesh the engine lives where its parameters live: a replica
        # whose params were placed on local device i (dcp-serve
        # --replicas) keeps its pool and row state there, and the
        # compiled programs follow their committed operands
        self._device = (None if mesh is not None else next(iter(
            jax.tree.leaves(params)[0].devices())))

        def zeros(shape, dtype, spec):
            if mesh is None:
                # born on the engine's device: nothing lands on device 0
                return jnp.zeros(shape, dtype, device=self._device)
            return jax.device_put(jnp.zeros(shape, dtype),
                                  named_sharding(mesh, spec))

        # one entry a layer, of the leaves the layer declares (_declared):
        # a dense family's is the paged K/V pool [2(k/v), P, hk, bt, hd],
        # each tick's write one window DMA per row through the block table
        # (ops/pallas/cache_update.py::kv_pool_insert_rows_pallas), with
        # the int8 "scale" leaf beside it; a layer of kinds says its own
        # (models/hybrid.py::HybridBlock.cache_leaves). A leaf keyed by
        # BLOCK is pinned to the pool's sharding, on the block table and
        # the free list; one keyed by SLOT is none of these. Every consumer
        # (attention ops, COW copies, reset/reconstruct zeroing: zero = a
        # slot or a block that holds nothing yet) goes by that placement.
        self._cache_kinds = (("paged",) * n_layers
                             if self._layer_blocks is None else
                             tuple(b.cache_kind for b in self._layer_blocks))
        self._leaves = self._declared(slots, pool_blocks)
        # which layers keep anything on the block table
        self._on_table = [any(l.by_block for l in leaves.values())
                          for leaves in self._leaves]
        if not any(self._on_table):
            raise ValueError(
                "a model of window and state layers only is not served: "
                "the scheduler's block accounting needs one paged layer")
        # the layer the block accounting and the engine report look at
        self._paged0 = self._on_table.index(True)
        self._caches = [
            {name: zeros(l.shape, l.dtype, _POOL_SPEC if l.by_block else None)
             for name, l in leaves.items()} for leaves in self._leaves]
        # does any layer keep a leaf by SLOT? Then an admission dispatch is
        # told which slot each of its rows fills, and a row that prefills
        # nothing is dispatched all the same (_prefill_wave)
        self._slot_state = any(not l.by_block for leaves in self._leaves
                               for l in leaves.values())
        # does any layer keep no tokens at all (a state a slot)?
        self._state_layers = any(
            not any(l.tokens for l in leaves.values())
            for leaves in self._leaves)
        # bytes one layer of each kind keeps of one cached token, as
        # allocated (a latent token's 576 channels in 640 lanes: 1280; a
        # pooled index key is shared by the tokens of its group; a state
        # layer keeps none) ...
        layers = list(zip(self._caches, self._leaves, self._cache_kinds))
        self._cache_bytes_per_token = {
            kind: sum(c[name].nbytes // (l.tokens * l.shape[
                          1 if l.by_block else l.slot_axis])
                      for name, l in leaves.items() if l.tokens)
            for c, leaves, kind in layers}
        # ... and of a SLOT beside what grows with its tokens (kinds that
        # keep none are left out)
        self._state_bytes_per_slot = {
            kind: sum(c[name].nbytes for name, l in leaves.items()
                      if not l.tokens) // slots
            for c, leaves, kind in layers
            if not all(l.tokens for l in leaves.values())}
        # the stats that each entry of a decode tick's count vector adds
        # to (none for a model without held experts)
        held = (model.counted_experts()
                if hasattr(model, "counted_experts") else 0)
        # ... and, last, the two counts of a layer that attends a learned
        # SELECTION of its cache: the tokens the rows in the plan attended
        # and the tokens they had in context, summed over ticks and layers
        self._select_keys = (
            ("sparse_tokens_attended", "sparse_tokens_in_context")
            if any(getattr(b, "selects", False)
                   for b in self._layer_blocks or ()) else ())
        self._count_keys = (
            ("expert_assignments", "expert_assignments_held")
            # assignments on a choice that no chip holds (a skip)
            + (("expert_assignments_skipped",)
               if getattr(model, "counts_skips", False) else ())
            # the held experts some row in the plan chose, and the held
            # experts, a layer a tick: what a tick needs of their weights
            + ("experts_chosen", "experts_held_ticks")
            + tuple(f"expert_load_{e}" for e in range(held))
            if held else ()) + self._select_keys
        # which engine writes the pool each tick is decided by where the
        # pool lives: the Pallas window write off-mesh on TPU, the XLA
        # scatter under a mesh (a Mosaic call cannot be partitioned) and
        # on CPU. Off-mesh on TPU there is no second choice to fall to.
        # (asked of the leaves that hold a row a token: one with fewer,
        # as pooled index keys, goes through its layer's own row scatter)
        pool0 = self._pool0()
        self._pallas_write = mesh is None and _pallas_ok(
            {name: leaf for name, leaf in pool0.items()
             if leaf.shape[3] == self.bt}, axis=3)
        if (jax.default_backend() == "tpu" and mesh is None
                and not self._pallas_write):
            raise ValueError(
                f"the KV pool (block size {self.bt}) cannot take the "
                f"Pallas window write on this TPU: every decode tick "
                f"would copy the whole pool through an XLA scatter")
        # which engine READS the pool each decode tick: what the tick's
        # trace will be told, by the same function under the same mesh
        # context (ops/attention.py::paged_read_path): the block-table
        # kernel over the pool in place, or the gathered logical view.
        # Asked here and not noted by the trace: engines of one shape
        # family share their jitted programs (_PROGRAM_CACHE), so a
        # trace belongs to whichever engine dispatched first.
        # A layer of kinds answers for its own leaves (read_path)
        with self._mesh_ctx():
            self._paged_read = (
                paged_read_path(pool0, 1) if self._layer_blocks is None else
                self._layer_blocks[self._paged0].read_path(
                    self._caches[self._paged0]))
        if (self._layer_blocks is not None and decode_width_buckets is None
                and (self._paged_read in ("kernel", "selected")
                     or self._blockgen is not None)):
            # the kernel's traffic follows each row's position whatever
            # rung the table was shipped at (PERF.md, PR 25), so for a
            # pool read in place the ladder buys nothing and costs a
            # compiled segment a rung, the top one reached only by a row
            # past half the horizon: mid-traffic. A model of layer kinds
            # starts with the one full-width rung; the families that were
            # served before it keep their ladder (and their programs). So
            # does a model that generates by blocks wherever it runs: its
            # pass is read in place on the chip, and a row's position moves
            # by its phases, not a slot a tick, which a warm-up sized for
            # ticks would not cover rung by rung.
            self._width_ladder = (self.nb,)
            self._cur_width = self.nb
        # HBM bytes ONE gathered block read moves per (row, layer):
        # both K/V planes of every pool leaf (the int8 scale leaf
        # rides along when present) — the unit behind
        # serve.width.bytes_saved_vs_full
        self._gather_block_bytes = sum(
            leaf.nbytes // leaf.shape[1] for leaf in pool0.values())
        row_spec = P(("data", "fsdp"))
        self._cur_tok = zeros((slots,), jnp.int32, row_spec)
        self._n_logical = zeros((slots,), jnp.int32, row_spec)
        if self._blockgen is not None:
            # a row's block on the device, carried from segment to segment:
            # its tokens and WHICH ARE MASKED (state, never a comparison
            # with the mask id: a prompt may hold it). The host's side of a
            # row's block (_row_passes): the slot its block starts at
            # (_row_pos), the positions still masked, the positions the
            # block generates (all but a first block's prompt tail), and
            # the slot at which the request's last block ends
            L = self._blockgen[0]
            self._blk_tok = zeros((slots, L), jnp.int32, None)
            self._blk_masked = zeros((slots, L), jnp.bool_, None)
            self._blk_left = [0] * slots
            self._blk_gen = [0] * slots
            self._stop_pos = np.zeros((slots,), np.int32)
        # host-side paged-cache state: the refcounted block pool, the
        # per-row block tables (shipped with every dispatch; trash = 0),
        # and the radix prefix cache
        self._pool = BlockPool(pool_blocks)
        self._tables = np.full((slots, self.nb), BlockPool.TRASH, np.int32)
        self._radix = (RadixCache(self._pool, self.bt)
                       if prefix_cache else None)
        if self._radix is not None:
            # every entry inserted from here carries the stamp
            self._radix.weights_version = self.weights_version
        # hierarchical KV (kv_tier.py): a host-RAM block pool (and an
        # optional CRC-verified disk tier below it) that eviction
        # demotes into and admission promotes from — the radix working
        # set outlives the device pool
        self._tier = None
        self._tier_promote_t0 = None
        if _tier_on:
            np_dtype = np.dtype(dtype)
            scale_isz = 4 if kv_dtype == "int8" else 0
            hb = (host_cache_blocks if host_cache_blocks is not None
                  else host_blocks_for_mb(host_cache_mb, n_layers, hk,
                                          self.bt, hd, np_dtype.itemsize,
                                          scale_itemsize=scale_isz))
            self._tier = KVTierManager(
                self._radix,
                HostBlockPool(hb, n_layers, hk, self.bt, hd, np_dtype,
                              scale_dtype=(np.float32
                                           if kv_dtype == "int8"
                                           else None)),
                DiskTier(disk_cache_dir, async_writes=True)
                if disk_cache_dir else None)
            # disk spills stamp their sidecars with this; adoption
            # declines shards carrying any other stamp (ISSUE 20)
            self._tier.weights_version = self.weights_version
        # per-row slot of the last written token (host-tracked: admission
        # rewinds a row to its head length - 1; each segment advances
        # every row by S; parked rows sit at 0 writing into trash)
        self._row_pos = [0] * slots
        # per-row sampling settings (host-tracked, set at admission,
        # shipped with every segment dispatch — no fetch)
        self._temp = np.zeros((slots,), np.float32)
        self._topk = np.zeros((slots,), np.int32)       # 0 = off
        self._topp = np.full((slots,), 2.0, np.float32)  # >= 1 = off
        self._seed = np.zeros((slots,), np.uint32)
        # host MIRRORS of _cur_tok/_n_logical: the verify path builds
        # its windows entirely host-side (the accept decision is host
        # logic anyway — one fetch per verify either way), so in spec
        # mode the device copies go stale and these are authoritative;
        # prefill and reconstruction keep both in lockstep, and
        # auto-disable pushes the mirrors back before plain decode
        # resumes
        self._cur_h = np.zeros((slots,), np.int32)
        self._nlog_h = np.zeros((slots,), np.int32)
        # crash-durable serving (serve_journal.py): the write-ahead
        # session log. A shared writer instance (a router fleet logging
        # into one journal) wins over journal_dir; either way the
        # journal's counter dict is rebound to the serve.journal.*
        # MetricDict in _zero_stats so gauges and dict agree.
        if journal is None and journal_dir is not None:
            from distributed_compute_pytorch_tpu.serve_journal import (
                ServeJournal)
            journal = ServeJournal(journal_dir, fsync=journal_fsync)
        self._journal = journal
        # recovery-replay admission metadata, set by _run_recovered for
        # the duration of one inner _run: sub-request index -> (request
        # id, original prompt, tokens already emitted) so the admit
        # frame records the TRUE session, not the continuation shape
        self._replay_admits: dict = {}
        self.ticks = 0             # decode ticks run this session
        # abstract signature of each compiled program's FIRST dispatch
        # (kernel_census re-lowers exactly these, off the serving path)
        self._program_sigs: dict = {}
        self._zero_stats()
        # a restarted disk tier re-enters the radix: shards whose
        # sidecars carry prefix tokens AND match this engine's cache
        # geometry become TIER_DISK entries — the warm-restart half of
        # crash durability (cold prefill only for what disk lost)
        if self._tier is not None and self._tier.disk is not None:
            np_dtype = np.dtype(dtype)
            if kv_dtype == "int8":
                # int8 shards must also match the scale geometry — a
                # bf16 engine refuses int8 shards and vice versa (the
                # 2-tuple form carries no scale expectation)
                self._tier.adopt_disk_index(
                    lambda n: ((n_layers, 2, -(-n // self.bt), hk,
                                self.bt, hd), str(np_dtype),
                               (n_layers, 2, -(-n // self.bt), hk,
                                self.bt, 1), "float32"))
            else:
                self._tier.adopt_disk_index(
                    lambda n: ((n_layers, 2, -(-n // self.bt), hk,
                                self.bt, hd), str(np_dtype)))
        # moe_capacity is STATIC: capacity shapes the routing one-hots, so
        # each distinct (dispatch shape, dispatch-max capacity) pair
        # compiles its own admission program; per-row capacities ride
        # along as a traced [K] vector. Suffix/prefix window widths are
        # static per dispatch too — a wave that attaches nothing takes
        # the admission ladder's shapes, attach waves one program per
        # (block-rounded suffix, prefix bucket rung) pair.
        #
        # Compiled-PROGRAM sharing: jitting bound methods makes every
        # instance pay its own trace+compile even when an identical
        # batcher is already warm — and identical batchers are the
        # common case (a spec-on/off parity pair over one model, a
        # router's N replicas). Everything the traces read from `self`
        # is derived from (model class + frozen config, block tokens,
        # segment length) plus the ambient mesh; ALL remaining
        # variation — slots, t_max, wave widths, verify W, int8 vs
        # bf16 params, sampling — arrives through argument avals and
        # static argnames, which the shared jit keys on itself. A
        # borrowed bound method keeps its donor alive (incl. the
        # donor's pool), so the registry holds weakrefs: a donor with
        # no borrowers frees with its last user.
        try:
            key = (type(self.model), self.model.config, self.bt, self.S,
                   self.kv_dtype,
                   # the width-bucket knob: donors with different
                   # ladders prewarm (and therefore cache) different
                   # per-rung programs, so an on/off parity pair never
                   # shares a donor by accident (each rung's program is
                   # still keyed by the jit itself, on the table aval)
                   self.decode_width_buckets,
                   None if mesh is None else
                   (tuple(mesh.devices.flat), tuple(mesh.axis_names)))
            hash(key)
        except (AttributeError, TypeError):
            # duck-typed model without a hashable frozen config: no
            # sharing, every instance jits its own programs (the
            # pre-cache behavior)
            key = None
        with _PROGRAM_CACHE_LOCK:
            ref = _PROGRAM_CACHE.get(key) if key is not None else None
            donor = ref() if ref is not None else None
            if donor is not None:
                if self._blockgen is not None:
                    self._block_segment_c = donor._block_segment_c
                self._admit_c = donor._admit_c
                self._segment_c = donor._segment_c
                self._copy_c = donor._copy_c
                self._verify_c = donor._verify_c
                self._promote_c = donor._promote_c
            else:
                self._admit_c = jax.jit(self._admit_impl,
                                        donate_argnums=(1,),
                                        static_argnames=("moe_capacity",))
                self._segment_c = jax.jit(self._segment_impl,
                                          donate_argnums=(1,),
                                          static_argnames=("sampling",))
                self._copy_c = jax.jit(self._copy_impl, donate_argnums=(0,))
                self._verify_c = jax.jit(self._verify_impl,
                                         donate_argnums=(1,),
                                         static_argnames=("sampling",))
                self._promote_c = jax.jit(self._promote_impl,
                                          donate_argnums=(0,))
                if self._blockgen is not None:
                    self._block_segment_c = jax.jit(
                        self._block_segment_impl, donate_argnums=(1,))
                if key is not None:
                    _PROGRAM_CACHE[key] = weakref.ref(self)

    def _zero_stats(self):
        # a FRESH per-batcher registry each session: the stats/waste
        # dicts below are live views over it (obs.metrics.MetricDict —
        # plain-dict reads/JSON, every write mirrored to a gauge), and
        # the SLO histograms accumulate beside them until the next
        # reset(). Telemetry-disabled runs keep the views counting —
        # they are functional scheduler state, not diagnostics.
        self.obs = obs_metrics.Registry()
        # transport counters (module docstring; asserted by
        # tests/test_serve.py): fetches == segments, every fetch with
        # live rows behind it issued AFTER the next segment's dispatch
        self.stats = obs_metrics.MetricDict(self.obs, "serve.", {
            "segments": 0, "fetches": 0, "fetches_overlapped": 0,
            # admission, by DISPATCHES (one call of every kernel in the
            # program each) and rows admitted; the real head tokens they
            # prefilled and the window (rows x tokens, pads included)
            # dispatched for them
            "prefill_calls": 0, "prefill_rows": 0,
            "prefill_tokens": 0, "prefill_window_tokens": 0,
            # slot-ticks dispatched under an all-trash table (rows out of
            # the plan: ``waste``'s two parked counts together), for which
            # the paged decode kernels attend nothing
            "decode_rows_parked": 0,
            # slot-ticks of the rows IN a plan of a model with state layers:
            # the rows whose per-slot state each such layer reads and
            # rewrites, summed over ticks (0 for a model without one)
            "state_rows_advanced": 0,
            # deliveries that closed a gap (every harvest handing a request
            # new tokens, but the request's first), split by whether the
            # device ran admission between the two segments that delivered
            # (``_run::mark_segment``), the gaps' sums in seconds, and
            # their distribution: one count a gap in the bucket named by
            # its upper edge in ms. The one store of the distribution:
            # ``stats_snapshot()["slo"]["delivery_gap_s"]`` is read off it
            "deliveries_clear": 0, "deliveries_behind_admission": 0,
            "delivery_gap_s_clear": 0.0,
            "delivery_gap_s_behind_admission": 0.0,
            **dict.fromkeys(_GAP_COUNTERS, 0),
            # fault-tolerance counters (serve_lifecycle /
            # DESIGN.md "Serving under failure")
            "faults": 0, "reconstructions": 0,
            "reconstruction_rows": 0, "recovery_s": 0.0,
            # prefix-cache counters: admissions that attached,
            # tokens attached instead of re-prefilled (the
            # compute the cache saved), copy-on-write block
            # copies, and the pool's peak allocated fraction
            "prefix_hits": 0, "cached_prefix_tokens": 0,
            "prefill_tokens_saved": 0, "cow_copies": 0,
            "block_pool_occupancy": 0.0,
            # routed experts (models/moe.py::HeldExperts), counted on the
            # device by the decode ticks and fetched with their tokens:
            # assignments of the rows in the plan (ticks x top_k), those
            # that fell on the experts this chip holds, and each held
            # expert's load
            **dict.fromkeys(self._count_keys, 0),
            **dict.fromkeys(_BLOCK_COUNTERS if self._blockgen is not None
                            else (), 0)})
        self.last_slot_leaks = 0   # rows still owned at serve() exit
        self.last_block_leaks = 0  # pool refs unaccounted at serve() exit
                                   # (both must be 0 — asserted by tests)
        # row-tick attribution (the waste breakdown): useful
        # tokens = planned_ticks - tail (tail = post-eos + budget
        # rounding); parked ticks split by whether work was waiting
        self.waste = obs_metrics.MetricDict(self.obs, "serve.waste.", {
            "planned_ticks": 0, "parked_admission_lag": 0,
            "parked_drain": 0})
        # speculative-decoding attribution (ISSUE 12): drafts proposed/
        # accepted, the running acceptance rate, verify columns that
        # bought no emitted token (the speculation waste), verify
        # dispatches and tokens they emitted (useful-tokens-per-segment
        # = emitted_tokens / verify_segments), and auto-disable trips
        self.spec = obs_metrics.MetricDict(self.obs, "serve.spec.", {
            "proposed": 0, "accepted": 0, "acceptance_rate": 0.0,
            "wasted_verify_tokens": 0, "verify_segments": 0,
            "emitted_tokens": 0, "autodisabled": 0})
        # hierarchical-KV attribution (ISSUE 13): evictions demoted D2H
        # instead of discarded, demoted prefixes promoted back, hits per
        # spill tier, bytes moved each way, the host-side wall the
        # promotion copy overlapped with admission, and both pools'
        # peak occupancy. The KVTierManager writes these through the
        # same dict, so gauges and dict can never disagree.
        self.tier = obs_metrics.MetricDict(self.obs, "serve.tier.",
                                           dict(TIER_STATS))
        if getattr(self, "_tier", None) is not None:
            self._tier.stats = self.tier
        # chunked/disaggregated prefill attribution (ISSUE 14):
        # admissions deferred mid-prompt, between-segment extension
        # waves and the suffix tokens they prefilled, decode ticks a
        # mid-chunk row sat parked (the latency chunking trades away
        # from the admission stall), and the router handoff seam —
        # prefix entries exported/imported as bytes, declines that
        # fell back to replay, and the bytes moved either way
        self.prefill = obs_metrics.MetricDict(self.obs, "serve.prefill.", {
            "chunked_admissions": 0, "chunk_waves": 0,
            "chunk_tokens": 0, "stall_ticks": 0,
            "handoff_exports": 0, "handoff_imports": 0,
            "handoff_declined": 0, "handoff_bytes": 0})
        # write-ahead-journal attribution (ISSUE 15): frames/bytes
        # appended, fsyncs paid (the durability price), torn tails
        # repaired on open, and the recovery ledger — sessions replayed,
        # completions deduped, tokens re-admitted as replay prompt. The
        # journal WRITER outlives serve sessions (it is process-scoped
        # state, like the log file itself), so its counters CARRY OVER
        # a reset instead of zeroing, then the writer is rebound to the
        # MetricDict so dict and gauges can never disagree.
        _jr = getattr(self, "_journal", None)
        self.journal = obs_metrics.MetricDict(
            self.obs, "serve.journal.",
            {**dict(JOURNAL_STATS),
             **({} if _jr is None else dict(_jr.stats))})
        if _jr is not None:
            _jr.stats = self.journal
        # quantized-KV attribution (ISSUE 16): blocks living int8 in
        # the pool, dispatches that dequantized a gathered read, bytes
        # the int8 layout saved against the bf16 one (HBM computed once
        # from the actual cache geometry; D2H/handoff accumulated per
        # move), and handoffs declined for a dtype mismatch
        self.kvq = obs_metrics.MetricDict(self.obs, "serve.kvq.", {
            "quantized_blocks": 0, "dequant_reads": 0,
            "bytes_saved_hbm": 0, "bytes_saved_d2h": 0,
            "bytes_saved_handoff": 0, "handoff_dtype_declined": 0})
        if getattr(self, "kv_dtype", "bf16") == "int8":
            saved = 0
            for c in self._caches:
                kv = c["kv"]
                # the bf16 pool would spend 2 bytes where int8 spends
                # 1, minus what the f32 scales give back
                saved += kv.size * 2 - kv.size - c["scale"].size * 4
            self.kvq["bytes_saved_hbm"] = saved
        # width-bucket attribution (ISSUE 19): the rung each dispatch
        # ran at (blocks) and how full it was, gathered block reads vs
        # what the fixed full-horizon design would have issued (and the
        # HBM bytes the difference saved), bucket GROWTHS (the only
        # step that can eat a new compile mid-traffic — each one also
        # drops a flight-recorder instant), and rungs compiled up front
        # by prewarm_widths()
        self.width = obs_metrics.MetricDict(self.obs, "serve.width.", {
            "bucket_blocks": 0, "bucket_occupancy": 0.0,
            "gathered_block_reads": 0, "full_width_block_reads": 0,
            "bytes_saved_vs_full": 0, "bucket_growths": 0,
            "prewarmed_programs": 0})
        # elastic-fleet attribution, engine side (ISSUE 20): the
        # running weights' version stamp, hot reloads paid, and
        # cross-version KV declines (handoff imports + disk-shard
        # adoptions refused for a stamp mismatch — each one a replay
        # fallback, never an error). The fleet controller aggregates
        # these per-replica dicts under its own scale/upgrade counters.
        self.fleet = obs_metrics.MetricDict(self.obs, "serve.fleet.", {
            "weights_version": int(getattr(self, "weights_version", 0)),
            "weight_reloads": 0, "version_declined": 0})
        if getattr(self, "_tier", None) is not None:
            self._tier.fleet_stats = self.fleet
        self.last_host_block_leaks = 0  # host blocks unaccounted at exit
        # per-request SLO distributions (serve_lifecycle.RequestResult
        # field docs define the measurement points); seconds, log
        # buckets 1 µs .. 10 ks
        self._slo = {name: self.obs.histogram(f"serve.slo.{name}")
                     for name in ("queue_wait_s", "ttft_s", "tpot_s",
                                  "e2e_s")}
        self._longest_gap_s = 0.0       # between two deliveries of a request

    def _gap_summary(self) -> dict:
        """The digest of the gaps between two deliveries of one request,
        in the form of the SLO histograms' and read off the ``stats``
        counters that hold the distribution: a percentile is the upper
        edge of the bucket it falls in (up to a bucket's 15.5% over),
        held to the longest gap there was."""
        st = self.stats
        counts = [st[name] for name in _GAP_COUNTERS]
        total = sum(counts)
        if not total:
            return {"count": 0}

        def edge(q):
            rank, below = math.ceil(q * total), 0
            for upper, count in zip((*_GAP_EDGES_S, math.inf), counts):
                below += count
                if count and below >= rank:
                    return min(upper, self._longest_gap_s)

        return {"count": total,
                "mean": (st["delivery_gap_s_clear"]
                         + st["delivery_gap_s_behind_admission"]) / total,
                "max": self._longest_gap_s,
                "p50": edge(0.50), "p90": edge(0.90),
                "p95": edge(0.95), "p99": edge(0.99)}

    def stats_snapshot(self) -> dict:
        """One JSON-serialisable view of everything the batcher
        measures: the legacy ``stats``/``waste`` counters (the dicts
        and the snapshot can never disagree — same registry), the SLO
        histogram digests (count/mean/min/max/p50/p90/p95/p99), tick
        totals and the leak counters. This is the record ``dcp-serve``
        heartbeats, ``--metrics_jsonl`` appends, and the benchmark
        (``perfbench/runners/serve.py``) reads as differences over a
        window."""
        return {
            "stats": dict(self.stats),
            "waste": dict(self.waste),
            "spec": dict(self.spec),
            "tier": dict(self.tier),
            "prefill": dict(self.prefill),
            "journal": dict(self.journal),
            "kvq": dict(self.kvq),
            "width": dict(self.width),
            "fleet": dict(self.fleet),
            "engine": self.engine_info(),
            "slo": {**{name: h.summary() for name, h in self._slo.items()},
                    "delivery_gap_s": self._gap_summary()},
            "ticks": self.ticks,
            # static: the pool read the decode tick was compiled with
            "paged_read": self._paged_read,
            # static: per layer, the label of what it keeps ("paged" for a
            # dense family; a layer of kinds says its cache_kind)
            "cache_kinds": list(self._cache_kinds),
            # static: per kind, the bytes a layer keeps of a cached token
            "cache_bytes_per_token": dict(self._cache_bytes_per_token),
            # static: per kind that keeps any, the bytes of a slot's tail
            # and state
            "state_bytes_per_slot": dict(self._state_bytes_per_slot),
            **({"expert_load_max_over_mean": self._expert_load_spread()}
               if self._count_keys else {}),
            "slot_leaks": self.last_slot_leaks,
            "block_leaks": self.last_block_leaks,
            "host_block_leaks": self.last_host_block_leaks,
            # device memory at snapshot time ({} on CPU/no stats): the
            # heartbeat is often the ONLY live signal a long serve run
            # emits, so HBM pressure must ride it, not just the trainer
            # log cadence
            "mem": device_memory_gauges(self.obs, prefix="serve.mem."),
        }

    def _expert_load_spread(self):
        """Largest held expert's load over the mean load (1.0 = even);
        None before any assignment was counted."""
        load = [self.stats[key] for key in self._count_keys
                if key.startswith("expert_load_")]
        return max(load) * len(load) / sum(load) if sum(load) else None

    def engine_info(self) -> dict:
        """Where and in what dtype this engine runs, as IT sees it: a
        bf16 checkpoint must show bf16 weights and pool here, a replica
        its own device, and the pool write the engine that performs it."""
        pool = next(iter(self._pool0().values()))
        return {"param_dtype": str(self._cdtype),
                "pool_dtype": str(pool.dtype),
                "platform": jax.default_backend(),
                "devices": sorted(d.id for d in pool.devices()),
                "pool_write": "pallas" if self._pallas_write else "xla"}

    def prefix_match_len(self, tokens) -> int:
        """Affinity probe for the replica router: how many of
        ``tokens``'s prompt-HEAD tokens this batcher's radix cache
        holds (the length admission would attach). READ-ONLY —
        ``RadixCache.longest_match_len`` touches no LRU stamp and no
        refcount, so probing every replica per routing decision cannot
        evict or promote anything. 0 with the prefix cache off. The
        head excludes the last prompt token (never prefilled, never
        cached — ``kv_pool`` module docstring). Counts ANY tier: a
        HOST/DISK-demoted prefix (kv_tier.py) reports its full length
        — promotion is one H2D copy, far cheaper than the re-prefill a
        cold replica would pay, so the router should treat demoted
        state as warm."""
        if self._radix is None or len(tokens) < 2:
            return 0
        return self._radix.longest_match_len(list(tokens)[:-1])

    def export_prefix(self, tokens) -> dict | None:
        """HANDOFF EXPORT (DESIGN.md "Disaggregated and chunked
        prefill"): the longest cached prefix of ``tokens``'s prompt
        head as portable bytes — ``{"tokens", "n_tokens", "kv"
        [L, 2, nb, hk, bt, hd], "crc", "bt"}`` — for a decode replica
        to :meth:`import_prefix`. Cached K/V is position-portable
        (``kv_tier`` module docstring: absolute logical positions,
        post-projection), so the payload restores bit-exactly into ANY
        pool's free blocks — a handoff of bytes, not a re-prefill.
        READ-ONLY: device entries are peeked D2H, demoted entries read
        without releasing their tier copy. None = nothing to export
        (cache off, no match, or a disk part failing CRC) — the caller
        falls back to token-identical replay.

        int8 pools export their scale arrays beside the blocks
        (``"scale"`` + its own ``"scale_crc"`` stamp) and stamp the
        pool dtype (``"kv_dtype"``) so a mixed-dtype import declines
        to replay instead of landing bytes it cannot read."""
        if self._radix is None or len(tokens) < 2:
            return None
        head = list(tokens)[:-1]
        if self._tier is not None:
            m, entry = self._radix.match_entry(head)
        else:
            m, blocks = self._radix.match(head)
            entry = None
        m = min(m, len(head))
        if m < 1:
            return None
        k = -(-m // self.bt)
        if entry is None:                   # tier-off: device blocks
            content = self._peek_blocks(blocks[:k])
        elif entry.tier == TIER_DEVICE:
            content = self._peek_blocks(entry.blocks[:k])
        elif entry.tier == TIER_HOST:
            content = self._tier.host.read(entry.host_blocks[:k])
        else:                               # TIER_DISK
            got, _corrupt = self._tier.disk.get(entry.disk_key)
            if got is None:
                return None                 # CRC miss: caller replays
            content = {name: leaf[:, :, :k]
                       for name, leaf in self._as_content(got).items()}
        content = self._as_content(content)
        kv = content["kv"]
        total = sum(int(leaf.nbytes) for leaf in content.values())
        self.prefill["handoff_exports"] += 1
        self.prefill["handoff_bytes"] += total
        payload = {"tokens": tuple(head[:m]), "n_tokens": m,
                   "kv": kv, "crc": _crc(kv), "bt": self.bt,
                   "kv_dtype": self.kv_dtype,
                   "weights_version": self.weights_version}
        if "scale" in content:
            payload["scale"] = content["scale"]
            payload["scale_crc"] = _crc(content["scale"])
            # the bf16 payload would be 2 bytes/element of kv alone
            self.kvq["bytes_saved_handoff"] += int(kv.nbytes) * 2 - total
        return payload

    def _peek_blocks(self, blocks) -> dict:
        """D2H peek of pool ``blocks`` across every layer/leaf:
        ``{"kv": [L, 2, n, hk, bt, hd]}`` plus ``"scale"`` for int8
        pools — the tier/handoff content dict."""
        idx = jnp.asarray(blocks, jnp.int32)
        return {name: np.stack([np.asarray(c[name][:, idx])
                                for c in self._caches])
                for name in self._caches[0]}

    @staticmethod
    def _as_content(content) -> dict:
        """Normalise tier/handoff content: a bare array is the legacy
        bf16 ``kv``-only form, a dict carries scales beside it."""
        return (content if isinstance(content, dict)
                else {"kv": content})

    def import_prefix(self, payload) -> bool:
        """HANDOFF IMPORT: land an :meth:`export_prefix` payload in
        THIS batcher's prefix cache so the next admission of the same
        prompt attaches instead of re-prefilling. With a host tier the
        bytes register as a demoted entry (zero device blocks now; the
        existing PR 13 promotion scatters them H2D on first match);
        tier-less they scatter straight into freshly allocated pool
        blocks. False = declined — CRC/shape/layout/dtype mismatch or
        pool pressure — and nothing changed: the caller's
        token-identical replay fallback costs only the compute the
        handoff would have saved.

        The geometry check covers the SCALE arrays too (ISSUE 16): an
        int8 pool requires a well-shaped ``"scale"`` whose
        ``"scale_crc"`` verifies, a bf16 pool refuses any payload
        carrying one, and a ``"kv_dtype"`` stamp mismatch declines
        with its own counter (``serve.kvq.handoff_dtype_declined``) —
        every mismatch declines to replay, never raises. The
        ``"weights_version"`` stamp is checked the same way (ISSUE 20):
        KV computed under other weights declines with
        ``serve.fleet.version_declined``."""
        if self._radix is None or not payload:
            return False
        if payload.get("kv_dtype", "bf16") != self.kv_dtype:
            # prefill and decode tiers must agree on the pool dtype —
            # int8 bytes are unreadable without this pool's dequant
            # convention and vice versa (cli_serve validates the fleet;
            # this guards cross-process handoffs)
            self.kvq["handoff_dtype_declined"] += 1
            self.prefill["handoff_declined"] += 1
            return False
        if int(payload.get("weights_version", 0)) != self.weights_version:
            # KV computed under different weights is not this model's
            # state — mid-rolling-upgrade handoffs between versions
            # decline to replay (ISSUE 20), exactly like a dtype
            # mismatch, and the counter makes the decline visible
            self.fleet["version_declined"] += 1
            self.prefill["handoff_declined"] += 1
            return False
        kv = payload.get("kv")
        scale = payload.get("scale")
        n = int(payload.get("n_tokens", 0))
        toks = tuple(payload.get("tokens", ()))
        cache = self._caches[0]["kv"]
        k = -(-n // self.bt)
        want = (len(self._caches), 2, k, cache.shape[2], self.bt,
                cache.shape[4])
        swant = (len(self._caches), 2, k, cache.shape[2], self.bt, 1)
        if (kv is None or n < 1 or len(toks) != n
                or payload.get("bt") != self.bt
                or tuple(kv.shape) != want
                or payload.get("crc") != _crc(kv)
                or (self.kv_dtype == "int8"
                    and (scale is None or tuple(scale.shape) != swant
                         or payload.get("scale_crc") != _crc(scale)))
                or (self.kv_dtype != "int8" and scale is not None)):
            self.prefill["handoff_declined"] += 1
            return False
        content = {"kv": np.asarray(kv)}
        if scale is not None:
            content["scale"] = np.asarray(scale)
        total = sum(int(leaf.nbytes) for leaf in content.values())
        if self._tier is not None:
            entry = self._radix.insert_demoted(toks)
            if entry is None:      # already cached here: a handoff hit
                self.prefill["handoff_imports"] += 1
                return True
            if self._tier.store(entry, content if scale is not None
                                else content["kv"]):
                self.prefill["handoff_imports"] += 1
                self.prefill["handoff_bytes"] += total
                if scale is not None:
                    self.kvq["bytes_saved_handoff"] += (
                        int(kv.nbytes) * 2 - total)
                return True
            # no host room even after spilling: drop the placeholder
            # (a tier-less entry left in the tree would crash a later
            # fetch) and fall through to the direct-device path
            self._tier._remove(entry)
        try:
            blocks = self._alloc(k)
        except PoolExhausted:
            self.prefill["handoff_declined"] += 1
            return False
        with self._mesh_ctx():
            self._caches = self._promote_c(
                self._caches, jnp.asarray(blocks, jnp.int32),
                {name: jnp.asarray(leaf)
                 for name, leaf in content.items()})
        # the tree owns the refs from here; drop the alloc's. insert
        # returning False (exact duplicate raced in) release the blocks
        # to garbage — harmless, they are free and unreferenced
        self._radix.insert(toks, blocks)
        self._pool.release(blocks)
        self.prefill["handoff_imports"] += 1
        self.prefill["handoff_bytes"] += total
        if scale is not None:
            self.kvq["bytes_saved_handoff"] += int(kv.nbytes) * 2 - total
        return True

    def logit_probe(self, tokens, prefill: int = 0) -> np.ndarray:
        """Teacher-forced per-position logits ``[n, V]`` (f32) for
        ``tokens``, computed through a SCRATCH one-row paged pool in
        THIS engine's KV dtype — token ``i`` embeds at logical count
        ``i`` and writes/attends at slot ``i``, the exact (position,
        count) pairs serving uses, through the same fused
        quantize-on-write / dequantize-on-read block route. Run on a
        bf16 and an int8 engine over the same stream it gives the
        per-position KL — the bounded-error half of the relaxed parity
        contract (``tests/test_kv_quant.py``).
        The live pool is untouched (scratch blocks, scratch table);
        under a mesh the scratch runs replicated.

        ``prefill``: the first ``prefill`` tokens go through the
        ADMISSION program (a one-row wave into the scratch caches, every
        layer's own kind of cache) and only the rest through decode
        ticks: prefill-then-decode as a request lives it. The logits
        returned are then those of positions ``prefill .. n-1``."""
        if self._blockgen is not None:
            raise NotImplementedError(
                "logit_probe walks a stream a token a tick; a model that "
                "generates by block diffusion has no such tick")
        toks = [int(t) for t in tokens]
        n = len(toks)
        if n == 0:
            return np.zeros((0, 0), np.float32)
        self._cut_weights()
        nbp = -(-n // self.bt)
        scratch = [{name: jnp.zeros(l.shape, l.dtype)
                    for name, l in leaves.items()}
                   for leaves in self._declared(1, nbp)]
        table = jnp.arange(nbp, dtype=jnp.int32)[None, :]
        model = self.model
        if prefill:
            W = -(-prefill // self.bt) * self.bt
            at = np.arange(W)
            real = at < prefill
            kw = ({"ring_rows": jnp.zeros((1,), jnp.int32)}
                  if self._slot_state else {})
            with self._mesh_ctx():
                scratch = jax.jit(self._admit_impl)(
                    self.params, scratch, table,
                    jnp.asarray([toks[:prefill] + [0] * (W - prefill)],
                                jnp.int32),
                    jnp.asarray(real[None], jnp.float32),
                    jnp.asarray(at[None], jnp.int32),
                    jnp.zeros((1, 0), jnp.float32),
                    jnp.asarray(np.where(real, at // self.bt, nbp)[None],
                                jnp.int32),
                    jnp.asarray((at % self.bt)[None], jnp.int32), **kw)

        def step(params, caches, tok, pos):
            x = model.embed(params, tok[:, None], pos[:, None])
            new_caches, carry = [], None
            for li in range(self._n_layers):
                x, c2, carry = self._decode_layer(
                    li, params, x, caches[li], table, pos, pin=False,
                    carry=carry)
                new_caches.append(c2)
            return new_caches, model.readout(params, x)[:, -1]

        step_c = jax.jit(step)
        out = []
        with self._mesh_ctx():
            for i in range(prefill, n):
                scratch, logits = step_c(
                    self.params, scratch, jnp.asarray([toks[i]], jnp.int32),
                    jnp.asarray([i], jnp.int32))
                out.append(np.asarray(logits[0], jnp.float32))
        return np.stack(out)

    def profile_next(self, segments: int, profile_dir: str) -> None:
        """Arm ON-DEMAND XLA profiling: the next ``segments``
        dispatched decode segments run under ``jax.profiler`` traces
        written to ``profile_dir`` (``dcp-serve --profile_segments``,
        triggered by SIGUSR1 mid-run). The stop blocks on the last
        profiled segment's tokens so the device work is actually in
        the trace; one bounded sync, only when armed."""
        if segments < 1:
            raise ValueError(f"segments must be >= 1, got {segments}")
        self._profile_req = {"remaining": int(segments),
                             "dir": profile_dir, "active": False}

    # what a model of layer kinds cannot be served with yet, by the kind of
    # cache that stands in the way: (whose layers, {option: reason}). The
    # ring's entry speaks for the held experts too, which every such model
    # has had beside its rings
    _LAYER_KIND_REFUSALS = {
        "ring": ("a model of window layers and held experts", {
            "prefix_cache": "a cached prefix holds pool blocks only: a "
                            "window layer's ring at the prefix's end is "
                            "not kept, so an attached request could not "
                            "be resumed",
            "speculate": "a verify window writes several ring slots at "
                         "once and a rejected draft would have "
                         "overwritten tokens the window still needs",
            "host_cache_mb/host_cache_blocks/disk_cache_dir":
                "KV tiers demote and promote pool blocks; rings have none",
            "kv_dtype='int8'": "the ring has no scale leaf",
            "mesh": "the ring write and the held experts' grouped "
                    "products are single-device programs, and the experts "
                    "held are one chip's share already",
            "prefill_chunk_tokens": "a chunk would have to attend the "
                                    "ring of the chunk before it",
        }),
        "latent": ("latent-attention layers", {
            "prefix_cache": "an attached prefix is gathered as K/V pairs "
                            "at head width, and a latent pool holds "
                            "neither: the expanded form would have to "
                            "re-expand the prefix's compressed vectors",
            "speculate": "a latent layer has no verify window: its decode "
                         "form attends one absorbed query a row",
            "host_cache_mb/host_cache_blocks/disk_cache_dir":
                "the host and disk tiers are laid out for K/V pairs at "
                "head width",
            "kv_dtype='int8'": "the latent pool has no scale leaf, and a "
                               "token's compressed and rotary channels "
                               "would need scales of their own",
            "mesh": "the latent decode kernel, the pool write and the "
                    "held experts' grouped products are single-device "
                    "programs",
            "prefill_chunk_tokens": "a chunk would have to attend the "
                                    "expanded K/V of the chunks before it",
        }),
        "paged+tail": ("layers that keep a per-slot tail beside the pool", {
            "prefix_cache": "a cached prefix holds pool blocks only: the "
                            "tail at the prefix's end is not kept, so the "
                            "suffix's first token would lack the tokens "
                            "before it",
            "speculate": "a rejected draft would have advanced the tail "
                         "past the accepted tokens",
            "host_cache_mb/host_cache_blocks/disk_cache_dir":
                "KV tiers demote and promote pool blocks; a tail is none",
            "kv_dtype='int8'": "the tail has no scale leaf, and the int8 "
                               "pool is read through the gather",
            "mesh": "the tail is indexed by slot where the pool is sharded "
                    "by block, and the held experts' grouped products are "
                    "single-device programs",
            "prefill_chunk_tokens": "a chunk would need the tail of the "
                                    "chunk before it",
        }),
        "state": ("linear-attention layers, whose state is a function of "
                  "the whole prefix", {
            "prefix_cache": "a cached prefix holds pool blocks only: the "
                            "state at the prefix's end is not kept (no "
                            "snapshot at block boundaries), so the suffix "
                            "would start from nothing",
            "speculate": "a rejected draft would have advanced the state "
                         "past the accepted tokens, and a state cannot be "
                         "rolled back",
            "host_cache_mb/host_cache_blocks/disk_cache_dir":
                "KV tiers demote and promote pool blocks; a state is none",
            "kv_dtype='int8'": "the state is float32 and has no scale leaf",
            "mesh": "the state is indexed by slot where the pool is "
                    "sharded by block, and the held experts' grouped "
                    "products are single-device programs",
            "prefill_chunk_tokens": "a chunk would have to take the state "
                                    "and the tail of the chunk before it",
        }),
        "latent+index": ("sparse latent layers behind an indexer", {
            "prefix_cache": "an attached prefix is gathered as K/V pairs "
                            "at head width; a latent pool holds neither, "
                            "and the index tail at the prefix's end is "
                            "not kept",
            "speculate": "a sparse latent layer has no verify window: its "
                         "decode form selects for one query a row, and a "
                         "rejected draft would have advanced the index "
                         "tail",
            "host_cache_mb/host_cache_blocks/disk_cache_dir":
                "the host and disk tiers are laid out for K/V pairs at "
                "head width, and the pooled index keys have no tier",
            "kv_dtype='int8'": "neither the latent pool nor the pooled "
                               "index keys have a scale leaf",
            "mesh": "the selected read, the pool writes and the held "
                    "experts' grouped products are single-device programs",
            "prefill_chunk_tokens": "a chunk would have to score and "
                                    "attend the latent vectors and pooled "
                                    "keys of the chunks before it",
        }),
    }

    @classmethod
    def _refuse_for_layer_kinds(cls, kinds, *, prefix_cache, speculate,
                                tiers, kv_dtype, mesh, prefill_chunk_tokens):
        """What a model of layer kinds cannot be served with yet, refused
        with the reason of EVERY kind of cache among ``kinds`` (its
        layers' ``cache_kind``) that stands in the way; a model of pool
        layers alone is refused as one of rings is, for its held
        experts."""
        asked = {
            "prefix_cache": prefix_cache,
            "speculate": speculate is not None,
            "host_cache_mb/host_cache_blocks/disk_cache_dir": tiers,
            "kv_dtype='int8'": kv_dtype == "int8",
            "mesh": mesh is not None,
            "prefill_chunk_tokens": prefill_chunk_tokens is not None,
        }
        present = ([k for k in cls._LAYER_KIND_REFUSALS if k in kinds]
                   or ["ring"])
        for name, on in asked.items():
            if on:
                raise ValueError("; ".join(
                    f"{name} does not compose with {what} yet: {why[name]}"
                    for what, why in map(cls._LAYER_KIND_REFUSALS.get,
                                         present)))

    @staticmethod
    def _refuse_for_block_generation(remasking, *, prefix_cache, speculate,
                                     kv_dtype, mesh, prefill_chunk_tokens):
        """What a model that generates by block diffusion is not served
        with, each with its reason (sampled rows are refused a request at a
        time: :meth:`_validate_one`)."""
        if remasking == "low_confidence_dynamic":
            raise ValueError(
                "remasking 'low_confidence_dynamic' is not served: a "
                "confidence threshold makes a pass's yield depend on the "
                "data, so the host could not charge a row's budget at "
                "dispatch nor dispatch a segment before it fetched the one "
                "before")
        for name, on, why in (
            ("prefix_cache", prefix_cache,
             "a cached prefix would have to end on a block boundary and "
             "be attended under the block mask; the radix cache keys on "
             "prompt heads and the attached-prefix path is causal"),
            ("speculate", speculate is not None,
             "a verify window scores causal drafts a position at a time; a "
             "block pass already yields up to block_length tokens"),
            ("kv_dtype='int8'", kv_dtype == "int8",
             "the block pass's span write and one-range read have no "
             "quantized form"),
            ("mesh", mesh is not None,
             "the span write and the block-table read are single-device "
             "kernels"),
            ("prefill_chunk_tokens", prefill_chunk_tokens is not None,
             "a chunk would have to attend the blocks before it through "
             "the pool under the block mask, and the chunk path is causal"),
        ):
            if on:
                raise ValueError(f"{name} does not compose with generation "
                                 f"by block diffusion yet: {why}")

    def _hold(self, params):
        """Take ``params`` as the engine's weights, in the form the caller
        has them. A model of layer kinds already keeps one tree a layer
        (``params["layers"]``). Every other family hands over
        ``params["blocks"]`` STACKED, each leaf with a leading layer axis,
        and stays so until :meth:`_cut_weights`; the top-level dict is
        the engine's own from here (the cut swaps ``"blocks"`` in it,
        never in the caller's)."""
        if self._layer_blocks is not None:
            self.params = params
            self._n_layers = len(self._layer_blocks)
            return
        self.params = dict(params)
        self._n_layers = int(
            jax.tree.leaves(params["blocks"])[0].shape[0])

    def _cut_weights(self):
        """Put the weights into the form the compiled programs take: for
        a stacked family ``params["blocks"]`` becomes a tuple of
        per-layer trees, cut ONCE here. A tick that cut its layers out
        of a stack copied every q, k and v weight once more, every tick
        (PERF.md, PR 39). Every entry point that dispatches a program
        calls this first (:meth:`_run`, :meth:`prewarm_widths`,
        :meth:`logit_probe`) and so does :meth:`reload_weights`; after
        the first it returns at once.

        The stack is cut a leaf at a time and the engine lets go of each
        stacked leaf once its slices exist, so beside the weights there
        is never more than the largest leaf again (never a second copy
        of the tree), provided the caller holds no reference of its own
        by now. That is why the cut waits for the first dispatch and
        does not run in the constructor: a caller at full memory drops
        its tree once the constructor has returned. The caller's arrays
        are neither deleted nor donated: one that keeps them keeps both
        forms."""
        if self._layer_blocks is not None or isinstance(
                self.params["blocks"], tuple):
            return
        leaves, treedef = jax.tree.flatten(self.params.pop("blocks"))
        for j in range(len(leaves)):
            at = leaves[j].sharding
            # the slices exist before the stacked leaf goes (output
            # buffers are allotted at dispatch: unawaited, every leaf's
            # slices would stand beside every stacked leaf)
            leaves[j] = jax.block_until_ready(_unstack(
                leaves[j], NamedSharding(at.mesh, P(*at.spec[1:]))
                if isinstance(at, NamedSharding) else None))
        self.params["blocks"] = tuple(
            treedef.unflatten([cut[i] for cut in leaves])
            for i in range(self._n_layers))

    def _layer(self, params, i: int):
        """(block, parameters) of layer ``i``."""
        if self._layer_blocks is None:
            return self._block, params["blocks"][i]
        return self._layer_blocks[i], self.model.layer_params(params, i)

    def _declared(self, slots: int, pool_blocks: int) -> list:
        """Per layer, the leaves of its cache for ``slots`` rows over
        ``pool_blocks`` blocks, ``{name: CacheLeaf}``: what a layer of
        kinds declares, the paged K/V pool for every layer of a dense
        family."""
        from distributed_compute_pytorch_tpu.ops.attention import (
            kv_pool_leaves)
        if self._layer_blocks is not None:
            return [b.cache_leaves(slots, pool_blocks, self.bt, self._cdtype,
                                   self.kv_dtype)
                    for b in self._layer_blocks]
        hk, hd = self.model.kv_cache_spec()
        return [kv_pool_leaves(pool_blocks, hk, self.bt, hd, self._cdtype,
                               self.kv_dtype)] * self._n_layers

    def _pool0(self) -> dict:
        """The leaves keyed by block of the layer the block accounting
        and the engine report look at."""
        return {name: leaf
                for name, leaf in self._caches[self._paged0].items()
                if self._leaves[self._paged0][name].by_block}

    def _decode_layer(self, i: int, params, x, cache, tables, pos,
                      live=None, counts=None, pin: bool = True, carry=None,
                      selected=None):
        """One layer's decode tick against its own leaves (the table
        beside them where any is keyed by block); returns ``(x, new_cache,
        carry)``, the leaves keyed by block pinned to the pool's layout
        unless ``pin`` is off (a scratch pool has none). ``carry``
        is what a block that carries took from the block below and hands
        the next (None for every other block); ``selected`` the sink of a
        block that attends a selection of its cache."""
        block, p_l = self._layer(params, i)
        kw = ({} if self._layer_blocks is None
              else {"live": live, "counts_sink": counts})
        carries = getattr(block, "carries", False)
        if carries:
            kw["carry"] = carry
        if getattr(block, "selects", False):
            kw["select_sink"] = selected
        out = block.decode_step(
            p_l, x, {**cache, "table": tables} if self._on_table[i]
            else cache, pos, **kw)
        x, c2, carry = out if carries else (*out, None)
        c2 = {name: constrain(c2[name], _POOL_SPEC)
              if pin and l.by_block else c2[name]
              for name, l in self._leaves[i].items()}
        return x, c2, carry

    def _mesh_ctx(self):
        return (use_mesh(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def _note_program(self, name: str, fn, args: tuple, kw: dict) -> None:
        """Remember ``name``'s first dispatch as abstract values (shape,
        dtype, and the sharding of committed operands)."""
        if name not in self._program_sigs:
            self._program_sigs[name] = (fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if a.committed else None),
                args), kw)

    def kernel_census(self) -> dict:
        """``{program: count_hlo_kernels record}`` for the admission
        prefill and decode segment this engine actually dispatched: the
        Mosaic calls in each COMPILED program, by kernel name — the
        proof that flash attention and the pool window write reached
        the device instead of an XLA fallback. Re-lowers the recorded
        first-dispatch signatures (persistent-cache hits) — call it
        after serving, not between segments (``dcp-serve
        --metrics_jsonl`` does)."""
        from distributed_compute_pytorch_tpu.parallel.collectives import (
            compiled_hlo_text, count_hlo_kernels)
        with self._mesh_ctx():
            return {name: count_hlo_kernels(
                compiled_hlo_text(fn, *args, **kw))
                for name, (fn, args, kw) in self._program_sigs.items()}

    def reset(self):
        """Fresh session on the SAME compiled programs: zero the pool,
        free every block, drop the radix cache and rewind every row.
        Lets a caller (the tests; a long-running server) run many
        sessions while paying trace+compile once."""
        if self._radix is not None:
            self._radix.clear()
        if self._tier is not None:
            self._tier.reset()
        self._pool.reset()
        self._tables[:] = BlockPool.TRASH
        self._caches = jax.tree.map(jnp.zeros_like, self._caches)
        self._cur_tok = jnp.zeros_like(self._cur_tok)
        self._n_logical = jnp.zeros_like(self._n_logical)
        self._row_pos = [0] * self.B
        self._zero_blocks()
        self._temp[:] = 0.0
        self._topk[:] = 0
        self._topp[:] = 2.0
        self._seed[:] = 0
        self._cur_h[:] = 0
        self._nlog_h[:] = 0
        self._spec_win = [0, 0]
        self._spec_on = self._spec is not None   # un-stick auto-disable
        self._cur_width = self._width_ladder[0]
        self._widths_dispatched.clear()
        self.ticks = 0
        self._zero_stats()

    def _zero_blocks(self) -> None:
        """Forget every row's block (a model that generates by blocks)."""
        if self._blockgen is None:
            return
        self._blk_tok = jnp.zeros_like(self._blk_tok)
        self._blk_masked = jnp.zeros_like(self._blk_masked)
        self._blk_left = [0] * self.B
        self._blk_gen = [0] * self.B
        self._stop_pos[:] = 0

    def reload_weights(self, params, weights_version: int | None = None):
        """HOT WEIGHT SWAP (ISSUE 20): install ``params`` as this
        engine's serving weights and stamp every byte cached from here
        on with ``weights_version`` (defaults to the current version
        + 1). The caller must be between serve calls — the fleet
        controller's upgrade walk drains a replica's live sessions to
        survivors first (they replay token-identically there), reloads,
        then re-admits it to dispatch.

        Everything KV-derived is dropped — radix cache (all tiers,
        including this replica's own disk shards: a same-process
        ``fetch`` has no version gate, so stale shards must not
        survive the swap), block pool, row state — because KV computed
        under the old weights is not the new model's state. The
        COMPILED programs survive: params enter every dispatch as
        traced arguments (the `_PROGRAM_CACHE` key is config-derived),
        so a reloaded replica re-enters traffic with zero recompiles —
        the whole point of upgrading in place instead of respawning."""
        if weights_version is None:
            weights_version = self.weights_version + 1
        old = self.weights_version
        # off-mesh the new weights follow the engine to ITS device (a
        # no-op when the caller already placed them there; a copy of the
        # engine's own when they come from the host or another device)
        self._hold(params if self._device is None
                   else jax.device_put(params, self._device))
        self._cut_weights()
        self.weights_version = int(weights_version)
        self.reset()
        if self._radix is not None:
            self._radix.weights_version = self.weights_version
        if self._tier is not None:
            self._tier.weights_version = self.weights_version
        self.fleet["weight_reloads"] += 1
        self.fleet["weights_version"] = self.weights_version
        instant("weights_reloaded", old_version=old,
                new_version=self.weights_version)
        flight.record("weights_reloaded", old_version=old,
                      new_version=self.weights_version)

    # ---- compiled pieces -------------------------------------------------

    def _admit_impl(self, params, caches, tables, prompt, pmask, positions,
                    prefix_mask, blk_idx, off_idx,
                    moe_capacity=None, moe_capacity_rows=None,
                    ring_rows=None):
        """Prefill ONE DISPATCH of an admission wave into the block pool:
        ``K`` requests' UNSHARED suffix tokens (``prompt``/``pmask``
        ``[K, ws]``, laid out from column 0 — an n-token suffix occupies
        columns ``0..n-1``), each row's token ``t`` at LOGICAL position
        ``positions[j, t] = m_j + t`` (``m_j`` = the row's cached-prefix
        length, 0 with the prefix cache off) — one compiled forward for
        the ``[K, ws]`` batch. ``_prefill_wave`` chooses the shape: a
        rung of the admission ladder with the rows of the wave that it
        covers, or the one window of an attach or chunk wave.

        When the wave carries attachments (static ``Lp =
        prefix_mask.shape[1] > 0``), each layer gathers the rows' cached
        prefix K/V from its pool through ``tables`` and the blocks
        attend the suffix against it (``kv_prefix`` — the bottom-right-
        aligned causal mask gives "all prefix + window up to self" for
        free); ``prefix_mask`` hides table entries past each row's own
        ``m_j``. The computed suffix K/V scatter to their physical
        (block, offset) targets ``blk_idx``/``off_idx`` (out-of-range
        ids = pad slots, ``mode="drop"``) — pads both for rows shorter
        than the window and for the rows padding ``K`` up to a power of
        two and a batch-axes multiple (an UNEVENLY batch-sharded prefill
        was observed to miscompile under mixed-axes meshes on this
        backend). With no attachment in the dispatch (``Lp == 0``,
        static: every window starts at position 0) and a float pool the
        write goes in whole blocks instead (``_admit_blocks``): the
        per-token scatter indexes two axes of the pool and XLA transposes
        the whole pool for it, twice a layer, whatever the dispatch holds
        (33 ms of a Mistral-7B dispatch on a v5e: PERF.md, PR 29).

        A layer of kinds hands over its leaves by name, each in the form
        it is written (``models/hybrid.py::HybridBlock.apply``), and the
        write goes by the leaf's placement: one keyed by block in whole
        blocks (such a model is never attached, never chunked: refused at
        construction), one keyed by slot whole into the slots ``ring_rows
        [K]`` the wave's rows fill (a slot id out of range = a pad row,
        dropped; passed whenever a layer keeps such a leaf).

        Each request's LAST prompt token is deliberately NOT prefilled:
        the host sets it as the row's current token and the next
        segment's first tick consumes it — writing its K/V at the
        row's head length and sampling the first new token exactly as a
        standalone ``generate`` would. Admission stays a pure dispatch
        (no device->host read).
        """
        from distributed_compute_pytorch_tpu.ops.attention import (
            gather_kv_blocks)
        with scope("admit"):
            model = self.model
            Lp = prefix_mask.shape[1]
            x = constrain(model.embed(params, prompt, positions),
                          P(("data", "fsdp"), None, None))
            new_caches, carry = [], None
            for i in range(self._n_layers):
                block, p_i = self._layer(params, i)
                sink: list = []
                kw = {"kv_sink": sink, "kv_mask": pmask}
                carries = getattr(block, "carries", False)
                if carries:
                    kw["carry"] = carry
                if Lp:
                    # attached-prefix K/V: gathered from the pool and
                    # resharded into the row-sharded compute layout (the
                    # portable-redistribution move). int8 pools dequantize
                    # here — the kv_prefix seam concatenates with the
                    # suffix's float K/V (models/transformer.py::
                    # _concat_kv_prefix), so the scales must be applied
                    # before the prefix leaves the pool's dtype domain
                    with scope("kv_gather"):
                        pk = gather_kv_blocks(caches[i]["kv"],
                                              tables[:, :Lp // self.bt])
                        if "scale" in caches[i]:
                            ps = gather_kv_blocks(
                                caches[i]["scale"],
                                tables[:, :Lp // self.bt])
                            pk = (pk.astype(jnp.float32) * ps).astype(
                                self._cdtype)
                    pk = constrain(pk, _CACHE_SPEC)
                    kw["kv_prefix"] = (pk[0], pk[1], prefix_mask)
                if self._block_takes_positions:
                    kw["positions"] = positions
                if self._block_takes_moe_capacity and moe_capacity is not None:
                    # expert queues sized for each row's REAL token count:
                    # pads route nowhere (kv_mask) and every row is its own
                    # routing group (models/moe.py)
                    kw["moe_capacity"] = moe_capacity
                    if (self._block_takes_moe_capacity_rows
                            and moe_capacity_rows is not None):
                        kw["moe_capacity_rows"] = moe_capacity_rows
                x = block.apply(p_i, x, **kw)
                if carries:
                    x, carry = x
                elif isinstance(x, tuple):   # MoE blocks return (x, aux)
                    x = x[0]
                # a dense family's pair (k, v) [K, hk, ws, hd], or a layer
                # of kinds' {name: content}
                kept, = sink
                with scope("kv_write"):
                    if self._layer_blocks is not None:
                        new = {}
                        for name, content in kept.items():
                            leaf, l = caches[i][name], self._leaves[i][name]
                            # by block: whole blocks; by slot: whole into
                            # the rows' slots (a pad row's is dropped)
                            new[name] = self._admit_blocks(
                                leaf, content, tables, pmask
                            ) if l.by_block else leaf.at[
                                (slice(None),) * l.slot_axis + (ring_rows,)
                            ].set(content.astype(leaf.dtype), mode="drop")
                        new_caches.append(new)
                    elif Lp == 0 and "scale" not in caches[i]:
                        # every window starts at position 0 (static)
                        new_caches.append({"kv": self._admit_blocks(
                            caches[i]["kv"], jnp.stack(kept), tables,
                            pmask)})
                    else:
                        new_caches.append(self._admit_scatter(
                            caches[i], *kept, blk_idx, off_idx))
            return new_caches

    @staticmethod
    def _admit_scatter(cache, k, v, blk_idx, off_idx):
        """One layer's admission write: each suffix token's K/V
        (``[K, hk, ws, hd]``) scattered to its physical (block, offset).
        int8 pools quantize per (row, head, position) HERE — fused into
        the scatter, the same per-row symmetric form the decode tick's
        write uses (ops/attention.py) — and scatter the f32 scales
        through the identical index targets."""
        def scatter(leaf, upd):
            # advanced indices at pool axes (1, 3) land broadcast-first,
            # so the update region is [K, ws, 2, hk, x]
            return constrain(leaf.at[:, blk_idx, :, off_idx, :].set(
                upd.astype(leaf.dtype).transpose(1, 3, 0, 2, 4),
                mode="drop"), _POOL_SPEC)
        if "scale" in cache:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            return {"kv": scatter(cache["kv"], jnp.stack([kq, vq])),
                    "scale": scatter(cache["scale"], jnp.stack([ks, vs]))}
        return {"kv": scatter(cache["kv"], jnp.stack([k, v]))}

    def _admit_blocks(self, pool, kv, tables, pmask):
        """The admission write of one leaf keyed by BLOCK, in WHOLE BLOCKS
        (``kv [s, K, hk, ws, hd]``: the K/V planes, or one plane of token
        vectors with one "head", or the planes of a leaf a block of which
        holds fewer rows than tokens, as pooled index keys; every window
        of the dispatch starts at position 0: nothing attached, no chunk
        extension): block ``j`` of wave row ``r`` goes to pool block
        ``tables[r, j]`` if it holds a real token, else nowhere. One index
        on the pool's block axis, so the pool is updated in place; the
        per-token scatter of :meth:`_admit_scatter` indexes two axes and
        XLA transposes the whole pool for it, twice (2.7 GB of scratch
        for a pool of one layer). The tail of a row's last block takes
        the pad tokens' K/V: past the row's live position, never attended,
        and overwritten by the ticks that reach it."""
        s, K, hk, ws, hd = kv.shape
        bt = pool.shape[3]                       # rows to a pool block
        nbw = ws // bt
        kv = kv.reshape(s, K, hk, nbw, bt, hd).transpose(
            0, 1, 3, 2, 4, 5).reshape(s, K * nbw, hk, bt, hd)
        n_tok = jnp.sum(pmask > 0.5, axis=1)
        real = (jnp.arange(nbw) * self.bt)[None, :] < n_tok[:, None]
        ids = jnp.where(real, tables[:, :nbw], pool.shape[1]).reshape(-1)
        return constrain(
            pool.at[:, ids].set(kv.astype(pool.dtype), mode="drop"),
            _POOL_SPEC)

    def _copy_impl(self, caches, src, dst):
        """Copy-on-write block copies: pool blocks ``src [M]`` duplicated
        into ``dst [M]`` across every layer, one compiled dispatch per
        wave. The copy's tail past the attacher's matched length is the
        donor's (divergent) K/V — never attended (the per-row position
        mask stops at the live position) and overwritten as the attacher
        writes its own suffix."""
        # a leaf keyed by slot belongs to no block (and nothing that copies
        # a block is served with one)
        return [{name: constrain(leaf.at[:, dst].set(leaf[:, src]),
                                 _POOL_SPEC)
                 if leaves[name].by_block else leaf
                 for name, leaf in c.items()}
                for c, leaves in zip(caches, self._leaves)]

    def _promote_impl(self, caches, dst, payload):
        """Hierarchical-KV promotion: host-tier K/V ``payload`` — a
        dict of per-leaf stacks (``{"kv": [L, 2, M, hk, bt, hd]}``,
        plus ``"scale": [L, 2, M, hk, bt, 1]`` for int8 pools) —
        restored into pool blocks ``dst [M]`` across every layer, one
        compiled dispatch per promoted entry. Quantized bytes promote
        AS-IS (no requantization round trip: demote→promote is
        bit-exact on the int8 payload). Under a mesh the payload
        arrives replicated (it was host bytes) and the constrain lands
        it straight in the block-axis-sharded pool layout — the same
        portable-redistribution move admission-prefill K/V rides
        (``_admit_impl``), so each device keeps only its own block
        shards."""
        out = []
        for i, c in enumerate(caches):
            out.append({name: constrain(
                leaf.at[:, dst].set(payload[name][i].astype(leaf.dtype)),
                _POOL_SPEC) for name, leaf in c.items()})
        return out

    def _segment_impl(self, params, caches, tables, tok, n_logical,
                      positions0, temp, top_k, top_p, seeds,
                      sampling: bool = False):
        """``S`` decode ticks for every row at its OWN logical position
        (``positions0 [B]`` = each row's last written slot); returns the
        [B, S] next tokens and the carried state. Each tick's cache op
        is the PAGED format of ``ops/attention.py::
        cache_write_and_attend``: the write resolves through ``tables``
        to one (block, offset) per row, attention reads the row's live
        blocks through the same table (in place where
        ``stats_snapshot()["paged_read"]`` says ``kernel``, else a
        gathered logical view). Rows not in the dispatch plan arrive with
        their table swapped for the all-trash row, so their unavoidable
        writes (the compiled segment ticks all rows) land in the
        reserved trash block. ``sampling`` (static) compiles the per-row
        sampling path in; per-tick keys are PRE-SPLIT outside the scan,
        keyed on (row seed, tokens-so-far) so sampled streams are
        scheduling- and attachment-invariant."""
        model = self.model
        # rows out of the plan arrive with an all-trash table; a live
        # row's first block never is the trash block. Held experts count
        # the live rows only, on the device, in the scan's carry: the
        # sums come back beside the tokens (a fifth result)
        counted = bool(self._count_keys)
        live = ((tables[:, 0] != BlockPool.TRASH).astype(jnp.float32)
                if counted else None)
        if sampling:
            base = jax.vmap(jax.random.key)(seeds)
            keys = jax.vmap(lambda k, n0: jax.vmap(
                lambda i: jax.random.fold_in(k, n0 + i))(
                    jnp.arange(self.S)))(base, n_logical)     # [B, S]
            tick_keys = jnp.swapaxes(keys, 0, 1)              # scan xs
        else:
            tick_keys = jnp.zeros((self.S,), jnp.uint32)      # unused xs

        def tick(carry, xs):
            with scope("decode"):
                i, key = xs
                tok, caches, n_log, *xc = carry
                p = positions0 + 1 + i         # [B] per-row slot being written
                x = constrain(
                    model.embed(params, tok[:, None], n_log[:, None]),
                    P(("data", "fsdp"), None, None))
                new_caches, lcarry = [], None
                counts: list | None = [] if counted else None
                selected: list = []
                for li in range(self._n_layers):
                    x, c2, lcarry = self._decode_layer(
                        li, params, x, caches[li], tables, p, live, counts,
                        carry=lcarry, selected=selected)
                    new_caches.append(c2)
                logits = model.readout(params, x)[:, -1]
                with scope("sample"):
                    if sampling:
                        nxt = sample_rows(logits, temp, top_k, top_p, key)
                    else:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if counted:
                    # the experts' vector, then the selection's two
                    xc = [xc[0] + jnp.concatenate(
                        ([sum(counts)] if counts else [])
                        + ([sum(selected)] if selected else []))]
                return (nxt, new_caches, n_log + 1, *xc), nxt

        xc0 = ([jnp.zeros((len(self._count_keys),), jnp.int32)]
               if counted else [])
        (tok, caches, n_logical, *xc), toks = lax.scan(
            tick, (tok, caches, n_logical, *xc0),
            (jnp.arange(self.S), tick_keys))
        return (caches, tok, n_logical, toks.transpose(1, 0), *xc)

    def _block_segment_impl(self, params, caches, tables, blk_tok,
                            blk_masked, pos0, stop):
        """``S`` passes of a model that generates by BLOCK DIFFUSION. A
        row carries a block of ``L = block_length`` positions: its tokens
        ``blk_tok [B, L]``, which of them are masked ``blk_masked [B, L]``
        (state), and the slot it starts at (``pos0 [B]``, a multiple of
        ``L``). One pass, every row: embed the block (a masked position
        embeds the mask token) at its absolute positions; every layer
        projects the block's q/k/v, WRITES the block's K/V at its slots
        through the table (every pass writes; a denoise pass's values are
        overwritten by the next) and attends slots ``0 .. pos + L - 1``,
        one range a row for all its queries (``HybridBlock.block_step``);
        read-out at the block's positions (position ``i``'s logits are
        token ``i``'s), the greedy token a position; then, by the row's
        phase (the step contract: ``_row_passes``):

        - DENOISE (some position is masked): the rule marks ``L /
          denoising_steps`` masked positions (all that are left if fewer),
          which take their token: ``sequential`` the leftmost,
          ``low_confidence_static`` those whose token has the largest
          probability, ties to the left. The position stays.
        - COMMIT (none is masked): the K/V this pass wrote are the block's
          final ones; the block's tokens are what the pass hands back, the
          position moves by ``L`` and the next block opens all masked.

        A row past ``stop [B]`` (the slot at which its request's last block
        ends; a request can end inside a segment) is out of the rest of the
        segment like a row out of the plan: its table is swapped for the
        all-trash row pass by pass, so it writes into trash, the kernel
        passes it by and the experts do not count it.

        Returns the carried state, ``toks [B, S, L]`` (pass ``s``'s block as
        it went in: final where pass ``s`` committed, which the host knows)
        and the blocks each row committed ``[B]`` (held against the host's
        plan at the harvest), then the experts' counts as a tick's."""
        L, steps, rule, mask_id = self._blockgen
        per_pass = L // steps
        model = self.model
        counted = bool(self._count_keys)
        trash = jnp.int32(BlockPool.TRASH)
        at = jnp.arange(L)

        def one_pass(carry, _):
            with scope("block_pass"):
                tok, masked, pos, caches, *xc = carry
                active = pos < stop
                tables_p = jnp.where(active[:, None], tables, trash)
                live = (tables_p[:, 0] != trash).astype(jnp.float32)
                x = model.embed(params, jnp.where(masked, mask_id, tok))
                counts: list | None = [] if counted else None
                new_caches = []
                for li in range(self._n_layers):
                    block, p_l = self._layer(params, li)
                    x, c2 = block.block_step(
                        p_l, x, {**caches[li], "table": tables_p}, pos,
                        live=live, counts_sink=counts)
                    new_caches.append(
                        {name: c2[name] for name in self._leaves[li]})
                logits = model.readout(params, x)              # [B, L, V]
                with scope("unmask"):
                    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    if rule == "sequential":
                        # a masked position's place among the masked
                        rank = jnp.cumsum(masked.astype(jnp.int32), 1) - 1
                    else:
                        # the greedy token's probability, 1 / sum exp(l -
                        # max); a position goes after those of larger
                        # probability and, at a tie, those to its left
                        lg = logits.astype(jnp.float32)
                        conf = 1.0 / jnp.sum(jnp.exp(
                            lg - jnp.max(lg, -1, keepdims=True)), -1)
                        sc = jnp.where(masked, conf, -1.0)
                        first = (sc[:, None, :] > sc[:, :, None]) | (
                            (sc[:, None, :] == sc[:, :, None])
                            & (at[None, None, :] < at[None, :, None]))
                        rank = jnp.sum(first, axis=2)
                    take = masked & (rank < per_pass)
                    commit = ~jnp.any(masked, axis=1)
                    moved = commit & active
                    out = tok
                    tok = jnp.where(take, pred, tok)
                    masked = jnp.where(commit[:, None], True, masked & ~take)
                    pos = jnp.where(moved, pos + L, pos)
                if counted:
                    xc = [xc[0] + sum(counts)]
                return (tok, masked, pos, new_caches, *xc), (out, moved)

        xc0 = ([jnp.zeros((len(self._count_keys),), jnp.int32)]
               if counted else [])
        (tok, masked, _, caches, *xc), (toks, moved) = lax.scan(
            one_pass, (blk_tok, blk_masked, pos0, caches, *xc0), None,
            length=self.S)
        return (caches, tok, masked, toks.transpose(1, 0, 2),
                jnp.sum(moved, axis=0).astype(jnp.int32), *xc)

    def _verify_impl(self, params, caches, tables, toks, positions0,
                     n_logical, temp, top_k, top_p, seeds,
                     sampling: bool = False):
        """Score a whole draft WINDOW in ONE forward pass: ``toks
        [B, W]`` (column 0 = each row's current token, columns 1..k =
        its drafts) embeds at logical counts ``n_logical[b] + i`` and
        writes/attends at slots ``positions0[b] + 1 + i`` — numerically
        the SAME (position, count) pairs ``W`` sequential
        :meth:`_segment_impl` ticks would use, through the blocks'
        ``verify_step`` (per-query staircase attention,
        ``ops/attention.py::cache_verify_and_attend``).

        Returns ``(caches, true [B, W])`` where ``true[b, i]`` is the
        target model's OWN next token after consuming window columns
        ``0..i`` — argmax, or ``infer.verify_sample_rows`` under the
        exact (seed, tokens-generated) fold-in schedule plain decode
        uses at those counts. The host accepts the longest prefix where
        drafts match ``true`` and emits one more: ``true`` at the first
        mismatch IS the deterministic rejection resample, so emitted
        streams are bit-identical to ``speculate=None`` by induction —
        draft quality can only change HOW MANY tokens emit per pass,
        never which tokens."""
        model = self.model
        W = toks.shape[1]
        pos = positions0[:, None] + 1 + jnp.arange(W)[None, :]   # [B, W]
        npos = n_logical[:, None] + jnp.arange(W)[None, :]       # [B, W]
        x = constrain(model.embed(params, toks, npos),
                      P(("data", "fsdp"), None, None))
        new_caches = []
        for li in range(self._n_layers):
            block, p_l = self._layer(params, li)
            paged = {**caches[li], "table": tables}
            x, c2 = block.verify_step(p_l, x, paged, pos)
            new_caches.append(
                {name: constrain(leaf, _POOL_SPEC)
                 for name, leaf in c2.items() if name != "table"})
        logits = model.readout(params, x)                        # [B, W, V]
        if sampling:
            base = jax.vmap(jax.random.key)(seeds)
            keys = jax.vmap(lambda k, n0: jax.vmap(
                lambda i: jax.random.fold_in(k, n0 + i))(
                    jnp.arange(W)))(base, n_logical)             # [B, W]
            true = verify_sample_rows(logits, temp, top_k, top_p, keys)
        else:
            true = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return new_caches, true

    # ---- host block accounting -------------------------------------------

    def _alloc(self, n: int) -> list:
        """Allocate ``n`` fresh blocks, evicting LRU radix entries first
        when the free list runs short (eviction frees refcount-0 blocks
        only, so live rows are never robbed). With the hierarchical-KV
        tier on, eviction DEMOTES instead of discarding: the victim's
        K/V is copied D2H into the host pool and its entry stays in the
        tree, promotable on the next match."""
        if self._pool.free_count < n and self._radix is not None:
            self._radix.evict_for(
                n, on_evict=(self._tier_demote if self._tier is not None
                             else None))
        if self.kv_dtype == "int8":
            self.kvq["quantized_blocks"] += n
        return self._pool.alloc(n)

    def _tier_demote(self, entry, doomed) -> bool:
        """``RadixCache.evict_for``'s ``on_evict`` hook: capture the
        victim's blocks D2H into the host tier. ``doomed`` (the blocks
        this eviction actually frees) is unused beyond being the
        hook's contract — the WHOLE entry is captured, because a
        shared block's device copy survives only as long as its
        sharing row does, while the demoted entry must outlive both.
        Truthy return = entry demoted in place of discarded. Int8
        pools demote the quantized bytes plus scales — roughly half
        the bf16 D2H traffic, counted in ``serve.kvq``."""
        content = self._peek_blocks(entry.blocks)
        if "scale" in content:
            self.kvq["bytes_saved_d2h"] += (
                int(content["kv"].nbytes) - int(content["scale"].nbytes))
            return self._tier.store(entry, content)
        # legacy bf16 form: bare kv stack, tier stores it unchanged
        return self._tier.store(entry, content["kv"])

    def _promote_entry(self, entry) -> bool:
        """Restore a demoted entry's K/V to the device pool: allocate
        fresh blocks (which may itself demote colder entries), take the
        bytes from the host/disk tier, and DISPATCH the compiled H2D
        scatter — asynchronously, so the copy overlaps the admission
        wave the caller is still assembling host-side (device program
        order makes the bytes land before the wave's prefill or any
        attached read; ``promote_overlap_ms`` measures the overlapped
        window). False = promotion declined (pool pressure: not enough
        free + evictable blocks) or the disk copy failed its CRC —
        either way the caller re-prefills, outputs unchanged."""
        k = -(-entry.n_tokens // self.bt)
        self._tier.pin = entry      # the alloc below may demote/spill
        try:                        # colder entries — never this one
            blocks = self._alloc(k)
        except PoolExhausted:
            return False
        finally:
            self._tier.pin = None
        content = self._tier.fetch(entry)
        if content is None:                  # disk CRC miss: entry gone
            self._pool.release(blocks)
            return False
        content = self._as_content(content)
        t0 = time.monotonic()
        with self._mesh_ctx():
            self._caches = self._promote_c(
                self._caches, jnp.asarray(blocks, jnp.int32),
                {name: jnp.asarray(leaf)
                 for name, leaf in content.items()})
        entry.blocks = blocks                # the tree now owns the refs
        entry.tier = TIER_DEVICE
        self.tier["promotions"] += 1
        if self._tier_promote_t0 is None:
            self._tier_promote_t0 = t0
        return True

    def _assign_blocks(self, b: int, slot: _Slot, known: list,
                       remaining: int):
        """Build row ``b``'s block table for serving ``known`` (prompt,
        or prompt+generated on reconstruction) with ``remaining`` budget:
        attach the radix cache's longest prefix (full blocks shared
        read-only, a partial tail block copy-on-write), allocate fresh
        blocks for the rest of the row's worst-case extent, and point
        the table at them. Returns ``(m, cow_pairs)`` — the attached
        prefix length and the (src, dst) block copies the caller must
        dispatch BEFORE the wave's prefill."""
        head = known[:-1]
        nn = len(head)
        extent = nn + self._rounded_need(remaining)
        nblocks = -(-extent // self.bt)
        m, src = 0, []
        if self._radix is not None:
            if self._tier is not None:
                # tier-aware lookup: a demoted prefix is still a hit —
                # promote it (one async H2D copy) instead of
                # re-prefilling; a declined/failed promotion degrades
                # to a plain miss
                m, entry = self._radix.match_entry(head)
                if m and entry.tier != TIER_DEVICE:
                    if not self._promote_entry(entry):
                        m, entry = 0, None
                src = list(entry.blocks) if m else []
            else:
                m, src = self._radix.match(head)
            m = min(m, nn)
            src = src[:-(-m // self.bt)] if m else []
        f, r = divmod(m, self.bt)
        row_blocks = []
        for blk in src[:f]:
            self._pool.acquire(blk)          # shared, read-only
            row_blocks.append(blk)
        cow = []
        if r:
            dst = self._alloc(1)[0]
            cow.append((src[f], dst))        # partial block: copy-on-write
            row_blocks.append(dst)
        row_blocks += self._alloc(nblocks - len(row_blocks))
        self._tables[b, :] = BlockPool.TRASH
        self._tables[b, :nblocks] = row_blocks
        slot.blocks = row_blocks
        if self._blockgen is not None:
            # the slot at which the request's last block ends
            L = self._blockgen[0]
            self._stop_pos[b] = -(-(len(known) + remaining) // L) * L
        self.stats["block_pool_occupancy"] = max(
            self.stats["block_pool_occupancy"],
            self._pool.allocated / self._pool.num_blocks)
        return m, cow

    def _copy_blocks(self, pairs: list) -> None:
        """Dispatch one compiled copy for a wave's COW pairs."""
        src = jnp.asarray([s for s, _ in pairs], jnp.int32)
        dst = jnp.asarray([d for _, d in pairs], jnp.int32)
        with self._mesh_ctx():
            self._caches = self._copy_c(self._caches, src, dst)

    # ---- host scheduler --------------------------------------------------

    def _rounded_need(self, max_new: int) -> int:
        """Decode slots a request consumes past its head before its
        row is harvested and freed: the SEGMENT-ROUNDED budget (a row
        runs whole segments; eos can only shorten the output, not the
        worst-case tick count). With speculation configured, exactly
        ``max_new``: verify emission is clamped to the remaining budget
        at harvest (never segment-rounded), drafted writes past the
        extent drop at the horizon sentinel or land in trash-table
        entries, and a post-auto-disable plain tail's overshoot ticks
        write past the budget only within the row's own tail block or
        trash — never a shared one (shared full blocks sit at or below
        the prompt head, strictly inside the extent)."""
        if self._spec is not None:
            return max_new
        if self._blockgen is not None:
            # whole blocks from the block the prompt's tail opens: at most
            # block_length slots past head + max_new. A request that ends
            # inside a segment writes nothing after it (its stop position)
            return max_new + self._blockgen[0]
        return -(-max_new // self.S) * self.S

    def _row_passes(self, b: int, remaining: int) -> tuple:
        """THE STEP CONTRACT, where a segment is planned: what the next
        ``S`` passes of row ``b`` yield, known to the host at dispatch. A
        pass has a PHASE, and the phase says how many tokens the pass
        yields and whether the row's position moves.

        A causal model is the case "every pass yields one token and moves
        one slot": ``min(remaining, S)`` tokens, as ``dispatch_segment``
        charges them. A model that generates by block diffusion
        (``_block_segment_impl``):

        - DENOISE: the row's block holds masked positions; the pass
          unmasks ``block_length / denoising_steps`` of them (all that are
          left if fewer: a first block opened by ``r`` prompt tokens has
          ``block_length - r``). Nothing is delivered, the position stays.
        - COMMIT: nothing is masked; the pass yields the block's generated
          tokens (the last block's cut to ``remaining``: a request is
          served exactly ``max_new``), its K/V are final, the position
          moves by ``block_length`` and the next block opens all masked.

        Tokens are delivered when their block commits, so every delivered
        token is in the cache and a reconstruction or a journal replay
        re-admits prompt + delivered as ever. Advances the host's side of
        the row's block and returns ``(deliveries, remaining)``:
        ``deliveries`` one ``(pass, first offset in the block, tokens)`` a
        commit, ``remaining`` what the request is still owed after them (0:
        it ends in this segment and sits out what is left of it)."""
        L, steps = self._blockgen[:2]
        deliveries, denoised, cut = [], 0, 0
        for s in range(self.S):
            if remaining <= 0:
                break
            if self._blk_left[b]:
                self._blk_left[b] -= min(L // steps, self._blk_left[b])
                denoised += 1
                continue
            gen = self._blk_gen[b]
            n = min(gen, remaining)
            deliveries.append((s, L - gen, n))
            remaining -= n
            cut += gen - n
            self._row_pos[b] += L
            self._blk_left[b] = self._blk_gen[b] = L
        # (once a row, not once a pass: every write is mirrored to a gauge)
        st, commits = self.stats, len(deliveries)
        st["block_row_passes"] += denoised + commits
        st["denoise_row_passes"] += denoised
        st["commit_row_passes"] += commits
        st["blocks_committed"] += commits
        st["block_tokens_cut"] += cut
        return deliveries, remaining

    # ---- width buckets (ISSUE 19) ---------------------------------------

    def _bucket_width(self, need_slots: int) -> int:
        """Smallest bucket-ladder rung (a table width, in blocks) whose
        horizon covers ``need_slots`` logical slots, capped at the full
        table. Dispatch slices the shipped tables to this width; the
        compiled program's gathered views and masks are rung-wide
        because every attention-op width derives from the table
        argument, and the shared jit keys on the table aval — so the
        ladder bounds the compiled-program count."""
        need = min(self.nb, -(-max(1, need_slots) // self.bt))
        for w in self._width_ladder:
            if w >= need:
                return w
        return self._width_ladder[-1]

    def _note_width(self, nb_w: int, ticks: int, need_blocks: int) -> None:
        """Per-dispatch width accounting: the rung chosen and how full
        it ran, gathered-block traffic vs the fixed full-horizon
        design (every pre-bucketing dispatch gathered all ``nb`` table
        entries per row per layer per tick), and a flight-recorder
        instant on every bucket GROWTH — growth is the only step that
        can eat a new XLA compile mid-traffic, so each one must be
        post-mortem visible."""
        self._widths_dispatched.add(nb_w)
        if nb_w > self._cur_width:
            self.width["bucket_growths"] += 1
            instant("width_bucket_growth",
                    from_blocks=int(self._cur_width), to_blocks=int(nb_w))
            flight.record("width_bucket_growth",
                          from_blocks=int(self._cur_width),
                          to_blocks=int(nb_w),
                          segment=int(self.stats["segments"]))
        self._cur_width = nb_w
        self.width["bucket_blocks"] = nb_w
        self.width["bucket_occupancy"] = need_blocks / nb_w
        reads = self.B * nb_w * self._n_layers * ticks
        full = self.B * self.nb * self._n_layers * ticks
        self.width["gathered_block_reads"] += reads
        self.width["full_width_block_reads"] += full
        self.width["bytes_saved_vs_full"] += (
            (full - reads) * self._gather_block_bytes)

    def prewarm_widths(self, *, sampling: bool = False) -> int:
        """Compile the decode-segment program for every bucket-ladder
        rung NOW (``--prewarm_widths``): one dispatch per rung over
        all-trash tables with every row parked at position 0, so the
        first long request never eats a mid-traffic XLA compile when
        its bucket grows. Rides the shared jit (and therefore the
        ``_PROGRAM_CACHE`` donor), so a router fleet pays each rung
        once; a ``--supervise`` respawn re-runs the CLI entrypoint and
        prewarms again by construction. The throwaway ticks write only
        into the reserved trash block and the device token/position
        state is rewound afterwards, so a prewarmed batcher is
        indistinguishable from a fresh one. Returns the number of
        rungs dispatched (== programs compiled on a cold jit cache);
        counted in ``serve.width.prewarmed_programs``."""
        self._cut_weights()
        for w in self._width_ladder:
            tables = np.full((self.B, w), BlockPool.TRASH, np.int32)
            if self._blockgen is not None:
                with span("prewarm_width", blocks=int(w)):
                    (self._caches, self._blk_tok, self._blk_masked, *_
                     ) = self._block_segment_c(
                        self.params, self._caches, jnp.asarray(tables),
                        self._blk_tok, self._blk_masked,
                        jnp.zeros((self.B,), jnp.int32),
                        jnp.zeros((self.B,), jnp.int32))
                self.width["prewarmed_programs"] += 1
                continue
            with span("prewarm_width", blocks=int(w)), self._mesh_ctx():
                (self._caches, self._cur_tok, self._n_logical, *_
                 ) = self._segment_c(
                    self.params, self._caches, jnp.asarray(tables),
                    self._cur_tok, self._n_logical,
                    jnp.asarray([0] * self.B, jnp.int32),
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    jnp.asarray(self._topp), jnp.asarray(self._seed),
                    sampling=sampling)
            self.width["prewarmed_programs"] += 1
        # rewind the state the throwaway ticks advanced
        self._caches = jax.tree.map(jnp.zeros_like, self._caches)
        self._cur_tok = jnp.zeros_like(self._cur_tok)
        self._n_logical = jnp.zeros_like(self._n_logical)
        self._zero_blocks()
        return len(self._width_ladder)

    def _width_fraction(self) -> float:
        """Cost weight of one decode tick HERE relative to a
        full-horizon tick: the current bucket width over the full
        table width. A tick's HBM traffic is dominated by the KV
        gather, and the gather is rung-wide — so the router must price
        a tick by the bucket it would actually run at, not by
        ``t_max`` (the ISSUE 19 pricing fix: a replica serving short
        sessions stops being priced as if every tick gathered the
        horizon, and placement prefers replicas whose bucket stays
        small)."""
        return self._cur_width / self.nb

    def load_estimate(self, max_new: int) -> int:
        """Router-facing cost of serving ``max_new`` tokens here, in
        FULL-WIDTH tick equivalents (``serve_router`` load-balances on
        this): the segment-rounded budget for plain decode; under LIVE
        speculation, expected verify dispatches times the window width
        — each verify costs ``k + 1`` tick-equivalents and emits ``1 +
        rate * k`` tokens in expectation, with the batcher's own
        measured acceptance rate (0 until measured: admitting
        "speculation may not pay" keeps cold estimates conservative).
        Either tick count is then weighted by :meth:`_width_fraction`,
        so a replica whose bucket stays small undercuts one already
        gathering a long session's horizon."""
        if self._blockgen is not None:
            # a block is its denoising passes and one commit pass
            L, steps = self._blockgen[:2]
            ticks = -(-(-(-max_new // L) * (steps + 1)) // self.S) * self.S
        elif self._spec is None or not self._spec_on:
            ticks = -(-max_new // self.S) * self.S
        else:
            rate = min(1.0, max(0.0, float(self.spec["acceptance_rate"])))
            verifies = int(np.ceil(max_new / (1.0 + rate * self._spec.k)))
            ticks = max(verifies, 1) * self._spec_w
        return max(1, int(np.ceil(ticks * self._width_fraction())))

    def prefill_cost(self, suffix_tokens: int) -> int:
        """Router-facing cost of prefilling ``suffix_tokens`` uncached
        prompt tokens here, in the same tick units as
        :meth:`load_estimate`. Unchunked, a wave prefills the whole
        suffix in one stall — one token ≈ one tick of decode latency
        stolen from the live rows, independent of the decode bucket.
        CHUNKED, the suffix spreads over ``ceil(suffix / chunk)``
        bounded waves, each riding one decode-segment gap — the
        placement cost is segments, not tokens (the ISSUE 14 pricing
        fix), and each stalled segment is priced at the replica's
        CURRENT bucket width like any other decode tick (ISSUE 19)."""
        if suffix_tokens <= 0:
            return 0
        if self._chunk is None:
            return suffix_tokens
        segs = -(-suffix_tokens // self._chunk) * self.S
        return max(1, int(np.ceil(segs * self._width_fraction())))

    def _fits(self, req: Request) -> bool:
        return self.Tb + self._rounded_need(req.max_new) <= self.t_max

    def _validate_one(self, r: Request) -> str | None:
        """One request's submission-time validation; returns the error
        string (``None`` = valid). ``serve_detailed`` turns a non-None
        result into a structured ``failed`` outcome with ZERO device
        work and no slot occupancy; the legacy ``serve`` raises it."""
        if len(r.tokens) > self.Tb:
            return (f"prompt of {len(r.tokens)} tokens exceeds "
                    f"prompt_buf={self.Tb}")
        if len(r.tokens) == 0:
            return "empty prompt"
        if r.max_new < 1:
            return f"max_new must be >= 1, got {r.max_new}"
        if r.temperature < 0.0:
            return f"temperature must be >= 0, got {r.temperature}"
        if r.temperature > 0.0 and self._blockgen is not None:
            return ("sampled rows are not served by a model that generates "
                    "by block diffusion: a pass takes each position's "
                    "greedy token, and the rules choose among those "
                    "(temperature must be 0)")
        if r.temperature == 0.0 and (r.top_k is not None
                                     or r.top_p is not None):
            return ("top_k/top_p require temperature > 0 "
                    "(temperature 0 is greedy)")
        if r.top_k is not None and r.top_k < 1:
            return f"top_k must be >= 1, got {r.top_k}"
        if r.top_p is not None and not 0.0 < r.top_p <= 1.0:
            return f"top_p must be in (0, 1], got {r.top_p}"
        vocab = getattr(getattr(self.model, "config", None),
                        "vocab_size", None)
        if vocab is not None:
            bad = [t for t in r.tokens if not 0 <= t < vocab]
            if bad:
                # JAX gather CLAMPS out-of-range ids instead of raising,
                # so an unchecked bad id would silently decode garbage
                return (f"token ids {bad[:8]} outside the model vocab "
                        f"[0, {vocab})")
        if r.deadline_s is not None and r.deadline_s <= 0:
            return f"deadline_s must be > 0, got {r.deadline_s}"
        if getattr(r, "arrival_s", 0.0) < 0:
            return f"arrival_s must be >= 0, got {r.arrival_s}"
        return None

    def _validate(self, requests):
        for r in requests:
            err = self._validate_one(r)
            if err is not None:
                raise ValueError(err)

    def cancel(self, request_index: int) -> None:
        """Cancel one request of the serve call currently in flight, by
        its index in that call's request list. Thread-safe — a server
        front-end calls this from another thread; tests from a chaos
        ``on_segment`` hook. A still-queued request is finalised
        ``cancelled`` with no device work; an in-flight one is cut at
        the next segment boundary and returns its partial tokens.
        Unknown or already-finished indices are ignored; the set clears
        when a new serve call starts."""
        with self._cancel_mu:
            self._cancelled.add(int(request_index))

    def serve(self, requests: list[Request]) -> list[list[int]]:
        """Run every request through the pool; returns each request's
        generated tokens (trimmed at eos), in request order.

        Requests whose segment-rounded budget can never fit a row
        (``prompt_buf + ceil(max_new/segment)*segment > t_max``) are
        rejected: everything else is served to completion FIRST, then
        :class:`HorizonError` is raised with ``.outputs`` carrying the
        completed results. Admission order follows ``admit_policy``
        (class docstring: strict-FIFO fairness by default).

        This is the LEGACY all-or-nothing surface: invalid requests
        raise, infeasible ones raise after the rest complete. The
        fault-tolerant per-request surface — structured outcomes,
        deadlines, cancellation, drain, device-failure recovery — is
        :meth:`serve_detailed`; this wrapper runs the same engine."""
        self._validate(requests)
        results = self._run(requests)
        outputs = [r.tokens if r.status == OK else [] for r in results]
        rejected = [i for i, r in enumerate(results)
                    if r.status != OK and r.error is not None
                    and "horizon" in r.error]
        if rejected:
            worst = max(self._rounded_need(requests[i].max_new)
                        for i in rejected)
            raise HorizonError(
                f"per-row horizon exhausted for {len(rejected)} "
                f"request(s): prompt_buf={self.Tb} + segment-rounded "
                f"max_new (worst {worst}) exceeds t_max={self.t_max} — "
                f"raise t_max or shrink max_new (completed outputs are "
                f"on this error's .outputs)", outputs)
        return outputs

    def serve_detailed(self, requests: list[Request], *, drain=None,
                       drain_deadline_s: float | None = None,
                       chaos=None, recovery=None) -> list:
        """Fault-tolerant serving: run every request through the pool
        and return a :class:`serve_lifecycle.RequestResult` PER REQUEST
        (in request order) — nothing raises away the call, and no
        completed work is ever discarded. Each result carries its
        ``cached_prefix_tokens`` (how much of its prompt attached to
        the radix cache instead of re-prefilling; 0 with the cache
        off).

        Per-request lifecycle (``serve_lifecycle`` status vocabulary):
        validation failures and horizon-infeasible budgets come back
        ``failed`` with zero device work; ``Request.deadline_s`` expiry
        returns the partial stream as ``timeout``; :meth:`cancel` (from
        another thread or a chaos hook) returns ``cancelled``; bounded
        admission (``max_pending``) rejects overload as ``shed`` at
        submission.

        ``drain`` — graceful shutdown: any object with a ``preempted``
        attribute (``train/elastic.PreemptionGuard``, so SIGTERM drives
        it). When it flips, admission stops (the still-queued requests
        are ``shed``), in-flight rows run to completion within
        ``drain_deadline_s`` (None = unbounded), and everything already
        completed is returned ``ok``; rows still live at the drain
        deadline return their partial streams ``cancelled``.

        Device failures (a raised segment/harvest, or a harvest hung
        past ``tick_timeout_s``) trigger SESSION RECONSTRUCTION
        (``_reconstruct``): live rows are rebuilt token-exactly from
        host-tracked state and decode resumes — bounded by
        ``max_recoveries``, with a newest-admission eviction heuristic
        when a fault survives reconstruction. ``chaos`` injects faults
        for drills (:class:`serve_lifecycle.ChaosInjector`); production
        passes None.

        ``recovery`` — a ``serve_journal.RecoveryManifest`` (from
        ``serve_journal.recover(dir)``) built from a PREVIOUS process's
        journal: requests the journal shows completed return their
        recorded stream with zero device work (dedup by request id),
        and incomplete sessions re-enter admission as
        prompt+emitted-so-far replays, token-identical to the
        uninterrupted run (greedy and sampled — the (seed,
        tokens-generated) key schedule restores exactly, PR 5's
        reconstruction argument across a process boundary).
        """
        if recovery is not None and getattr(recovery, "sessions", None):
            return self._run_recovered(
                requests, recovery, drain=drain,
                drain_deadline_s=drain_deadline_s, chaos=chaos)
        return self._run(requests, drain=drain,
                         drain_deadline_s=drain_deadline_s, chaos=chaos)

    def _run_recovered(self, requests, recovery, **kw) -> list:
        """Split a resubmitted request list against a recovery
        manifest: journal-completed requests dedup (their recorded
        stream IS the result), journal-incomplete ones become
        continuation replays (prompt + emitted-so-far, remaining
        budget, the journaled seed), everything else passes through
        untouched. The merged result list is in request order and the
        replayed sessions' results carry the FULL stream (recorded
        prefix + newly decoded suffix) with ``recoveries`` bumped."""
        n = len(requests)
        pre: list[RequestResult | None] = [None] * n
        sub: list[Request] = []
        sub_meta: list[tuple[int, list]] = []   # (orig index, emitted)
        replay_admits: dict = {}
        for i, r in enumerate(requests):
            rid = getattr(r, "request_id", None) or f"req-{i}"
            # materialize the positional-default seed NOW: dedup below
            # shifts positions, and a sampled replay must re-admit
            # under the seed the original run actually used
            seed = r.seed
            if seed is None and r.temperature > 0.0:
                seed = i
            sess = recovery.sessions.get(rid)
            if sess is None or getattr(sess, "prompt", None) is None:
                sub_meta.append((i, []))
                sub.append(replace(r, request_id=rid, seed=seed))
                continue
            if sess.completed:
                # exactly-once emission: the journal already holds the
                # terminal stream — return it, spend nothing
                self.journal["deduped_completions"] += 1
                pre[i] = RequestResult(
                    status=sess.status, tokens=list(sess.emitted),
                    error=sess.error, request_id=rid)
                continue
            emitted = [int(t) for t in sess.emitted]
            seed = sess.seed if sess.seed is not None else seed
            prompt = [int(t) for t in sess.prompt]
            remaining = r.max_new - len(emitted)
            cont = prompt + emitted
            self.journal["recovered_sessions"] += 1
            self.journal["recovery_replay_tokens"] += len(emitted)
            instant("journal_session_replay", request_id=rid,
                    emitted=len(emitted), remaining=max(0, remaining))
            flight.record("journal_session_replay", request_id=rid,
                          emitted=len(emitted),
                          remaining=max(0, remaining))
            if emitted and remaining < 1:
                # the recorded stream already fills the budget — the
                # crash hit between the last delta and the end frame;
                # nothing left to decode
                pre[i] = RequestResult(status=OK,
                                       tokens=emitted[:r.max_new],
                                       request_id=rid)
                continue
            if emitted and len(cont) <= self.Tb:
                # continuation replay: the emitted tokens become prompt
                # suffix — same (seed, logical-position) schedule, so
                # the stream continues bit-exactly (see module-level
                # soundness note in serve_journal.py)
                sub_meta.append((i, emitted))
                replay_admits[len(sub)] = (rid, prompt, emitted)
                sub.append(replace(
                    r, tokens=cont, max_new=remaining, seed=seed,
                    request_id=rid, arrival_s=0.0))
            else:
                # full replay from scratch (budget spent, or the
                # continuation outgrows the prompt window): same seed
                # -> token-identical stream, just recomputed
                sub_meta.append((i, []))
                sub.append(replace(r, request_id=rid, seed=seed,
                                   arrival_s=0.0))
        self._replay_admits = replay_admits
        try:
            sub_results = self._run(sub, **kw)
        finally:
            self._replay_admits = {}
        for (i, emitted), res in zip(sub_meta, sub_results):
            if emitted and res is not None:
                res = replace(res, tokens=emitted + list(res.tokens),
                              recoveries=res.recoveries + 1)
            pre[i] = res
        return pre

    def _run(self, requests: list[Request], *, drain=None,
             drain_deadline_s: float | None = None, chaos=None) -> list:
        """The scheduler engine behind :meth:`serve` and
        :meth:`serve_detailed` — the overlapped dispatch/harvest loop
        (module docstring) with the request lifecycle, drain protocol,
        fault recovery and block accounting threaded through its
        host-side decision points."""
        self._cut_weights()
        t0 = time.monotonic()
        with self._cancel_mu:
            self._cancelled.clear()
        n = len(requests)
        results: list[RequestResult | None] = [None] * n
        ticks_charged = [0] * n
        recs = [0] * n
        cached_prefix = [0] * n
        # SLO timestamps (serve_lifecycle.RequestResult field docs):
        # arrival (open-loop offset; t0 for the legacy shape), admission
        # (its prefill wave's dispatch) and the first harvested token
        arrive_at = [t0 + getattr(requests[i], "arrival_s", 0.0)
                     for i in range(n)]
        admit_at: list[float | None] = [None] * n
        first_tok_at: list[float | None] = [None] * n
        # delivery stamps (``delivered``): when the request's newest
        # delivery reached the host, the mark of the segment that made it
        # (``mark_segment``), how many it has had and the longest gap
        last_delivery_at: list[float | None] = [None] * n
        last_mark: list[int | None] = [None] * n
        deliveries = [0] * n
        max_gap: list[float | None] = [None] * n
        # journal identities: the positional default makes a whole call
        # deterministic by id the same way the seed default does by
        # stream; explicit ids win (the router / recovery replays set
        # them)
        jr = self._journal
        jids = [getattr(requests[i], "request_id", None) or f"req-{i}"
                for i in range(n)]

        def fin(i, status, tokens, error=None):
            if results[i] is not None:
                return                      # first terminal event wins
            now = time.monotonic()
            latency = max(0.0, now - arrive_at[i])
            qw = (admit_at[i] - arrive_at[i]
                  if admit_at[i] is not None else None)
            ttft = (first_tok_at[i] - arrive_at[i]
                    if first_tok_at[i] is not None else None)
            tokens = list(tokens)
            tpot = ((latency - ttft) / (len(tokens) - 1)
                    if ttft is not None and len(tokens) > 1 else None)
            if admit_at[i] is not None:
                self._slo["e2e_s"].record(latency)
            if tpot is not None:
                self._slo["tpot_s"].record(tpot)
            results[i] = RequestResult(
                status=status, tokens=tokens, error=error,
                ticks=ticks_charged[i],
                latency_s=latency,
                recoveries=recs[i],
                cached_prefix_tokens=cached_prefix[i],
                queue_wait_s=qw, ttft_s=ttft, tpot_s=tpot,
                max_gap_s=max_gap[i], deliveries=deliveries[i],
                request_id=jids[i])
            if jr is not None:
                # terminal frame: no tokens (the admit's emitted prefix
                # plus the deltas since already hold the stream)
                jr.end(jids[i], status, error=error)

        # -- submission: validation failures are structured, not raised
        valid = []
        for i, r in enumerate(requests):
            err = self._validate_one(r)
            if err is not None:
                fin(i, FAILED, [], err)
            else:
                valid.append(i)
        sampling = any(requests[i].temperature > 0.0 for i in valid)
        deadline_at: list[float | None] = [None] * n
        for i in valid:
            if requests[i].deadline_s is not None:
                deadline_at[i] = t0 + requests[i].deadline_s

        def horizon_msg(req):
            return (f"per-row horizon exhausted: prompt_buf={self.Tb} + "
                    f"segment-rounded max_new "
                    f"({self._rounded_need(req.max_new)}) exceeds "
                    f"t_max={self.t_max}")

        if self.admit_policy == "fifo":
            # per-request horizon gate (segment-rounded): a reject here
            # is PERMANENT — per-row positions admit at the same window
            # offset every time, so what can't fit now can never fit,
            # and FIFO refuses to leapfrog, so an infeasible head would
            # block the queue forever
            queue = []
            for i in valid:
                if self._fits(requests[i]):
                    queue.append(i)
                else:
                    fin(i, FAILED, [], horizon_msg(requests[i]))
        else:
            # skip_fit: never-fitting requests are skipped in place at
            # admission time and reported at the end
            queue = list(valid)

        # -- bounded admission: overload rejects cheaply at submission
        if self.max_pending is not None:
            cap = self.B + self.max_pending
            if len(queue) > cap:
                for i in queue[cap:]:
                    fin(i, SHED, [],
                        f"shed: admission queue full ({len(queue)} "
                        f"requests > slots ({self.B}) + max_pending "
                        f"({self.max_pending}))")
                queue = queue[:cap]

        # -- write-ahead admission records: every request that survived
        # submission is journaled BEFORE it can consume device work, so
        # a crash at ANY later point finds its identity, prompt, params
        # and materialized seed on disk. Replayed sessions record their
        # TRUE shape (original prompt + emitted prefix), not the
        # continuation prompt — a second crash recovers the full stream.
        if jr is not None:
            replays = self._replay_admits
            for qi in queue:
                r = requests[qi]
                rep = replays.get(qi)
                if rep is not None:
                    rid, prompt, emitted = rep
                    total_new = r.max_new + len(emitted)
                else:
                    rid, prompt, emitted = jids[qi], list(r.tokens), []
                    total_new = r.max_new
                jr.admit(
                    rid, prompt, total_new,
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p,
                    # the admission-time seed default (admit_wave uses
                    # the request's index in THIS call) materializes
                    # into the frame so a sampled replay restores the
                    # identical stream
                    seed=(r.seed if r.seed is not None
                          else (qi if r.temperature > 0.0 else None)),
                    deadline_s=r.deadline_s, emitted=emitted)
            jr.commit()

        table = [_Slot() for _ in range(self.B)]
        admit_seq = [0]
        draining = {"on": False, "deadline": None}
        fault_state = {"recoveries": 0, "consecutive": 0}
        hb = {"next": (t0 + self.heartbeat_s)
              if (self.heartbeat_s is not None
                  and self.on_heartbeat is not None) else None}

        def free_row(b):
            """Release row ``b``'s pool references and park its table at
            trash. Every terminal slot transition funnels here — the
            block-leak invariant depends on it."""
            slot = table[b]
            if slot.blocks:
                self._pool.release(slot.blocks)
            self._tables[b, :] = BlockPool.TRASH
            slot.free()

        def police():
            """Host-known lifecycle transitions between device calls:
            drain start (stop admission, shed the queue), cancellations
            and deadline expiries (queued AND in-flight), and the drain
            deadline. Pure host bookkeeping — no device work, so the
            checks cost nothing on the hot path."""
            now = time.monotonic()
            if hb["next"] is not None and now >= hb["next"]:
                hb["next"] = now + self.heartbeat_s
                try:
                    self.on_heartbeat(self.stats_snapshot())
                except Exception:   # noqa: BLE001 — telemetry must
                    pass            # never fail a request
            if (drain is not None and getattr(drain, "preempted", False)
                    and not draining["on"]):
                draining["on"] = True
                instant("drain_start", queued=len(queue))
                # a preempting host may never reach a clean exit — dump
                # the ring the moment the SIGTERM latch is observed
                flight.dump_on_fault("sigterm_drain",
                                     queued=len(queue))
                if drain_deadline_s is not None:
                    draining["deadline"] = now + drain_deadline_s
                for i in list(queue):
                    fin(i, SHED, [], "shed: draining (admission stopped)")
                queue.clear()
            with self._cancel_mu:
                cancelled = set(self._cancelled)
            for i in list(queue):
                if i in cancelled:
                    queue.remove(i)
                    fin(i, CANCELLED, [], "cancelled while queued")
                elif deadline_at[i] is not None and now >= deadline_at[i]:
                    queue.remove(i)
                    fin(i, TIMEOUT, [],
                        f"deadline_s={requests[i].deadline_s} expired "
                        f"while queued")
            for b, slot in enumerate(table):
                i = slot.req_index
                if i < 0:
                    continue
                if i in cancelled:
                    fin(i, CANCELLED, slot.out, "cancelled in flight")
                    free_row(b)
                elif deadline_at[i] is not None and now >= deadline_at[i]:
                    fin(i, TIMEOUT, slot.out,
                        f"deadline_s={requests[i].deadline_s} expired "
                        f"in flight")
                    free_row(b)
            if (draining["on"] and draining["deadline"] is not None
                    and now > draining["deadline"]):
                for b, slot in enumerate(table):
                    if slot.req_index < 0:
                        continue
                    fin(slot.req_index, CANCELLED, slot.out,
                        f"drain deadline ({drain_deadline_s}s) expired")
                    free_row(b)

        def pick_admissions(k_free: int) -> list[int]:
            take: list[int] = []
            if draining["on"]:
                return take                 # drain: admission stopped
            now = time.monotonic()
            heads: list[int] = []

            def room(ri: int) -> bool:
                # the wave's bound: _WAVE_TOKENS of prefill window between
                # two decode segments (the first row always goes)
                heads.append(len(requests[ri].tokens) - 1)
                if take and self._wave_window(heads) > _WAVE_TOKENS:
                    heads.pop()
                    return False
                return True

            if self.admit_policy == "fifo":
                # an unarrived head BLOCKS the wave: open-loop arrivals
                # keep the same no-leapfrog fairness as submissions
                while (queue and len(take) < k_free
                       and arrive_at[queue[0]] <= now and room(queue[0])):
                    take.append(queue.pop(0))
            else:
                i = 0
                while i < len(queue) and len(take) < k_free:
                    if (self._fits(requests[queue[i]])
                            and arrive_at[queue[i]] <= now
                            and room(queue[i])):
                        take.append(queue.pop(i))
                    else:
                        i += 1
            return take

        def admit_wave():
            """ONE wave for every pending request that has a free row
            (the batched admission: k admissions, the few dispatches
            their window rungs need, back to back — ``_prefill_wave``).
            Radix attach + block allocation + COW copies happen here, on
            the host, before the wave's device work. All host->device,
            no fetch. With CHUNKED PREFILL on, the wave shares one
            suffix-token budget: rows past it admit mid-prompt (their
            slot carries the progress mark) and extend between decode
            segments via ``chunk_wave`` — a long-prompt admission storm
            can never widen a single wave past the chunk. A wave holds
            at most ``_WAVE_TOKENS`` of prefill window, counted as its
            dispatches will hold it (``_wave_window``); requests past
            that stay queued for the next wave, one decode segment
            later."""
            free = [b for b, s in enumerate(table) if s.req_index < 0]
            take = pick_admissions(len(free))
            if not take:
                return
            with span("admit_wave", rows=len(take),
                      rids=" ".join(jids[ri] for ri in take)):
                now = time.monotonic()
                rows = free[:len(take)]
                entries, cow_all = [], []
                budget = self._chunk
                for b, ri in zip(rows, take):
                    req = requests[ri]
                    admit_at[ri] = now
                    self._slo["queue_wait_s"].record(
                        max(0.0, now - arrive_at[ri]))
                    self._temp[b] = req.temperature
                    self._topk[b] = req.top_k or 0
                    self._topp[b] = (req.top_p if req.top_p is not None
                                     else 2.0)
                    self._seed[b] = np.uint32(
                        req.seed if req.seed is not None else ri)
                    slot = table[b]
                    slot.req_index = ri
                    slot.out = []
                    slot.remaining = req.max_new
                    slot.admit_seq = admit_seq[0]
                    admit_seq[0] += 1
                    m, cow = self._assign_blocks(b, slot,
                                                 list(req.tokens),
                                                 req.max_new)
                    cow_all.extend(cow)
                    cached_prefix[ri] = m
                    if m:
                        self.stats["prefix_hits"] += 1
                    self.stats["cached_prefix_tokens"] += m
                    self.stats["prefill_tokens_saved"] += m
                    head_len = len(req.tokens) - 1
                    upto = head_len
                    if self._blockgen is not None:
                        # the prompt's whole blocks; its tail opens the
                        # first generated block (_open_blocks)
                        upto = (len(req.tokens) // self._blockgen[0]
                                * self._blockgen[0])
                    if budget is not None:
                        give = min(head_len - m, budget)
                        budget -= give
                        upto = m + give
                        if upto < head_len:
                            slot.pf_known = list(req.tokens)
                            slot.pf_done = upto
                            self.prefill["chunked_admissions"] += 1
                    entries.append((b, list(req.tokens), m, upto))
                self.stats["cow_copies"] += len(cow_all)
                if cow_all:
                    self._copy_blocks(cow_all)
                self._prefill_wave(entries, self._chunk)
                self.stats["prefill_rows"] += len(take)
                if self._tier_promote_t0 is not None:
                    # the wave's promotion H2D copies were dispatched
                    # back in _assign_blocks and ran while the host
                    # built + dispatched this prefill — the overlapped
                    # window, closed here (both dispatches are async;
                    # device order serialises copy before read)
                    self.tier["promote_overlap_ms"] += (
                        time.monotonic() - self._tier_promote_t0) * 1e3
                    self._tier_promote_t0 = None
                if self._radix is not None:
                    # the wave's freshly-prefilled heads enter the cache
                    # so later arrivals can attach to them (insert AFTER
                    # the prefill dispatch: device order makes the
                    # blocks valid before any attacher's wave can read
                    # them). Mid-chunk rows DEFER their insert to the
                    # extension wave that finishes the head — a partial
                    # head in the tree would hand attachers blocks whose
                    # tail is still unwritten.
                    for b, known, m, upto in entries:
                        head = known[:-1]
                        if head and upto >= len(known) - 1:
                            nb_head = -(-len(head) // self.bt)
                            self._radix.insert(
                                head, [int(x) for x in
                                       self._tables[b, :nb_head]])

        def chunk_wave():
            """ONE chunk-budgeted extension prefill for every row
            admitted mid-prompt (``prefill_chunk_tokens``): advance
            each pending row's prefill by up to the shared budget
            through the same ``kv_prefix`` suffix path an attach wave
            rides, finalising rows that reach their head (they join the
            next decode plan; their head enters the radix cache only
            now, once every block is written). Called between decode
            segments — each admission storm costs the decode rows one
            bounded wave per gap, never one whole-prompt prefill."""
            if self._chunk is None:
                return
            budget = self._chunk
            entries = []
            for b, slot in enumerate(table):
                if slot.req_index < 0 or slot.pf_known is None:
                    continue
                head_len = len(slot.pf_known) - 1
                give = min(head_len - slot.pf_done, budget)
                if give <= 0:
                    continue       # this wave's budget is spent
                budget -= give
                entries.append((b, slot.pf_known, slot.pf_done,
                                slot.pf_done + give))
                slot.pf_done += give
            if not entries:
                return
            with span("chunk_wave", rows=len(entries)):
                self._prefill_wave(entries, self._chunk)
                self.prefill["chunk_waves"] += 1
                self.prefill["chunk_tokens"] += sum(
                    upto - m for _, _, m, upto in entries)
                for b, known, _m, upto in entries:
                    if upto < len(known) - 1:
                        continue               # still mid-prompt
                    slot = table[b]
                    slot.pf_known = None
                    slot.pf_done = 0
                    if self._radix is not None:
                        head = known[:-1]
                        if head:
                            nb_head = -(-len(head) // self.bt)
                            self._radix.insert(
                                head, [int(x) for x in
                                       self._tables[b, :nb_head]])

        window_at_segment = [self.stats["prefill_window_tokens"]]

        def mark_segment() -> tuple:
            """The segment about to go out: its MARK, the running count
            of admission programs that reached the device before it
            (prefill dispatches, chunk extensions among them, and the copy
            and promote programs, which raise no ``prefill_calls``), and
            the prefill window dispatched since the segment before it (the
            dispatch span's note). The mark rides the segment to its
            harvest: two segments with one mark had no admission between
            them on the DEVICE, whatever the host's clock says (the
            harvest of segment k runs after segment k+1 went out)."""
            window = self.stats["prefill_window_tokens"]
            since = window - window_at_segment[0]
            window_at_segment[0] = window
            return (self.stats["prefill_calls"] + self.stats["cow_copies"]
                    + self.tier["promotions"]), since

        def delivered(ri: int, now: float, mark: int, groups: dict):
            """A harvest handed request ``ri`` new tokens at ``now`` (the
            harvest's one clock read) from a segment dispatched at
            ``mark``: stamp the delivery and, unless it is the request's
            first (that interval is TTFT's), file its gap in ``groups``
            under the previous delivery's stamp and mark. Rows whose
            previous delivery came from one segment share both."""
            prev = last_delivery_at[ri]
            if prev is not None:
                gap = now - prev
                if max_gap[ri] is None or gap > max_gap[ri]:
                    max_gap[ri] = gap
                key = (prev, last_mark[ri])
                groups[key] = groups.get(key, 0) + 1
            deliveries[ri] += 1
            last_delivery_at[ri] = now
            last_mark[ri] = mark

        def count_gaps(groups: dict, now: float, mark: int) -> dict:
            """The gaps one harvest closed (``delivered``) into ``stats``,
            one update a distinct gap: CLEAR where no admission program
            reached the device between the two segments that delivered,
            BEHIND ADMISSION otherwise. Returns the harvest span's notes:
            the longest gap and the deliveries behind admission."""
            longest, behind = 0.0, 0
            for (prev, before), rows in groups.items():
                gap = now - prev
                kind = "clear"
                if mark != before:
                    kind = "behind_admission"
                    behind += rows
                longest = max(longest, gap)
                self.stats["deliveries_" + kind] += rows
                self.stats["delivery_gap_s_" + kind] += gap * rows
                self.stats[_GAP_COUNTERS[bisect_right(_GAP_EDGES_S,
                                                      gap)]] += rows
            self._longest_gap_s = max(self._longest_gap_s, longest)
            return {"gap_max_ms": round(1e3 * longest, 3),
                    "behind_admission": behind}

        def dispatch_segment():
            """Dispatch ONE compiled segment (no fetch). Returns the
            (device tokens, plan, admission mark) record the later harvest
            consumes, or None when no row has budget left to tick. Budget
            depletion is applied HERE, at dispatch — it is host-known — so the
            overlapping caller can decide about segment N+1 without
            waiting for segment N's tokens; rows that are done (or
            free) are parked at position 0 with their table swapped for
            the all-trash row, so their garbage writes land in the
            reserved trash block and can never touch a live or cached
            block. Rows still mid-chunk (``pf_known``) park too: their
            head is not fully prefilled, so a decode tick would attend
            unwritten K/V."""
            plan = []
            for b, slot in enumerate(table):
                if (slot.req_index >= 0 and slot.remaining > 0
                        and slot.pf_known is None):
                    take = min(slot.remaining, self.S)
                    plan.append((b, slot.req_index, take,
                                 slot.remaining - take <= 0))
            if not plan:
                return None
            pending = (bool(queue) if self.admit_policy == "fifo"
                       else any(self._fits(requests[i]) for i in queue))
            active = {b for b, _, _, _ in plan}
            tables_now = self._tables.copy()
            for b in range(self.B):
                if b not in active:
                    tables_now[b, :] = BlockPool.TRASH
                    self._row_pos[b] = 0
                    key = ("parked_admission_lag" if pending
                           else "parked_drain")
                    self.waste[key] += self.S
                    self.stats["decode_rows_parked"] += self.S
                    if table[b].pf_known is not None:
                        self.prefill["stall_ticks"] += self.S
            # width bucket (ISSUE 19): the segment's S ticks write
            # slots up to row_pos + S and attend nothing beyond, so
            # the smallest rung covering max(live row_pos) + S + 1
            # slots is exact — parked rows sit at 0 under all-trash
            # tables (trash block id 0 is in-range at ANY width, and
            # the paged write clamps), so the slice is safe for them
            # at every rung
            need = max(self._row_pos[b] for b in active) + self.S + 1
            nb_w = self._bucket_width(need)
            self._note_width(nb_w, self.S,
                             min(self.nb, -(-need // self.bt)))
            prof = self._profile_req
            if prof is not None and not prof["active"]:
                # profile_next() armed mid-run: open the XLA trace just
                # before this segment's dispatch
                jax.profiler.start_trace(prof["dir"])
                prof["active"] = True
            mark, admitted = mark_segment()
            with span("dispatch_segment", rows=len(plan),
                      rids=" ".join(jids[ri] for _, ri, _, _ in plan),
                      admitted_window_tokens=admitted):
                args = (self.params, self._caches,
                        jnp.asarray(tables_now[:, :nb_w]),
                        self._cur_tok, self._n_logical,
                        jnp.asarray(self._row_pos, jnp.int32),
                        jnp.asarray(self._temp), jnp.asarray(self._topk),
                        jnp.asarray(self._topp), jnp.asarray(self._seed))
                self._note_program("segment", self._segment_c, args,
                                   {"sampling": sampling})
                with self._mesh_ctx():
                    (self._caches, self._cur_tok, self._n_logical, toks,
                     *xc) = self._segment_c(*args, sampling=sampling)
                del args
            if prof is not None and prof["active"]:
                prof["remaining"] -= 1
                if prof["remaining"] <= 0:
                    # one bounded sync so the profiled segments' device
                    # work is actually inside the trace window
                    jax.block_until_ready(toks)
                    jax.profiler.stop_trace()
                    self._profile_req = None
            for b in range(self.B):
                self._row_pos[b] += self.S
            self.ticks += self.S
            self.stats["segments"] += 1
            if self.kv_dtype == "int8":
                # every decode tick gathers + dequantizes the row's
                # resident blocks inside the fused attend
                self.kvq["dequant_reads"] += 1
            for b, ri, take, _ in plan:
                table[b].remaining -= take
                ticks_charged[ri] += take
                self.waste["planned_ticks"] += self.S
            if self._state_layers:
                self.stats["state_rows_advanced"] += len(plan) * self.S
            if chaos is not None and chaos.on_segment is not None:
                # host observation hook: drills flip drain flags /
                # cancel requests at a deterministic segment
                chaos.on_segment(self.stats["segments"])
            # held experts' counts ride with the tokens to the harvest
            return "plain", (toks, *xc), plan, mark

        def dispatch_block_segment():
            """:func:`dispatch_segment` for a model that generates by block
            diffusion: ONE compiled segment of ``S`` passes
            (``_block_segment_impl``), no fetch. What each row's passes
            yield is the step contract's (``_row_passes``), host-known: the
            budget is charged here and segment N+1 goes out before segment
            N is fetched, as ever. A row's plan entry carries its
            deliveries in place of a tick count."""
            rows = [b for b, slot in enumerate(table)
                    if slot.req_index >= 0 and slot.remaining > 0]
            if not rows:
                return None
            pos0 = np.zeros((self.B,), np.int32)
            stop = np.zeros((self.B,), np.int32)
            plan = []
            for b in rows:
                slot = table[b]
                pos0[b], stop[b] = self._row_pos[b], self._stop_pos[b]
                deliveries, left = self._row_passes(b, slot.remaining)
                ticks_charged[slot.req_index] += slot.remaining - left
                slot.remaining = left
                plan.append((b, slot.req_index, deliveries, left <= 0))
                self.waste["planned_ticks"] += self.S
            pending = (bool(queue) if self.admit_policy == "fifo"
                       else any(self._fits(requests[i]) for i in queue))
            tables_now = self._tables.copy()
            for b in set(range(self.B)) - set(rows):
                tables_now[b, :] = BlockPool.TRASH
                self._row_pos[b] = 0
                self.waste["parked_admission_lag" if pending
                           else "parked_drain"] += self.S
                self.stats["decode_rows_parked"] += self.S
            # the segment's passes write and attend nothing past the block a
            # row ends the segment in (a finished row: past its stop)
            need = max(min(self._row_pos[b] + self._blockgen[0],
                           int(self._stop_pos[b])) for b in rows)
            nb_w = self._bucket_width(need)
            self._note_width(nb_w, self.S, min(self.nb, -(-need // self.bt)))
            prof = self._profile_req
            if prof is not None and not prof["active"]:
                jax.profiler.start_trace(prof["dir"])
                prof["active"] = True
            mark, admitted = mark_segment()
            with span("dispatch_segment", rows=len(plan),
                      rids=" ".join(jids[ri] for _, ri, _, _ in plan),
                      admitted_window_tokens=admitted):
                args = (self.params, self._caches,
                        jnp.asarray(tables_now[:, :nb_w]), self._blk_tok,
                        self._blk_masked, jnp.asarray(pos0),
                        jnp.asarray(stop))
                self._note_program("segment", self._block_segment_c, args,
                                   {})
                (self._caches, self._blk_tok, self._blk_masked,
                 *outs) = self._block_segment_c(*args)
                del args
            if prof is not None and prof["active"]:
                prof["remaining"] -= 1
                if prof["remaining"] <= 0:
                    jax.block_until_ready(outs[0])
                    jax.profiler.stop_trace()
                    self._profile_req = None
            self.ticks += self.S
            self.stats["segments"] += 1
            self.stats["block_passes"] += self.S
            if chaos is not None and chaos.on_segment is not None:
                chaos.on_segment(self.stats["segments"])
            return "block", tuple(outs), plan, mark

        def cow_for_write(plan):
            """Speculation rollback-safety guard (ISSUE 12): a verify
            window writes slots ``row_pos+1 .. row_pos+W``, and every
            block under that span must be EXCLUSIVELY owned before the
            dispatch. A shared ref there can only be a radix entry whose
            valid tokens end at or before the row's live position
            (append-beyond-valid-span), but the invariant is enforced
            rather than assumed: any refcount>1 block in the write span
            is copy-on-write'd first — the radix keeps the original
            (and its bytes: a copy, not a move), the row re-points at
            its private copy, and content up to the live position is
            identical, so attached readers and this row's own prefix
            reads cannot move. Rejected drafts therefore provably never
            mutate a radix-attached prefix block
            (``tests/test_kv_pool.py`` drills this)."""
            pairs = []
            for b, _ri, _d in plan:
                slot = table[b]
                lo = (self._row_pos[b] + 1) // self.bt
                hi = min((self._row_pos[b] + self._spec_w) // self.bt,
                         self.nb - 1)
                for idx in range(lo, hi + 1):
                    blk = int(self._tables[b, idx])
                    if (blk == BlockPool.TRASH
                            or not self._pool.shared(blk)):
                        continue
                    dst = self._alloc(1)[0]
                    pairs.append((blk, dst))
                    self._tables[b, idx] = dst
                    slot.blocks[slot.blocks.index(blk)] = dst
                    self._pool.release([blk])
            if pairs:
                self.stats["cow_copies"] += len(pairs)
                self._copy_blocks(pairs)

        def dispatch_verify():
            """Dispatch ONE speculative verify step (no fetch): draft
            ``k`` tokens per live row from its host-tracked history
            (prompt + emitted), stack them behind the row's current
            token, and score all ``k + 1`` positions in one compiled
            forward (``_verify_impl``). Budget decrements at HARVEST by
            the emitted length — the next window's drafts depend on
            this one's outcome, so verify steps never overlap (the
            weight-stream amortisation that overlap bought plain decode
            is what verification itself provides here)."""
            W = self._spec_w
            toks = np.zeros((self.B, W), np.int32)
            plan = []
            for b, slot in enumerate(table):
                if (slot.req_index >= 0 and slot.remaining > 0
                        and slot.pf_known is None):
                    ri = slot.req_index
                    ctx = list(requests[ri].tokens) + slot.out
                    drafts = [int(t) for t in
                              self._proposer.propose(ctx, W - 1)][:W - 1]
                    if len(drafts) < W - 1:
                        tail = drafts[-1] if drafts else 0
                        drafts += [tail] * (W - 1 - len(drafts))
                    toks[b, 0] = self._cur_h[b]
                    toks[b, 1:] = drafts
                    plan.append((b, ri, drafts))
            if not plan:
                return None
            # COW BEFORE snapshotting the tables: the dispatch below must
            # see the post-copy block ids, or this window's col-0 write
            # would land in the old shared block while the row's table
            # already points at the copy (which would then be missing it)
            cow_for_write(plan)
            pending = (bool(queue) if self.admit_policy == "fifo"
                       else any(self._fits(requests[i]) for i in queue))
            active = {b for b, _, _ in plan}
            tables_now = self._tables.copy()
            for b in range(self.B):
                if b not in active:
                    tables_now[b, :] = BlockPool.TRASH
                    self._row_pos[b] = 0
                    key = ("parked_admission_lag" if pending
                           else "parked_drain")
                    self.waste[key] += W
                    self.stats["decode_rows_parked"] += W
                    if table[b].pf_known is not None:
                        self.prefill["stall_ticks"] += W
            # width bucket (ISSUE 19): a verify window writes slots
            # row_pos+1 .. row_pos+W, and _verify_impl's beyond-horizon
            # sentinel drops writes at positions >= nb_w * bt — so the
            # rung MUST cover max(live row_pos) + W + 1 slots or an
            # in-horizon accepted token would lose its K/V. Capped at
            # nb, where the sentinel semantics match the full-width
            # program exactly
            need = max(self._row_pos[b] for b in active) + W + 1
            nb_w = self._bucket_width(need)
            self._note_width(nb_w, W, min(self.nb, -(-need // self.bt)))
            prof = self._profile_req
            if prof is not None and not prof["active"]:
                jax.profiler.start_trace(prof["dir"])
                prof["active"] = True
            mark, admitted = mark_segment()   # after this window's own copies
            with span("dispatch_verify", rows=len(plan),
                      admitted_window_tokens=admitted):
                with self._mesh_ctx():
                    self._caches, true = self._verify_c(
                        self.params, self._caches,
                        jnp.asarray(tables_now[:, :nb_w]),
                        jnp.asarray(toks),
                        jnp.asarray(self._row_pos, jnp.int32),
                        jnp.asarray(self._nlog_h),
                        jnp.asarray(self._temp), jnp.asarray(self._topk),
                        jnp.asarray(self._topp), jnp.asarray(self._seed),
                        sampling=sampling)
            if prof is not None and prof["active"]:
                prof["remaining"] -= 1
                if prof["remaining"] <= 0:
                    jax.block_until_ready(true)
                    jax.profiler.stop_trace()
                    self._profile_req = None
            # NOTE: _row_pos does NOT advance here — harvest_verify
            # moves each row by its ACCEPTED length only (the rollback
            # is free: garbage K/V beyond the live position is never
            # attended and the next verify overwrites it)
            self.ticks += W
            self.stats["segments"] += 1
            self.spec["verify_segments"] += 1
            if self.kv_dtype == "int8":
                self.kvq["dequant_reads"] += 1
            for _b, _ri, _d in plan:
                self.waste["planned_ticks"] += W
            if chaos is not None and chaos.on_segment is not None:
                chaos.on_segment(self.stats["segments"])
            return "spec", true, plan, mark

        def maybe_autodisable():
            """Throughput guard: over each window of
            ``autodisable_window`` proposed drafts, sustained acceptance
            below ``autodisable_below`` flips back to plain segment
            decode (sticky until :meth:`reset`) — a verify step that
            accepts nothing still streams the weights once, so losing
            speculation costs nothing but keeping a useless proposer
            costs the wasted verify columns forever. Outputs are
            unaffected either way (the accept rule is exact)."""
            prop, acc = self._spec_win
            if prop < self._spec.autodisable_window:
                return
            rate = acc / prop
            if rate >= self._spec.autodisable_below:
                self._spec_win = [0, 0]
                return
            self._spec_on = False
            self._spec_win = [0, 0]
            self.spec["autodisabled"] += 1
            instant("spec_autodisable", window_proposed=prop,
                    window_accepted=acc, rate=round(rate, 4))
            # the verify path ran entirely off the host mirrors, so the
            # device _cur_tok/_n_logical are stale — push the mirrors
            # back so the next plain segment resumes exactly
            with self._mesh_ctx():
                self._cur_tok = self._cur_tok.at[:].set(
                    jnp.asarray(self._cur_h))
                self._n_logical = self._n_logical.at[:].set(
                    jnp.asarray(self._nlog_h))

        def harvest_verify(seg):
            """THE fetch for a verify step: compare each row's drafts to
            the target's own ``true`` tokens and emit the longest
            accepted prefix PLUS the ``true`` token at the first
            mismatch — which IS the deterministic rejection resample
            (``_verify_impl`` docstring) — clamped to the remaining
            budget. Every accept/reject decision is host logic over one
            fetched ``[B, W]`` array; per-row state (position, logical
            count, current token) advances by the emitted length only,
            which is the entire rollback."""
            _kind, true_dev, plan, mark = seg
            with span("harvest_verify", rows=len(plan)) as sp:
                gaps = {}
                self.stats["fetches"] += 1
                if chaos is not None:
                    chaos.pre_fetch(self.stats["segments"],
                                    [ri for _, ri, _ in plan])

                def fetch():
                    if chaos is not None:
                        chaos.in_fetch(self.stats["segments"])
                    return np.asarray(true_dev)

                if self.tick_timeout_s is not None:
                    true_h = call_with_timeout(fetch, self.tick_timeout_s,
                                               "serve verify harvest")
                else:
                    true_h = fetch()
                now = time.monotonic()
                W = self._spec_w
                for b, ri, drafts in plan:
                    if results[ri] is not None:
                        continue   # cancelled/timed out while in flight
                    slot = table[b]
                    if slot.req_index != ri:
                        continue
                    row = true_h[b]
                    j = 0
                    while j < W - 1 and drafts[j] == int(row[j]):
                        j += 1
                    emit = [int(t) for t in row[:j + 1]][:slot.remaining]
                    self.spec["proposed"] += W - 1
                    self.spec["accepted"] += j
                    self.spec["emitted_tokens"] += len(emit)
                    self.spec["wasted_verify_tokens"] += W - len(emit)
                    self._spec_win[0] += W - 1
                    self._spec_win[1] += j
                    ticks_charged[ri] += W
                    slot.remaining -= len(emit)
                    was_empty = not slot.out
                    prev_out = len(slot.out)
                    slot.out.extend(emit)
                    self._row_pos[b] += len(emit)
                    self._nlog_h[b] += len(emit)
                    if emit:
                        self._cur_h[b] = emit[-1]
                    if (was_empty and slot.out
                            and first_tok_at[ri] is None):
                        first_tok_at[ri] = now
                        self._slo["ttft_s"].record(
                            max(0.0, now - arrive_at[ri]))
                    done = slot.remaining <= 0
                    if (self.eos_id is not None
                            and self.eos_id in slot.out):
                        slot.out = slot.out[
                            :slot.out.index(self.eos_id) + 1]
                        done = True
                    if len(slot.out) > prev_out:
                        delivered(ri, now, mark, gaps)
                        if jr is not None:
                            # post-trim: only DELIVERED tokens are journaled
                            jr.delta(jids[ri], slot.out[prev_out:])
                    if done:
                        fin(ri, OK, slot.out)
                        free_row(b)
                sp.note(**count_gaps(gaps, now, mark))
                if jr is not None:
                    jr.commit()        # harvest = the durability boundary
                if self.spec["proposed"]:
                    self.spec["acceptance_rate"] = (
                        self.spec["accepted"] / self.spec["proposed"])
                maybe_autodisable()

        def dispatch_next():
            """Route to the live dispatch flavour: speculative verify
            while speculation is configured and not auto-disabled,
            plain segments otherwise."""
            if self._spec is not None and self._spec_on:
                return dispatch_verify()
            if self._blockgen is not None:
                return dispatch_block_segment()
            return dispatch_segment()

        def harvest(seg, overlapped: bool):
            """THE one device->host fetch per segment, under the tick
            watchdog when configured. ``overlapped`` records whether
            the next segment was already dispatched (the counter
            ``tests/test_serve.py`` asserts)."""
            if seg[0] == "spec":
                harvest_verify(seg)
                return
            _kind, outs, plan, mark = seg   # (tokens, and the counts if any)
            with span("harvest", overlapped=overlapped) as sp:
                first_ids, done_ids, gaps = [], [], {}
                self.stats["fetches"] += 1
                if overlapped:
                    self.stats["fetches_overlapped"] += 1
                if chaos is not None:
                    chaos.pre_fetch(self.stats["segments"],
                                    [ri for _, ri, _, _ in plan])

                def fetch():
                    if chaos is not None:
                        chaos.in_fetch(self.stats["segments"])
                    return jax.device_get(outs)

                if self.tick_timeout_s is not None:
                    toks_h, *xc_h = call_with_timeout(
                        fetch, self.tick_timeout_s, "serve tick harvest")
                else:
                    toks_h, *xc_h = fetch()
                if _kind == "block":
                    # toks_h [B, S, L]; a row's ``take`` is its deliveries
                    # (_row_passes), and the device committed as planned
                    commits_h, *xc_h = xc_h
                    off = [b for b, _, take, _ in plan
                           if commits_h[b] != len(take)]
                    if off:
                        raise RuntimeError(
                            f"rows {off} committed "
                            f"{[int(commits_h[b]) for b in off]} blocks "
                            f"against the host's plan")
                for key, c in zip(self._count_keys, *xc_h):
                    self.stats[key] += int(c)
                now = time.monotonic()
                for b, ri, take, done_after in plan:
                    if results[ri] is not None:
                        # the request finished (eos) — or was cancelled
                        # / timed out — in an earlier segment while this
                        # one was already in flight: its ticks are
                        # overlap tail waste, never tokens
                        continue
                    slot = table[b]
                    if slot.req_index != ri:
                        continue   # row re-admitted after an early free
                    was_empty = not slot.out
                    prev_out = len(slot.out)
                    new = (toks_h[b, :take] if _kind == "plain" else
                           [t for s, o, k in take
                            for t in toks_h[b, s, o:o + k]])
                    slot.out.extend(int(t) for t in new)
                    if (was_empty and slot.out
                            and first_tok_at[ri] is None):
                        # first generated token reached the host: TTFT
                        first_tok_at[ri] = now
                        first_ids.append(jids[ri])
                        self._slo["ttft_s"].record(
                            max(0.0, now - arrive_at[ri]))
                    done = done_after
                    if (self.eos_id is not None
                            and self.eos_id in slot.out):
                        slot.out = slot.out[
                            :slot.out.index(self.eos_id) + 1]
                        done = True
                    if len(slot.out) > prev_out:
                        delivered(ri, now, mark, gaps)
                        if jr is not None:
                            # post-trim: only DELIVERED tokens are journaled
                            jr.delta(jids[ri], slot.out[prev_out:])
                    if done:
                        fin(ri, OK, slot.out)
                        free_row(b)
                        done_ids.append(jids[ri])
                sp.note(first=" ".join(first_ids) or "-",
                        done=" ".join(done_ids) or "-",
                        **count_gaps(gaps, now, mark))
                if jr is not None:
                    jr.commit()        # harvest = the durability boundary

        def handle_fault(e: BaseException) -> bool:
            """A device interaction failed (raised or hung). Recover by
            session reconstruction, bounded by ``max_recoveries``; a
            fault that SURVIVES reconstruction implicates a poison row,
            and the newest admission is evicted before the next attempt
            (the fault appeared after it joined the pool). Returns
            False when the budget is exhausted — every remaining
            request is failed with the underlying error instead of
            wedging or crashing the process."""
            self.stats["faults"] += 1
            fault_state["consecutive"] += 1
            fault_state["last_error"] = err = f"{type(e).__name__}: {e}"
            t_fault = time.monotonic()
            instant("fault", error=err)
            # the forensic moment: the ring now holds the event history
            # leading up to this fault (instant("fault") above included)
            flight.dump_on_fault(
                "serve_fault", fault=err,
                consecutive=fault_state["consecutive"],
                recoveries=fault_state["recoveries"])
            if fault_state["recoveries"] >= self.max_recoveries:
                msg = (f"device lost after {fault_state['recoveries']} "
                       f"recovery attempt(s) ({err})")
                for b, slot in enumerate(table):
                    if slot.req_index >= 0:
                        fin(slot.req_index, FAILED, slot.out, msg)
                        free_row(b)
                for i in list(queue):
                    fin(i, FAILED, [], msg)
                queue.clear()
                return False
            fault_state["recoveries"] += 1
            if fault_state["consecutive"] >= 2:
                live = [b for b, s in enumerate(table) if s.req_index >= 0]
                if live:
                    victim = max(live, key=lambda b: table[b].admit_seq)
                    instant("poison_eviction",
                            request=table[victim].req_index, error=err)
                    fin(table[victim].req_index, FAILED,
                        table[victim].out,
                        f"evicted as suspected poison row after "
                        f"repeated faults ({err})")
                    free_row(victim)
                    flight.dump_on_fault("poison_eviction", fault=err)
            for slot in table:
                if slot.req_index >= 0:
                    recs[slot.req_index] += 1
            with span("reconstruct"):
                self._reconstruct(table, requests, fin, free_row)
            self.stats["reconstructions"] += 1
            self.stats["recovery_s"] += time.monotonic() - t_fault
            return True

        def dispatch_or_wait():
            """``dispatch_segment`` across open-loop arrival gaps: when
            nothing is live but the queue holds FUTURE arrivals
            (``Request.arrival_s``), idle to the earliest one in
            bounded naps (cancel/deadline/drain stay responsive via
            ``police``) and admit. The legacy all-at-submission shape
            never waits — every queued request has already arrived —
            and the overlap dispatch never calls this (it must not
            block with a harvest pending). Rows still mid-chunk keep
            prefilling here even when no row can decode (or the drain
            latch is on): each ``chunk_wave`` advances the first
            pending row by at least one block, so the loop always
            terminates — in a finalised row or a drain-deadline
            ``police`` free."""
            while True:
                seg = dispatch_next()
                if seg is not None:
                    return seg
                if any(s.req_index >= 0 and s.pf_known is not None
                       for s in table):
                    chunk_wave()
                    police()
                    continue
                if draining["on"]:
                    return None
                now = time.monotonic()
                future = [arrive_at[i] for i in queue
                          if arrive_at[i] > now]
                if not future:
                    # nothing live, nothing still to arrive: the queue
                    # is empty or holds only never-admissible requests
                    # (skip_fit horizon rejects, reported at exit)
                    return None
                with span("await_arrival"):
                    time.sleep(min(min(future) - now, 0.02))
                police()
                admit_wave()
                chunk_wave()

        # ---- the overlapped loop: dispatch N+1 BEFORE fetching N,
        # every device interaction under the fault/recovery wrap ----
        police()
        admit_wave()
        chunk_wave()
        seg = dispatch_or_wait()
        while seg is not None:
            nxt = None
            try:
                if seg[0] != "spec":
                    # overlap (None: nothing live). Verify steps never
                    # overlap: the next window's drafts depend on THIS
                    # harvest's accepted tokens
                    nxt = dispatch_next()
                harvest(seg, overlapped=nxt is not None)
                fault_state["consecutive"] = 0
            except Exception as e:  # noqa: BLE001 — the fault path:
                # chaos injection, the tick watchdog, or a real XLA
                # runtime error. Degrade per request (reconstruct or
                # fail the affected requests), never per process.
                nxt = None
                if not handle_fault(e):
                    break
            police()
            admit_wave()                   # freed rows -> next wave
            chunk_wave()                   # mid-chunk rows -> next chunk
            if nxt is None:
                nxt = dispatch_or_wait()   # revived by fresh admissions,
                                           # post-reconstruction, or the
                                           # next open-loop arrival
            seg = nxt

        # whatever is still queued can never be admitted: skip_fit's
        # never-fitting requests report their horizon error here
        for i in list(queue):
            if results[i] is None:
                req = requests[i]
                fin(i, FAILED, [],
                    horizon_msg(req) if not self._fits(req) else
                    "not served (scheduler exited with work queued)")
        # slot-accounting invariant: every row must be free at exit —
        # a leak means a cancelled/failed row kept its slot (the tests
        # assert last_slot_leaks == 0)
        leaked = [b for b, s in enumerate(table) if s.req_index >= 0
                  and results[s.req_index] is None]
        self.last_slot_leaks = len(leaked)
        for b in leaked:
            fin(table[b].req_index, FAILED, table[b].out,
                "slot leak (scheduler bug)")
            free_row(b)
        for b, s in enumerate(table):
            if s.req_index >= 0:
                free_row(b)                # finalised elsewhere; release
        # block-accounting invariant (the PR 5 slot-leak discipline
        # extended to blocks): with every row freed, the only live pool
        # references are the radix cache's (and the pinned trash block)
        held = self._radix.held() if self._radix is not None else {}
        self.last_block_leaks = self._pool.leak_check(held)
        # ... and to the HOST pool: every allocated host block must be
        # owned by exactly one demoted entry (the tier analogue)
        if self._tier is not None:
            if self._tier.disk is not None:
                # flush the async spill writer so the part directory is
                # consistent (and CRC-verifiable) when serve() returns
                self._tier.disk.drain()
            self.last_host_block_leaks = self._tier.leak_check()
            self.tier["host_pool_occupancy"] = max(
                self.tier["host_pool_occupancy"],
                self._tier.host.high_water / self._tier.host.num_blocks)
        self.stats["block_pool_occupancy"] = max(
            self.stats["block_pool_occupancy"],
            self._pool.high_water / self._pool.num_blocks)
        for i in range(n):
            if results[i] is None:
                fin(i, FAILED, [], "not served (scheduler bug)")
        if jr is not None:
            jr.commit()    # exit-path terminal frames (drain sheds,
                           # leftover-queue fins) reach the log too
        # a session that saw faults or chaos trips gets a final dump
        # even when every fault was absorbed without raising ("slow"
        # chaos never reaches handle_fault; a recovered session's
        # per-fault dumps would otherwise be the only record)
        if self.stats["faults"] > 0 or (chaos is not None
                                        and chaos.trips > 0):
            flight.dump_on_fault(
                "serve_session_end",
                fault=fault_state.get("last_error"),
                faults=self.stats["faults"],
                chaos_trips=chaos.trips if chaos is not None else 0)
        return results

    # ---- admission / recovery waves ---------------------------------------

    def _wave_window(self, heads: list) -> int:
        """The prefill window (rows x tokens) a wave of rows with these
        head lengths dispatches: what ``_WAVE_TOKENS`` bounds. Counted
        from the ladder's dispatches; a chunked wave is one dispatch of a
        chunk a row, and a row that may attach is counted at the widest
        window it can take."""
        if self._chunk is not None or self._radix is not None:
            return len(heads) * (self._chunk or self._admit_ladder[0][0])
        return sum(w * r for w, r, _ in wave_dispatches(
            heads, self._admit_ladder, self._dp))

    def _prefill_wave(self, entries, window: int | None = None):
        """Prefill ``entries`` ``(row, known_tokens, from_m, upto)``:
        every entry's head tokens ``known[from_m:upto]`` (logical
        positions ``from_m..upto-1``, past its already-resident prefix)
        land from column 0 of a static-shaped batch and are written into
        the row's table-mapped blocks. ``from_m`` is the attached-prefix
        length at admission, or the chunked-prefill progress mark on an
        extension wave — the bottom-right-causal ``kv_prefix`` mask
        makes both the same computation. An entry REACHING its head
        (``upto == head_len``) finalises: the last known token becomes
        the row's current token and the row rewinds to ``head_len -
        1``; a mid-chunk entry leaves the row parked for its next
        extension wave.

        A wave whose windows all start at position 0 (nothing attached,
        no chunking, no ``window`` given) costs what its prompts hold: its
        rows are grouped by the smallest rung of ``admission_ladder`` that
        covers each head and go out as SEVERAL compiled dispatches back to
        back (``wave_dispatches``), each of at most half of ``prompt_buf``
        in window unless it is one full-window row, the last group of a
        rung padded with rows that hold and write nothing. The ladder's shapes are all built at the first
        such wave (``_warm_ladder``), so no later wave meets a new one.

        Otherwise the wave is ONE dispatch of every entry at one
        ``window``: the one given (the chunk, with CHUNKING on; what
        reconstruction needs for a head grown past ``prompt_buf``), else
        the block-rounded longest suffix of a wave that attaches. The
        prefix-gather width ``Lp`` rides the bucket ladder (ISSUE 19):
        the smallest rung covering the wave's longest attached prefix,
        garbage beyond each row's prefix hidden by ``prefix_mask`` — the
        program count stays bounded (one per (window, rung) pair) and a
        short attach stops gathering the horizon. Rows whose head is
        fully cached contribute zero suffix tokens — a wave that is ALL
        attach skips the device prefill entirely (the block lookup IS
        the admission). Pure dispatch — no fetch."""
        suffixes = [upto - m for _, _, m, upto in entries]
        max_m = max(m for _, _, m, _ in entries)
        if window is None and max_m == 0:
            self._warm_ladder()
            groups = [(w, r, [entries[j] for j in take])
                      for w, r, take in wave_dispatches(
                          suffixes, self._admit_ladder, self._dp)]
        else:
            if window is None:
                window = max(self.bt,
                             -(-max(suffixes) // self.bt) * self.bt)
            groups = [(window, -(-len(entries) // self._dp) * self._dp,
                       entries)]
        Lp = 0 if max_m == 0 else self._bucket_width(max_m) * self.bt
        for w, r, group in groups:
            # a row that prefills nothing still takes its slot's leaves
            # over from the former tenant: they have to be written
            if self._slot_state or any(upto > m for _, _, m, upto in group):
                self._dispatch_prefill(group, r, w, Lp)
        if self._blockgen is not None:
            self._open_blocks([(b, known) for b, known, *_ in entries])
            return
        final = [(b, known) for b, known, _m, upto in entries
                 if upto >= len(known) - 1]
        if final:
            # one shape whatever the wave holds: a mask over all slots
            sel = np.zeros((self.B,), bool)
            lasts = np.zeros((self.B,), np.int32)
            n_log = np.zeros((self.B,), np.int32)
            for b, known in final:
                sel[b], lasts[b], n_log[b] = True, known[-1], len(known) - 1
                self._row_pos[b] = len(known) - 2  # head_len - 1
                self._cur_h[b] = known[-1]     # host mirrors (spec path)
                self._nlog_h[b] = len(known) - 1
            with self._mesh_ctx():
                self._cur_tok = jnp.where(sel, lasts, self._cur_tok)
                self._n_logical = jnp.where(sel, n_log, self._n_logical)

    def _open_blocks(self, rows) -> None:
        """Open the first generated block of each admitted row ``(b,
        known)`` (a model that generates by block diffusion; ``known`` the
        prompt, or prompt + delivered on a reconstruction or a replay): the
        tail of ``known`` that fills no block holds the block's first
        positions as known tokens, the rest is masked. The whole blocks
        before it are what the wave prefilled."""
        L = self._blockgen[0]
        sel = np.zeros((self.B,), bool)
        toks = np.zeros((self.B, L), np.int32)
        masked = np.zeros((self.B, L), bool)
        for b, known in rows:
            start = len(known) // L * L
            tail = known[start:]
            sel[b] = True
            toks[b, :len(tail)] = tail
            masked[b, len(tail):] = True
            self._row_pos[b] = start
            self._blk_left[b] = self._blk_gen[b] = L - len(tail)
            self.stats["prompt_tail_tokens"] += len(tail)
        self._blk_tok = jnp.where(sel[:, None], toks, self._blk_tok)
        self._blk_masked = jnp.where(sel[:, None], masked, self._blk_masked)

    def _warm_ladder(self) -> None:
        """Build every shape of the admission ladder, once, before the
        first wave that uses it: each runs as a NULL dispatch (every row
        masked, every write target out of range) through ``_admit_c``, so
        ``jit``'s own cache holds it and no later wave traces, compiles
        or fetches a program, whatever its lengths and row count (an
        ahead-of-time ``lower().compile()`` would not do: the later call
        would trace again and fetch). A batcher that borrowed a warm
        donor's programs only runs them. Blocks that take a STATIC
        ``moe_capacity`` compile a program per capacity as their waves
        come, which no null wave can cover."""
        if self._ladder_warm:
            return
        self._ladder_warm = True
        if self._block_takes_moe_capacity:
            return
        for r, w in ladder_shapes(self._admit_ladder, self._dp):
            self._dispatch_prefill([], r, w, 0)

    def _dispatch_prefill(self, entries, R: int, window: int,
                          Lp: int) -> None:
        """ONE compiled prefill of ``entries`` in a static ``[R, window]``
        batch (``R >= len(entries)``; the rows past them are pads: all
        masked, their write targets OUT OF BOUNDS and so dropped — see
        ``_admit_impl``'s partitioner note)."""
        K = len(entries)
        P_oob = self._pool.num_blocks
        prompt = np.zeros((R, window), np.int32)
        pmask = np.zeros((R, window), np.float32)
        positions = np.tile(np.arange(window, dtype=np.int32), (R, 1))
        prefix_mask = np.zeros((R, Lp), np.float32)
        blk_idx = np.full((R, window), P_oob, np.int32)
        off_idx = np.zeros((R, window), np.int32)
        tables_wave = np.full((R, self.nb), BlockPool.TRASH, np.int32)
        caps = []
        for j, (b, known, m, upto) in enumerate(entries):
            suf = known[m:upto]
            sn = len(suf)
            if sn:
                prompt[j, :sn] = suf
                pmask[j, :sn] = 1.0
            positions[j, :] += m
            if m:
                prefix_mask[j, :m] = 1.0
            tables_wave[j] = self._tables[b]
            logical = m + np.arange(sn)
            blk_idx[j, :sn] = self._tables[b][logical // self.bt]
            off_idx[j, :sn] = logical % self.bt
            if self._block_takes_moe_capacity:
                caps.append(self._block.prefill_capacity(len(known)))
        kw = {}
        if self._slot_state:
            # the slot each wave row fills; pad rows out of range
            kw["ring_rows"] = jnp.asarray(
                [b for b, *_ in entries] + [self.B] * (R - K), jnp.int32)
        if caps:
            kw["moe_capacity"] = max(caps)
            if self._block_takes_moe_capacity_rows:
                kw["moe_capacity_rows"] = jnp.asarray(
                    caps + [1] * (R - K), jnp.int32)
        if self.kv_dtype == "int8" and Lp > 0:
            # attached-prefix gather dequantizes int8 blocks inside
            # the admission forward (see _admit_impl)
            self.kvq["dequant_reads"] += 1
        args = (self.params, self._caches, jnp.asarray(tables_wave),
                jnp.asarray(prompt), jnp.asarray(pmask),
                jnp.asarray(positions), jnp.asarray(prefix_mask),
                jnp.asarray(blk_idx), jnp.asarray(off_idx))
        self._note_program("admit", self._admit_c, args, kw)
        with span("prefill_wave", rows=K, window=window), \
                self._mesh_ctx():
            self._caches = self._admit_c(*args, **kw)
        del args
        if K:
            self.stats["prefill_calls"] += 1
            self.stats["prefill_tokens"] += int(pmask.sum())
            self.stats["prefill_window_tokens"] += R * window

    def _reconstruct(self, table, requests, fin, free_row) -> None:
        """Device-failure session reconstruction: rebuild every live
        row's KV blocks by re-prefilling ``prompt + generated-so-far``
        from HOST-TRACKED state, then resume decode.

        Soundness (DESIGN.md "Serving under failure"): the host knows
        each live row's full token prefix exactly — the prompt plus
        every HARVESTED token — and its true remaining budget.
        Re-prefilling that prefix reproduces the lost K/V (same params;
        logical positions are laid out identically every time), and
        sampling keys depend only on (seed, tokens-so-far) — so the
        resumed stream is TOKEN-IDENTICAL to the uninterrupted one,
        greedy or sampled. The RADIX CACHE is cleared too: its entries
        point into the zeroed pool, so trusting them would attach
        requests to dead K/V. Tokens generated but never harvested died
        with the device buffers and are simply recomputed.

        Rows whose grown prefix no longer fits the per-row horizon
        (window + segment-rounded remaining > t_max) cannot be rebuilt
        and are finalised ``failed`` WITH their partial stream. Rows
        whose prefix still fits ``prompt_buf`` re-prefill as an admission
        wave does, in the ladder's dispatches; longer ones in waves
        grouped by window width, each distinct width compiled once.
        """
        # fresh device + host pool state on the SAME compiled programs:
        # the old buffers are untrusted after a fault. Order matters —
        # the radix releases its refs into the pool before the pool
        # resets, and slots drop their (now-dead) block lists without
        # releasing them twice.
        if self._radix is not None:
            self._radix.clear()
        if self._tier is not None:
            # ALL tiers zero with the device pool: host/disk bytes
            # physically survive a device fault, but the radix that
            # indexes them just died — a stale tier entry promoted
            # after recovery could attach replayed rows to K/V from
            # the pre-fault session
            self._tier.reset()
        for slot in table:
            slot.blocks = []
        self._pool.reset()
        self._tables[:] = BlockPool.TRASH
        self._caches = jax.tree.map(jnp.zeros_like, self._caches)
        self._cur_tok = jnp.zeros_like(self._cur_tok)
        self._n_logical = jnp.zeros_like(self._n_logical)
        self._row_pos = [0] * self.B
        self._zero_blocks()
        waves: dict[int, list] = {}
        for b, slot in enumerate(table):
            if slot.req_index < 0:
                continue
            # a row that was mid-chunk replays its WHOLE head in one
            # wave below (rare path; token-identical either way) — its
            # chunk progress died with the device buffers
            slot.pf_known = None
            slot.pf_done = 0
            req = requests[slot.req_index]
            known = list(req.tokens) + list(slot.out)
            head = len(known) - 1
            # a prefix that still fits the admission window goes out as
            # an admission wave does (no new compile); a longer one takes
            # the next block-aligned width
            W = (self.Tb if head <= self.Tb
                 else -(-head // self.bt) * self.bt)
            remaining = req.max_new - len(slot.out)
            if W + self._rounded_need(remaining) > self.t_max:
                fin(slot.req_index, FAILED, slot.out,
                    f"reconstruction needs window {W} + "
                    f"{self._rounded_need(remaining)} decode slots > "
                    f"t_max={self.t_max} (raise t_max for "
                    f"fault-tolerance headroom)")
                free_row(b)
                continue
            waves.setdefault(W, []).append((b, slot, known, remaining))
        for W, rows in sorted(waves.items()):
            for b, slot, known, remaining in rows:
                # the radix was cleared, so these allocations are always
                # fresh blocks (m == 0) — replay never trusts dead K/V
                self._assign_blocks(b, slot, known, remaining)
            # (a model that generates by blocks prefills whole blocks)
            L = self._blockgen[0] if self._blockgen is not None else 0
            self._prefill_wave(
                [(b, known, 0, len(known) // L * L if L else len(known) - 1)
                 for b, _, known, _ in rows],
                None if W == self.Tb else W)
            for b, slot, known, remaining in rows:
                # host-known truth: the in-flight plan's budget
                # decrement died with the old buffers
                slot.remaining = remaining
            self.stats["reconstruction_rows"] += len(rows)
