"""The paged decode read (``dcp_paged_decode_attn``,
ops/pallas/decode_attention.py::paged_decode_attention_pallas): the pool
read in place through the block table must give what the dense cached
attention gives over the gathered logical view, at every position a
serving row can sit at; and the dispatcher
(``ops/attention.py::paged_read_path``) must take it exactly where the
operands allow, nowhere else.

On CPU the kernel runs in the Pallas interpreter (which models the async
copies and their semaphores in this jax); the Mosaic-compiled kernel at
the benchmark cell's shape runs under ``DCP_TEST_TPU=1`` on the chip,
like tests/test_flash_tpu.py."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.core.mesh import make_mesh, use_mesh
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.pallas.decode_attention import (
    _CHUNK_TOKENS, _SCRATCH_BYTES, _chunk_blocks,
    paged_decode_attention_pallas)

_TPU = os.environ.get("DCP_TEST_TPU") == "1"
on_tpu = pytest.mark.skipif(
    not _TPU, reason="TPU-only (set DCP_TEST_TPU=1 on hardware)")
# the tier-1 half: the interpreter, and what the rule says on a CPU backend
on_cpu = pytest.mark.skipif(
    _TPU, reason="CPU tier (the faked 8-device mesh, the interpreter)")

BT = 8
PARKED = -1          # a case's position for a row parked on the trash block


def _paged_case(positions, *, hk, G, hd, nb, dtype, seed):
    """Rows at ``positions`` (``PARKED`` = an all-trash table at position
    0) over ONE pool: block 0 is the trash block, every row's live
    blocks are drawn without replacement from a shuffled pool, table
    entries past a row's live extent point at trash."""
    rng = np.random.default_rng(seed)
    live = [0 if p == PARKED else p // BT + 1 for p in positions]
    n_blocks = 1 + sum(live)
    pool = jnp.asarray(rng.standard_normal((2, n_blocks, hk, BT, hd)), dtype)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    table = np.zeros((len(positions), nb), np.int32)
    for b, n in enumerate(live):
        table[b, :n] = [free.pop() for _ in range(n)]
    q = jnp.asarray(rng.standard_normal((len(positions), hk * G, 1, hd)),
                    dtype)
    pos = jnp.asarray([max(p, 0) for p in positions], jnp.int32)
    return q, pool, jnp.asarray(table), pos


def _assert_live_match_and_parked_zero(got, want, parked, tol):
    """Live rows give what the gathered reference gives; a parked row's
    output is exactly zero (the kernel attends nothing for it)."""
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    parked = np.asarray(parked, bool)
    np.testing.assert_allclose(got[~parked], want[~parked],
                               atol=tol, rtol=tol)
    assert not got[parked].any()


def _check(positions, *, hk, G, hd=128, nb, dtype, tol, interpret, seed=0):
    q, pool, table, pos = _paged_case(positions, hk=hk, G=G, hd=hd, nb=nb,
                                      dtype=dtype, seed=seed)
    view = A.gather_kv_blocks(pool, table)
    want = A.cached_attention(q, view[0], view[1], pos)
    got = jax.jit(lambda *a: paged_decode_attention_pallas(
        *a, interpret=interpret))(q, pool, table, pos)
    _assert_live_match_and_parked_zero(
        got, want, [p == PARKED for p in positions], tol)


# positions a serving row can sit at: the first slot, a block's last and
# the next block's first two, one short of / at / past a chunk edge, a
# full prompt window, the horizon's last slot
EDGE = _CHUNK_TOKENS - 1        # 511: the last slot of a DMA chunk
POSITION_SETS = {
    "block_edges": [0, 7, 8, 9],
    "chunk_edges": [EDGE - 1, EDGE, EDGE + 1, 2 * _CHUNK_TOKENS],
    "long_rows": [2047, 2559],
    "mixed_with_parked": [9, PARKED, 2047, 0, EDGE + 1, PARKED],
    "all_parked": [PARKED, PARKED],
}


@on_cpu
@pytest.mark.parametrize("name", list(POSITION_SETS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_interpreted_kernel_matches_gathered_attention(name, dtype):
    """Tier-1: the kernel's table walk, guarded copies, cross-row
    prefetch, masks and online softmax, in the Pallas interpreter at a
    narrow shape (2 KV heads of 4 query rows)."""
    tol = 2e-5 if dtype == "float32" else 2e-2
    _check(POSITION_SETS[name], hk=2, G=4, nb=320, dtype=getattr(jnp, dtype),
           tol=tol, interpret=True)


# live (a position, some past a chunk's edge so that the prefetch is
# handed over a parked run on both of its sides) and PARKED rows
PARKED_PATTERNS = {
    "first_parked": [PARKED, PARKED, EDGE + 9, 3],
    "last_parked": [2 * _CHUNK_TOKENS, 40, PARKED],
    "alternating": [PARKED, EDGE + 1, PARKED, 700, PARKED, 5],
    "one_live_row_in_the_middle": [PARKED, PARKED, 1100, PARKED, PARKED],
    "long_rows_round_a_parked_run": [1300, PARKED, PARKED, PARKED, 1030],
    "all_parked": [PARKED, PARKED, PARKED],
    "none_parked": [600, 0, EDGE, 1025],
}


def _park(table, parked):
    """``table`` with the ``parked`` rows swapped for the all-trash row, as
    ``serve.py``'s dispatch hands rows out of the plan to the device."""
    return jnp.where(jnp.asarray(parked)[:, None], 0, table)


@on_cpu
@pytest.mark.parametrize("name", list(PARKED_PATTERNS))
def test_interpreted_kernel_skips_parked_rows(name):
    """Whatever rows are parked, and wherever: a live row's output is BIT
    FOR BIT the one the kernel gives with every row live (same chunks,
    same order of products; the stream's hand-over and the buffer parity
    change nothing a row can see), it matches the gathered reference, and
    a parked row's output is exactly zero."""
    pattern = PARKED_PATTERNS[name]
    parked = [p == PARKED for p in pattern]
    # every row live (a parked one at some short position of its own) ...
    q, pool, table, pos = _paged_case(
        [17 * b + 1 if p == PARKED else p for b, p in enumerate(pattern)],
        hk=2, G=4, hd=128, nb=320, dtype=jnp.float32, seed=7)
    run = jax.jit(lambda *a: paged_decode_attention_pallas(
        *a, interpret=True))
    every_row_live = np.asarray(run(q, pool, table, pos))
    # ... then the pattern's rows handed the all-trash table
    got = run(q, pool, _park(table, parked), pos)
    view = A.gather_kv_blocks(pool, table)
    _assert_live_match_and_parked_zero(
        got, A.cached_attention(q, view[0], view[1], pos), parked, 2e-5)
    live = ~np.asarray(parked)
    np.testing.assert_array_equal(np.asarray(got)[live], every_row_live[live])


@on_cpu
def test_interpreted_kernel_narrow_table_and_mha():
    """A table slice narrower than one chunk (a low width rung: the
    kernel must never read the table past it), one query row per KV
    head, heads of 256 lanes, and a position beyond the shipped table
    (clamped, as the gather path's write clamps)."""
    _check([0, 15, 23], hk=3, G=1, hd=256, nb=3, dtype=jnp.float32,
           tol=2e-5, interpret=True)
    q, pool, table, pos = _paged_case([15, 9], hk=2, G=2, hd=128, nb=2,
                                      dtype=jnp.float32, seed=1)
    got = paged_decode_attention_pallas(q, pool, table,
                                        jnp.asarray([400, 9], jnp.int32),
                                        interpret=True)
    view = A.gather_kv_blocks(pool, table)
    want = A.cached_attention(q, view[0], view[1],
                              jnp.asarray([15, 9], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# name: (KV heads, head width, dtype, table width) -> blocks to a chunk
CHUNKS = {
    "mistral_bf16": ((8, 128, "bfloat16", 320), 64),       # 512 tokens
    "llama2_7b_mha_bf16": ((32, 128, "bfloat16", 320), 16),
    "f32_pool_hd256": ((8, 256, "float32", 320), 16),
    "mha_f32": ((32, 128, "float32", 320), 8),
    "low_rung": ((8, 128, "bfloat16", 4), 4),
    "one_block_a_buffer": ((128, 256, "float32", 320), 1),
    "not_the_kernels": ((256, 256, "float32", 320), 0),
}


@on_cpu
@pytest.mark.parametrize("name", list(CHUNKS))
def test_chunk_follows_the_scratch_budget(name):
    """The kernel's two VMEM buffers stay inside ``_SCRATCH_BYTES`` (a
    quarter of what a v5e kernel may use unasked) whatever the pool's
    heads and dtype; Mistral's shape keeps the measured 512 tokens."""
    (hk, hd, dtype, nb_w), want = CHUNKS[name]
    itemsize = jnp.dtype(dtype).itemsize
    C = _chunk_blocks((2, 9, hk, BT, hd), itemsize, nb_w)
    assert C == want
    assert 2 * C * 2 * hk * BT * hd * itemsize <= _SCRATCH_BYTES


@on_cpu
def test_interpreted_kernel_budget_bound_chunk():
    """32 KV heads in f32: the byte budget, not the token count, sets the
    chunk (8 blocks: 64 tokens); rows short of, at and past its edges."""
    assert _chunk_blocks((2, 9, 32, BT, 128), 4, 40) == 8
    _check([62, 63, 64, 65, 300, PARKED], hk=32, G=1, nb=40,
           dtype=jnp.float32, tol=2e-5, interpret=True)


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A DESCRIBED v5e chip: the TPU's compiler is installed here and
    compiles for a chip that is not attached. Made inside the fixture,
    never at import (one process at a time may load the TPU's library)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@on_cpu
@pytest.mark.parametrize("name", [n for n, ((_, _, _, nb), c) in CHUNKS.items() if c and nb == 320])
def test_kernel_compiles_for_a_described_v5e(name, one_v5e_chip):
    """Mosaic takes the kernel at real widths, 32 rows over tables of 320
    blocks: the scratch fits the default VMEM at every eligible shape (a
    pool that does not compile has no second path to fall to)."""
    (hk, hd, dtype, nb_w), _ = CHUNKS[name]
    G = 1 if hk >= 32 else 4

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    compiled = jax.jit(paged_decode_attention_pallas).lower(
        arg((32, hk * G, 1, hd), dtype), arg((2, 64, hk, BT, hd), dtype),
        arg((32, nb_w), jnp.int32), arg((32,), jnp.int32)).compile()
    assert "dcp_paged_decode_attn" in compiled.as_text()


@on_cpu
def test_latent_kernel_compiles_for_a_described_v5e(one_v5e_chip):
    """Mosaic takes ``dcp_paged_latent_decode_attn`` at the long-document
    cell's shape: 64 rows of 32 heads over vectors of 640 lanes (512 of
    them the value), blocks of 32 tokens, tables of 608 blocks."""
    from distributed_compute_pytorch_tpu.ops.pallas.decode_attention import (
        paged_latent_decode_attention_pallas)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    compiled = jax.jit(
        paged_latent_decode_attention_pallas,
        static_argnames=("v_width", "scale")).lower(
        arg((64, 32, 640), jnp.bfloat16),
        arg((1, 64, 1, 32, 640), jnp.bfloat16),
        arg((64, 608), jnp.int32), arg((64,), jnp.int32),
        v_width=512, scale=0.07).compile()
    assert "dcp_paged_latent_decode_attn" in compiled.as_text()


@on_cpu
@pytest.mark.parametrize("rows,window,lower_bound", [
    (1, 16384, -5.0), (4, 2048, -5.0), (1, 2048, None), (4, 256, None)])
def test_kda_scan_kernel_compiles_for_a_described_v5e(
        rows, window, lower_bound, one_v5e_chip, monkeypatch):
    """Mosaic takes ``dcp_kda_chunk_scan`` (``ops/pallas/kda_scan.py``) at
    the long-context cell's shapes, the longest and the widest program of
    its admission ladder, and, in the form for a gate with no floor
    (``lower_bound`` None), at the long-generation cell's: 64 heads of 128
    in bf16, a gate of rank 128. (In
    this file because one process at a time may load the TPU's library:
    the fixture is this module's. Interpret mode passes forms that Mosaic
    aborts on: PERF.md section 7, "After PR 43" (4).)"""
    from distributed_compute_pytorch_tpu.ops.pallas import kda_scan
    monkeypatch.setattr(kda_scan, "_use_interpret", lambda: False)
    # the choice is read while tracing: no trace of the other kind, before
    # or after
    kda_scan.kda_chunk_scan.clear_cache()

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    wide = 64 * 128
    compiled = kda_scan.kda_chunk_scan.lower(
        arg((rows, window, wide)), arg((rows, window, wide)),
        arg((rows, window, wide)), arg((rows, window, 128)),
        arg((128, wide)), arg((wide,), jnp.float32), arg((64,), jnp.float32),
        arg((rows, window, 64), jnp.float32),
        arg((rows, window), jnp.float32),
        lower_bound=lower_bound, chunk=64, sub=16).compile()
    kda_scan.kda_chunk_scan.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert "dcp_kda_chunk_scan" in compiled.as_text()


@on_cpu
@pytest.mark.parametrize("rows", [160, 32])
def test_kda_step_kernel_compiles_for_a_described_v5e(
        rows, one_v5e_chip, monkeypatch):
    """Mosaic takes ``dcp_kda_step`` (``ops/pallas/kda_step.py``) at the
    long-generation cell's shape and the long-context cell's: 160 and 32
    slots of 64 heads of 128 x 128 float32, the vectors turned inside the
    kernel. (In this file for the fixture's sake, as the test above.)"""
    from distributed_compute_pytorch_tpu.ops.pallas import kda_step
    monkeypatch.setattr(kda_step, "_use_interpret", lambda: False)
    kda_step.kda_step_rows.clear_cache()

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_v5e_chip)
    vec = arg(rows, 64, 128)
    compiled = kda_step.kda_step_rows.lower(
        arg(rows, 64, 128, 128), vec, vec, vec, vec, arg(rows, 64),
        arg(rows)).compile()
    kda_step.kda_step_rows.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert "dcp_kda_step" in compiled.as_text()


@on_cpu
@pytest.mark.parametrize("n,d,f,rows,k,limit", [
    (36, 4096, 2048, 32, 8, 10.0), (16, 2048, 2048, 20, 1, 0.0),
    (32, 2048, 768, 64, 8, 0.0)],
    ids=["glm53flash", "zaya1", "joyai"])
def test_held_experts_kernel_compiles_for_a_described_v5e(
        n, d, f, rows, k, limit, one_v5e_chip, monkeypatch):
    """Mosaic takes ``dcp_held_experts`` (``ops/pallas/held_experts.py``) at
    the ticks of the three cells whose rows choose few enough of their held
    experts: bfloat16 blocks of 2 MB (1.5 where ``f`` is 768) in two buffers
    each beside the rows and the float32 result, inside the VMEM the call
    asks for. (In this file for the fixture's sake, as the tests above.)"""
    from distributed_compute_pytorch_tpu.ops.pallas import held_experts
    monkeypatch.setattr(held_experts, "_use_interpret", lambda: False)
    held_experts.held_experts_chosen.clear_cache()

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    compiled = held_experts.held_experts_chosen.lower(
        arg((n, d, f)), arg((n, d, f)), arg((n, f, d)), arg((rows, d)),
        arg((rows, k), jnp.int32), arg((rows, k), jnp.float32),
        swiglu_limit=limit).compile()
    held_experts.held_experts_chosen.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert "dcp_held_experts" in compiled.as_text()


@on_cpu
def test_block_pass_kernels_compile_for_a_described_v5e(one_v5e_chip):
    """Mosaic takes what a block-diffusion pass hands the pool at SDAR's
    widths (96 rows, 32 query heads on 4 KV heads of 128, tables of 336
    blocks of 8): ``dcp_paged_decode_attn`` with a block's 4 positions
    beside the head group (32 query rows to a KV head) and
    ``dcp_kv_pool_write_span`` (4 slots a row in one window). (In this file
    for the fixture's sake, as the tests above.)"""
    from distributed_compute_pytorch_tpu.ops.pallas import cache_update

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    pool = arg((2, 64, 4, BT, 128))
    read = jax.jit(paged_decode_attention_pallas).lower(
        arg((96, 32, 4, 128)), pool, arg((96, 336), jnp.int32),
        arg((96,), jnp.int32)).compile()
    assert "dcp_paged_decode_attn" in read.as_text()
    write = jax.jit(cache_update.kv_pool_insert_span_pallas).lower(
        {"kv": pool}, {"kv": arg((2, 96, 4, 4, 128))},
        arg((96,), jnp.int32), arg((96,), jnp.int32)).compile()
    assert "dcp_kv_pool_write_span" in write.as_text()


@on_cpu
@pytest.mark.parametrize("t,masked", [(1536, True), (192, False)])
def test_block_masked_flash_forward_compiles_for_a_described_v5e(
        t, masked, one_v5e_chip, monkeypatch):
    """Mosaic takes the flash forward under the block mask (the columns'
    low bits cleared on the diagonal tiles) at the admission ladder's widest
    and narrowest windows, with and without a pad mask."""
    import importlib
    flash_attention = importlib.import_module(
        "distributed_compute_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_v5e_chip)
    block = 512 if t % 512 == 0 else 128
    fn = lambda q, k, v, *m: flash_attention.flash_attention(
        q, k, v, causal=True, kv_mask=m[0] if m else None, block_q=block,
        block_k=block, mask_block=4)
    qkv = [arg((1, 32, t, 128))] * 3
    compiled = jax.jit(fn).lower(
        *qkv, *([arg((1, t), jnp.float32)] if masked else [])).compile()
    assert "dcp_flash_fwd" in compiled.as_text()


@on_tpu
@pytest.mark.parametrize("shape", ["llama2_7b_mha_bf16", "f32_pool_hd256"])
def test_compiled_kernel_at_budget_bound_chunks(shape):
    """Chip: pools whose 512-token chunk would not fit the default VMEM
    (Llama-2-7B's 32 KV heads; an f32 pool of 8 x 256) run 128-token
    chunks: block, chunk and window edges, a parked row."""
    (hk, hd, dtype, nb_w), C = CHUNKS[shape]
    assert C * BT == 128
    f32 = dtype == "float32"
    _check([0, 9, 126, 127, 128, 129, 2047, PARKED, 2559], hk=hk,
           G=4 if f32 else 1, hd=hd, nb=nb_w, dtype=getattr(jnp, dtype),
           tol=2e-2, interpret=False)


@on_tpu
@pytest.mark.parametrize("name", list(POSITION_SETS))
def test_compiled_kernel_matches_gathered_attention_at_cell_shape(name):
    """Chip: the Mosaic kernel at the benchmark cell's shape (Mistral-7B:
    8 KV heads x 4 query rows x 128, blocks of 8 tokens, bf16, tables of
    320 blocks)."""
    _check(POSITION_SETS[name], hk=8, G=4, nb=320, dtype=jnp.bfloat16,
           tol=2e-2, interpret=False)


@on_tpu
def test_compiled_kernel_float32_pool_and_low_rung():
    _check([0, 9, 300, PARKED], hk=2, G=2, nb=64, dtype=jnp.float32,
           tol=2e-2, interpret=False)
    _check([0, 7, 15], hk=8, G=4, nb=2, dtype=jnp.bfloat16, tol=2e-2,
           interpret=False)


@on_tpu
def test_compiled_tick_takes_the_kernel_and_matches_the_gather():
    """The dispatcher on the chip: ``cache_write_and_attend`` over an
    eligible pool writes then reads through the kernel, and gives what
    the gather path gives for the same operands."""
    q, pool, table, pos = _paged_case([9, 300, PARKED, 2047], hk=8, G=4,
                                      hd=128, nb=320, dtype=jnp.bfloat16,
                                      seed=3)
    rng = np.random.default_rng(4)
    k, v = (jnp.asarray(rng.standard_normal((4, 8, 1, 128)), jnp.bfloat16)
            for _ in range(2))
    cache = {"kv": pool, "table": table}
    assert A.paged_read_path({"kv": pool}, 1) == "kernel"
    got, new = jax.jit(A.cache_write_and_attend)(q, k, v, cache, pos)
    blk = jnp.take_along_axis(table, (pos // BT)[:, None], axis=1)[:, 0]
    want_pool = pool.at[:, blk, :, pos % BT].set(
        jnp.moveaxis(jnp.stack([k, v])[:, :, :, 0], 1, 0))
    # rows 0, 1, 3 own their written block; the parked row wrote trash
    np.testing.assert_array_equal(np.asarray(new["kv"][:, 1:], np.float32),
                                  np.asarray(want_pool[:, 1:], np.float32))
    view = A.gather_kv_blocks(new["kv"], table)
    want = A.cached_attention(q, view[0], view[1], pos)
    _assert_live_match_and_parked_zero(got, want, [0, 0, 1, 0], 2e-2)


# ------------------------------------------------------ the eligibility rule

def _pool(hd=128, dtype=jnp.bfloat16, scale=False, bt=BT, hk=2):
    if scale:
        return {"kv": jnp.zeros((2, 3, hk, 32, hd), jnp.int8),
                "scale": jnp.zeros((2, 3, hk, 32, 1), jnp.float32)}
    return {"kv": jnp.zeros((2, 3, hk, bt, hd), dtype)}


ELIGIBILITY = {
    # name: (backend, mesh, pool kwargs, q_len, slot_mask?, path)
    "tpu_bf16_hd128": ("tpu", False, {}, 1, False, "kernel"),
    "tpu_f32_hd256": ("tpu", False, {"hd": 256, "dtype": jnp.float32}, 1,
                      False, "kernel"),
    "cpu_backend": ("cpu", False, {}, 1, False, "gather"),
    "under_a_mesh": ("tpu", True, {}, 1, False, "gather"),
    "int8_pool_scale_leaf": ("tpu", False, {"scale": True}, 1, False,
                             "gather"),
    "verify_window_q_len_4": ("tpu", False, {}, 4, False, "gather"),
    "slot_mask_given": ("tpu", False, {}, 1, True, "gather"),
    "hd64_gpt2": ("tpu", False, {"hd": 64}, 1, False, "gather"),
    "hd192_not_whole_tiles": ("tpu", False, {"hd": 192}, 1, False, "gather"),
    "block_not_window_aligned": ("tpu", False, {"bt": 4}, 1, False,
                                 "gather"),
    "block_past_the_vmem_scratch": ("tpu", False, {
        "hd": 256, "dtype": jnp.float32, "hk": 256}, 1, False, "gather"),
}


@on_cpu
@pytest.mark.parametrize("name", list(ELIGIBILITY))
def test_paged_read_path_is_decided_from_the_operands(name, monkeypatch,
                                                      devices8):
    """One rule, no flag: backend, mesh, pool leaves, query length, slot
    mask and head width decide; each case names the path it takes."""
    backend, mesh, pool_kw, q_len, masked, path = ELIGIBILITY[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    pool = _pool(**pool_kw)
    slot_mask = jnp.ones((1, 16), bool) if masked else None
    if mesh:
        with use_mesh(make_mesh("data=2", devices8[:2])):
            assert A.paged_read_path(pool, q_len, slot_mask) == path
    else:
        assert A.paged_read_path(pool, q_len, slot_mask) == path


@on_cpu
@pytest.mark.parametrize("path", ["kernel", "gather"])
def test_tick_dispatches_on_paged_read_path(path, monkeypatch):
    """``cache_write_and_attend`` follows the rule: with it forced either
    way (the kernel interpreted here) the same tick gives the same pool
    and the same attention output for every live row."""
    q, pool, table, pos = _paged_case([9, PARKED, 300], hk=2, G=4, hd=128,
                                      nb=64, dtype=jnp.float32, seed=5)
    rng = np.random.default_rng(6)
    k, v = (jnp.asarray(rng.standard_normal((3, 2, 1, 128)), jnp.float32)
            for _ in range(2))
    from distributed_compute_pytorch_tpu.ops.pallas import decode_attention
    called = []
    real = decode_attention.paged_decode_attention_pallas

    def interpreted(*a, **kw):
        called.append(1)
        return real(*a, **kw, interpret=True)
    monkeypatch.setattr(decode_attention, "paged_decode_attention_pallas",
                        interpreted)
    base, base_pool = A.cache_write_and_attend(
        q, k, v, {"kv": pool, "table": table}, pos)
    assert not called                      # CPU: the gather, as before
    monkeypatch.setattr(A, "paged_read_path", lambda *a, **kw: path)
    got, got_pool = A.cache_write_and_attend(
        q, k, v, {"kv": pool, "table": table}, pos)
    assert bool(called) == (path == "kernel")
    np.testing.assert_array_equal(np.asarray(got_pool["kv"]),
                                  np.asarray(base_pool["kv"]))
    if path == "kernel":        # the kernel attends nothing for a parked row
        _assert_live_match_and_parked_zero(got, base, [0, 1, 0], 2e-5)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@on_cpu
@pytest.mark.parametrize("family", ["gpt2_hd16", "llama_hd128"])
def test_stats_snapshot_names_the_read_path(family):
    """An operator sees the compiled tick's read path without a profile;
    on CPU it is the gather whatever the head width."""
    from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
    from distributed_compute_pytorch_tpu.models.llama import (
        LlamaConfig, LlamaLM)
    from distributed_compute_pytorch_tpu.serve import (
        ContinuousBatcher, Request)
    if family == "gpt2_hd16":
        model = GPT2(GPT2Config.tiny())
    else:
        model = LlamaLM(dataclasses.replace(
            LlamaConfig.tiny(), d_model=256, num_heads=2, num_kv_heads=1))
        assert model.config.head_dim == 128
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=8,
                           segment=4)
    assert cb.stats_snapshot()["paged_read"] == "gather"
    outs = cb.serve([Request(tokens=[3, 5, 7], max_new=4)])
    assert len(outs[0]) == 4
    assert cb.stats_snapshot()["paged_read"] == "gather"
