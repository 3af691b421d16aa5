"""Hierarchical KV (kv_tier.py + the serve-engine wiring): the
host-RAM/disk spill tier under the radix prefix cache. Pins the
subsystem's whole contract: demote-on-evict captures exactly the bytes
leaving the device, promote-on-match restores them bit-for-bit into
ANY free device blocks (logical positions make demoted prefixes
position-portable), disk parts are CRC-verified with corruption
degrading to a cache miss, and — the acceptance bar — spill-on serving
is token-identical to spill-off for greedy AND sampled rows, under a
mesh, and across a reconstruction fault, with zero block leaks in the
device AND host pools.

Kept CPU-cheap (tier-1 budget note in ROADMAP): tiny models, tiny
pools (the deliberately starved 8-block device pool is what forces
demotions), and batchers sharing compiled programs via the per-config
program cache."""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.kv_pool import (
    TIER_DEVICE, TIER_DISK, TIER_HOST)
from distributed_compute_pytorch_tpu.kv_tier import (
    DiskTier, HostBlockPool, host_blocks_for_mb)
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request)
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector


# --------------------------------------------------- unit: the tiers


def test_host_pool_roundtrip_and_reset():
    """Write/read through the host pool is bit-exact, the free list
    balances, and reset() zeroes the backing slabs (reconstruction
    zeroes ALL tiers)."""
    pool = HostBlockPool(4, n_layers=2, hk=2, bt=4, hd=8,
                         dtype=np.float32)
    rng = np.random.default_rng(0)
    content = rng.standard_normal((2, 2, 2, 2, 4, 8)).astype(np.float32)
    blocks = pool.alloc(2)
    pool.write(blocks, content)
    assert pool.allocated == 2 and pool.high_water == 2
    got = pool.read(blocks)
    np.testing.assert_array_equal(got, content)
    pool.release(blocks)
    assert pool.free_count == 4 and pool.high_water == 2
    more = pool.alloc(2)
    pool.write(more, content)
    pool.reset()
    assert pool.free_count == 4
    assert all(not d.any() for d in pool.data)


def test_disk_tier_crc_roundtrip_and_corruption(tmp_path):
    """put/get round-trips bit-exact through the v2-style part files;
    flipped bytes (or a truncated part) come back as (None, corrupt) —
    never an exception; drop removes both files."""
    disk = DiskTier(str(tmp_path))
    rng = np.random.default_rng(1)
    content = rng.standard_normal((2, 2, 3, 2, 4, 8)).astype(np.float32)
    key = disk.put(content)
    assert os.path.exists(tmp_path / f"{key}.npz")
    assert os.path.exists(tmp_path / f"{key}.json")
    got, corrupt = disk.get(key)
    assert not corrupt
    np.testing.assert_array_equal(got, content)
    # corrupt the payload mid-file: CRC catches it, caller sees a miss
    path = tmp_path / f"{key}.npz"
    raw = bytearray(path.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    path.write_bytes(bytes(raw))
    got, corrupt = disk.get(key)
    assert got is None and corrupt
    # unknown key is a plain miss, not corruption
    assert disk.get("part-99999") == (None, False)
    disk.drop(key)
    assert not list(tmp_path.glob(f"{key}.*"))


def test_host_blocks_for_mb_sizing():
    """The --host_cache_mb budget → block count math: floors to whole
    blocks, never below one."""
    # one block = 2 * 2 layers * 2 hk * 4 bt * 8 hd * 4 B = 1024 B
    assert host_blocks_for_mb(1, 2, 2, 4, 8, 4) == 1024
    assert host_blocks_for_mb(0.001, 2, 2, 4, 8, 4) == 1   # never zero
    assert host_blocks_for_mb(2, 2, 2, 4, 8, 4) == 2048


# ------------------------------------------ serve-engine integration
#
# The starvation recipe every integration test shares: bt=8, t_max=32
# -> 4 blocks per row; pool_blocks=8 -> 7 usable, so two cached
# 17-token heads (3 blocks each) + one live row can never coexist and
# the LRU head demotes on the next admission. slots=1 serialises
# admissions, making the evict/promote order deterministic.


_COMMON = dict(slots=1, t_max=32, prompt_buf=24, segment=4,
               prefix_cache=True, pool_blocks=8)


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return model, params


def _hot(rng, n=3, ln=17):
    """n hot prefixes, each ending mid-block so COW attaches run."""
    return [[int(t) for t in rng.integers(0, 256, ln)] for _ in range(n)]


def _reqs(heads, seed=1, sampled=()):
    """One request per head: the hot prefix plus a 2-token random tail;
    indices in ``sampled`` become temperature>0 rows."""
    r = np.random.default_rng(seed)
    out = []
    for i, h in enumerate(heads):
        req = Request(h + [int(t) for t in r.integers(0, 256, 2)], 6)
        if i in sampled:
            req.temperature = 0.8
            req.seed = 900 + i
        out.append(req)
    return out


def test_tier_parity_greedy_and_sampled_gpt2(gpt2):
    """THE acceptance pin: spill-on serving is token-identical to
    spill-off for greedy AND sampled rows. The stream's hot set (A, B)
    exceeds the starved device pool, so tier-off re-prefills the
    round-robin rehits while tier-on demotes and promotes — and the
    promotion must change only where K/V bytes come from, never a
    logical position, so the (seed, tokens-so-far) sampling key
    schedule is untouched."""
    model, params = gpt2
    rng = np.random.default_rng(5)
    A, B = _hot(rng, 2)
    waves = [((A,), 1, ()), ((B,), 2, ()), ((A, A), 3, (1,)),
             ((B, B), 4, (0,))]
    off = ContinuousBatcher(model, params, **_COMMON)
    want = [off.serve(_reqs([*h], seed=s, sampled=sm))
            for h, s, sm in waves]
    on = ContinuousBatcher(model, params, **_COMMON,
                           host_cache_blocks=64)
    got = [on.serve(_reqs([*h], seed=s, sampled=sm))
           for h, s, sm in waves]
    assert got == want
    t = dict(on.tier)
    assert t["demotions"] >= 1 and t["promotions"] >= 1
    assert t["host_hits"] >= 1
    assert t["bytes_d2h"] > 0 and t["bytes_h2d"] > 0
    assert 0 < t["host_pool_occupancy"] <= 1
    # tier-off pays prefill the tier-on run saved
    assert on.stats["prefix_hits"] > off.stats["prefix_hits"]
    assert on.last_block_leaks == 0 and on.last_slot_leaks == 0
    assert on.last_host_block_leaks == 0
    # the counters ride the public snapshot
    snap = on.stats_snapshot()
    assert snap["tier"]["promotions"] == t["promotions"]
    assert snap["host_block_leaks"] == 0


def test_tier_parity_llama(gpt2):
    """Second model family (RoPE/GQA): promotion restores K/V whose
    rotary phases were baked at prefill — logical positions make the
    bytes portable across device blocks, so parity must hold
    unchanged."""
    del gpt2
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    A, B = _hot(rng, 2)
    stream = [(A,), (B,), (A,), (B,)]
    off = ContinuousBatcher(model, params, **_COMMON)
    want = [off.serve(_reqs([*h], seed=i)) for i, h in enumerate(stream)]
    on = ContinuousBatcher(model, params, **_COMMON,
                           host_cache_blocks=64)
    got = [on.serve(_reqs([*h], seed=i)) for i, h in enumerate(stream)]
    assert got == want
    assert on.tier["promotions"] >= 1
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0


def test_demote_promote_block_bit_exact(gpt2):
    """White-box round-trip: snapshot the device bytes of a cached
    head, force it through demote (D2H) and promote (H2D into
    DIFFERENT device blocks), and require the restored bytes equal the
    originals bit for bit — the position-portability claim at block
    granularity, not just via token parity."""
    model, params = gpt2
    rng = np.random.default_rng(11)
    A, B, C = _hot(rng, 3)
    on = ContinuousBatcher(model, params, **_COMMON,
                           host_cache_blocks=64)
    on.serve(_reqs([A], seed=1))
    (entry,) = on._radix.entries
    before_blocks = list(entry.blocks)
    before = [np.asarray(c["kv"][:, before_blocks]) for c in on._caches]
    # pressure from B and C demotes A (the LRU head)
    on.serve(_reqs([B], seed=2))
    on.serve(_reqs([C], seed=3))
    assert entry.tier == TIER_HOST and entry.blocks == []
    # the router's affinity probe still counts the demoted prefix as
    # warm (promotion beats re-prefilling on a cold replica)
    assert on.prefix_match_len(A + [1, 2]) == len(A)
    # the rehit promotes A into whatever blocks are free now
    on.serve(_reqs([A], seed=4))
    assert entry.tier == TIER_DEVICE
    after = [np.asarray(c["kv"][:, entry.blocks]) for c in on._caches]
    for li, (b, a) in enumerate(zip(before, after)):
        np.testing.assert_array_equal(b, a, err_msg=f"layer {li}")
    assert on.tier["promotions"] >= 1
    assert on.last_host_block_leaks == 0


def test_mesh_sharded_promotion_parity(devices8, gpt2):
    """Under a data-sharded mesh the device pool is block-axis sharded;
    promotion must constrain the replicated host payload back into
    that sharding (the same redistribution move admission-prefill K/V
    uses) and stay token-identical to the unsharded-tier-off truth."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model, params = gpt2
    mesh = make_mesh("data=2", devices=devices8[:2])
    sparams = shard_pytree(params, pick_strategy(mesh, model), mesh)
    rng = np.random.default_rng(13)
    A, B, C = _hot(rng, 3)
    # slots must divide the batch axes; pool sized so the third head's
    # admission is what forces the first demotion
    common = dict(slots=2, t_max=32, prompt_buf=24, segment=4,
                  prefix_cache=True, pool_blocks=10, mesh=mesh)
    off = ContinuousBatcher(model, sparams, **common)
    want = [off.serve(_reqs([h], seed=i))
            for i, h in enumerate((A, B, C, A))]
    on = ContinuousBatcher(model, sparams, **common,
                           host_cache_blocks=16)
    got = [on.serve(_reqs([h], seed=i))
           for i, h in enumerate((A, B, C, A))]
    assert got == want
    kv = on._caches[0]["kv"]
    assert not kv.sharding.is_fully_replicated   # pool genuinely sharded
    assert on.tier["promotions"] >= 1 and on.tier["host_hits"] >= 1
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0


def test_disk_spill_roundtrip(gpt2, tmp_path):
    """A host pool too small for the working set cascades to disk
    (host LRU -> part files) and disk hits promote back through host
    with token parity. host_cache_blocks=3 holds exactly ONE demoted
    head, so the second demotion must spill the first to disk."""
    model, params = gpt2
    rng = np.random.default_rng(17)
    A, B, C = _hot(rng, 3)
    stream = (A, B, C, A, B, C)
    off = ContinuousBatcher(model, params, **_COMMON)
    want = [off.serve(_reqs([h], seed=i)) for i, h in enumerate(stream)]
    on = ContinuousBatcher(model, params, **_COMMON, host_cache_blocks=3,
                           disk_cache_dir=str(tmp_path))
    got = [on.serve(_reqs([h], seed=i)) for i, h in enumerate(stream)]
    assert got == want
    t = dict(on.tier)
    assert t["disk_spills"] >= 1 and t["disk_hits"] >= 1
    assert t["disk_crc_miss"] == 0
    # three heads round-robin over a pool that holds one beside a live
    # row is LRU's worst case: without the tier every rehit finds its
    # head evicted; with it the hits come back from host and disk while
    # the device pool stays the fixed allocation it was
    assert off.stats["prefix_hits"] == 0 and on.stats["prefix_hits"] > 0
    assert 0 < on.stats["block_pool_occupancy"] <= 1.0
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0
    # every disk-tier entry still indexes a live part; no orphan files
    disk_keys = {e.disk_key for e in on._radix.entries
                 if e.tier == TIER_DISK}
    parts = {os.path.basename(p)[:-len(".npz")]
             for p in glob.glob(str(tmp_path / "*.npz"))}
    assert disk_keys == parts


def test_disk_crc_corruption_degrades_to_miss(gpt2, tmp_path):
    """Flip bytes in every on-disk part: the rehit's promotion fails
    CRC, the entry silently degrades to a cache miss (re-prefill), the
    stream stays token-identical, and the corrupt part is dropped —
    tier-3 bytes can never poison or crash serving."""
    model, params = gpt2
    rng = np.random.default_rng(19)
    A, B, C = _hot(rng, 3)
    off = ContinuousBatcher(model, params, **_COMMON)
    want = [off.serve(_reqs([h], seed=i))
            for i, h in enumerate((A, B, C, A))]
    on = ContinuousBatcher(model, params, **_COMMON, host_cache_blocks=16,
                           disk_cache_dir=str(tmp_path))
    for i, h in enumerate((A, B, C)):
        assert on.serve(_reqs([h], seed=i)) == want[i]
    # eviction is lazy, so push the demoted head (A) to disk explicitly
    # rather than growing the stream until host pressure does it
    on._tier._spill_one()
    on._tier.disk.drain()  # async writer: part must be on disk to corrupt
    parts = glob.glob(str(tmp_path / "*.npz"))
    assert parts and [e for e in on._radix.entries
                      if e.tier == TIER_DISK]
    for p in parts:
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(p, "wb").write(bytes(raw))
    assert on.serve(_reqs([A], seed=3)) == want[3]
    assert on.tier["disk_crc_miss"] >= 1
    assert on.tier["disk_hits"] == 0
    # the dropped entry is gone, not stranded half-demoted
    assert not [e for e in on._radix.entries if e.disk_key is not None]
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0


def test_reconstruction_zeroes_tiers_and_replays(gpt2):
    """A device fault mid-stream with demoted entries outstanding: the
    host/disk bytes physically survive, but the radix that indexes
    them died with the pool — reconstruction must zero ALL tiers (a
    stale host entry would attach pre-fault K/V to a replayed row) and
    the resumed streams must equal a fault-free tier-off run token for
    token."""
    model, params = gpt2
    rng = np.random.default_rng(23)
    A, B, C = _hot(rng, 3)
    reqs = _reqs([A, B, C, A], seed=1, sampled=(2,))
    off = ContinuousBatcher(model, params, **_COMMON)
    want = off.serve([dataclasses.replace(r) for r in reqs])
    on = ContinuousBatcher(model, params, **_COMMON,
                           host_cache_blocks=64)
    res = on.serve_detailed(
        [dataclasses.replace(r) for r in reqs],
        chaos=ChaosInjector(fault_at_segment=2, fault_mode="raise"))
    assert on.stats["reconstructions"] == 1
    assert [r.tokens for r in res] == want
    # the drill actually exercised the tier (demotions happened), and
    # after the replay (which may legitimately re-demote under the same
    # pressure) every ledger balances: host blocks allocated are exactly
    # the HOST-tier entries' holdings, nothing leaked anywhere
    assert on.tier["demotions"] >= 1
    owned = sum(len(e.host_blocks) for e in on._radix.entries
                if e.tier == TIER_HOST)
    assert owned == on._tier.host.allocated
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0
    assert on.last_host_block_leaks == 0
    # a fresh reset drains the tier completely
    on.reset()
    assert on._tier.host.allocated == 0
    assert not [e for e in on._radix.entries if e.tier != TIER_DEVICE]


def test_tier_leak_discipline_across_cycles(gpt2):
    """Many demote/promote cycles: after every wave the host pool's
    allocated blocks are exactly the HOST-tier entries' holdings (the
    last_host_block_leaks ledger), and reset() drains everything."""
    model, params = gpt2
    rng = np.random.default_rng(29)
    A, B, C = _hot(rng, 3)
    on = ContinuousBatcher(model, params, **_COMMON,
                           host_cache_blocks=64)
    for i, h in enumerate((A, B, C, A, C, B, A, B)):
        on.serve(_reqs([h], seed=i))
        assert on.last_host_block_leaks == 0, i
        assert on.last_block_leaks == 0, i
        owned = sum(len(e.host_blocks) for e in on._radix.entries
                    if e.tier == TIER_HOST)
        assert owned == on._tier.host.allocated, i
    assert on.tier["demotions"] >= 3 and on.tier["promotions"] >= 2
    on.reset()
    assert on._tier.host.allocated == 0
    assert not [e for e in on._radix.entries if e.tier != TIER_DEVICE]


def test_tier_config_validation(gpt2):
    """The spill tier rides the radix cache: host/disk flags without
    prefix_cache (or disk without a host tier) are config errors, not
    silent no-ops."""
    model, params = gpt2
    with pytest.raises(ValueError, match="prefix_cache"):
        ContinuousBatcher(model, params, slots=1, t_max=32,
                          prompt_buf=24, segment=4, host_cache_mb=8)
    with pytest.raises(ValueError, match="host"):
        ContinuousBatcher(model, params, slots=1, t_max=32,
                          prompt_buf=24, segment=4, prefix_cache=True,
                          disk_cache_dir="/tmp/x")
    with pytest.raises(ValueError, match="host_cache_mb"):
        ContinuousBatcher(model, params, slots=1, t_max=32,
                          prompt_buf=24, segment=4, prefix_cache=True,
                          host_cache_mb=-1)


def test_cli_tier_flag_validation():
    """dcp-serve rejects inconsistent tier flags up front — before any
    checkpoint load or compile."""
    from distributed_compute_pytorch_tpu.cli_serve import main
    base = ["--ckpt_path", "nope.npz", "--requests", "nope.txt"]
    with pytest.raises(SystemExit, match="prefix_cache"):
        main(base + ["--host_cache_mb", "8"])
    with pytest.raises(SystemExit, match="host_cache_mb"):
        main(base + ["--prefix_cache", "--disk_cache_dir", "/tmp/d"])
    with pytest.raises(SystemExit, match="> 0"):
        main(base + ["--prefix_cache", "--host_cache_mb", "0"])
