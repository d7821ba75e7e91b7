"""The serve loop's block manager (filters/kv_blocks.py) driven directly:
no model, no jax, nothing dispatched.  What the serving tests can only
observe after a loop has run — the refcount / free-list invariant, the
prefix index, eviction order, the reservation arithmetic of a prefix hit,
the allocator's loud shortfall — is pinned here at the calls the
scheduler makes."""

import collections
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

# by file path: importing the package would pull jax in, and this file
# proves the manager needs none of it
_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "nnstreamer_tpu", "filters", "kv_blocks.py")
_spec = importlib.util.spec_from_file_location("_kv_blocks_alone", _PATH)
kv_blocks = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kv_blocks)
BlockManager = kv_blocks.BlockManager


def make(n_blocks=16, slots=4, bs=8, chunk=8, max_blocks=8, win_ring=0,
         prefix_cache=True):
    counts = collections.Counter()

    def count(name, n=1):
        counts[name] += n

    kv = BlockManager(slots=slots, block_size=bs, prefill_chunk=chunk,
                      n_blocks=n_blocks, max_blocks=max_blocks,
                      win_ring=win_ring, win_blocks=slots * win_ring,
                      prefix_cache=prefix_cache, count=count)
    return kv, counts


def serve(kv, s, sid, row, n):
    """What the scheduler does with one prompt, from lookup to the end
    of its prefill; returns the plan and the fork pair."""
    plan = kv.lookup(sid, row, len(row), n)
    assert kv.fits(plan)
    forked = kv.admit(s, plan)
    kv.register(s, plan.hashes)
    return plan, forked


def check_invariants(kv):
    held = collections.Counter(b for bl in kv.slot_blocks for b in bl)
    # a block is on the free list iff its refcount is 0, once
    assert sorted(kv.free) == [b for b in range(kv.n_blocks)
                               if kv.ref[b] == 0]
    assert all(kv.ref[b] == held[b] for b in range(kv.n_blocks))
    assert sorted(set(kv.free) | set(held)) == list(range(kv.n_blocks))
    # the index and block_hash are each other's inverse
    assert {b: h for h, b in kv.prefix_index.items()} == kv.block_hash
    # the table says what the slot holds, and the sentinel past it
    for s, bl in enumerate(kv.slot_blocks):
        assert list(kv.tables[s, :len(bl)]) == bl
        assert (kv.tables[s, len(bl):] == kv.sentinel).all()


def test_the_module_imports_no_jax():
    code = ("import importlib.util as u, sys; "
            f"s = u.spec_from_file_location('m', {_PATH!r}); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "m.BlockManager(slots=1, block_size=4, prefill_chunk=4, "
            "n_blocks=2, max_blocks=2, win_ring=0, win_blocks=0, "
            "prefix_cache=True, count=print).reserve(0, 8); "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


def test_a_block_is_freed_only_at_refcount_zero():
    kv, _ = make()
    row = np.arange(1, 25, dtype=np.int32)  # 3 full blocks
    serve(kv, 0, 100, row, 8)
    plan, forked = serve(kv, 1, 101, np.append(row, np.int32([7, 7])), 8)
    assert plan.shared == 3 and forked is None
    shared = kv.slot_blocks[0][:3]
    assert kv.slot_blocks[1][:3] == shared
    assert all(kv.ref[b] == 2 for b in shared)
    kv.release(0)
    assert all(kv.ref[b] == 1 for b in shared)
    assert not set(shared) & set(kv.free)
    check_invariants(kv)
    kv.release(1)
    assert set(shared) <= set(kv.free) and (kv.ref == 0).all()
    # content and index outlive the holders: they rest in the free list
    assert set(kv.block_hash) == set(shared)
    check_invariants(kv)


def test_eviction_is_allocation_oldest_first():
    kv, counts = make(n_blocks=6, slots=2, max_blocks=6)
    a = np.arange(1, 17, dtype=np.int32)        # 2 full blocks
    b = np.arange(101, 117, dtype=np.int32)
    serve(kv, 0, 1, a, 1)                        # blocks 0, 1 (+ tail 2)
    serve(kv, 1, 2, b, 1)                        # blocks 3, 4 (+ tail 5)
    kv.release(0)
    kv.release(1)
    assert kv.free == [0, 1, 2, 3, 4, 5]
    assert sorted(kv.block_hash) == [0, 1, 3, 4]
    # cached blocks are skipped while uncached ones remain ...
    assert kv.take_blocks(2) == [2, 5]
    assert kv.free == [0, 1, 3, 4]
    assert not counts["llm.serve.prefix_evictions"]
    # ... then taken oldest-released first, their index entries dropped
    assert kv.take_blocks(3) == [0, 1, 3]
    assert counts["llm.serve.prefix_evictions"] == 3
    assert kv.free == [4] and list(kv.block_hash) == [4]
    assert list(kv.prefix_index.values()) == [4]
    # the evicted prompt is a miss now, the surviving one's chain broke too
    assert kv.lookup(3, a, 16, 1).matched == []
    assert kv.lookup(4, b, 16, 1).matched == []


def test_resting_matched_blocks_are_demanded_on_top_of_phys():
    kv, _ = make(n_blocks=5, slots=2, max_blocks=5)
    row = np.arange(1, 26, dtype=np.int32)      # 25 tokens, 3 full blocks
    serve(kv, 0, 1, row, 7)                      # 4 blocks
    kv.release(0)
    plan = kv.lookup(2, row, 25, 7)
    assert (plan.shared, plan.phys, plan.resting) == (3, 1, 3)
    assert len(kv.free) == 5 and kv.fits(plan)   # 5 >= 1 + 3
    # two blocks go elsewhere (uncached ones first): phys alone would
    # still fit, phys on top of the resting blocks does not
    kv.reserve(1, 16)
    assert len(kv.free) == 3 >= plan.phys and not kv.fits(plan)
    kv.release(1)
    assert kv.fits(kv.lookup(2, row, 25, 7))
    check_invariants(kv)


@pytest.mark.parametrize("T,n,bs,C,cached,want", [
    # cold: nothing matched, everything reserved, prefill from 0
    (20, 12, 8, 8, 0, dict(p0=0, shared=0, fork=0, phys=4)),
    # two blocks cached, chunk = block: resume on the block boundary
    (20, 12, 8, 8, 16, dict(p0=16, shared=2, fork=0, phys=2)),
    # whole prompt cached, T a block multiple: the last real token is
    # recomputed, so p0 = (T-1)//C*C = 20 straddles block 2 -> forked
    (24, 8, 8, 4, 24, dict(p0=20, shared=2, fork=1, phys=2)),
    # chunk 16 over blocks of 8: three blocks match but p0 falls back to
    # 16; the third matched block lies past p0 and is re-reserved
    (40, 8, 8, 16, 24, dict(p0=16, shared=2, fork=0, phys=4)),
    # chunk 12: s0 = 16 -> p0 = 12 straddles block 1 (forked); block 1's
    # twin is taken fresh, block 0 alone is shared
    (40, 8, 8, 12, 16, dict(p0=12, shared=1, fork=1, phys=5)),
    # a match shorter than one chunk shares nothing
    (40, 8, 8, 16, 8, dict(p0=0, shared=0, fork=0, phys=6)),
])
def test_what_a_prefix_hit_shares_forks_and_reserves(T, n, bs, C, cached,
                                                     want):
    kv, counts = make(n_blocks=32, slots=2, bs=bs, chunk=C, max_blocks=16)
    row = np.arange(1, T + 1, dtype=np.int32)
    if cached:
        serve(kv, 0, 1, row[:cached], 1)
    plan = kv.lookup(2, row, T, n)
    assert len(plan.matched) == cached // bs
    assert {k: getattr(plan, k) for k in want} == want
    forked = kv.admit(1, plan)
    blocks = kv.slot_blocks[1]
    assert len(blocks) == -(-(T + n) // bs)
    assert blocks[:plan.shared] == plan.matched[:plan.shared]
    if want["fork"]:
        # the straddled block is copied into the first fresh block, which
        # sits at its logical place in the table; the source is untouched
        assert forked == (plan.matched[plan.shared], blocks[plan.shared])
        assert kv.ref[forked[0]] == 1 and kv.ref[forked[1]] == 1
        assert counts["llm.serve.cow_forks"] == 1
    else:
        assert forked is None and not counts["llm.serve.cow_forks"]
    # matched blocks past the shared extent are NOT mapped twice
    for b in plan.matched[plan.shared:]:
        assert kv.ref[b] == 1
    assert counts["llm.serve.prefix_hits"] == (1 if plan.shared else 0)
    assert counts["llm.serve.prefix_hit_blocks"] == plan.shared
    check_invariants(kv)


def test_chain_hash_j_commits_to_blocks_0_to_j():
    kv, _ = make()
    row = np.arange(1, 33, dtype=np.int32)
    base = kv.chain_hashes(row, 4)
    assert len(base) == 4 and len(set(base)) == 4
    for j in range(4):
        other = row.copy()
        other[j * 8] += 1                        # one token of block j
        got = kv.chain_hashes(other, 4)
        assert got[:j] == base[:j]
        assert all(g != b for g, b in zip(got[j:], base[j:]))
    # the same block content at another depth hashes differently
    rep = np.tile(row[:8], 2)
    h = kv.chain_hashes(rep, 2)
    assert h[0] == base[0] and h[1] != h[0]


@pytest.mark.parametrize("held,need", [(0, 17), (10, 7), (16, 1)])
def test_a_shortfall_raises_and_leaves_free_and_ref_alone(held, need):
    kv, counts = make(n_blocks=16, slots=4, max_blocks=16)
    row = np.arange(1, 17, dtype=np.int32)
    serve(kv, 0, 1, row, 1)                      # some cached blocks,
    kv.release(0)                                # resting in the list
    if held:
        kv.reserve(1, held * 8)
    free, ref = list(kv.free), kv.ref.copy()
    index, hashes = dict(kv.prefix_index), dict(kv.block_hash)
    evicted = counts["llm.serve.prefix_evictions"]
    with pytest.raises(RuntimeError, match="KV allocator invariant "
                       f"violated: asked for {need} blocks, only "
                       f"{16 - held} allocatable"):
        kv.take_blocks(need)
    assert kv.free == free and (kv.ref == ref).all()
    assert kv.prefix_index == index and kv.block_hash == hashes
    assert counts["llm.serve.prefix_evictions"] == evicted
    check_invariants(kv)


@pytest.mark.parametrize("seed", range(6))
def test_any_sequence_keeps_the_pool_whole(seed):
    """Admit / register / release / plain reservation drawn from a seed,
    over a pool small enough to evict and to defer: after every step the
    free list and the held blocks partition the pool and the index stays
    the inverse of block_hash."""
    rng = np.random.default_rng(seed)
    bs, C = 4, (4, 8, 6)[seed % 3]
    kv, counts = make(n_blocks=24, slots=4, bs=bs, chunk=C, max_blocks=12)
    # three system prompts that most requests start from
    stems = [rng.integers(1, 50, (int(t),), np.int32) for t in (8, 12, 17)]
    live, sid, deferred, hits = {}, 0, 0, 0
    for _ in range(200):
        free_slots = [s for s in range(4) if s not in live]
        if free_slots and rng.random() < 0.6:
            sid += 1
            row = np.concatenate([
                stems[rng.integers(3)],
                rng.integers(1, 50, (int(rng.integers(1, 9)),), np.int32)])
            n = int(rng.integers(1, 12))
            s = free_slots[0]
            if rng.random() < 0.15:
                if kv.blocks_for(len(row) + n) <= len(kv.free):
                    kv.reserve(s, len(row) + n)  # adopt: no lookup
                    live[s] = None
            else:
                plan = kv.lookup(sid, row, len(row), n)
                if not kv.fits(plan):
                    deferred += 1
                else:
                    forked = kv.admit(s, plan)
                    assert (forked is not None) == bool(plan.fork)
                    hits += bool(plan.shared)
                    # registration comes chunks later: another stream may
                    # be seated or retired in between
                    live[s] = plan.hashes
            kv.prune({sid})
            assert set(kv.chain_cache) <= {sid}
        elif live:
            s = list(live)[rng.integers(len(live))]
            if live[s] is not None and rng.random() < 0.8:
                kv.register(s, live[s])
            ids, n_shared = kv.used(s, kv.bs * len(kv.slot_blocks[s]))
            assert list(ids) == kv.slot_blocks[s]
            assert n_shared == sum(kv.ref[b] > 1 for b in ids)
            kv.release(s)
            del live[s]
        check_invariants(kv)
        assert kv.held(live) == sum(len(kv.slot_blocks[s]) for s in live)
        st = kv.stats()
        assert st["blocks_free"] == len(kv.free)
        assert st["blocks_cached"] == len(kv.block_hash)
        assert st["blocks_shared"] == int((kv.ref > 1).sum())
    for s in list(live):
        kv.release(s)
    check_invariants(kv)
    assert sorted(kv.free) == list(range(24)) and (kv.ref == 0).all()
    assert (kv.tables == kv.sentinel).all()
    # the walk reached the branches it is there for
    assert hits and counts["llm.serve.prefix_hits"] == hits
    assert counts["llm.serve.prefix_evictions"] and deferred


@pytest.mark.parametrize("positions,want", [
    ([], 0),                                     # every slot parked
    ([1], 1), ([8], 1), ([9], 2),                # ceil(p / bs)
    ([200], 3),                                  # capped at the ring
    ([0, 5, 17, 300], 0 + 1 + 3 + 3),
])
def test_the_ring_table_is_fixed_and_counts_live_entries(positions, want):
    kv, _ = make(n_blocks=64, slots=4, bs=8, max_blocks=64, win_ring=3)
    ring = kv.win_tables.copy()
    assert ring.tolist() == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]
    pos = np.full((4,), kv.park, np.int32)
    pos[:len(positions)] = positions
    for s, p in enumerate(positions):
        kv.reserve(s, p + 8)
    assert kv.win_blocks_live(pos) == want
    t = kv.tabs(slice(1, 3))
    assert set(t) == {"full", "win"}
    assert t["win"].tolist() == ring[1:3].tolist()
    assert t["full"].tolist() == kv.tables[1:3].tolist()
    t["full"][:] = -1                            # a copy: dispatch is async
    assert (kv.tables[1:3] != -1).all()
    # window layers: no stream resumes from another's prefix
    assert not kv.share_prefix
    plan = kv.lookup(9, np.arange(1, 33, dtype=np.int32), 32, 8)
    assert plan.hashes == [] and plan.shared == 0 and not kv.chain_cache
    for s in range(len(positions)):
        kv.release(s)
    assert (kv.win_tables == ring).all()
    assert kv.stats()["win_blocks_total"] == 12


def test_one_pool_tables_are_a_bare_copy_and_count_no_ring():
    kv, _ = make()
    kv.reserve(2, 20)
    t = kv.tabs()
    assert isinstance(t, np.ndarray) and t.tolist() == kv.tables.tolist()
    t[:] = -1
    assert kv.tables[2, :3].tolist() == kv.slot_blocks[2]
    assert kv.win_blocks_live(np.asarray([3, 9, kv.park, kv.park])) == 0
