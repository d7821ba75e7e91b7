"""Host side of the serve loop's block-paged KV cache.

The continuous serve loop (``filters/llm.py`` ``_ContinuousLoop``) is a
scheduler: it decides WHEN a stream joins, prefills, decodes and leaves.
WHICH pool blocks a stream holds, and what the block tables say, is
decided here, by one ``BlockManager`` the scheduler calls.  The split:

* **Host state lives here** — the free list, per-block reference counts,
  per-slot block lists, the block table, the window layers' ring
  table and the slot ids that name convolution layers' state, the prefix
  chain index — and nothing else writes it.
* **Device state stays with the loop** — the pools, the token / key /
  position vectors and every compiled program.  A copy-on-write fork is
  CHOSEN here (``admit`` returns ``(src, dst)``) and COPIED there.

numpy and hashlib only: the manager imports no jax, dispatches nothing,
and is driven without a model by ``tests/test_kv_blocks.py``.
"""

import hashlib
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np


class PrefixPlan(NamedTuple):
    """What a waiting prompt's reservation comes to, given the prefix
    index as it stands: ``lookup`` computes it, ``fits`` and ``admit``
    consume it within the same admission pass."""

    hashes: list    #: chain hashes of the prompt's full blocks
    matched: list   #: pool block ids of the matched leading blocks
    p0: int         #: position the suffix prefill starts at
    shared: int     #: matched blocks mapped as they are
    fork: int       #: 1 when the block straddling ``p0`` is forked
    phys: int       #: blocks to take off the free list
    resting: int    #: shared blocks now resting in the free list


class BlockManager:
    """Allocator, tables and prefix cache of one serve loop's pool.

    Built from the geometry ``serving_plan`` returns; ``count`` is the
    loop's ``metrics.count`` (the manager counts its own events:
    ``llm.serve.prefix_evictions``, ``cow_forks``, ``prefix_hits``,
    ``prefix_hit_blocks``).  Single-threaded by contract: the serve
    thread mutates, other threads read accounting at quiesce points.
    """

    def __init__(self, *, slots: int, block_size: int, prefill_chunk: int,
                 n_blocks: int, max_blocks: int, win_ring: int,
                 win_blocks: int, prefix_cache: bool,
                 count: Callable[..., None], conv_state_bytes: int = 0):
        self.bs, self.chunk = block_size, prefill_chunk
        self.n_blocks = n_blocks
        self.win_ring, self.win_blocks = win_ring, win_blocks
        #: bytes of the convolution layers' state, ``slots`` streams'
        #: worth for good (0 = the model has no such layer): slot s's
        #: columns are row s of the pool's ``conv`` leaf, which no
        #: allocator touches and no table but ``slot_ids`` names
        self.conv_state_bytes = conv_state_bytes
        self.slot_ids = np.arange(slots, dtype=np.int32)
        self.sentinel = n_blocks  # unallocated table entry
        self.park = max_blocks * block_size  # idle-slot position
        self._count = count
        #: a window layer's K/V live in the ring of the slot that wrote
        #: them and nowhere else, and a convolution layer's state at the
        #: end of a shared prefix is in no block at all (a hit would
        #: continue from zeros), so on a model with either no other
        #: stream can resume from a cached prefix: every lookup is a
        #: miss and nothing is indexed (docs/SERVING.md §4e)
        self.share_prefix = bool(prefix_cache) and not win_ring \
            and not conv_state_bytes
        self.tables = np.full((slots, max_blocks), self.sentinel, np.int32)
        #: window layers: slot s owns blocks [s * ring, (s + 1) * ring)
        #: of the window pool for good — logical block j of its stream
        #: lives at ring entry j % ring, so the table never changes and
        #: a window layer holds `ring` blocks a slot whatever the context
        self.win_tables = (
            np.arange(slots, dtype=np.int32)[:, None] * win_ring
            + np.arange(win_ring, dtype=np.int32)[None, :])
        self.free: List[int] = list(range(n_blocks))  # block ids
        self.slot_blocks: List[list] = [[] for _ in range(slots)]
        #: per-block reference counts: 0 = on the free list, 1 = one
        #: private owner, >1 = a prefix-shared block mapped into several
        #: streams' tables.  A block returns to the free list ONLY at
        #: refcount 0 (release) — the prefix-sharing invariant the
        #: property tests in tests/test_kv_blocks.py pin.
        self.ref = np.zeros((n_blocks,), np.int64)
        #: prefix cache: chain-hash -> pool block id.  Cached blocks with
        #: refcount 0 LIVE IN THE FREE LIST (content + index intact):
        #: the cache never shrinks admission capacity, and eviction is
        #: simply allocation — popping an indexed block drops its entry.
        self.prefix_index: Dict[bytes, int] = {}
        self.block_hash: Dict[int, bytes] = {}
        #: sid -> chain_hashes(prompt) memo for WAITING prompts: a
        #: capacity-deferred entry is re-scanned every loop iteration,
        #: and its prompt is immutable after submit — re-hashing a long
        #: prompt per spin would burn serve-thread time exactly when
        #: the system is saturated.  Pruned against the live waiting
        #: set each admission phase, so no path can leak entries.
        self.chain_cache: Dict[int, list] = {}

    # -- what the programs are handed ---------------------------------------
    def tabs(self, rows=slice(None)):
        """The table argument of a program for the slots ``rows``: a
        copy of the block table (dispatch is asynchronous and the tables
        are mutated in place between dispatches), with the ring table
        beside it where the model has window layers and the slots' own
        ids where convolution layers keep state by slot."""
        if not self.win_ring and not self.conv_state_bytes:
            return self.tables[rows].copy()
        out = {"full": self.tables[rows].copy()}
        if self.win_ring:
            out["win"] = self.win_tables[rows]
        if self.conv_state_bytes:
            out["slot"] = self.slot_ids[rows]
        return out

    # -- allocation ----------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.bs)

    def take_blocks(self, need: int) -> list:
        """Allocate ``need`` private blocks (refcount 1) off the
        free list, preferring blocks that do NOT hold a cached
        prefix; when only cached blocks remain, the oldest-released
        ones are evicted (their index entries dropped) — eviction
        IS allocation, so the prefix cache can never make admission
        defer.

        O(need * len(free)) from the head-pops — per ADMISSION,
        not per token; at the worst-case bench pool (64 7B
        streams, ~4.6k blocks) that is ~1 ms of host time under
        the prefill dispatch it precedes.  Revisit with a deque +
        free-set if pools grow past that."""
        free = self.free
        if need > len(free):
            # every caller pre-checks capacity (admission counts
            # resting matched blocks on top of phys; adopt checks
            # len(free)); a shortfall here is an allocator-invariant
            # bug — fail LOUDLY, before anything is touched, instead
            # of handing back a short list that becomes a silently
            # truncated block table and bit-wrong output
            raise RuntimeError(
                f"KV allocator invariant violated: asked for {need} "
                f"blocks, only {len(free)} allocatable")
        got: list = []
        cached: list = []
        while len(got) < need and free:
            b = free.pop(0)
            (cached if b in self.block_hash else got).append(b)
        while len(got) < need:
            b = cached.pop(0)
            del self.prefix_index[self.block_hash.pop(b)]
            self._count("llm.serve.prefix_evictions")
            got.append(b)
        free[0:0] = cached  # skipped cached blocks keep their place
        for b in got:
            self.ref[b] = 1
        return got

    def _drop(self, blocks) -> None:
        """Drop one reference per block; a block returns to the
        free list ONLY at refcount 0 (prefix-shared blocks stay
        resident for their other holders; cached content + index
        survive until eviction-by-allocation)."""
        ref = self.ref
        for b in blocks:
            ref[b] -= 1
            if ref[b] <= 0:
                ref[b] = 0
                self.free.append(b)

    def _map_shared(self, bid: int) -> None:
        """Take one more reference on a cached/shared block — off
        the free list if it was resting there at refcount 0."""
        if self.ref[bid] == 0:
            self.free.remove(bid)
        self.ref[bid] += 1

    def _seat(self, s: int, blocks: list) -> None:
        self.slot_blocks[s] = blocks
        self.tables[s, :len(blocks)] = blocks

    def reserve(self, s: int, n_tokens: int) -> list:
        """A plain reservation of ``n_tokens`` for slot ``s`` (adopt and
        warm-up): private blocks, written into the slot's table row."""
        blocks = self.take_blocks(self.blocks_for(n_tokens))
        self._seat(s, blocks)
        return blocks

    def release(self, s: int) -> None:
        """Slot ``s`` leaves: one reference dropped per block it held,
        its table row back to the sentinel."""
        self._drop(self.slot_blocks[s])
        self.slot_blocks[s] = []
        self.tables[s, :] = self.sentinel

    # -- prefix cache --------------------------------------------------------
    def chain_hashes(self, row: np.ndarray, full: int) -> list:
        """Token-block chain hashes: hash j commits to ALL tokens
        of blocks 0..j, so two prompts share block j only when
        their entire prefixes match — which is exactly when the
        cached K/V rows (position-dependent through RoPE) are
        bit-valid for both."""
        bs = self.bs
        h = b"nns-prefix-v1"
        out = []
        for j in range(full):
            h = hashlib.sha1(
                h + row[j * bs:(j + 1) * bs].tobytes()).digest()
            out.append(h)
        return out

    def prune(self, waiting_sids) -> None:
        """Forget the hash memo of every stream no longer waiting
        (``waiting_sids``: any iterable, walked only if there is a
        memo to prune)."""
        if self.chain_cache:
            waiting = set(waiting_sids)
            for k in [k for k in self.chain_cache if k not in waiting]:
                del self.chain_cache[k]

    def lookup(self, sid, row: np.ndarray, T: int, n: int) -> PrefixPlan:
        """The reservation of a waiting prompt (``row``, ``T`` tokens,
        ``n`` to generate) against the prefix index as it stands."""
        # Prefix lookup BEFORE the capacity check: a cache hit
        # shrinks the PHYSICAL reservation to ~the non-shared
        # suffix, so a hit prompt admits where a cold one
        # defers.  The suffix prefill starts at p0 — the
        # largest prefill_chunk multiple not past the shared
        # extent (or the last real token): chunk ends stay on
        # the cold path's grid, so the table-span arithmetic in
        # serving_plan() is untouched.  A matched block
        # straddling p0 is copy-on-write FORKED (the chunk
        # rewrites part of it); matched blocks past p0 are
        # simply re-prefilled into fresh private blocks.
        bs, C = self.bs, self.chunk
        hashes: list = []
        matched: list = []
        if self.share_prefix:
            hashes = self.chain_cache.get(sid)
            if hashes is None:
                hashes = self.chain_cache[sid] = self.chain_hashes(
                    row, T // bs)
            for h in hashes:
                bid = self.prefix_index.get(h)
                if bid is None:
                    break
                matched.append(bid)
        s0 = len(matched) * bs
        p0 = min(s0 // C, (T - 1) // C) * C if s0 else 0
        shared = p0 // bs
        fork = 1 if p0 % bs else 0
        phys = self.blocks_for(T + n) - shared
        # matched blocks RESTING in the free list (refcount 0,
        # cached content) still count as free right now, but
        # admit pulls each one OUT of the list — the
        # capacity check must demand phys blocks ON TOP of
        # them, or take_blocks comes up short and the stream
        # gets a silently truncated table
        resting = sum(1 for b in matched[:shared] if self.ref[b] == 0)
        return PrefixPlan(hashes, matched, p0, shared, fork, phys, resting)

    def fits(self, plan: PrefixPlan) -> bool:
        return len(self.free) >= plan.phys + plan.resting

    def admit(self, s: int, plan: PrefixPlan) -> Optional[Tuple[int, int]]:
        """Seat a prompt in slot ``s``: map the shared blocks, take the
        rest, write the slot's table row.  Returns ``(src, dst)`` when
        the block straddling ``p0`` is forked — a stream about to WRITE
        into a block it shares gets a private copy first; the caller
        copies ``src``'s pool rows into ``dst`` before the suffix
        prefill, and the source keeps its other holders' references."""
        blocks = list(plan.matched[:plan.shared])
        for bid in blocks:
            self._map_shared(bid)
        fresh = self.take_blocks(plan.phys)
        self._seat(s, blocks + fresh)
        if plan.shared:
            self._count("llm.serve.prefix_hits")
            self._count("llm.serve.prefix_hit_blocks", plan.shared)
        if not plan.fork:
            return None
        self._count("llm.serve.cow_forks")
        return plan.matched[plan.shared], fresh[0]

    def register(self, s: int, hashes: list) -> None:
        """Index the full blocks of slot ``s``'s prompt after its last
        prefill chunk.  Forked/shared blocks' hashes are already
        present — only fresh tails register."""
        if not self.share_prefix:
            return
        for j, h in enumerate(hashes):
            if h not in self.prefix_index:
                bid = self.slot_blocks[s][j]
                self.prefix_index[h] = bid
                self.block_hash[bid] = h

    # -- reads ---------------------------------------------------------------
    def used(self, s: int, p: int) -> Tuple[np.ndarray, int]:
        """The block ids under positions ``[0, p)`` of slot ``s``, and
        how many of them another stream maps too (drain)."""
        held = self.slot_blocks[s][:self.blocks_for(p)]
        return (np.asarray(held, np.int32),
                sum(1 for b in held if self.ref[b] > 1))

    def held(self, rows) -> int:
        """Blocks the slots ``rows`` hold, per reference (tenant
        quota)."""
        return sum(len(self.slot_blocks[s]) for s in rows)

    def win_blocks_live(self, pos: np.ndarray) -> int:
        """Ring entries that hold rows of a live stream: a slot at
        position p has written ``ceil(p / bs)`` logical blocks, of
        which its ring keeps the last ``ring``."""
        if not self.win_ring:
            return 0
        p = pos[pos < self.park]
        return int(np.minimum(-(-p // self.bs), self.win_ring).sum())

    def stats(self) -> Dict[str, int]:
        """The pool's accounting, as ``pool_stats()`` prints it."""
        return {
            "blocks_total": self.n_blocks,
            "blocks_free": len(self.free),
            # window layers' pool: `win_ring` blocks a slot, for good
            "win_blocks_total": self.win_blocks,
            "win_ring": self.win_ring,
            # convolution layers' state: every slot's, for good
            "conv_state_bytes": self.conv_state_bytes,
            # prefix-sharing accounting: blocks whose content + chain
            # hash are indexed (many resting in the free list at
            # refcount 0), and blocks currently mapped by >1 stream
            "blocks_cached": len(self.block_hash),
            "blocks_shared": int((self.ref > 1).sum()),
        }
