"""Prefix-sharing copy-on-write paged KV cache + speculative decoding
(ISSUE 15, docs/SERVING.md §4b/§4c).

Covers the contracts the serve-a-million-tenants PR promises:

* ref-count/CoW allocator invariants: blocks free ONLY at refcount 0,
  fork-on-write isolation (a CoW fork never perturbs the sharing
  streams), recycled-slot identity under churn with a full free-list
  drain and cache eviction, and the stale-table sentinel still dropping
  multi-token (verify-shaped) writes to reclaimed blocks;
* prefix sharing: a cache-hit prompt's PHYSICAL admission reservation
  collapses to ~the non-shared suffix (two sharing streams fit a pool
  two cold ones cannot), bit-identity at every hit/miss/fork mix;
* per-tenant ``kv_blocks`` quotas charge LOGICAL blocks (per reference):
  a shared prefix never lets a tenant exceed quota for free;
* speculative decoding: greedy bit-identity of spec vs plain decode at
  accept rates 0, partial, and 1; the accepted/bonus ``spec_draft``
  meta flag and its pipeline-native routing homes (tensor_if
  META_VALUE, tensor_demux by-meta);
* the zero-recompile pin: the speculative loop compiles EXACTLY the 5
  programs ``serving_plan()`` predicts (target/draft prefill, propose,
  verify, slot-token setter — the plain decode chunk never compiles)
  and stream churn, cache hits, CoW forks, and accept/reject ratios
  change VALUES only.
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core.log import metrics
from nnstreamer_tpu.models import llama


def _metric(name):
    return metrics.snapshot().get(name, 0.0)


def _fw(custom, model="llama_tiny"):
    from nnstreamer_tpu.filters.llm import LLMFramework

    fw = LLMFramework()
    fw.open({"model": model, "custom": custom})
    return fw


def _plain_tokens(prompt, custom, model="llama_tiny"):
    fw = _fw(custom, model)
    try:
        return [int(ids[0]) for ids, *_ in fw.invoke_stream([prompt])]
    finally:
        fw.close()


def _serve_tokens(fw, prompts, metas=None, timeout=300.0):
    got = {i: [] for i in range(len(prompts))}
    lock = threading.Lock()

    def emit_for(i):
        def emit(tensors, meta):
            with lock:
                got[i].append(int(tensors[0][0]))
                if metas is not None:
                    metas.setdefault(i, []).append(meta)
        return emit

    for i, p in enumerate(prompts):
        fw.submit([p], {}, emit_for(i))
    assert fw.drain(timeout=timeout)
    return got


def _serve_staggered(fw, prompts, metas=None, timeout=300.0):
    """Submit one prompt at a time, waiting for each stream's FIRST
    token before submitting the next — guarantees the earlier prompt's
    prefill completed and its blocks are registered in the prefix
    index before the later one is admitted."""
    got = {i: [] for i in range(len(prompts))}
    lock = threading.Lock()

    def emit_for(i):
        def emit(tensors, meta):
            with lock:
                got[i].append(int(tensors[0][0]))
                if metas is not None:
                    metas.setdefault(i, []).append(meta)
        return emit

    for i, p in enumerate(prompts):
        fw.submit([p], {}, emit_for(i))
        deadline = time.monotonic() + timeout
        while not got[i]:
            assert time.monotonic() < deadline, f"stream {i} first token"
            time.sleep(0.005)
    assert fw.drain(timeout=timeout)
    return got


BASE = "max_new:5,stream_chunk:2,temperature:0.0,dtype:float32"


def _shared_prompts(rng, prefix_len=24, suffixes=(3, 5)):
    pre = rng.integers(1, 500, (prefix_len,), dtype=np.int32)
    return [np.concatenate([pre, rng.integers(1, 500, (t,), np.int32)])
            for t in suffixes]


# ---------------------------------------------------------------------------
# allocator invariants: refcounts, CoW, eviction, sentinel
# ---------------------------------------------------------------------------

class TestRefcountAllocator:
    def test_free_only_at_refcount_zero(self):
        """Two staggered streams share the prefix blocks (refcount 2);
        the first retiring must NOT return shared blocks to the free
        list while the second still decodes; both retiring must."""
        rng = np.random.default_rng(10)
        pa, pb = _shared_prompts(rng, prefix_len=32, suffixes=(2, 3))
        short = BASE + ",serve:continuous,slots:2,block_size:8," \
            "prefill_chunk:8"
        # stream A short (retires first), stream B long
        fw = _fw("max_new:64,stream_chunk:2,temperature:0.0,"
                 "dtype:float32,serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:8")
        got = {0: [], 1: []}
        lock = threading.Lock()

        def em(i, n_stop=None):
            def e(t, m):
                with lock:
                    got[i].append(int(t[0][0]))
            return e

        fw.submit([pa], {}, em(0))
        while not got[0]:
            time.sleep(0.005)
        fw.submit([pb], {}, em(1))
        while not got[1]:
            time.sleep(0.005)
        serve = fw._serve
        stats = serve.pool_stats()
        assert stats["live_streams"] == 2
        assert stats["blocks_shared"] >= 4, stats  # 32-token prefix / 8
        shared_ids = [b for b in range(serve.n_blocks)
                      if serve.kv.ref[b] > 1]
        assert fw.drain(180)
        # retired: every shared block released down to 0 and free again
        assert sorted(serve.kv.free) == list(range(serve.n_blocks))
        assert (np.asarray(serve.kv.ref) == 0).all()
        assert shared_ids, "expected shared blocks while both live"
        fw.close()
        del short

    def test_cow_fork_isolation(self):
        """Full-coverage hit (T a block multiple, whole prompt cached):
        the re-prefilled tail block is FORKED, the forking stream's
        writes never perturb the original — both emit reference ids,
        and the fork is counted."""
        rng = np.random.default_rng(11)
        p = rng.integers(1, 500, (24,), np.int32)  # 3 blocks of 8
        want = _plain_tokens(p, BASE)
        # prefill_chunk 4 < block_size 8: the recompute start (T-1)//4*4
        # = 20 straddles block 2 -> CoW fork
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:4")
        try:
            got = _serve_staggered(fw, [p])
            assert got[0] == want
            f0 = _metric("llm.serve.cow_forks")
            got = _serve_staggered(fw, [p, p])
            assert got[0] == want and got[1] == want
            assert _metric("llm.serve.cow_forks") - f0 >= 2
            # and the original prompt still replays bit-identically off
            # the (unperturbed) cached blocks
            got = _serve_tokens(fw, [p])
            assert got[0] == want
            assert sorted(fw._serve.kv.free) == \
                list(range(fw._serve.n_blocks))
        finally:
            fw.close()

    def test_recycled_slots_and_eviction_under_churn(self):
        """slots:1 + a pool barely bigger than one stream: every
        admission recycles the predecessor's blocks, evicting its cache
        entries — every stream still emits reference ids and the free
        list fully drains back."""
        rng = np.random.default_rng(12)
        prompts = [rng.integers(1, 500, (t,), np.int32)
                   for t in (17, 19, 23, 18)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        fw = _fw(BASE + ",serve:continuous,slots:1,block_size:8,"
                 "kv_blocks:4,prefill_chunk:8")
        try:
            e0 = _metric("llm.serve.prefix_evictions")
            got = _serve_tokens(fw, prompts)
            for i in range(len(prompts)):
                assert got[i] == want[i], f"stream {i} after recycle"
            serve = fw._serve
            assert sorted(serve.kv.free) == list(range(serve.n_blocks))
            assert (np.asarray(serve.kv.ref) == 0).all()
            assert _metric("llm.serve.prefix_evictions") > e0
            # index never points at an unindexed block and vice versa
            assert set(serve.kv.prefix_index.values()) == \
                set(serve.kv.block_hash.keys())
        finally:
            fw.close()

    def test_sentinel_drops_multitoken_writes(self):
        """The verify step's T=k+1 writes through a cleared (sentinel)
        table must DROP — a reclaimed shared block can never be written
        through a stale table, even by the new multi-token programs."""
        import jax.numpy as jnp

        cfg = llama.PRESETS["llama_tiny"]
        params = llama.init_params(cfg, seed=0)
        pool = llama.init_paged_cache(cfg, 4, 8, dtype="float32")
        n_blocks = 4
        tables = np.full((2, 6), n_blocks, np.int32)  # all sentinel
        park = np.full((2,), 6 * 8, np.int32)
        toks = np.asarray([[5, 6, 7, 8], [9, 10, 11, 12]], np.int32)
        _, pool2 = llama.forward_paged(
            params, jnp.asarray(toks), pool, jnp.asarray(tables),
            jnp.asarray(park), cfg, compute_dtype="float32")
        np.testing.assert_array_equal(np.asarray(pool2["k"]),
                                      np.zeros_like(pool2["k"]))
        np.testing.assert_array_equal(np.asarray(pool2["v"]),
                                      np.zeros_like(pool2["v"]))


# ---------------------------------------------------------------------------
# prefix sharing: admission, quota, bit-identity
# ---------------------------------------------------------------------------

class TestPrefixSharing:
    def test_hit_admits_where_cold_defers(self):
        """The reservation drop IS the tentpole: a 64-token shared
        prefix, 17-block pool.  Each stream's LOGICAL need is 12
        blocks — a cold second stream must wait for the first to
        finish; a SHARING second stream reserves only its ~4-block
        suffix and decodes CONCURRENTLY.  (max_new 24 keeps the
        first stream decoding long enough that the ordering assert
        is not load-sensitive.)"""
        rng = np.random.default_rng(13)
        pa, pb = _shared_prompts(rng, prefix_len=64, suffixes=(2, 3))
        custom = ("max_new:24,stream_chunk:2,temperature:0.0,"
                  "dtype:float32,serve:continuous,slots:2,block_size:8,"
                  "kv_blocks:17,prefill_chunk:8")

        def run(extra):
            fw = _fw(custom + extra)
            got = {0: [], 1: []}
            stamp = {0: [], 1: []}
            lock = threading.Lock()

            def em(i):
                def e(t, m):
                    with lock:
                        got[i].append(int(t[0][0]))
                        stamp[i].append(time.monotonic())
                return e

            try:
                fw.submit([pa], {}, em(0))
                while not got[0]:
                    time.sleep(0.005)
                fw.submit([pb], {}, em(1))
                assert fw.drain(180)
            finally:
                fw.close()
            assert len(got[0]) == 24 and len(got[1]) == 24
            # did B's first token land while A was decoding (concurrent)
            # or only once A had retired (deferred)?  A's last chunk is
            # SETTLED before B can take its blocks, but since PR 32 its
            # two tokens may leave after B's first one (a request was
            # queued, so their delivery waits for the next dispatch):
            # A's last token of the chunk before is the mark.
            return stamp[1][0] < stamp[0][-3]

        h0 = _metric("llm.serve.prefix_hits")
        assert run(",prefix_cache:0") is False, \
            "cold control: pool must defer the second stream"
        assert _metric("llm.serve.prefix_hits") == h0
        assert run("") is True, \
            "sharing must fit both streams concurrently"
        assert _metric("llm.serve.prefix_hits") > h0

    def test_resting_matched_blocks_not_double_counted_as_free(self):
        """Admission regression: a hit's matched blocks RESTING in the
        free list (refcount 0 after their writer retired) satisfy the
        mapping, not the reservation — the capacity check must demand
        ``phys`` blocks ON TOP of them.  Pool 12, cached prefix rests
        as 8 free blocks, a cold stream holds 4: the sharing stream
        (needs 8 resting + 2 fresh) must defer, then emit exactly the
        reference ids — the old check admitted it into a silently
        truncated table (bit-wrong output, no error)."""
        rng = np.random.default_rng(24)
        pre = rng.integers(1, 500, (64,), np.int32)
        pc = rng.integers(1, 500, (17,), np.int32)
        pb = np.concatenate([pre, rng.integers(1, 500, (2,), np.int32)])
        custom = ("max_new:8,stream_chunk:2,temperature:0.0,"
                  "dtype:float32,serve:continuous,slots:3,block_size:8,"
                  "kv_blocks:12,prefill_chunk:8")
        want_b = _plain_tokens(
            pb, "max_new:8,stream_chunk:2,temperature:0.0,dtype:float32")
        fw = _fw(custom)
        got = {0: [], 1: [], 2: []}
        lock = threading.Lock()

        def em(i):
            def e(t, m):
                with lock:
                    got[i].append(int(t[0][0]))
            return e

        try:
            # stream A caches the prefix, retires: 8 cached blocks rest
            # in the free list
            fw.submit([pre], {}, em(0))
            assert fw.drain(120)
            # cold C takes the uncached blocks and keeps decoding
            fw.submit([pc], {}, em(1))
            while not got[1]:
                time.sleep(0.002)
            h0 = _metric("llm.serve.prefix_hits")
            fw.submit([pb], {}, em(2))
            assert fw.drain(120)
            assert _metric("llm.serve.prefix_hits") > h0
            assert got[2] == want_b, (got[2], want_b)
            serve = fw._serve
            assert sorted(serve.kv.free) == list(range(serve.n_blocks))
        finally:
            fw.close()

    def test_quota_charges_logical_blocks(self):
        """A tenant's kv_blocks quota charges per-REFERENCE: its second
        shared-prefix stream defers on quota even though its physical
        need is ~1 block — a shared prefix is not a quota discount."""
        rng = np.random.default_rng(14)
        pa, pb = _shared_prompts(rng, prefix_len=32, suffixes=(2, 3))
        fw = _fw("max_new:24,stream_chunk:2,temperature:0.0,"
                 "dtype:float32,serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:8")
        got = {0: [], 1: []}
        lock = threading.Lock()

        def em(i):
            def e(t, m):
                with lock:
                    got[i].append(int(t[0][0]))
            return e

        try:
            # logical need per stream = ceil((34|35 + 24)/8) = 8 blocks;
            # quota 9 < 16 -> the second stream must defer on QUOTA even
            # though sharing leaves plenty of physical blocks free
            fw.submit([pa], {"_tenant": "acme"}, em(0))
            while not got[0]:
                time.sleep(0.005)
            fw._serve.set_tenant_quota("acme", 9)
            q0 = _metric("llm.serve.quota_deferred")
            fw.submit([pb], {"_tenant": "acme"}, em(1))
            deadline = time.monotonic() + 30
            while _metric("llm.serve.quota_deferred") == q0:
                assert time.monotonic() < deadline, \
                    "expected quota deferral for the shared stream"
                time.sleep(0.01)
            assert fw._serve.pool_stats()["live_streams"] == 1
            # plenty of PHYSICAL space all along
            assert len(fw._serve.kv.free) > 2
            # stream 1 admits after stream 0 retires
            assert fw.drain(180)
            assert len(got[1]) == 24
        finally:
            fw.close()

    def test_bit_identity_hit_miss_fork_mix(self):
        """Cache hits, partial hits, forks, and cold misses all emit
        exactly the dense-path reference ids."""
        rng = np.random.default_rng(15)
        pre = rng.integers(1, 500, (16,), np.int32)
        prompts = [
            np.concatenate([pre, rng.integers(1, 500, (5,), np.int32)]),
            np.concatenate([pre, rng.integers(1, 500, (9,), np.int32)]),
            pre.copy(),                       # full coverage -> fork
            rng.integers(1, 500, (11,), np.int32),  # cold miss
        ]
        want = [_plain_tokens(p, BASE) for p in prompts]
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:4")
        try:
            got = _serve_staggered(fw, prompts)
            for i in range(len(prompts)):
                assert got[i] == want[i], f"stream {i}"
        finally:
            fw.close()


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeDecoding:
    def test_accept_rate_one_bit_identity(self):
        """draft == target (same preset + seed): every proposal matches
        the target's argmax — k accepted + 1 bonus per round, outputs
        bit-identical to plain greedy decode."""
        rng = np.random.default_rng(16)
        prompts = [rng.integers(1, 500, (t,), np.int32) for t in (6, 11)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        a0, r0 = (_metric("llm.serve.spec_accepted"),
                  _metric("llm.serve.spec_rejected"))
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "draft:llama_tiny,spec_k:3")
        metas = {}
        try:
            got = _serve_tokens(fw, prompts, metas=metas)
            for i, w in enumerate(want):
                assert got[i] == w, f"stream {i}"
        finally:
            fw.close()
        assert _metric("llm.serve.spec_accepted") > a0
        assert _metric("llm.serve.spec_rejected") == r0
        # the accept/reject flag rides every round token's meta: 1 for
        # accepted draft proposals, 0 for the target's bonus token
        flags = [m.get("spec_draft") for m in metas[0][1:]]
        assert set(flags) <= {0, 1} and 1 in flags

    def test_partial_accept_bit_identity(self):
        """A differently-seeded draft accepts a partial prefix some
        rounds — emitted ids must STILL be exactly the plain greedy
        stream (the target decides every token)."""
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, 500, (t,), np.int32) for t in (7, 13)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "draft:llama_tiny,spec_k:3,draft_seed:7")
        try:
            got = _serve_tokens(fw, prompts)
            for i, w in enumerate(want):
                assert got[i] == w, f"stream {i}"
        finally:
            fw.close()

    def test_accept_rate_zero_bit_identity(self):
        """Force every proposal off the target's argmax: each round
        emits ONLY the bonus token (the plain-decode degenerate case)
        — still bit-identical, with zero accepted proposals."""
        rng = np.random.default_rng(18)
        prompt = rng.integers(1, 450, (9,), np.int32)
        # enumerate the greedy continuation far past max_new, pick a
        # proposal id the target can never argmax inside this run
        cont = _plain_tokens(
            prompt, "max_new:32,stream_chunk:2,temperature:0.0,"
            "dtype:float32")
        dead = next(t for t in range(451, 512) if t not in cont)
        want = _plain_tokens(prompt, BASE)
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "draft:llama_tiny,spec_k:3")
        try:
            serve_loop = None
            got = {0: []}
            lock = threading.Lock()

            def emit(t, m):
                with lock:
                    got[0].append(int(t[0][0]))

            # wrap _propose AFTER the loop exists (first submit builds
            # it) — run one warm stream first, then patch
            fw.submit([prompt], {}, lambda t, m: None)
            assert fw.drain(120)
            a0 = _metric("llm.serve.spec_accepted")
            serve_loop = fw._serve
            real = serve_loop._propose

            def all_rejected(dp, tp, tk, pool, tables, pos, keys):
                props, dprobs, pool = real(dp, tp, tk, pool, tables,
                                           pos, keys)
                import jax.numpy as jnp

                return jnp.full_like(props, dead), dprobs, pool

            serve_loop._propose = all_rejected
            fw.submit([prompt], {}, emit)
            assert fw.drain(120)
            serve_loop._propose = real
        finally:
            fw.close()
        assert got[0] == want
        assert _metric("llm.serve.spec_accepted") == a0

    def test_bit_identity_at_max_seq_edge(self):
        """Final-round regression: the fixed [slots, k+1]-wide verify
        dispatches even when fewer tokens remain, so positions reach
        max_seq-1+k — the table must span them (serving_plan widens
        max_blocks by spec_k) or the stale-table clamp zeroes the live
        row's context and the LAST tokens go bit-wrong."""
        cfg16 = "max_new:16,stream_chunk:2,temperature:0.0,dtype:float32"
        rng = np.random.default_rng(25)
        # T=240 + max_new 16 == llama_tiny's max_seq 256 exactly; with
        # block_size 16 / prefill_chunk 32 the unwidened table would end
        # at position 256 and the verify at pos 252..255 would overrun
        prompt = rng.integers(1, 500, (240,), np.int32)
        want = _plain_tokens(prompt, cfg16)
        assert len(want) == 16
        fw = _fw(cfg16 + ",serve:continuous,slots:2,block_size:16,"
                 "prefill_chunk:32,draft:llama_tiny,spec_k:4,"
                 "draft_seed:7")
        try:
            got = _serve_tokens(fw, [prompt])
            assert got[0] == want, (got[0], want)
        finally:
            fw.close()

    def test_spec_with_prefix_sharing(self):
        """Speculation and sharing compose: the draft pool's blocks are
        shared/forked alongside the target's, greedy ids stay exact."""
        rng = np.random.default_rng(19)
        pa, pb = _shared_prompts(rng, prefix_len=16, suffixes=(3, 6))
        want = [_plain_tokens(p, BASE) for p in (pa, pb)]
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:8,draft:llama_tiny,spec_k:3,"
                 "draft_seed:7")
        h0 = _metric("llm.serve.prefix_hits")
        try:
            got = _serve_staggered(fw, [pa, pb])
            assert got[0] == want[0] and got[1] == want[1]
            assert _metric("llm.serve.prefix_hits") > h0
            assert sorted(fw._serve.kv.free) == \
                list(range(fw._serve.n_blocks))
        finally:
            fw.close()

    def test_preset_only_and_continuous_only_are_rejected(self):
        """draft: still demands a preset zoo name + the continuous
        loop; temperature > 0 is NO LONGER rejected (speculative
        rejection sampling, docs/SERVING.md §4d) — pinned by the
        sampled-spec tests in tests/test_sampling.py."""
        from nnstreamer_tpu.filters.base import FrameworkError

        with pytest.raises(FrameworkError, match="preset"):
            _fw("serve:continuous,temperature:0.0,draft:/tmp/x.gguf")
        with pytest.raises(FrameworkError, match="serve:continuous"):
            _fw("temperature:0.0,draft:llama_tiny")
        # sampled + draft constructs (the old greedy-only guard is gone)
        fw = _fw("serve:continuous,temperature:0.8,draft:llama_tiny")
        fw.close()


# ---------------------------------------------------------------------------
# zero-recompile census
# ---------------------------------------------------------------------------

class TestSpecCensus:
    def test_five_program_pin_across_churn(self):
        """serving_plan() predicts 5 programs under speculation; churn
        with new lengths, cache hits, CoW forks, and every accept ratio
        must compile NOTHING new — and the plain decode chunk must
        never compile at all."""
        from nnstreamer_tpu.filters.llm import serving_plan

        cfg = llama.PRESETS["llama_tiny"]
        plan = serving_plan(cfg, slots=3, block_size=8, prefill_chunk=4,
                            draft_cfg=cfg, spec_k=3, dtype="float32")
        assert plan["programs"] == 5
        assert plan["draft_pool_bytes"] > 0
        rng = np.random.default_rng(20)
        fw = _fw(BASE + ",serve:continuous,slots:3,block_size:8,"
                 "prefill_chunk:4,draft:llama_tiny,spec_k:3,"
                 "draft_seed:7")
        try:
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32)])
            serve = fw._serve
            names = ("_prefill", "_set_tok", "_draft_prefill",
                     "_propose", "_verify")
            warm = {n: getattr(serve, n)._cache_size() for n in names}
            assert warm == {n: 1 for n in names}, warm
            assert serve._decode._cache_size() == 0
            p = rng.integers(1, 500, (24,), np.int32)
            _serve_tokens(fw, [p])
            _serve_tokens(fw, [p, p])  # hits + CoW forks
            _serve_tokens(fw, [rng.integers(1, 500, (t,), np.int32)
                               for t in (1, 7, 13)])
            after = {n: getattr(serve, n)._cache_size() for n in names}
            assert after == warm, f"recompile on churn: {warm}->{after}"
            assert serve._decode._cache_size() == 0
        finally:
            fw.close()

    def test_xray_census_drift_zero_with_spec_active(self):
        """nns-xray's live census: the enlarged 5-program budget is
        installed when speculation is on, and churn + cache hits + CoW
        forks + accept/reject keep measured drift at exactly 0."""
        from nnstreamer_tpu.utils.xray import ProgramRegistry

        reg = ProgramRegistry()
        rng = np.random.default_rng(23)
        pre = rng.integers(1, 500, (16,), np.int32)
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:4,draft:llama_tiny,spec_k:3,"
                 "draft_seed:7", model="llama_tiny")
        fw.attach_xray(reg, "llm")
        try:
            for wave in range(2):  # churn + hits + forks
                prompts = [
                    np.concatenate([pre, rng.integers(1, 500, (t,),
                                                      np.int32)])
                    for t in (2, 5)] + [pre.copy()]
                _serve_tokens(fw, prompts)
            census = reg.census()
            kinds = ("prefill", "set_tok", "draft_prefill", "propose",
                     "verify")
            for kind in kinds:
                e = census[f"llm.serve/{kind}"]
                assert e["predicted"] == 1
                assert e["live_compiles"] == 1, (kind, e)
                assert e["within"]
            assert len([k for k in census if k.startswith("llm.serve/")]) \
                == len(kinds)
            assert reg.drift_count() == 0
        finally:
            fw.close()

    def test_sharing_keeps_three_program_pin(self):
        """Without a draft the census stays 3 — prefix hits and forks
        are host values."""
        rng = np.random.default_rng(21)
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "prefill_chunk:4")
        try:
            p = rng.integers(1, 500, (24,), np.int32)
            _serve_tokens(fw, [p])
            serve = fw._serve
            warm = {n: getattr(serve, n)._cache_size()
                    for n in ("_decode", "_prefill", "_set_tok")}
            assert warm == {"_decode": 1, "_prefill": 1, "_set_tok": 1}
            _serve_tokens(fw, [p, p])  # hits + forks
            after = {n: getattr(serve, n)._cache_size()
                     for n in ("_decode", "_prefill", "_set_tok")}
            assert after == warm
        finally:
            fw.close()


# ---------------------------------------------------------------------------
# pipeline-native accept/reject routing
# ---------------------------------------------------------------------------

class TestSpecRouting:
    def test_tensor_if_meta_value_gates_spec_flag(self):
        from nnstreamer_tpu.core.buffer import Buffer
        from nnstreamer_tpu.elements.cond import TensorIf

        el = TensorIf({"compared_value": "META_VALUE",
                       "compared_value_option": "spec_draft",
                       "operator": "GE", "supplied_value": "1"})
        el.configure({"sink": None}, ["src_0", "src_1"])
        acc = Buffer([np.asarray([3], np.int32)],
                     meta={"spec_draft": 1})
        bonus = Buffer([np.asarray([4], np.int32)],
                       meta={"spec_draft": 0})
        unstamped = Buffer([np.asarray([5], np.int32)])
        assert el.process("sink", acc) == [("src_0", acc)]
        assert el.process("sink", bonus) == [("src_1", bonus)]
        assert el.process("sink", unstamped) == [("src_1", unstamped)]

    def test_demux_by_meta_routes_whole_buffer(self):
        from nnstreamer_tpu.core.buffer import Buffer
        from nnstreamer_tpu.core.caps import Caps
        from nnstreamer_tpu.elements.routing import TensorDemux

        el = TensorDemux({"by-meta": "spec_draft"})
        el.configure({"sink": Caps.any()}, ["src_0", "src_1"])
        acc = Buffer([np.asarray([3], np.int32),
                      np.asarray([9], np.uint8)],
                     meta={"spec_draft": 1})
        bonus = Buffer([np.asarray([4], np.int32)],
                       meta={"spec_draft": 0})
        out = el.process("sink", acc)
        assert out == [("src_1", acc)]  # whole buffer, both tensors
        assert len(out[0][1].tensors) == 2
        assert el.process("sink", bonus) == [("src_0", bonus)]
        # out-of-range / junk meta clamps to src_0, never raises
        junk = Buffer([np.asarray([1], np.int32)],
                      meta={"spec_draft": "nan?"})
        assert el.process("sink", junk)[0][0] == "src_0"

    def test_serve_loop_stamps_spec_draft(self):
        rng = np.random.default_rng(22)
        prompt = rng.integers(1, 500, (6,), np.int32)
        fw = _fw(BASE + ",serve:continuous,slots:2,block_size:8,"
                 "draft:llama_tiny,spec_k:3")
        metas = {}
        try:
            _serve_tokens(fw, [prompt], metas=metas)
        finally:
            fw.close()
        # every round token carries the flag (the prefill-sampled first
        # token predates any proposal and is unstamped)
        assert all("spec_draft" in m for m in metas[0][1:])


# ---------------------------------------------------------------------------
# deep lint pricing
# ---------------------------------------------------------------------------

class TestSpecDeepLint:
    DESC = ("appsrc name=src ! tensor_filter framework=llm "
            "model=llama_small custom=max_new:16,serve:continuous,"
            "slots:4,block_size:16,kv_blocks:64,draft:llama_tiny,"
            "spec_k:4 invoke-dynamic=true ! tensor_sink name=out")

    def test_draft_params_pool_and_census_priced(self):
        import nnstreamer_tpu as nt

        rep = nt.analyze(self.DESC, deep=True)
        stage = rep.resources.stages[0]
        assert stage.variants == 5
        assert stage.draft_param_bytes > 0
        assert stage.draft_pool_bytes > 0
        # the draft rides the params/kv_pool ledger categories (what
        # nns-xray reconciles measured bytes against)
        tiny = llama.PRESETS["llama_tiny"]
        small = llama.PRESETS["llama_small"]
        dcfg = llama.resolve_config(
            "llama_tiny", {"vocab": small.vocab,
                           "max_seq": small.max_seq})
        assert stage.draft_param_bytes == llama.param_bytes_estimate(
            dcfg, param_dtype="float32")
        del tiny
        text = rep.resources.render()
        assert "draft params" in text and "draft pool" in text

    def test_unresolvable_draft_warns(self):
        import nnstreamer_tpu as nt

        rep = nt.analyze(self.DESC.replace("draft:llama_tiny",
                                           "draft:nope"), deep=True)
        assert any(d.code == "serving-unpriced"
                   and "draft" in d.message for d in rep.diagnostics)

    def test_reconfig_table_covers_spec_knobs(self):
        from nnstreamer_tpu.utils import elastic

        assert elastic.SERVE_KNOB_SIGNATURE["draft"] is True
        assert elastic.SERVE_KNOB_SIGNATURE["spec_k"] is True
        assert elastic.SERVE_KNOB_SIGNATURE["prefix_cache"] is False


# ---------------------------------------------------------------------------
# the first token is committed by the target's prefill program (PR 37)
# ---------------------------------------------------------------------------
class TestSpecFirstTokenInPrefill:
    SPEC = (BASE + ",serve:continuous,slots:2,block_size:8,prefill_chunk:4,"
            "draft:llama_tiny,spec_k:3,draft_seed:7")

    def test_multi_chunk_prompts_commit_once_and_streams_are_greedy(self):
        """The speculative loop admits through the same ``prefill_step``:
        prompts of one, two and four chunks (the third into a recycled
        slot) each commit ONE first token, streams stay the plain greedy
        decode's, and the two eager setters left on the admission path
        (``tok_prev``, the position twin) are all ``_set_tok`` is called
        for — the token itself no longer goes through it."""
        rng = np.random.default_rng(21)
        prompts = [rng.integers(1, 500, (t,), np.int32) for t in (3, 7, 14)]
        want = [_plain_tokens(p, BASE) for p in prompts]
        n0 = _metric("llm.serve.first_token_in_prefill")
        fw = _fw(self.SPEC)
        try:
            # the loop is up and warm after one request
            _serve_tokens(fw, [np.asarray([5, 6], np.int32)])
            serve = fw._serve
            sets, commits = [], []
            real_set, real_prefill = serve._set_tok, serve._prefill

            def set_tok(a, i, v):
                sets.append((int(i), int(v)))
                return real_set(a, i, v)

            def prefill(*a):
                commits.append(int(a[4][3]) < 2)
                return real_prefill(*a)

            serve._set_tok, serve._prefill = set_tok, prefill
            got = _serve_tokens(fw, prompts)
            census = [f._cache_size() for f in (
                real_prefill, real_set, serve._draft_prefill,
                serve._propose, serve._verify)]
        finally:
            fw.close()
        for i, w in enumerate(want):
            assert got[i] == w, f"stream {i}"
        assert census == [1] * 5
        # 1 + 2 + 4 chunks, three of them final
        assert len(commits) == 7 and sum(commits) == 3
        assert _metric("llm.serve.first_token_in_prefill") - n0 == 4
        # an admission: tok_prev <- the prompt's last token, pos <- T;
        # a retirement: pos <- the park position.  Never a sampled token.
        admitted = [(int(p[-1]), len(p)) for p in prompts]
        values = [v for _i, v in sets]
        for last, T in admitted:
            assert last in values and T in values
        assert len(sets) == 3 * 2 + 3
