"""Production sampling in the serve hot loop (docs/SERVING.md §4d).

The sampled decode path must behave like a PRODUCT feature, not a
demo knob:

* **Distribution**: speculative rejection sampling emits tokens
  distributed EXACTLY as the non-spec sampler — chi-squared here
  against the target marginal by driving the module-level
  ``spec_rejection_commit`` core directly (thousands of independent
  slot keys in ONE call, no serve loop needed).
* **Reproducibility**: a stream's sampled tokens are a pure function
  of (framework seed, admission number, absolute position) — two
  same-seed runs are bitwise identical, and batch composition
  (sequential vs concurrent admission) changes nothing.
* **Elasticity**: drain/adopt carries the slot's PRNG key in the
  snapshot, so a migrated sampled stream continues bit-identically.
* **Census**: the sampler adds ZERO programs — greedy and sampled
  loops share one signature (the key folds are dead code XLA drops at
  temperature 0), so the 3-program (non-spec) and 5-program (spec)
  zero-recompile pins hold with temperature > 0.
* **Traffic**: the fused verify commits on-device; the host reads back
  only the emitted rows + accept counts, and the per-round
  device->host set never contains the proposals or any re-upload.
"""

import collections
import threading

import numpy as np
import pytest

from nnstreamer_tpu.models import llama


def _fw(custom, model="llama_tiny"):
    from nnstreamer_tpu.filters.llm import LLMFramework

    fw = LLMFramework()
    fw.open({"model": model, "custom": custom})
    return fw


def _serve_tokens(fw, prompts, timeout=300.0):
    got = {i: [] for i in range(len(prompts))}
    lock = threading.Lock()

    def emit_for(i):
        def emit(tensors, meta):
            with lock:
                got[i].append(int(tensors[0][0]))
        return emit

    for i, p in enumerate(prompts):
        fw.submit([p], {}, emit_for(i))
    assert fw.drain(timeout=timeout)
    return got


class Collector:
    def __init__(self):
        self.toks = []
        self.done = threading.Event()

    def __call__(self, tensors, meta):
        self.toks.append((int(tensors[0][0]) if len(tensors[0]) else -1,
                          dict(meta)))
        if meta.get("stream_last"):
            self.done.set()

    @property
    def ids(self):
        return [t for t, m in self.toks if t >= 0]

    @property
    def sid(self):
        return self.toks[0][1].get("stream_id") if self.toks else None


SAMPLED = ("max_new:8,stream_chunk:2,temperature:0.9,seed:5,"
           "dtype:float32,serve:continuous,slots:2,block_size:8,"
           "prefill_chunk:4")
SPEC = SAMPLED + ",draft:llama_tiny,spec_k:3,draft_seed:7"


# ---------------------------------------------------------------------------
# rejection sampling is distribution-exact (the §4d guarantee)
# ---------------------------------------------------------------------------

class TestRejectionSamplingDistribution:
    """Drive spec_rejection_commit with a known target/draft pair over
    thousands of independent slot keys and chi-square the emitted
    marginals against the TARGET distribution — the draft must steer
    speed, never the law.  Fixed seeds: deterministic, not flaky."""

    V, K, B = 8, 3, 20000
    CHI2_999 = 26.02  # chi-square df=7 critical value at p = 0.999

    def _run(self, pt_row, q_row, *, seed=7):
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.filters.llm import spec_rejection_commit

        B, K, V = self.B, self.K, self.V
        pt = jnp.broadcast_to(jnp.asarray(pt_row, jnp.float32),
                              (B, K + 1, V))
        dprobs = jnp.broadcast_to(jnp.asarray(q_row, jnp.float32),
                                  (B, K, V))
        # proposals drawn FROM the draft distribution, as propose() does
        props = jax.random.categorical(
            jax.random.PRNGKey(seed + 1),
            jnp.log(jnp.asarray(q_row, jnp.float32)),
            shape=(B, K)).astype(jnp.int32)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), B),
                          np.uint32)
        pos = jnp.asarray(np.arange(B) % 97 + 4, jnp.int32)
        live = jnp.ones((B,), bool)
        em, acc = spec_rejection_commit(pt, dprobs, props, keys, pos, live)
        return np.asarray(em), np.asarray(acc), np.asarray(props)

    def _chi2(self, draws, probs):
        counts = np.bincount(draws, minlength=self.V).astype(np.float64)
        expected = len(draws) * np.asarray(probs, np.float64)
        return float(((counts - expected) ** 2 / expected).sum())

    def test_mismatched_draft_still_emits_target_marginal(self):
        """Draft mass concentrated where the target's is thin: low
        accept rate, but position 0's emitted token (accepted proposal
        OR residual resample) must still be ~ pt."""
        pt_row = np.asarray([.30, .22, .16, .12, .08, .06, .04, .02])
        q_row = pt_row[::-1].copy()  # adversarially misaligned
        em, acc, _ = self._run(pt_row, q_row)
        assert self._chi2(em[:, 0], pt_row) < self.CHI2_999
        # the mismatch must actually exercise the rejection path
        assert 0.05 < float((acc > 0).mean()) < 0.95

    def test_matched_draft_accepts_everything(self):
        """q == p: u*q < p is u < 1, always true — every proposal
        accepts, em carries the proposals verbatim, and the bonus
        column (position k) is itself a clean target draw."""
        pt_row = np.asarray([.30, .22, .16, .12, .08, .06, .04, .02])
        em, acc, props = self._run(pt_row, pt_row)
        assert (acc == self.K).all()
        assert np.array_equal(em[:, :self.K], props)
        assert self._chi2(em[:, self.K], pt_row) < self.CHI2_999

    def test_parked_rows_commit_nothing(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.filters.llm import spec_rejection_commit

        pt_row = np.full((self.V,), 1.0 / self.V)
        em, acc, _ = self._run(pt_row, pt_row)
        # same inputs with every row parked: acc pinned to 0
        import jax

        pt = jnp.broadcast_to(jnp.asarray(pt_row, jnp.float32),
                              (4, self.K + 1, self.V))
        dprobs = pt[:, :self.K]
        props = jnp.zeros((4, self.K), jnp.int32)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 4),
                          np.uint32)
        pos = jnp.full((4,), 9, jnp.int32)
        _, acc0 = spec_rejection_commit(
            pt, dprobs, props, keys, pos, jnp.zeros((4,), bool))
        assert (np.asarray(acc0) == 0).all()


# ---------------------------------------------------------------------------
# seeded reproducibility: position-keyed draws
# ---------------------------------------------------------------------------

class TestSeededReproducibility:
    def _prompts(self):
        rng = np.random.default_rng(31)
        return [rng.integers(1, 500, (t,), np.int32) for t in (3, 6)]

    @pytest.mark.parametrize("custom", [SAMPLED, SPEC],
                             ids=["plain", "spec"])
    def test_two_runs_bitwise_identical(self, custom):
        pa, pb = self._prompts()
        runs = []
        for _ in range(2):
            fw = _fw(custom)
            try:
                runs.append(_serve_tokens(fw, [pa, pb]))
            finally:
                fw.close()
        assert runs[0] == runs[1]
        assert len(runs[0][0]) == 8  # it actually decoded

    def test_seed_changes_the_stream(self):
        pa, pb = self._prompts()
        fw = _fw(SAMPLED)
        try:
            base = _serve_tokens(fw, [pa, pb])
        finally:
            fw.close()
        fw = _fw(SAMPLED.replace("seed:5", "seed:6"))
        try:
            other = _serve_tokens(fw, [pa, pb])
        finally:
            fw.close()
        assert base != other

    @pytest.mark.parametrize("custom", [SAMPLED, SPEC],
                             ids=["plain", "spec"])
    def test_batch_composition_independence(self, custom):
        """Tokens are keyed by (slot key, absolute position), NOT by
        decode-round batch state: admitting the two prompts together
        (concurrent rounds) and one after the other (solo rounds) emits
        identical streams — admission ORDER fixes the slot keys."""
        pa, pb = self._prompts()
        fw = _fw(custom)
        try:
            together = _serve_tokens(fw, [pa, pb])
        finally:
            fw.close()
        fw = _fw(custom)
        try:
            solo_a = _serve_tokens(fw, [pa])[0]
            solo_b = _serve_tokens(fw, [pb])[0]
        finally:
            fw.close()
        assert together[0] == solo_a
        assert together[1] == solo_b


# ---------------------------------------------------------------------------
# drain/adopt carries the slot PRNG
# ---------------------------------------------------------------------------

class TestSampledDrainAdopt:
    def test_sampled_stream_migrates_bit_identically(self):
        prompt = np.asarray([3, 5, 7, 9], np.int32)
        ref_c = Collector()
        fw_ref = _fw(SAMPLED)
        fw_ref.submit([prompt], {}, ref_c)
        assert ref_c.done.wait(120)
        ref = ref_c.ids

        fw_a, fw_b = _fw(SAMPLED), _fw(SAMPLED)
        got = Collector()
        seen3 = threading.Event()

        def emit_a(tensors, meta):
            got(tensors, meta)
            if len(got.toks) >= 3:
                seen3.set()

        fw_a.submit([prompt], {}, emit_a)
        assert seen3.wait(120)
        snap = fw_a.drain_stream(got.sid, timeout=60)
        assert snap["kind"] == "live" and snap["greedy"] is False
        # the slot's key rides the snapshot — the §4d migration contract
        assert len(snap["prng_key"]) == 2

        cont = Collector()
        fw_b.adopt_stream(snap, cont)
        assert cont.done.wait(120)
        assert got.ids[:snap["sidx"]] + cont.ids == ref, \
            (got.ids[:snap["sidx"]], cont.ids, ref)
        for fw in (fw_ref, fw_a, fw_b):
            fw.close()


# ---------------------------------------------------------------------------
# census: the sampler adds zero programs
# ---------------------------------------------------------------------------

class TestSampledCensus:
    def test_three_program_pin_nonspec(self):
        from nnstreamer_tpu.filters.llm import serving_plan

        plan = serving_plan(llama.PRESETS["llama_tiny"], slots=2,
                            block_size=8, prefill_chunk=4,
                            dtype="float32", temperature=0.9)
        assert plan["programs"] == 3
        assert plan["prng_state_bytes"] == 2 * 2 * 4
        rng = np.random.default_rng(40)
        fw = _fw(SAMPLED)
        try:
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32)])
            serve = fw._serve
            warm = {n: getattr(serve, n)._cache_size()
                    for n in ("_decode", "_prefill", "_set_tok")}
            assert warm == {"_decode": 1, "_prefill": 1, "_set_tok": 1}
            _serve_tokens(fw, [rng.integers(1, 500, (t,), np.int32)
                               for t in (1, 5, 7)])
            after = {n: getattr(serve, n)._cache_size()
                     for n in ("_decode", "_prefill", "_set_tok")}
            assert after == warm, f"sampler recompiled: {warm}->{after}"
        finally:
            fw.close()

    def test_five_program_pin_spec(self):
        rng = np.random.default_rng(41)
        fw = _fw(SPEC)
        try:
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32)])
            serve = fw._serve
            names = ("_prefill", "_set_tok", "_draft_prefill",
                     "_propose", "_verify")
            warm = {n: getattr(serve, n)._cache_size() for n in names}
            assert warm == {n: 1 for n in names}, warm
            assert serve._decode._cache_size() == 0
            _serve_tokens(fw, [rng.integers(1, 500, (t,), np.int32)
                               for t in (1, 5, 9)])
            after = {n: getattr(serve, n)._cache_size() for n in names}
            assert after == warm, f"sampler recompiled: {warm}->{after}"
            assert serve._decode._cache_size() == 0
        finally:
            fw.close()


# ---------------------------------------------------------------------------
# fused verify: host round-trip budget
# ---------------------------------------------------------------------------

class TestVerifyTransferBudget:
    def test_proposals_never_leave_the_device(self, monkeypatch):
        """The fused verify commits tok/tok_prev/positions in-program;
        the ONLY per-round device->host reads are the emitted rows
        [slots, k+1] and the accept counts [slots].  In particular the
        [slots, k] proposals — which the pre-fusion loop downloaded to
        run host-side acceptance — must never be fetched, and nothing
        batch-shaped is re-uploaded through the slot-token setter
        during steady decode."""
        import jax

        from nnstreamer_tpu.filters import llm as llm_mod

        real_np = llm_mod.np
        xfer = collections.Counter()

        class NpProxy:
            def __getattr__(self, name):
                val = getattr(real_np, name)
                if name == "asarray":
                    def asarray(a, *args, **kw):
                        if isinstance(a, jax.Array):
                            xfer[(tuple(a.shape), str(a.dtype))] += 1
                        return val(a, *args, **kw)
                    return asarray
                return val

        monkeypatch.setattr(llm_mod, "np", NpProxy())
        rng = np.random.default_rng(50)
        fw = _fw(SPEC)  # slots:2, spec_k:3
        try:
            # the loop is lazily built on first submit — force it now so
            # the counting wrapper is in place before ANY admission
            fw._serve = llm_mod._ContinuousLoop(fw)
            set_tok_calls = []
            real_set = fw._serve._set_tok

            def counting_set(*a, **kw):
                set_tok_calls.append(1)
                return real_set(*a, **kw)

            counting_set._cache_size = real_set._cache_size
            fw._serve._set_tok = counting_set
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32),
                               rng.integers(1, 500, (5,), np.int32)])
            admission_set_calls = len(set_tok_calls)
            em, acc = ((2, 4), "int32"), ((2,), "int32")
            # [slots, k] proposals never crossed to host
            assert ((2, 3), "int32") not in xfer, dict(xfer)
            # emitted rows + accept counts did — ONE pair per verify
            # round, plus the warmup round's emitted rows (its accept
            # count is discarded on device); the only other transfers
            # are the (2,)-uint32 PRNG key mints at init/admission
            assert xfer[acc] >= 2, dict(xfer)
            assert xfer[em] == xfer[acc] + 1, dict(xfer)
        finally:
            fw.close()
        # _set_tok traffic is per-EVENT (admission/retire), not
        # per-round: decoding 4x more tokens adds zero calls
        fw = _fw(SPEC.replace("max_new:8", "max_new:32"))
        try:
            fw._serve = llm_mod._ContinuousLoop(fw)
            set_tok_calls2 = []
            real_set2 = fw._serve._set_tok

            def counting_set2(*a, **kw):
                set_tok_calls2.append(1)
                return real_set2(*a, **kw)

            counting_set2._cache_size = real_set2._cache_size
            fw._serve._set_tok = counting_set2
            _serve_tokens(fw, [rng.integers(1, 500, (3,), np.int32),
                               rng.integers(1, 500, (5,), np.int32)])
            assert len(set_tok_calls2) == admission_set_calls
        finally:
            fw.close()


# ---------------------------------------------------------------------------
# the first token is sampled and committed inside the prefill program
# ---------------------------------------------------------------------------

def _recipe_stream(fw, prompt, adm_no, n):
    """The stream the serving contract (docs/SERVING.md §4d) prescribes
    for ``prompt`` as the loop's ``adm_no``-th admission, computed with no
    serve loop: the slot key is fold_in(PRNGKey(seed), adm_no) and the
    token at absolute position p is drawn from the filtered logits with
    that key folded at (p, TAG_SAMPLE).  The first token, at position T,
    through ``sample_token`` — what the loop called eagerly before the
    prefill program took the draw over."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.llm import TAG_SAMPLE

    slot_key = jax.random.fold_in(jax.random.PRNGKey(fw.seed), adm_no)
    toks, out = [int(t) for t in prompt], []
    for _ in range(n):
        logits = llama.forward(fw.bundle.params, jnp.asarray([toks]),
                               fw.cfg, compute_dtype=fw.dtype)[:, -1]
        k = jax.random.fold_in(
            jax.random.fold_in(slot_key, len(toks)), TAG_SAMPLE)
        out.append(int(llama.sample_token(
            logits, k, fw.temperature, fw.top_k, fw.top_p)[0]))
        toks.append(out[-1])
    return out, [int(v) for v in np.asarray(slot_key, np.uint32)]


FIRST = ("max_new:6,stream_chunk:2,dtype:float32,serve:continuous,slots:2,"
         "block_size:8,prefill_chunk:4")


class TestFirstTokenInPrefill:
    @pytest.mark.parametrize("sampler", [
        "temperature:0.0,seed:3",
        "temperature:0.9,seed:5",
        "temperature:0.7,top_k:12,seed:11",
        "temperature:1.1,top_p:0.8,seed:2",
        "temperature:0.8,top_k:40,top_p:0.9,seed:7"],
        ids=["greedy", "temperature", "top_k", "top_p", "top_k_top_p"])
    def test_streams_are_the_recipes_token_for_token(self, sampler):
        """Three admissions (single-chunk and multi-chunk prompts, the
        third into a recycled slot): every stream, from its FIRST token
        on, is what (seed, admission number, positions) prescribe."""
        rng = np.random.default_rng(60)
        prompts = [rng.integers(1, 500, (t,), np.int32) for t in (3, 9, 5)]
        fw = _fw(f"{FIRST},{sampler}")
        try:
            want = [_recipe_stream(fw, p, i, 6)[0]
                    for i, p in enumerate(prompts)]
            # one after the other: the admission numbers are 0, 1, 2
            got = [_serve_tokens(fw, [p])[0] for p in prompts]
        finally:
            fw.close()
        assert got == want

    @pytest.mark.parametrize("custom", [SAMPLED, SPEC],
                             ids=["plain", "spec"])
    def test_first_token_and_snapshot_key_are_the_recipes(self, custom):
        """The slot key the program derived comes home with the first
        token: a drained stream's snapshot carries
        fold_in(PRNGKey(seed), admission number), word for word, and the
        token that was sampled with it is the recipe's."""
        prompt = np.asarray([3, 5, 7, 9, 11], np.int32)
        fw = _fw(custom.replace("max_new:8", "max_new:64"))
        try:
            # admission 0 runs to its end; the drained stream is number 1
            _serve_tokens(fw, [np.asarray([2, 4], np.int32)])
            (first,), key = _recipe_stream(fw, prompt, 1, 1)
            got = Collector()
            seen = threading.Event()

            def emit(tensors, meta):
                got(tensors, meta)
                seen.set()

            fw.submit([prompt], {}, emit)
            assert seen.wait(120)
            snap = fw.drain_stream(got.sid, timeout=60)
        finally:
            fw.close()
        assert got.ids[0] == first
        assert snap["prng_key"] == key
