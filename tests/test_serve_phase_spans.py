"""Phase spans of the continuous serve loop (ISSUE 26): one tracing
system with two sinks.  With ``trace_mode=ring`` every iteration that
progressed records a ``serve.iter`` whose children tile it, request-bound
spans carry the request's trace id, and ``serve.queue`` ends where
``serve.prefill`` starts; with ``trace_mode=off`` nothing is recorded and
no profiler annotation is even constructed.  Toy decoder, CPU.
"""

import os
import time

import numpy as np
import pytest

import nnstreamer_tpu as nt
from nnstreamer_tpu.utils import tracing
from nnstreamer_tpu.utils.tracing import FlightRecorder, recorder

STAGE = "llm.serve"
#: the children that tile a serve.iter; serve.decode by design spans
#: dispatch -> materialization and overlaps serve.first_token
TILING = {"serve.intake", "serve.admit_pass", "serve.prefill_chunk",
          "serve.first_token", "serve.emit"}
PROMPTS = [np.array([1, 5, 9, 2], np.int32),
           np.arange(1, 20, dtype=np.int32),       # three prefill chunks
           np.array([7, 7, 3], np.int32)]          # waits for a free slot
MAX_NEW = 6


def _desc(extra=""):
    return ("appsrc name=src ! tensor_filter framework=llm "
            f"model=llama_tiny custom=max_new:{MAX_NEW},serve:continuous,"
            "slots:2,temperature:0.0,block_size:8,prefill_chunk:8,"
            f"stream_chunk:2{extra} invoke-dynamic=true name=f ! "
            "tensor_sink name=out")


#: what the loop's accounting said at the end of the last ``_serve``
POOL_STATS = {}


def _serve(trace_mode, extra="", idle_s=0.2):
    """Three prompts through two slots; returns (pulled buffers, serve
    spans at the last token, serve spans after ``idle_s`` more — the
    last token is pulled while the loop is still inside the delivery
    that pushed it, so only the later list holds that iteration whole)."""
    recorder.configure("off")
    recorder.clear()
    p = nt.Pipeline(_desc(extra), trace_mode=trace_mode)
    with p:
        for x in PROMPTS:
            p.push("src", x)
        bufs = [p.pull("out", timeout=120)
                for _ in range(MAX_NEW * len(PROMPTS))]
        at_last = [e for e in recorder.events() if e.stage == STAGE]
        time.sleep(idle_s)
        after = [e for e in recorder.events() if e.stage == STAGE]
        POOL_STATS.clear()
        POOL_STATS.update(p.element("f").fw._serve.pool_stats())
        p.eos("src")
        p.wait(timeout=120)
    recorder.configure("off")
    recorder.clear()
    return bufs, at_last, after


@pytest.fixture(scope="module")
def traced():
    bufs, _at_last, spans = _serve("ring")
    return bufs, spans, dict(POOL_STATS)


def _by_iter(spans):
    out = {}
    for e in spans:
        if e.args and "iter" in e.args:
            out.setdefault(e.args["iter"], []).append(e)
    return out


def _inside(e, parent):
    return parent.ts <= e.ts and e.ts + e.dur <= parent.ts + parent.dur


def test_every_iteration_holds_its_children_without_overlap(traced):
    _bufs, spans, _stats = traced
    iters = _by_iter(spans)
    assert len(iters) >= 3
    parent_of = {}
    for it, group in iters.items():
        parents = [e for e in group if e.kind == "serve.iter"]
        assert len(parents) == 1, (it, [e.kind for e in group])
        parent_of[it] = parents[0]
    for it, group in iters.items():
        kids = sorted((e for e in group if e.kind != "serve.iter"),
                      key=lambda e: e.ts)
        assert {e.kind for e in kids} >= {"serve.intake",
                                          "serve.admit_pass"}
        for e in kids:
            if e.kind == "serve.emit" and not _inside(e, parent_of[it]):
                # since PR 32 a chunk's delivery may be put off until the
                # next chunk is dispatched: it then lies in the NEXT
                # iteration, after that iteration's first tokens
                assert _inside(e, parent_of[it + 1]), (it, e)
                continue
            assert _inside(e, parent_of[it]), (it, e)
        # serve.admit is the tail of one prompt's admission, inside the pass
        passes = [e for e in kids if e.kind == "serve.admit_pass"]
        for e in kids:
            if e.kind == "serve.admit":
                assert _inside(e, passes[0])
    # the tiles that lie in one iteration, whichever chunk they deliver
    for parent in parent_of.values():
        tiles = sorted((e for e in spans
                        if e.kind in TILING and _inside(e, parent)),
                       key=lambda e: e.ts)
        for a, b in zip(tiles, tiles[1:]):
            assert a.ts + a.dur <= b.ts, (parent, a, b)


def test_children_sum_to_no_more_than_the_parent(traced):
    _bufs, spans, _stats = traced
    for parent in (e for e in spans if e.kind == "serve.iter"):
        assert sum(e.dur for e in spans
                   if e.kind in TILING and _inside(e, parent)) <= parent.dur


def test_iter_is_monotonic_dense_and_shared(traced):
    _bufs, spans, stats = traced
    parents = [e for e in spans if e.kind == "serve.iter"]
    nums = [e.args["iter"] for e in sorted(parents, key=lambda e: e.ts)]
    assert nums == list(range(1, len(nums) + 1))
    for e in spans:
        if e.kind in TILING | {"serve.decode", "serve.admit"}:
            assert e.args["iter"] in nums, e
    # an iteration that decoded delivered: decode and emit share its number
    dec = {e.args["iter"] for e in spans if e.kind == "serve.decode"}
    assert dec and dec == {e.args["iter"] for e in spans
                           if e.kind == "serve.emit"}
    # since PR 29 the parent also gauges the blocks live in each pool (the
    # window pool's stay 0 on a model without window layers)
    assert set(parents[-1].args) == {"iter", "live", "waiting",
                                     "full_blocks", "win_blocks"}
    # a constant of the deployment is the accounting's, not every span's
    assert stats["conv_state_bytes"] == 0
    assert all(e.args["win_blocks"] == 0 for e in parents)
    assert max(e.args["full_blocks"] for e in parents) > 0


def test_the_chunk_that_commits_the_first_token_says_so(traced):
    """``sampled`` is 1 on a prompt's final ``serve.prefill_chunk`` (its
    program sampled and committed the first token) and 0 on every chunk
    before it; there is one such chunk for every ``serve.first_token``."""
    _bufs, spans, _stats = traced
    chunks = [e for e in spans if e.kind == "serve.prefill_chunk"]
    assert len(chunks) == 5                 # prompts of 1, 3 and 1 chunks
    for e in chunks:
        assert e.args["sampled"] == int(e.args["final"]), e
    firsts = [e for e in spans if e.kind == "serve.first_token"]
    assert sum(e.args["sampled"] for e in chunks) == len(firsts) \
        == len(PROMPTS)
    # a request's committing chunk is dispatched before its first token
    # is fetched, in the same iteration
    for f in firsts:
        mine = [e for e in chunks if e.tid == f.tid and e.args["sampled"]]
        assert len(mine) == 1
        assert mine[0].ts + mine[0].dur <= f.ts
        assert mine[0].args["iter"] == f.args["iter"]


def test_the_counter_equals_the_first_token_spans():
    from nnstreamer_tpu.core.log import metrics

    n0 = metrics.snapshot().get("llm.serve.first_token_in_prefill", 0.0)
    _bufs, _at_last, spans = _serve("ring")
    n = metrics.snapshot()["llm.serve.first_token_in_prefill"] - n0
    assert n == len(PROMPTS) == sum(
        1 for e in spans if e.kind == "serve.first_token")


def test_each_request_has_one_queue_and_one_prefill_that_meet(traced):
    bufs, spans, _stats = traced
    tids = {b.meta[tracing.META_TRACE_ID] for b in bufs}
    assert len(tids) == len(PROMPTS)
    for tid in tids:
        q = [e for e in spans if e.kind == "serve.queue" and e.tid == tid]
        pf = [e for e in spans if e.kind == "serve.prefill"
              and e.tid == tid]
        assert len(q) == 1 and len(pf) == 1, tid
        assert q[0].ts + q[0].dur == pf[0].ts
        assert q[0].args["tid"] == tid == pf[0].args["tid"]
        assert set(q[0].args) == {"tid", "slot", "tokens", "blocks",
                                  "shared"}
        assert set(pf[0].args) == {"tid", "slot", "chunks"}
        # the request's other spans carry the same id, in args too
        mine = {e.kind for e in spans if e.tid == tid}
        assert mine >= {"serve.admit", "serve.prefill_chunk",
                        "serve.first_token"}
        assert all(e.args["tid"] == tid for e in spans if e.tid == tid)
    chunks = sorted(e.args["chunks"] for e in spans
                    if e.kind == "serve.prefill")
    assert chunks == [1, 1, 3]
    # the third prompt found both slots taken: it waited at least the
    # iteration that freed one
    waits = sorted(e.dur for e in spans if e.kind == "serve.queue")
    assert waits[-1] > waits[0]


def test_decode_wait_is_part_of_the_decode_span(traced):
    _bufs, spans, _stats = traced
    dec = [e for e in spans if e.kind == "serve.decode"]
    assert dec
    for e in dec:
        assert 0 <= e.args["wait_ns"] <= e.dur
        # the jitted call's own host time, stamped at its return: the
        # chip had nothing before it, and the span covers it
        assert 0 < e.args["dispatch_ns"] <= e.dur
        assert e.args["dispatch_ns"] + e.args["wait_ns"] <= e.dur
        assert e.args["chunk"] == 2 and e.args["occupancy"] >= 1
    # the wait is an annotation alone: the ring keeps the whole decode
    assert not [e for e in spans if e.kind.endswith(".wait")]
    emits = [e for e in spans if e.kind == "serve.emit"]
    assert sum(e.args["tokens"] for e in emits) == \
        (MAX_NEW - 1) * len(PROMPTS)
    assert sum(e.args["retired"] for e in emits) == len(PROMPTS)


def test_a_sparse_models_decode_span_carries_the_five_expert_counts():
    """``serve.decode`` of a model with experts: the four routing counts
    and ``moe_weight_passes`` — the grouped kernel's passes over an
    expert's matrices, 0 here, where ``ragged_dot`` computes the experts
    (the kernel is the TPU's path) — from the fifth extra row of the
    chunk's token matrix."""
    recorder.configure("off")
    recorder.clear()
    p = nt.Pipeline(
        "appsrc name=src ! tensor_filter framework=llm "
        "model=hybrid_moe_tiny custom=max_new:5,serve:continuous,slots:2,"
        "temperature:0.0,block_size:8,prefill_chunk:8,stream_chunk:2 "
        "invoke-dynamic=true ! tensor_sink name=out", trace_mode="ring")
    with p:
        p.push("src", PROMPTS[0])
        for _ in range(5):
            p.pull("out", timeout=120)
        time.sleep(0.2)
        dec = [e.args for e in recorder.events()
               if e.stage == STAGE and e.kind == "serve.decode"]
        p.eos("src")
        p.wait(timeout=120)
    recorder.configure("off")
    recorder.clear()
    assert dec
    for a in dec:
        assert {k for k in a if k.startswith("moe_")} == {
            "moe_pairs", "moe_experts_hit", "moe_max_per_expert",
            "moe_zero_pairs", "moe_weight_passes"}
        assert a["moe_weight_passes"] == 0 < a["moe_experts_hit"]


def test_deliveries_under_the_next_chunk_are_marked_and_counted():
    """``serve.emit`` says on which side of the next dispatch it ran
    (``ahead``), and ``llm.serve.deliver_ahead`` counts the same chunks."""
    from nnstreamer_tpu.core.log import metrics

    n0 = metrics.snapshot().get("llm.serve.deliver_ahead", 0.0)
    _bufs, _at_last, spans = _serve("ring")
    counted = metrics.snapshot().get("llm.serve.deliver_ahead", 0.0) - n0
    emits = sorted((e for e in spans if e.kind == "serve.emit"),
                   key=lambda e: e.ts)
    assert all(set(e.args) == {"iter", "tokens", "retired", "ahead"}
               for e in emits)
    assert {e.args["ahead"] for e in emits} == {0, 1}
    assert sum(e.args["ahead"] for e in emits) == counted
    decodes = {e.args["iter"]: e for e in spans if e.kind == "serve.decode"}
    for e in emits:
        nxt = decodes.get(e.args["iter"] + 1)
        if e.args["ahead"]:
            # the next chunk was dispatched before this delivery began
            assert nxt is not None and nxt.ts <= e.ts, e
        else:
            assert nxt is None or e.ts + e.dur <= nxt.ts, e
    # the last chunk of all ended a stream with nothing queued
    assert emits[-1].args["ahead"] == 0 and emits[-1].args["retired"] >= 1


def test_an_idle_delivery_is_known_by_where_it_lies(traced):
    """What ``serve_gap_emit_pct`` reads needs no arg of its own: the
    delivery made with nothing queued on the chip is the ``ahead`` = 0
    span inside the iteration that dispatched its chunk, after that
    chunk closed and before anything else is dispatched; one put off
    lies in the NEXT iteration, under its chunk."""
    _bufs, spans, _stats = traced
    parent_of = {e.args["iter"]: e for e in spans if e.kind == "serve.iter"}
    decode_of = {e.args["iter"]: e for e in spans
                 if e.kind == "serve.decode"}
    emits = [e for e in spans if e.kind == "serve.emit"]
    idle = [e for e in emits if _inside(e, parent_of[e.args["iter"]])]
    assert idle and len(idle) < len(emits)
    for e in idle:
        dec = decode_of[e.args["iter"]]
        assert e.args["ahead"] == 0 and dec.ts + dec.dur <= e.ts
        # nothing was dispatched between the chunk's close and its end
        assert not [x for x in spans
                    if x.kind in ("serve.decode", "serve.prefill_chunk")
                    and dec.ts + dec.dur <= x.ts < e.ts + e.dur]
    for e in emits:
        if e.args["ahead"]:
            nxt = decode_of[e.args["iter"] + 1]
            assert nxt.ts <= e.ts and _inside(
                e, parent_of[e.args["iter"] + 1])
    # the first stream ended with the third prompt waiting for its slot:
    # that chunk's delivery was put off
    first_end = min((e for e in emits if e.args["retired"]),
                    key=lambda e: e.ts)
    assert first_end not in idle


def test_an_idle_loop_records_nothing():
    _bufs, at_last, after = _serve("ring", idle_s=0.4)
    # twenty spins of the idle loop later, at most the iteration that
    # was closing when the last token was pulled has been added
    n0 = sum(1 for e in at_last if e.kind == "serve.iter")
    n1 = sum(1 for e in after if e.kind == "serve.iter")
    assert n1 - n0 <= 1
    last = max(after, key=lambda e: e.ts + e.dur)
    assert last.kind == "serve.iter" and last.args["live"] >= 0


def test_span_kinds_names_every_kind_the_loop_records(traced):
    _bufs, spans, _stats = traced
    kinds = {e.kind for e in spans}
    assert kinds >= TILING | {"serve.iter", "serve.decode", "serve.admit",
                              "serve.queue", "serve.prefill"}
    assert kinds <= set(tracing.SPAN_KINDS), kinds - set(tracing.SPAN_KINDS)


def test_tracing_switched_on_between_admission_and_first_token(monkeypatch):
    """A request admitted while the recorder was off carries no admission
    stamp: its later spans have no id, it gets no ``serve.prefill``, and
    the loop runs on."""
    from nnstreamer_tpu.filters import llm

    count = llm.metrics.count
    admissions = []

    def switch_on_at_second_admission(name, *a, **kw):
        if name == "llm.serve.prefill_tokens":   # counted mid-admission
            admissions.append(name)
            if len(admissions) == 2:
                recorder.configure("ring")
        return count(name, *a, **kw)

    n_new = 48  # the first stream is still decoding when the second comes
    recorder.configure("off")
    recorder.clear()
    p = nt.Pipeline(_desc().replace(f"max_new:{MAX_NEW}",
                                    f"max_new:{n_new}"), trace_mode="ring")
    with p:
        recorder.configure("off")
        monkeypatch.setattr(llm.metrics, "count",
                            switch_on_at_second_admission)
        p.push("src", PROMPTS[0])
        bufs = [p.pull("out", timeout=120)]
        p.push("src", PROMPTS[1])    # three iterations of prefill
        bufs += [p.pull("out", timeout=120) for _ in range(2 * n_new - 1)]
        time.sleep(0.2)
        spans = [e for e in recorder.events() if e.stage == STAGE]
        p.eos("src")
        p.wait(timeout=120)
    recorder.configure("off")
    recorder.clear()
    assert len(bufs) == 2 * n_new and len(admissions) == 2
    kinds = [e.kind for e in spans]
    assert "serve.queue" not in kinds and "serve.prefill" not in kinds
    # its first chunk ran untraced, in the iteration that admitted it
    assert kinds.count("serve.prefill_chunk") == 2
    assert kinds.count("serve.first_token") == 1
    assert all(e.tid is None for e in spans)
    emits = [e for e in spans if e.kind == "serve.emit"]
    assert sum(e.args["retired"] for e in emits) == 2


class _Counting:
    """Stands in for ``jax.profiler.TraceAnnotation``."""
    made = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, dict(kw)
        _Counting.made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.kw.update(kw)


def test_off_mode_records_nothing_and_constructs_no_annotation(monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    _Counting.made = []
    bufs, _at_last, spans = _serve("off")
    assert len(bufs) == MAX_NEW * len(PROMPTS)
    assert spans == [] and len(recorder) == 0
    assert _Counting.made == []
    assert all(tracing.META_TRACE_ID not in b.meta for b in bufs)
    # and the same run traced does construct them, under the spans' names
    _serve("ring")
    names = {a.name for a in _Counting.made}
    assert names >= TILING | {"serve.iter", "serve.admit",
                              "serve.decode.wait"}
    emit = next(a for a in _Counting.made if a.name == "serve.emit")
    assert set(emit.kw) == {"iter", "tokens", "retired", "ahead"}


class _CountingClock:
    """Stands in for the ``time`` module in ``filters/llm.py``."""

    def __init__(self):
        self.ns_calls = 0

    def monotonic_ns(self):
        self.ns_calls += 1
        return time.monotonic_ns()

    def __getattr__(self, name):
        return getattr(time, name)


def test_off_mode_takes_no_stamp_for_the_dispatch(monkeypatch):
    """The dispatch's return is stamped only where a recorder is live:
    with ``trace_mode=off`` the loop reads the nanosecond clock once a
    decode dispatch (``t_dec``, as before) and once an admission, and
    that is all; traced, once more for every dispatch."""
    from nnstreamer_tpu.filters import llm

    dispatches = []
    real_init = llm._ContinuousLoop.__init__

    def init(self, fw):
        real_init(self, fw)
        decode = self._decode

        def counted(*a, **kw):
            dispatches.append(1)
            return decode(*a, **kw)

        self._decode = counted

    monkeypatch.setattr(llm._ContinuousLoop, "__init__", init)
    clock = _CountingClock()
    monkeypatch.setattr(llm, "time", clock)
    bufs, _at_last, spans = _serve("off")
    assert len(bufs) == MAX_NEW * len(PROMPTS) and spans == []
    # a loop's first call of the program is its warm-up, outside the loop
    n_off = len(dispatches) - 1
    assert n_off > 0 and clock.ns_calls == n_off + len(PROMPTS)
    off = clock.ns_calls
    _bufs, _at_last, spans = _serve("ring")
    n_ring = len(dispatches) - n_off - 2
    assert n_ring == sum(1 for e in spans if e.kind == "serve.decode")
    # t_dec and the stamp at the call's return (an admission's stamp is
    # its span's own start there)
    assert clock.ns_calls - off == 2 * n_ring


def test_speculative_rounds_carry_iter_and_wait():
    _bufs, _at_last, spans = _serve("ring", ",draft:llama_tiny,spec_k:2")
    rounds = [e for e in spans if e.kind == "serve.spec_verify"]
    assert rounds and not [e for e in spans if e.kind == "serve.decode"]
    for e in rounds:
        assert 0 <= e.args["wait_ns"] <= e.dur and e.args["k"] == 2
        assert 0 < e.args["dispatch_ns"] <= e.dur
    assert {e.args["iter"] for e in rounds} == \
        {e.args["iter"] for e in spans if e.kind == "serve.emit"}
    assert sum(e.args["tokens"] for e in spans if e.kind == "serve.emit") \
        == (MAX_NEW - 1) * len(PROMPTS)


def test_span_helper_feeds_both_sinks(monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    _Counting.made = []
    rec = FlightRecorder("full")
    with tracing.span(rec, "serve.emit", "s", iter=3) as sp:
        sp.note(tokens=4)
    held = tracing.span(rec, "serve.intake", "s", 77, iter=3).begin()
    held.end(hold=True, n=1)
    assert len(rec) == 1                      # held: not in the ring yet
    held.commit()
    a, b = rec.events()
    assert (a.kind, a.tid, a.args) == ("serve.emit", None,
                                       {"iter": 3, "tokens": 4})
    assert (b.kind, b.tid, b.args) == ("serve.intake", 77,
                                       {"iter": 3, "n": 1, "tid": 77})
    assert a.dur > 0 and b.ts >= a.ts + a.dur
    assert [(x.name, x.kw) for x in _Counting.made] == [
        ("serve.emit", {"iter": 3, "tokens": 4}),
        ("serve.intake", {"iter": 3, "n": 1, "tid": 77})]


def test_profiler_trace_asks_for_annotations_not_python_calls(
        monkeypatch, tmp_path):
    import jax

    from nnstreamer_tpu.utils import profiler

    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: seen.update(
                            d=d, o=profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: seen.update(stopped=True))
    with profiler.trace(str(tmp_path)):
        pass
    assert seen["stopped"] and seen["d"] == str(tmp_path)
    assert seen["o"].host_tracer_level == 1
    assert seen["o"].python_tracer_level == 0


#: a profile as plain data: three programs on the device, the serve
#: thread's annotations on the host, both on one clock (ns)
PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [("jit_decode_chunk(11)", 0, 1000),
                        ("jit_prefill_step(22)", 1400, 300),
                        ("jit_decode_chunk(11)", 2000, 1000),
                        ("jit_decode_chunk(11)", 3500, 100)],
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(...)", 0, 900)],
    },
    "/host:CPU": {
        "python": [("serve.iter", 900, 1500), ("serve.emit", 1000, 700),
                   ("serve.admit_pass", 1750, 100),
                   ("serve.decode.wait", 2450, 500),
                   ("PjitFunction(decode_chunk)", 1900, 50)],
        "other": [("serve.iter", 0, 10)],
    },
}


def test_gaps_are_named_by_the_innermost_annotation():
    from nnstreamer_tpu.tools import trace as cli

    r = cli.gaps_by_phase(PLANES)
    assert r["devices"] == 1 and r["host_line"] == ("/host:CPU", "python", 4)
    assert r["window_s"] == pytest.approx(3600e-9)
    assert r["idle_s"] == pytest.approx((400 + 300 + 500) * 1e-9)
    # 1000-1400 lies wholly inside serve.emit; 1700-2000 is split between
    # serve.iter's own time (1700-1750, 1850-2000) and serve.admit_pass;
    # 3000-3500 is after every annotation closed
    assert r["by_next"]["before jit_prefill_step"] == \
        pytest.approx({"serve.emit": 400e-9})
    assert r["by_next"]["before jit_decode_chunk"] == pytest.approx(
        {"serve.iter": 200e-9, "serve.admit_pass": 100e-9,
         cli.UNNAMED: 500e-9})
    assert r["by_phase"] == pytest.approx(
        {"serve.emit": 400e-9, "serve.iter": 200e-9,
         "serve.admit_pass": 100e-9, cli.UNNAMED: 500e-9})
    assert sum(r["by_phase"].values()) == pytest.approx(r["idle_s"])
    assert cli.gaps_by_phase({"/host:CPU": PLANES["/host:CPU"]}) == {
        "devices": 0, "host_line": ("/host:CPU", "python", 4)}


def test_gaps_cli_reads_a_profile_taken_here(tmp_path, capsys):
    """A profile of a traced toy run, taken with ``profiler.trace``, holds
    the serve annotations; on the CPU there is no TPU plane, which the
    subcommand says instead of printing an empty table."""
    import glob

    from nnstreamer_tpu.tools import trace as cli
    from nnstreamer_tpu.utils import profiler

    with profiler.trace(str(tmp_path)):
        _serve("ring")
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    names = {name for lines in cli.load_xplane(files[0]).values()
             for evs in lines.values() for name, _s, _d in evs}
    assert names >= TILING | {"serve.iter", "serve.decode.wait"}
    assert cli.main(["gaps", str(tmp_path)]) == 1
    assert "no device plane" in capsys.readouterr().err
    assert cli.main(["gaps", str(tmp_path / "nothing")]) == 1


def test_the_second_tracer_is_gone():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    word = "NNSTPU_" + "SERVE_TRACE"
    hits = []
    for top in ("nnstreamer_tpu", "tests", "tools", "docs", "benchmark",
                "examples"):
        for base, _dirs, files in os.walk(os.path.join(root, top)):
            for name in files:
                if name.endswith((".py", ".md", ".json", ".txt", ".ini")):
                    with open(os.path.join(base, name),
                              errors="replace") as f:
                        if word in f.read():
                            hits.append(os.path.join(base, name))
    assert hits == []
    with open(os.path.join(root, "nnstreamer_tpu", "filters",
                           "llm.py")) as f:
        src = f.read()
    assert "_tr(" not in src and "self._span(" not in src
