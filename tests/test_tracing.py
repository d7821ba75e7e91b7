"""nns-trace flight recorder + metrics pipeline (ISSUE 5 tentpole).

The contract: with ``trace_mode != off`` every buffer gets a trace id at
source ingress that survives tee/demux/collator fan-out and batching;
batched dispatch spans LINK every member row's id; the ring evicts oldest
first; Chrome dumps schema-validate and are monotonic in ``ts``; watchdog
fires dump the recent window; and with ``trace_mode=off`` the recorder is
structurally bypassed (zero events, zero meta stamps).  Plus the metrics
pipeline: real Prometheus histograms, sampler gauges, bounded thread-safe
reservoirs, and a /metrics server with clean shutdown.
"""

import json
import logging
import threading
import time
import urllib.request

import numpy as np
import pytest

import nnstreamer_tpu as nt
from nnstreamer_tpu.core.log import LATENCY_BUCKETS, Metrics, metrics
from nnstreamer_tpu.utils import tracing
from nnstreamer_tpu.utils.profiler import (metrics_server, metrics_text,
                                           start_metrics_server,
                                           stop_metrics_server)
from nnstreamer_tpu.utils.tracing import (FlightRecorder, recorder,
                                          to_chrome, validate_chrome)
from nnstreamer_tpu.utils.watchdog import Watchdog

DESC = (
    "appsrc name=src caps=other/tensors,dimensions=16,types=float32 ! "
    "tensor_filter framework=jax model=scaler custom=scale:2.0,dims:16 "
    "name=f ! tensor_sink name=out"
)


@pytest.fixture(autouse=True)
def _clean_state():
    metrics.reset()
    recorder.configure("off")
    recorder.clear()
    yield
    recorder.configure("off")
    recorder.clear()
    metrics.reset()


def _frames(n, dims=16):
    return [np.full((dims,), float(i), np.float32) for i in range(n)]


def _run(desc, frames, timeout=60, **kw):
    p = nt.Pipeline(desc, **kw)
    outs = []
    with p:
        for i, x in enumerate(frames):
            p.push("src", nt.Buffer([x], pts=i))
        for _ in frames:
            outs.append(p.pull("out", timeout=timeout))
        p.eos()
        p.wait(timeout=timeout)
    return outs


# -- recorder primitives ---------------------------------------------------

def test_ring_eviction_order():
    rec = FlightRecorder("ring", capacity=8)
    for i in range(20):
        rec.record("stage", "s", i, ts_ns=i * 1000, dur_ns=10)
    evs = rec.events()
    assert len(evs) == 8
    assert [e.tid for e in evs] == list(range(12, 20))  # oldest evicted
    assert [e.ts for e in evs] == sorted(e.ts for e in evs)


def test_full_mode_unbounded():
    rec = FlightRecorder("full")
    for i in range(tracing.DEFAULT_RING_CAPACITY // 8):
        rec.record("stage", "s", i, i, 1)
    assert len(rec) == tracing.DEFAULT_RING_CAPACITY // 8
    rec.configure("ring", capacity=16)
    assert len(rec.events()) == 16  # re-bounding keeps the newest


def test_bad_mode_rejected():
    with pytest.raises(ValueError, match="off|ring|full"):
        FlightRecorder().configure("sometimes")
    from nnstreamer_tpu.pipeline.runtime import PipelineError

    with pytest.raises(PipelineError, match="trace_mode"):
        nt.Pipeline(DESC, trace_mode="sometimes")


def test_recent_window():
    rec = FlightRecorder("ring", capacity=64)
    rec.record("stage", "old", 1, ts_ns=0, dur_ns=1000)
    rec.record("stage", "new", 2, ts_ns=int(9e9), dur_ns=1000)
    spans = rec.recent(seconds=1.0)
    assert [e.stage for e in spans] == ["new"]


# -- lanes: a span is evicted only by later spans of its own stage ----------

def _serve_like(rec, n=10, t0=1_000):
    """``n`` closed spans of the serve loop's stage, 1 µs apart."""
    for i in range(n):
        rec.record("serve.decode", "llm.serve", None, ts_ns=t0 + i * 1000,
                   dur_ns=500, iter=i)


def test_per_token_spans_cannot_evict_the_serve_loops():
    """(i) 300,000 spans of a sink, more than the capacity, leave the ten
    spans the serve loop recorded before them where they were, and the
    sum of the lanes never passes the capacity."""
    cap = tracing.DEFAULT_RING_CAPACITY
    rec = FlightRecorder("ring")            # the default capacity
    _serve_like(rec)
    for i in range(300_000):
        rec.record("e2e", "out", i, 100_000 + i, 1)
        if i % 50_000 == 0:
            assert len(rec) == min(cap, i + 11)
    evs = rec.events()
    assert [e.args["iter"] for e in evs if e.stage == "llm.serve"] \
        == list(range(10))
    out = [e.tid for e in evs if e.stage == "out"]
    assert out == list(range(300_000 - len(out), 300_000))
    assert len(rec) == len(evs) == cap


def test_within_a_stage_the_oldest_closed_goes_first():
    """(ii) What ``benchmark/ring_spans.covered_window`` argues from:
    every span of a stage that ended after the earliest surviving one is
    still there, whatever the other stages recorded meanwhile — and a
    lane is never cut below its share, capacity / lanes."""
    rec = FlightRecorder("ring", capacity=8)
    for i in range(20):
        rec.record("serve.decode", "llm.serve", None, ts_ns=i * 1000,
                   dur_ns=900, iter=i)
        for j in range(50):
            rec.record("fetch", "out", j, i * 1000 + j, 1)
        assert len(rec) == min(8, 51 * (i + 1))
    mine = [e for e in rec.events() if e.stage == "llm.serve"]
    assert [e.args["iter"] for e in mine] == list(range(16, 20))
    first_end = min(e.ts + e.dur for e in mine)
    assert all(e.ts + e.dur >= first_end for e in mine)
    assert [e.tid for e in rec.events() if e.stage == "out"] \
        == list(range(46, 50))


def test_many_stages_share_one_bound():
    """The always-on mode's host memory does not grow with the number of
    elements: forty stages that each record past the capacity hold the
    capacity between them, in equal shares."""
    rec = FlightRecorder("ring", capacity=4096)
    for i in range(6000):
        for s in range(40):
            rec.record("stage", f"el{s}", i, i * 1000 + s, 10)
    assert len(rec) == 4096
    sizes = [len(lane) for lane in rec._lanes.values()]
    assert len(sizes) == 40 and max(sizes) - min(sizes) <= 4096 >> 8


def test_concurrent_records_hold_the_bound():
    """No lock on the hot path: four threads recording into three lanes
    raise nothing, and the sum ends at the bound but for the steps that
    raced (a trim counts anew)."""
    import threading

    rec = FlightRecorder("ring", capacity=2048)
    errors = []

    def work(k):
        try:
            for i in range(20_000):
                rec.record("stage", f"el{k % 3}", i, i * 1000 + k, 10)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    rec.record("stage", "el0", 0, 0, 1)     # a quiet record settles it
    rec._trim()
    assert len(rec) == 2048
    assert min(len(lane) for lane in rec._lanes.values()) >= 2048 // 3 - 64


def test_readers_see_every_lane_as_one_list_in_close_order(caplog):
    """(iii) ``events``, ``recent``, ``len``, ``clear``, ``configure``,
    ``to_chrome`` and the watchdog's dump over three lanes."""
    rec = FlightRecorder("ring", capacity=16)
    now = time.monotonic_ns()
    rec.record("serve.iter", "llm.serve", None, now - 9_000_000, 8_000_000)
    rec.record("queue", "f", 1, now - 8_000_000, 1_000_000)
    rec.record("e2e", "out", 1, now - 8_000_000, 5_000_000)
    # recorded last from stamps, closed first
    rec.record("serve.queue", "llm.serve", 1, now - 20_000_000_000, 1_000)
    evs = rec.events()
    assert [e.kind for e in evs] == ["serve.queue", "queue", "e2e",
                                     "serve.iter"]
    assert [e.ts + e.dur for e in evs] == sorted(e.ts + e.dur for e in evs)
    assert len(rec) == 4
    assert [e.kind for e in rec.recent(1.0)] == ["queue", "e2e", "serve.iter"]
    obj = to_chrome(evs)
    assert validate_chrome(obj) == []
    assert {m["args"]["name"] for m in obj["traceEvents"]
            if m["name"] == "thread_name"} == {"llm.serve", "f", "out"}
    with caplog.at_level(logging.ERROR, logger="test.tracing"):
        n = tracing.dump_recent_to_log(logging.getLogger("test.tracing"),
                                       seconds=1.0, rec=rec)
    assert n == 3 and "serve.iter" in caplog.text and "e2e" in caplog.text
    # the lanes live through a switch; a smaller bound trims the longest
    lanes = dict(rec._lanes)
    rec.configure("ring", capacity=16)
    assert all(rec._lanes[k] is v for k, v in lanes.items())
    rec.configure("ring", capacity=3)
    assert sorted(e.kind for e in rec.events()) == ["e2e", "queue",
                                                    "serve.queue"]
    rec.configure("full")
    for i in range(40):
        rec.record("fetch", "out", i, now + i, 1)
    assert len(rec) == 43
    rec.configure("off")
    assert len(rec.events()) == 43          # still readable, post-mortem
    rec.clear()
    assert len(rec) == 0 and rec.events() == [] and rec.recent(1.0) == []


def test_two_lanes_survive_dump_load_and_merge(tmp_path):
    rec = FlightRecorder("ring", capacity=7)
    _serve_like(rec, n=3)
    for i in range(9):
        rec.record("e2e", "out", 100 + i, 2_000 + i * 1000, 10)
    path = str(tmp_path / "a.ring")
    assert tracing.dump_ring(path, rec, proc="server") == 7
    ring = tracing.load_ring(path)
    assert ring["spans"] == rec.events() and ring["proc"] == "server"
    assert [s.stage for s in ring["spans"]].count("llm.serve") == 3
    obj, stats = tracing.merge_rings([ring, ring])
    assert stats["spans"] == 14 and validate_chrome(obj) == []
    tracks = {(m["pid"], m["args"]["name"]) for m in obj["traceEvents"]
              if m["name"] == "thread_name"}
    assert tracks == {(1, "llm.serve"), (1, "out"),
                      (2, "llm.serve"), (2, "out")}


# -- trace-id propagation --------------------------------------------------

def test_trace_ids_assigned_and_unique():
    outs = _run(DESC, _frames(12), trace_mode="ring")
    tids = [o.meta.get(tracing.META_TRACE_ID) for o in outs]
    assert all(isinstance(t, int) for t in tids)
    assert len(set(tids)) == 12
    kinds = {e.kind for e in recorder.events()}
    assert {"ingress", "queue", "stage", "e2e", "fetch"} <= kinds


def test_off_mode_zero_events_and_clean_meta():
    """The instrumentation pin: with trace_mode=off the recorder must be
    STRUCTURALLY bypassed — record() monkeypatched to raise, pipeline
    still completes, no meta stamps written.  Tenant threading (ISSUE 8)
    rides the same pin: a Pipeline-level default tenant adds NO stamp on
    the off path either."""

    def boom(*a, **k):
        raise AssertionError("record() ran with trace_mode=off")

    orig = FlightRecorder.record
    FlightRecorder.record = boom
    try:
        outs = _run(DESC, _frames(8), queue_capacity=16, batch_max=4,
                    tenant="acme")
    finally:
        FlightRecorder.record = orig
    assert len(recorder.events()) == 0
    for o in outs:
        for key in (tracing.META_TRACE_ID, tracing.META_INGRESS_NS,
                    tracing.META_ENQUEUE_NS, tracing.META_TENANT):
            assert key not in o.meta


def test_tee_fanout_shares_trace_id():
    p = nt.Pipeline(
        "videotestsrc num-buffers=2 width=4 height=4 ! tensor_converter ! "
        "tee name=t t. ! tensor_sink name=a t. ! tensor_sink name=b",
        trace_mode="ring")
    with p:
        a = p.pull("a", timeout=15)
        b = p.pull("b", timeout=15)
        p.wait(timeout=15)
    assert a.meta[tracing.META_TRACE_ID] == b.meta[tracing.META_TRACE_ID]
    # both sinks recorded e2e spans for the SAME frame identity
    e2e = [e for e in recorder.events() if e.kind == "e2e"]
    assert {e.stage for e in e2e} == {"a", "b"}


def test_demux_fanout_shares_trace_id():
    p = nt.Pipeline(
        "appsrc name=src ! tensor_demux name=d "
        "d.src_0 ! tensor_sink name=a d.src_1 ! tensor_sink name=b",
        trace_mode="ring")
    with p:
        p.push("src", [np.zeros((2,), np.float32),
                       np.ones((3,), np.float32)])
        a = p.pull("a", timeout=15)
        b = p.pull("b", timeout=15)
        p.eos()
        p.wait(timeout=15)
    assert a.meta[tracing.META_TRACE_ID] == b.meta[tracing.META_TRACE_ID]


def test_collator_links_member_trace_ids():
    p = nt.Pipeline(
        "appsrc name=a caps=other/tensors,dimensions=4,types=float32 ! "
        "mux.sink_0 "
        "appsrc name=b caps=other/tensors,dimensions=4,types=float32 ! "
        "mux.sink_1 "
        "tensor_mux name=mux ! tensor_sink name=out", trace_mode="ring")
    x = np.ones((4,), np.float32)
    with p:
        p.push("a", x)
        p.push("b", 2 * x)
        out = p.pull("out", timeout=15)
        p.eos()
        p.wait(timeout=15)
    ing = {e.stage: e.tid for e in recorder.events() if e.kind == "ingress"}
    assert set(ing) == {"a", "b"}
    mux_spans = [e for e in recorder.events()
                 if e.kind == "stage" and e.stage == "mux"
                 and e.args and e.args.get("trace_ids")]
    assert mux_spans, "collation must record a linked stage span"
    assert set(mux_spans[0].args["trace_ids"]) == set(ing.values())
    assert out.meta[tracing.META_TRACE_ID] in ing.values()


@pytest.mark.parametrize("k", list(range(1, 9)))
def test_batch_span_linkage_all_occupancies(k):
    """At every backlog size 1..8 the union of linked trace ids across
    the filter's dispatch spans covers EVERY pushed buffer exactly, and
    each linked span's id count equals its row count — per-row
    attribution survives whatever occupancy partition the race produced."""
    outs = _run(DESC, _frames(k), queue_capacity=16, batch_max=8,
                trace_mode="ring")
    pushed = {o.meta[tracing.META_TRACE_ID] for o in outs}
    assert len(pushed) == k
    covered = set()
    for e in recorder.events():
        if e.kind != "stage" or e.stage != "f":
            continue
        linked = (e.args or {}).get("trace_ids")
        if linked:
            assert len(linked) == e.args["rows"]
            assert e.args["per_row_ns"] * e.args["rows"] <= e.dur + 1
            covered |= set(linked)
        else:
            covered.add(e.tid)
    assert covered == pushed


# -- Chrome export ---------------------------------------------------------

def test_chrome_dump_schema_and_monotonic(tmp_path):
    p = nt.Pipeline(DESC, queue_capacity=16, batch_max=8,
                    trace_mode="ring")
    frames = _frames(16)
    with p:
        for i, x in enumerate(frames):
            p.push("src", nt.Buffer([x], pts=i))
        for _ in frames:
            p.pull("out", timeout=60)
        p.eos()
        p.wait(timeout=60)
    path = tmp_path / "trace.json"
    n = p.dump_trace(str(path))
    assert n == len(recorder.events())
    obj = json.loads(path.read_text())
    assert validate_chrome(obj) == []
    tss = [e["ts"] for e in obj["traceEvents"]]
    assert tss == sorted(tss)  # monotonic in ts
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"src", "f", "out"} <= names
    # batch spans carry their member links into the JSON + flow arrows
    linked = [e for e in obj["traceEvents"]
              if (e.get("args") or {}).get("trace_ids")]
    assert linked
    flows = [e for e in obj["traceEvents"] if e.get("cat") == "row-link"]
    assert {f["ph"] for f in flows} <= {"s", "f"}


def test_validate_chrome_catches_problems():
    assert validate_chrome([]) != []
    assert validate_chrome({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [
        {"ph": "X", "ts": 5.0, "pid": 1, "tid": 1, "name": "a", "dur": 1.0},
        {"ph": "X", "ts": 1.0, "pid": 1, "tid": 1, "name": "b", "dur": -2.0},
    ]}
    problems = validate_chrome(bad)
    assert any("monotonic" in p for p in problems)
    assert any("dur" in p for p in problems)


def test_to_chrome_empty():
    obj = to_chrome([])
    assert validate_chrome(obj) == []


def test_cli_validate_and_summary(tmp_path, capsys):
    from nnstreamer_tpu.tools import trace as trace_cli

    _run(DESC, _frames(6), trace_mode="ring")
    path = tmp_path / "t.json"
    tracing.dump_chrome(recorder.events(), str(path))
    assert trace_cli.main(["validate", str(path)]) == 0
    assert trace_cli.main(["summary", str(path)]) == 0
    out = capsys.readouterr().out
    assert "OK:" in out and "stage" in out
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert trace_cli.main(["validate", str(bad)]) == 1


# -- post-mortem dumps -----------------------------------------------------

def test_watchdog_fire_dumps_stalled_stage_span(caplog):
    recorder.configure("ring")
    recorder.record("stage", "stalled_stage", 7,
                    time.monotonic_ns(), 2_000_000)
    fired = threading.Event()
    wd = Watchdog(0.05, fired.set)
    with caplog.at_level(logging.ERROR,
                         logger="nnstreamer_tpu.utils.watchdog"):
        wd.arm()
        assert fired.wait(5.0)
        wd.disarm()
    assert "flight recorder" in caplog.text
    assert "stalled_stage" in caplog.text
    assert "watchdog fired" in caplog.text


def test_record_error_dumps_ring(caplog):
    recorder.configure("ring")
    recorder.record("stage", "exploding_stage", 9,
                    time.monotonic_ns(), 1_000_000)
    p = nt.Pipeline(DESC)
    with caplog.at_level(logging.ERROR):
        p._record_error("f", RuntimeError("boom"))
    assert "exploding_stage" in caplog.text
    assert "boom" in caplog.text


def test_dump_recent_noop_when_off(caplog):
    recorder.configure("off")
    log = logging.getLogger("test.tracing")
    with caplog.at_level(logging.ERROR):
        assert tracing.dump_recent_to_log(log) == 0
    assert "flight recorder" not in caplog.text


# -- metrics pipeline ------------------------------------------------------

def test_histogram_exposition_cumulative():
    metrics.observe_latency("t.proc", 0.003)
    metrics.observe_latency("t.proc", 0.0004)
    metrics.observe_latency("t.proc", 99.0)  # lands in +Inf
    text = metrics_text()
    assert "# TYPE nnstpu_t_proc histogram" in text
    assert "# HELP nnstpu_t_proc" in text
    assert 'nnstpu_t_proc_bucket{le="0.0005"} 1' in text
    assert 'nnstpu_t_proc_bucket{le="0.005"} 2' in text
    assert 'nnstpu_t_proc_bucket{le="10"} 2' in text
    assert 'nnstpu_t_proc_bucket{le="+Inf"} 3' in text
    assert "nnstpu_t_proc_count 3" in text
    hists = metrics.histograms()
    counts, total, n = hists["t.proc"]
    assert n == 3 and sum(counts) == 3
    assert total == pytest.approx(99.0034)
    assert len(counts) == len(LATENCY_BUCKETS) + 1


def test_histogram_and_gauge_name_collisions_disambiguated():
    """Sanitized-name collisions get the same deterministic hash-suffix
    treatment in every sample family (counters had it; histograms and
    gauges must not silently emit duplicate series)."""
    metrics.observe_latency("a.b:c", 0.001)
    metrics.observe_latency("a.b/c", 0.002)
    metrics.gauge("g.x:y", 1.0)
    metrics.gauge("g.x/y", 2.0)
    text = metrics_text()
    counts = [line.split()[0] for line in text.splitlines()
              if line and not line.startswith("#")]
    assert len(counts) == len(set(counts)), "duplicate series emitted"
    assert sum("nnstpu_a_b_c_" in line and "_count" in line
               for line in text.splitlines()) == 2


def test_off_pipeline_isolated_from_global_recorder():
    """A trace_mode=off pipeline must not record spans even while another
    pipeline's ring mode has the process-global recorder active."""
    recorder.configure("ring")
    recorder.clear()
    _run(DESC, _frames(4), queue_capacity=16, batch_max=4)  # off pipeline
    assert all(e.stage not in ("src", "f", "out")
               for e in recorder.events())


def test_gauges_in_exposition():
    metrics.gauge("q.queue_depth", 3)
    metrics.gauge("out.staleness_s", 0.25)
    text = metrics_text()
    assert "# TYPE nnstpu_q_queue_depth gauge" in text
    assert "nnstpu_q_queue_depth 3" in text
    assert "nnstpu_out_staleness_s 0.25" in text
    assert metrics.snapshot()["q.queue_depth"] == 3.0


def test_observe_reservoir_bounded_under_concurrency():
    """Satellite: a hot stage must not grow memory for the process
    lifetime, and snapshot()/percentile() must be safe under concurrent
    runner writes."""
    m = Metrics()
    errors = []

    def writer(tag):
        try:
            for i in range(20000):
                m.observe_latency(f"hot.{tag}", i * 1e-6)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            for _ in range(200):
                m.snapshot()
                m.percentile("hot.0", 99.0)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t % 2,))
               for t in range(4)] + [threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = m.snapshot()
    for tag in (0, 1):
        assert snap[f"hot.{tag}.n"] <= m._lat_cap  # bounded reservoir
        _, _, n = m.histograms()[f"hot.{tag}"]
        assert n == 40000  # histogram counts stay exact (no decimation)


def test_metrics_server_scrape_twice_identical_and_stop():
    metrics.count("scrape.frames", 3)
    metrics.observe_latency("scrape.proc", 0.002)
    metrics.gauge("scrape.queue_depth", 1)
    # labeled twins (ISSUE 8): tenant series must render identically
    # across scrapes too, including hash-disambiguated tenant values
    metrics.observe_latency("scrape.proc", 0.004, tenant="acme")
    metrics.count("scrape.frames", 1, tenant="t:1")
    metrics.count("scrape.frames", 1, tenant="t/1")
    srv = start_metrics_server(port=0)
    try:
        url = f"http://127.0.0.1:{srv.server_port}/metrics"

        def series_names(body):
            return {line.split()[0]
                    for line in body.splitlines()
                    if line and not line.startswith("#")}

        one = urllib.request.urlopen(url, timeout=5).read().decode()
        two = urllib.request.urlopen(url, timeout=5).read().decode()
        assert one == two  # label values included, byte-identical
        assert len(series_names(one)) == len(set(series_names(one)))
        assert any(n.startswith("nnstpu_scrape_proc_bucket")
                   for n in series_names(one))
        assert 'nnstpu_scrape_proc_bucket{tenant="acme",le="0.005"} 1' \
            in one
    finally:
        stop_metrics_server(srv)
    with pytest.raises(OSError):
        urllib.request.urlopen(url, timeout=1)


def test_metrics_server_context_manager_rebinds_port():
    with metrics_server(port=0) as srv:
        port = srv.server_port
    # clean shutdown (+ SO_REUSEADDR) => the port is immediately reusable
    with metrics_server(port=port) as srv2:
        assert srv2.server_port == port


def test_sampler_gauges_during_traced_run():
    p = nt.Pipeline(DESC, trace_mode="ring")
    frames = _frames(6)
    with p:
        for i, x in enumerate(frames):
            p.push("src", nt.Buffer([x], pts=i))
        for _ in frames:
            p.pull("out", timeout=60)
        p.sample_queues()  # deterministic tick (thread also running)
        snap = metrics.snapshot()
        p.eos()
        p.wait(timeout=60)
    assert "f.queue_depth" in snap
    assert "out.watermark_pts" in snap and snap["out.watermark_pts"] == 5.0
    assert "out.staleness_s" in snap and snap["out.staleness_s"] >= 0.0


def test_e2e_and_queue_wait_series_from_traced_run():
    _run(DESC, _frames(10), trace_mode="ring")
    snap = metrics.snapshot()
    assert snap.get("out.e2e_latency.n", 0) == 10
    assert snap.get("f.queue_wait.n", 0) >= 1
    hists = metrics.histograms()
    assert "out.e2e_latency" in hists and "f.queue_wait" in hists


def test_batch_identity_unchanged_by_tracing():
    """Tracing must observe, not perturb: outputs of a traced batched run
    are value-identical to the untraced reference."""
    frames = _frames(13)
    traced = _run(DESC, frames, queue_capacity=16, batch_max=8,
                  trace_mode="ring")
    metrics.reset()
    recorder.configure("off")
    recorder.clear()
    plain = _run(DESC, frames, queue_capacity=16, batch_max=8)
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(np.asarray(a.tensors[0]),
                                      np.asarray(b.tensors[0]))
        assert a.pts == b.pts
