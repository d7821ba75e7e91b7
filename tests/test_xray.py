"""nns-xray: predicted-vs-actual reconciliation (ISSUE 13 tentpole).

The contract: with ``Pipeline(xray=True)`` every jit entry point
registers its compiles with the process-wide program registry, which
reconciles the live program set against the deep lint's predicted
census — an unpredicted signature (count past the budget, or a trigger
batch dim outside the ladder) fires ``census-drift`` with the
field-level signature diff and a flight-recorder dump; clean pipelines
(including the llm 3-program serve loop under churn and the device
aggregator) measure drift == 0.  Device time is attributed per stage
(``mfu`` / ``roofline_fraction`` / ``pad_waste_flops`` gauges + a
``device:<stage>`` Chrome-trace track), the HBM ledger reconciles
measured bytes against the deep-lint estimate per category, and
``Pipeline.explain()`` / the doctor CLI join everything into one
JSON-serializable report.  With xray OFF, the hooks are structurally
inert (registry methods monkeypatched to raise — the trace_mode=off
discipline) and every pipeline-owned thread stops on ``stop()``.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import nnstreamer_tpu as nt
from nnstreamer_tpu.core.log import metrics
from nnstreamer_tpu.utils import tracing, xray
from nnstreamer_tpu.utils.profiler import (OPENMETRICS_CONTENT_TYPE,
                                           metrics_text,
                                           start_metrics_server,
                                           stop_metrics_server)
from nnstreamer_tpu.utils.tracing import recorder
from nnstreamer_tpu.utils.xray import (ProgramRegistry, TrackedProgram,
                                       abstract_signature,
                                       explain_signature_drift, registry)

DIMS = 16
DESC = (
    f"appsrc name=src caps=other/tensors,dimensions={DIMS},types=float32 ! "
    f"tensor_filter framework=jax model=scaler custom=scale:2.0,dims:{DIMS} "
    "name=f ! tensor_sink name=out"
)


@pytest.fixture(autouse=True)
def _clean_state():
    metrics.reset()
    registry.reset()
    recorder.configure("off")
    recorder.clear()
    yield
    metrics.reset()
    registry.reset()
    recorder.configure("off")
    recorder.clear()


def _frames(n, dims=DIMS):
    return [np.full((dims,), float(i % 7), np.float32) for i in range(n)]


def _run(desc, frames, timeout=120, explain=False, **kw):
    p = nt.Pipeline(desc, **kw)
    outs, rep = [], None
    try:
        p.start()
        for i, x in enumerate(frames):
            p.push("src", nt.Buffer([x], pts=i))
        for _ in frames:
            outs.append(p.pull("out", timeout=timeout))
        p.eos()
        p.wait(timeout=timeout)
        if explain:
            rep = p.explain()  # BEFORE stop(): the ledger reads live fws
    finally:
        p.stop()
    return (outs, rep) if explain else outs


# -- signatures -------------------------------------------------------------

def test_abstract_signature_distinguishes_weak_scalars():
    import jax.numpy as jnp

    a = abstract_signature((jnp.zeros((4,), jnp.int32), np.int32(0)), {})
    b = abstract_signature((jnp.zeros((4,), jnp.int32), 0), {})
    assert a != b
    assert a[1][0] == "t" and b[1] == ("py", "int")


def test_signature_drift_diff_names_the_field():
    import jax.numpy as jnp

    base = abstract_signature((jnp.zeros((4,), jnp.float32),), {})
    drifted = abstract_signature((jnp.zeros((8,), jnp.float32),), {})
    diff = explain_signature_drift(drifted, base)
    assert "8" in diff and "4" in diff
    # python-scalar leaves fall back to the leaf-level diff
    trap = abstract_signature((0,), {})
    one = abstract_signature((np.int32(0),), {})
    diff = explain_signature_drift(trap, one)
    assert "py:int" in diff
    assert "arity" in explain_signature_drift(base, base + base)


# -- peak table --------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.fixture
def fresh_peak(monkeypatch):
    monkeypatch.setattr(xray, "_peak_cache", {})

    def use(platform, kind):
        import jax

        monkeypatch.setattr(jax, "devices",
                            lambda: [_FakeDevice(platform, kind)])
    return use


def test_peak_flops_keyed_by_device_kind(fresh_peak):
    # a v5e chip reports "TPU v5 lite": it matched neither "v5e" nor "v5p"
    # by substring and got the v5p peak
    fresh_peak("tpu", "TPU v5 lite")
    assert xray.peak_flops() == 197e12


def test_peak_flops_unknown_accelerator_is_an_error(fresh_peak):
    fresh_peak("tpu", "TPU v9 imaginary")
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        xray.peak_flops()


# -- tracked programs / registry -------------------------------------------

def test_tracked_program_registers_compiles_and_costs():
    import jax

    reg = ProgramRegistry()
    fn = reg.track(jax.jit(lambda x: x * 2.0), "s1", "stage")
    assert isinstance(fn, TrackedProgram)
    fn(np.ones((4,), np.float32))
    fn(np.ones((4,), np.float32))  # cache hit: a dispatch, not a compile
    fn(np.ones((8,), np.float32))  # new signature
    census = reg.census()
    e = census["s1/stage"]
    assert e["live_compiles"] == 2
    assert len(e["live_signatures"]) == 2
    assert metrics.snapshot().get("s1.compiles") == 2
    assert fn.flops > 0  # lowered cost analysis attached
    assert fn.disp_n == 1 and fn.disp_ns > 0
    # track() is idempotent; delegation keeps the jit surface usable
    assert reg.track(fn, "s1", "stage") is fn
    assert fn._cache_size() == 2


def test_budget_overflow_fires_census_drift_with_diff(caplog):
    """The PR 11 ``_set_tok`` trap, reproduced at the registry level: a
    numpy-scalar argument mints a second signature past the 1-program
    budget and must fire census-drift carrying the field-level diff."""
    import jax
    import jax.numpy as jnp
    import logging

    reg = ProgramRegistry()
    reg.expect("llm.serve", "set_tok", budget=1)
    fn = reg.track(jax.jit(lambda a, i, v: a.at[i].set(v)),
                   "llm.serve", "set_tok")
    tok = jnp.zeros((4,), jnp.int32)
    fn(tok, np.int32(0), np.int32(5))  # the predicted signature
    assert reg.drift_count() == 0
    with caplog.at_level(logging.WARNING):
        fn(tok, 0, np.int32(5))  # python int: weak-typed — the trap
    assert reg.drift_count() == 1
    d = reg.drifts()[0]
    assert d["stage"] == "llm.serve" and d["kind"] == "set_tok"
    assert "exceed the predicted census of 1" in d["reason"]
    assert "py:int" in d["diff"]
    assert metrics.snapshot().get("xray.census_drifts") == 1
    assert any("census-drift" in r.message for r in caplog.records)
    # the storm throttle: further drifts on the SAME key count but warn
    # at debug only (one ring dump per key — the watchdog discipline)
    with caplog.at_level(logging.WARNING):
        caplog.clear()
        fn(tok, np.int64(1), np.int16(2))  # a 3rd signature
    assert reg.drift_count() == 2
    assert metrics.snapshot().get("xray.census_drifts") == 2
    assert not any(r.levelno >= logging.WARNING for r in caplog.records)


def test_ladder_allow_set_fires_drift_on_unpredicted_bucket():
    import jax

    reg = ProgramRegistry()
    reg.expect("f", "batch", budget=3, allow={1, 2, 4})
    prog = reg.track(jax.jit(lambda x: x + 1), "f", "batch", rows=3)
    prog(np.ones((3, 4), np.float32))
    assert reg.drift_count() == 1
    assert "not in the predicted bucket ladder" in reg.drifts()[0]["reason"]


def test_reinstalled_expectation_retires_stale_drift():
    """A fresh expectation (a new pipeline generation for the stage)
    resets the live count AND retires the key's past drift verdicts —
    a clean successor must not inherit a predecessor's findings."""
    import jax

    reg = ProgramRegistry()
    reg.expect("s", "stage", budget=1)
    fn = reg.track(jax.jit(lambda x: x), "s", "stage")
    fn(np.ones((2,), np.float32))
    fn(np.ones((3,), np.float32))
    assert reg.drift_count() == 1
    reg.expect("s", "stage", budget=1)  # pipeline generation 2
    assert reg.drift_count() == 0
    assert reg.census()["s/stage"]["live_compiles"] == 0


def test_drift_dumps_ring_and_records_span():
    import jax

    recorder.configure("ring")
    recorder.record("stage", "ctx", 1, time.monotonic_ns(), 1000)
    reg = ProgramRegistry()
    reg.expect("s", "stage", budget=1)
    fn = reg.track(jax.jit(lambda x: x), "s", "stage")
    fn(np.ones((2,), np.float32))
    fn(np.ones((3,), np.float32))  # over budget
    kinds = {e.kind for e in recorder.events()}
    assert "xray.drift" in kinds


# -- pipeline end-to-end ----------------------------------------------------

def test_clean_pipeline_census_drift_zero_and_gauges():
    outs, rep = _run(DESC, _frames(32), queue_capacity=32, batch_max=4,
                     data_parallel=1, xray=True, trace_mode="ring",
                     explain=True)
    assert len(outs) == 32
    assert rep["census"]["drift_total"] == 0
    assert registry.drift_count() == 0
    progs = rep["census"]["programs"]
    assert progs["f/batch"]["predicted"] == 3  # ladder(4) = (1, 2, 4)
    assert progs["f/batch"]["allow"] == [1, 2, 4]
    assert progs["f/batch"]["within"] and progs["f/stage"]["within"]
    # at least one compile registered somewhere on the filter stage
    snap = metrics.snapshot()
    assert snap.get("f.compiles", 0) >= 1
    # gauges land in the Prometheus exposition after a reconciler tick
    registry.publish()
    text = metrics_text()
    assert "nnstpu_f_mfu" in text
    assert "nnstpu_f_roofline_fraction" in text
    assert "nnstpu_xray_census_drift 0" in text
    # report is the doctor CLI's machine-readable twin
    json.dumps(rep)
    assert rep["ok"] is True
    assert rep["plan"]["batch_max"] == 4
    assert rep["hbm"]["categories"]["params"]["ok"]


def test_sharded_census_stays_clean():
    """Under the 8-virtual-device data mesh the sharded single-program
    path's per-bucket signatures are shard-rounded — still inside the
    predicted allow set, drift 0."""
    outs, rep = _run(DESC, _frames(24), queue_capacity=32, batch_max=4,
                     data_parallel=2, xray=True, explain=True)
    assert len(outs) == 24
    assert rep["census"]["drift_total"] == 0
    e = rep["census"]["programs"]["f/batch"]
    assert e["within"] and e["allow"] == [1, 2, 4]


def test_pad_waste_flops_counts_padded_rows():
    """3 same-spec buffers pushed into a batch_max=4 runner with linger:
    the drain pads 3 -> 4 and the pad waste is priced in FLOPs."""
    outs = _run(DESC, _frames(3), queue_capacity=16, batch_max=4,
                data_parallel=1, batch_linger_ms=150.0, xray=True)
    assert len(outs) == 3
    snap = metrics.snapshot()
    if snap.get("f.batch_pad_waste", 0) > 0:  # a 3-row drain happened
        assert snap.get("f.pad_waste_flops", 0) > 0


def test_device_track_in_chrome_trace(tmp_path):
    _run(DESC, _frames(24), queue_capacity=32, batch_max=4, xray=True,
         trace_mode="ring")
    out = tmp_path / "trace.json"
    tracing.dump_chrome(recorder.events(), str(out))
    with open(out) as f:
        obj = json.load(f)
    assert not tracing.validate_chrome(obj)
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert any(n.startswith("device:") for n in names)
    assert any(e.get("name") == "device" for e in obj["traceEvents"])


def test_hbm_ledger_params_match_deep_estimate():
    _, rep = _run(DESC, _frames(8), queue_capacity=16, batch_max=4,
                  xray=True, explain=True)
    params = rep["hbm"]["categories"]["params"]
    assert params["predicted"] is not None and params["predicted"] > 0
    assert params["measured"] == params["predicted"]  # same accounting
    assert params["ratio"] == 1.0
    for cat in ("kv_pool", "agg_rings", "activations"):
        assert rep["hbm"]["categories"][cat]["ok"]


def test_second_pipeline_same_stage_names_no_false_drift():
    """The registry is process-wide: a second pipeline re-using stage
    names re-installs its expectations, which must RESET the live
    counts — its own warmup compiles are not drift."""
    for _ in range(2):
        _, rep = _run(DESC, _frames(12), queue_capacity=16, batch_max=4,
                      data_parallel=1, xray=True, explain=True)
        assert rep["census"]["drift_total"] == 0
        assert rep["census"]["programs"]["f/batch"]["within"]
    assert registry.drift_count() == 0


def test_explain_works_without_xray():
    _, rep = _run(DESC, _frames(4), queue_capacity=8, explain=True)
    assert rep["xray"] is False
    assert rep["census"]["programs"] == {}
    assert rep["ok"] is True
    json.dumps(rep)


def test_explain_after_stop_does_not_reload_frameworks():
    """The ledger probe on a STOPPED pipeline must not resurrect closed
    frameworks (param_bytes() lazily reloads — at llm scale that is a
    multi-GiB checkpoint load just to read a byte count)."""
    p = nt.Pipeline(DESC, queue_capacity=8, xray=True)
    with p:
        p.push("src", nt.Buffer([_frames(1)[0]]))
        p.pull("out", timeout=60)
        p.eos()
        p.wait(timeout=60)
    assert p.element("f").fw is None  # stop() closed it
    rep = p.explain()
    assert p.element("f").fw is None  # ...and explain() left it closed
    assert rep["hbm"]["categories"]["params"]["measured"] == 0


# -- the off pin ------------------------------------------------------------

def test_xray_off_structural_pin(monkeypatch):
    """With xray off (the default) the registry must be STRUCTURALLY
    bypassed: every registry entry point monkeypatched to raise, and a
    batched + traced pipeline still completes — the disabled hook is one
    pointer check, no wrappers, no cost_analysis."""

    def boom(*a, **k):
        raise AssertionError("xray hook ran with xray off")

    monkeypatch.setattr(ProgramRegistry, "track", boom)
    monkeypatch.setattr(ProgramRegistry, "register", boom)
    monkeypatch.setattr(ProgramRegistry, "expect", boom)
    monkeypatch.setattr(TrackedProgram, "__call__", boom)
    outs = _run(DESC, _frames(12), queue_capacity=16, batch_max=4,
                trace_mode="ring")
    assert len(outs) == 12
    assert registry.drift_count() == 0
    assert "compiles" not in metrics_text()


# -- llm serve loop + aggregator census ------------------------------------

LLM_BASE = "max_new:4,stream_chunk:2,temperature:0.0,dtype:float32"


def _llm_fw(xray_on=True):
    from nnstreamer_tpu.filters.llm import LLMFramework

    fw = LLMFramework()
    fw.open({"model": "llama_tiny",
             "custom": LLM_BASE + ",serve:continuous,slots:2,block_size:8"})
    if xray_on:
        fw.attach_xray(registry, "llm")
    return fw


def _serve(fw, prompts, timeout=300.0):
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


def test_llm_serve_loop_census_clean_under_churn():
    """The PR 6 acceptance twin, measured live: stream churn through the
    continuous loop compiles EXACTLY the 3 predicted programs — measured
    census drift 0, live program set == serving_plan()'s census."""
    rng = np.random.default_rng(3)
    fw = _llm_fw()
    try:
        for wave in range(3):  # join/leave/complete churn
            prompts = [rng.integers(1, 500, (t,), dtype=np.int32)
                       for t in (3, 6)]
            got = _serve(fw, prompts)
            assert all(len(v) for v in got.values())
        census = registry.census()
        for kind in ("decode", "prefill", "set_tok"):
            e = census[f"llm.serve/{kind}"]
            assert e["predicted"] == 1
            assert e["live_compiles"] == 1, (kind, e)
            assert e["within"]
        assert registry.drift_count() == 0
        snap = metrics.snapshot()
        assert snap.get("llm.serve.compiles") == 3
    finally:
        fw.close()


def test_llm_set_tok_numpy_scalar_trap_fires_drift_in_pipeline():
    """The golden DRIFTED pipeline: a serving pipeline deliberately
    mints the unpredicted 4th signature (the PR 11 trap — a weak-typed
    python scalar where the loop always passes strongly typed arrays) —
    census-drift must fire carrying the signature diff, while the run
    up to that point measured drift 0."""
    import jax.numpy as jnp

    p = nt.Pipeline(
        "appsrc name=src ! tensor_filter framework=llm "
        "model=llama_tiny custom=max_new:4,serve:continuous,slots:2,"
        "temperature:0.0,block_size:8 invoke-dynamic=true name=f ! "
        "tensor_sink name=out", xray=True, trace_mode="ring")
    try:
        p.start()
        p.push("src", np.array([1, 5, 9, 2], np.int32))
        bufs = [p.pull("out", timeout=120) for _ in range(4)]
        assert sum(1 for b in bufs if b.meta.get("stream_last")) == 1
        assert registry.drift_count() == 0  # the clean serve measured 0
        # the ledger closes exactly on the serving categories: live
        # params AND the paged pool match the deep-lint estimate
        clean = p.explain()
        for cat in ("params", "kv_pool"):
            c = clean["hbm"]["categories"][cat]
            assert c["measured"] > 0 and c["measured"] == c["predicted"]
        loop = p.element("f").fw._serve
        # a FRESH donated array (never the loop's own tok state); the
        # python-int index is the weak-typed trap
        loop._set_tok(jnp.zeros((2,), jnp.int32), 0, np.int32(7))
        assert registry.drift_count() == 1
        d = registry.drifts()[0]
        # the serve census is keyed by the ELEMENT's stage name (+.serve)
        assert d["stage"] == "f.serve" and d["kind"] == "set_tok"
        assert "py:int" in d["diff"]
        rep = p.explain()
        assert rep["ok"] is False
        assert rep["census"]["drift_total"] == 1
        assert any(e.kind == "xray.drift"
                   for e in recorder.events())
        p.eos("src")
        p.wait(timeout=120)
    finally:
        p.stop()


def test_aggregator_device_census_is_three_programs():
    desc = ("appsrc name=src caps=other/tensors,dimensions=8,"
            "types=float32 ! tensor_aggregator frames_in=1 frames_out=4 "
            "frames_dim=0 device=true name=agg ! tensor_sink name=out")
    p = nt.Pipeline(desc, xray=True)
    try:
        p.start()
        for i in range(8):
            p.push("src", np.full((8,), float(i), np.float32))
        wins = [p.pull("out", timeout=60) for _ in range(2)]
        assert len(wins) == 2
        census = registry.census()
        e = census["agg/agg"]
        assert e["predicted"] == 3
        assert e["live_compiles"] == 3 and e["within"]
        assert registry.drift_count() == 0
        p.eos()
        p.wait(timeout=60)
    finally:
        p.stop()


# -- openmetrics + thread audit satellites ---------------------------------

def test_openmetrics_negotiation_and_scrape_twice_identical():
    metrics.count("f.compiles", 2)
    metrics.count("web.requests", 1, tenant="acme")  # labeled family
    metrics.gauge("xray.hbm.params", 1024.0)
    metrics.observe_latency("out.e2e_latency", 0.01, tenant="acme")
    srv = start_metrics_server()
    try:
        url = f"http://127.0.0.1:{srv.server_port}/metrics"
        req = urllib.request.Request(
            url, headers={"Accept": "application/openmetrics-text"})
        with urllib.request.urlopen(req) as r:
            body1 = r.read().decode()
            assert r.headers["Content-Type"] == OPENMETRICS_CONTENT_TYPE
        assert body1.rstrip().endswith("# EOF")
        with urllib.request.urlopen(req) as r:
            body2 = r.read().decode()
        assert body1 == body2  # labeled + xray families scrape stable
        assert 'tenant="acme"' in body1
        assert "nnstpu_xray_hbm_params" in body1
        # OpenMetrics: typed counter SAMPLES carry the mandatory _total
        assert "nnstpu_f_compiles_total 2" in body1
        with urllib.request.urlopen(url) as r:  # no negotiation
            plain = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "# EOF" not in plain
        # the classic exposition is untouched: bare counter names, and
        # scraping it twice stays identical too
        assert "nnstpu_f_compiles 2" in plain
        assert "_total" not in plain
        with urllib.request.urlopen(url) as r:
            assert r.read().decode() == plain
    finally:
        stop_metrics_server(srv)


def test_all_pipeline_threads_stop_on_stop():
    """The shutdown audit: SLO engine, metrics sampler, and the xray
    reconciler all verifiably stop on Pipeline.stop() — assert via a
    threading.enumerate delta (a warmup run first absorbs jax's own
    lazily-spawned pools)."""
    slo = {"tenants": [{"tenant": "t", "p99_ms": 10000.0}]}
    kw = dict(queue_capacity=8, batch_max=2, xray=True, trace_mode="ring",
              slo=slo, tenant="t")
    _run(DESC, _frames(4), **kw)  # warmup: backend pools spawn here
    before = set(threading.enumerate())
    _run(DESC, _frames(4), **kw)
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive()]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"threads leaked past stop(): {leaked}"
    # the named pipeline threads specifically are gone
    names = {t.name for t in threading.enumerate()}
    for prefix in ("nns-sampler", "nns-xray", "nns-slo"):
        assert not any(n.startswith(prefix) for n in names), names


def test_journal_flusher_thread_stops_on_close(tmp_path):
    """The remaining audited daemon: a batch-fsync journal's flusher is
    alive while open and verifiably joined by close()."""
    from nnstreamer_tpu.utils.journal import Journal

    j = Journal(str(tmp_path / "wal"), fsync="batch")
    names = {t.name for t in threading.enumerate()}
    assert "nns-journal-flush" in names
    j.close()
    leaked = [t for t in threading.enumerate()
              if t.name == "nns-journal-flush" and t.is_alive()]
    assert not leaked


# -- doctor -----------------------------------------------------------------

def test_doctor_cli_bench_pipeline(tmp_path, capsys):
    from nnstreamer_tpu.tools import doctor

    out = tmp_path / "report.json"
    rc = doctor.main(["--frames", "48", "--json", str(out), "--gate"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0] == "census drift 0"
    assert lines[-1] == "doctor: OK"
    with open(out) as f:
        rep = json.load(f)
    assert rep["ok"] is True
    assert rep["census"]["drift_total"] == 0
    for cat in ("params", "kv_pool", "agg_rings", "activations"):
        assert rep["hbm"]["categories"][cat]["ok"]
