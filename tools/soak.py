#!/usr/bin/env python
"""Soak harness for the query front door (ISSUE 8, docs/SERVING.md
"Front door"): a multi-process load generator driving N tenants against
ONE query-server pipeline for minutes, recording per-tenant tail latency
and sustained-vs-burst throughput into BENCH_SOAK rows.

    python tools/soak.py --out BENCH_SOAK_r01.json          # full run
    python tools/soak.py --smoke --out /tmp/soak.json       # CI gate

Per profile, the harness:

1. builds a fresh server pipeline (``tensor_query_serversrc`` with the
   requested admission policy ! a custom-easy work stage with a
   configurable service time ! ``tensor_query_serversink``) with
   ``trace_mode=ring`` and a per-tenant SLO policy attached;
2. spawns one WORKER SUBPROCESS per tenant (own interpreter — the load
   generation never shares the server's GIL), each driving a client
   pipeline (``appsrc ! tensor_query_client tenant=... ! tensor_sink``)
   at a profile-shaped request rate, measuring per-request wall latency
   client-side (a ``t_send`` stamp rides the wire meta out and back);
3. evaluates the server's SLO engine, collects worker stats, and writes
   one row: per-tenant p50/p99/max latency, sustained fps (completions /
   duration) vs burst fps (best 0.5 s window), request/shed counts, the
   ``slo_report`` verdict, and — on any SLO breach or watchdog fire —
   the flight-recorder ring dump.

Profiles
--------
* ``steady``   — constant rate (the zero-shed low-load reference);
* ``ramp``     — rate climbs linearly 0 → peak over the duration;
* ``spike``    — 20% of peak baseline with full-peak bursts (20% duty);
* ``churn``    — steady rate, but each client tears its connection down
  and reconnects in 4 segments (admission/handshake churn);
* ``overload`` — offered load far above service capacity with a small
  ``max-backlog`` and slow service: admission control MUST shed, and
  the tight SLO must breach (the post-mortem path the gate asserts);
* ``elastic``  — half-rate until the midpoint, then the full peak: load
  DOUBLES mid-run while an ``utils/elastic.Autoscaler`` watches the
  burn-rate gauges (BENCH_ELASTIC rows, ``--elastic``).

Chaos profiles (``--chaos`` / ``--chaos-smoke``, ISSUE 11) drive a
continuous-serving LLM server (``serve:continuous``, bounded paged-KV
pool) and inject one fault mid-run via :class:`ChaosController`:

* ``kill_worker``  — SIGKILL one tenant's subprocess mid-stream: its
  connection dies, the serversink's dead-connection backchannel cancels
  the orphaned stream, and the serve loop reaps its KV blocks back to
  the free list (allocator accounting asserted in the row);
* ``drop_conn``    — sever every live server connection mid-run: the
  clients reconnect with capped-backoff + full jitter and finish their
  work (reconnect counters asserted);
* ``wedge_tenant`` — one client stops reading responses (tiny
  SO_RCVBUF, raw socket): the server's per-connection send timeout
  drops it instead of wedging the serversink behind it;
* ``slow_stage``   — test-only latency injected into the work stage
  (``utils/elastic.chaos_slow_stage``) for a window mid-run: the SLO
  engine must attribute the breach, and the run must recover.

The ``yank_process`` profile (``--yank`` / ``--yank-smoke``, ISSUE 12,
docs/ROBUSTNESS.md) is the durability row: the SERVER itself runs as a
subprocess with a request journal (``serversrc journal=DIR``), gets
SIGKILLed mid-run, and is restarted with ``journal-replay=true`` on the
same port while reconnecting clients resend their pending requests.
The row asserts the exactly-once contract: every accepted-but-unanswered
journal entry at the kill is re-admitted and answered (acked) exactly
once by the restarted process, the journal ends fully answered, and no
client loses a request.

The stdout tail is one JSON line carrying ``"metric"``: the run's
summary for whatever collects it.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DIMS = 32
BURST_WINDOW_S = 0.5

#: per-profile shape: (baseline fraction of peak, description)
PROFILES = ("steady", "ramp", "spike", "churn", "overload", "elastic")

#: fault-injection profiles (docs/SERVING.md "Elastic serving")
CHAOS_PROFILES = ("kill_worker", "drop_conn", "wedge_tenant",
                  "slow_stage")


# ---------------------------------------------------------------------------
# worker (subprocess): one tenant's load generator
# ---------------------------------------------------------------------------

def _rate_at(profile: str, t: float, duration: float, peak: float) -> float:
    """Offered request rate (req/s) at elapsed time ``t``."""
    if profile == "ramp":
        return peak * min(1.0, t / max(1e-9, duration))
    if profile == "spike":
        # 20% baseline; full peak during two bursts at 30-40% and
        # 60-80% of the run
        frac = t / max(1e-9, duration)
        burst = 0.3 <= frac < 0.4 or 0.6 <= frac < 0.8
        return peak if burst else 0.2 * peak
    if profile == "elastic":
        # load DOUBLES at the midpoint (the autoscaler row's shape)
        return 0.5 * peak if t < duration / 2 else peak
    return peak  # steady / churn / overload


def _worker_segment(port: int, tenant: str, profile: str,
                    duration: float, peak: float, timeout: float,
                    stats: dict, inflight: int = 8,
                    reconnect: int = 0) -> None:
    """One client-pipeline lifetime: push at the profile rate, pull every
    response, record latencies/sheds into ``stats``."""
    import nnstreamer_tpu as nt

    extra = (f"reconnect={reconnect} reconnect_cap_ms=1500 "
             if reconnect else "")
    cli = nt.Pipeline(
        f"appsrc name=src ! tensor_query_client name=qc port={port} "
        f"tenant={tenant} timeout={timeout} on-timeout=drop "
        f"max-in-flight={inflight} {extra}! "
        "tensor_sink name=out")
    done = threading.Event()

    def puller():
        # drain accounting is CUMULATIVE across churn segments: a
        # per-segment counter would read "drained" the moment segment
        # 2+ starts (earlier segments' completions already >= the new
        # segment's pushes) and leak in-flight responses out of the row
        while True:
            try:
                out = cli.pull("out", timeout=0.25)
            except TimeoutError:
                answered = (stats["completed"] + stats["sheds_seen"]
                            + stats["lost"])
                if done.is_set() and answered >= stats["requests"]:
                    return
                if done.is_set() and time.monotonic() > stats["_drain_by"]:
                    stats["lost"] += stats["requests"] - answered
                    return
                continue
            except Exception:  # noqa: BLE001 - pipeline died: stop pulling
                return
            now = time.time()
            if out.meta.get("shed"):
                stats["sheds_seen"] += 1
            else:
                t_send = out.meta.get("t_send")
                if t_send is not None:
                    stats["latencies_ms"].append((now - t_send) * 1e3)
                stats["completed"] += 1
                stats["completions"].append(time.monotonic())

    with cli:
        pull = threading.Thread(target=puller, daemon=True)
        pull.start()
        # rate integration, not per-request sleeps: accumulate "owed"
        # requests from the instantaneous profile rate each tick, so a
        # near-zero ramp start idles in 5 ms slices instead of sleeping
        # out 1/rate (which at rate->0 would park the worker for the
        # whole run)
        t0 = t_prev = time.monotonic()
        owed = 0.0
        while True:
            now = time.monotonic()
            t = now - t0
            if t >= duration:
                break
            owed += _rate_at(profile, t, duration, peak) * (now - t_prev)
            t_prev = now
            if owed < 1.0:
                time.sleep(0.005)
                continue
            dead = False
            while owed >= 1.0:
                owed -= 1.0
                buf = nt.Buffer([np.full((DIMS,), 1.0, np.float32)])
                buf.meta["t_send"] = time.time()
                try:
                    cli.push("src", buf)
                except Exception:  # noqa: BLE001 - server gone mid-churn
                    dead = True
                    break
                stats["requests"] += 1
            if dead:
                break
        stats["_drain_by"] = time.monotonic() + max(2.0, timeout)
        done.set()
        pull.join(timeout=max(5.0, timeout + 2.0))
        cli.eos("src")
        try:
            cli.wait(timeout=10)
        except Exception:  # noqa: BLE001 - drop-mode stragglers are fine
            pass


def _stream_worker(args) -> int:
    """Token-stream load generator (chaos rows): keep TWO llm
    ``serve:continuous`` streams in flight through a reconnecting query
    client (so a mid-run fault always lands on a live stream), demuxing
    interleaved token streams by their ``stream_id`` meta and recording
    first-token latency per request."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics as _metrics

    TARGET = 2  # streams kept in flight
    stats = {"requests": 0, "completed": 0, "aborted": 0, "lost": 0,
             "sheds_seen": 0, "latencies_ms": [], "completions": []}
    rng = np.random.default_rng(abs(hash(args.tenant)) % (1 << 32))
    cli = nt.Pipeline(
        f"appsrc name=src ! tensor_query_client name=qc port={args.port} "
        f"tenant={args.tenant} timeout={args.timeout} on-timeout=drop "
        f"reconnect=6 ! tensor_sink name=out",
        trace_mode="ring" if getattr(args, "ring_out", "") else "off")
    first_seen: set = set()  # stream_ids whose first token arrived
    t0 = time.monotonic()
    dead = False
    with cli:
        while True:
            now = time.monotonic()
            resolved = (stats["completed"] + stats["aborted"]
                        + stats["sheds_seen"] + stats["lost"])
            outstanding = stats["requests"] - resolved
            if now - t0 >= args.duration or dead:
                if outstanding <= 0:
                    break
            elif outstanding < TARGET:
                buf = nt.Buffer(
                    [rng.integers(1, 200, (4,), dtype=np.int32)])
                buf.meta["t_send"] = time.time()
                try:
                    cli.push("src", buf)
                    stats["requests"] += 1
                    continue
                except Exception:  # noqa: BLE001 - server gone
                    dead = True
            try:
                out = cli.pull("out", timeout=args.timeout + 5.0)
            except Exception:  # noqa: BLE001 - timeout/pipeline death
                stats["lost"] += outstanding
                break
            if out.meta.get("shed"):
                stats["sheds_seen"] += 1
                continue
            sid = out.meta.get("stream_id")
            if sid is not None and sid not in first_seen \
                    and len(out.tensors):
                first_seen.add(sid)
                ts = out.meta.get("t_send")
                if ts is not None:
                    stats["latencies_ms"].append(
                        (time.time() - ts) * 1e3)
            if out.meta.get("stream_aborted"):
                stats["aborted"] += 1
            elif out.meta.get("stream_last"):
                stats["completed"] += 1
                stats["completions"].append(time.monotonic())
        snap = _metrics.snapshot()
        stats["reconnects"] = snap.get("qc.reconnects", 0.0)
        stats["reconnect_backoff_ms"] = snap.get(
            "qc.reconnect_backoff_ms", 0.0)
        try:
            cli.eos("src")
            cli.wait(timeout=10)
        except Exception:  # noqa: BLE001 - drain stragglers are fine
            pass
    _write_worker_row(args, stats)
    return 0


def _wedge_worker(args) -> int:
    """wedge_tenant chaos: a raw-socket client with a TINY receive
    buffer that sends requests and then stops reading — the server's
    per-connection send timeout must drop it instead of wedging the
    serversink (and every other tenant) behind it."""
    import socket

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.utils import wire
    from nnstreamer_tpu.utils.net import client_handshake

    sock = socket.socket()
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    sock.connect(("127.0.0.1", args.port))
    client_handshake(sock, "hello", caps="other/tensors", topic="",
                     tenant=args.tenant)
    # enough concurrent streams that the unread token responses overrun
    # the (deliberately small) kernel buffers and sends start timing out
    n = 12
    for mid in range(n):
        buf = nt.Buffer([np.arange(1, 6, dtype=np.int32)])
        buf.meta["_query_msg"] = mid
        buf.meta["_tenant"] = args.tenant
        wire.write_frame(sock, wire.encode_buffer(buf))
    # wedged: never read another byte until the run ends
    time.sleep(args.duration)
    try:
        sock.close()
    except OSError:
        pass
    _write_worker_row(args, {"requests": n, "completed": 0, "aborted": 0,
                             "lost": n, "sheds_seen": 0, "wedged": True,
                             "latencies_ms": [], "completions": []})
    return 0


def _write_worker_row(args, stats: dict) -> None:
    lats = sorted(stats.get("latencies_ms", []))

    def pct(q):
        if not lats:
            return None
        return lats[min(len(lats) - 1,
                        max(0, int(len(lats) * q / 100.0 + 0.999999) - 1))]

    comps = stats.get("completions", [])
    span = (comps[-1] - comps[0]) if len(comps) > 1 else 0.0
    out = {
        "tenant": args.tenant, "profile": args.profile,
        "mode": args.mode,
        "requests": stats.get("requests", 0),
        "completed": stats.get("completed", 0),
        "aborted": stats.get("aborted", 0),
        "sheds_seen": stats.get("sheds_seen", 0),
        "lost": stats.get("lost", 0),
        "reconnects": stats.get("reconnects", 0.0),
        "reconnect_backoff_ms": stats.get("reconnect_backoff_ms", 0.0),
        "wedged": stats.get("wedged", False),
        "p50_ms": pct(50), "p99_ms": pct(99), "max_ms": pct(100),
        "sustained_fps": (stats.get("completed", 0) / span if span > 1.0
                          else stats.get("completed", 0) / args.duration),
        "burst_fps": None,
    }
    with open(args.out, "w") as f:
        json.dump(out, f)


def run_worker(args) -> int:
    try:
        return _run_worker(args)
    finally:
        # nns-weave: dump this worker's flight-recorder ring at normal
        # exit (the harness merges it with the server's; a SIGKILLed
        # worker never gets here — that is the server-only fallback)
        if getattr(args, "ring_out", ""):
            try:
                from nnstreamer_tpu.utils import tracing
                tracing.dump_ring(args.ring_out,
                                  proc=f"worker-{args.tenant}")
            except Exception:  # noqa: BLE001 - artifact is best-effort
                pass


def _run_worker(args) -> int:
    if args.mode == "stream":
        return _stream_worker(args)
    if args.mode == "wedge":
        return _wedge_worker(args)
    stats = {"requests": 0, "completed": 0, "sheds_seen": 0, "lost": 0,
             "latencies_ms": [], "completions": [],
             "_drain_by": float("inf")}
    segments = 4 if args.profile == "churn" else 1
    seg_dur = args.duration / segments
    for _ in range(segments):
        _worker_segment(args.port, args.tenant, args.profile, seg_dur,
                        args.rate, args.timeout, stats,
                        inflight=args.inflight,
                        reconnect=args.reconnect)
    lats = sorted(stats["latencies_ms"])

    def pct(q):
        if not lats:
            return None
        return lats[min(len(lats) - 1,
                        max(0, int(len(lats) * q / 100.0 + 0.999999) - 1))]

    # burst fps: the best BURST_WINDOW_S completion window; sustained
    # fps: completions over the actual first-to-last completion span
    # (NOT the nominal duration — under overload the drain tail would
    # otherwise inflate it past the burst number)
    comps = stats["completions"]
    burst = 0
    j = 0
    for i in range(len(comps)):
        while comps[i] - comps[j] > BURST_WINDOW_S:
            j += 1
        burst = max(burst, i - j + 1)
    span = (comps[-1] - comps[0]) if len(comps) > 1 else 0.0
    sustained = (stats["completed"] / span if span > 1.0
                 else stats["completed"] / args.duration)
    from nnstreamer_tpu.core.log import metrics as _metrics

    snap = _metrics.snapshot()
    out = {
        "tenant": args.tenant,
        "profile": args.profile,
        "requests": stats["requests"],
        "completed": stats["completed"],
        "sheds_seen": stats["sheds_seen"],
        "lost": stats["lost"],
        "reconnects": snap.get("qc.reconnects", 0.0),
        "resends": snap.get("qc.resends", 0.0),
        "p50_ms": pct(50), "p99_ms": pct(99), "max_ms": pct(100),
        "sustained_fps": sustained,
        "burst_fps": burst / BURST_WINDOW_S,
    }
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


# ---------------------------------------------------------------------------
# orchestrator: one profile = one fresh server + N tenant workers
# ---------------------------------------------------------------------------

def _register_work(service_ms: float) -> None:
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.filters.custom_easy import register_custom_easy
    from nnstreamer_tpu.utils import elastic

    spec = TensorsSpec.from_string(str(DIMS), "float32")
    service_s = service_ms / 1e3

    def work(ins):
        # chaos hook (test-only): the slow_stage profile injects extra
        # latency here without touching any production code path
        extra = elastic.chaos_slow_delay("soak-work")
        if service_s + extra > 0:
            time.sleep(service_s + extra)
        return [ins[0] * 2.0]

    register_custom_easy("soak-work", work, in_spec=spec, out_spec=spec)


def run_profile(profile: str, *, tenants: int, duration: float,
                rate: float, service_ms: float, admission: str,
                max_backlog: int, p99_ms: float, sid: int,
                watchdog_s: float = 5.0, chaos: str = None,
                slow_extra_ms: float = 80.0) -> dict:
    """One soak row: fresh server pipeline + metrics/ring state, N worker
    subprocesses, SLO verdict, ring dump on breach/watchdog."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils import tracing
    from nnstreamer_tpu.utils.watchdog import Watchdog

    metrics.reset()
    tracing.recorder.clear()
    tenant_names = [f"t{i}" for i in range(tenants)]
    _register_work(service_ms)
    policy = {
        "tenants": [{"tenant": t, "p99_ms": p99_ms, "error_budget": 0.01}
                    for t in tenant_names],
    }
    srv = nt.Pipeline(
        f"tensor_query_serversrc name=ssrc port=0 id={sid} "
        f"admission={admission} max-backlog={max_backlog} ! "
        f"tensor_filter framework=custom-easy model=soak-work ! "
        f"tensor_query_serversink name=ssink id={sid}",
        trace_mode="ring", slo=policy)
    row: dict = {"profile": profile, "tenants_n": tenants,
                 "duration_s": duration, "offered_rate_per_tenant": rate,
                 "service_ms": service_ms, "admission": admission,
                 "max_backlog": max_backlog, "p99_objective_ms": p99_ms}
    wd_fired = threading.Event()
    with srv:
        port = srv.element("ssrc").bound_port
        wd = Watchdog(watchdog_s, wd_fired.set)
        stop_mon = threading.Event()

        def monitor():
            # feed the watchdog while the server is healthy: either it
            # made progress since the last tick (responses/sheds
            # advanced) or it has nothing pending (idle is not hung —
            # worker subprocesses take seconds to spawn, and the drain
            # tail after the last request is quiet by design).  A wedged
            # pipeline — requests admitted, nothing answered — stops
            # feeding and the dog fires -> ring dump attached below.
            last = -1.0
            while not stop_mon.wait(0.25):
                snap = metrics.snapshot()
                answered = (snap.get("query_server.out", 0.0)
                            + snap.get("query_server.shed", 0.0))
                pending = snap.get("query_server.in", 0.0) - answered
                if answered != last or pending <= 0:
                    wd.feed()
                last = answered

        mon = threading.Thread(target=monitor, daemon=True)
        workers = []
        outs = []
        with wd:
            mon.start()
            for t in tenant_names:
                fd, path = tempfile.mkstemp(prefix=f"soak-{t}-",
                                            suffix=".json")
                os.close(fd)
                outs.append(path)
                workers.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--worker", "--port", str(port), "--tenant", t,
                     "--profile", profile, "--duration", str(duration),
                     "--rate", str(rate), "--timeout", "10",
                     "--out", path],
                    cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu")))
            ctl = None
            if chaos is not None:
                ctl = ChaosController(
                    chaos, duration, workers=workers,
                    core_getter=lambda: srv.element("ssrc")._core,
                    traffic_fn=lambda: metrics.snapshot().get(
                        "query_server.in", 0.0) > 0,
                    slow_extra_ms=slow_extra_ms)
                ctl.start()
            deadline = time.monotonic() + duration * 4 + 60
            stragglers = 0
            for w in workers:
                try:
                    w.wait(timeout=max(5.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    w.kill()
                    stragglers += 1
            row["worker_stragglers"] = stragglers
            if ctl is not None:
                ctl.stop()
                row["chaos_record"] = ctl.record
            stop_mon.set()
            mon.join(timeout=2.0)
        report = srv.slo_report()
        row["tenants"] = {}
        for path in outs:
            try:
                with open(path) as f:
                    w = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            finally:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            row["tenants"][w["tenant"]] = w
        snap = metrics.snapshot()
        lab = metrics.labeled_counters()
        row["server"] = {
            "requests_in": snap.get("query_server.in", 0.0),
            "responses_out": snap.get("query_server.out", 0.0),
            "sheds_total": snap.get("query_server.shed", 0.0),
            "downgraded_total": snap.get("query_server.downgraded", 0.0),
            "sheds_by_tenant": {
                t: v for (name, t), v in lab.items()
                if name == "query_server.shed"},
        }
        row["slo_report"] = report
        row["watchdog_fired"] = wd_fired.is_set()
        if wd_fired.is_set() or not report["ok"]:
            # the post-mortem contract: a degraded soak run ships with
            # its own flight-recorder timeline attached
            row["ring_dump"] = tracing.format_recent(5.0)[-120:]
        else:
            row["ring_dump"] = None
    return row


class ChaosController(threading.Thread):
    """Inject ONE fault into a running soak row at ``at_frac`` of the
    duration (docs/SERVING.md "Elastic serving").  ``kill_worker``
    SIGKILLs a tenant subprocess mid-stream; ``drop_conn`` severs every
    live server connection; ``slow_stage`` injects latency into the
    work stage for a window via the test-only
    ``utils/elastic.chaos_slow_stage`` hook (``wedge_tenant`` needs no
    controller — the wedge WORKER is the fault).  ``record`` is the
    audit trail the soak row ships."""

    def __init__(self, profile: str, duration: float, *,
                 workers=None, core_getter=None, traffic_fn=None,
                 at_frac: float = 0.5, slow_extra_ms: float = 0.0,
                 slow_window_frac: float = 0.25):
        super().__init__(name="soak-chaos", daemon=True)
        self.profile = profile
        self.duration, self.at_frac = duration, at_frac
        self.workers = workers or []
        self.core_getter = core_getter
        #: anchor predicate: the countdown starts once this returns True
        #: (worker subprocesses take seconds to import jax and connect —
        #: anchoring on first observed traffic keeps the fault mid-RUN,
        #: not mid-startup)
        self.traffic_fn = traffic_fn
        self.slow_extra_ms = slow_extra_ms
        self.slow_window_frac = slow_window_frac
        self.record: dict = {"profile": profile, "injected": False}
        self._stop = threading.Event()

    def stop(self) -> None:
        self._stop.set()

    def run(self) -> None:
        from nnstreamer_tpu.utils import elastic

        anchor = time.monotonic()
        if self.traffic_fn is not None:
            while not self.traffic_fn():
                if self._stop.wait(0.1):
                    return
            anchor = time.monotonic()
        if self._stop.wait(self.at_frac * self.duration):
            return
        self.record["injected"] = True
        self.record["t_injected_s"] = round(time.monotonic() - anchor, 3)
        if self.profile == "kill_worker" and self.workers:
            import signal as _signal

            victim = self.workers[0]
            try:
                os.kill(victim.pid, _signal.SIGKILL)
                self.record["killed_pid"] = victim.pid
            except OSError as e:
                self.record["error"] = str(e)
        elif self.profile == "drop_conn" and self.core_getter is not None:
            core = self.core_getter()
            dropped = 0
            for cid in list(core._conns):
                core.drop_conn(cid)
                dropped += 1
            self.record["conns_dropped"] = dropped
        elif self.profile == "slow_stage":
            elastic.chaos_slow_stage("soak-work", self.slow_extra_ms / 1e3)
            window = self.slow_window_frac * self.duration
            self._stop.wait(window)
            elastic.chaos_slow_stage("soak-work", 0.0)
            self.record["slow_window_s"] = round(window, 3)
            self.record["slow_extra_ms"] = self.slow_extra_ms


def _spawn_worker(profile: str, port: int, tenant: str, duration: float,
                  rate: float, timeout: float, mode: str = "plain",
                  inflight: int = 8, ring: bool = False):
    """Returns (proc, row_path, ring_path).  ``ring=True`` hands the
    worker a ``--ring-out`` path: it runs its client pipeline with the
    flight recorder on and dumps its ring there at normal exit — a
    SIGKILLed worker leaves the file empty, which the harness-side merge
    reports as a missing ring (docs/OBSERVABILITY.md "Distributed
    tracing")."""
    fd, path = tempfile.mkstemp(prefix=f"soak-{tenant}-", suffix=".json")
    os.close(fd)
    ring_path = ""
    argv = [sys.executable, os.path.abspath(__file__),
            "--worker", "--mode", mode, "--port", str(port),
            "--tenant", tenant, "--profile", profile,
            "--duration", str(duration), "--rate", str(rate),
            "--timeout", str(timeout), "--inflight", str(inflight),
            "--out", path]
    if ring:
        rfd, ring_path = tempfile.mkstemp(
            prefix=f"soak-ring-{tenant}-", suffix=".ring")
        os.close(rfd)
        argv += ["--ring-out", ring_path]
    proc = subprocess.Popen(
        argv, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return proc, path, ring_path


def _collect_worker_rows(row: dict, outs: list) -> None:
    row["tenants"] = {}
    for path in outs:
        try:
            with open(path) as f:
                w = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
        row["tenants"][w["tenant"]] = w


def _merge_chaos_rings(row: dict, worker_rings: list, tracing) -> None:
    """nns-weave distributed breach artifact: dump the server's ring,
    join it with every live worker's ring dump into ONE offset-corrected
    Chrome trace (``row["merged_trace"]``), and record which rings were
    missing (a SIGKILLed worker leaves an empty file — the server-side
    view is the documented fallback).  Merge stats + schema problems ride
    ``row["merged"]`` so the CI weave gate can assert on them."""
    fd, spath = tempfile.mkstemp(prefix="soak-ring-server-",
                                 suffix=".ring")
    os.close(fd)
    paths = [spath] + [p for p in worker_rings if p]
    try:
        tracing.dump_ring(spath, proc="server")
        rings, missing = [], []
        for p in paths:
            try:
                rings.append(tracing.load_ring(p))
            except (OSError, ValueError):
                missing.append(os.path.basename(p))
        obj, stats = tracing.merge_rings(rings)
        mfd, mpath = tempfile.mkstemp(prefix="soak-weave-",
                                      suffix=".trace.json")
        with os.fdopen(mfd, "w") as f:
            json.dump(obj, f)
        row["merged_trace"] = mpath
        row["merged"] = {**stats, "rings_missing": missing,
                         "problems": tracing.validate_chrome(obj)[:10]}
    except Exception as e:  # noqa: BLE001 - artifact is best-effort
        row["merged_trace"] = None
        row["merged"] = {"error": str(e)}
    finally:
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass


def run_chaos_profile(chaos: str, *, tenants: int = 3,
                      duration: float = 8.0, p99_ms: float = 15000.0,
                      sid: int = 950, slots: int = 4, max_new: int = 24,
                      watchdog_s: float = 15.0) -> dict:
    """One chaos row: a continuous-serving LLM server (bounded paged-KV
    pool, shed admission, reconnect-capable stream clients), one
    injected fault, and recovery assertions — surviving tenants' SLO
    green, orphaned KV blocks reclaimed to the free list."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils import tracing
    from nnstreamer_tpu.utils.watchdog import Watchdog

    metrics.reset()
    tracing.recorder.clear()
    tenant_names = [f"t{i}" for i in range(tenants)]
    policy = {"tenants": [
        {"tenant": t, "p99_ms": p99_ms, "error_budget": 0.5}
        for t in tenant_names]}
    # bounded pool: 3 blocks/slot (a stream reserves 2 at T<=8 +
    # max_new 24, block 16) — small enough that a leaked stream would
    # visibly dent the free list, roomy enough to never defer admission
    kv_blocks = 3 * slots
    # p99 objective is a STALL guardrail on the CPU proxy (queued-stream
    # tails legitimately reach seconds), not a perf claim; send-buf is
    # small so a wedged client's unread stream hits the send timeout
    # instead of being absorbed by kernel buffering
    srv = nt.Pipeline(
        f"tensor_query_serversrc name=ssrc port=0 id={sid} "
        f"admission=shed max-backlog=64 send-buf=8192 ! "
        f"tensor_filter name=f framework=llm model=llama_tiny "
        f"custom=max_new:{max_new},serve:continuous,slots:{slots},"
        f"stream_chunk:4,temperature:0.0,dtype:float32,"
        f"kv_blocks:{kv_blocks},stream_idle_timeout:0.5,admit_timeout:10 "
        f"invoke-dynamic=true ! "
        f"tensor_query_serversink name=ssink id={sid}",
        trace_mode="ring", slo=policy)
    row: dict = {"profile": f"chaos_{chaos}", "chaos": chaos,
                 "tenants_n": tenants, "duration_s": duration,
                 "slots": slots, "kv_blocks": kv_blocks,
                 "max_new": max_new, "p99_objective_ms": p99_ms}
    wd_fired = threading.Event()
    with srv:
        port = srv.element("ssrc").bound_port
        wd = Watchdog(watchdog_s, wd_fired.set)
        stop_mon = threading.Event()

        def monitor():
            # token-stream progress feed: query_server.out counts per
            # TOKEN here, so the request/response soak's `pending <= 0`
            # idle test is meaningless — instead feed on any forward
            # progress (requests in / tokens out / sheds), or when the
            # serve loop is genuinely EMPTY (no live slots, nothing
            # waiting or mid-prefill: the llm.serve gauges).  A wedged
            # loop — streams live or queued, nothing advancing — stops
            # feeding and the dog fires.
            last = -1.0
            while not stop_mon.wait(0.25):
                snap = metrics.snapshot()
                gauges = metrics.gauges()
                progress = (snap.get("query_server.in", 0.0)
                            + snap.get("llm.tokens", 0.0)
                            + snap.get("query_server.shed", 0.0))
                serve_empty = (gauges.get("llm.serve.occupancy",
                                          0.0) <= 0
                               and gauges.get("llm.serve.waiting",
                                              0.0) <= 0)
                if progress != last or serve_empty:
                    wd.feed()
                last = progress

        mon = threading.Thread(target=monitor, daemon=True)
        workers, outs = [], []
        with wd:
            mon.start()
            worker_rings = []
            for i, t in enumerate(tenant_names):
                mode = ("wedge" if chaos == "wedge_tenant" and i == 0
                        else "stream")
                proc, path, ring_path = _spawn_worker(
                    "steady", port, t, duration, 20.0, 15.0, mode=mode,
                    ring=True)
                workers.append(proc)
                outs.append(path)
                worker_rings.append(ring_path)
            ctl = ChaosController(
                chaos, duration, workers=workers,
                core_getter=lambda: srv.element("ssrc")._core,
                traffic_fn=lambda: metrics.snapshot().get(
                    "query_server.in", 0.0) > 0)
            if chaos in ("kill_worker", "drop_conn", "slow_stage"):
                ctl.start()
            deadline = time.monotonic() + duration * 4 + 120
            killed = []
            for i, w in enumerate(workers):
                try:
                    rc = w.wait(timeout=max(
                        5.0, deadline - time.monotonic()))
                    if rc not in (0, None) and rc < 0:
                        killed.append(tenant_names[i])
                except subprocess.TimeoutExpired:
                    w.kill()
            ctl.stop()
            row["chaos_record"] = ctl.record
            row["killed_tenants"] = killed
            # quiesce: every surviving stream finishes, every orphaned
            # one is cancelled + reaped (grace 0.5 s) — the allocator
            # accounting the row asserts
            fw = srv.element("f").fw
            fw.drain(timeout=60)
            loop = getattr(fw, "_serve", None)
            reclaim_by = time.monotonic() + 10.0
            while loop is not None and time.monotonic() < reclaim_by:
                stats = loop.pool_stats()
                if stats["blocks_free"] == stats["blocks_total"]:
                    break
                time.sleep(0.1)
            row["pool"] = loop.pool_stats() if loop is not None else None
            stop_mon.set()
            mon.join(timeout=2.0)
        _collect_worker_rows(row, outs)
        _merge_chaos_rings(row, worker_rings, tracing)
        snap = metrics.snapshot()
        row["serve"] = {
            "cancelled": snap.get("llm.serve.cancelled", 0.0),
            "reaped": snap.get("llm.serve.reaped", 0.0),
            "reaped_blocks": snap.get("llm.serve.reaped_blocks", 0.0),
            "admit_timeouts": snap.get("llm.serve.admit_timeouts", 0.0),
            "sink_streams_cancelled": snap.get(
                "ssink.streams_cancelled", 0.0),
            "sink_dropped": snap.get("ssink.dropped", 0.0),
        }
        report = srv.slo_report()
        row["slo_report"] = report
        row["watchdog_fired"] = wd_fired.is_set()
        surviving = [t for t in tenant_names
                     if t not in killed
                     and not (chaos == "wedge_tenant" and t == "t0")]
        bad = []
        for t in surviving:
            v = report["tenants"].get(t)
            if v is not None and any(
                    viol.startswith("p99") for viol in v["violations"]):
                bad.append(t)
        row["surviving"] = surviving
        row["surviving_p99_green"] = not bad
        row["reclaimed_ok"] = bool(
            row["pool"]
            and row["pool"]["blocks_free"] == row["pool"]["blocks_total"])
        # nns-tsan posture (docs/ANALYSIS.md "Threads pass"): with
        # NNS_TPU_TSAN=1 the tracked locks record-only here; the tsan
        # gate asserts zero live inversions over the whole chaos run
        from nnstreamer_tpu.utils import locks
        row["tsan"] = locks.report()
        if wd_fired.is_set() or not row["surviving_p99_green"]:
            row["ring_dump"] = tracing.format_recent(5.0)[-120:]
        else:
            row["ring_dump"] = None
    return row


def run_elastic_profile(*, tenants: int = 3, duration: float = 24.0,
                        rate: float = 60.0, service_ms: float = 5.0,
                        p99_ms: float = 500.0, max_backlog: int = 16,
                        inflight: int = 64, sid: int = 980) -> dict:
    """The autoscaler row (BENCH_ELASTIC): offered load DOUBLES at the
    midpoint past service capacity.  The front door starts in
    ``downgrade`` (degrade-by-default: overflow rides the low-priority
    lane, where it accrues latency and — once the lane fills — sheds);
    the burn-rate gauges spike on the overflow, and the
    :class:`~nnstreamer_tpu.utils.elastic.Autoscaler` reacts through
    its policy table, flipping the burning tenant class to ``shed``
    admission (the latency-protecting edge: answer the overflow
    immediately instead of parking it), span-stamped ``elastic.scale``
    and rate-limited with hysteresis."""
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils import elastic, tracing
    from nnstreamer_tpu.utils.watchdog import Watchdog

    metrics.reset()
    tracing.recorder.clear()
    tenant_names = [f"t{i}" for i in range(tenants)]
    _register_work(service_ms)
    policy = {"tenants": [
        {"tenant": t, "p99_ms": p99_ms, "error_budget": 0.01}
        for t in tenant_names]}
    srv = nt.Pipeline(
        f"tensor_query_serversrc name=ssrc port=0 id={sid} "
        f"admission=downgrade max-backlog={max_backlog} ! "
        f"tensor_filter framework=custom-easy model=soak-work ! "
        f"tensor_query_serversink name=ssink id={sid}",
        trace_mode="ring", slo=policy)
    scale_policy = {"rules": [
        {"tenant": "*", "burn_above": 2.0, "burn_below": 0.5,
         "action": "admission:shed", "cooldown_s": 1.0},
    ]}
    row: dict = {"profile": "elastic", "tenants_n": tenants,
                 "duration_s": duration,
                 "offered_rate_per_tenant_peak": rate,
                 "service_ms": service_ms,
                 "max_backlog": max_backlog,
                 "p99_objective_ms": p99_ms,
                 "autoscale_policy": scale_policy}
    wd_fired = threading.Event()
    with srv:
        port = srv.element("ssrc").bound_port
        scaler = elastic.Autoscaler(srv, scale_policy).start()
        wd = Watchdog(10.0, wd_fired.set)
        stop_mon = threading.Event()
        #: per-tenant timeline of p99-violation verdicts, one entry per
        #: 0.5 s eval window — the acceptance metric ("no tenant's p99
        #: objective breaches for more than one eval window")
        timeline: dict = {t: [] for t in tenant_names}

        def monitor():
            last = -1.0
            while not stop_mon.wait(0.5):
                snap = metrics.snapshot()
                answered = (snap.get("query_server.out", 0.0)
                            + snap.get("query_server.shed", 0.0)
                            + snap.get("query_server.downgraded", 0.0))
                pending = snap.get("query_server.in", 0.0) - answered
                if answered != last or pending <= 0:
                    wd.feed()
                last = answered
                try:
                    rep = srv.slo_report()
                except Exception:  # noqa: BLE001
                    continue
                for t in tenant_names:
                    v = rep["tenants"].get(t)
                    breach = bool(v and any(
                        viol.startswith("p99") for viol in v["violations"]))
                    timeline[t].append(breach)

        mon = threading.Thread(target=monitor, daemon=True)
        workers, outs = [], []
        with wd:
            mon.start()
            for t in tenant_names:
                proc, path, _ = _spawn_worker(
                    "elastic", port, t, duration, rate, 10.0,
                    inflight=inflight)
                workers.append(proc)
                outs.append(path)
            deadline = time.monotonic() + duration * 4 + 60
            for w in workers:
                try:
                    w.wait(timeout=max(5.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    w.kill()
            stop_mon.set()
            mon.join(timeout=3.0)
        scaler.stop()
        _collect_worker_rows(row, outs)
        snap = metrics.snapshot()
        row["server"] = {
            "requests_in": snap.get("query_server.in", 0.0),
            "responses_out": snap.get("query_server.out", 0.0),
            "sheds_total": snap.get("query_server.shed", 0.0),
            "downgraded_total": snap.get("query_server.downgraded", 0.0),
        }
        row["autoscaler_actions"] = list(scaler.actions)
        row["scale_spans"] = sum(
            1 for e in tracing.recorder.events()
            if e.kind == "elastic.scale")
        row["max_consecutive_p99_windows"] = {
            t: max((len(list(g)) for k, g in itertools.groupby(tl) if k),
                   default=0)
            for t, tl in timeline.items()}
        row["slo_report"] = srv.slo_report()
        row["watchdog_fired"] = wd_fired.is_set()
        row["ring_dump"] = (tracing.format_recent(5.0)[-120:]
                            if wd_fired.is_set() else None)
    return row


# ---------------------------------------------------------------------------
# yank_process: kill -9 the serving process, restart with journal replay
# ---------------------------------------------------------------------------

def run_server(args) -> int:
    """--server worker mode: the KILLABLE serving process of the
    yank_process profile — a journaled front door on a FIXED port that
    runs until SIGTERM (clean stats dump) or SIGKILL (the fault)."""
    import signal as _signal

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics
    from nnstreamer_tpu.utils.journal import scan

    _register_work(args.service_ms)
    replay = " journal-replay=true" if args.journal_replay else ""
    srv = nt.Pipeline(
        f"tensor_query_serversrc name=ssrc port={args.port} "
        f"id={args.sid} admission=block max-backlog=256 "
        f"journal={args.journal} journal-fsync={args.journal_fsync}"
        f"{replay} ! "
        f"tensor_filter framework=custom-easy model=soak-work ! "
        f"tensor_query_serversink id={args.sid}")
    stop = threading.Event()
    _signal.signal(_signal.SIGTERM, lambda *a: stop.set())
    with srv:
        print("SERVER_READY", flush=True)
        stop.wait(args.duration)
        # quiesce: let in-flight answers drain before the stats dump
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            snap = metrics.snapshot()
            if snap.get("query_server.in", 0.0) + snap.get(
                    "query_server.replayed", 0.0) <= \
                    snap.get("query_server.out", 0.0) + snap.get(
                        "query_server.replay_answered", 0.0) + snap.get(
                        "query_server.shed", 0.0):
                break
            time.sleep(0.1)
    snap = metrics.snapshot()
    st = scan(args.journal)
    row = {
        "requests_in": snap.get("query_server.in", 0.0),
        "responses_out": snap.get("query_server.out", 0.0),
        "replayed": snap.get("query_server.replayed", 0.0),
        "replay_answered": snap.get("query_server.replay_answered", 0.0),
        "journal_appends": snap.get("journal.appends", 0.0),
        "journal_acks": snap.get("journal.acks", 0.0),
        "wire_rejects": snap.get("query_server.wire_rejects", 0.0),
        "journal_unanswered_at_exit": len(st.unanswered),
    }
    with open(args.out, "w") as f:
        json.dump(row, f)
    return 0


def _free_port() -> int:
    import socket as _socket

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(port: int, sid: int, jdir: str, replay: bool,
                  service_ms: float, fsync: str, lifetime: float):
    fd, path = tempfile.mkstemp(prefix="soak-srv-", suffix=".json")
    os.close(fd)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--server",
         "--port", str(port), "--sid", str(sid), "--journal", jdir,
         "--journal-replay", "1" if replay else "0",
         "--journal-fsync", fsync,
         "--service-ms", str(service_ms),
         "--duration", str(lifetime), "--out", path],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, text=True)
    return proc, path


def _await_port(port: int, timeout: float = 90.0) -> bool:
    import socket as _socket

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            _socket.create_connection(("127.0.0.1", port),
                                      timeout=1.0).close()
            return True
        except OSError:
            time.sleep(0.1)
    return False


def run_yank_profile(*, tenants: int = 2, duration: float = 8.0,
                     rate: float = 40.0, service_ms: float = 15.0,
                     sid: int = 940, fsync: str = "batch") -> dict:
    """The yank_process durability row (ISSUE 12): SIGKILL the serving
    subprocess mid-run, restart it with journal replay on the same
    port, and prove the exactly-once contract on the journal files
    themselves (unanswered-at-kill == replayed == replay-answered, ack
    multiplicity 1, nothing unanswered at the end, no client losses)."""
    import signal as _signal

    from nnstreamer_tpu.utils.journal import scan

    jdir = tempfile.mkdtemp(prefix="soak-journal-")
    port = _free_port()
    row: dict = {"profile": "yank_process", "tenants_n": tenants,
                 "duration_s": duration, "rate_per_tenant": rate,
                 "service_ms": service_ms, "journal_fsync": fsync,
                 "port": port}
    srv_a, stats_a_path = _spawn_server(
        port, sid, jdir, False, service_ms, fsync, duration * 6 + 120)
    try:
        if not _await_port(port):
            row["error"] = "server A never came up"
            return row
        workers, outs = [], []
        for i in range(tenants):
            fd, path = tempfile.mkstemp(prefix="soak-yank-",
                                        suffix=".json")
            os.close(fd)
            outs.append(path)
            workers.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 "--port", str(port), "--tenant", f"t{i}",
                 "--profile", "steady", "--duration", str(duration),
                 "--rate", str(rate), "--timeout", "60",
                 "--reconnect", "25", "--out", path],
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu")))
        # anchor the kill on observed traffic (journal bytes), then
        # yank mid-run
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline and not scan(jdir).requests:
            time.sleep(0.1)
        time.sleep(duration * 0.35)
        os.kill(srv_a.pid, _signal.SIGKILL)
        srv_a.wait(timeout=10)
        row["killed"] = True
        st_kill = scan(jdir)
        row["journaled_at_kill"] = len(st_kill.requests)
        row["unanswered_at_kill"] = len(st_kill.unanswered)
        # restart on the SAME port with replay: reconnecting clients
        # resend their pending requests as NEW journal entries while
        # the replayed ones answer server-side
        srv_b, stats_b_path = _spawn_server(
            port, sid, jdir, True, service_ms, fsync,
            duration * 6 + 120)
        try:
            row["restarted"] = _await_port(port)
            w_deadline = time.monotonic() + duration * 6 + 120
            for w in workers:
                try:
                    w.wait(timeout=max(5.0,
                                       w_deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    w.kill()
            # the journal must drain to fully-answered
            drain_by = time.monotonic() + 30.0
            while time.monotonic() < drain_by \
                    and scan(jdir).unanswered:
                time.sleep(0.2)
            srv_b.send_signal(_signal.SIGTERM)
            try:
                srv_b.wait(timeout=30)
            except subprocess.TimeoutExpired:
                srv_b.kill()
        finally:
            if srv_b.poll() is None:
                srv_b.kill()
        _collect_worker_rows(row, outs)
        try:
            with open(stats_b_path) as f:
                row["server_b"] = json.load(f)
        except (OSError, json.JSONDecodeError):
            row["server_b"] = None
        st_end = scan(jdir)
        row["journaled_total"] = len(st_end.requests)
        row["unanswered_end"] = len(st_end.unanswered)
        row["ack_multiplicity_ok"] = all(
            m == 1 for m in st_end.ack_multiplicity.values())
        row["lost_total"] = sum(
            w.get("lost", 0) for w in (row.get("tenants") or {}).values())
        row["completed_total"] = sum(
            w.get("completed", 0)
            for w in (row.get("tenants") or {}).values())
        row["reconnects_total"] = sum(
            w.get("reconnects", 0.0)
            for w in (row.get("tenants") or {}).values())
        sb = row.get("server_b") or {}
        row["replayed"] = sb.get("replayed")
        row["replay_answered"] = sb.get("replay_answered")
        row["replay_exactly_once"] = bool(
            sb
            and sb.get("replayed") == row["unanswered_at_kill"]
            and sb.get("replay_answered") == sb.get("replayed")
            and row["unanswered_end"] == 0
            and row["ack_multiplicity_ok"])
        return row
    finally:
        for leftover in (srv_a,):
            if leftover.poll() is None:
                leftover.kill()
        try:
            os.unlink(stats_a_path)
        except OSError:
            pass


def default_profiles(smoke: bool) -> list:
    """(profile, kwargs) rows.  Smoke = the seconds-long CI shape: a
    low-load steady pass that must shed nothing, and a deliberately
    overloaded pass that must shed and breach."""
    if smoke:
        return [
            ("steady", dict(tenants=2, duration=2.5, rate=25.0,
                            service_ms=1.0, admission="shed",
                            max_backlog=64, p99_ms=2000.0)),
            ("overload", dict(tenants=2, duration=2.5, rate=250.0,
                              service_ms=15.0, admission="shed",
                              max_backlog=4, p99_ms=30.0)),
        ]
    full = dict(tenants=3, service_ms=2.0, admission="shed",
                max_backlog=64, p99_ms=500.0)
    return [
        ("ramp", dict(full, duration=30.0, rate=60.0)),
        ("spike", dict(full, duration=30.0, rate=80.0)),
        ("churn", dict(full, duration=30.0, rate=40.0)),
        ("overload", dict(tenants=3, duration=15.0, rate=300.0,
                          service_ms=15.0, admission="shed",
                          max_backlog=8, p99_ms=50.0)),
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_SOAK_r01.json")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-long 2-tenant CI shape (steady + "
                         "overload)")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos-injected soak: kill_worker / drop_conn / "
                         "wedge_tenant against a continuous-serving LLM "
                         "server + a slow_stage row (ISSUE 11)")
    ap.add_argument("--chaos-smoke", dest="chaos_smoke",
                    action="store_true",
                    help="seconds-long kill_worker + drop_conn chaos "
                         "shape (the CI chaos gate)")
    ap.add_argument("--elastic", action="store_true",
                    help="the autoscaler row: load doubles mid-run, the "
                         "utils/elastic.Autoscaler must react "
                         "(BENCH_ELASTIC rows)")
    ap.add_argument("--yank", action="store_true",
                    help="yank_process durability row (ISSUE 12): "
                         "SIGKILL the journaled serving subprocess "
                         "mid-run, restart with journal-replay, assert "
                         "exactly-once answers (BENCH_ARMOR rows)")
    ap.add_argument("--yank-smoke", dest="yank_smoke",
                    action="store_true",
                    help="seconds-long yank_process shape (the CI "
                         "armor gate)")
    ap.add_argument("--profiles", default=None,
                    help=f"comma-separated subset of {PROFILES}")
    ap.add_argument("--duration", type=float, default=None,
                    help="override per-profile duration (s)")
    # worker mode (internal): one tenant's load generator
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--ring-out", dest="ring_out", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--mode", default="plain",
                    choices=("plain", "stream", "wedge"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--tenant", default="t0", help=argparse.SUPPRESS)
    ap.add_argument("--profile", default="steady", help=argparse.SUPPRESS)
    ap.add_argument("--rate", type=float, default=50.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--timeout", type=float, default=10.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--inflight", type=int, default=8,
                    help=argparse.SUPPRESS)
    ap.add_argument("--reconnect", type=int, default=0,
                    help=argparse.SUPPRESS)
    # server mode (internal): the yank_process killable serving process
    ap.add_argument("--server", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--sid", type=int, default=940,
                    help=argparse.SUPPRESS)
    ap.add_argument("--journal", default="", help=argparse.SUPPRESS)
    ap.add_argument("--journal-replay", dest="journal_replay",
                    default="0", help=argparse.SUPPRESS)
    ap.add_argument("--journal-fsync", dest="journal_fsync",
                    default="batch", help=argparse.SUPPRESS)
    ap.add_argument("--service-ms", dest="service_ms", type=float,
                    default=2.0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.server:
        args.journal_replay = args.journal_replay in ("1", "true")
        args.duration = args.duration or 600.0
        return run_server(args)
    if args.worker:
        return run_worker(args)

    out_path = args.out if os.path.isabs(args.out) \
        else os.path.join(os.getcwd(), args.out)

    if args.chaos or args.chaos_smoke:
        t_start = time.time()
        rows = []
        plan = (["kill_worker", "drop_conn"] if args.chaos_smoke
                else ["kill_worker", "drop_conn", "wedge_tenant"])
        dur = args.duration or (6.0 if args.chaos_smoke else 10.0)
        for i, chaos in enumerate(plan):
            print(f"== chaos {chaos} ({dur}s)", flush=True)
            row = run_chaos_profile(chaos, duration=dur, sid=950 + i)
            print(f"   reclaimed={row['reclaimed_ok']} "
                  f"surviving_green={row['surviving_p99_green']} "
                  f"cancelled={row['serve']['cancelled']:.0f} "
                  f"reaped={row['serve']['reaped']:.0f} "
                  f"watchdog={row['watchdog_fired']}", flush=True)
            rows.append(row)
        if args.chaos:
            print("== chaos slow_stage", flush=True)
            row = run_profile(
                "steady", tenants=3, duration=dur, rate=40.0,
                service_ms=2.0, admission="shed", max_backlog=64,
                p99_ms=60.0, sid=960, chaos="slow_stage",
                slow_extra_ms=120.0)
            row["profile"] = "chaos_slow_stage"
            print(f"   slo_ok={row['slo_report']['ok']} "
                  f"chaos={row.get('chaos_record')}", flush=True)
            rows.append(row)
        recovered = all(r.get("reclaimed_ok", True)
                        and r.get("surviving_p99_green", True)
                        and not r.get("watchdog_fired")
                        for r in rows)
        doc = {
            "note": "chaos-injected soak (tools/soak.py --chaos): one "
                    "fault per row against a continuous-serving LLM "
                    "front door; recovery = surviving tenants' p99 "
                    "green + orphaned KV blocks reclaimed to the free "
                    "list + no watchdog fire.",
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+00:00",
                                         time.gmtime(t_start)),
            "smoke": bool(args.chaos_smoke),
            "rows": rows,
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({
            "metric": "soak_chaos_recovered",
            "value": 1.0 if recovered else 0.0, "unit": "bool",
            "profiles": [r["profile"] for r in rows],
            "cancelled": sum(r.get("serve", {}).get("cancelled", 0.0)
                             for r in rows),
            "artifact": os.path.basename(out_path),
        }))
        print(f"wrote {out_path} ({len(rows)} rows)")
        return 0 if recovered else 1

    if args.yank or args.yank_smoke:
        t_start = time.time()
        dur = args.duration or (6.0 if args.yank_smoke else 12.0)
        print(f"== yank_process ({dur}s, fsync=batch)", flush=True)
        row = run_yank_profile(duration=dur)
        ok = bool(row.get("replay_exactly_once")
                  and row.get("lost_total", 1) == 0
                  and row.get("unanswered_at_kill", 0) >= 1)
        print(f"   killed={row.get('killed')} "
              f"unanswered_at_kill={row.get('unanswered_at_kill')} "
              f"replayed={row.get('replayed')} "
              f"replay_answered={row.get('replay_answered')} "
              f"unanswered_end={row.get('unanswered_end')} "
              f"lost={row.get('lost_total')} "
              f"reconnects={row.get('reconnects_total')}", flush=True)
        doc = {
            "note": "yank_process durability soak (tools/soak.py "
                    "--yank, ISSUE 12): the journaled serving process "
                    "is SIGKILLed mid-run and restarted with "
                    "journal-replay=true on the same port; exactly-once "
                    "= every accepted-but-unanswered entry at the kill "
                    "is re-admitted and acked once (journal files are "
                    "the source of truth), reconnecting clients resend "
                    "pending requests as new entries and lose nothing.",
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+00:00",
                                         time.gmtime(t_start)),
            "smoke": bool(args.yank_smoke),
            "rows": [row],
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({
            "metric": "yank_replay_exactly_once",
            "value": 1.0 if ok else 0.0, "unit": "bool",
            "unanswered_at_kill": row.get("unanswered_at_kill"),
            "replayed": row.get("replayed"),
            "lost_total": row.get("lost_total"),
            "artifact": os.path.basename(out_path),
        }))
        print(f"wrote {out_path} (1 row)")
        return 0 if ok else 1

    if args.elastic:
        t_start = time.time()
        row = run_elastic_profile(duration=args.duration or 24.0)
        worst = max(row["max_consecutive_p99_windows"].values(),
                    default=0)
        doc = {
            "note": "autoscaler soak (tools/soak.py --elastic): offered "
                    "load doubles at the midpoint to ~1.5x capacity; "
                    "the shed-bounded front door keeps p99 green while "
                    "the burn-rate gauges spike, and the "
                    "utils/elastic.Autoscaler reacts through its policy "
                    "table (elastic.scale spans, hysteresis bands, "
                    "cooldown).",
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+00:00",
                                         time.gmtime(t_start)),
            "rows": [row],
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({
            "metric": "elastic_scale_actions",
            "value": len(row["autoscaler_actions"]), "unit": "actions",
            "scale_spans": row["scale_spans"],
            "max_consecutive_p99_windows": worst,
            "sheds_total": row["server"]["sheds_total"],
            "downgraded_total": row["server"]["downgraded_total"],
            "artifact": os.path.basename(out_path),
        }))
        print(f"wrote {out_path} (1 row)")
        ok = (row["autoscaler_actions"] and row["scale_spans"] >= 1
              and worst <= 1 and not row["watchdog_fired"])
        return 0 if ok else 1

    rows = []
    plan = default_profiles(args.smoke)
    if args.profiles:
        want = set(args.profiles.split(","))
        unknown = want - set(PROFILES)
        if unknown:
            ap.error(f"unknown profile(s): {sorted(unknown)}")
        plan = [(p, kw) for p, kw in plan if p in want]
    t_start = time.time()
    for i, (profile, kw) in enumerate(plan):
        if args.duration:
            kw = dict(kw, duration=args.duration)
        print(f"== soak {profile}: {kw}", flush=True)
        row = run_profile(profile, sid=900 + i, **kw)
        srv = row["server"]
        print(f"   in={srv['requests_in']:.0f} out={srv['responses_out']:.0f} "
              f"sheds={srv['sheds_total']:.0f} "
              f"slo_ok={row['slo_report']['ok']} "
              f"watchdog={row['watchdog_fired']}", flush=True)
        rows.append(row)
    doc = {
        "note": "query front-door soak (tools/soak.py): N tenant worker "
                "subprocesses per profile against one fresh "
                "serversrc!custom-easy!serversink pipeline, "
                "trace_mode=ring, per-tenant SLO engine live.  Client "
                "latencies are wall-clock push->pull (t_send meta rides "
                "the wire); burst fps = best 0.5 s completion window.",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+00:00",
                                     time.gmtime(t_start)),
        "smoke": bool(args.smoke),
        "rows": rows,
    }
    out_path = args.out if os.path.isabs(args.out) \
        else os.path.join(os.getcwd(), args.out)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    total_fps = sum(t.get("sustained_fps", 0.0)
                    for r in rows for t in r.get("tenants", {}).values())
    # the summary line (last JSON line with "metric")
    print(json.dumps({
        "metric": "soak_sustained_fps_sum", "value": round(total_fps, 2),
        "unit": "fps",
        "profiles": [r["profile"] for r in rows],
        "sheds_total": sum(r["server"]["sheds_total"] for r in rows),
        "slo_ok": all(r["slo_report"]["ok"] for r in rows),
        "artifact": os.path.basename(out_path),
    }))
    print(f"wrote {out_path} ({len(rows)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
