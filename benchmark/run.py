#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix, makes weights and inputs
from the seed, warms every program the cell uses (set-up), measures one
window, checks what the window produced against the plain reference, and
prints one JSON object as the last line of standard output.  Exits
non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import peaks, trace  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

#: the contract allows a cell's first run in a checkout 1200 s
FIRST_RUN_BUDGET_S = 1100.0
TRACE_SECONDS = 3.0


class CompileClock:
    """When jax compiled (or loaded from its persistent cache) a program."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.at = []
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_kw):
        if event == self.EVENT:
            with self._lock:
                self.at.append(time.perf_counter())

    def start(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)

    def between(self, lo: float, hi: float) -> int:
        with self._lock:
            return sum(1 for t in self.at if lo <= t < hi)


class Context:
    """What a driver needs of the run it belongs to."""

    def __init__(self, manifest, cell, seed, seconds, traced,
                 t_start=_T_START):
        self.manifest, self.cell = manifest, cell
        self.cfg, self.mix = manifest.config(cell), manifest.mix(cell)
        self.model = manifest.model(self.cfg)
        self.reference = manifest.reference(self.cfg)
        self.seed, self.seconds, self.trace = seed, float(seconds), traced
        self.limits = self.cfg["limits"]
        self.first_run_budget_s = FIRST_RUN_BUDGET_S
        self.t_start = t_start
        self.window = None
        self.window_ns = None
        self.compiles = CompileClock()
        self.notes = []
        self.tracer = None
        if traced:
            span = min(TRACE_SECONDS, self.seconds / 3)
            self.tracer = trace.DeviceTrace(
                os.path.join(manifest.root, ".bench_trace", cell["name"]),
                at_s=(self.seconds - span) / 2, for_s=span)

    def note(self, msg: str):
        self.notes.append(msg)

    def window_opens(self, w0: float):
        # ring spans are on time.monotonic_ns; keep the offset to ours
        off = time.monotonic_ns() - int(time.perf_counter() * 1e9)
        self.window = [w0, None]
        self.window_ns = [int(w0 * 1e9) + off, None]
        if self.tracer is not None:
            self.tracer.arm(w0)

    def window_closes(self, w1: float):
        self.window[1] = w1
        self.window_ns[1] = self.window_ns[0] + int(
            (w1 - self.window[0]) * 1e9)

    def compiles_in_window(self) -> int:
        return self.compiles.between(*self.window)

    def memory_peak(self) -> int:
        import jax

        peak = 0
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


def configure_jax(root: str):
    """The persistent compile cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), and every program kept in
    it, however quickly it compiled.  libtpu's logs, which default to a
    fixed ``/tmp/tpu_logs``, go inside the checkout too."""
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, ".tpu_logs"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(root, ".xla_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_chips(need: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        raise SystemExit(f"benchmark: needs an accelerator, jax found "
                         f"only {d.platform!r} ({d.device_kind})")
    if len(devs) < need:
        raise SystemExit(f"benchmark: the cell needs {need} chips, jax "
                         f"found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_driver(ctx):
    """The ``Driver`` of ``drivers/<kind>.py`` over this run."""
    return ctx.manifest.driver(ctx.cfg)(ctx)


def run_cell(manifest: Manifest, workload: str, seed: int, seconds: float,
             traced: bool, device: dict, t_start: float = _T_START) -> dict:
    """Everything of a run after the look for a chip; returns the result
    line's object."""
    cell = manifest.cell(workload)
    ctx = Context(manifest, cell, seed, seconds, traced, t_start)
    ctx.compiles.start()
    t_enter = time.perf_counter()
    driver = make_driver(ctx)
    driver.load()
    t_loaded = time.perf_counter()
    out = driver.run()
    ctx.note(f"set-up: {t_enter - t_start:.1f} s to start-up and imports, "
             f"{t_loaded - t_enter:.1f} s to weights, inputs and pipeline, "
             f"{ctx.window[0] - t_loaded:.1f} s to warm-up and ramp; "
             f"{len(ctx.compiles.at)} programs compiled or loaded")

    values = dict(out["end_to_end"])
    values["setup_s"] = ctx.window[0] - t_start
    if traced:
        reduced = ctx.tracer.read()
        if not reduced.get("devices"):
            raise SystemExit("benchmark: the trace holds no operation on "
                             "a device plane")
        observed = dict(out["observed"], trace=reduced,
                        peaks=peaks.peaks_for(device["kind"])
                        if device["platform"] != "cpu" else None,
                        chips=cell["chips"])
        wanted = manifest.per_layer(workload)
        values = {m["name"]: manifest.reader(m["name"])(observed)
                  for m in wanted}
        device = dict(device, busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
    else:
        wanted = manifest.end_to_end(workload)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}

    checks = [{"name": n, "value": v, "limit": lim, "ok": bool(v <= lim)}
              for n, v, lim in out["checks"]]
    result = {
        "correct": all(c["ok"] for c in checks),
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=out["memory_peak_bytes"]),
    }
    if traced:
        result["breakdown"] = trace.breakdown(reduced)
    result["notes"] = ctx.notes
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = Manifest(_ROOT)
    cell = manifest.cell(args.workload)
    configure_jax(_ROOT)
    device = find_chips(int(cell["chips"]))
    result = run_cell(manifest, args.workload, args.seed, args.seconds,
                      bool(args.trace), device)
    sys.stdout.flush()
    for note in result["notes"]:
        print(f"benchmark: {note}", file=sys.stderr)
    for c in result["checks"]:
        print(f"benchmark: check {c['name']} = {c['value']!r} (limit "
              f"{c['limit']!r}) {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
