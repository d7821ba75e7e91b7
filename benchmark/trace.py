"""From a profiler trace to numbers: which planes are devices, the union
of the intervals in which an operation ran, sums by name, idle gaps.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
plain data — ``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` — and
everything else works on that, so the arithmetic is tested on a
hand-written fixture and a later trace format needs one new loader.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Dict, List, Tuple

from .stats import interval_union

Event = Tuple[str, int, int]
Planes = Dict[str, Dict[str, List[Event]]]

#: the device planes of a TPU trace, and the lines on them that hold
#: leaf operations and whole compiled programs (seen by hand, PERF.md)
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(path: str) -> Planes:
    from jax.profiler import ProfileData

    planes: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, int(ev.start_ns),
                            int(ev.duration_ns)))
    return planes


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
#: wrappers whose events enclose the operations they run
CONTROL_FLOW = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """``%fusion.3 bf16[32,128] fusion`` from the HLO text the profiler
    names a device operation by: its name, result shape and opcode."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    shape = "(tuple)" if rest.startswith("(") else rest.split("{")[0]
    m = _OPCODE.search(rest)
    return f"{name} {shape.strip()} {m.group(1) if m else '?'}"


def device_planes(planes: Planes) -> Dict[str, Dict[str, List[Event]]]:
    return {name: lines for name, lines in planes.items()
            if name.startswith(DEVICE_PLANE_PREFIX)
            and lines.get(OPS_LINE)}


def _intervals(events: List[Event]):
    return [(s, s + d) for _, s, d in events if d > 0]


def reduce(planes: Planes) -> dict:
    """Busy union, traced span, per-name sums and the longest idle gaps,
    averaged over the device planes that ran anything.

    The traced span of a device is from its first operation's start to its
    last one's end: the profiler's own start and stop cost host time in
    which nothing is recorded, and must not read as idle."""
    devs = device_planes(planes)
    if not devs:
        return {"devices": 0}
    busy, span = [], []
    ops: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    modules: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    gaps: List[Tuple[float, str]] = []
    for lines in devs.values():
        iv = sorted(_intervals(lines[OPS_LINE]))
        lo, hi = iv[0][0], max(e for _, e in iv)
        busy.append(interval_union(iv) / 1e9)
        span.append((hi - lo) / 1e9)
        for hlo, _s, d in lines[OPS_LINE]:
            name = short_name(hlo)
            if name.rsplit(" ", 1)[-1] in CONTROL_FLOW:
                continue   # its body's operations are counted themselves
            ops[name] = ops.get(name, 0.0) + d / 1e9
            op_calls[name] = op_calls.get(name, 0) + 1
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for name, _s, d in mods:
            modules[name] = modules.get(name, 0.0) + d / 1e9
            module_calls[name] = module_calls.get(name, 0) + 1
        # idle gaps between compiled programs, named by what ran next
        for (_n0, s0, d0), (n1, s1, _d1) in zip(mods, mods[1:]):
            if s1 > s0 + d0:
                gaps.append(((s1 - s0 - d0) / 1e9, f"before {n1}"))
    n = len(devs)
    gap_sum: Dict[str, float] = {}
    for g, name in gaps:
        gap_sum[name] = gap_sum.get(name, 0.0) + g / n
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "window_s": sum(span) / n,
        "ops": {k: v / n for k, v in ops.items()},
        "op_calls": op_calls,
        "modules": {k: v / n for k, v in modules.items()},
        "module_calls": module_calls,
        "idle_gaps": gap_sum,
    }


def idle_percent(reduced) -> "float | None":
    """Share of the traced span in which no operation ran on the device."""
    if not reduced or not reduced.get("window_s"):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def program_totals(reduced: dict, fragment: str):
    """Executions and device seconds of the compiled programs whose name
    holds ``fragment``."""
    calls = sum(n for name, n in reduced["module_calls"].items()
                if fragment in name)
    seconds = sum(s for name, s in reduced["modules"].items()
                  if fragment in name)
    return calls, seconds


def breakdown(reduced: dict, top: int = 10) -> dict:
    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"device_ops": first(reduced.get("ops", {})),
            "idle_gaps": first(reduced.get("idle_gaps", {}))}


class DeviceTrace:
    """Profiles a stretch in the middle of the window from a thread of
    its own, so starting and stopping the profiler never stalls the
    load."""

    def __init__(self, directory: str, at_s: float, for_s: float):
        self.directory = directory
        self.at_s, self.for_s = at_s, for_s
        self.host_span = None   # perf_counter at start and stop
        self.error = None
        self._thread = None

    def arm(self, window_start: float):
        import threading

        def body():
            import jax

            try:
                delay = window_start + self.at_s - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                shutil.rmtree(self.directory, ignore_errors=True)
                # device planes only: the Python tracer records every
                # call of the serve loop, and the host tracer hundreds of
                # thousands of futex events a second on a busy stream
                # pipeline (stopping then took two minutes)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.directory,
                                         profiler_options=opts)
                t0 = time.perf_counter()
                time.sleep(self.for_s)
                t1 = time.perf_counter()
                jax.profiler.stop_trace()
                self.host_span = (t0, t1)
            except Exception as e:  # noqa: BLE001 - reported by read()
                self.error = e

        self._thread = threading.Thread(target=body, name="bench-trace",
                                        daemon=True)
        self._thread.start()

    def read(self, timeout: float = 120.0) -> dict:
        """Wait for the profiler to finish writing, reduce the trace, and
        remove it (a trace is tens of megabytes; none is kept)."""
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("the profiler did not stop")
        if self.error is not None:
            raise self.error
        try:
            files = glob.glob(os.path.join(self.directory, "**",
                                           "*.xplane.pb"), recursive=True)
            if not files:
                raise FileNotFoundError(
                    f"no .xplane.pb under {self.directory}")
            reduced = reduce(load_xplane(max(files, key=os.path.getmtime)))
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
        reduced["host_span"] = self.host_span
        return reduced
