"""nns-trace CLI: validate, summarize, and capture flight-recorder dumps,
and read a device profile's idle gaps by host phase.

    # schema-check a dump (traceEvents present, required keys, ts monotonic)
    python -m nnstreamer_tpu.tools.trace validate trace.json

    # per-(stage, kind) latency table of a dump
    python -m nnstreamer_tpu.tools.trace summary trace.json

    # run a self-driving pipeline string with the flight recorder on and
    # write the Chrome trace next to you (load in Perfetto / chrome://tracing)
    python -m nnstreamer_tpu.tools.trace run \\
        "videotestsrc num-buffers=64 ! tensor_converter ! tensor_sink" \\
        --out trace.json

    # join N per-process ring dumps (tracing.dump_ring) into ONE
    # offset-corrected Chrome trace with cross-wire flow arrows
    # (docs/OBSERVABILITY.md "Distributed tracing")
    python -m nnstreamer_tpu.tools.trace merge server.ring client.ring \\
        --out merged.json

    # what the host was doing in every idle gap between device programs:
    # a profile taken with utils.profiler.trace() while trace_mode != off
    # holds the flight recorder's spans as host annotations on the
    # profiler's clock (docs/OBSERVABILITY.md "One timeline")
    python -m nnstreamer_tpu.tools.trace gaps /tmp/profile

See docs/OBSERVABILITY.md for the span taxonomy and how the per-buffer
trace ids link batched dispatches back to individual rows.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys

#: how a TPU profile names its device planes and the line that holds one
#: event per compiled-program execution (seen by hand, PERF.md §3)
DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
#: the host annotations gaps are attributed to: the serve loop's phase
#: spans, the only ones written through ``utils.tracing.span`` today
PHASE_PREFIX = "serve."
UNNAMED = "(no annotation)"


def _cmd_validate(args) -> int:
    from ..utils.tracing import validate_chrome

    try:
        with open(args.file) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"{args.file}: unreadable: {e}", file=sys.stderr)
        return 1
    problems = validate_chrome(obj)
    if problems:
        for p in problems[:50]:
            print(f"{args.file}: {p}", file=sys.stderr)
        if len(problems) > 50:
            print(f"... and {len(problems) - 50} more", file=sys.stderr)
        return 1
    n = len(obj.get("traceEvents", []))
    linked = sum(1 for e in obj["traceEvents"]
                 if isinstance(e, dict)
                 and (e.get("args") or {}).get("trace_ids"))
    print(f"OK: {n} events, {linked} batch-linked spans")
    return 0


def _cmd_summary(args) -> int:
    try:
        with open(args.file) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"{args.file}: unreadable: {e}", file=sys.stderr)
        return 1
    # aggregate straight off the Chrome events (a dump may come from
    # another process — no recorder state needed)
    tracks = {e["tid"]: e["args"]["name"]
              for e in obj.get("traceEvents", [])
              if isinstance(e, dict) and e.get("ph") == "M"
              and e.get("name") == "thread_name"}
    agg: dict = {}
    for e in obj.get("traceEvents", []):
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        key = (tracks.get(e.get("tid"), f"tid{e.get('tid')}"),
               e.get("name", "?"))
        a = agg.setdefault(key, [0, 0.0, 0.0])
        a[0] += 1
        dur_ms = float(e.get("dur", 0.0)) / 1e3
        a[1] += dur_ms
        a[2] = max(a[2], dur_ms)
    if not agg:
        print("no complete (ph=X) spans in dump")
        return 0
    print(f"{'stage':<22s} {'kind':<10s} {'count':>7s} {'total ms':>10s} "
          f"{'mean ms':>9s} {'max ms':>9s}")
    for (stage, kind), (n, total, mx) in sorted(
            agg.items(), key=lambda kv: -kv[1][1]):
        print(f"{stage:<22s} {kind:<10s} {n:>7d} {total:>10.3f} "
              f"{total / n:>9.3f} {mx:>9.3f}")
    return 0


def _cmd_run(args) -> int:
    import nnstreamer_tpu as nt
    from ..utils.tracing import recorder

    recorder.clear()
    p = nt.Pipeline(args.pipeline, trace_mode=args.mode)
    with p:
        p.wait(timeout=args.timeout)
    n = p.dump_trace(args.out)
    print(f"{args.out}: {n} spans "
          f"(load in https://ui.perfetto.dev or chrome://tracing)")
    return 0


def _cmd_merge(args) -> int:
    from ..utils.tracing import merge_ring_files, validate_chrome

    try:
        obj, stats = merge_ring_files(args.files)
    except (OSError, ValueError) as e:
        print(f"merge: {e}", file=sys.stderr)
        return 1
    problems = validate_chrome(obj)
    with open(args.out, "w") as f:
        json.dump(obj, f)
    align = obj.get("otherData", {}).get("weave", [])
    unaligned = [a["proc"] for a in align if not a.get("aligned", True)]
    print(f"{args.out}: {stats['rings']} rings, {stats['spans']} spans, "
          f"{stats['arrows']} cross-wire arrows"
          + (f"; UNALIGNED (no clock path): {', '.join(unaligned)}"
             if unaligned else ""))
    if problems:
        for p in problems[:20]:
            print(f"{args.out}: {p}", file=sys.stderr)
        return 1
    return 0


def load_xplane(path: str) -> dict:
    """An ``.xplane.pb`` as plain data:
    ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return planes


def leaf_segments(events) -> list:
    """One thread's nested spans as disjoint ``(start, end, name)``
    segments, sorted, each named by the INNERMOST span that covers it —
    time inside ``serve.iter`` but inside none of its children is
    ``serve.iter``'s own."""
    out: list = []
    stack: list = []  # (end, name) of the open spans, outermost first
    cur = 0

    def emit(hi, name):
        nonlocal cur
        if hi > cur:
            out.append((cur, hi, name))
            cur = hi

    for s, e, name in sorted(((s, s + d, n) for n, s, d in events if d > 0),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        if stack:
            emit(s, stack[-1][1])
        cur = max(cur, s)
        stack.append((e, name))
    while stack:
        emit(*stack.pop())
    return out


def _program(name: str) -> str:
    """``jit_decode_chunk(2871896173145356635)`` -> ``jit_decode_chunk``."""
    return name.split("(", 1)[0]


def gaps_by_phase(planes: dict) -> dict:
    """For every idle gap between compiled programs on each device plane's
    ``XLA Modules`` line: which host annotation (name starting with
    ``PHASE_PREFIX``) covered how much of it.  Both are on the profiler's
    clock.

    Returns seconds, averaged over the device planes that ran anything:
    ``window_s`` (first program's start to the last one's end),
    ``idle_s``, ``by_phase`` {annotation or ``UNNAMED``: s} and
    ``by_next`` {"before <program>": {annotation: s}}; plus ``host_line``
    = the (plane, line, count) the annotations were read from."""
    best = (None, None, 0)
    for pname, lines in planes.items():
        if pname.startswith(DEVICE_PLANE_PREFIX):
            continue
        for lname, evs in lines.items():
            n = sum(1 for name, _s, _d in evs
                    if name.startswith(PHASE_PREFIX))
            if n > best[2]:
                best = (pname, lname, n)
    segs = leaf_segments(
        [e for e in planes[best[0]][best[1]]
         if e[0].startswith(PHASE_PREFIX)]) if best[2] else []
    starts = [s for s, _e, _n in segs]

    devices = window = idle = 0
    by_phase: dict = {}
    by_next: dict = {}
    for pname, lines in planes.items():
        mods = sorted((e for e in lines.get(MODULES_LINE, []) if e[2] > 0),
                      key=lambda e: e[1])
        if not pname.startswith(DEVICE_PLANE_PREFIX) or not mods:
            continue
        devices += 1
        busy_to = mods[0][1] + mods[0][2]
        for name, s, d in mods[1:]:
            if s > busy_to:
                row = by_next.setdefault(f"before {_program(name)}", {})
                left = s - busy_to
                i = max(0, bisect.bisect_right(starts, busy_to) - 1)
                while i < len(segs) and segs[i][0] < s:
                    lo, hi, phase = segs[i]
                    part = min(hi, s) - max(lo, busy_to)
                    if part > 0:
                        row[phase] = row.get(phase, 0) + part
                        left -= part
                    i += 1
                if left > 0:
                    row[UNNAMED] = row.get(UNNAMED, 0) + left
                idle += s - busy_to
            busy_to = max(busy_to, s + d)
        window += busy_to - mods[0][1]
    if not devices:
        return {"devices": 0, "host_line": best}
    k = 1e9 * devices
    for nxt, row in by_next.items():
        for phase, ns in row.items():
            row[phase] = ns / k
            by_phase[phase] = by_phase.get(phase, 0.0) + ns / k
    return {"devices": devices, "window_s": window / k, "idle_s": idle / k,
            "by_phase": by_phase, "by_next": by_next, "host_line": best}


def _cmd_gaps(args) -> int:
    path = args.path
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            print(f"{path}: no .xplane.pb under it", file=sys.stderr)
            return 1
        path = max(files, key=os.path.getmtime)
    try:
        r = gaps_by_phase(load_xplane(path))
    except Exception as e:  # noqa: BLE001 - unreadable / not an xplane
        print(f"{path}: unreadable: {e}", file=sys.stderr)
        return 1
    if not r["devices"]:
        print(f"{path}: no device plane with an '{MODULES_LINE}' line "
              f"(planes start with {DEVICE_PLANE_PREFIX})", file=sys.stderr)
        return 1
    plane, line, n = r["host_line"]
    idle = r["idle_s"]
    print(f"{path}: {r['devices']} device plane(s), window "
          f"{r['window_s']:.6f} s, idle between programs {idle:.6f} s "
          f"({100 * idle / r['window_s']:.2f} %)")
    print(f"host annotations: {n} '{PHASE_PREFIX}*' on {plane} / {line}"
          if n else f"host annotations: none named '{PHASE_PREFIX}*' — was "
          "the profile taken with utils.profiler.trace() and trace_mode on?")
    print(f"\n{'host phase':<26s} {'idle s':>10s} {'of idle':>8s}")
    for phase, sec in sorted(r["by_phase"].items(), key=lambda kv: -kv[1]):
        print(f"{phase:<26s} {sec:>10.6f} {100 * sec / idle:>7.1f}%")
    print(f"\n{'next program':<34s} {'idle s':>10s}  by host phase")
    for nxt, row in sorted(r["by_next"].items(),
                           key=lambda kv: -sum(kv[1].values())):
        parts = ", ".join(f"{ph} {sec:.6f}" for ph, sec in
                          sorted(row.items(), key=lambda kv: -kv[1]))
        print(f"{nxt:<34s} {sum(row.values()):>10.6f}  {parts}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m nnstreamer_tpu.tools.trace",
        description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    v = sub.add_parser("validate", help="schema-check a Chrome trace dump")
    v.add_argument("file")
    s = sub.add_parser("summary", help="per-stage/kind latency table")
    s.add_argument("file")
    r = sub.add_parser(
        "run", help="run a self-driving pipeline string traced, dump JSON")
    r.add_argument("pipeline")
    r.add_argument("--out", default="trace.json")
    r.add_argument("--mode", default="ring", choices=["ring", "full"])
    r.add_argument("--timeout", type=float, default=120.0)
    m = sub.add_parser(
        "merge", help="join N per-process ring dumps into one Chrome "
        "trace (offset-corrected, cross-wire flow arrows)")
    m.add_argument("files", nargs="+")
    m.add_argument("--out", default="merged.json")
    g = sub.add_parser(
        "gaps", help="device idle gaps of a profile, by the host "
        "annotation (flight-recorder span) that covers them")
    g.add_argument("path", help="an .xplane.pb, or a profile directory "
                   "(the newest one under it is read)")
    args = ap.parse_args(argv)
    return {"validate": _cmd_validate, "summary": _cmd_summary,
            "run": _cmd_run, "merge": _cmd_merge,
            "gaps": _cmd_gaps}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
