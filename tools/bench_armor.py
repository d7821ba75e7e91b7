#!/usr/bin/env python
"""nns-armor bench (ISSUE 12, docs/ROBUSTNESS.md): the journal-overhead
A/B on the query front door + the yank_process replay row, written as
BENCH_ARMOR_r{N}.json.

    python tools/bench_armor.py --out BENCH_ARMOR_r01.json

Row 1, ``journal_overhead_ab``: the SAME serversrc!work!serversink
front door driven by an in-process client at a fixed request count,
measured once with the request journal OFF and once with
``journal=DIR journal-fsync=batch`` — per-request wall p50/p99 and
sustained fps for both, overhead = (p50_on - p50_off) / p50_off.
Target: < 3% p50 (the batch fsync policy exists so durability costs a
page-cache write + an amortized fsync, not a per-request fsync).

Row 2, ``yank_process``: tools/soak.py --yank in a subprocess — the
kill -9 / journal-replay exactly-once demonstration (see soak.py).

The stdout tail is one {"metric": ...} JSON line carrying the overhead
number.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DIMS = 32
N_REQUESTS = 600
N_WARMUP = 50


def _register_work():
    from nnstreamer_tpu.core.types import TensorsSpec
    from nnstreamer_tpu.filters.custom_easy import register_custom_easy

    spec = TensorsSpec.from_string(str(DIMS), "float32")
    register_custom_easy("armor-bench-work", lambda ins: [ins[0] * 2.0],
                         in_spec=spec, out_spec=spec)


def _drive(port: int, n: int, warmup: int) -> dict:
    """Raw-socket client: send/await one request at a time (the latency
    shape journaling actually changes — batching would hide the append
    behind pipelining)."""
    from nnstreamer_tpu.core.buffer import Buffer
    from nnstreamer_tpu.utils import wire
    from nnstreamer_tpu.utils.net import client_handshake

    sock = socket.create_connection(("127.0.0.1", port), timeout=10.0)
    try:
        client_handshake(sock, "hello", caps="other/tensors", topic="",
                         tenant="bench")
        sock.settimeout(10.0)
        lats = []
        payload = np.full((DIMS,), 1.0, np.float32)
        t_run0 = None
        for i in range(warmup + n):
            buf = Buffer([payload], meta={"_query_msg": i})
            t0 = time.perf_counter()
            wire.write_frame(sock, wire.encode_buffer(buf))
            while True:
                try:
                    raw = wire.read_frame(sock)
                    break
                except socket.timeout:
                    continue
            dt = time.perf_counter() - t0
            wire.decode_buffer(raw)
            if i == warmup:
                t_run0 = time.perf_counter()
            if i >= warmup:
                lats.append(dt * 1e3)
        span = time.perf_counter() - t_run0
        lats.sort()

        def pct(q):
            return lats[min(len(lats) - 1,
                            max(0, int(len(lats) * q / 100.0
                                       + 0.999999) - 1))]

        return {"n": n, "p50_ms": pct(50), "p99_ms": pct(99),
                "max_ms": pct(100), "fps": n / span}
    finally:
        sock.close()


def measure(journal_dir: str | None, sid: int) -> dict:
    import nnstreamer_tpu as nt
    from nnstreamer_tpu.core.log import metrics

    metrics.reset()
    _register_work()
    jprops = (f" journal={journal_dir} journal-fsync=batch"
              if journal_dir else "")
    srv = nt.Pipeline(
        f"tensor_query_serversrc name=ssrc port=0 id={sid}{jprops} ! "
        f"tensor_filter framework=custom-easy model=armor-bench-work ! "
        f"tensor_query_serversink id={sid}")
    with srv:
        port = srv.element("ssrc").bound_port
        row = _drive(port, N_REQUESTS, N_WARMUP)
    snap = metrics.snapshot()
    row["journal"] = bool(journal_dir)
    row["journal_appends"] = snap.get("journal.appends", 0.0)
    row["journal_acks"] = snap.get("journal.acks", 0.0)
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_ARMOR_r01.json")
    ap.add_argument("--skip-yank", action="store_true",
                    help="only the journal A/B (faster iteration)")
    args = ap.parse_args()
    t_start = time.time()

    # interleaved rounds + medians: a single off-then-on pass confounds
    # the delta with host drift (the shared-host p50 wanders more per
    # minute than the journal costs)
    rounds = 5
    offs, ons = [], []
    jdir = tempfile.mkdtemp(prefix="bench-armor-journal-")
    try:
        for r in range(rounds):
            offs.append(measure(None, sid=930))
            ons.append(measure(jdir, sid=931))
            print(f"   round {r}: off p50 {offs[-1]['p50_ms']:.3f}ms "
                  f"on p50 {ons[-1]['p50_ms']:.3f}ms", flush=True)
    finally:
        shutil.rmtree(jdir, ignore_errors=True)
    assert all(r["journal_appends"] >= N_REQUESTS for r in ons), \
        "journal never engaged"

    def med(rows, key):
        return float(np.median([r[key] for r in rows]))

    off = {"p50_ms": med(offs, "p50_ms"), "p99_ms": med(offs, "p99_ms"),
           "fps": med(offs, "fps")}
    on = {"p50_ms": med(ons, "p50_ms"), "p99_ms": med(ons, "p99_ms"),
          "fps": med(ons, "fps"),
          "journal_appends": ons[-1]["journal_appends"],
          "journal_acks": ons[-1]["journal_acks"]}
    overhead = (on["p50_ms"] - off["p50_ms"]) / off["p50_ms"]
    ab = {
        "row": "journal_overhead_ab",
        "requests": N_REQUESTS, "rounds": rounds,
        "fsync": "batch",
        "journal_off": off,
        "journal_on": on,
        "p50_rounds_off_ms": [round(r["p50_ms"], 4) for r in offs],
        "p50_rounds_on_ms": [round(r["p50_ms"], 4) for r in ons],
        "p50_overhead_pct": round(100.0 * overhead, 2),
        "p99_overhead_pct": round(
            100.0 * (on["p99_ms"] - off["p99_ms"]) / off["p99_ms"], 2),
        "target_pct": 3.0,
    }
    print(f"== journal_overhead_ab: off p50 {off['p50_ms']:.3f}ms "
          f"on p50 {on['p50_ms']:.3f}ms "
          f"({ab['p50_overhead_pct']:+.2f}%, median of {rounds})",
          flush=True)

    rows = [ab]
    if not args.skip_yank:
        yank_out = os.path.join(tempfile.gettempdir(),
                                "bench_armor_yank.json")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "soak.py"),
             "--yank", "--out", yank_out],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        try:
            with open(yank_out) as f:
                yank_doc = json.load(f)
            rows.extend(yank_doc.get("rows", []))
        except (OSError, json.JSONDecodeError):
            rows.append({"row": "yank_process",
                         "error": f"soak --yank rc={proc.returncode}",
                         "tail": (proc.stdout or "").splitlines()[-5:]})

    doc = {
        "note": "nns-armor rows (ISSUE 12): journal_overhead_ab = the "
                "SAME front door with the request journal off vs "
                "fsync=batch, serial request/response latency (the "
                "shape an append actually sits on).  The per-round "
                "p50 arrays show the shared-host noise floor; a "
                "reported overhead inside that spread (incl. a "
                "negative one) means the journal's true cost — "
                "~12.6us/record microbenched (append+ack, buffered "
                "write + kicked background fsync) — is below what "
                "this host can resolve end-to-end, well under the 3% "
                "p50 target.  yank_process = kill -9 the journaled "
                "serving process mid-run, restart with "
                "journal-replay=true, exactly-once re-admission "
                "asserted on the journal files.",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S+00:00",
                                     time.gmtime(t_start)),
        "rows": rows,
    }
    out_path = args.out if os.path.isabs(args.out) \
        else os.path.join(os.getcwd(), args.out)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    yank = next((r for r in rows if r.get("profile") == "yank_process"),
                {})
    print(json.dumps({
        "metric": "journal_overhead_p50_pct",
        "value": ab["p50_overhead_pct"], "unit": "%",
        "p50_off_ms": round(off["p50_ms"], 4),
        "p50_on_ms": round(on["p50_ms"], 4),
        "fps_off": round(off["fps"], 1), "fps_on": round(on["fps"], 1),
        "yank_exactly_once": yank.get("replay_exactly_once"),
        "artifact": os.path.basename(out_path),
    }))
    print(f"wrote {out_path} ({len(rows)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
