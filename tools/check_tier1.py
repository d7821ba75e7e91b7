#!/usr/bin/env python
"""Tier-1 regression gate: run the ROADMAP verify command and FAIL when
the passing-test count drops below the checked-in floor.

    python tools/check_tier1.py            # gate (CI / pre-merge)
    python tools/check_tier1.py --update   # bump the floor after adding tests

The floor lives in tools/tier1_floor.txt so a PR that silently loses
passing tests (the batching refactor and everything after it) cannot
merge green.  DOTS_PASSED is counted exactly the way the ROADMAP verify
line counts it: dots in pytest's progress lines.

The gate ALSO runs nns-lint (see docs/ANALYSIS.md) over every pipeline
string in examples/ + tests/test_pipeline_e2e.py and over the framework's
own device_fns (the jit-purity dogfood), in strict mode against
tools/lint_baseline.txt: any diagnostic not already accepted in the
baseline fails the gate — including ``unresolvable-pipeline`` warnings,
so a new example the linter cannot see statically fails CI instead of
silently shrinking coverage.  ``--update`` refreshes the baseline too.

AND it runs the DEEP pass (``lint --deep --dogfood --examples``, see
docs/ANALYSIS.md "Deep pass") against tools/deep_baseline.txt, pinned to
``JAX_PLATFORMS=cpu``: every example/e2e pipeline string is abstractly
executed (shape/dtype contract checks + static HBM/recompile budgets)
and the bundled zoo model families are eval_shape-traced against their
declared specs — zero device dispatch, every run.

AND it runs tests/test_sharded_batching.py as its OWN pytest process with
``--xla_force_host_platform_device_count=8`` pinned in XLA_FLAGS: the
flag must be set before jax initializes, and a separate process
guarantees it can never arrive too late (or leak a forced device count
into anything else).

AND it runs the mesh gate (docs/BATCHING.md "2-D sharded dispatch"):
tests/test_model_parallel.py as its own pytest process with the same
pinned XLA flag — 2-D (data x model) dispatch bit-identity vs dp-only,
model-axis placement counters, TP paged decode identity, and the
zero-recompile pin under TP — then a deep-lint assertion that a
``model_parallel=4`` llama-7B serving pipeline prices per-chip params
and KV-pool bytes at ~1/4 (sheared leaves /M, embed+norms replicated).

AND it runs the tracing gate (tools/tracing_gate.py, see
docs/OBSERVABILITY.md): a backlogged batching run with
``trace_mode=ring`` must dump schema-valid Chrome trace JSON whose
batched dispatch spans link every member row's trace id, ``/metrics``
must serve bucketed histograms for stage latency / queue wait / e2e
latency, and ``trace_mode=off`` must be STRUCTURALLY untraced (recorder
monkeypatched to raise) with measured overhead within 2%.

AND it runs the fetch gate (docs/FETCH.md): tests/test_fetch.py in its own
pytest process (fetch-window in-order emission, ingress-donation identity,
zero-d2h pins for device-resident edges, reduced-output selection
goldens), then ``lint --deep`` over examples/fetch_bound.py with the
calibrated link pinned (NNS_TPU_LINK_D2H_MBPS/NNS_TPU_LINK_RTT_MS),
asserting the ``fetch-bound`` diagnostic fires, strict against
tools/fetch_deep_baseline.txt.

AND it runs the soak smoke gate (docs/SERVING.md "Front door"):
``tools/soak.py --smoke`` — a seconds-long 2-tenant soak in two passes:
a low-load steady profile that must shed NOTHING with a green SLO
report, and a deliberately overloaded profile (offered load >> service
capacity, tiny max-backlog) where admission control must shed >= 1
request, the per-tenant SLO must breach naming a dominant span kind,
and the flight-recorder ring dump must ride the report.  The report
schema is asserted field-by-field — the shape BENCH_SOAK rows and
``Pipeline.slo_report()`` consumers depend on.

AND it runs the MXU gate (ISSUE 10, docs/BATCHING.md "Adaptive ladder" +
docs/ARCHITECTURE.md "Streaming state"): tests/test_adaptive_batching.py
and tests/test_aggregator_device.py each as their OWN pytest process
(ladder refinement/budget/warm-start/bit-identity + the ladder-rounded
recompile-unbounded regression; aggregator device-vs-host bit-identity,
3-program zero-recompile pin, zero-d2h transfer trap, EOS flush), then
``lint --deep`` over examples/asr_streaming_window.py with
``NNS_TPU_HBM_BUDGET`` pinned below the estimate — the resource report
must PRICE the aggregator ring ("agg ring" bytes + the 3-program census)
— strict against tools/asr_deep_baseline.txt.

AND it runs the elastic gate (ISSUE 11, docs/SERVING.md "Elastic
serving"): tests/test_elastic.py in its own pytest process (drain/adopt
greedy bit-identity with the 3-program census pinned on both pipelines,
orphan reaping back to the free list, admit-timeout head-of-line
rejection, autoscaler hysteresis + elastic.scale spans, the
recompile-on-reconfig lint goldens), then ``tools/soak.py
--chaos-smoke``: a SIGKILLed tenant's stream must be cancelled through
the dead-connection backchannel with its KV blocks reclaimed, and a
mid-run connection cut must be survived via client reconnect
(backoff + full jitter) — surviving tenants' p99 green both times.

AND it runs the armor gate (ISSUE 12, docs/ROBUSTNESS.md):
tests/test_wire_armor.py + tests/test_journal.py + tests/test_armor.py
as their own pytest process (typed WireError rejects + limits, the
SIGKILL crash-consistency property test, the journal replay golden,
poison quarantine/DLQ/breaker), then ``tools/fuzz_wire.py --smoke``
(the committed regression corpus + 2000 seeded structure-aware
mutations over decode_buffer/read_frame/the parser — zero uncaught
exceptions, zero over-limit allocations), then ``tools/soak.py
--yank-smoke``: SIGKILL the journaled serving subprocess mid-run,
restart it with journal-replay on the same port, and assert the
exactly-once contract (unanswered-at-kill all re-admitted and acked
once, journal fully answered at the end, no client losses).

AND it runs the xray gate (ISSUE 13, docs/OBSERVABILITY.md "Predicted
vs actual"): tests/test_xray.py as its own pytest process (census-drift
goldens incl. the numpy-scalar serve-loop trap, the llm 3-program churn
census, MFU/pad-waste gauges, the HBM ledger, the xray-off structural
pin, OpenMetrics negotiation, the thread-shutdown audit), then
``python -m nnstreamer_tpu.tools.doctor --gate`` on the built-in bench
pipeline — census drift must be 0 and every HBM ledger category within
tolerance — with the deterministic verdict lines pinned strict against
tools/xray_baseline.txt (``--update`` refreshes it).

AND it runs the learn gate (ISSUE 14, docs/TRAINING.md):
tests/test_learn.py + tests/test_trainer.py as their own pytest process
— device-window streaming vs host-accumulated bit-identity, the trainer
3-program census pins, mesh-sharded trajectories, checkpoint save→kill→
resume continuation identity, train-while-serve hot-swap with zero
recompiles on the serving stage — then ``lint --deep`` over
examples/training.py with ``NNS_TPU_HBM_BUDGET`` pinned below the
estimate, asserting the resource report prices the trainer's
optimizer-state + gradient HBM (the "train state" line + the budget
warning naming it), strict against tools/learn_deep_baseline.txt.

AND it runs the spec gate (ISSUE 15, docs/SERVING.md §4b/§4c):
tests/test_spec_decode.py in its own pytest process — ref-count/CoW
allocator invariants (free only at refcount 0, fork-on-write isolation,
recycled-slot identity under churn, the stale-table sentinel on
multi-token writes), shared-prefix admission collapse, logical-block
tenant quotas, greedy bit-identity of speculative vs plain decode at
accept rates 0/partial/1, and the 5-program census pin — then ``lint
--deep`` over examples/llm_prefix_serving.py with ``NNS_TPU_HBM_BUDGET``
pinned below the estimate, asserting the resource report PRICES the
draft model's params + block pool beside the ref-counted KV pool
("draft params" / "draft pool" / "kv pool" lines + the budget warning),
strict against tools/spec_deep_baseline.txt.

AND it runs the kernel gate (ISSUE 16, docs/ARCHITECTURE.md "Kernels
and lane discipline" + docs/SERVING.md §4d): tests/test_kernels_gqa.py
+ tests/test_sampling.py as their own pytest process — grouped-GQA
flash/paged kernel bit-identity vs the repeated layout at every H/Hkv
ratio incl. MQA, the grid/DMA stream-count scaling pins (K/V streams
x Hkv, not H), the serving_plan decode-traffic coefficient regression,
chi-squared rejection-sampling distribution equivalence, fixed-seed
bitwise reproducibility + batch-composition independence, sampled
drain/adopt PRNG carry, the 3/5-program census pins with the sampler
compiled in, and the fused-verify transfer-budget trap — then
``python -m nnstreamer_tpu.tools.doctor --gate`` re-asserting census
drift 0 with the sampled/spec programs in the build.

AND it runs the tsan gate (ISSUE 17, docs/ANALYSIS.md "Threads pass"):
``lint --threads --strict`` over the whole package — the ``_GUARDED_BY``
write discipline, the nested-``with`` lock-order graph (cycle = a
``lock-order-inversion`` naming both acquisition paths), thread
join-lifecycle + bare-condition-wait audits — strict against
tools/tsan_baseline.txt (reviewed daemon-thread suppressions only;
errors are never baselined), with the pass asserted jax-free; then the
chaos smoke re-run with ``NNS_TPU_TSAN=1`` so every hot lock owner vends
tracked primitives — the rows must report zero LIVE inversions and zero
guarded-field violations with a non-empty order graph.

AND it runs the proto gate (ISSUE 19, docs/ANALYSIS.md "Protocol
pass"): a jax-free probe (``lint --proto`` and the bounded model
checker must import and run without jax in sys.modules), then ``lint
--proto --strict`` in its own process — message-alphabet + handler-
totality lint, the unanswered-path call-proof over the serving
handlers, and the model-vs-code alphabet drift gate (a new message
kind without a model update fails CI) — strict against
tools/proto_baseline.txt (empty: protocol errors are fixed in-code,
never baselined); then a mutated-model smoke: a deliberately broken
exactly-once model (client dedupe off) must yield a counterexample
trace, proving the checker can actually falsify, not just verify.

AND it runs the serving gate (docs/SERVING.md §4):
tests/test_llm_continuous.py in its own pytest process — paged-vs-dense
bit-identity, block allocator churn, and the compile-counter pin that
stream join/leave/complete triggers ZERO XLA compilations once the
continuous loop is warm — then ``lint --deep`` over
examples/llm_continuous_serving.py with ``NNS_TPU_HBM_BUDGET`` pinned
below the estimate, asserting the resource report prices the paged KV
block pool (the "kv pool" line + the budget warning naming it), strict
against tools/serving_deep_baseline.txt.
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOOR_FILE = os.path.join(REPO, "tools", "tier1_floor.txt")
LINT_BASELINE = os.path.join(REPO, "tools", "lint_baseline.txt")
DEEP_BASELINE = os.path.join(REPO, "tools", "deep_baseline.txt")
SERVING_BASELINE = os.path.join(REPO, "tools", "serving_deep_baseline.txt")
FETCH_BASELINE = os.path.join(REPO, "tools", "fetch_deep_baseline.txt")
ASR_BASELINE = os.path.join(REPO, "tools", "asr_deep_baseline.txt")
XRAY_BASELINE = os.path.join(REPO, "tools", "xray_baseline.txt")
LEARN_BASELINE = os.path.join(REPO, "tools", "learn_deep_baseline.txt")
SPEC_BASELINE = os.path.join(REPO, "tools", "spec_deep_baseline.txt")
TSAN_BASELINE = os.path.join(REPO, "tools", "tsan_baseline.txt")
PROTO_BASELINE = os.path.join(REPO, "tools", "proto_baseline.txt")

#: HBM budget the MXU gate pins for the streaming-ASR example's deep
#: lint: below the estimate, so the hbm-budget warning fires with the
#: aggregator ring priced INSIDE the estimate — proving ring bytes feed
#: Config.hbm_budget_bytes, not just the report text.
ASR_GATE_BUDGET = str(1 << 16)

#: a SLOW link the fetch gate pins for the deliberately fetch-bound
#: example (38.2 MB/s d2h, 88 ms small-fetch RTT — fixed gate inputs, not
#: a measurement of any current machine) — the ``fetch-bound`` diagnostic must fire and
#: be baseline-accepted, proving planned fetch bytes are actually priced
#: against Config.link_d2h_mbps, not just rendered.
FETCH_GATE_D2H_MBPS = "38.2"
FETCH_GATE_RTT_MS = "88"

#: HBM budget the serving gate pins for the example's deep lint: far
#: below the llama_tiny estimate, so the hbm-budget warning (naming the
#: paged KV pool) must fire and be baseline-accepted — proving the pool
#: is actually priced against Config.hbm_budget_bytes, not just rendered.
SERVING_GATE_BUDGET = str(1 << 20)

#: the ROADMAP "Tier-1 verify" pytest invocation, verbatim
PYTEST_ARGS = [
    "-m", "pytest", "tests/", "-q", "-m", "not slow",
    "--continue-on-collection-errors", "-p", "no:cacheprovider",
    "-p", "no:xdist", "-p", "no:randomly",
]

# ROADMAP's grep uses [.FEsx]; 'X' (xpass) added here so one xpassing test
# cannot void a whole progress line's pass-dots and fake a regression
_DOTS_RE = re.compile(r"^[.FEsxX]+( *\[ *[0-9]+%\])?$")


def count_dots(text: str) -> int:
    return sum(line.count(".") for line in text.splitlines()
               if _DOTS_RE.match(line.strip()))


def run_lint_gate(update: bool) -> int:
    """nns-lint over example/e2e pipeline strings + the purity dogfood,
    failing on any diagnostic not in the accepted baseline."""
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--examples", "--dogfood", "--strict",
           "--baseline", LINT_BASELINE]
    if update:
        cmd.append("--update-baseline")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("lint gate: TIMED OUT after 300s", file=sys.stderr)
        return 2
    tag = "updated" if update else ("OK" if proc.returncode == 0
                                    else "NEW DIAGNOSTICS")
    print(f"lint gate: {tag}")
    if proc.returncode != 0:
        # stdout carries diagnostics, stderr carries crashes/usage errors —
        # a CI failure must explain itself either way
        for line in (proc.stdout + proc.stderr).strip().splitlines():
            print(f"  {line}", file=sys.stderr)
    return proc.returncode


def run_deep_gate(update: bool, timeout: int = 600) -> int:
    """The deep-analysis gate: abstract shape execution + static
    HBM/recompile budgeting over every example/e2e pipeline string plus
    the zoo-model dogfood, strict against tools/deep_baseline.txt.  Its
    own subprocess with JAX_PLATFORMS=cpu pinned: the deep pass imports
    jax (the syntactic lint gate stays jax-free) but never dispatches."""
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "--examples", "--dogfood", "--strict",
           "--baseline", DEEP_BASELINE]
    if update:
        cmd.append("--update-baseline")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"deep gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    tag = "updated" if update else ("OK" if proc.returncode == 0
                                    else "NEW DIAGNOSTICS")
    print(f"deep gate: {tag}")
    if proc.returncode != 0:
        for line in (proc.stdout + proc.stderr).strip().splitlines():
            print(f"  {line}", file=sys.stderr)
    return proc.returncode


def run_sharded_gate(timeout: int = 600) -> int:
    """tests/test_sharded_batching.py in its own process, with the forced
    8-host-device XLA flag pinned (see module docstring)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_sharded_batching.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"sharded gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    tag = "OK" if proc.returncode == 0 else "FAILED"
    print(f"sharded gate: {tag} ({passed} passed)")
    if proc.returncode != 0:
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
    return proc.returncode


#: the mesh gate's deep-lint assertion pipeline: a REAL 7B-shaped TP
#: serving config, priced statically (resolve_config — no params ever
#: materialize).  model_parallel=4 must price per-chip params + KV pool
#: at ~1/4: sheared leaves (the big mats + lm_head) divide by M, embed +
#: norms replicate, the paged pool shards its head dim.
MESH_GATE_SNIPPET = r"""
import nnstreamer_tpu as nt
from nnstreamer_tpu.models import llama

DESC = ("appsrc name=src ! tensor_filter framework=llm model=llama2_7b "
        "custom=max_new:32,serve:continuous,slots:4,param_dtype:bfloat16 "
        "invoke-dynamic=true ! tensor_sink name=out")
M = 4
r1 = nt.analyze(DESC, deep=True, model_parallel=1)
rM = nt.analyze(DESC, deep=True, model_parallel=M)
assert not r1.errors and not rM.errors, (r1.render(), rM.render())
s1, sM = r1.resources.stages[0], rM.resources.stages[0]
assert rM.resources.model_parallel == M
assert sM.pool_bytes * M == s1.pool_bytes, (sM.pool_bytes, s1.pool_bytes)
ratio = sM.param_bytes / s1.param_bytes
# ~1/M per chip: the bf16 embed (vocab*dim) replicates, everything big
# shards — for 7B that bounds the ratio just above 0.25
assert 1.0 / M <= ratio <= 1.1 / M, f"per-chip param ratio {ratio:.4f}"
assert sM.variants == 3, sM.variants  # the census stays closed under TP
print(f"mesh gate lint: per-chip params ratio {ratio:.4f} (~1/{M}), "
      f"pool /{M}, 3-program census")
"""


def run_mesh_gate(timeout: int = 900) -> int:
    """2-D placement gate (docs/BATCHING.md "2-D sharded dispatch"):
    tests/test_model_parallel.py as its own pytest process with the
    8-host-device XLA flag pinned (bit-identity of 2-D dispatch vs
    dp-only, model-axis placement counters, TP paged decode identity,
    the zero-recompile pin under TP, make_mesh/mesh_plan semantics,
    divisibility/missing-axis lint goldens), then the deep-lint pricing
    assertion: a model_parallel=4 llama-7B serving pipeline must price
    per-chip params + KV pool at ~1/4 (MESH_GATE_SNIPPET)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_model_parallel.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"mesh gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"mesh gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    try:
        lint = subprocess.run([sys.executable, "-c", MESH_GATE_SNIPPET],
                              cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("mesh gate: deep lint TIMED OUT after 300s", file=sys.stderr)
        return 2
    ok = lint.returncode == 0
    tag = "OK" if ok else "TP NOT PRICED PER CHIP"
    print(f"mesh gate: {tag} ({passed} tests passed)")
    for line in lint.stdout.strip().splitlines():
        if line.startswith("mesh gate lint:"):
            print(f"  {line}")
    if not ok:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


def run_tracing_gate(timeout: int = 600) -> int:
    """tools/tracing_gate.py in its own process (fresh recorder/metrics
    state, CPU pinned): flight-recorder e2e + off-mode purity + overhead."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "tools", "tracing_gate.py")]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"tracing gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    tag = "OK" if proc.returncode == 0 else "FAILED"
    print(f"tracing gate: {tag}")
    for line in proc.stdout.strip().splitlines():
        if line.startswith("tracing gate:") and line != "tracing gate: OK":
            print(f"  {line}")
    if proc.returncode != 0:
        for line in (proc.stdout + proc.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
    return proc.returncode


def run_mxu_gate(update: bool, timeout: int = 900) -> int:
    """MXU-feeding gate (ISSUE 10, see module docstring): the adaptive
    ladder and device-aggregator test files each as their own pytest
    process, then ``lint --deep`` over the streaming-ASR example with a
    sub-estimate HBM budget pinned — the report must price the
    aggregator ring, strict against tools/asr_deep_baseline.txt."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    passed = 0
    for test_file in ("tests/test_adaptive_batching.py",
                      "tests/test_aggregator_device.py"):
        cmd = [sys.executable, "-m", "pytest", test_file, "-q",
               "-p", "no:cacheprovider", "-p", "no:xdist",
               "-p", "no:randomly"]
        try:
            proc = subprocess.run(cmd, cwd=REPO, env=env,
                                  capture_output=True, text=True,
                                  timeout=timeout)
        except subprocess.TimeoutExpired:
            print(f"mxu gate: {test_file} TIMED OUT after {timeout}s",
                  file=sys.stderr)
            return 2
        passed += count_dots(proc.stdout)
        if proc.returncode != 0:
            print(f"mxu gate: {test_file} FAILED ({passed} passed)")
            for line in proc.stdout.strip().splitlines()[-15:]:
                print(f"  {line}", file=sys.stderr)
            return proc.returncode

    env["NNS_TPU_HBM_BUDGET"] = ASR_GATE_BUDGET
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "-v", "--strict",
           "--files", os.path.join("examples", "asr_streaming_window.py"),
           "--baseline", ASR_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("mxu gate: deep lint TIMED OUT after 300s", file=sys.stderr)
        return 2
    priced = "agg ring" in lint.stdout
    ok = lint.returncode == 0 and priced
    tag = ("updated" if update else
           "OK" if ok else
           "RING NOT PRICED" if not priced else "NEW DIAGNOSTICS")
    print(f"mxu gate: {tag} ({passed} tests passed)")
    if not ok and not update:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


def run_kernel_gate(timeout: int = 900) -> int:
    """Grouped-GQA kernel + production-sampling gate (ISSUE 16, see
    module docstring): the two test files as their own pytest process,
    then ``doctor --gate`` — its rc is the census-drift verdict; the
    xray gate owns the verdict-line baseline, this run only re-asserts
    drift 0 with the sampler/spec programs compiled in."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_kernels_gqa.py", "tests/test_sampling.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"kernel gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"kernel gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.doctor", "--gate"]
    try:
        doc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                             text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"kernel gate: doctor TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    if doc.returncode != 0:
        print(f"kernel gate: DOCTOR DRIFT ({passed} tests passed)")
        for line in (doc.stdout + doc.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return doc.returncode
    print(f"kernel gate: OK ({passed} tests passed, doctor census "
          "drift 0)")
    return 0


def run_serving_gate(update: bool, timeout: int = 900) -> int:
    """Continuous-serving gate (see module docstring): the paged-KV test
    file as its own pytest process (compile-counter pin included), then
    the deep lint of the serving example with a sub-estimate HBM budget
    pinned — the report must price the paged KV pool."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_llm_continuous.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"serving gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"serving gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    env["NNS_TPU_HBM_BUDGET"] = SERVING_GATE_BUDGET
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "-v", "--strict",
           "--files", os.path.join("examples", "llm_continuous_serving.py"),
           "--baseline", SERVING_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("serving gate: deep lint TIMED OUT after 300s",
              file=sys.stderr)
        return 2
    priced = "kv pool" in lint.stdout
    ok = lint.returncode == 0 and priced
    tag = ("updated" if update else
           "OK" if ok else
           "POOL NOT PRICED" if not priced else "NEW DIAGNOSTICS")
    print(f"serving gate: {tag} ({passed} tests passed)")
    if not ok and not update:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


def run_spec_gate(update: bool, timeout: int = 900) -> int:
    """Prefix-sharing + speculative-decoding gate (ISSUE 15, docs/
    SERVING.md §4b/§4c): tests/test_spec_decode.py as its own pytest
    process (allocator refcount/CoW invariants, shared-prefix admission
    collapse, logical-block quotas, spec-vs-plain greedy bit-identity at
    every accept rate, the 5-program census pin), then ``lint --deep``
    over the shared-prefix serving example with a sub-estimate HBM
    budget pinned — the report must PRICE the draft's params and block
    pool beside the ref-counted KV pool."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_spec_decode.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"spec gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"spec gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    env["NNS_TPU_HBM_BUDGET"] = SERVING_GATE_BUDGET
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "-v", "--strict",
           "--files", os.path.join("examples", "llm_prefix_serving.py"),
           "--baseline", SPEC_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("spec gate: deep lint TIMED OUT after 300s", file=sys.stderr)
        return 2
    priced = all(k in lint.stdout
                 for k in ("draft params", "draft pool", "kv pool"))
    ok = lint.returncode == 0 and priced
    tag = ("updated" if update else
           "OK" if ok else
           "DRAFT NOT PRICED" if not priced else "NEW DIAGNOSTICS")
    print(f"spec gate: {tag} ({passed} tests passed)")
    if not ok and not update:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


def run_fetch_gate(update: bool, timeout: int = 900) -> int:
    """Fetch-engine gate (docs/FETCH.md): tests/test_fetch.py as its own
    pytest process (in-order fetch-window emission, donation identity,
    zero-d2h pins, reduced-output selection goldens), then ``lint --deep``
    over the deliberately fetch-bound example with the calibrated link
    pinned — the ``fetch-bound`` diagnostic must fire, strict against
    tools/fetch_deep_baseline.txt."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_fetch.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"fetch gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"fetch gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    env["NNS_TPU_LINK_D2H_MBPS"] = FETCH_GATE_D2H_MBPS
    env["NNS_TPU_LINK_RTT_MS"] = FETCH_GATE_RTT_MS
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "-v", "--strict",
           "--files", os.path.join("examples", "fetch_bound.py"),
           "--baseline", FETCH_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("fetch gate: deep lint TIMED OUT after 300s", file=sys.stderr)
        return 2
    flagged = "fetch-bound" in lint.stdout
    ok = lint.returncode == 0 and flagged
    tag = ("updated" if update else
           "OK" if ok else
           "FETCH-BOUND NOT FLAGGED" if not flagged else "NEW DIAGNOSTICS")
    print(f"fetch gate: {tag} ({passed} tests passed)")
    if not ok and not update:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


#: slo_report schema the soak gate (and every BENCH_SOAK consumer)
#: depends on — keys of the report root and of each tenant verdict
SLO_REPORT_KEYS = {"window_s", "ok", "breaches", "tenants"}
SLO_VERDICT_KEYS = {"tenant", "ok", "violations", "p50_ms", "p99_ms",
                    "fps", "requests", "sheds", "burn_rate", "objectives"}


def run_soak_gate(timeout: int = 600) -> int:
    """Soak smoke gate (see module docstring): tools/soak.py --smoke in
    its own process, then schema + shed/ring-dump assertions over the
    written rows."""
    import json
    import tempfile

    out = os.path.join(tempfile.gettempdir(), "nns_soak_gate.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py"),
           "--smoke", "--out", out]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"soak gate: TIMED OUT after {timeout}s", file=sys.stderr)
        return 2
    problems = []
    if proc.returncode != 0:
        problems.append(f"soak.py rc={proc.returncode}")
    rows = {}
    try:
        with open(out) as f:
            rows = {r["profile"]: r for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError) as e:
        problems.append(f"unreadable soak artifact: {e}")
    for profile in ("steady", "overload"):
        if profile not in rows:
            problems.append(f"missing {profile} row")
            continue
        r = rows[profile]
        rep = r.get("slo_report") or {}
        missing = SLO_REPORT_KEYS - set(rep)
        if missing:
            problems.append(f"{profile}: slo_report missing {missing}")
            continue
        for t, v in rep["tenants"].items():
            mv = SLO_VERDICT_KEYS - set(v)
            if mv:
                problems.append(f"{profile}: verdict[{t}] missing {mv}")
        if not r.get("tenants"):
            problems.append(f"{profile}: no worker rows")
        for t, w in (r.get("tenants") or {}).items():
            for key in ("p50_ms", "p99_ms", "sustained_fps", "burst_fps",
                        "requests", "completed", "sheds_seen"):
                if key not in w:
                    problems.append(f"{profile}: worker {t} missing "
                                    f"{key}")
    steady, overload = rows.get("steady", {}), rows.get("overload", {})
    if steady and steady.get("server", {}).get("sheds_total", -1) != 0:
        problems.append(
            f"steady: expected 0 sheds at low load, got "
            f"{steady.get('server', {}).get('sheds_total')}")
    if overload:
        srv = overload.get("server", {})
        rep = overload.get("slo_report", {})
        if srv.get("sheds_total", 0) < 1:
            problems.append("overload: expected >= 1 shed")
        if not srv.get("sheds_by_tenant"):
            problems.append("overload: sheds not counted per tenant")
        if rep.get("ok", True) or not rep.get("breaches"):
            problems.append("overload: SLO did not breach")
        for t in rep.get("breaches", []):
            if not rep["tenants"][t].get("dominant_span_kind"):
                problems.append(
                    f"overload: breach {t} missing dominant_span_kind")
        if not overload.get("ring_dump"):
            problems.append("overload: ring dump not attached")
    tag = "OK" if not problems else "FAILED"
    print(f"soak gate: {tag}")
    for p in problems:
        print(f"  soak gate: {p}", file=sys.stderr)
    if problems and proc.stdout:
        for line in proc.stdout.strip().splitlines()[-8:]:
            print(f"  {line}", file=sys.stderr)
    return 1 if problems else 0


def run_elastic_gate(timeout: int = 900) -> int:
    """Elastic gate (ISSUE 11, docs/SERVING.md "Elastic serving"):
    tests/test_elastic.py as its own pytest process (drain/adopt greedy
    bit-identity + the 3-program census pin on both pipelines, orphan
    reap accounting, admit-timeout head-of-line rejection, autoscaler
    hysteresis/spans, recompile-on-reconfig lint goldens), then the
    chaos smoke (``tools/soak.py --chaos-smoke``): the kill_worker and
    drop_conn profiles must RECOVER — surviving tenants' p99 green,
    orphaned KV blocks reclaimed to the free list, reconnects observed,
    slo_report schema intact."""
    import json
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest", "tests/test_elastic.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"elastic gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"elastic gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    out = os.path.join(tempfile.gettempdir(), "nns_chaos_gate.json")
    cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py"),
           "--chaos-smoke", "--out", out]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"elastic gate: chaos smoke TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    problems = []
    if proc.returncode != 0:
        problems.append(f"soak.py --chaos-smoke rc={proc.returncode}")
    rows = {}
    try:
        with open(out) as f:
            rows = {r["profile"]: r for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError) as e:
        problems.append(f"unreadable chaos artifact: {e}")
    for profile in ("chaos_kill_worker", "chaos_drop_conn"):
        if profile not in rows:
            problems.append(f"missing {profile} row")
            continue
        r = rows[profile]
        if not r.get("reclaimed_ok"):
            problems.append(
                f"{profile}: KV blocks not reclaimed to the free list "
                f"(pool={r.get('pool')})")
        if not r.get("surviving_p99_green"):
            problems.append(f"{profile}: surviving tenants' p99 not "
                            f"green ({r.get('slo_report', {})})")
        if r.get("watchdog_fired"):
            problems.append(f"{profile}: watchdog fired")
        rep = r.get("slo_report") or {}
        missing = SLO_REPORT_KEYS - set(rep)
        if missing:
            problems.append(f"{profile}: slo_report missing {missing}")
        else:
            for t, v in rep["tenants"].items():
                mv = SLO_VERDICT_KEYS - set(v)
                if mv:
                    problems.append(
                        f"{profile}: verdict[{t}] missing {mv}")
    kill = rows.get("chaos_kill_worker", {})
    if kill:
        if not kill.get("killed_tenants"):
            problems.append("kill_worker: no worker was killed")
        if kill.get("serve", {}).get("cancelled", 0) < 1:
            problems.append(
                "kill_worker: dead-connection backchannel cancelled no "
                "stream")
    drop = rows.get("chaos_drop_conn", {})
    if drop:
        if not drop.get("chaos_record", {}).get("conns_dropped"):
            problems.append("drop_conn: no connections were severed")
        reconnects = sum(w.get("reconnects", 0.0)
                         for w in (drop.get("tenants") or {}).values())
        if reconnects < 1:
            problems.append("drop_conn: no client reconnected")
        if not all(w.get("completed", 0) >= 1
                   for w in (drop.get("tenants") or {}).values()):
            problems.append(
                "drop_conn: a tenant completed nothing after the cut")
    tag = "OK" if not problems else "FAILED"
    print(f"elastic gate: {tag} ({passed} tests passed)")
    for p in problems:
        print(f"  elastic gate: {p}", file=sys.stderr)
    if problems and proc.stdout:
        for line in proc.stdout.strip().splitlines()[-8:]:
            print(f"  {line}", file=sys.stderr)
    return 1 if problems else 0


def run_weave_gate() -> int:
    """nns-weave gate (ISSUE 20, docs/OBSERVABILITY.md "Distributed
    tracing"): reads the chaos artifact run_elastic_gate just produced
    and asserts each chaos profile emitted ONE merged distributed trace
    — schema-clean at merge time, readable on disk, ts-monotonic per
    process, server pid present, at least one cross-wire s/f flow-arrow
    pair, and (for drop_conn, where no worker is killed) spanning the
    server plus >=2 tenant worker subprocesses.  The off-mode overhead
    bound over the weave wire hook sites (query send/recv/reply, clock
    probe) is re-asserted by run_tracing_gate via HOOKS_PER_BUFFER."""
    import json
    import tempfile

    out = os.path.join(tempfile.gettempdir(), "nns_chaos_gate.json")
    problems = []
    rows = {}
    try:
        with open(out) as f:
            rows = {r["profile"]: r for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError) as e:
        problems.append(f"unreadable chaos artifact: {e}")
    for profile in ("chaos_kill_worker", "chaos_drop_conn"):
        r = rows.get(profile)
        if r is None:
            problems.append(f"missing {profile} row")
            continue
        merged = r.get("merged") or {}
        if merged.get("error"):
            problems.append(f"{profile}: ring merge failed: "
                            f"{merged['error']}")
            continue
        if merged.get("problems"):
            problems.append(f"{profile}: merged trace schema problems: "
                            f"{merged['problems'][:3]}")
        if merged.get("arrows", 0) < 1:
            problems.append(f"{profile}: no cross-wire flow arrow "
                            "survived the merge")
        if merged.get("unaligned"):
            problems.append(f"{profile}: rings with no clock path to the "
                            f"reference: {merged['unaligned']}")
        try:
            with open(r.get("merged_trace") or "") as f:
                obj = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{profile}: merged trace unreadable: {e}")
            continue
        evs = [e for e in obj.get("traceEvents", []) if isinstance(e, dict)]
        procs = {e["args"]["name"].split(" epoch=")[0] for e in evs
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        if "server" not in procs:
            problems.append(f"{profile}: merged trace has no server "
                            f"process (procs={sorted(procs)})")
        workers = {p for p in procs if p.startswith("worker-")}
        if profile == "chaos_drop_conn" and len(workers) < 2:
            problems.append(
                f"{profile}: merged trace spans only {len(workers)} "
                f"worker subprocesses (<2): {sorted(procs)}")
        starts = sum(1 for e in evs if e.get("ph") == "s")
        finishes = sum(1 for e in evs if e.get("ph") == "f")
        if starts < 1 or starts != finishes:
            problems.append(f"{profile}: flow arrows unpaired "
                            f"({starts} s vs {finishes} f)")
        last: dict = {}
        for e in evs:
            if e.get("ph") != "X":
                continue
            pid = e.get("pid")
            if e["ts"] < last.get(pid, float("-inf")):
                problems.append(
                    f"{profile}: ts not monotonic within pid {pid}")
                break
            last[pid] = e["ts"]
    tag = "OK" if not problems else "FAILED"
    detail = ", ".join(
        f"{p.split('chaos_')[-1]}={rows.get(p, {}).get('merged', {}).get('arrows', '?')} arrows"
        for p in ("chaos_kill_worker", "chaos_drop_conn"))
    print(f"weave gate: {tag} ({detail})")
    for p in problems:
        print(f"  weave gate: {p}", file=sys.stderr)
    return 1 if problems else 0


def run_armor_gate(timeout: int = 900) -> int:
    """nns-armor gate (ISSUE 12, see module docstring): the armor test
    files as their own pytest process, the seeded fuzz smoke over the
    wire codec + parser, and the yank_process kill -9 / journal-replay
    exactly-once smoke."""
    import json
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_wire_armor.py", "tests/test_journal.py",
           "tests/test_armor.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"armor gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"armor gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    cmd = [sys.executable, os.path.join(REPO, "tools", "fuzz_wire.py"),
           "--smoke"]
    try:
        fuzz = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"armor gate: fuzz smoke TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    if fuzz.returncode != 0:
        print(f"armor gate: FUZZ FAILED ({passed} tests passed)")
        for line in (fuzz.stdout + fuzz.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return fuzz.returncode

    out = os.path.join(tempfile.gettempdir(), "nns_yank_gate.json")
    cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py"),
           "--yank-smoke", "--out", out]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"armor gate: yank smoke TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    problems = []
    if proc.returncode != 0:
        problems.append(f"soak.py --yank-smoke rc={proc.returncode}")
    try:
        with open(out) as f:
            row = json.load(f)["rows"][0]
    except (OSError, ValueError, KeyError, IndexError) as e:
        row = {}
        problems.append(f"unreadable yank artifact: {e}")
    if row:
        if not row.get("killed"):
            problems.append("yank: server was never killed")
        if row.get("unanswered_at_kill", 0) < 1:
            problems.append("yank: nothing unanswered at the kill "
                            "(fault missed the live window)")
        if not row.get("replay_exactly_once"):
            problems.append(
                f"yank: exactly-once contract failed "
                f"(unanswered_at_kill={row.get('unanswered_at_kill')}, "
                f"replayed={row.get('replayed')}, "
                f"replay_answered={row.get('replay_answered')}, "
                f"unanswered_end={row.get('unanswered_end')}, "
                f"ack_multiplicity_ok={row.get('ack_multiplicity_ok')})")
        if row.get("lost_total", 1) != 0:
            problems.append(f"yank: clients lost "
                            f"{row.get('lost_total')} request(s)")
    tag = "OK" if not problems else "FAILED"
    print(f"armor gate: {tag} ({passed} tests passed, fuzz clean, "
          f"yank replayed={row.get('replayed')})")
    for p in problems:
        print(f"  armor gate: {p}", file=sys.stderr)
    if problems and proc.stdout:
        for line in proc.stdout.strip().splitlines()[-8:]:
            print(f"  {line}", file=sys.stderr)
    return 1 if problems else 0


#: HBM budget the learn gate pins for the training example's deep lint:
#: far below the trainer stage's opt-state + window estimate, so the
#: ``hbm-budget`` warning must fire with "train state" priced into the
#: resource report — proving optimizer/gradient HBM is actually budgeted
LEARN_GATE_HBM_BUDGET = "256"


def run_learn_gate(update: bool, timeout: int = 900) -> int:
    """nns-learn gate (ISSUE 14, docs/TRAINING.md): the trainer test
    files as their own pytest process (streaming-vs-host bit-identity,
    3-program census pins, mesh trajectories, checkpoint save→kill→
    resume identity, train-while-serve hot-swap with census drift 0),
    then ``lint --deep`` over examples/training.py with
    ``NNS_TPU_HBM_BUDGET`` pinned below the estimate — "train state"
    must be PRICED — strict against tools/learn_deep_baseline.txt."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_learn.py", "tests/test_trainer.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"learn gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"learn gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    # the example's dataset files exist before its pipeline runs in CI
    # drives elsewhere; the lint itself never opens them
    prep = subprocess.run(
        [sys.executable, os.path.join("examples", "training.py"),
         "--prepare-only"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120)
    if prep.returncode != 0:
        print("learn gate: example --prepare-only FAILED", file=sys.stderr)
        for line in (prep.stdout + prep.stderr).strip().splitlines()[-8:]:
            print(f"  {line}", file=sys.stderr)
        return prep.returncode

    env["NNS_TPU_HBM_BUDGET"] = LEARN_GATE_HBM_BUDGET
    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--deep", "-v", "--strict",
           "--files", os.path.join("examples", "training.py"),
           "--baseline", LEARN_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("learn gate: deep lint TIMED OUT after 300s",
              file=sys.stderr)
        return 2
    priced = "train state" in lint.stdout
    budgeted = "hbm-budget" in lint.stdout
    ok = lint.returncode == 0 and priced and budgeted
    tag = ("updated" if update else
           "OK" if ok else
           "TRAIN STATE NOT PRICED" if not priced else
           "BUDGET NOT ENFORCED" if not budgeted else "NEW DIAGNOSTICS")
    print(f"learn gate: {tag} ({passed} tests passed)")
    if not ok and not update:
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


def run_xray_gate(update: bool, timeout: int = 900) -> int:
    """nns-xray gate (ISSUE 13, see module docstring): the predicted-vs-
    actual test file as its own pytest process, then the doctor CLI on
    the built-in bench pipeline — census drift must be 0 and every HBM
    category within tolerance — with the deterministic verdict lines
    pinned against tools/xray_baseline.txt."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "pytest", "tests/test_xray.py", "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"xray gate: tests TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    if proc.returncode != 0:
        print(f"xray gate: tests FAILED ({passed} passed)")
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.doctor", "--gate"]
    try:
        doc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                             text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"xray gate: doctor TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    lines = [ln.rstrip() for ln in doc.stdout.strip().splitlines()]
    if doc.returncode != 0:
        print(f"xray gate: DOCTOR DRIFT ({passed} tests passed)")
        for line in (doc.stdout + doc.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return doc.returncode
    if update:
        with open(XRAY_BASELINE, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"xray gate: updated ({passed} tests passed)")
        return 0
    try:
        with open(XRAY_BASELINE) as f:
            want = [ln.rstrip() for ln in f.read().strip().splitlines()]
    except OSError:
        print(f"xray gate: no baseline at {XRAY_BASELINE} — run with "
              "--update once to check one in", file=sys.stderr)
        return 2
    if lines != want:
        print(f"xray gate: VERDICT DRIFT vs baseline ({passed} tests "
              "passed)")
        for got, exp in zip(lines + ["<missing>"] * len(want),
                            want + ["<missing>"] * len(lines)):
            if got != exp:
                print(f"  got {got!r} != baseline {exp!r}",
                      file=sys.stderr)
        return 1
    print(f"xray gate: OK ({passed} tests passed, doctor census drift 0)")
    return 0


def run_tsan_gate(update: bool, timeout: int = 600) -> int:
    """nns-tsan gate (ISSUE 17, docs/ANALYSIS.md "Threads pass"): the
    static concurrency lint (``lint --threads --strict``) over the whole
    package in its own process — guarded-by discipline, the nested-with
    lock-order graph, thread lifecycles — strict against
    tools/tsan_baseline.txt (daemon-thread suppressions only: errors
    are never baselined), with the pass asserted jax-free; then the
    chaos smoke re-run with ``NNS_TPU_TSAN=1`` so every tracked lock
    records into the live order graph — the rows must report ZERO
    observed inversions and zero guarded-field violations."""
    import json
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    probe = (
        "import sys\n"
        "from nnstreamer_tpu.analysis import concurrency\n"
        "concurrency.lint_package()\n"
        "assert 'jax' not in sys.modules, "
        "'lint --threads must stay jax-free'\n")
    cmd = [sys.executable, "-c", probe]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=120)
    except subprocess.TimeoutExpired:
        print("tsan gate: jax-free probe TIMED OUT", file=sys.stderr)
        return 2
    if proc.returncode != 0:
        print("tsan gate: STATIC PASS IMPORTS JAX (or crashed)")
        for line in (proc.stdout + proc.stderr).strip().splitlines()[-10:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode

    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--threads", "--strict", "--baseline", TSAN_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("tsan gate: lint --threads TIMED OUT after 300s",
              file=sys.stderr)
        return 2
    if lint.returncode != 0 and not update:
        print("tsan gate: NEW DIAGNOSTICS")
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return lint.returncode
    summary = next((ln for ln in lint.stdout.splitlines()
                    if ln.startswith("threads:")), "")

    out = os.path.join(tempfile.gettempdir(), "nns_tsan_gate.json")
    env["NNS_TPU_TSAN"] = "1"
    cmd = [sys.executable, os.path.join(REPO, "tools", "soak.py"),
           "--chaos-smoke", "--out", out]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"tsan gate: chaos smoke TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    problems = []
    if proc.returncode != 0:
        problems.append(f"soak.py --chaos-smoke rc={proc.returncode}")
    rows = {}
    try:
        with open(out) as f:
            rows = {r["profile"]: r for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError) as e:
        problems.append(f"unreadable tsan chaos artifact: {e}")
    for profile, r in rows.items():
        tsan = r.get("tsan") or {}
        if not tsan.get("enabled"):
            problems.append(f"{profile}: tracked locks not engaged "
                            f"(tsan={tsan})")
            continue
        if tsan.get("inversions"):
            problems.append(
                f"{profile}: LIVE lock-order inversion(s): "
                f"{tsan['inversions']}")
        if tsan.get("guard_violations"):
            problems.append(
                f"{profile}: guarded-field violation(s): "
                f"{tsan['guard_violations']}")
        # edges need two DISTINCT tracked locks nested, which a clean
        # chaos run may legitimately never do — liveness is pinned on
        # the acquisition counter instead
        if tsan.get("acquisitions", 0) < 1:
            problems.append(f"{profile}: zero tracked-lock acquisitions "
                            "— the sanitizer never engaged")
    if not rows:
        problems.append("no chaos rows produced")
    tag = ("updated" if update and not problems else
           "OK" if not problems else "FAILED")
    print(f"tsan gate: {tag} ({summary or 'no lint summary'})")
    for p in problems:
        print(f"  tsan gate: {p}", file=sys.stderr)
    return 1 if problems else 0


def run_proto_gate(update: bool, timeout: int = 600) -> int:
    """nns-proto gate (ISSUE 19, docs/ANALYSIS.md "Protocol pass"):
    jax-free probe (the lint AND the bounded model checker must run
    with jax never imported), then ``lint --proto --strict`` against
    tools/proto_baseline.txt — alphabet/totality lint, unanswered-path
    proof, the shipped protocol models verified under
    drop/dup/reorder/crash faults, and the model-vs-code alphabet
    drift gate — then a mutated-model smoke proving the checker can
    FALSIFY (a dedupe-less exactly-once model must produce a
    counterexample trace)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    probe = (
        "import sys\n"
        "from nnstreamer_tpu.analysis import protocol, statemachine\n"
        "protocol.lint_package()\n"
        "res = statemachine.check(statemachine.exactly_once_model())\n"
        "assert res.ok, res.violation.render()\n"
        "bad = statemachine.check(\n"
        "    statemachine.exactly_once_model(client_dedupe=False))\n"
        "assert not bad.ok and bad.violation.trace, "
        "'mutated model was not falsified'\n"
        "assert 'jax' not in sys.modules, "
        "'lint --proto must stay jax-free'\n"
        "print(f'proto probe: {res.states} states ok, mutated model "
        "falsified in {bad.states} states')\n")
    try:
        proc = subprocess.run([sys.executable, "-c", probe], cwd=REPO,
                              env=env, capture_output=True, text=True,
                              timeout=300)
    except subprocess.TimeoutExpired:
        print("proto gate: jax-free probe TIMED OUT", file=sys.stderr)
        return 2
    if proc.returncode != 0:
        print("proto gate: PROBE FAILED (imports jax, model broken, or "
              "checker cannot falsify)")
        for line in (proc.stdout + proc.stderr).strip().splitlines()[-10:]:
            print(f"  {line}", file=sys.stderr)
        return proc.returncode
    probe_line = next((ln for ln in proc.stdout.splitlines()
                       if ln.startswith("proto probe:")), "")

    cmd = [sys.executable, "-m", "nnstreamer_tpu.tools.lint",
           "--proto", "--strict", "--baseline", PROTO_BASELINE]
    if update:
        cmd.append("--update-baseline")
    try:
        lint = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"proto gate: lint --proto TIMED OUT after {timeout}s",
              file=sys.stderr)
        return 2
    summary = next((ln for ln in lint.stdout.splitlines()
                    if ln.startswith("proto:")), "")
    if lint.returncode != 0 and not update:
        print("proto gate: NEW DIAGNOSTICS")
        for line in (lint.stdout + lint.stderr).strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return lint.returncode
    tag = "updated" if update else "OK"
    print(f"proto gate: {tag} ({summary or 'no lint summary'}; "
          f"{probe_line or 'no probe line'})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true",
                    help="write the measured count as the new floor (and "
                         "refresh the lint baseline)")
    ap.add_argument("--timeout", type=int, default=870,
                    help="seconds before the suite is killed (ROADMAP "
                         "budget)")
    args = ap.parse_args()

    lint_rc = run_lint_gate(args.update)
    deep_rc = run_deep_gate(args.update)
    sharded_rc = run_sharded_gate()
    mesh_rc = run_mesh_gate()
    tracing_rc = run_tracing_gate()
    mxu_rc = run_mxu_gate(args.update)
    serving_rc = run_serving_gate(args.update)
    spec_rc = run_spec_gate(args.update)
    kernel_rc = run_kernel_gate()
    fetch_rc = run_fetch_gate(args.update)
    soak_rc = run_soak_gate()
    elastic_rc = run_elastic_gate()
    weave_rc = run_weave_gate()
    armor_rc = run_armor_gate()
    xray_rc = run_xray_gate(args.update)
    learn_rc = run_learn_gate(args.update)
    tsan_rc = run_tsan_gate(args.update)
    proto_rc = run_proto_gate(args.update)
    lint_rc = (lint_rc or deep_rc or sharded_rc or mesh_rc or tracing_rc
               or mxu_rc or serving_rc or spec_rc or kernel_rc or fetch_rc
               or soak_rc or elastic_rc or weave_rc or armor_rc or xray_rc
               or learn_rc or tsan_rc or proto_rc)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable] + PYTEST_ARGS, cwd=REPO, env=env,
            capture_output=True, text=True, timeout=args.timeout)
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"")
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        print(f"tier1: suite timed out after {args.timeout}s "
              f"(partial DOTS_PASSED={count_dots(out)})", file=sys.stderr)
        return 2
    passed = count_dots(proc.stdout)
    print(f"DOTS_PASSED={passed}")

    if args.update:
        with open(FLOOR_FILE, "w") as f:
            f.write(f"{passed}\n")
        print(f"tier1: floor updated to {passed}")
        return lint_rc

    if not os.path.exists(FLOOR_FILE):
        print(f"tier1: no floor file at {FLOOR_FILE} — run with --update "
              "once to check one in", file=sys.stderr)
        return 2
    with open(FLOOR_FILE) as f:
        floor = int(f.read().strip())
    if passed < floor:
        print(f"tier1: REGRESSION — {passed} passed < floor {floor} "
              f"(pytest rc={proc.returncode}); tail:", file=sys.stderr)
        for line in proc.stdout.strip().splitlines()[-15:]:
            print(f"  {line}", file=sys.stderr)
        return 1
    print(f"tier1: OK — {passed} passed >= floor {floor}")
    if passed > floor:
        print(f"tier1: floor can be raised to {passed} "
              "(python tools/check_tier1.py --update)")
    return lint_rc


if __name__ == "__main__":
    sys.exit(main())
