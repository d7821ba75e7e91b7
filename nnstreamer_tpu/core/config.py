"""Global configuration registry.

Reference analog: ``gst/nnstreamer/nnstreamer_conf.c`` + ``nnstreamer.ini``
(sub-plugin search paths, per-framework priority for ``framework=auto``,
env-var overrides NNSTREAMER_CONF/FILTERS/DECODERS/CONVERTERS) —
upstream-reconstructed, SURVEY.md §5.6.

TPU build: one dataclass, populated from (in priority order) explicit set() >
environment > ini file (``NNS_TPU_CONF`` path, default ``~/.nnstreamer_tpu.ini``)
> defaults.  Sub-plugin discovery is module-import based (see registry.py), so
"paths" become module lists.
"""

from __future__ import annotations

import configparser
import dataclasses
import os
import threading
from typing import Dict, List, Optional, Tuple

_ENV_CONF = "NNS_TPU_CONF"
_ENV_PLUGINS = "NNS_TPU_PLUGINS"
_ENV_FW_PRIORITY = "NNS_TPU_FILTER_PRIORITY"
_ENV_BUCKETING = "NNS_TPU_SHAPE_BUCKETING"
_ENV_ADAPTIVE = "NNS_TPU_ADAPTIVE_BUCKETS"
_ENV_LADDERS = "NNS_TPU_BUCKET_LADDERS"
_ENV_BATCH_MAX = "NNS_TPU_BATCH_MAX"
_ENV_DATA_PARALLEL = "NNS_TPU_DATA_PARALLEL"
_ENV_MODEL_PARALLEL = "NNS_TPU_MODEL_PARALLEL"
_ENV_DISPATCH_DEPTH = "NNS_TPU_DISPATCH_DEPTH"
_ENV_HBM_BUDGET = "NNS_TPU_HBM_BUDGET"
_ENV_MAX_VARIANTS = "NNS_TPU_MAX_COMPILED_VARIANTS"
_ENV_TRACE = "NNS_TPU_TRACE"
_ENV_TRACE_RING = "NNS_TPU_TRACE_RING"
_ENV_FETCH_DEPTH = "NNS_TPU_FETCH_DEPTH"
_ENV_DONATE_INGRESS = "NNS_TPU_DONATE_INGRESS"
_ENV_REDUCE_OUTPUTS = "NNS_TPU_REDUCE_OUTPUTS"
_ENV_LINK_D2H_MBPS = "NNS_TPU_LINK_D2H_MBPS"
_ENV_LINK_RTT_MS = "NNS_TPU_LINK_RTT_MS"
_ENV_STAGE_RESTARTS = "NNS_TPU_MAX_STAGE_RESTARTS"
_ENV_XRAY = "NNS_TPU_XRAY"
_ENV_XRAY_HBM_TOL = "NNS_TPU_XRAY_HBM_TOLERANCE"
_ENV_PEAK_TFLOPS = "NNS_TPU_PEAK_TFLOPS"


@dataclasses.dataclass
class Config:
    #: extra plugin modules to import at registry init (comma/colon separated env)
    plugin_modules: List[str] = dataclasses.field(default_factory=list)
    #: framework priority for tensor_filter framework=auto
    filter_priority: List[str] = dataclasses.field(
        default_factory=lambda: ["jax", "custom-easy", "python3"]
    )
    #: default queue capacity between pipeline stages (buffers)
    queue_capacity: int = 4
    #: adaptive micro-batching: max already-queued buffers a device stage
    #: drains into ONE bucketed XLA dispatch (1 = off, the seed semantics)
    batch_max: int = 1
    #: allowed stacked batch sizes (bounds XLA recompiles); empty = powers
    #: of two up to batch_max
    batch_buckets: List[int] = dataclasses.field(default_factory=list)
    #: optional wait (ms) for more buffers once one is in hand; 0 = never
    #: trade latency for occupancy (drain only what is already queued)
    batch_linger_ms: float = 0.0
    #: adaptive bucket ladder (docs/BATCHING.md "Adaptive ladder"): each
    #: batchable stage refines its ladder online from observed drain
    #: occupancies — persistent skew mints an exact bucket instead of
    #: padding to the next power of two — bounded per stage by
    #: ``pipeline/plan.adaptive_variant_budget`` against
    #: ``max_compiled_variants`` so the deep-lint recompile census stays
    #: closed.  False = the static ladder, bit-identical behavior.
    adaptive_buckets: bool = False
    #: warm-start ladders per stage name (the export of a previous run's
    #: ``Pipeline.ladder_snapshot()``): ``{"f": [1, 2, 4, 6, 8]}``.  Ini
    #: ``[ladders]`` section (``f = 1,2,4,6,8``) or env
    #: ``NNS_TPU_BUCKET_LADDERS=f:1|2|4|6|8;g:...``.  Minted sizes
    #: compile at warmup, so steady-state deployments skip the online
    #: learning phase entirely.
    bucket_ladders: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    #: data-parallel replicas a bucketed micro-batch is sharded over (the
    #: ``data`` mesh axis): 0 = all local devices once batch_max > 1,
    #: 1 = single-device dispatch (the pre-mesh behavior), N = exactly N
    #: local devices.  Only shard-eligible stages (see pipeline/plan.py)
    #: ever see the mesh.
    data_parallel: int = 0
    #: tensor-parallel ways over the pipeline mesh's ``model`` axis
    #: (pipeline/plan.mesh_plan): 1 = off (the dp-only legacy path,
    #: bit-identical), N = exactly N ways (shardable stages place params
    #: per their ``param_pspecs``; the llm filter runs TP on the SAME
    #: mesh), 0 = auto — absorb every local device the ``data`` axis
    #: doesn't claim.  Unlike data_parallel this is NOT gated on
    #: batch_max: TP-only pipelines shard weights without micro-batching.
    model_parallel: int = 1
    #: in-flight dispatch window for batching device stages: how many
    #: micro-batches a runner may have dispatched-but-not-yet-emitted, so
    #: the next drain overlaps the previous dispatch (1 = the lockstep
    #: drain->dispatch->emit loop)
    dispatch_depth: int = 2
    #: pad flexible shapes up to the next bucket to bound XLA recompiles
    shape_bucketing: bool = True
    #: async fetch window at sinks (the output-side twin of
    #: ``dispatch_depth``): how many buffers a tensor_sink may have in
    #: background D2H / host-post resolution at once, so the fetch of
    #: buffer N overlaps the dispatch of buffer N+1 instead of being paid
    #: inside pop().  1 = the serial resolver — see docs/FETCH.md.
    fetch_depth: int = 2
    #: donate host-fed ingress buffers to the fused program (appsrc et al:
    #: the stage device_puts the pushed frame and XLA reuses that HBM for
    #: outputs — steady-state H2D stops allocating).  Only applies where
    #: the planner can prove sole ownership; see docs/FETCH.md.
    donate_ingress: bool = True
    #: HBM-residency planner: let the planner auto-select a model's
    #: REDUCED output (e.g. deeplab's native-stride class map, 256x less
    #: D2H) when every downstream consumer's negotiated caps admit it —
    #: "fetch the smaller thing" becomes the default instead of a
    #: hand-tuned custom= option.  See docs/FETCH.md "Residency rules".
    reduce_outputs: bool = True
    #: calibrated D2H link bandwidth in MB/s (the bench ``link_calibration``
    #: row) — lets nns-lint --deep price each sink edge's planned fetch
    #: bytes in milliseconds and flag ``fetch-bound`` pipelines statically.
    #: 0 = uncalibrated: fetch bytes are still reported, never priced.
    link_d2h_mbps: float = 0.0
    #: calibrated small-fetch roundtrip (ms), recorded next to the
    #: bandwidth term in the deep pass's fetch report.  Deliberately NOT
    #: part of the ``fetch-bound`` decision: the RTT amortizes behind the
    #: async fetch window (the point of ``fetch_depth``), link occupancy
    #: cannot — see docs/FETCH.md "Static fetch pricing".
    link_fetch_rtt_ms: float = 0.0
    #: static-analysis budget (nns-lint --deep): estimated per-device HBM
    #: high-water mark in bytes a pipeline may plan for before the deep
    #: pass warns (0 = no budget).  The estimate multiplies per-stage
    #: param + abstract activation bytes over the bucket ladder,
    #: data_parallel replication, and the dispatch_depth in-flight window
    #: — see docs/ANALYSIS.md "Deep pass".
    hbm_budget_bytes: int = 0
    #: static-analysis budget (nns-lint --deep): max distinct compiled XLA
    #: signatures (buckets x spec variants across device stages) before
    #: the deep pass warns of a recompile storm (0 = no budget)
    max_compiled_variants: int = 0
    #: elastic stage restarts (docs/SERVING.md "Elastic serving"): how
    #: many times a PURE/STATELESS stage's runner thread may be
    #: restarted in place after an exception before the pipeline fails
    #: for real (with the flight-recorder ring dumped).  0 = off (the
    #: pre-elastic fail-fast behavior); restarts are counted in
    #: ``<stage>.restarts``.
    max_stage_restarts: int = 0
    #: flight-recorder trace mode (utils/tracing.py, docs/OBSERVABILITY.md):
    #: ``off`` = no recorder installed (hot paths pay one pointer check),
    #: ``ring`` = always-on bounded ring of span events (post-mortem mode;
    #: watchdog fires / pipeline errors dump the recent window),
    #: ``full`` = unbounded capture for short profiling runs
    trace_mode: str = "off"
    #: span capacity of the ``ring`` trace mode, shared by its lanes
    #: (``utils.tracing.DEFAULT_RING_CAPACITY`` says why this many)
    trace_ring_capacity: int = 262144
    #: nns-xray predicted-vs-actual reconciliation (utils/xray.py,
    #: docs/OBSERVABILITY.md "Predicted vs actual"): register every jit
    #: entry point's compiles with the live program census, attribute
    #: per-stage device time / MFU, and reconcile the HBM ledger against
    #: the deep-lint estimate.  False = structurally off — every hook is
    #: one pointer check, no meta, no cost_analysis calls.
    xray: bool = False
    #: HBM-ledger drift tolerance: a category whose measured bytes drift
    #: past this factor from the deep-lint estimate (either direction,
    #: above the 1 MiB noise floor) warns once
    xray_hbm_tolerance: float = 2.0
    #: peak dense-matmul TFLOPs per chip for the MFU gauges (0 = derive
    #: from the device kind; utils/xray.peak_flops)
    peak_tflops: float = 0.0
    #: emit per-stage latency measurements
    enable_latency: bool = True
    #: free-form per-framework options ([filter-jax] section of the ini)
    framework_options: Dict[str, Dict[str, str]] = dataclasses.field(default_factory=dict)

    @classmethod
    def load(cls) -> "Config":
        cfg = cls()
        path = os.environ.get(_ENV_CONF, os.path.expanduser("~/.nnstreamer_tpu.ini"))
        if path and os.path.exists(path):
            ini = configparser.ConfigParser()
            ini.read(path)
            if ini.has_option("common", "plugin_modules"):
                cfg.plugin_modules = _split(ini.get("common", "plugin_modules"))
            if ini.has_option("filter", "priority"):
                cfg.filter_priority = _split(ini.get("filter", "priority"))
            if ini.has_option("common", "queue_capacity"):
                cfg.queue_capacity = ini.getint("common", "queue_capacity")
            if ini.has_option("common", "batch_max"):
                cfg.batch_max = ini.getint("common", "batch_max")
            if ini.has_option("common", "batch_buckets"):
                cfg.batch_buckets = [
                    int(v) for v in _split(ini.get("common", "batch_buckets"))
                ]
            if ini.has_option("common", "batch_linger_ms"):
                cfg.batch_linger_ms = ini.getfloat("common",
                                                   "batch_linger_ms")
            if ini.has_option("common", "adaptive_buckets"):
                cfg.adaptive_buckets = ini.getboolean("common",
                                                      "adaptive_buckets")
            if ini.has_section("ladders"):
                # case-preserving re-read: configparser lowercases option
                # keys by default, but stage names are case-sensitive
                # (ladder_snapshot() exports them verbatim) — a lowercased
                # key would silently miss the warm-start lookup
                cased = configparser.ConfigParser()
                cased.optionxform = str
                cased.read(path)
                cfg.bucket_ladders = {
                    stage: [int(v) for v in _split(sizes)]
                    for stage, sizes in cased.items("ladders")
                }
            if ini.has_option("common", "data_parallel"):
                cfg.data_parallel = ini.getint("common", "data_parallel")
            if ini.has_option("common", "model_parallel"):
                cfg.model_parallel = ini.getint("common", "model_parallel")
            if ini.has_option("common", "dispatch_depth"):
                cfg.dispatch_depth = ini.getint("common", "dispatch_depth")
            if ini.has_option("common", "shape_bucketing"):
                cfg.shape_bucketing = ini.getboolean("common",
                                                     "shape_bucketing")
            if ini.has_option("common", "hbm_budget_bytes"):
                cfg.hbm_budget_bytes = ini.getint("common",
                                                  "hbm_budget_bytes")
            if ini.has_option("common", "max_compiled_variants"):
                cfg.max_compiled_variants = ini.getint(
                    "common", "max_compiled_variants")
            if ini.has_option("common", "fetch_depth"):
                cfg.fetch_depth = ini.getint("common", "fetch_depth")
            if ini.has_option("common", "donate_ingress"):
                cfg.donate_ingress = ini.getboolean("common",
                                                    "donate_ingress")
            if ini.has_option("common", "reduce_outputs"):
                cfg.reduce_outputs = ini.getboolean("common",
                                                    "reduce_outputs")
            if ini.has_option("common", "link_d2h_mbps"):
                cfg.link_d2h_mbps = ini.getfloat("common", "link_d2h_mbps")
            if ini.has_option("common", "link_fetch_rtt_ms"):
                cfg.link_fetch_rtt_ms = ini.getfloat(
                    "common", "link_fetch_rtt_ms")
            if ini.has_option("common", "max_stage_restarts"):
                cfg.max_stage_restarts = ini.getint(
                    "common", "max_stage_restarts")
            if ini.has_option("common", "trace_mode"):
                cfg.trace_mode = ini.get("common",
                                         "trace_mode").strip().lower()
            if ini.has_option("common", "trace_ring_capacity"):
                cfg.trace_ring_capacity = ini.getint(
                    "common", "trace_ring_capacity")
            if ini.has_option("common", "xray"):
                cfg.xray = ini.getboolean("common", "xray")
            if ini.has_option("common", "xray_hbm_tolerance"):
                cfg.xray_hbm_tolerance = ini.getfloat(
                    "common", "xray_hbm_tolerance")
            if ini.has_option("common", "peak_tflops"):
                cfg.peak_tflops = ini.getfloat("common", "peak_tflops")
            for sec in ini.sections():
                if sec.startswith("filter-"):
                    cfg.framework_options[sec[len("filter-"):]] = dict(ini.items(sec))
        if os.environ.get(_ENV_PLUGINS):
            cfg.plugin_modules = _split(os.environ[_ENV_PLUGINS])
        if os.environ.get(_ENV_FW_PRIORITY):
            cfg.filter_priority = _split(os.environ[_ENV_FW_PRIORITY])
        if os.environ.get(_ENV_BATCH_MAX):
            cfg.batch_max = int(os.environ[_ENV_BATCH_MAX])
        if os.environ.get(_ENV_DATA_PARALLEL):
            cfg.data_parallel = int(os.environ[_ENV_DATA_PARALLEL])
        if os.environ.get(_ENV_MODEL_PARALLEL):
            cfg.model_parallel = int(os.environ[_ENV_MODEL_PARALLEL])
        if os.environ.get(_ENV_DISPATCH_DEPTH):
            cfg.dispatch_depth = int(os.environ[_ENV_DISPATCH_DEPTH])
        if os.environ.get(_ENV_HBM_BUDGET):
            cfg.hbm_budget_bytes = int(os.environ[_ENV_HBM_BUDGET])
        if os.environ.get(_ENV_MAX_VARIANTS):
            cfg.max_compiled_variants = int(os.environ[_ENV_MAX_VARIANTS])
        if os.environ.get(_ENV_FETCH_DEPTH):
            cfg.fetch_depth = int(os.environ[_ENV_FETCH_DEPTH])
        if os.environ.get(_ENV_DONATE_INGRESS):
            cfg.donate_ingress = os.environ[_ENV_DONATE_INGRESS].lower() in (
                "1", "true", "yes", "on")
        if os.environ.get(_ENV_REDUCE_OUTPUTS):
            cfg.reduce_outputs = os.environ[_ENV_REDUCE_OUTPUTS].lower() in (
                "1", "true", "yes", "on")
        if os.environ.get(_ENV_LINK_D2H_MBPS):
            cfg.link_d2h_mbps = float(os.environ[_ENV_LINK_D2H_MBPS])
        if os.environ.get(_ENV_LINK_RTT_MS):
            cfg.link_fetch_rtt_ms = float(os.environ[_ENV_LINK_RTT_MS])
        if os.environ.get(_ENV_STAGE_RESTARTS):
            cfg.max_stage_restarts = int(os.environ[_ENV_STAGE_RESTARTS])
        if os.environ.get(_ENV_XRAY):
            cfg.xray = os.environ[_ENV_XRAY].lower() in (
                "1", "true", "yes", "on")
        if os.environ.get(_ENV_XRAY_HBM_TOL):
            cfg.xray_hbm_tolerance = float(os.environ[_ENV_XRAY_HBM_TOL])
        if os.environ.get(_ENV_PEAK_TFLOPS):
            cfg.peak_tflops = float(os.environ[_ENV_PEAK_TFLOPS])
        if os.environ.get(_ENV_TRACE):
            cfg.trace_mode = os.environ[_ENV_TRACE].strip().lower()
        if os.environ.get(_ENV_TRACE_RING):
            cfg.trace_ring_capacity = int(os.environ[_ENV_TRACE_RING])
        if os.environ.get(_ENV_BUCKETING):
            cfg.shape_bucketing = os.environ[_ENV_BUCKETING].lower() in (
                "1", "true", "yes", "on")
        if os.environ.get(_ENV_ADAPTIVE):
            cfg.adaptive_buckets = os.environ[_ENV_ADAPTIVE].lower() in (
                "1", "true", "yes", "on")
        if os.environ.get(_ENV_LADDERS):
            cfg.bucket_ladders = parse_ladders(os.environ[_ENV_LADDERS])
        return cfg


def parse_ladders(s: str) -> Dict[str, List[int]]:
    """``"f:1|2|4|6;g:1|2|8"`` -> ``{"f": [1,2,4,6], "g": [1,2,8]}`` (the
    env encoding of a ladder snapshot; ':' splits stage from sizes, '|'
    splits sizes — both survive shells unquoted)."""
    out: Dict[str, List[int]] = {}
    for part in s.split(";"):
        part = part.strip()
        if not part:
            continue
        stage, _, sizes = part.partition(":")
        out[stage.strip()] = [int(v) for v in sizes.split("|") if v.strip()]
    return out


def _split(s: str) -> List[str]:
    out = []
    for part in s.replace(":", ",").split(","):
        part = part.strip()
        if part:
            out.append(part)
    return out


_config: Optional[Config] = None
_lock = threading.Lock()


def get_config() -> Config:
    global _config
    if _config is None:
        with _lock:
            if _config is None:
                _config = Config.load()
    return _config


def set_config(cfg: Config) -> None:
    global _config
    with _lock:
        _config = cfg


def reset_config() -> None:
    global _config
    with _lock:
        _config = None
