"""HBM-residency planner: decide, per sink edge, what actually crosses to host.

Upstream nnstreamer's core promise is that tensors stay pipeline-resident
between elements (PAPER §0).  On TPU the pipeline-resident place is HBM and
the expensive boundary is the D2H link: a pipeline that ships a
full-resolution class map where the 256x-smaller native-stride one would do
is bound by the link, not the model.  This module makes that a planner
decision:

* **Fetch plan** (:func:`plan_residency`): for every edge into a sink, the
  planner records statically what is going to cross to host per buffer —
  the fused sink reduction's tiny device outputs when the stage tail pairs
  ``device_fn`` with ``host_post`` (argmax/top-k/NMS/decode already run on
  device), or the negotiated spec's full payload otherwise.  Edges between
  device stages are device-resident by construction (buffers are jax
  Arrays in HBM end to end) and are pinned so by tests.
* **Reduced-output selection** (:func:`mark_reduced_admissible`): when a
  model offers a REDUCED output variant (``ModelBundle.reduced_variant``,
  e.g. deeplab's native-stride score map: the class decision at the
  model's true resolution, of which full res is only a bilinear blow-up)
  and EVERY downstream consumer's negotiated caps admit arbitrary tensor
  geometry (``admits_reduced_payload``), the planner selects it — "fetch
  the 256x-smaller thing" becomes the default, not a hand-tuned
  ``custom=upsample:0`` row.  ``Pipeline(reduce_outputs=False)`` /
  ``NNS_TPU_REDUCE_OUTPUTS=0`` opts out.
* **Pricing** (:func:`fetch_ms` / :func:`compute_floor_ms`): the shared
  arithmetic the deep lint (``analysis/tracecheck.py``) uses to convert
  planned fetch bytes into milliseconds on the calibrated link and flag
  ``fetch-bound`` pipelines before a chip is touched.

See docs/FETCH.md.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from ..core.log import logger
from ..elements.base import Element, SinkElement, SourceElement

log = logger(__name__)

#: HBM bandwidth (GB/s, v5e spec sheet) behind the static compute-floor
#: roofline: a device stage cannot finish a buffer faster than streaming
#: its params + activations through HBM once.  Deliberately a FLOOR — the
#: ``fetch-bound`` diagnostic only fires when planned D2H time exceeds
#: even this lower bound on compute, so it never over-fires on
#: compute-heavy stages.
HBM_GBPS = 819.0


def fetch_ms(nbytes: int, d2h_mbps: float, rtt_ms: float = 0.0) -> float:
    """Planned D2H milliseconds for one buffer on the calibrated link:
    bandwidth term + one small-fetch roundtrip (every pull that catches
    the prefetcher pays the RTT once)."""
    if d2h_mbps <= 0:
        return 0.0
    return nbytes / (d2h_mbps * 1e6) * 1e3 + max(0.0, rtt_ms)


def compute_floor_ms(touched_bytes: int) -> float:
    """Roofline lower bound on a device stage's per-buffer time: bytes it
    must stream through HBM (params + in/out activations), at
    :data:`HBM_GBPS`."""
    return touched_bytes / (HBM_GBPS * 1e9) * 1e3


@dataclasses.dataclass
class FetchEdge:
    """Planned D2H crossing for one edge into a sink."""

    sink: str  # sink element name
    producer: str  # stage/element label feeding it
    #: planned bytes crossing to host per buffer (-1 = unknown statically:
    #: flexible spec, host-derived payload)
    bytes_per_buffer: int
    #: how the payload was shrunk before crossing (None = raw negotiated
    #: spec crosses): "fused host_post" = device reduction's tiny outputs,
    #: "reduced output" = planner-selected reduced model output
    reduced: Optional[str] = None
    #: pricing (filled only when a calibrated link is configured)
    d2h_ms: float = 0.0
    compute_floor_ms: float = 0.0


@dataclasses.dataclass
class ResidencyPlan:
    """The residency planner's verdict for one pipeline."""

    fetch: List[FetchEdge]
    #: inter-stage edges whose payload stays a device array in HBM
    resident_edges: int = 0
    #: element names whose reduced output variant the planner selected
    reduced_outputs: List[str] = dataclasses.field(default_factory=list)

    def render(self) -> str:
        lines = [f"residency plan: {self.resident_edges} device-resident "
                 f"edge(s)"]
        for name in self.reduced_outputs:
            lines.append(f"  reduced output selected: {name}")
        for e in self.fetch:
            size = ("?" if e.bytes_per_buffer < 0
                    else f"{e.bytes_per_buffer} B")
            via = f" via {e.reduced}" if e.reduced else ""
            lines.append(
                f"  fetch {e.sink} <- {e.producer}: {size}/buffer{via}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# reduced-output admissibility
# ---------------------------------------------------------------------------

def _admits_downstream(graph, elements: Dict[int, Element], nid: int,
                       memo: Dict[int, bool]) -> bool:
    """True when EVERY path from ``nid``'s outputs to a sink runs through
    elements that declare ``admits_reduced_payload`` — i.e. no consumer's
    negotiated contract depends on the producer's full output geometry.
    Conservative by default: an element that doesn't opt in vetoes."""
    if nid in memo:
        return memo[nid]
    memo[nid] = False  # cycle-safe: a loop can never reach a sink
    outs = graph.out_edges(nid)
    if not outs:
        memo[nid] = False  # dangling edge: nothing admits
        return False
    for e in outs:
        dst = elements[e.dst]
        if not getattr(dst, "admits_reduced_payload", False):
            return False
        if not isinstance(dst, SinkElement) \
                and not _admits_downstream(graph, elements, e.dst, memo):
            return False
    memo[nid] = True
    return True


def mark_reduced_admissible(graph, elements: Dict[int, Element]) -> List[str]:
    """Pre-negotiation pass: mark every tensor_filter whose downstream
    consumers all admit reduced geometry with ``_reduced_admissible`` so
    its ``configure()`` may switch the framework to the model's reduced
    output variant (if it offers one).  Runs BEFORE caps negotiation —
    the switch changes the negotiated spec.  Returns the marked names."""
    from ..elements.filter import TensorFilter

    memo: Dict[int, bool] = {}
    marked: List[str] = []
    for nid, el in elements.items():
        if not isinstance(el, TensorFilter):
            continue
        if _admits_downstream(graph, elements, nid, memo):
            el._reduced_admissible = True
            marked.append(el.name)
    return marked


# ---------------------------------------------------------------------------
# fetch plan (runtime: post-negotiation, post-stage-planning)
# ---------------------------------------------------------------------------

def _spec_bytes(caps) -> int:
    spec = getattr(caps, "spec", None)
    if spec is None or spec.is_flexible:
        return -1
    try:
        return int(spec.nbytes)
    except (TypeError, ValueError):
        return -1


def plan_residency(graph, elements: Dict[int, Element],
                   stages) -> ResidencyPlan:
    """Build the pipeline's :class:`ResidencyPlan` from the negotiated
    graph and the planned stages.  Per sink edge the planned fetch is:

    * the producing fused stage's DEVICE out spec when its tail pairs
      ``device_fn`` with a deferred ``host_post`` (the fused sink
      reduction: only argmax indices / kept boxes / class ids cross,
      resolved to media on the app side);
    * otherwise the negotiated spec's bytes at the edge (-1 when flexible).
    """
    node_to_stage = {}
    for st in stages:
        for nid in st.node_ids:
            node_to_stage[nid] = st

    def _device_stage(st) -> bool:
        el = st.element
        # device_resident: stateful device elements (the aggregator's HBM
        # ring) that expose no fusable device_fn but still emit device
        # arrays — their downstream edges stay in HBM
        return (st.batchable or getattr(el, "kind", "") == "fused"
                or getattr(el, "device_resident", False)
                or type(el).device_fn is not Element.device_fn)

    fetch: List[FetchEdge] = []
    resident = 0
    reduced_names = [el.name for el in elements.values()
                     if getattr(el, "reduced_output_selected", None)]
    for e in graph.edges:
        src_st = node_to_stage.get(e.src)
        dst_st = node_to_stage.get(e.dst)
        if src_st is None or dst_st is None or src_st is dst_st:
            continue  # fused-internal edge: resident by construction
        dst_el = dst_st.element
        if isinstance(dst_el, SinkElement):
            prod = src_st.element
            # a folded device source wraps the fused chain — the chain
            # carries the host_post / device out spec
            fused = getattr(prod, "fused", prod)
            host_post = getattr(fused, "_host_post", None)
            if host_post is not None and getattr(fused, "_out_spec", None) \
                    is not None:
                spec = fused._out_spec
                nbytes = -1 if spec.is_flexible else int(spec.nbytes)
                fetch.append(FetchEdge(
                    sink=dst_el.name, producer=prod.name,
                    bytes_per_buffer=nbytes, reduced="fused host_post"))
            else:
                src_el = elements.get(e.src)
                caps = (src_el.out_caps.get(e.src_pad)
                        if src_el is not None else None)
                red = ("reduced output"
                       if src_el is not None and getattr(
                           src_el, "reduced_output_selected", None)
                       else None)
                fetch.append(FetchEdge(
                    sink=dst_el.name, producer=src_st.element.name,
                    bytes_per_buffer=_spec_bytes(caps), reduced=red))
        elif _device_stage(src_st) and _device_stage(dst_st) \
                and not isinstance(src_st.element, SourceElement):
            # device stage -> device stage: the payload is a jax Array
            # that never leaves HBM (zero-copy hop, pinned by
            # tests/test_fetch.py)
            resident += 1
    return ResidencyPlan(fetch=fetch, resident_edges=resident,
                         reduced_outputs=reduced_names)
