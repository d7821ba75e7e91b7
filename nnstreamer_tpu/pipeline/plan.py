"""Stage planner: physical execution plan + XLA fusion pass.

This is the capability the reference cannot have (SURVEY §7 "Stage fusion is
the superpower"): contiguous device-capable elements (converter repack,
tensor_transform chains, the jax tensor_filter, decoder math) are grouped
into ONE jitted XLA program.  The element graph stays the *logical* model;
the plan is the *physical* one, with host boundaries only where unavoidable
(app sources, sinks, host-only elements).

Fusion rule: a maximal linear chain of nodes where every element exposes
``device_fn`` for its negotiated input spec, with single in/out edges on the
default pads, collapses into a :class:`FusedElement`.  The composed function
is jitted once, so intermediate tensors never leave HBM and XLA fuses
elementwise stages into the matmul kernels around them; the folded-source
path additionally donates its input buffers (sole ownership is guaranteed
there), letting XLA reuse the generated frame's HBM for outputs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from ..core.buffer import Buffer
from ..core.caps import Caps, MediaType
from ..core.log import logger
from ..core.types import TensorsSpec
from ..elements.base import Element, SourceElement, SRC, SINK
from .graph import Edge, PipelineGraph

log = logger(__name__)


@dataclasses.dataclass
class Stage:
    """One schedulable unit: a single element or a fused chain."""

    element: Element
    node_ids: List[int]
    head: int  # node id receiving external input
    tail: int  # node id producing external output
    #: device stage whose runner may drain a micro-batch from its queue
    #: into one bucketed XLA dispatch (set by the planner; the runtime
    #: additionally requires the pipeline's batch_max > 1)
    batchable: bool = False
    #: batchable stage whose bucketed dispatch may additionally be
    #: SHARDED over the ``data`` axis of a local device mesh: requires a
    #: static negotiated input spec (one sharded program, not one per
    #: signature) and no deferred host_post mapping (its async D2H
    #: ordering is tuned for single-device rows).  The runtime
    #: additionally requires ``data_parallel`` to resolve to > 1.
    shardable: bool = False
    #: PURE/STATELESS stage whose runner thread may be restarted in
    #: place after an exception instead of failing the pipeline (the
    #: elastic stage-restart path, bounded by the pipeline's
    #: ``max_stage_restarts`` — docs/SERVING.md "Elastic serving").
    #: True for fused device chains and single elements whose work is a
    #: pure device fn (the batchable predicate); sources, sinks, and
    #: elements with cross-buffer state (aggregators, async emitters)
    #: stay fail-fast.
    restartable: bool = False

    def external_out_pad(self, edge: Edge) -> str:
        return edge.src_pad

    def external_in_pad(self, edge: Edge) -> str:
        return edge.dst_pad


class FusedElement(Element):
    """A chain of device elements compiled into one jitted function."""

    kind = "fused"

    def __init__(self, elements: List[Element], specs: List[TensorsSpec],
                 donate: bool = False, ingress_put: bool = False):
        super().__init__({}, name="+".join(e.name for e in elements))
        self.chain = elements
        self._fn = None
        self._batcher = None
        self._out_spec: Optional[TensorsSpec] = None
        self._in_spec = specs[0]
        self._specs = list(specs)
        # Host-fed ingress donation (docs/FETCH.md): the stage device_puts
        # the pushed host arrays itself and hands XLA freshly-minted device
        # buffers it solely owns — the donated program then reuses their
        # HBM for outputs, so steady-state H2D stops allocating.  Only set
        # by the planner when the feeding source is a host source with
        # this stage as its single consumer.
        self._ingress_put = ingress_put
        self._donate_active = False  # decided at first _jitted() call
        # Tail element may pair its device_fn with a deferred host mapping
        # (e.g. image_labeling: device argmax -> host label text).  The fused
        # stage emits the tiny device outputs with an async D2H already in
        # flight; the sink resolves `_host_post` in the app thread, so the
        # D2H roundtrip adds pipeline depth, not throughput.
        self._host_post = getattr(elements[-1], "host_post", None)
        self._build(specs[0], donate)

    def _build(self, in_spec: TensorsSpec, donate: bool) -> None:
        fns: List[Callable] = []
        spec = in_spec
        for el in self.chain:
            df = el.device_fn(spec)
            if df is None:  # pragma: no cover - planner guarantees fusable
                raise RuntimeError(f"element {el.name} not fusable")
            fn, spec = df
            fns.append(fn)
        self._out_spec = spec

        def composed(arrays: Tuple) -> Tuple:
            for f in fns:
                arrays = f(arrays)
            return arrays

        self._composed = composed
        self._donate = donate

    def _jitted(self):
        """Build the jitted program on FIRST use, not at plan time: the
        donation gate reads jax.default_backend(), which initializes the
        backend, and pipeline CONSTRUCTION must stay backend-free (a
        process that only parses or lints a pipeline must not claim the
        chip)."""
        if self._fn is None:
            import jax

            # Donation is only legal when the caller guarantees sole
            # ownership of the input buffers (the folded-source path: the
            # source mints a fresh device array per batch and this program
            # is its only consumer) — XLA then reuses the input HBM for
            # outputs.  CPU backends can't donate and would warn per
            # compile, so gate it.
            if self._donate and jax.default_backend() not in ("cpu",):
                self._fn = jax.jit(self._composed, donate_argnums=(0,))
                self._donate_active = True
            else:
                self._fn = jax.jit(self._composed)
                self._donate_active = False
            xr = getattr(self, "_xray", None)
            if xr is not None:
                # nns-xray census: the fused chain's single-buffer
                # program (the bucketed twins register via BatchRunner)
                self._fn = xr.track(
                    self._fn, self.name, "stage",
                    rec=getattr(self, "_trace_rec", None))
        return self._fn

    @property
    def out_spec(self) -> TensorsSpec:
        return self._out_spec

    def start(self) -> None:
        for el in self.chain:
            el.start()

    def stop(self) -> None:
        for el in self.chain:
            el.stop()

    def _finish(self, buf: Buffer, out) -> Buffer:
        """Shared output tail for the single and batched paths: spec
        fallback for odd shapes (a truncated tail batch from a device
        source with non-aligned num-buffers has a different leading dim
        than the negotiated spec — let the buffer derive its spec so
        wire/shm consumers see truthful byte counts), plus the deferred
        host-post mapping with its async D2H already in flight."""
        spec = self._out_spec
        if (spec is not None and len(out) and hasattr(out[0], "shape")
                and tuple(out[0].shape) != spec[0].shape):
            spec = None
        new = buf.with_tensors(list(out), spec=spec)
        if self._host_post is not None:
            for t in out:
                if hasattr(t, "copy_to_host_async"):
                    t.copy_to_host_async()
            new.meta["_host_post"] = self._host_post
        return new

    def process(self, pad: str, buf: Buffer):
        # Fused-chain-to-fused-chain hop (the common case): the upstream
        # stage's outputs are ALREADY device arrays, and jit re-wraps its
        # own argument types for free — per-tensor jnp.asarray here only
        # added a host round through the dispatch path (~1.6x the whole
        # call overhead for a 4-tensor buffer, see PR microbench note).
        fn = self._jitted()  # first call decides _donate_active
        ingress_put = self._ingress_put and self._donate_active
        if buf.on_device:
            if ingress_put:
                # The donated program consumes its inputs.  An app CAN
                # push device arrays through appsrc (no host copy to
                # mint fresh ownership from), so force a copy — handing
                # app-owned arrays to donate_argnums would invalidate
                # the caller's references ("Array has been deleted").
                import jax.numpy as jnp

                arrays = tuple(jnp.array(t, copy=True) for t in buf.tensors)
            else:
                arrays = tuple(buf.tensors)
        elif ingress_put:
            # Donated ingress: explicit device_put mints device arrays
            # this call solely owns (the app's numpy frame is copied,
            # never aliased), so the donated program may reuse their HBM
            # for outputs.  When donation is compiled OUT (CPU backend)
            # ingress_put is False and the plain asarray path below
            # avoids paying copies that protect nothing.
            import jax

            arrays = tuple(jax.device_put(t) for t in buf.tensors)
        else:
            import jax.numpy as jnp

            arrays = tuple(jnp.asarray(t) for t in buf.tensors)
        out = fn(arrays)
        return [(SRC, self._finish(buf, out))]

    # -- micro-batching ----------------------------------------------------
    def batch_capable(self) -> bool:
        return True

    def place_params(self, mesh) -> bool:
        """Place every chain element's params onto ``mesh`` (shard over
        the ``model`` axis per each element's pspecs, replicate the
        rest), then rebuild the composed function so its device_fn
        closures capture the placed trees (a stale closure would keep
        dragging the original single-device arrays into every sharded
        dispatch)."""
        moved = False
        for el in self.chain:
            moved = el.place_params(mesh) or moved
        if moved:
            self._fn = None  # re-jit from the recaptured closures
            self._build(self._specs[0], self._donate)
        return moved

    def _shard_prepare(self, mesh):
        """BatchRunner prepare hook: place once, hand back the rebuilt
        composed fn."""
        self.place_params(mesh)
        return self._composed

    def process_batch(self, pad: str, bufs):
        """N same-spec buffers -> ONE bucketed vmapped dispatch of the
        fused program (see pipeline/batching.py); per-buffer outputs keep
        their own pts/meta and order.  With a ``data`` mesh attached by
        the runtime (``_shard_mesh``), the bucketed batch dim is sharded
        across the mesh's chips."""
        from .batching import BatchRunner

        if self._batcher is None:
            mesh = getattr(self, "_shard_mesh", None)
            self._batcher = BatchRunner(
                self._composed, getattr(self, "_batch_buckets", None),
                name=self.name, mesh=mesh,
                prepare=self._shard_prepare if mesh is not None else None,
                tracer=getattr(self, "_trace_rec", None),
                ladder=getattr(self, "_batch_ladder", None),
                xray=getattr(self, "_xray", None))
        rows = self._batcher.run([tuple(b.tensors) for b in bufs])
        return [(SRC, self._finish(buf, row)) for buf, row in zip(bufs, rows)]

    def finalize(self):
        outs = []
        for el in self.chain:
            outs.extend(el.finalize())
        # flushed buffers from mid-pipeline elements are NOT re-run through
        # the remaining fused fns; fusable elements are stateless so
        # finalize() output is empty in practice.
        return outs


class FusedSourceElement(SourceElement):
    """A device-resident source folded into its downstream fused chain.

    When the source generates ON DEVICE (``videotestsrc device=true``,
    ``audiotestsrc device=true``), running it as its own stage buys
    nothing: every batch pays a queue hop and a thread wakeup between two
    async device dispatches.  Folding the source into the fused stage makes
    the whole pipeline front ONE schedulable unit — generate and process
    dispatch back-to-back on the same thread, and the only queue hop left
    on the hot path is the sink's (round-2 bench: host-side stage hops cost
    ~13x the 0.27 ms device time per 64-batch).
    """

    kind = "fused"

    def __init__(self, source: Element, fused: "FusedElement"):
        super().__init__({}, name=f"{source.name}+{fused.name}")
        self.source = source
        self.fused = fused

    # cost-analysis hooks (bench reads the fused program off stage elements)
    @property
    def _fn(self):
        return self.fused._fn

    @property
    def _in_spec(self):
        return self.fused._in_spec

    # No start()/stop() overrides: the pipeline starts/stops the ORIGINAL
    # per-node elements directly (runtime iterates self.elements, not stage
    # wrappers), so overrides here would either never run or double-start.

    def generate(self):
        from ..core.buffer import Buffer as _Buffer

        for item in self.source.generate():
            if not isinstance(item, _Buffer):
                yield item  # events pass through
                continue
            outs = self.fused.process(SINK, item)
            for _, out in outs:
                yield out

    def finalize(self):
        return self.source.finalize() + self.fused.finalize()


#: minted buckets an adaptive ladder may add per stage when no
#: ``max_compiled_variants`` budget is configured (0 = uncapped would
#: leave the recompile census open — never allowed)
ADAPTIVE_EXTRA_DEFAULT = 4


def adaptive_variant_budget(base_len: int, n_batchable: int,
                            max_compiled_variants: int) -> int:
    """Max ladder entries (base + minted) ONE adaptive stage may compile —
    the single home for the arithmetic shared by the runtime (each
    stage's ``AdaptiveLadder.budget``) and the deep analyzer's recompile
    census (which prices the WORST CASE: every adaptive stage at its full
    budget), so the census stays closed by construction: the ladders can
    never mint past what the static report already charged.

    With ``max_compiled_variants`` configured, the budget splits it
    evenly across the pipeline's batchable stages (never below the base
    ladder — refinement may be squeezed out entirely, the census may
    not).  Unconfigured, each stage gets the base ladder plus
    :data:`ADAPTIVE_EXTRA_DEFAULT` minted sizes."""
    if max_compiled_variants > 0:
        return max(base_len, max_compiled_variants // max(1, n_batchable))
    return base_len + ADAPTIVE_EXTRA_DEFAULT


def replication_plan(data_parallel: int, batch_max: int,
                     n_devices: int) -> int:
    """Resolve the configured ``data_parallel`` knob to the ``data``-axis
    replica count a pipeline would actually run with — the ONE place the
    0=auto / 1=off / N=exact semantics live, shared by the runtime's mesh
    builder and the deep analyzer's static HBM/recompile budgeting.
    ``n_devices`` is the local device count (the caller queries it so this
    stays importable without initializing a backend).  Returns 1 whenever
    sharding would be skipped (batch_max=1, dp=1, or a 1-wide mesh); the
    dp > n_devices startup error is the caller's to raise/report.

    2-D placements resolve through :func:`mesh_plan`, which calls this
    for the ``data`` axis after carving out the ``model`` axis."""
    if batch_max <= 1 or data_parallel == 1:
        return 1
    dp = data_parallel or n_devices
    return max(1, dp)


def mesh_plan(data_parallel: int, model_parallel: int, batch_max: int,
              n_devices: int) -> Tuple[int, int]:
    """Resolve the 2-D placement knobs to the ``(data, model)`` axis sizes
    ONE pipeline mesh would be built with — the single home for the
    0=auto / 1=off / N=exact semantics of BOTH axes, shared by the
    runtime's mesh builder (``Pipeline._shared_mesh``) and the deep
    analyzer's static HBM/recompile budgeting.

    * ``model_parallel`` — 1 = off (dp-only, the bit-identical legacy
      path), N = exactly N ways tensor-parallel, 0 = auto: absorb every
      local device the ``data`` axis doesn't claim.  Unlike ``data``,
      the model axis is NOT gated on ``batch_max``: a TP-only pipeline
      (the llm filter) shards weights with no micro-batching at all.
    * ``data_parallel`` — exactly :func:`replication_plan`, sized
      against the devices LEFT after the model axis took its share.
    * both auto (``data=0, model=0`` with batching on) — data wins: the
      historical ``data_parallel=0`` auto-absorb stays what it was.

    Over-asks (dp * mp > n_devices) are returned as requested; raising
    the startup error (or the static diagnostic) is the caller's job."""
    mp_knob = int(model_parallel)
    dp_knob = int(data_parallel)
    if mp_knob == 0:
        dp_res = replication_plan(dp_knob, batch_max, n_devices)
        if dp_knob == 0 and dp_res > 1:
            mp = 1  # both axes auto: data absorbs, dp-only semantics hold
        else:
            mp = max(1, n_devices // max(1, dp_res))
    else:
        mp = max(1, mp_knob)
    dp = replication_plan(dp_knob, batch_max, max(1, n_devices // mp))
    return dp, mp


def _element_batchable(el: Element) -> bool:
    """Can this stage's runner drain micro-batches?  Sources have no input
    queue; batch_capable() must not veto planning by raising (a framework
    that cannot even load will fail loudly at start() instead)."""
    if isinstance(el, SourceElement):
        return False
    try:
        return bool(el.batch_capable())
    except Exception:  # noqa: BLE001 - capability probe only
        return False


def _element_shardable(el: Element, batchable: bool) -> bool:
    """Shard-eligibility for a SINGLE-element stage: batchable, a STATIC
    negotiated input spec (a flexible stream re-specializes per buffer
    signature — sharding would compile a mesh program per signature and
    defeat the bucket ladder), and no deferred host_post mapping."""
    if not batchable or getattr(el, "host_post", None) is not None:
        return False
    caps = el.in_caps.get(SINK)
    spec = caps.spec if caps is not None else None
    return spec is not None and spec.format.value == "static"


def plan_stages(
    graph: PipelineGraph, elements: Dict[int, Element], *, fuse: bool = True,
    donate_ingress: bool = False
) -> List[Stage]:
    """Partition the graph into stages; fuse linear device chains.

    ``donate_ingress`` lets a fused chain fed by a HOST source (appsrc,
    file/camera ingest — not ``device=true`` test sources, which already
    donate via the folded-source path) device_put its input buffers and
    donate them to the compiled program: the planner can prove sole
    ownership when the source has this chain as its only consumer, so XLA
    reuses the ingress HBM for outputs (docs/FETCH.md)."""
    order = graph.topo_order()
    if not fuse:
        stages = []
        for n in order:
            b = _element_batchable(elements[n.id])
            stages.append(Stage(
                elements[n.id], [n.id], n.id, n.id, batchable=b,
                shardable=_element_shardable(elements[n.id], b),
                restartable=b))
        return stages

    def linear(nid: int) -> bool:
        ins = graph.in_edges(nid)
        outs = graph.out_edges(nid)
        return (
            len(ins) == 1
            and len(outs) <= 1
            and ins[0].dst_pad == SINK
            and all(e.src_pad == SRC for e in outs)
        )

    def fusable(nid: int) -> Optional[TensorsSpec]:
        """In-spec if the element can join a fused chain, else None."""
        el = elements[nid]
        caps = el.in_caps.get(SINK)
        if caps is None or caps.media not in (MediaType.TENSORS, MediaType.FLEX_TENSORS):
            return None
        spec = caps.spec
        if spec is None or spec.format.value != "static":
            return None
        if el.device_fn(spec) is None:
            return None
        return spec

    stages: List[Stage] = []
    consumed: set = set()

    def grow(first: int) -> Optional[Tuple[List[int], List[TensorsSpec]]]:
        """Maximal fusable chain from ``first`` (None if it can't fuse)."""
        if first in consumed or not linear(first):
            return None
        spec = fusable(first)
        if spec is None:
            return None
        chain = [first]
        specs = [spec]
        cur_spec = elements[first].device_fn(spec)[1]
        cur = first
        while True:
            outs = graph.out_edges(cur)
            if len(outs) != 1:
                break
            nxt = outs[0].dst
            if nxt in consumed or not linear(nxt):
                break
            el = elements[nxt]
            caps = el.in_caps.get(SINK)
            nspec = caps.spec if caps else None
            nspec = nspec or cur_spec
            if el.device_fn(nspec) is None:
                break
            chain.append(nxt)
            specs.append(nspec)
            cur_spec = el.device_fn(nspec)[1]
            cur = nxt
        return chain, specs

    for node in order:
        if node.id in consumed:
            continue
        el = elements[node.id]
        # Device-resident sources fold into their downstream chain: the
        # whole pipeline front becomes one stage (no queue hop between
        # generate and the fused program).  `device is True` exactly: on
        # tensor_src_iio `device` is a PATH STRING (a blocking host
        # reader), and folding that would serialize I/O with compute.
        if isinstance(el, SourceElement) and getattr(el, "device", None) is True:
            outs = graph.out_edges(node.id)
            if (len(outs) == 1 and outs[0].src_pad == SRC
                    and outs[0].dst_pad == SINK):
                grown = grow(outs[0].dst)
                if grown is not None:
                    chain, specs = grown
                    fe = FusedElement([elements[i] for i in chain], specs,
                                      donate=True)
                    fs = FusedSourceElement(el, fe)
                    log.info("fused device source into XLA stage: %s",
                             fs.name)
                    stages.append(
                        Stage(fs, [node.id] + chain, node.id, chain[-1]))
                    consumed.add(node.id)
                    consumed.update(chain)
                    continue
        grown = grow(node.id)
        if grown is None or len(grown[0]) == 1:
            b = _element_batchable(elements[node.id])
            stages.append(Stage(
                elements[node.id], [node.id], node.id, node.id, batchable=b,
                shardable=_element_shardable(elements[node.id], b),
                restartable=b))
            consumed.add(node.id)
            continue
        chain, specs = grown
        donate = False
        if donate_ingress:
            ins = graph.in_edges(chain[0])
            if len(ins) == 1:
                feeder = elements[ins[0].src]
                # Host source with a single consumer: every pushed buffer
                # is minted fresh by the chain's own device_put and this
                # program is its only reader — donation is legal.  A
                # device=true source folds (and donates) above instead.
                donate = (isinstance(feeder, SourceElement)
                          and getattr(feeder, "device", None) is not True
                          and len(graph.out_edges(ins[0].src)) == 1)
        fe = FusedElement([elements[i] for i in chain], specs,
                          donate=donate, ingress_put=donate)
        if donate:
            log.info("ingress donation enabled for fused stage %s", fe.name)
        log.info("fused %d elements into one XLA stage: %s", len(chain), fe.name)
        # Fused chains negotiated a static spec by construction (fusable()
        # requires it); only a deferred host_post gates sharding.
        stages.append(Stage(fe, chain, chain[0], chain[-1], batchable=True,
                            shardable=fe._host_post is None,
                            restartable=True))
        consumed.update(chain)
    return stages
