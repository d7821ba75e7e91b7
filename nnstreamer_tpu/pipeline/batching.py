"""Adaptive micro-batch dispatch: N queued buffers -> ONE jitted XLA call.

The executor's unit of work is one buffer; per-dispatch overhead (python
jit call, XLA launch, fetch roundtrip) is paid per buffer.  When a device
stage's queue is backlogged, that overhead dominates small models.

:class:`BatchRunner` wraps a stage's pure per-buffer function
``tuple(arrays) -> tuple(arrays)`` and executes a LIST of per-buffer input
rows as one compiled program:

* the batch is padded up to a small set of **buckets** (default powers of
  two) so XLA compiles one program per bucket, not per occupancy;
* padding repeats the last real row — valid data, no masking, and the
  repeated references cost nothing outside jit;
* stack -> vmap(fn) -> split all happen INSIDE the jitted program, so a
  batch of 8 costs exactly one dispatch (no per-row slice dispatches), and
  the split rows are device buffers that stay in HBM.

Row outputs are bit-equal across occupancies of the same bucket (same
compiled program; pad rows only append rows, never change the math of the
real ones).

**Sharded mode** (the mesh-DP tentpole, docs/BATCHING.md "Sharded
dispatch"): given a mesh whose ``data`` axis is > 1, the bucketed batch
becomes the unit of data parallelism — the stacked batch dim is sharded
over the ``data`` axis (``in_shardings``/``out_shardings`` via
``parallel/sharding.data_sharding``), buckets round up to multiples of
the axis size so every replica holds equal rows, and stage parameters
are replicated onto the mesh ONCE before the first sharded dispatch (the
``prepare`` hook), not per call.  ``vmap`` guarantees rows never
interact, so the per-row math — and for elementwise stages the exact
bits — matches the single-device program.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..core.buffer import pad_rows, split_rows, stack_tensors
from ..core.log import metrics

#: default bucket ladder; bucket_for() LADDER-ROUNDS above it (multiples
#: of the top bucket), so programs stay bounded at any batch_max
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def bucket_for(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest allowed batch size >= n.  Above the ladder top the size is
    LADDER-ROUNDED — the next multiple of the top bucket — never the exact
    occupancy: an exact fallback minted one compiled program PER OCCUPANCY
    once ``batch_max`` exceeded the top (a 1000-deep drain could compile
    hundreds of signatures), which is precisely the recompile storm the
    ladder exists to prevent.  Rounding bounds the census at
    ``len(ladder) + batch_max // top`` programs (see :func:`ladder`)."""
    bs = buckets or DEFAULT_BUCKETS
    for b in bs:
        if b >= n:
            return b
    top = bs[-1]
    return top * (-(-n // top))


def ladder(batch_max: int, buckets: Optional[Sequence[int]] = None
           ) -> Tuple[int, ...]:
    """Every bucket size a runner with this ``batch_max`` can ever dispatch
    (ascending).  Mirrors :func:`bucket_for` exactly: sizes above the top
    bucket appear as multiples of the top (the ladder-rounded fallback) up
    to the rounded ``batch_max``, so the set never contains a size the
    runtime cannot produce — and never misses one it can.  This is the
    compiled-signature ladder the deep analyzer multiplies out for its
    recompile census and HBM high-water estimate — one compiled program
    per entry, per stage."""
    bs = tuple(sorted(set(buckets))) if buckets else DEFAULT_BUCKETS
    bm = max(1, batch_max)
    top = bucket_for(bm, bs)
    out = [b for b in bs if b <= top]
    if top > bs[-1]:
        out.extend(range(2 * bs[-1], top + 1, bs[-1]))
    return tuple(out)


def shard_bucket_for(n: int, replicas: int,
                     buckets: Optional[Sequence[int]] = None) -> int:
    """Bucket for a batch sharded over ``replicas``: the ladder bucket,
    rounded UP to a multiple of the replica count so every replica gets
    the same number of rows (XLA SPMD partitions the batch dim evenly —
    a ragged split would be a different program per remainder)."""
    b = bucket_for(n, buckets)
    return b + (-b) % max(1, replicas)


#: occupancy observations of one size before the adaptive ladder mints a
#: bucket for it: high enough that a transient burst shape never costs a
#: compile, low enough that a persistent drain pattern refines within the
#: first seconds of a backlogged run
MINT_AFTER = 24


class AdaptiveLadder:
    """Per-stage bucket ladder refined ONLINE from observed occupancies.

    The static powers-of-two ladder pads every drain up to the next power
    of two — a runner that persistently drains 5–7 rows pays bucket-8
    compute forever (pad-waste is a measured counter:
    ``<stage>.batch_pad_waste``).  This ladder watches the same occupancy
    stream the Prometheus histogram renders (``<stage>.batch_occupancy``,
    cumulative ``_bucket{le=}`` exposition) and MINTS an exact bucket for
    any occupancy observed :data:`MINT_AFTER` times that the current
    ladder would pad — so steady-state skew compiles one right-sized
    program instead of padding into a bigger one.

    Two hard bounds keep the deep-lint recompile census CLOSED:

    * ``budget`` — max ladder entries (base + minted), resolved by
      ``pipeline/plan.adaptive_variant_budget`` from
      ``Config.max_compiled_variants`` so the census the deep pass prices
      is the worst case this ladder can ever reach;
    * ``align`` — minted sizes round up to a multiple of the mesh's
      ``data``-axis width, so :func:`shard_bucket_for`'s replica rounding
      still applies bucket-for-bucket under 2-D placement.

    ``warm`` pre-seeds minted sizes (the export/warm-start path:
    ``Pipeline.ladder_snapshot()`` -> ``Config.bucket_ladders`` /
    ``Pipeline(bucket_ladders=...)``), so a steady-state deployment
    compiles its refined ladder at warmup instead of re-learning it.

    Thread-safety: ``bucket_for``/``observe`` run on the owning stage
    thread; ``sizes``/``export`` may be read from the app thread — the
    ladder tuple is swapped atomically under a small lock.
    """

    _GUARDED_BY = {"_minted": "_lock", "_sizes": "_lock",
                   "_align": "_lock"}

    def __init__(self, base: Optional[Sequence[int]] = None, *,
                 budget: int = 0, align: int = 1,
                 warm: Optional[Sequence[int]] = None,
                 mint_after: int = MINT_AFTER, name: Optional[str] = None):
        self.base: Tuple[int, ...] = (tuple(sorted(set(base))) if base
                                      else DEFAULT_BUCKETS)
        self._align = max(1, align)
        self.budget = max(len(self.base), budget) if budget else 0
        self.mint_after = max(1, mint_after)
        self.name = name
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._minted: set = set()
        self._sizes = self.base
        self._minted_metric = f"{name}.ladder_minted" if name else None
        if warm:
            for s in warm:
                self._mint(int(s))

    @property
    def align(self) -> int:
        return self._align

    @align.setter
    def align(self, value: int) -> None:
        """Re-align every already-minted size to the new replica count.
        Warm-start sizes are minted at construction (align=1 — the mesh
        does not exist yet), and the runtime assigns the real ``data``
        width at start(): a dp=1 snapshot's minted 6 warm-started into a
        dp=4 deployment re-rounds to 8 here (deduping against the base),
        instead of sitting in the ladder as a never-dispatchable entry
        that burns a census budget slot."""
        # nns-tsan unguarded-write: the re-round below READS _align, so
        # the swap must be atomic with it — a racing setter otherwise
        # re-rounds _minted against the other thread's width
        with self._lock:
            self._align = max(1, int(value))
            self._minted = {self._aligned(s) for s in self._minted}
            self._minted.difference_update(self.base)
            self._sizes = tuple(sorted(set(self.base) | self._minted))

    def sizes(self) -> Tuple[int, ...]:
        """The current ladder (base + minted, ascending) — what
        :func:`bucket_for`/:func:`shard_bucket_for` round against and
        what the deep census would count if it could see this run."""
        return self._sizes

    def export(self) -> List[int]:
        """The ladder as a warm-startable list (``Config.bucket_ladders``
        value; feed back via ``Pipeline(bucket_ladders={stage: [...]})``)."""
        return list(self._sizes)

    def _aligned(self, n: int) -> int:
        return n + (-n) % self.align

    def _room(self) -> bool:
        return self.budget <= 0 or len(self._sizes) < self.budget

    def _mint(self, n: int) -> None:
        n = self._aligned(n)
        if n in self._sizes or n <= 0 or not self._room():
            return
        with self._lock:
            self._minted.add(n)
            self._sizes = tuple(sorted(set(self.base) | self._minted))
        if self._minted_metric:
            metrics.count(self._minted_metric)

    def observe(self, n: int) -> None:
        """Record one drain's occupancy; mint an exact (aligned) bucket
        once the same padded occupancy repeats ``mint_after`` times."""
        want = self._aligned(n)
        if want in self._sizes:
            return  # no pad at this occupancy: nothing to refine
        c = self._counts.get(want, 0) + 1
        self._counts[want] = c
        if c >= self.mint_after:
            del self._counts[want]
            self._mint(want)

    def bucket_for(self, n: int) -> int:
        """Observe ``n`` and return its bucket under the CURRENT ladder
        (refinement applies from the next drain on — the dispatch that
        triggered a mint still pads, so bucket choice never races the
        ladder swap)."""
        sizes = self._sizes
        self.observe(n)
        return bucket_for(n, sizes)


class BatchRunner:
    """Per-stage cache of bucketed ``jit(vmap(fn))`` programs.

    ``fn`` is the stage's pure per-buffer function.  jit's own cache
    handles input shape/dtype changes; this cache keys only the bucket
    size (which is baked into the program's split).

    ``mesh`` (with a ``data`` OR ``model`` axis > 1) switches on sharded
    dispatch: the batch dim shards over ``data`` while stage parameters
    are PLACED per their ``param_pspecs`` — sharded over ``model``,
    replicated otherwise.  ``prepare(mesh) -> Optional[new_fn]`` runs
    exactly once before the first sharded dispatch so the stage can place
    its parameters onto the mesh and hand back a fresh closure capturing
    the placed tree.
    """

    def __init__(self, fn: Callable, buckets: Optional[Sequence[int]] = None,
                 name: Optional[str] = None, mesh=None,
                 prepare: Optional[Callable] = None, tracer=None,
                 ladder: Optional[AdaptiveLadder] = None, xray=None):
        self.fn = fn
        self.buckets = tuple(sorted(set(buckets))) if buckets else None
        # adaptive mode: the per-stage AdaptiveLadder replaces the static
        # bucket list for rounding decisions (and observes every drain)
        self.ladder = ladder
        self._name = name or "batch"
        # the owning pipeline's flight recorder (None = that pipeline runs
        # trace_mode=off, even if another pipeline enabled the global one)
        self._tracer = tracer
        # the owning pipeline's nns-xray program registry (None = off:
        # bucket programs compile untracked, one pointer check here)
        self._xray = xray
        self._progs: Dict[int, Callable] = {}
        self._pad_metric = f"{name}.batch_pad_waste" if name else None
        self._waste_flops_metric = (f"{name}.pad_waste_flops"
                                    if name else None)
        self._shard_metric = f"{name}.shard_rows" if name else None
        self._dispatch_metric = f"{name}.shard_dispatch" if name else None
        self.mesh = None
        self.replicas = 1
        self.model_axis = 1
        self._sharding = None
        self._dev_coords = None
        if mesh is not None:
            from ..parallel.mesh import device_coords, mesh_axis_size

            d = mesh_axis_size(mesh, "data")
            m = mesh_axis_size(mesh, "model")
            # a (1, 1) mesh is exactly the unsharded path; a >1 model
            # axis engages the sharded path even at data=1 so the
            # prepare hook can SHARD stage params over `model` (2-D
            # placement, docs/BATCHING.md "2-D sharded dispatch")
            if d > 1 or m > 1:
                from ..parallel.sharding import data_sharding

                self.mesh = mesh
                self.replicas = d
                self.model_axis = m
                # invariant per runner: built once, reused by every
                # dispatch's device_put AND the program's in/out_shardings
                self._sharding = data_sharding(mesh)
                if m > 1:
                    # device id -> (data, model) coordinate: 2-D runs name
                    # per-replica counters by mesh position, not raw id
                    self._dev_coords = device_coords(mesh)
        self._prepare = prepare
        self._prepared = False

    def run(self, rows: List[Tuple]) -> List[Tuple]:
        """Execute per-buffer input rows as one dispatch; returns one
        output row per input row, in order."""
        if self.mesh is not None:
            return self._run_sharded(rows)
        n = len(rows)
        bucket = (self.ladder.bucket_for(n) if self.ladder is not None
                  else bucket_for(n, self.buckets))
        prog = self._progs.get(bucket)
        if prog is None:
            prog = self._progs[bucket] = self._build(bucket)
        if bucket > n:
            rows = pad_rows(rows, bucket)
            if self._pad_metric:
                metrics.count(self._pad_metric, bucket - n)
        out = list(prog(*rows)[:n])
        if self._xray is not None and bucket > n:
            # pad waste priced in FLOPs, not rows: the bucket program's
            # cost analysis split per row times the pad rows appended
            flops = getattr(prog, "flops", 0.0)
            if flops and self._waste_flops_metric:
                metrics.count(self._waste_flops_metric,
                              flops * (bucket - n) / bucket)
        return out

    def _build(self, bucket: int) -> Callable:
        import jax

        fn = self.fn

        def prog(*per_buf):
            stacked = stack_tensors(per_buf)
            outs = jax.vmap(fn)(stacked)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            return tuple(split_rows(tuple(outs), bucket))

        jitted = jax.jit(prog)
        if self._xray is not None:
            # the trigger batch dim is the bucket (stacking happens
            # INSIDE the program, so the registry can't read it off the
            # args) — the census allow-check prices it against the ladder
            jitted = self._xray.track(jitted, self._name, "batch",
                                      rec=self._tracer, rows=bucket)
        return jitted

    # -- sharded dispatch --------------------------------------------------
    def _run_sharded(self, rows: List[Tuple]) -> List[Tuple]:
        """One bucketed dispatch with the batch dim sharded over the mesh's
        ``data`` axis.  Stack and pad happen on host (the stacked arrays
        must carry the sharded layout INTO the program, so the stack can't
        live inside it like the single-device path's does); split rows are
        lazy slices of the sharded outputs."""
        import jax
        import time as _time

        n = len(rows)
        t_trace0 = _time.monotonic_ns() if self._tracer is not None else 0
        if not self._prepared:
            # Param replication is once-per-runner, BEFORE the first
            # program builds: the jitted closure must capture the
            # replicated tree, or every dispatch re-broadcasts weights.
            self._prepared = True
            if self._prepare is not None:
                new_fn = self._prepare(self.mesh)
                if new_fn is not None:
                    self.fn = new_fn
                    self._progs.clear()
        if self.ladder is not None:
            # minted sizes are replica-aligned (AdaptiveLadder.align), so
            # the replica rounding below is a no-op on them — static base
            # buckets still round up exactly as before
            self.ladder.observe(n)
            bucket = shard_bucket_for(n, self.replicas, self.ladder.sizes())
        else:
            bucket = shard_bucket_for(n, self.replicas, self.buckets)
        if bucket > n:
            rows = pad_rows(rows, bucket)
            if self._pad_metric:
                metrics.count(self._pad_metric, bucket - n)
        stacked = tuple(
            jax.device_put(x, self._sharding)
            for x in self._host_stack(rows))
        # ONE program serves every bucket here (see _build_sharded); the
        # cache key is fixed so a prepare()-swapped fn still invalidates.
        prog = self._progs.get(-1)
        if prog is None:
            prog = self._progs[-1] = self._build_sharded()
        outs = prog(*stacked)
        if self._xray is not None and bucket > n:
            # approximation: the tracked program's cost is the LATEST
            # compiled bucket's — steady-state drains sit in one bucket,
            # where this is exact
            flops = getattr(prog, "flops", 0.0)
            if flops and self._waste_flops_metric:
                metrics.count(self._waste_flops_metric,
                              flops * (bucket - n) / bucket)
        if self._dispatch_metric:
            metrics.count(self._dispatch_metric)
            # Per-replica placement counters: read the real shard layout
            # off the first output (proof of N-way placement, not an
            # assumption about what XLA did).  dp-only keeps the legacy
            # `.d<device-id>` names; a 2-D mesh names each chip by its
            # (data, model) coordinate — `.d<di>m<mi>` — so the counters
            # stay truthful when the output is replicated over `model`.
            for s in outs[0].addressable_shards:
                if self._dev_coords is None:
                    key = f"{self._shard_metric}.d{s.device.id}"
                else:
                    di, mi = self._dev_coords[s.device.id]
                    key = f"{self._shard_metric}.d{di}m{mi}"
                metrics.count(key, s.data.shape[0])
        # Reassemble each output with ONE host fetch per tensor, then
        # split into numpy views (free).  Per-row slicing of a
        # data-sharded array is catastrophic — every row becomes a
        # cross-replica gather+broadcast (measured 13x slower end-to-end
        # than not sharding); a device-side gather + in-program split
        # still pays per-row fetch dispatches (measured 0.9x).  The one
        # assembled fetch measured 4.4x vs the single-device path on the
        # same backlogged batch.  Sharded rows therefore continue as HOST
        # arrays — the right trade for the backlogged-serving shape
        # (sinks materialize anyway, and a following sharded stage
        # re-stacks on host zero-copy); keep data_parallel=1 for chains
        # that must stay HBM-resident between unfused device stages.
        import numpy as np

        host = [np.asarray(a) for a in outs]
        if t_trace0:
            # the sharded-dispatch window: stack+device_put+program+fetch
            # as one span (per-row trace ids live one layer up, in the
            # runner's batch span — this is the device-side cost bucket).
            # 2-D runs additionally carry the model-axis width so the
            # span names its full (data, model) placement.
            extra = ({"model": self.model_axis}
                     if self.model_axis > 1 else {})
            self._tracer.record("shard", self._name, None, t_trace0,
                                _time.monotonic_ns() - t_trace0,
                                rows=n, bucket=bucket,
                                replicas=self.replicas, **extra)
        return [tuple(h[i] for h in host) for i in range(n)]

    @staticmethod
    def _host_stack(rows: List[Tuple]) -> Tuple:
        """Stack per-buffer rows for sharded device_put.  All-numpy
        columns (the host-ingest case) stack on HOST — device_put then
        places each shard zero-copy — while device-array columns (a fused
        chain upstream) go through the jnp path."""
        import numpy as np

        k = len(rows[0])
        cols = []
        for t in range(k):
            vals = [r[t] for r in rows]
            if all(isinstance(v, np.ndarray) for v in vals):
                cols.append(np.stack(vals))
            else:
                cols.append(stack_tensors([(v,) for v in vals])[0])
        return tuple(cols)

    def _build_sharded(self) -> Callable:
        """The sharded program: vmap over already-stacked inputs whose
        batch dim carries the data-axis sharding.  One program serves
        every bucket (the batch dim is an input shape, and jit's own
        cache keys shapes) — the bucket ladder still bounds how many
        shapes ever reach it."""
        import jax

        fn = self.fn
        sh = self._sharding

        def prog(*stacked):
            outs = jax.vmap(fn)(stacked)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            return tuple(outs)

        # One sharding broadcasts over all args/outputs (rank-agnostic
        # P("data") — see parallel/sharding.data_sharding).
        jitted = jax.jit(prog, in_shardings=sh, out_shardings=sh)
        if self._xray is not None:
            # ONE jit serves every bucket here (cache keys shapes), so
            # the trigger batch dim is read off the stacked leading dim;
            # the program's cost analysis covers the GLOBAL batch spread
            # over the mesh, so MFU denominates in the aggregate peak
            jitted = self._xray.track(jitted, self._name, "batch",
                                      rec=self._tracer,
                                      rows_from_leading=True,
                                      devices=self.replicas
                                      * self.model_axis)
        return jitted
