"""Sparse-expert feed-forward layer that knows which experts it holds.

A router scores every token against ALL ``n_experts`` and picks
``top_k`` of them; this process holds the contiguous share
``[held_first, held_first + held_count)`` of the routed experts (its
rank's share under expert parallelism) plus the shared expert, which
every rank holds.  The layer returns

    sum over chosen experts i that are HELD of w_i * E_i(h)  +  E_shared(h)

— the PARTIAL result of this rank.  What the experts held elsewhere
would have added is added by the exchange between ranks (``parallel/``,
not built yet: ROADMAP M2); on one chip the layer runs without it and
nothing here stands in for the absent ranks.  With the whole set held
(``held_count == n_experts``) the partial result is the layer.

Routing is DROPLESS: there is no capacity factor and no token is ever
skipped.  Inside the serve loop's fixed shapes the routed (token,
expert) pairs — ``tokens * top_k`` rows, a static bound — are sorted by
expert, pairs of experts held elsewhere last, and the three expert
matrices are applied as a grouped product (``ops/grouped_ffn.py``: a
Pallas kernel whose row tile fits the handful of rows an expert has at
decode, XLA's ``ragged_dot`` where an expert expects hundreds; the rule
reads static shapes only) whose group sizes are VALUES: how the tokens
spread over the experts changes no shape, so nothing recompiles.  Rows
past the held groups belong to no expert; what the product leaves there
is not read.

**Identity (zero-compute) experts.**  A router may score ``zero_experts``
further outputs, numbered after the routed ones: a token that chooses one
gets ``g * h`` for it, its own input scaled by the pair's weight, and no
matrix is read.  Such a pair needs no dispatch — it is computed where the
token lives, by every rank alike, and counted ONCE when the ranks' partial
results are added (as a shared expert is).  The grouped product's rows
stay ``tokens * top_k``; an identity pair sorts last with the pairs of
experts held elsewhere.  How many of a token's choices are identity is a
value: a token computes between 0 and ``top_k`` real experts.

Weights of one layer (``lp``): ``w_router`` [D, E + zero] and ``router_bias``
[E + zero] float32 (the router runs in float32: a choice among near-equal
scores must not depend on bf16 rounding); ``we_gate``, ``we_up`` [held,
D, F], ``we_down`` [held, F, D]; ``ws_gate``, ``ws_up`` [D, Fs],
``ws_down`` [Fs, D] with ``Fs = shared * F``.

**Group-limited routing.**  A router may split its routed experts into
``n_group`` groups of consecutive ids and let a token choose only among the
``topk_group`` groups that score best, a group's score being the sum of its
two largest biased scores; the experts of every other group are masked to
``-inf`` before the ``top_k`` choice, so they can never be chosen.  Under
expert parallelism a group is the experts of a few ranks (two chips for
the benchmark's A.X-K1 share: 24 experts a group, 12 a rank), so the rule
also bounds the ranks a token is dispatched to.  Where the groups are
live, ``stats`` gains a sixth entry: the live rows whose kept groups
include a group of this rank's experts.

**The expert matrices are never sliced out of their stack.**  A grouped
product is a custom call, and a slice that feeds one is materialized:
taking layer ``l``'s ``[held, D, F]`` out of a kind's ``[n, held, D,
F]`` stack copied 0.4 GB a matrix a layer in the decode program (4.2 GB
of temporaries at 8 layers; chipless v5e compile, PR 29).  So the walk
hands the WHOLE stack over (``STACKED_LEAVES``) with the layer's index
``lp["_layer"]``, the stack is viewed as ``n * held`` groups (a reshape
of the leading dims, no data moves), and the layer's group sizes are
written at ``[l * held, (l + 1) * held)`` of a vector that is zero
elsewhere: groups of size zero own no row and are not read.

**The combine reads each token's rows where they lie.**  Each token's
routed outputs are weighted and summed into its row (the named scope
``moe.combine``): the row of pair ``j`` of token ``n`` lies where the sort
put it (the inverse of the sort's permutation), and the sum is ``top_k``
row gathers of ``[N, D]``, each weighted where its pair is held and zero
elsewhere, added in the order of the token's choices — float32 products
and sums, the arithmetic of the scatter-add it replaced in another order.
A token reads its own pairs' rows only, so nothing one row holds (a NaN
left past the groups, a parked slot's garbage) reaches another.  On a v5e
the scatter-add into ``[N, D]`` ran at ≈ 15 % of its memory floor (131
µs a layer at ``[512, 7168]`` -> 64 rows, PERF.md section 5); the
serve loop counts the decode step's combines (:func:`combine_calls`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


#: leaves :func:`moe_ffn` takes as the kind's whole stack (see above)
STACKED_LEAVES = ("we_gate", "we_up", "we_down")
#: entries of :func:`moe_ffn`'s ``stats`` (one more where the router's
#: choice is group-limited: :func:`n_stats`)
N_STATS = 5
#: the named scope of the combine (module docstring)
COMBINE_SCOPE = "moe.combine"


@dataclasses.dataclass(frozen=True)
class ExpertsConfig:
    """The sparse FFN of a model: ``n_experts`` routed experts of width
    ``hidden``, ``top_k`` a token, ``shared`` always-on experts of the
    same width; scores are sigmoids or a softmax over all the router's
    outputs (``scoring``), with a per-expert correction bias added FOR
    THE CHOICE ONLY; ``norm_topk`` divides the chosen weights by their
    sum (plus ``norm_eps``, where a model's published code adds one);
    ``scale`` multiplies them.  ``zero_experts`` identity experts
    follow the routed ones in the router's outputs (module docstring).
    ``n_group`` groups of consecutive routed experts, of which a token
    keeps ``topk_group`` (1 and 1 = no groups; module docstring).
    ``held_first``/``held_count`` name this process's share of the
    routed experts (``held_count`` 0 = all)."""

    n_experts: int
    top_k: int
    hidden: int
    shared: int = 0
    scoring: str = "sigmoid"
    norm_topk: bool = True
    scale: float = 1.0
    held_first: int = 0
    held_count: int = 0
    zero_experts: int = 0
    norm_eps: float = 0.0
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"expert scoring {self.scoring!r}: sigmoid "
                             "and softmax scores are built")
        if self.zero_experts < 0:
            raise ValueError(f"zero_experts {self.zero_experts}")
        if not 0 < self.top_k <= self.n_router:
            raise ValueError(f"top_k {self.top_k} of {self.n_router} "
                             "router outputs")
        if self.held_first < 0 or \
                self.held_first + self.n_held > self.n_experts:
            raise ValueError(
                f"held experts [{self.held_first}, "
                f"{self.held_first + self.n_held}) outside the "
                f"{self.n_experts} routed")
        if self.n_group < 1 or self.n_experts % self.n_group:
            raise ValueError(f"n_group {self.n_group}: the {self.n_experts} "
                             "routed experts split into groups of equal "
                             "size")
        if self.grouped:
            size = self.group_size
            if size < 2:
                raise ValueError(
                    f"groups of {size} expert: a group's score is the sum "
                    "of its two best, so a group needs at least two")
            if not 1 <= self.topk_group <= self.n_group:
                raise ValueError(f"topk_group {self.topk_group} of "
                                 f"{self.n_group} groups")
            if self.top_k > self.topk_group * size:
                raise ValueError(
                    f"top_k {self.top_k} from {self.topk_group} kept groups "
                    f"of {size} experts")
            if self.zero_experts:
                raise ValueError(
                    "identity experts with groups: identity experts are no "
                    "group's, and the group-limited choice is built over "
                    "the routed experts only")
        elif self.topk_group != 1:
            raise ValueError(f"topk_group {self.topk_group} without groups "
                             "(n_group 1)")

    @property
    def n_held(self) -> int:
        return self.held_count or self.n_experts

    @property
    def grouped(self) -> bool:
        """Whether the choice is group-limited."""
        return self.n_group > 1

    @property
    def group_size(self) -> int:
        return self.n_experts // self.n_group

    @property
    def held_groups(self) -> range:
        """The groups this process's experts lie in."""
        size = self.group_size
        return range(self.held_first // size,
                     (self.held_first + self.n_held - 1) // size + 1)

    @property
    def n_router(self) -> int:
        """Outputs the router scores: routed, then identity experts."""
        return self.n_experts + self.zero_experts


def n_stats(ex: ExpertsConfig) -> int:
    """Entries of :func:`moe_ffn`'s ``stats`` for this router."""
    return N_STATS + ex.grouped


def keep_groups(biased, ex: ExpertsConfig):
    """``biased`` [N, n_router] (scores plus the correction bias) -> (the
    same with every expert outside the token's ``topk_group`` best groups
    at ``-inf``, the kept groups [N, n_group] bool).  A group scores the
    sum of its two largest ``biased``; of equal scores the lower id wins,
    as in ``top_k``."""
    import jax
    import jax.numpy as jnp

    N = biased.shape[0]
    by_group = biased.reshape(N, ex.n_group, ex.group_size)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(score, ex.topk_group)
    kept = jnp.zeros((N, ex.n_group), bool).at[
        jnp.arange(N)[:, None], best].set(True)
    masked = jnp.where(kept[:, :, None], by_group, -jnp.inf)
    return masked.reshape(N, ex.n_experts), kept


def route(h, lp, ex: ExpertsConfig, kept=None):
    """The router over ALL its outputs: ``h`` [N, D] -> (``idx`` [N, k]
    the chosen experts, ids from ``n_experts`` on being identity experts,
    ``w`` [N, k] float32 their weights, normalised (where the model
    does) over all k chosen wherever they live).  ``kept``, a list,
    receives a group-limited router's kept groups [N, n_group]."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("moe.router"):
        logits = jnp.dot(h.astype(jnp.float32),
                         lp["w_router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits) if ex.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        biased = s + lp["router_bias"].astype(jnp.float32)
        if ex.grouped:
            with jax.named_scope("moe.router.groups"):
                biased, groups = keep_groups(biased, ex)
            if kept is not None:
                kept.append(groups)
        _, idx = jax.lax.top_k(biased, ex.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if ex.norm_topk:
            total = jnp.sum(w, axis=-1, keepdims=True)
            w = w / (total + ex.norm_eps if ex.norm_eps else total)
        return idx, w * ex.scale


def combine(y, order, held, w):
    """Each token's routed outputs, weighted and summed into its row:
    ``y`` [N * k, D] float32 the grouped product's rows in the order
    ``order`` [N * k] sorted the pairs, ``held`` [N, k] whether a held
    expert computed a token's pair, ``w`` [N, k] its weight -> [N, D]
    float32.  A pair that is not held reads as zero, whatever its row
    holds (module docstring)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(COMBINE_SCOPE):
        # the row the sort put each (token, choice) pair in
        P = order.shape[0]
        at = jnp.zeros((P,), jnp.int32).at[order].set(
            jnp.arange(P, dtype=jnp.int32), unique_indices=True)
        at = at.reshape(w.shape)
        terms = [jnp.where(held[:, i, None], y[at[:, i]] * w[:, i, None], 0.0)
                 for i in range(w.shape[1])]
        return sum(terms[1:], terms[0])


def combine_calls(jaxpr) -> int:
    """How many combines a program runs: the runs of consecutive equations
    traced under :data:`COMBINE_SCOPE` in ``jaxpr`` (open or closed), a
    run inside a scan once a turn.  Every branch of a ``cond`` is
    counted; a ``while`` body once."""
    import jax

    n, inside = 0, False
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        here = COMBINE_SCOPE in str(eqn.source_info.name_stack).split("/")
        n += here and not inside
        inside = here
        if not here:
            inner = sum(combine_calls(j)
                        for j in jax.core.jaxprs_in_params(eqn.params))
            n += inner * (eqn.params["length"]
                          if eqn.primitive.name == "scan" else 1)
    return n


def moe_ffn(h, lp, ex: ExpertsConfig, dt, live=None):
    """``h`` [B, T, D] (already normed) -> (this rank's partial FFN
    output [B, T, D], ``stats``).  ``stats`` is int32 [:func:`n_stats`]
    — routed pairs computed by held experts, held experts hit, most pairs
    on one expert, identity pairs, all four over the rows ``live`` [B]
    marks (all when None), and the weight passes the grouped kernel
    made: how many times an expert's three matrices were streamed, over
    ALL rows (a parked row's pairs are computed like any other), 0 where
    ``ragged_dot`` ran;
    passes / experts hit = 1 says every expert hit was streamed once;
    where the choice is group-limited, the live rows whose kept groups
    include one of this rank's (module docstring).  It is what the serve
    loop's ``serve.decode`` span reports."""
    import jax
    import jax.nn as jnn
    import jax.numpy as jnp

    from ..ops.grouped_ffn import grouped_swiglu

    B, T, D = h.shape
    N, k, E = B * T, ex.top_k, ex.n_held
    x = h.reshape(N, D)
    kept = []
    idx, w = route(x, lp, ex, kept)

    with jax.named_scope("moe.experts"):
        # pairs sorted by local expert; an expert held elsewhere sorts
        # last (local id E) and belongs to no group
        local = idx - ex.held_first
        held = (local >= 0) & (local < E)
        local = jnp.where(held, local, E).reshape(N * k)
        order = jnp.argsort(local, stable=True)
        tok = order // k
        counts = jnp.zeros((E + 1,), jnp.int32).at[local].add(1)
        sizes = groups = counts[:E]
        we = {leaf: lp[leaf] for leaf in STACKED_LEAVES}
        if we["we_gate"].ndim == 4:   # the kind's stack: [n, E, ., .]
            n = we["we_gate"].shape[0]
            we = {leaf: a.reshape((n * E,) + a.shape[2:])
                  for leaf, a in we.items()}
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), jnp.int32), sizes,
                (jnp.asarray(lp["_layer"], jnp.int32) * E,))
        # at most E groups own a row, and one is expected to own as many
        # as there are rows to a router output: both static
        y, passes = grouped_swiglu(
            x[tok].astype(dt), *(we[leaf].astype(dt)
                                 for leaf in STACKED_LEAVES), groups,
            live=E, expect=N * k / ex.n_router)
        out = combine(y, order, held, w)

    if ex.shared:
        with jax.named_scope("moe.shared"):
            g = jnn.silu(x.astype(dt) @ lp["ws_gate"].astype(dt))
            u = x.astype(dt) @ lp["ws_up"].astype(dt)
            out = out + ((g * u) @ lp["ws_down"].astype(dt)).astype(
                jnp.float32)
    zero = idx >= ex.n_experts   # [N, k]: the identity pairs
    if ex.zero_experts:
        with jax.named_scope("moe.zero"):
            out = out + x.astype(jnp.float32) * jnp.sum(
                jnp.where(zero, w, 0.0), axis=-1, keepdims=True)

    # what the span reports, over live rows only: a parked slot decodes
    # garbage whose routing nobody asked for
    if live is None:
        lcounts = sizes
    else:
        lrow = jnp.repeat(live, T)[:, None]
        lheld, zero = held & lrow, zero & lrow
        lcounts = jnp.zeros((E + 1,), jnp.int32).at[
            jnp.where(lheld, idx - ex.held_first, E).reshape(N * k)
        ].add(1)[:E]
    counts = [lcounts.sum(), (lcounts > 0).sum(), lcounts.max(), zero.sum(),
              passes]
    if kept:
        g = ex.held_groups
        hits = jnp.any(kept[0][:, g.start:g.stop], axis=-1)
        if live is not None:
            hits = hits & jnp.repeat(live, T)
        counts.append(hits.sum())
    stats = jnp.stack(counts).astype(jnp.int32)
    return out.astype(dt).reshape(B, T, D), stats


def merge_stats(a: Optional[object], b):
    """Sums pairs, experts hit, identity pairs, weight passes and group
    hits, keeps the largest per-expert load."""
    import jax.numpy as jnp

    if a is None:
        return b
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]),
                      a[3] + b[3], a[4] + b[4]]
                     + [a[i] + b[i] for i in range(N_STATS, b.shape[0])])
