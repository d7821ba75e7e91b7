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
"""

from __future__ import annotations

import dataclasses
from typing import Optional


#: leaves :func:`moe_ffn` takes as the kind's whole stack (see above)
STACKED_LEAVES = ("we_gate", "we_up", "we_down")
#: entries of :func:`moe_ffn`'s ``stats``
N_STATS = 5


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

    @property
    def n_held(self) -> int:
        return self.held_count or self.n_experts

    @property
    def n_router(self) -> int:
        """Outputs the router scores: routed, then identity experts."""
        return self.n_experts + self.zero_experts


def route(h, lp, ex: ExpertsConfig):
    """The router over ALL its outputs: ``h`` [N, D] -> (``idx`` [N, k]
    the chosen experts, ids from ``n_experts`` on being identity experts,
    ``w`` [N, k] float32 their weights, normalised (where the model
    does) over all k chosen wherever they live)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("moe.router"):
        logits = jnp.dot(h.astype(jnp.float32),
                         lp["w_router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits) if ex.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                               ex.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if ex.norm_topk:
            total = jnp.sum(w, axis=-1, keepdims=True)
            w = w / (total + ex.norm_eps if ex.norm_eps else total)
        return idx, w * ex.scale


def moe_ffn(h, lp, ex: ExpertsConfig, dt, live=None):
    """``h`` [B, T, D] (already normed) -> (this rank's partial FFN
    output [B, T, D], ``stats``).  ``stats`` is int32 [5] — routed pairs
    computed by held experts, held experts hit, most pairs on one expert,
    identity pairs, all four over the rows ``live`` [B] marks (all when
    None), and the weight passes the grouped kernel made: how many times
    an expert's three matrices were streamed, over ALL rows (a parked
    row's pairs are computed like any other), 0 where ``ragged_dot`` ran;
    passes / experts hit = 1 says every expert hit was streamed once.
    It is what the serve loop's ``serve.decode`` span reports."""
    import jax
    import jax.nn as jnn
    import jax.numpy as jnp

    from ..ops.grouped_ffn import grouped_swiglu

    B, T, D = h.shape
    N, k, E = B * T, ex.top_k, ex.n_held
    x = h.reshape(N, D)
    idx, w = route(x, lp, ex)

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
        # rows past the groups belong to no expert: whatever the grouped
        # product left there is not read
        hs = held.reshape(N * k)[order]
        wp = w.reshape(N * k)[order]
        routed = jnp.zeros((N, D), jnp.float32).at[tok].add(
            jnp.where(hs[:, None], y * wp[:, None], 0.0))

    out = routed
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
    stats = jnp.stack([lcounts.sum(), (lcounts > 0).sum(), lcounts.max(),
                       zero.sum(), passes]).astype(jnp.int32)
    return out.astype(dt).reshape(B, T, D), stats


def merge_stats(a: Optional[object], b):
    """Sums pairs, experts hit, identity pairs and weight passes, keeps
    the largest per-expert load."""
    import jax.numpy as jnp

    if a is None:
        return b
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]),
                      a[3] + b[3], a[4] + b[4]])
