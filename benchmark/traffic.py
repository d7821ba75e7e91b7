"""One generator for every traffic mix: a mix is a file of parameters.

Every seed gets the SAME multiset of lengths and of gaps between arrivals
— the distribution's own quantiles — in another order, so two seeds offer
the same work and differ only in what meets what.  Token ids and frames
are drawn from the seed.

A serve mix's file:

    arrival     {"mode": "closed", "clients": N}
                {"mode": "poisson", "rate_per_s": R, "ramp_seconds": S}
                {"mode": "bursty", "rate_per_s": R, "burst_size": K,
                 "intra_gap_s": G, "ramp_seconds": S}
    prompt_len  {"dist": "uniform" | "lognormal" | "fixed", ...,
                 "min": a, "max": b}
    shared_prefix (optional) {"tokens": T, "groups": G}
    max_new, check_requests

A stream mix's file: ``batch``, ``max_inflight``, ``sink_buffers``,
``pool_batches``, ``feed`` ("host" | "device"), ``check_rows``.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, for any whole-number seed."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
             *stream.encode()]
    return np.random.default_rng(np.random.SeedSequence(words))


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number."""
    return int(rng_for(seed, stream).integers(0, 2**31 - 1))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles, ascending."""
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    q = _quantiles(n)
    if dist == "fixed":
        vals = np.full(n, float(spec.get("value", lo)))
    elif dist == "uniform":
        vals = lo + q * (hi + 1 - lo) - 0.5
    elif dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in q])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def stratified_gaps(n: int, mean: float) -> np.ndarray:
    """``n`` exponential gaps at the quantiles, rescaled to the mean."""
    g = -np.log1p(-_quantiles(n))
    return g * (mean / g.mean())


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]      # relative to the window's start; None = closed
    prompt: np.ndarray          # int32 [T]


class ServeTraffic:
    """The requests of one run of a serve mix."""

    #: lengths drawn per cycle of a closed loop (a multiple of every
    #: sensible client count, so each cycle holds the whole multiset)
    CLOSED_CYCLE = 256

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.arrival = dict(mix["arrival"])
        self.mode = self.arrival["mode"]
        self.max_new = int(mix["max_new"])
        self.max_prompt = int(mix["prompt_len"]["max"])
        sp = mix.get("shared_prefix")
        self._prefixes: List[np.ndarray] = []
        if sp:
            prng = rng_for(seed, "prefix")
            self._prefixes = [
                prng.integers(1, self.vocab, int(sp["tokens"]),
                              dtype=np.int32)
                for _ in range(int(sp.get("groups", 1)))]
            self.max_prompt += int(sp["tokens"])
        self._ids = rng_for(seed, "ids")
        self._order = rng_for(seed, "order")

    # -- what the deployment must be sized for -----------------------------
    @property
    def clients(self) -> int:
        return int(self.arrival.get("clients", 0))

    @property
    def ramp_seconds(self) -> float:
        return float(self.arrival.get("ramp_seconds", 0.0))

    def _prompt(self, index: int, length: int) -> np.ndarray:
        body = self._ids.integers(1, self.vocab, int(length), dtype=np.int32)
        if self._prefixes:
            pre = self._prefixes[index % len(self._prefixes)]
            return np.concatenate([pre, body])
        return body

    # -- open loop ----------------------------------------------------------
    def schedule(self) -> List[Request]:
        """Every request of an open-loop run with the instant it is due,
        from ``-ramp_seconds`` (load that fills the system before the
        window, counted as set-up) to the window's end."""
        if self.mode == "closed":
            raise ValueError("a closed loop has no schedule")
        rate = float(self.arrival["rate_per_s"])
        span = self.ramp_seconds + self.seconds
        if self.mode == "poisson":
            n = max(1, round(rate * span))
            gaps = stratified_gaps(n, span / n)
            self._order.shuffle(gaps)
            due = np.cumsum(gaps) - gaps[0] * 0.5
        elif self.mode == "bursty":
            k = int(self.arrival["burst_size"])
            intra = float(self.arrival.get("intra_gap_s", 0.0))
            nb = max(1, round(rate * span / k))
            gaps = stratified_gaps(nb, span / nb)
            self._order.shuffle(gaps)
            starts = np.cumsum(gaps) - gaps[0] * 0.5
            due = (starts[:, None] + intra * np.arange(k)[None, :]).ravel()
            due = np.sort(due[due < span])
        else:
            raise ValueError(f"unknown arrival mode {self.mode!r}")
        lens = stratified_lengths(self.mix["prompt_len"], len(due))
        self._order.shuffle(lens)
        return [Request(i, float(t) - self.ramp_seconds,
                        self._prompt(i, int(ln)))
                for i, (t, ln) in enumerate(zip(due, lens))]

    # -- closed loop --------------------------------------------------------
    def closed_prompts(self) -> Iterator[Request]:
        """An endless stream of requests for a closed loop's clients:
        cycles of the whole multiset of lengths, each cycle reshuffled."""
        if self.mode != "closed":
            raise ValueError("only a closed loop draws prompts on demand")
        index = 0
        while True:
            lens = stratified_lengths(self.mix["prompt_len"],
                                      self.CLOSED_CYCLE)
            self._order.shuffle(lens)
            for ln in lens:
                yield Request(index, None, self._prompt(index, int(ln)))
                index += 1


def stream_frames(mix: dict, size: int, seed: int) -> List[np.ndarray]:
    """The pool of uint8 ``[batch, size, size, 3]`` batches a stream run
    cycles through.  Each frame has a tint of its own and a coarse pattern
    around it (a grid of ``size // 32`` cells a side, blown up): pixel
    noise alone averages out in the classifier's pooling, and every frame
    would get the same label and nearly the same score."""
    rng = rng_for(seed, "frames")
    batch = int(mix["batch"])
    cells = max(2, size // 32)
    rep = -(-size // cells)
    pool = []
    for _ in range(int(mix["pool_batches"])):
        tint = rng.integers(0, 256, (batch, 1, 1, 3))
        coarse = np.clip(tint + rng.integers(-64, 65,
                                             (batch, cells, cells, 3)),
                         0, 255).astype(np.uint8)
        x = coarse.repeat(rep, axis=1).repeat(rep, axis=2)
        pool.append(np.ascontiguousarray(x[:, :size, :size]))
    return pool
