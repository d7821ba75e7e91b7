"""Drives a stream cell: a pusher thread feeds batches from a small pool
as fast as admission allows, the calling thread pulls the labelled
results; every instant is taken on the benchmark's own clock.
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from benchmark import adapter
from benchmark.stats import percentile
from benchmark.traffic import rng_for, stream_frames

WARM_BATCHES = 2


class Driver:
    """``ctx.model`` supplies the weights, the registration and the
    FLOPs; ``ctx.reference`` the logits."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.cfg, ctx.mix
        self.model, self.reference = ctx.model, ctx.reference
        self.batch = int(self.mix["batch"])
        self._stop = threading.Event()
        self._errors: List[Exception] = []
        self.pushed: List[tuple] = []   # (pool index, admitted at)
        self.tree = None
        self.pool = None
        self.p = None

    def load(self):
        self.tree = self.model.weights(self.cfg, self.ctx.seed)
        self.model.register(self.model.ZOO_NAME, self.cfg, self.tree)
        self.p = self.pipeline()
        self.pool = stream_frames(self.mix, self.cfg["image_size"],
                                  self.ctx.seed)
        self.p.start()

    def pipeline(self):
        return adapter.stream_pipeline(self.model.ZOO_NAME, self.cfg,
                                       self.mix)

    def _push_loop(self):
        try:
            i = 0
            while not self._stop.is_set():
                k = i % len(self.pool)
                self.p.push("src", self.pool[k])
                self.pushed.append((k, time.perf_counter()))
                i += 1
        except Exception as e:  # noqa: BLE001 - reported by run()
            # "appsrc stopping" is how the loop ends once run() has stopped
            if not (isinstance(e, RuntimeError) and self._stop.is_set()):
                self._errors.append(e)
                self._stop.set()

    def run(self) -> dict:
        ctx = self.ctx
        pusher = threading.Thread(target=self._push_loop, name="bench-push",
                                  daemon=True)
        pusher.start()
        pulled = []   # (pulled at, label ids)

        def pull():
            b = self.p.pull("out", timeout=ctx.first_run_budget_s)
            if self._errors:
                raise self._errors[0]
            pulled.append((time.perf_counter(),
                           np.asarray(b.meta["label_index"]),
                           np.asarray(b.meta["score"], np.float32)))

        for _ in range(WARM_BATCHES):
            pull()
        w0 = time.perf_counter()
        ctx.window_opens(w0)
        w1 = w0 + ctx.seconds
        while time.perf_counter() < w1:
            pull()
        ctx.window_closes(w1)
        mem_peak = ctx.memory_peak()
        self._stop.set()
        self.p.stop()
        self.p = None
        pusher.join(timeout=30)
        if self._errors:
            raise self._errors[0]

        # answer i belongs to push i: one source, one sink, FIFO
        inside = [i for i, a in enumerate(pulled) if w0 <= a[0] < w1]
        lat = [(pulled[i][0] - self.pushed[i][1]) * 1e3 for i in inside]
        frames = len(inside) * self.batch
        e2e = {"stream_fps": frames / ctx.seconds}
        observed = {
            "cfg": self.cfg, "window_s": ctx.seconds,
            "flops_in_window": frames
            * self.model.flops_per_frame(self.cfg),
            "batch_ms": lat, "spans": [],
            "window_ns": ctx.window_ns,
        }
        good = [i for i in inside if pulled[i][1].shape == (self.batch,)
                and pulled[i][2].shape == (self.batch,)]
        bad_shape = len(inside) - len(good)
        checks = self.check([(self.pushed[i][0], pulled[i][1], pulled[i][2])
                             for i in good], bad_shape)
        return {"attempted": len(inside), "failed": bad_shape,
                "end_to_end": e2e, "observed": observed, "checks": checks,
                "memory_peak_bytes": mem_peak}

    def check(self, answers, bad_shape: int) -> list:
        """Every answer of the window against the float32 reference's
        logits for the frames it labels, on rows drawn from the seed.

        ``score_err_within``: the score an answer carries is its class's
        logit; its signed distance from the reference's logit for that
        class is taken over all rows of all answers, each class's mean
        distance is taken off, and the number is the standard deviation
        of what is left, as a share of the mean logit range.  The spread,
        not the mean: random weights give every class of every seed an
        offset of its own, common to all the frames it labels (up to
        0.3 % of the range in sound runs, as large as the control's),
        while the frame-to-frame part is what the precision sets.  Taken
        over all classes at once, as until PR 28, the spread also holds
        the distance between the classes' offsets: 0.00293 on a seed whose
        frames split between two large classes, 0.00103 inside them
        (PERF.md section 6, PR 28)."""
        limits = self.ctx.limits
        checks = [("answers_malformed", bad_shape,
                   limits["answers_malformed"]),
                  ("compiles_in_window", self.ctx.compiles_in_window(),
                   limits["compiles_in_window"])]
        if not answers:
            checks.append(("nothing_to_compare", 1, 0))
            return checks
        rows = np.sort(rng_for(self.ctx.seed, "check").choice(
            self.batch, min(int(self.mix["check_rows"]), self.batch),
            replace=False))
        ref = [self.reference.logits_in_blocks(self.tree, frames[rows])
               for frames in self.pool]
        self._compared = (rows, ref)
        gap_w, signed, answered, agree = 0.0, [], [], 0
        for k, ids, scores in answers:
            gap, err = compare(ref[k], ids[rows], scores[rows])
            gap_w = max(gap_w, float(gap.max()))
            signed.append(err)
            answered.append(ids[rows])
            agree += int((gap <= 0).sum())
        signed = np.concatenate(signed)
        within = within_classes(signed, np.concatenate(answered))
        classes = len({int(c) for lg in ref for c in lg.argmax(axis=1)})
        self.ctx.note(f"reference labelled {len(rows)} rows of "
                      f"{len(self.pool)} batches into {classes} classes; "
                      f"{len(answers)} answers compared; exact agreement "
                      f"{agree / len(signed):.4f}; widest label gap "
                      f"{gap_w:.5f}, mean score offset "
                      f"{float(signed.mean()):+.5f} and score spread over "
                      f"all classes {_finite(signed.std()):.5f} of the "
                      f"logit range")
        checks.append(("score_err_within", _finite(within.std()),
                       limits["score_err_within"]))
        return checks

    def close(self):
        """Frees the weights (for a process that reads several seeds)."""
        adapter.forget(self.model.ZOO_NAME)
        self.tree = self._compared = None

    def control_reading(self) -> dict:
        """The control on the rows the last run compared: the answers
        the lower precision (float8 compute for bfloat16) would give."""
        rows, ref = self._compared
        gap_w, signed, answered = 0.0, [], []
        for frames, lg in zip(self.pool, ref):
            low = self.reference.logits_in_blocks(
                self.tree, frames[rows], **self.model.CONTROL)
            ids = low.argmax(axis=1)
            gap, err = compare(lg, ids, low[np.arange(len(ids)), ids])
            gap_w = max(gap_w, float(gap.max()))
            signed.append(err)
            answered.append(ids)
        signed = np.concatenate(signed)
        within = within_classes(signed, np.concatenate(answered))
        return {"score_err_within": _finite(within.std()),
                "score_err_spread": _finite(signed.std()),
                "label_gap_max": gap_w,
                "score_offset": float(signed.mean())}


def _finite(x) -> float:
    return float(x) if np.isfinite(x) else 1e9


def within_classes(err, ids):
    """``err`` with the mean over the rows of each answered class taken
    off; a class with an infinite distance in it stays not finite."""
    out = np.array(err, np.float64)
    with np.errstate(invalid="ignore"):
        for c in np.unique(ids):
            rows = ids == c
            out[rows] -= out[rows].mean()
    return out


def compare(ref_logits, ids, scores):
    """Per row: the chosen class's gap below the reference's best, and the
    score's signed distance from the reference's logit for that class,
    both as shares of the rows' mean logit range; an impossible class id
    or score reads infinite."""
    n = len(ids)
    ok = (ids >= 0) & (ids < ref_logits.shape[1])
    safe = np.where(ok, ids, 0)
    span = float((ref_logits.max(axis=1) - ref_logits.min(axis=1)).mean())
    at = ref_logits[np.arange(n), safe]
    gap = np.where(ok, (ref_logits.max(axis=1) - at) / span, np.inf)
    err = np.where(ok & np.isfinite(scores), (scores - at) / span, np.inf)
    return gap, err
