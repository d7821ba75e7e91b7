"""Drives a serve cell: requests in through ``appsrc``, tokens out of the
sink, every instant taken on the benchmark's own clock.

One sender thread and one puller thread.  A closed loop's client sends its
next request when the last token of its previous one was pulled; an open
loop's requests are pushed when they are due and timed from when they were
DUE, so a stall is charged to every request it delays.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import adapter
from benchmark.stats import percentile
from benchmark.traffic import Request, ServeTraffic, rng_for

#: how long after the window's close a request due inside it may take to
#: show its first token before it counts as never answered
LATE_GRACE_S = 60.0


@dataclasses.dataclass
class Served:
    req: Request
    sent: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    fault: Optional[str] = None
    client: Optional[int] = None


class Driver:
    """``ctx.model`` supplies the weights, the registration, the pipeline
    string's model options and the FLOPs; ``ctx.reference`` the gaps."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix = ctx.cfg, ctx.mix
        self.model, self.reference = ctx.model, ctx.reference
        self.traffic = ServeTraffic(self.mix, self.cfg["vocab_size"],
                                    ctx.seed, ctx.seconds)
        self.max_new = self.traffic.max_new
        bs = self.cfg["serve"]["block_size"]
        self.kv_blocks = self.cfg["serve"]["slots"] * math.ceil(
            (self.traffic.max_prompt + self.max_new) / bs)
        self.served: Dict[int, Served] = {}
        self._stop = threading.Event()
        self._closing = threading.Event()
        self._errors: List[Exception] = []
        self._free_clients: "queue.Queue" = queue.Queue()
        self.tree = None
        self.p = None

    # -- set-up -------------------------------------------------------------
    def load(self):
        self.tree = self.model.weights(self.cfg, self.ctx.seed)
        self.model.register(self.model.ZOO_NAME, self.cfg, self.tree)
        self.p = self.pipeline()
        self.p.start()

    def pipeline(self):
        return adapter.serve_pipeline(
            self.model.ZOO_NAME, self.cfg, max_new=self.max_new,
            kv_blocks=self.kv_blocks, traced=self.ctx.trace,
            options=self.model.pipeline_options(self.cfg))

    # -- the two threads ----------------------------------------------------
    def _send(self, req: Request, client: Optional[int] = None) -> Served:
        s = Served(req=req, client=client)
        self.served[req.index] = s
        s.sent = time.perf_counter()
        self.p.push("src", adapter.request_buffer(req.prompt, req.index))
        return s

    def _pull_loop(self):
        try:
            while not self._stop.is_set():
                try:
                    b = self.p.pull("out", timeout=0.2)
                except TimeoutError:
                    continue
                now = time.perf_counter()
                s = self.served[b.meta["bench_req"]]
                tok = int(np.asarray(b.tensors[0]).reshape(-1)[0])
                last = bool(b.meta.get("stream_last"))
                if b.meta.get("stream_aborted"):
                    s.fault = f"aborted: {b.meta.get('abort_reason')!r}"
                elif b.meta["stream_index"] != len(s.tokens):
                    s.fault = (f"index {b.meta['stream_index']} after "
                               f"{len(s.tokens)} tokens")
                elif not 0 <= tok < self.cfg["vocab_size"]:
                    s.fault = f"token id {tok} out of range"
                elif last != (len(s.tokens) + 1 == self.max_new):
                    s.fault = (f"stream_last={last} at token "
                               f"{len(s.tokens) + 1} of {self.max_new}")
                s.tokens.append(tok)
                s.times.append(now)
                if last:
                    s.done = True
                    if s.client is not None:
                        self._free_clients.put(s.client)
        except Exception as e:  # noqa: BLE001 - reported by run()
            self._errors.append(e)
            self._stop.set()

    def _closed_loop(self):
        try:
            prompts = self.traffic.closed_prompts()
            # the ramp: each client starts once the one before it has its
            # first token, so the streams sit at different depths and
            # retire one by one, as clients with answers of their own do;
            # started together, equal-length answers would stay in
            # lockstep and every wave would queue behind one prefill lane
            for c in range(self.traffic.clients):
                s = self._send(next(prompts), client=c)
                while not s.tokens and not self._stop.is_set():
                    time.sleep(0.002)
            while not self._stop.is_set():
                try:
                    c = self._free_clients.get(timeout=0.2)
                except queue.Empty:
                    continue
                if self._closing.is_set():
                    continue
                self._send(next(prompts), client=c)
        except Exception as e:  # noqa: BLE001 - reported by run()
            self._errors.append(e)
            self._stop.set()

    def _open_loop(self, schedule: List[Request], origin: float):
        try:
            for req in schedule:
                due = origin + req.due_s
                while not self._stop.is_set():
                    wait = due - time.perf_counter()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05))
                if self._stop.is_set():
                    return
                self._send(req)
        except Exception as e:  # noqa: BLE001 - reported by run()
            self._errors.append(e)
            self._stop.set()

    def _check_alive(self):
        if self._errors:
            raise self._errors[0]

    def _wait(self, cond, timeout: float, what: str):
        deadline = time.perf_counter() + timeout
        while not cond():
            self._check_alive()
            if time.perf_counter() > deadline:
                raise TimeoutError(f"{what} not reached in {timeout:.0f}s")
            time.sleep(0.01)

    # -- one run ------------------------------------------------------------
    def run(self) -> dict:
        ctx = self.ctx
        puller = threading.Thread(target=self._pull_loop, name="bench-pull",
                                  daemon=True)
        puller.start()
        closed = self.traffic.mode == "closed"
        if closed:
            sender = threading.Thread(target=self._closed_loop,
                                      name="bench-send", daemon=True)
            sender.start()
            n = self.traffic.clients
            # the window opens with every client's stream live: the ramp
            # (first compile, one admission per iteration) is set-up
            self._wait(lambda: sum(1 for s in list(self.served.values())
                                   if len(s.tokens) >= 2) >= n,
                       ctx.first_run_budget_s, "every client streaming")
            w0 = time.perf_counter()
        else:
            # warm the three programs before the schedule starts, so the
            # ramp runs on time
            warm = [Request(-1 - i, None, np.full(
                (self.mix["prompt_len"]["min"] + 40 * i,), 1 + i, np.int32))
                for i in range(2)]
            for r in warm:
                self._send(r)
            self._wait(lambda: all(self.served[r.index].done for r in warm),
                       ctx.first_run_budget_s, "warm-up requests")
            schedule = self.traffic.schedule()
            origin = time.perf_counter() + self.traffic.ramp_seconds + 0.05
            sender = threading.Thread(target=self._open_loop,
                                      args=(schedule, origin),
                                      name="bench-send", daemon=True)
            sender.start()
            self._wait(lambda: time.perf_counter() >= origin,
                       self.traffic.ramp_seconds + 5, "the ramp")
            w0 = origin
        ctx.window_opens(w0)
        w1 = w0 + ctx.seconds
        while time.perf_counter() < w1:
            self._check_alive()
            time.sleep(0.02)
        ctx.window_closes(w1)
        self._closing.set()

        # requests due (open) or sent (closed) inside the window
        def stamp(s: Served):
            return s.sent if closed else w0 + s.req.due_s

        def in_window():
            return [s for s in list(self.served.values())
                    if s.req.index >= 0 and s.sent is not None
                    and w0 <= stamp(s) < w1]

        if not closed:
            self._wait(lambda: len(self.served) >= len(schedule) + 2
                       or time.perf_counter() > w1 + 1.0, 5.0,
                       "the sender's last push")
        deadline = w1 + LATE_GRACE_S
        while time.perf_counter() < deadline and any(
                not s.tokens and not s.fault for s in in_window()):
            self._check_alive()
            time.sleep(0.01)
        mem_peak = ctx.memory_peak()
        self._stop.set()
        sender.join(timeout=10)
        puller.join(timeout=10)
        self._check_alive()
        spans = adapter.ring_spans() if ctx.trace else []
        steps_per_call = int(getattr(self.p.element("f").fw, "chunk", 0))
        self.p.stop()
        self.p = None

        everything = [s for s in self.served.values() if s.req.index >= 0]
        mine = in_window()
        # a request never answered is censored at the end of the grace
        ttft = [((s.times[0] if s.tokens and not s.fault else deadline)
                 - stamp(s)) * 1e3 for s in mine]
        gaps, out_tokens, late = [], 0, []
        flop_sum, decoded = 0.0, []   # decoded: (pulled at, context)
        for s in everything:
            T = len(s.req.prompt)
            for i, t in enumerate(s.times):
                if not w0 <= t < w1:
                    continue
                out_tokens += 1
                if i == 0:
                    # its prompt was prefilled just before: charge it here
                    flop_sum += sum(self.model.flops_per_token(
                        self.cfg, j + 1) for j in range(T))
                else:
                    gaps.append((t - s.times[i - 1]) * 1e3)
                    flop_sum += self.model.flops_per_token(self.cfg, T + i)
                    decoded.append((t, T + i))
            if not closed and s.sent is not None and \
                    w0 <= w0 + s.req.due_s < w1:
                late.append((s.sent - (w0 + s.req.due_s)) * 1e3)
        failed = sum(1 for s in mine if s.fault or not s.tokens)
        faults = [f"req {s.req.index}: {s.fault}" for s in everything
                  if s.fault]

        e2e = {
            "serve_tok_s": out_tokens / ctx.seconds,
            "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
            "itl_p95_ms": percentile(gaps, 95) if gaps else None,
        }
        observed = {
            "cfg": self.cfg, "window_s": ctx.seconds,
            "flops_in_window": flop_sum, "decoded": decoded,
            "late_ms": late, "spans": spans, "window": ctx.window,
            "window_ns": ctx.window_ns,
            "decode_steps_per_call": steps_per_call,
        }
        checks = self.check(everything, w1, faults)
        return {"attempted": len(mine), "failed": failed, "end_to_end": e2e,
                "observed": observed, "checks": checks,
                "memory_peak_bytes": mem_peak}

    # -- correct ------------------------------------------------------------
    def sample_finished(self, everything: List[Served], w1: float):
        """The requests the reference replays: drawn from the seed among
        those the window finished, the longest always among them."""
        fin = sorted((s for s in everything
                      if s.done and not s.fault and s.times[-1] < w1),
                     key=lambda s: s.req.index)
        if not fin:
            fin = sorted((s for s in everything if s.done and not s.fault),
                         key=lambda s: s.req.index)
        if not fin:
            return []
        n = min(int(self.mix["check_requests"]), len(fin))
        longest = max(fin, key=lambda s: (len(s.req.prompt) + len(s.tokens),
                                          -s.req.index))
        rest = [s for s in fin if s is not longest]
        rng_for(self.ctx.seed, "check").shuffle(rest)
        return [longest] + rest[:n - 1]

    def token_matrix(self, sample: List[Served]):
        """Prompt + served tokens of each sampled request, padded to the
        one shape the reference is compiled for, and the mask of positions
        whose NEXT token was served."""
        width = self.traffic.max_prompt + self.max_new
        toks = np.zeros((int(self.mix["check_requests"]), width), np.int32)
        mask = np.zeros(toks.shape, bool)
        for b, s in enumerate(sample):
            T, n = len(s.req.prompt), len(s.tokens)
            toks[b, :T] = s.req.prompt
            toks[b, T:T + n] = s.tokens
            mask[b, T - 1:T + n - 1] = True
        return toks, mask

    def check(self, everything, w1, faults) -> list:
        """Each number compared, beside its limit."""
        limits = self.ctx.limits
        sample = self.sample_finished(everything, w1)
        checks = [("stream_faults", len(faults), limits["stream_faults"]),
                  ("compiles_in_window", self.ctx.compiles_in_window(),
                   limits["compiles_in_window"])]
        for f in faults[:5]:
            self.ctx.note(f)
        if not sample:
            checks.append(("nothing_to_compare", 1, 0))
            return checks
        toks, mask = self.token_matrix(sample)
        self._compared = (toks, mask)
        gap, _ = self.reference.served_gaps(self.tree, toks, self.cfg)
        gap = np.asarray(gap)
        self.ctx.note(f"reference replayed {len(sample)} requests, "
                      f"{int(mask.sum())} served tokens; exact agreement "
                      f"{float((gap[mask] <= 0).mean()):.4f}")
        checks.append(("logit_gap_max", float(gap[mask].max()),
                       limits["logit_gap_max"]))
        return checks

    def close(self):
        """Frees the weights (for a process that reads several seeds)."""
        adapter.forget(self.model.ZOO_NAME)
        self.tree = self._compared = None

    def control_reading(self) -> dict:
        """The control on the prompts and tokens the last run compared:
        the widest gap of the token that the lower precision (int4 weights
        for int8) puts first."""
        toks, mask = self._compared
        gap, _ = self.reference.control_gaps(self.tree, toks, self.cfg,
                                             **self.model.CONTROL)
        gap = np.asarray(gap)[mask]
        return {"logit_gap_max": float(gap.max()),
                "exact_agreement": float((gap <= 0).mean())}
