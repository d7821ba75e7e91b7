"""Prefill chunks dispatched per decode iteration.  A count, not a time:
the ``serve.prefill_chunk`` span closes right after an asynchronous
dispatch and times only the enqueue."""


def read(obs):
    lo, hi = obs["window_ns"]
    n = {"serve.prefill_chunk": 0, "serve.decode": 0}
    for kind, ts, dur, _a in obs.get("spans", []):
        if kind in n and lo <= ts + dur < hi:
            n[kind] += 1
    if not n["serve.decode"]:
        return None
    return n["serve.prefill_chunk"] / n["serve.decode"]
