"""Mean number of live slots per decode iteration: the ``occupancy`` the
program's ``serve.decode`` ring spans carry, over the window."""


def read(obs):
    lo, hi = obs["window_ns"]
    occ = [a["occupancy"] for kind, ts, dur, a in obs.get("spans", [])
           if kind == "serve.decode" and lo <= ts + dur < hi
           and "occupancy" in a]
    return sum(occ) / len(occ) if occ else None
