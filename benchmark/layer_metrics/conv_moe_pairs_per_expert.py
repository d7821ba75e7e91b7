"""Mean routed (token, expert) pairs an expert computes a decode step a
sparse layer where every expert is held: ``moe_pairs`` of the program's
``serve.decode`` spans (summed there over the chunk's steps and the
sparse layers, live rows only) over steps x sparse layers x experts.  It
says whether the load the cell is sized for is met: 64 rows x 4 choices /
64 experts = 4.0 at full occupancy."""

from benchmark.models import conv_moe_decoder as model


def read(obs):
    lo, hi = obs["window_ns"]
    cfg = obs["cfg"]
    per_step = [a["moe_pairs"] / a["chunk"]
                for kind, ts, dur, a in obs.get("spans", [])
                if kind == "serve.decode" and lo <= ts + dur < hi
                and "moe_pairs" in a and a.get("chunk")]
    if not per_step:
        return None
    return (sum(per_step) / len(per_step)
            / (model.n_expert_layers(cfg) * model.held_experts(cfg)))
