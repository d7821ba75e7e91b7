"""Mean routed (token, expert) pairs a held expert computes a decode step
an expert layer, for any model whose module names ``n_expert_layers`` and
``held_experts`` (resolved through the configuration's ``model``):
``moe_pairs`` of the program's ``serve.decode`` spans (summed there over
the chunk's steps and the expert layers, live rows only) over steps x
expert layers x experts held.  It says how near the batch is to the load
the deployment's experts see: 64 rows x 8 choices x 12 held / 192 routed
/ 12 = 2.67 for the A.X-K1 share, where the router spreads evenly."""

from benchmark.layer_metrics.grouped_moe_expert_roofline import model_of


def read(obs):
    lo, hi = obs["window_ns"]
    cfg = obs["cfg"]
    per_step = [a["moe_pairs"] / a["chunk"]
                for kind, ts, dur, a in obs.get("spans", [])
                if kind == "serve.decode" and lo <= ts + dur < hi
                and "moe_pairs" in a and a.get("chunk")]
    if not per_step:
        return None
    model = model_of(cfg)
    return (sum(per_step) / len(per_step)
            / (model.n_expert_layers(cfg) * model.held_experts(cfg)))
