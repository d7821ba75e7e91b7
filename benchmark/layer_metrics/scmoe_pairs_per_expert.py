"""Mean routed (token, expert) pairs a held expert computes a decode step
an expert layer: ``moe_pairs`` of the program's ``serve.decode`` spans
(summed there over the chunk's steps and the expert layers, live rows
only, identity pairs not among them) over steps x expert layers x experts
held.  It says how near the batch is to the load the deployment's experts
see: 64 rows x 12 choices x 512/768 real / 512 routed experts = 1.0 where
the router spreads evenly."""

from benchmark.models import scmoe_latent_decoder as model


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
            / (model.n_expert_layers(cfg) * cfg["n_routed_experts"]))
