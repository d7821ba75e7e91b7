"""Share of the live rows' router choices that fell on identity
(zero-compute) experts: ``moe_zero_pairs`` of the program's
``serve.decode`` spans (identity pairs over the chunk's steps, the expert
layers and the live rows) over ALL pairs of those rows, ``occupancy x
chunk x moe_topk x expert layers``.  256 of the router's 768 outputs are
identity experts, so 33.3 % where it spreads evenly; it is the knob by
which the compute a token costs varies (a token computes between 0 and
``moe_topk`` real experts).  A program whose spans lack the count gives
nothing to read."""

from benchmark.models import scmoe_latent_decoder as model


def read(obs):
    lo, hi = obs["window_ns"]
    cfg = obs["cfg"]
    zero = pairs = 0
    for kind, ts, dur, a in obs.get("spans", []):
        if kind == "serve.decode" and lo <= ts + dur < hi \
                and "moe_zero_pairs" in a and a.get("chunk") \
                and a.get("occupancy"):
            zero += a["moe_zero_pairs"]
            pairs += (a["occupancy"] * a["chunk"] * cfg["moe_topk"]
                      * model.n_expert_layers(cfg))
    return 100.0 * zero / pairs if pairs else None
