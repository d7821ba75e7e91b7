"""Share of the live rows whose group-limited router kept a group of this
rank's experts: ``moe_group_hits`` of the program's ``serve.decode``
spans (live rows that kept such a group, over the chunk's steps and the
expert layers) over ALL live rows of those steps and layers, ``occupancy
x chunk x expert layers`` (``n_expert_layers`` of the model resolved
through the configuration's ``model``).  Under expert parallelism it is
the share of rows whose dispatch may reach this rank: ``topk_group /
n_group`` = 4 / 8 = 50 % where the router spreads evenly.  A program
whose spans lack the count gives nothing to read."""

from benchmark.layer_metrics.grouped_moe_expert_roofline import model_of


def read(obs):
    lo, hi = obs["window_ns"]
    hits = rows = 0
    for kind, ts, dur, a in obs.get("spans", []):
        if kind == "serve.decode" and lo <= ts + dur < hi \
                and "moe_group_hits" in a and a.get("chunk") \
                and a.get("occupancy"):
            hits += a["moe_group_hits"]
            rows += a["occupancy"] * a["chunk"]
    if not rows:
        return None
    cfg = obs["cfg"]
    return 100.0 * hits / (rows * model_of(cfg).n_expert_layers(cfg))
