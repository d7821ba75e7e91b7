"""A toy architecture the benchmark does not know, for its tests: the
dense decoder with a SECOND SwiGLU branch of the same width beside the
first in every block,

    x + down(silu(gate h) * up h) + down2(silu(gate2 h) * up2 h),

which ``reference/dense_decoder.py`` cannot compute.  ``add_toy_cells``
writes this file and its reference into a copy of the benchmark as
``models/`` and ``reference/two_branch_decoder.py``: an architecture
enters as new files."""

from benchmark import adapter, flops
from benchmark import weights as _weights

ZOO_NAME = "bench_two_branch"
CONTROL = {"weight_bits": 4}
BRANCH_2 = {"w_gate2": "w_gate", "w_up2": "w_up", "w_down2": "w_down"}


def weights(cfg: dict, seed: int):
    """The dense tree, and the second branch's matrices from the next
    seed's; ``w_down2`` is scaled by ``w_down_s``, so whoever holds the
    two down matrices as one ``[2F, D]`` matrix needs one scale."""
    tree = _weights.decoder_tree(cfg, seed)
    other = _weights.decoder_tree(cfg, seed + 1)["layers"]
    layers = dict(tree["layers"])
    for new, old in BRANCH_2.items():
        layers[new + "_q"] = other[old + "_q"]
        if new != "w_down2":
            layers[new + "_s"] = other[old + "_s"]
    return dict(tree, layers=layers)


def register(name: str, cfg: dict, tree) -> None:
    """The program's decoder computes the two branches as one SwiGLU of
    twice the width: gate and up side by side, the down matrices stacked."""
    import jax.numpy as jnp

    lp = tree["layers"]
    layers = {k: v for k, v in lp.items() if k[:-2] not in BRANCH_2}
    for new, old in BRANCH_2.items():
        # stacked leaves are [L, in, out]; down's shared dimension is `in`
        axis = 1 if new == "w_down2" else 2
        layers[old + "_q"] = jnp.concatenate(
            [lp[old + "_q"], lp[new + "_q"]], axis=axis)
        if new != "w_down2":
            layers[old + "_s"] = jnp.concatenate(
                [lp[old + "_s"], lp[new + "_s"]], axis=2)
    adapter.register_decoder(name, _doubled(cfg), dict(tree, layers=layers))


def pipeline_options(cfg: dict) -> list:
    return [f"quant:{cfg['precision']['weights']}"]


def flops_per_token(cfg: dict, context: float) -> float:
    return flops.decoder_flops_per_token(_doubled(cfg), context)


def _doubled(cfg: dict) -> dict:
    return dict(cfg, intermediate_size=2 * cfg["intermediate_size"])
