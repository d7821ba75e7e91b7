"""The dense causal decoder (Mistral-7B's family) as the ``serve`` driver
meets it: weight-only int8 tree, the program's decoder registered over
it, the quantization the pipeline string states, FLOPs per token.  Bound
here, computed in ``weights.py``, ``adapter.py`` and ``flops.py``; its
reference is ``reference/dense_decoder.py``."""

from benchmark import adapter, flops
from benchmark import weights as _weights

ZOO_NAME = "bench_decoder"
#: the reference one precision below the int8 weights the family states
CONTROL = {"weight_bits": 4}

weights = _weights.decoder_tree
register = adapter.register_decoder
flops_per_token = flops.decoder_flops_per_token


def pipeline_options(cfg: dict) -> list:
    """What the pipeline string says of the model beyond the deployment's
    sizes: the weight quantization, as the configuration states it."""
    return [f"quant:{cfg['precision']['weights']}"]
