"""MobileNet-v1 as the ``stream`` driver meets it: float32 tree in
inference form, the program's classifier registered over it, FLOPs per
frame.  Bound here, computed in ``weights.py``, ``adapter.py`` and
``flops.py``; its reference is ``reference/mobilenet_v1.py``."""

from benchmark import adapter, flops
from benchmark import weights as _weights

ZOO_NAME = "bench_mobilenet_v1"
#: the reference one precision below the bfloat16 compute the
#: configuration states
CONTROL = {"compute": "float8"}

weights = _weights.mobilenet_v1_tree
register = adapter.register_mobilenet_v1
flops_per_frame = flops.mobilenet_v1_flops_per_frame
