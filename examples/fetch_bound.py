"""A deliberately FETCH-BOUND pipeline: full-resolution segmentation overlay.

The overlay decode pins full output geometry (RGBA media), so the
HBM-residency planner cannot select deeplab's native-stride reduced output
— every frame ships its full-resolution class map over the D2H link,
256x the bytes of the native-stride classmap.  ``nns-lint --deep``
flags it statically when a calibrated link is configured::

    NNS_TPU_LINK_D2H_MBPS=38.2 NNS_TPU_LINK_RTT_MS=88 \
        python -m nnstreamer_tpu.tools.lint --deep -v \
        --files examples/fetch_bound.py

emitting the ``fetch-bound`` diagnostic: planned D2H per buffer exceeds
the device stages' HBM-roofline compute floor, so no dispatch overlap can
hide the link.  The fix is in the warning text: a geometry-agnostic sink
payload (``option1=classmap`` lets the planner pick the native-stride
map) — see docs/FETCH.md.  CI pins this via tools/check_tier1.py's fetch
gate against tools/fetch_deep_baseline.txt.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import nnstreamer_tpu as nt

BATCH, SIZE, NUM = 8, 224, 32

pipe = nt.Pipeline(
    f"videotestsrc device=true batch={BATCH} num-buffers={NUM} "
    f"width={SIZE} height={SIZE} pattern=smpte name=src ! "
    "tensor_transform mode=arithmetic option=typecast:float32,div:255.0 ! "
    f"tensor_filter framework=jax model=deeplab_mobilenet "
    f"custom=size:{SIZE},batch:{BATCH} name=f ! "
    "tensor_decoder mode=image_segment ! tensor_sink name=out",
)
print("residency:", pipe.residency.render())
with pipe:
    buf = pipe.pull("out", timeout=300)
    pipe.wait(timeout=120)
print("overlay:", np.asarray(buf.tensors[0]).shape)
