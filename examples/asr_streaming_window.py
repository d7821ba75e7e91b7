"""Windowed streaming ASR with DEVICE-RESIDENT aggregator state (ISSUE 10).

The classic nnstreamer audio shape — ``tensor_aggregator`` windows feeding
a speech model — but the window carry lives in HBM between dispatches
(``tensor_aggregator device=true``): each 4000-sample chunk is appended to
the ring IN-PROGRAM (dynamic-update-slice at a traced offset), every
complete 16000-sample window slides out as a device array straight into
the speech filter, and the 75%-overlap advance is a static roll in the
same program.  Zero host round-trips between windows — the host path pays
a full D2H + concatenate + H2D per window.

Exactly 3 programs compile for the aggregator's lifetime (ring init,
append, window+advance; the continuous-serving 3-program discipline), and
``nns-lint --deep`` prices the ring::

    NNS_TPU_HBM_BUDGET=65536 python -m nnstreamer_tpu.tools.lint --deep -v \
        --files examples/asr_streaming_window.py

shows the ``agg ring`` bytes inside the budgeted HBM estimate — CI pins
this via tools/check_tier1.py's MXU gate against tools/asr_deep_baseline.txt.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import nnstreamer_tpu as nt

CHUNK, WINDOW, RATE, CHUNKS = 4000, 16000, 16000, 24

pipe = nt.Pipeline(
    f"audiotestsrc device=true num-buffers={CHUNKS} "
    f"samplesperbuffer={CHUNK} rate={RATE} freq=880 name=src ! "
    f"tensor_aggregator frames_in={CHUNK} frames_out={WINDOW} "
    f"frames_flush={CHUNK} frames_dim=0 device=true name=agg ! "
    "tensor_filter framework=jax model=speech_commands "
    "custom=dtype:float32 name=f ! "
    "tensor_sink name=out",
)
print("residency:", pipe.residency.render())
n_windows = (CHUNKS * CHUNK - WINDOW) // CHUNK + 1
with pipe:
    scores = [np.asarray(pipe.pull("out", timeout=300).tensors[0])
              for _ in range(n_windows)]
    pipe.wait(timeout=120)
print(f"{len(scores)} overlapping windows decoded; "
      f"argmax per window: {[int(s.ravel().argmax()) for s in scores[:8]]}")
