"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  One table; an unknown kind is an error."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" system architecture page: 197
#: TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s.
#: ``hbm_bytes`` is the ``bytes_limit`` the runtime reports on that chip
#: (PERF.md, PR 21), which the memory floor is a share of.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16_909_336_064,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per-chip "
                  "specifications)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to benchmark/peaks.py") from None


def mfu_percent(obs: dict) -> "float | None":
    """FLOPs the mathematics needed in the window, over the window and
    the bf16 peak of the chips the cell holds."""
    if not obs.get("peaks") or not obs.get("flops_in_window"):
        return None
    peak = obs["peaks"]["bf16_flops"] * obs["chips"]
    return 100.0 * obs["flops_in_window"] / obs["window_s"] / peak
