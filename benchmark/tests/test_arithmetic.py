"""Hand-worked numbers for the FLOP and byte functions, the peaks, the
percentile and the trace reduction."""

import json
import os
import sys

import pytest

from benchmark import flops, peaks, stats, trace

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_mistral_7b_bytes_and_flops():
    cfg = _cfg("mistral_7b")
    per_layer = (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    assert per_layer == 218_103_808
    assert flops.decoder_matmul_params(cfg) == 32 * per_layer + 4096 * 32000
    assert flops.decoder_matmul_params(cfg) == 7_110_393_856   # 7.11 G
    assert flops.decoder_weight_bytes(cfg) == 7_110_393_856.0  # int8
    assert flops.kv_bytes_per_token(cfg) == 128 * 1024         # 128 KiB
    assert flops.decoder_flops_per_token(cfg, 100) == (
        2 * 7_110_393_856 + 4 * 32 * 4096 * 100)


def test_mobilenet_v1_flops_per_frame():
    macs = flops.mobilenet_v1_flops_per_frame(_cfg("mobilenet_v1")) / 2
    assert macs == 568_741_376            # the paper's 569 M mult-adds
    assert round(2 * macs / 1e9, 2) == 1.14


def test_peaks_are_keyed_by_kind_and_unknown_raises():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16_909_336_064 and p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_percentile_and_union():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([10.0], 95) == 10.0
    assert stats.interval_union([(0, 4), (2, 6), (10, 11)]) == 7


# a device that ran: op a 0-40 ns, op b 30-60 (overlaps a), op a again
# 100-140; two executions of one program, 0-60 and 100-140
FIXTURE = {
    "/device:TPU:0": {
        "XLA Ops": [
            ("%a.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 40),
            ("%b.2 = f32[8]{0:T(8)S(1)} custom-call(f32[8]{0} %a.1)", 30, 30),
            ("%a.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 40),
            ("%w.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
             0, 140),
        ],
        "XLA Modules": [("jit_step(1)", 0, 60), ("jit_step(1)", 100, 40)],
    },
    "/host:CPU": {"python3": [("noise", 0, 1000)]},
}


def test_trace_reduction_on_a_hand_made_trace():
    r = trace.reduce(FIXTURE)
    assert r["devices"] == 1
    # the while wrapper spans 0-140, so the union is the whole span; the
    # leaf sums leave it out
    assert r["window_s"] == pytest.approx(140e-9)
    assert r["busy_s"] == pytest.approx(140e-9)
    assert r["ops"] == {"%a.1 f32[8] fusion": pytest.approx(80e-9),
                        "%b.2 f32[8] custom-call": pytest.approx(30e-9)}
    assert r["op_calls"]["%a.1 f32[8] fusion"] == 2
    assert r["modules"] == {"jit_step(1)": pytest.approx(100e-9)}
    assert r["module_calls"] == {"jit_step(1)": 2}
    assert r["idle_gaps"] == {"before jit_step(1)": pytest.approx(40e-9)}
    leaves = dict(FIXTURE)
    leaves["/device:TPU:0"] = dict(
        FIXTURE["/device:TPU:0"],
        **{"XLA Ops": FIXTURE["/device:TPU:0"]["XLA Ops"][:3]})
    r = trace.reduce(leaves)
    assert r["busy_s"] == pytest.approx(100e-9)       # 0-60 and 100-140
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        100 * 40 / 140)
    bd = trace.breakdown(r)
    assert bd["device_ops"][0] == ["%a.1 f32[8] fusion",
                                   pytest.approx(80e-9)]
    assert trace.reduce({"/host:CPU": FIXTURE["/host:CPU"]}) == {
        "devices": 0}


def test_a_real_trace_loads(tmp_path):
    """The loader reads what this jax's profiler writes (on the CPU it
    holds host planes only, so no device plane is found)."""
    import time

    import jax
    import jax.numpy as jnp

    t = trace.DeviceTrace(str(tmp_path / "tr"), at_s=0.0, for_s=0.2)
    t.arm(time.perf_counter())
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    for _ in range(50):
        f(x).block_until_ready()
    r = t.read()
    assert r["devices"] == 0 and r["host_span"][1] > r["host_span"][0]
    assert not os.path.exists(str(tmp_path / "tr"))


def test_within_classes_takes_each_class_offset_off():
    """Two classes, each with an offset of its own and the same scatter:
    what is left is the scatter; an impossible answer stays not finite."""
    import numpy as np

    from benchmark.manifest import Manifest

    driver = Manifest().driver({"kind": "stream"})
    within_classes = sys.modules[driver.__module__].within_classes
    scatter = np.tile([-0.001, 0.001], 50)
    ids = np.repeat([7, 9], 50)
    err = scatter + np.where(ids == 7, 0.004, -0.002)
    assert err.std() > 0.003
    assert within_classes(err, ids).std() == pytest.approx(0.001)
    err[3] = np.inf
    assert not np.isfinite(within_classes(err, ids).std())
