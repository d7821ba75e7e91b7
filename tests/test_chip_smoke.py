"""chip_smoke.py's dry run: the same leg functions the chip runs, at toy
size on the CPU (kernels interpreted), so a chip call is never spent on a
fault the sandbox could have shown.  ``python chip_smoke.py`` itself always
demands the chip — pinned by the exit-code test."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_stream_leg_toy():
    facts = chip_smoke.stream_leg(size=16, batch=4, batches=8)
    assert facts["device_batches"] == 8 and facts["host_batches"] == 4
    # donation is compiled out on CPU; the leg checks it is ON elsewhere
    assert facts["donation_active"] is False


def test_sharded_stream_leg_toy():
    facts = chip_smoke.sharded_stream_leg(size=16, batch=4, n_push=16,
                                          replicas=4)
    assert facts["mesh_shape"] == [4, 1]
    assert len(facts["shard_counters"]) == 4


def test_serving_leg_toy():
    facts = chip_smoke.serving_leg(model="llama_tiny", max_new=6,
                                   prompt_lens=(5, 40, 3), slots=3,
                                   first_token_timeout=300.0)
    assert facts["programs"] == 3 and facts["replay_identical"]
    assert facts["full_depth"] and facts["tokens"] == 4 * 6


def test_serving_leg_cuts_only_depth():
    facts = chip_smoke.serving_leg(model="llama_tiny", n_layers=1,
                                   max_new=4, prompt_lens=(5, 9, 3),
                                   first_token_timeout=300.0)
    assert facts["n_layers"] == 1 and not facts["full_depth"]
    assert facts["dim"] == 128


def test_kernel_leg_toy_interpreted():
    facts = chip_smoke.kernel_leg(n_heads=4, n_kv_heads=(4, 2), head_dim=64,
                                  dim=256, ffn=512, slots=3, block_size=8,
                                  seq=128, paged_heads=((4, 4), (8, 2)),
                                  paged_lens=(0, 5, 30, 128, 129, 200),
                                  window=32, grouped_dims=(256, 128),
                                  grouped=((16, 8, 32, 8, 2),
                                           (32, 4, 16, 16, 2, (128, 256))),
                                  paged_narrow=((8, 4, 32),),
                                  interpret=True)
    names = " ".join(facts["rel_err"])
    for kernel in ("flash_attention", "paged_attention", "matmul_int4",
                   "grouped_swiglu"):
        assert kernel in names
    # the grouped kernel ran on the router's sizes and on skewed ones
    assert {"grouped_swiglu 128x16 cell", "grouped_swiglu 128x16 skewed"} \
        <= set(facts["rel_err"])
    # ... and where every expert is held, at widths of its own
    assert "grouped_swiglu 128x32 128x256 cell" in facts["rel_err"]
    # the paged kernel ran without a window and with one over a ring
    assert "paged_attention 8/2 window 32 ring 7" in facts["rel_err"]
    # ... and with heads narrower than the lanes, four to a lane row
    assert {"paged_attention 8/4 x 32",
            "paged_attention 8/4 x 32 window 32 ring 7"} \
        <= set(facts["rel_err"])


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out  # no result line without the chip
    assert "cpu" in out.err
