"""Whole runs at toy size on the CPU, through ``run.run_cell`` (everything
of a run after the look for a chip): the references agree with the
program, the controls do not, and a timed path broken underneath comes
out as not correct."""

import pytest

from conftest import run_toy

SERVE = "toy_decoder.toy_closed"
OPEN = "toy_decoder.toy_open"
STREAM = "toy_mobilenet.toy_hostfed"


def _check(result, name):
    return next(c for c in result["checks"] if c["name"] == name)


@pytest.mark.parametrize("cell,number", [(SERVE, "logit_gap_max"),
                                         (OPEN, "logit_gap_max"),
                                         (STREAM, "score_err_within")])
def test_program_agrees_with_the_reference(toy_root, cell, number):
    r = run_toy(toy_root, cell, seed=2**31 + 5)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert _check(r, number)["value"] <= _check(r, number)["limit"]
    assert _check(r, "compiles_in_window")["value"] == 0
    assert set(r["metrics"]) == (
        {"stream_fps", "setup_s"} if cell == STREAM else
        {"serve_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"})
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"      # the compared numbers come last


def _control(toy_root, cell, seed):
    """The control's reading and the program's, as control.py takes them."""
    from benchmark import control
    from benchmark.manifest import Manifest

    return control.read_seed(Manifest(toy_root), cell, seed, 1.5)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_int4_control_is_not_correct(toy_root, seed):
    row = _control(toy_root, SERVE, seed)
    limit = 0.25
    assert row["program"]["logit_gap_max"] <= limit
    assert row["control"]["logit_gap_max"] > 3 * row["program"][
        "logit_gap_max"]
    assert row["control"]["logit_gap_max"] > limit


@pytest.mark.parametrize("seed", [21, 23, 24])
def test_float8_control_is_not_correct(toy_root, seed):
    row = _control(toy_root, STREAM, seed)
    assert row["program"]["score_err_within"] <= 0.0028
    assert row["control"]["score_err_within"] > 0.0028
    assert row["control"]["score_err_within"] > 3 * row["program"][
        "score_err_within"]


def test_an_altered_token_is_not_correct(toy_root, monkeypatch):
    """The fault a serve cell can have: a token altered where it is
    produced (the sampler of the decode step picks the runner-up)."""
    from nnstreamer_tpu.models import llama

    real = llama.sample_token_per_slot

    def runner_up(logits, *a, **k):
        import jax.numpy as jnp

        best = real(logits, *a, **k)
        masked = logits.at[jnp.arange(logits.shape[0]), best].set(-1e30)
        worst_of_two = real(masked, *a, **k)
        # every fourth vocabulary row keeps its token: most are altered
        return jnp.where(best % 4 == 0, best, worst_of_two)

    monkeypatch.setattr(llama, "sample_token_per_slot", runner_up)
    r = run_toy(toy_root, SERVE, seed=31)
    assert not r["correct"]
    assert not _check(r, "logit_gap_max")["ok"]


def test_an_altered_answer_is_not_correct(toy_root, monkeypatch):
    """The fault a stream cell can have: an answer altered where it is
    produced (the classifier's logits shifted by one class)."""
    from benchmark import adapter
    from nnstreamer_tpu.models import mobilenet

    import jax.numpy as jnp

    real = mobilenet.apply
    # the adapter reads mobilenet.apply when it registers the model
    monkeypatch.setattr(
        mobilenet, "apply",
        lambda params, x, **k: jnp.roll(real(params, x, **k), 1, axis=-1))
    assert adapter.register_mobilenet_v1
    r = run_toy(toy_root, STREAM, seed=32)
    assert not r["correct"]
    assert not _check(r, "score_err_within")["ok"]


def test_a_stall_counts_against_every_metric(toy_root, monkeypatch):
    """All work over all time, every request from when it was due: a 1 s
    stall injected into the server's emission lowers the rate and raises
    both tails of an open loop; no piece of the window is set aside."""
    from nnstreamer_tpu.filters import llm

    base = run_toy(toy_root, OPEN, seed=41, seconds=3.0)
    real = llm._ContinuousLoop._emit_token
    state = {"n": 0}

    def stalling(self, *a, **k):
        import time

        state["n"] += 1
        if state["n"] == 120:
            time.sleep(1.0)
        return real(self, *a, **k)

    monkeypatch.setattr(llm._ContinuousLoop, "_emit_token", stalling)
    stalled = run_toy(toy_root, OPEN, seed=41, seconds=3.0)
    assert state["n"] > 120
    b, s = base["metrics"], stalled["metrics"]
    assert stalled["attempted"] == base["attempted"]   # the same schedule
    assert s["ttft_p95_ms"]["value"] > b["ttft_p95_ms"]["value"] + 250
    assert s["serve_tok_s"]["value"] <= b["serve_tok_s"]["value"]
    assert stalled["correct"]
