"""The five serve-loop readers on hand-written ring spans: two iterations
with known gaps, a span that straddles the window's edge, requests that
were admitted inside and outside the window, and nothing at all."""

import pytest

from conftest import ROOT

from benchmark import ring_spans
from benchmark.manifest import Manifest

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS          # a window of one second


def span(kind, start_ms, dur_ms, **args):
    return (kind, LO + int(start_ms * MS), int(dur_ms * MS), args)


#: iteration 1 closes its decode at 300 ms, delivers for 20 ms, takes in
#: and admits for 2 + 8 ms and dispatches a prefill chunk at 335 ms (gap
#: 35); iteration 2 closes at 700, delivers 25 and dispatches its decode
#: at 728 (gap 28); its decode closes at 1010, outside the window, and
#: the delivery after it straddles nothing.  The first delivery of the
#: list started 10 ms before the window and reaches 15 ms into it.
SPANS = [
    span("serve.decode", -400, 390, iter=0, occupancy=32, chunk=8,
         wait_ns=1),
    span("serve.emit", -10, 25, iter=0, tokens=256, retired=0),
    span("serve.decode", 0, 300, iter=1, occupancy=32, chunk=8, wait_ns=1),
    span("serve.emit", 300, 20, iter=1, tokens=256, retired=1),
    span("serve.intake", 321, 2, iter=2, n=1),
    span("serve.admit_pass", 323, 8, iter=2, looked=1, admitted=1),
    span("serve.admit", 330, 1, iter=2, tid=7, slot=3),
    span("serve.queue", 310, 20, tid=7, slot=3, tokens=20, blocks=34,
         shared=0),
    span("serve.prefill_chunk", 335, 1, iter=2, tid=7, slot=3, pos=0,
         final=True),
    span("serve.decode", 340, 360, iter=2, occupancy=32, chunk=8,
         wait_ns=2),
    span("serve.first_token", 341, 30, iter=2, tid=7, slot=3),
    span("serve.prefill", 330, 40, tid=7, slot=3, chunks=1),
    span("serve.emit", 700, 25, iter=2, tokens=256, retired=0),
    span("serve.intake", 725, 1, iter=3, n=0),
    span("serve.admit_pass", 726, 1, iter=3, looked=0, admitted=0),
    span("serve.decode", 728, 282, iter=3, occupancy=32, chunk=8,
         wait_ns=3),
    # admitted before the window opened / first token after it closed
    span("serve.queue", -50, 40, tid=5, slot=1, tokens=16, blocks=33,
         shared=0),
    span("serve.prefill", 980, 60, tid=9, slot=2, chunks=1),
    span("serve.queue", 900, 60, tid=9, slot=2, tokens=31, blocks=34,
         shared=0),
]


def obs(spans):
    return {"spans": spans, "window_ns": [LO, HI], "window_s": 1.0,
            "trace": None, "peaks": None, "chips": 1, "cfg": {}}


@pytest.fixture(scope="module")
def reader():
    return Manifest(ROOT).reader


@pytest.mark.parametrize("name,expected", [
    # gaps 300->335 and 700->728, of 1000 ms; the one from -10 to the
    # dispatch at 0 lies before the window
    ("serve_host_gap_pct", 100 * (35 + 28) / 1000),
    # 15 of the straddling 25, then 20 and 25
    ("serve_emit_pct", 100 * (15 + 20 + 25) / 1000),
    ("serve_admit_pct", 100 * (2 + 8 + 1 + 1) / 1000),
    # admitted in the window: 20 and 60 ms (not the 40 before it)
    ("serve_queue_p95_ms", 20 + 0.95 * 40),
    # first token in the window: 40 ms alone (the 60 left after it)
    ("serve_admit_to_first_p95_ms", 40.0),
])
def test_reader_on_two_known_iterations(reader, name, expected):
    assert reader(name)(obs(SPANS)) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "serve_host_gap_pct", "serve_emit_pct", "serve_admit_pct",
    "serve_queue_p95_ms", "serve_admit_to_first_p95_ms"])
def test_reader_with_nothing_to_read(reader, name):
    assert reader(name)(obs([])) is None
    # the parent's spans: no phase span, no per-request span
    old = [s for s in SPANS if s[0] in ("serve.admit", "serve.decode",
                                        "serve.prefill_chunk")]
    got = reader(name)(obs(old))
    if name == "serve_host_gap_pct":
        assert got == pytest.approx(6.3)   # dispatches alone define it
    else:
        assert got is None
    assert reader(name)({"spans": SPANS, "window_ns": None}) is None


def test_shares_are_of_the_part_of_the_window_the_ring_still_holds(reader):
    """A traced pipeline records spans for every token, so the ring has
    evicted the window's first part by the time it is read: what is left
    starts with iteration 2's close at 700 ms, and the shares are of the
    300 ms from there on, not of the second."""
    late = [s for s in SPANS if s[1] + s[2] >= LO + 700 * MS]
    o = obs(late)
    assert ring_spans.covered_window(o) == (LO + 700 * MS, HI)
    assert reader("serve_host_gap_pct")(o) == pytest.approx(100 * 28 / 300)
    assert reader("serve_emit_pct")(o) == pytest.approx(100 * 25 / 300)
    assert reader("serve_admit_pct")(o) == pytest.approx(100 * 2 / 300)
    # tails are over the requests the ring still holds
    assert reader("serve_queue_p95_ms")(o) == pytest.approx(60.0)
    assert reader("serve_admit_to_first_p95_ms")(o) is None


def test_emit_and_admit_lie_inside_the_host_gap(reader):
    o = obs(SPANS)
    # the straddling delivery's iteration closed before the window: its
    # 15 ms are the emit reader's and no gap's, hence the allowance
    assert (reader("serve_emit_pct")(o) + reader("serve_admit_pct")(o)
            <= reader("serve_host_gap_pct")(o) + 1.5)


def test_a_gap_that_straddles_the_edge_counts_its_inside_part():
    spans = [span("serve.decode", -400, 390), span("serve.decode", 20, 300),
             span("serve.decode", 330, 700)]
    # closes at -10, next dispatch at 20: 20 ms inside; 320 -> 330: 10
    assert ring_spans.host_gaps(spans) == [
        (LO - 10 * MS, LO + 20 * MS), (LO + 320 * MS, LO + 330 * MS)]
    assert ring_spans.clipped_ns(ring_spans.host_gaps(spans), LO, HI) \
        == 30 * MS


def test_no_gap_is_invented_when_a_dispatch_is_missing():
    # the ring evicted iteration 2's dispatch: its close has no successor
    spans = [span("serve.decode", 0, 100), span("serve.decode", 400, 100)]
    assert ring_spans.host_gaps(spans) == [(LO + 100 * MS, LO + 400 * MS)]
    assert ring_spans.host_gaps(spans[:1]) == []
