"""``serve_deliver_ahead_pct`` on hand-written ring spans: the share of
deliveries made under the next chunk, over the part of the window the
ring still covers, and nothing where the program records no ``ahead``."""

import pytest

from conftest import ROOT

from benchmark.manifest import Manifest

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS          # a window of one second


def span(kind, start_ms, dur_ms, **args):
    return (kind, LO + int(start_ms * MS), int(dur_ms * MS), args)


#: four chunks close at 100, 300, 500 and 700 ms.  Chunk 1 retired
#: nothing: its delivery runs under chunk 2 (ahead); chunk 2 retired a
#: stream with nothing queued: delivered at once, the chip idle; chunk 3
#: again ahead; chunk 4's delivery (ahead) closes after the window.  The
#: first delivery of the list closed before the window opened.
SPANS = [
    span("serve.emit", -40, 30, iter=0, tokens=256, retired=0, ahead=1),
    span("serve.decode", 0, 100, iter=1, occupancy=32, chunk=8, wait_ns=1),
    span("serve.decode", 103, 197, iter=2, occupancy=32, chunk=8,
         wait_ns=1),
    span("serve.emit", 105, 30, iter=1, tokens=256, retired=0, ahead=1),
    span("serve.emit", 300, 30, iter=2, tokens=250, retired=1, ahead=0),
    span("serve.decode", 340, 160, iter=3, occupancy=32, chunk=8,
         wait_ns=1),
    span("serve.decode", 503, 197, iter=4, occupancy=32, chunk=8,
         wait_ns=1),
    span("serve.emit", 505, 30, iter=3, tokens=256, retired=0, ahead=1),
    span("serve.decode", 703, 300, iter=5, occupancy=32, chunk=8,
         wait_ns=1),
    span("serve.emit", 990, 30, iter=4, tokens=256, retired=0, ahead=1),
]


def obs(spans, window=(LO, HI)):
    return {"spans": spans, "window_ns": list(window) if window else None,
            "window_s": 1.0, "trace": None, "peaks": None, "chips": 1,
            "cfg": {}}


@pytest.fixture(scope="module")
def read():
    return Manifest(ROOT).reader("serve_deliver_ahead_pct")


def test_share_of_the_deliveries_that_closed_in_the_window(read):
    # 135, 330 and 535 ms close inside: two of three ahead
    assert read(obs(SPANS)) == pytest.approx(100 * 2 / 3)


def test_share_is_over_the_part_of_the_window_the_ring_still_holds(read):
    # the ring has lost everything that closed before 500 ms: it covers
    # from chunk 3's close on, and one delivery closed in there
    late = [s for s in SPANS if s[1] + s[2] >= LO + 500 * MS]
    assert read(obs(late)) == pytest.approx(100.0)
    only_now = [s for s in late if not s[3].get("ahead")]
    assert read(obs(only_now)) is None       # no delivery left to count


@pytest.mark.parametrize("spans,window", [
    ([], (LO, HI)),                          # nothing recorded
    (SPANS, None),                           # no window
    # the parent's spans: deliveries carry no `ahead`
    ([(k, ts, dur, {x: v for x, v in a.items() if x != "ahead"})
      for k, ts, dur, a in SPANS], (LO, HI)),
    # deliveries but no iteration end: no covered window
    ([s for s in SPANS if s[0] == "serve.emit"], (LO, HI)),
])
def test_nothing_to_read_is_none_not_zero(read, spans, window):
    assert read(obs(spans, window)) is None


def test_a_program_that_never_runs_ahead_reads_zero(read):
    never = [(k, ts, dur, dict(a, ahead=0) if k == "serve.emit" else a)
             for k, ts, dur, a in SPANS]
    assert read(obs(never)) == 0.0
