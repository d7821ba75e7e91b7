"""The six readers of PR 38 on a hand-written second of the serve
loop: thirteen iterations of which every third admits a request — its
boundary holds an idle delivery, an intake, an admission and a prefill
dispatch — and the others go straight to the next decode dispatch; one
round is a speculative one.  A stall put into one phase moves that
phase's reader and no other; a ring that lost the window's first half
says so."""

import pytest

from conftest import ROOT

from benchmark import ring_spans
from benchmark.manifest import Manifest

MS = 1_000_000
LO, HI = 1_000 * MS, 2_000 * MS          # a window of one second

#: the phases of a boundary, ms.  One that admits: the chunk closes, the
#: loop settles it, delivers 38 tokens with nothing on the chip, takes the
#: freed caller's request in, admits it, prepares and dispatches the
#: prefill chunk and then the decode chunk behind it.
SETTLE, IDLE_EMIT, TAIL, INTAKE, ADMIT, PREP, PREFILL = \
    0.3, 8.0, 0.05, 0.1, 0.3, 0.4, 1.5
#: one that does not: 0.1 → intake 0.05 → admission pass 0.05 → 0.2
PLAIN = (0.1, 0.05, 0.05, 0.2)
DISPATCH, DECODE, FIRST_TOKEN = 0.4, 90.0, 17.0
STALL_MS = 5.0


def timeline(stall=None, spec_round=8):
    """Spans of thirteen iterations from 150 ms before the window on;
    ``stall`` = (phase, iteration) gets ``STALL_MS`` more."""
    spans, t = [], -150.0

    def extra(phase, it):
        return STALL_MS if stall == (phase, it) else 0.0

    def add(kind, start, dur, **args):
        spans.append((kind, LO + round(start * MS), round(dur * MS), args))

    def decode(it, start, admitted):
        kind = "serve.spec_verify" if it == spec_round else "serve.decode"
        host = DISPATCH + extra("decode", it)
        dur = DECODE + extra("decode", it)
        add(kind, start, dur, iter=it, occupancy=32, chunk=8,
            wait_ns=round(50 * MS), dispatch_ns=round(host * MS))
        at = start + host + 0.1
        if admitted:
            wait = FIRST_TOKEN + extra("first_token", it)
            add("serve.first_token", at + 0.4, wait, iter=it, tid=it,
                slot=3)
            add("serve.emit", at + 0.4 + wait + 0.1, 30.0, iter=it - 1,
                tokens=218, retired=0, ahead=1)
        else:
            add("serve.emit", at, 33.0, iter=it - 1, tokens=256, retired=0,
                ahead=1)
        return start + dur

    t = decode(0, t, False)
    for it in range(1, 13):
        if it % 3 == 1:                        # a stream ended: admits
            t += SETTLE
            dur = IDLE_EMIT + extra("emit", it)
            add("serve.emit", t, dur, iter=it - 1, tokens=38, retired=1,
                ahead=0)
            t += dur + TAIL
            add("serve.intake", t, INTAKE, iter=it, n=1)
            t += INTAKE
            dur = ADMIT + extra("admit", it)
            add("serve.admit_pass", t, dur, iter=it, looked=1, admitted=1)
            add("serve.admit", t + dur - 0.1, 0.1, iter=it, tid=it, slot=3)
            t += dur + PREP
            dur = PREFILL + extra("prefill", it)
            add("serve.prefill_chunk", t, dur, iter=it, tid=it, slot=3,
                pos=0, final=True, sampled=1)
            t += dur + 0.1
        else:
            t += PLAIN[0]
            add("serve.intake", t, PLAIN[1], iter=it, n=0)
            add("serve.admit_pass", t + PLAIN[1], PLAIN[2], iter=it,
                looked=0, admitted=0)
            t += PLAIN[1] + PLAIN[2] + PLAIN[3]
        t = decode(it, t, it % 3 == 1)
    return spans


def obs(spans, **more):
    return dict({"spans": spans, "window_ns": [LO, HI], "window_s": 1.0,
                 "trace": None, "peaks": None, "chips": 1, "cfg": {}},
                **more)


@pytest.fixture(scope="module")
def reader():
    return Manifest(ROOT).reader


#: in the window: boundaries that admit before iterations 4, 7 and 10,
#: plain ones before 2, 3, 5, 6, 8, 9, 11 and 12 (iteration 1's decode is
#: running when the window opens; 12's closes after it)
ADMITTING, PLAIN_ONES = 3, 8
GAP_A = SETTLE + IDLE_EMIT + TAIL + INTAKE + ADMIT + PREP       # 9.15 ms
EXPECTED = {
    "serve_ring_window_pct": 100.0,
    "serve_host_gap_pct": (ADMITTING * GAP_A + PLAIN_ONES * 0.4) / 10,
    "serve_gap_emit_pct": ADMITTING * IDLE_EMIT / 10,            # 2.4
    "serve_admit_pct": (ADMITTING * 0.4 + PLAIN_ONES * 0.1) / 10,
    # a prefill ends a boundary that admits, a decode or a round the rest
    "serve_dispatch_pct": (ADMITTING * PREFILL + PLAIN_ONES * DISPATCH) / 10,
    # deliveries closed in the window: 7 whole chunks of 33 ms, and 3 in
    # two parts of 8 and 30 ms; 256 tokens each
    "serve_emit_us_per_token": (7 * 33.0 + 3 * 38.0) * 1e3 / (10 * 256),
    "serve_first_token_wait_p95_ms": FIRST_TOKEN,
}
SPAN_READERS = sorted(set(EXPECTED) - {"serve_host_gap_pct"})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_the_known_second(reader, name):
    assert EXPECTED["serve_host_gap_pct"] == pytest.approx(3.065)
    assert reader(name)(obs(timeline())) == pytest.approx(EXPECTED[name])


def test_tokens_a_second_of_the_traced_run_itself(reader):
    decoded = [(10.0 + i * 1e-4, 40 + i % 500) for i in range(2_077)]
    read = reader("serve_traced_tok_s")
    assert read(obs([], decoded=decoded)) == pytest.approx(2_077.0)
    assert read(obs([], decoded=decoded, window_s=45.0)) \
        == pytest.approx(2_077 / 45)
    assert read(obs([], decoded=[])) is None and read(obs([])) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_reader_with_nothing_to_read(reader, name):
    assert reader(name)(obs([])) is None
    assert reader(name)({"spans": timeline(), "window_ns": None}) is None


def test_the_parents_spans_read_all_but_the_dispatch(reader):
    """A program before PR 38 records no ``dispatch_ns``:
    the dispatch reader gives nothing (half a sum would read as a gain),
    the others read the same spans as ever."""
    old = [(k, ts, dur, {x: v for x, v in a.items()
                         if x != "dispatch_ns"})
           for k, ts, dur, a in timeline()]
    for name in SPAN_READERS:
        got = reader(name)(obs(old))
        if name == "serve_dispatch_pct":
            assert got is None
        else:
            assert got == pytest.approx(EXPECTED[name]), name


def test_the_parts_lie_inside_the_host_gap(reader):
    o = obs(timeline())
    gap = reader("serve_host_gap_pct")(o)
    emit, admit = (reader("serve_gap_emit_pct")(o),
                   reader("serve_admit_pct")(o))
    assert 0 < emit and 0 < admit and emit + admit <= gap
    # what is left is the loop's bookkeeping: settle, tail, preparation
    book = ADMITTING * (SETTLE + TAIL + PREP) + PLAIN_ONES * 0.3
    assert gap - emit - admit == pytest.approx(book / 10)
    # deliveries under the next chunk are no part of any gap
    assert emit < reader("serve_emit_pct")(o)
    # gap + dispatch: the program's own reckoning of the idle chip
    assert gap + reader("serve_dispatch_pct")(o) == pytest.approx(3.835)


@pytest.mark.parametrize("stall,moves", [
    (("emit", 4), {"serve_gap_emit_pct", "serve_emit_us_per_token"}),
    (("admit", 4), {"serve_admit_pct"}),
    (("prefill", 4), {"serve_dispatch_pct"}),
    (("decode", 5), {"serve_dispatch_pct"}),
    (("first_token", 4), {"serve_first_token_wait_p95_ms"}),
], ids=lambda v: v[0] if isinstance(v, tuple) else None)
def test_a_stall_moves_its_phases_reader_and_no_other(reader, stall, moves):
    calm, stalled = obs(timeline()), obs(timeline(stall))
    for name in SPAN_READERS:
        a, b = reader(name)(calm), reader(name)(stalled)
        if name in moves:
            assert b > a * 1.01, (name, a, b)
        else:
            assert b == pytest.approx(a), (name, a, b)
    share = {"serve_gap_emit_pct", "serve_admit_pct",
             "serve_dispatch_pct"} & moves
    for name in share:       # 5 ms of a second, whole
        assert reader(name)(stalled) - reader(name)(calm) \
            == pytest.approx(STALL_MS / 10)
    # a stall inside the gap is the old gap reader's too; one in a
    # dispatch lies after it
    d_gap = reader("serve_host_gap_pct")(stalled) \
        - reader("serve_host_gap_pct")(calm)
    in_gap = stall[0] in ("emit", "admit")
    assert d_gap == pytest.approx(STALL_MS / 10 if in_gap else 0.0)


def test_a_ring_that_lost_the_first_half_says_so(reader):
    late = [s for s in timeline() if s[1] + s[2] >= LO + 500 * MS]
    o = obs(late)
    # iteration 6's round closes 503.1 ms in: the earliest still there
    assert ring_spans.covered_window(o) == (LO + round(503.1 * MS), HI)
    assert reader("serve_ring_window_pct")(o) == pytest.approx(49.69)
    # the shares are of what is left: two boundaries that admit
    assert reader("serve_gap_emit_pct")(o) == pytest.approx(
        100 * 2 * IDLE_EMIT / 496.9)
    assert reader("serve_dispatch_pct")(o) == pytest.approx(
        100 * (2 * PREFILL + 4 * DISPATCH) / 496.9)


def test_a_gap_is_charged_the_dispatch_that_ends_it_only():
    """The decode dispatched 0.1 ms behind a prefill runs under it: its
    ``dispatch_ns`` is no idle time and is not counted."""
    from benchmark.layer_metrics.serve_dispatch_pct import \
        dispatch_intervals

    iv = dispatch_intervals(timeline())
    by_len = sorted({round((e - s) / MS, 3) for s, e in iv})
    assert by_len == [DISPATCH, PREFILL] and len(iv) == 12
