"""The readers of the program's own spans, on a synthetic trace reduction
and a synthetic span ring whose idle gaps and spans are known."""

import sys
from types import SimpleNamespace

import pytest

from harness import program_spans as PS, spec, trace as T
from repro import obs

W0, W1 = 100.0, 110.0          # the window on time.perf_counter


def _read(name, w):
    return spec.Bench().metric_module(name).read(w)


def _round(t, pick=None):
    """One round at t: sched.pick, then engine.round and its parts."""
    recs = []
    if pick is not None:
        recs.append(("sched.pick", t, t + 0.1, None,
                     {"bs": 3, "k": 2, "predicted_s": pick, "reason": "ok"}))
    recs += [
        ("engine.round.inputs", t + 0.1, t + 0.2, "engine.round", {}),
        ("colo.round", t + 0.25, t + 1.4, "engine.round.step", {"k": 2}),
        ("engine.round.step", t + 0.2, t + 1.5, "engine.round", {}),
        ("engine.round.pull", t + 1.5, t + 1.9, "engine.round", {}),
        ("engine.round.commit", t + 1.9, t + 2.0, "engine.round", {}),
        ("engine.round", t + 0.1, t + 2.0, None, {"bs": 3}),
    ]
    return recs


def _admission(t, rid=1):
    return [
        ("engine.admit.cache", t, t + 0.1, "engine.admit", {}),
        ("engine.admit.prefill", t + 0.1, t + 0.5, "engine.admit", {}),
        ("engine.admit.insert", t + 0.5, t + 0.6, "engine.admit", {}),
        ("engine.admit.first_token", t + 0.6, t + 0.95, "engine.admit", {}),
        ("engine.admit", t, t + 1.0, None,
         {"rid": rid, "prompt_len": 64, "admitted": True}),
    ]


def _ring(monkeypatch, recs, capacity=1024):
    ring = obs.Ring(capacity)
    for r in sorted(recs, key=lambda r: r[2]):
        ring.buf.append(r)
    monkeypatch.setattr(obs, "RING", ring)
    return ring


def _w(gaps_perf, w0=W0, w1=W1, trace_at=5.0, rate=1.0, rounds=2,
       admissions=1):
    """A window whose trace clock reads trace_at at w0 and runs ``rate``
    times as fast; gaps are given on the perf clock."""
    to_trace = lambda t: trace_at + (t - w0) * rate
    reduced = T.Reduced(
        n_devices=1, window=(to_trace(w0), to_trace(w1)), busy_s=0.0,
        op_s={}, modules=[],
        idle_by_span={"round": 0.3, "pick": 0.05, "admit": 0.2},
        idle_gaps=[(to_trace(a), to_trace(b)) for a, b in gaps_perf])
    lines = []
    rec = {"w0": w0, "w1": w1, "rounds": [None] * rounds,
           "admissions": [None] * admissions}
    return SimpleNamespace(rec=rec, trace=reduced, info=lines.append,
                           lines=lines)


# gaps on the perf clock, and where each instant of them falls
GAPS = [
    (100.05, 100.15),   # pick 0.05, inputs 0.05
    (101.30, 101.45),   # colo.round 0.10, step (its own) 0.05
    (101.95, 102.50),   # commit 0.05, outside 0.50
    (104.60, 104.70),   # second round's pull 0.10
    (106.55, 106.70),   # insert 0.05, first_token 0.10
    (106.95, 107.00),   # the admission's own 0.05
]


def test_idle_is_split_by_the_innermost_span(monkeypatch):
    _ring(monkeypatch, _round(100.0, 0.03) + _round(103.0, 0.03)
          + _admission(106.0))
    w = _w(GAPS)
    # rounds: 0.05 + 0.05 + 0.10 + 0.05 + 0.05 + 0.10 s over 2
    assert _read("round_idle_ms", w) == pytest.approx(200.0)
    # admission: 0.05 + 0.10 + 0.05 s over 1
    assert _read("admit_idle_ms", w) == pytest.approx(200.0)
    rnd = next(s for s in w.lines if s.startswith("round_idle_ms"))
    for part in ("pull 100.000 ms", "colo.round 100.000 ms",
                 "inputs 50.000 ms", "step 50.000 ms", "commit 50.000 ms",
                 "sched.pick 50.000 ms", "outside every program span "
                 "500.000 ms", "idle in round + pick 350.000 ms"):
        assert part in rnd, (part, rnd)
    adm = next(s for s in w.lines if s.startswith("admit_idle_ms"))
    for part in ("first_token 100.000 ms", "insert 50.000 ms",
                 "self 50.000 ms", "over 1 admissions"):
        assert part in adm, (part, adm)


def test_innermost_pieces_cover_nested_spans():
    pieces = PS.innermost([(0.0, 10.0, "a"), (2.0, 5.0, "b"),
                           (3.0, 4.0, "c"), (6.0, 12.0, "d"),
                           (20.0, 21.0, "e")])
    # d outlives a: it is cut at a's end
    assert pieces == [(0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 4.0, "c"),
                      (4.0, 5.0, "b"), (5.0, 6.0, "a"), (6.0, 10.0, "d"),
                      (20.0, 21.0, "e")]
    idle, outside = PS.charge([(1.0, 3.5), (9.0, 11.0)], pieces)
    assert dict(idle) == pytest.approx({"a": 1.0, "b": 1.0, "c": 0.5,
                                        "d": 1.0})
    assert outside == pytest.approx(1.0)


def test_two_point_anchor_follows_a_skewed_clock(monkeypatch):
    """The trace clock runs 500 ppm fast over a 60 s window: mapped by an
    offset alone, a round near the end would be 30 ms off its gap."""
    w0, w1, rate = 100.0, 160.0, 1.0005
    _ring(monkeypatch, [("engine.round", 159.2, 159.3, None, {"bs": 1})])
    w = _w([(159.2, 159.3)], w0=w0, w1=w1, rate=rate, rounds=1)
    assert _read("round_idle_ms", w) == pytest.approx(100.0 * rate,
                                                      rel=1e-9)


def test_none_when_the_ring_starts_after_the_window(monkeypatch):
    recs = _round(103.0, 0.03)                     # 7 records, all late
    _ring(monkeypatch, recs, capacity=len(recs))   # full: older ones gone
    for name in ("round_idle_ms", "admit_idle_ms", "predictor_abs_err_pct"):
        w = _w(GAPS)
        assert _read(name, w) is None
        assert any("no longer holds the whole window" in s
                   for s in w.lines), w.lines


def test_none_where_the_program_keeps_no_spans(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in ("round_idle_ms", "admit_idle_ms", "predictor_abs_err_pct"):
        w = _w(GAPS)
        assert _read(name, w) is None
        assert any("no repro.obs" in s for s in w.lines), w.lines


def test_none_without_a_trace(monkeypatch):
    _ring(monkeypatch, _round(100.0, 0.03))
    w = _w(GAPS)
    w.trace = None
    assert _read("round_idle_ms", w) is None
    assert _read("admit_idle_ms", w) is None


def test_predictor_error_pairs_each_round_with_its_pick(monkeypatch):
    recs = []
    # before the window: not counted
    recs += _round(90.0, 1.0)
    # measured rounds last 1.9 s: predictions 0.95, 1.52 and 2.28 s are
    # -50%, -20% and +20% off
    for t, p in ((100.0, 0.95), (102.5, 1.52), (105.0, 2.28)):
        recs += _round(t, p)
    # a round no pick decided
    recs += [r for r in _round(107.5) if r[0] == "engine.round"]
    _ring(monkeypatch, recs)
    w = _w(GAPS)
    pairs = PS.predictor_pairs(w, obs.records(W0, W1))
    assert [x for p in pairs for x in p] == pytest.approx(
        [0.95, 1.9, 1.52, 1.9, 2.28, 1.9])
    assert _read("predictor_abs_err_pct", w) == pytest.approx(20.0)
    line = next(s for s in w.lines if s.startswith("predictor_abs_err_pct"))
    assert "over 3 rounds" in line
    assert "signed median -20.000%" in line
    assert "under-predicted in 66.667% of rounds" in line
