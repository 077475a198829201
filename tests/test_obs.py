"""Host spans (repro.obs): nesting, the fixed-size ring, attributes, the
scheduler's span, and the spans' place in a profiler trace."""

import time
import tracemalloc
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.costmodel import CostModel, InstanceSpec
from repro.core.predictor import TwoStageLatencyPredictor
from repro.core.scheduler import QoSScheduler, SchedulerConfig


def _since(t0, name=None):
    recs = obs.records(t0)
    return [r for r in recs if name is None or r.name == name]


def test_spans_nest_with_their_parent():
    t0 = time.perf_counter()
    with obs.span("t.outer"):
        with obs.span("t.mid"):
            with obs.span("t.inner"):
                pass
        with obs.span("t.second"):
            pass
    recs = {r.name: r for r in _since(t0)}
    assert recs["t.outer"].parent is None
    assert recs["t.mid"].parent == "t.outer"
    assert recs["t.inner"].parent == "t.mid"
    assert recs["t.second"].parent == "t.outer"
    o, m, i = recs["t.outer"], recs["t.mid"], recs["t.inner"]
    assert o.t0 <= m.t0 <= i.t0 <= i.t1 <= m.t1 <= o.t1
    # the ring holds them in the order they closed
    assert [r.name for r in _since(t0)] == ["t.inner", "t.mid", "t.second",
                                            "t.outer"]


def test_span_keeps_attributes_given_and_set():
    t0 = time.perf_counter()
    with obs.span("t.attrs", rid=7, prompt_len=128) as sp:
        sp.set(admitted=True)
    (r,) = _since(t0, "t.attrs")
    assert r.attrs == {"rid": 7, "prompt_len": 128, "admitted": True}


def test_span_is_recorded_when_its_body_raises():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with obs.span("t.raises"):
            raise ValueError("x")
    assert len(_since(t0, "t.raises")) == 1
    with obs.span("t.after"):
        pass
    assert _since(t0, "t.after")[0].parent is None


def test_ring_drops_the_oldest_and_stays_flat(monkeypatch):
    ring = obs.Ring(capacity=64)
    monkeypatch.setattr(obs, "RING", ring)
    for i in range(64 + 5):
        with obs.span("t.fill", i=i):
            pass
    assert len(ring) == 64
    oldest_end = ring.buf[0][2]
    kept = ring.records(oldest_end)
    assert [r.attrs["i"] for r in kept] == list(range(5, 69))
    # full: what ended before its oldest record may be gone
    assert ring.records(oldest_end - 1e-9) is None

    def fill(n):
        for _ in range(n):
            with obs.span("t.fill", i=0):
                pass

    fill(200)
    tracemalloc.start()
    try:
        fill(200)
        before = tracemalloc.get_traced_memory()[0]
        fill(2000)
        grown = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert len(ring) == 64
    assert grown < 16 * 1024, grown


def test_summary_counts_and_percentiles(monkeypatch):
    ring = obs.Ring(capacity=16)
    monkeypatch.setattr(obs, "RING", ring)
    for d in (0.001, 0.002, 0.003, 0.004):
        ring.buf.append(("t.a", 10.0, 10.0 + d, None, {}))
    s = obs.summary()["t.a"]
    assert s["count"] == 4
    assert s["total_s"] == pytest.approx(0.010)
    assert s["p50_ms"] == pytest.approx(2.5)
    assert s["p95_ms"] == pytest.approx(3.85)


def test_pick_records_the_prediction_it_returned():
    pred = TwoStageLatencyPredictor(k_max=10)
    pred.fit_from_costmodel(CostModel(get_config("llama3-8b"),
                                      InstanceSpec(tp=2), seed=5))
    sched = QoSScheduler(pred, SchedulerConfig(k_max=10))
    t0 = time.perf_counter()
    got = [sched.pick(bs, 800.0, ft_ready=True, ft_units_available=10)
           for bs in (4, 32)]
    got.append(sched.pick(8, 800.0, ft_ready=False, ft_units_available=0))
    recs = _since(t0, "sched.pick")
    assert [r.attrs for r in recs] == [
        {"bs": bs, "k": d.k, "predicted_s": d.predicted_s,
         "reason": d.reason} for bs, d in zip((4, 32, 8), got)]


def test_spans_land_on_the_trace_under_their_bare_names(tmp_path):
    """Each span is a profiler event of its own name; mapped through two
    anchors (the ends of an enclosing span) its ring start lies within
    50 us of its event's."""
    from jax.profiler import ProfileData
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("t.anchor"):
            for i in range(5):
                with obs.span("t.step", i=i) as sp:
                    (x @ x).block_until_ready()
                    sp.set(done=1)
    finally:
        jax.profiler.stop_trace()
    (anchor,) = _since(t0, "t.anchor")
    steps = _since(t0, "t.step")
    events = {}
    pd = ProfileData.from_file(
        str(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]))
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("t."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    assert set(events) == {"t.anchor", "t.step"}
    assert len(events["t.step"]) == len(steps) == 5
    ((e0, d0),) = events["t.anchor"]
    scale = d0 / (anchor.t1 - anchor.t0)
    for r, (e, _) in zip(steps, sorted(events["t.step"])):
        mapped = e0 + (r.t0 - anchor.t0) * scale
        assert abs(mapped - e) < 50e-6, (mapped - e)
