"""The reduction of a small profiler trace recorded on a TPU v5e
(``record_trace.py``: a wait, then two rounds of a matmul and a paged
decode-attention call, each followed by an idle "admit" span)."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import spec, trace as T

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(FIXTURE)


def test_busy_and_idle_add_up_to_the_window(reduced):
    assert reduced.n_devices == 1
    assert 0 < reduced.busy_s < reduced.window_s
    idle = sum(b - a for a, b in reduced.idle_gaps)
    assert idle + reduced.busy_s == pytest.approx(reduced.window_s, rel=1e-6)
    share = spec.Bench().metric_module("device_idle_share").read(
        SimpleNamespace(trace=reduced))
    assert share == pytest.approx(100 * idle / reduced.window_s)
    assert 50 < share < 100          # the device idles through admit/wait


def test_kernel_time_is_the_sum_of_its_calls(reduced):
    from jax.profiler import ProfileData
    w0, w1 = reduced.window
    calls = [e.duration_ns * 1e-9
             for p in ProfileData.from_file(str(FIXTURE)).planes
             if p.name.startswith("/device:TPU:")
             for line in p.lines if line.name == "XLA Ops"
             for e in line.events
             if "paged_decode_attention" in e.name
             and w0 <= e.start_ns * 1e-9 <= w1]
    assert len(calls) >= 1
    assert reduced.op_time("paged_decode_attention") == pytest.approx(
        sum(calls))


def test_breakdown_names_ops_and_the_host_span_of_each_gap(reduced):
    b = reduced.breakdown()
    ops = b["device_ops"]
    assert 1 <= len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert all(" " not in n and not n.startswith("%") for n, _ in ops)
    assert any(n.startswith("paged_decode_attention") for n, _ in ops)
    gaps = dict(b["idle_gaps"])
    assert set(gaps) <= set(T.HOST_SPANS) | {"other"}
    assert max(gaps, key=gaps.get) == "admit"


def test_op_name_is_the_instruction_name():
    assert T.op_name("%fusion.12 = bf16[2]{0} fusion(%a), kind=kLoop") == \
        "fusion.12"
    assert T.op_name("while.3") == "while.3"
