from types import SimpleNamespace

import numpy as np
import pytest

from harness import cell as C, spec


def _read(name, w):
    return spec.Bench().metric_module(name).read(w)


def _w():
    # two requests due in the window [10, 20]: one with 1 + 4 tokens, one
    # that got no first token before the window closed (censored)
    a = C.Req(1, 10.0, np.zeros(4, np.int32), 8, True,
              token_times=[10.5, 11.0, 11.1, 11.2, 13.2])
    b = C.Req(2, 19.0, np.zeros(4, np.int32), 8, True)
    old = C.Req(0, 5.0, np.zeros(4, np.int32), 8, False,
                token_times=[6.0, 9.9, 10.4])
    reqs = [old, a, b]
    rec = {"w0": 10.0, "w1": 20.0, "window_s": 10.0, "due": [a, b],
           "gaps": [y - x for r in reqs
                    for x, y in zip(r.token_times, r.token_times[1:])
                    if 10.0 <= y <= 20.0],
           "rounds": [], "admissions": []}
    return SimpleNamespace(rec=rec, info=lambda s: None)


def test_tpot_and_itl_are_over_all_gaps():
    w = _w()
    gaps = [0.5, 0.5, 0.1, 0.1, 2.0]          # old's last gap ends at 10.4
    assert sorted(w.rec["gaps"]) == pytest.approx(sorted(gaps))
    assert _read("tpot_mean_ms", w) == pytest.approx(1e3 * np.mean(gaps))
    assert _read("itl_p95_ms", w) == pytest.approx(
        1e3 * np.percentile(gaps, 95))


def test_ttft_counts_the_censored_wait():
    # a: 10.5 - 10.0; b: no first token, window end 20 - due 19
    assert _read("ttft_p90_ms.conv_steady", _w()) == pytest.approx(
        1e3 * np.percentile([0.5, 1.0], 90))


def test_ft_tokens_per_s_counts_units_not_iterations():
    w = _w()
    w.cell = SimpleNamespace(tokens_per_unit=107.0)
    w.rec["rounds"] = [C.Round(0, 0, 2, 0, []), C.Round(0, 0, 3, 2, [])]
    assert _read("ft_tokens_per_s", w) == pytest.approx(5 * 107.0 / 10.0)
