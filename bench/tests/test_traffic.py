import json

import numpy as np
import pytest

from harness import spec, traffic as T

BIG_SEEDS = (0, 2**31 + 7, 3_000_000_001)


def _cells():
    b = spec.Bench()
    return [(w["name"], b.config(w["config"]), b.traffic(w["traffic"]),
             b.rate(w["name"])) for w in b.benchmark["workloads"]]


@pytest.mark.parametrize("name,conf,tr,rate", _cells(), ids=lambda x: x
                         if isinstance(x, str) else "")
def test_every_request_fits_its_slot(name, conf, tr, rate):
    s_max = conf["serving"]["s_max"]
    for seed in BIG_SEEDS:
        plan = T.schedule(tr, rate, 30.0, seed, s_max)
        assert max(p.prompt_len + p.max_new_tokens for p in plan) <= s_max - 1
        assert all(p.prompt_len % tr["prompt"]["round_to"] == 0 for p in plan)
        assert all(p.max_new_tokens >= 1 for p in plan)


@pytest.mark.parametrize("name,conf,tr,rate", _cells(), ids=lambda x: x
                         if isinstance(x, str) else "")
def test_same_seed_repeats_and_seeds_shuffle_one_population(name, conf, tr,
                                                            rate):
    s_max = conf["serving"]["s_max"]
    a = T.schedule(tr, rate, 30.0, BIG_SEEDS[1], s_max)
    b = T.schedule(tr, rate, 30.0, BIG_SEEDS[1], s_max)
    c = T.schedule(tr, rate, 30.0, BIG_SEEDS[2], s_max)
    assert a == b
    assert [(p.due, p.prompt_len) for p in a] != \
        [(p.due, p.prompt_len) for p in c]
    for plan in (a, c):
        win = [p for p in plan if p.window]
        pre = tr["pre_window_s"]
        assert len(win) == round(rate * 30.0)
        assert all(pre <= p.due < pre + 30.0 for p in win)
    for f in ("prompt_len", "max_new_tokens"):
        assert sorted(getattr(p, f) for p in a if p.window) == \
            sorted(getattr(p, f) for p in c if p.window)


def test_refuses_traffic_that_does_not_fit():
    tr = json.loads(json.dumps(spec.Bench().traffic("conv_steady")))
    tr["output"]["cap"] = 4096
    tr["output"]["median"] = 4000
    with pytest.raises(ValueError, match="s_max"):
        T.schedule(tr, 1.0, 30.0, 1, 4096)


def test_envelope_keeps_count_and_bunches_arrivals():
    tr = json.loads(json.dumps(spec.Bench().traffic("conv_steady")))
    tr["arrival"] = {"shape": 0.25, "amplitude": 0.9, "period_s": 20.0}
    plan = [p for p in T.schedule(tr, 2.0, 40.0, 5, 4096) if p.window]
    due = np.array([p.due for p in plan]) - tr["pre_window_s"]
    assert len(due) == 80
    # the first half-period of the sine carries more arrivals than the second
    per = tr["arrival"]["period_s"]
    phase = (due % per) / per
    assert (phase < 0.5).sum() > 1.5 * (phase >= 0.5).sum()


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json")))
def test_every_mix_fits_every_configuration(traffic):
    b = spec.Bench()
    tr = b.traffic(traffic)
    for c in b.benchmark["configs"]:
        s_max = b.config(c["name"])["serving"]["s_max"]
        assert tr["prompt"]["cap"] + tr["output"]["cap"] <= s_max - 1
        assert tr["prompt"]["cap"] % tr["prompt"]["round_to"] == 0
