"""Open-loop request schedule for one run, from a traffic file and a seed.

The arithmetic is that of ``serving/trace.py`` (Gamma inter-arrival gaps of
mean 1/rate with shape ``shape``, a sine rate envelope, capped lognormal
prompt and output lengths), kept here so that a change to the program does
not move the yardstick. Two things differ, both for steadiness:

* Each stretch (the pre-window stretch and the measured window) draws its
  population of gaps and lengths once, from the traffic file's
  ``population_seed``. The run's seed shuffles them: every seed offers the
  same requests, the same count and the same total work, in another order.
* Prompt lengths are rounded up to a multiple of ``round_to`` (the engine
  compiles one prefill program per prompt length), and every request fits
  its slot by construction: prompt + output <= s_max - 1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due: float            # seconds after the stretch opens
    prompt_len: int
    max_new_tokens: int
    window: bool          # due inside the measured window


def _population(traffic: Dict, rate: float, n: int, salt: int):
    """n gaps (mean 1/rate, operational time) and lengths, as trace.py
    draws them, from the traffic's fixed population seed."""
    a, p, o = traffic["arrival"], traffic["prompt"], traffic["output"]
    rng = np.random.default_rng([traffic["population_seed"], salt])
    gaps = rng.gamma(a["shape"], 1.0 / (rate * a["shape"]), size=n)
    prompts = np.minimum(rng.lognormal(math.log(p["median"]), p["sigma"],
                                       size=n), p["cap"]).astype(np.int64)
    outputs = np.minimum(rng.lognormal(math.log(o["median"]), o["sigma"],
                                       size=n), o["cap"]).astype(np.int64)
    step = p["round_to"]
    prompts = np.minimum(-(-np.maximum(prompts, 1) // step) * step, p["cap"])
    return gaps, prompts, np.maximum(outputs, 1)


def _envelope_times(op_times: np.ndarray, duration: float, amplitude: float,
                    period: float) -> np.ndarray:
    """Map arrivals spread evenly in operational time onto real time under
    the rate envelope 1 + amplitude * sin(2 pi t / period), phase 0 at the
    stretch's start, by inverting its integral on a fine grid."""
    if amplitude == 0.0:
        return op_times
    grid = np.linspace(0.0, duration, 20001)
    lam = grid + amplitude * period / (2 * math.pi) * (
        1.0 - np.cos(2 * math.pi * grid / period))
    return np.interp(op_times * lam[-1] / duration, lam, grid)


def stretch(traffic: Dict, rate: float, duration: float, seed: int,
            salt: int) -> List[tuple]:
    """(due, prompt_len, max_new_tokens) for one stretch of ``duration``
    seconds: round(rate * duration) requests, all due inside it."""
    n = max(int(round(rate * duration)), 1)
    gaps, prompts, outputs = _population(traffic, rate, n, salt)
    rng = np.random.default_rng([seed, salt])
    gaps = gaps[rng.permutation(n)]
    prompts = prompts[rng.permutation(n)]
    outputs = outputs[rng.permutation(n)]
    # scale the gaps so the n-th arrival falls one mean gap before the end
    op = np.cumsum(gaps) * duration / (gaps.sum() + duration / n)
    a = traffic["arrival"]
    due = _envelope_times(op, duration, a["amplitude"], a["period_s"])
    return list(zip(due.tolist(), prompts.tolist(), outputs.tolist()))


def schedule(traffic: Dict, rate: float, window_s: float, seed: int,
             s_max: int) -> List[Planned]:
    """The pre-window stretch and then the window, due times relative to
    the pre-window's start. Raises if any request does not fit its slot."""
    pre = traffic["pre_window_s"]
    out: List[Planned] = []
    for salt, (t0, dur, win) in enumerate(((0.0, pre, False),
                                           (pre, window_s, True))):
        for due, pl, on in stretch(traffic, rate, dur, seed, salt):
            out.append(Planned(len(out), t0 + due, int(pl), int(on), win))
    worst = max(r.prompt_len + r.max_new_tokens for r in out)
    if worst > s_max - 1:
        raise ValueError(f"traffic draws prompt + output = {worst} tokens, "
                         f"more than s_max - 1 = {s_max - 1}")
    return out
