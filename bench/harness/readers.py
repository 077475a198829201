"""Arithmetic that more than one metric reader shares."""

from __future__ import annotations

from typing import Optional

import numpy as np

ROUND_PROGRAM = "jit_step"      # ColocatedRunner's jitted round


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile of all samples (linear interpolation)."""
    return float(np.percentile(values, q)) if len(values) else None


def ttft_p90_ms(w) -> Optional[float]:
    """90th percentile, over every request due in the window, of first
    token minus due time. A request with no first token by the window's
    end counts with its wait so far (end - due): a lower bound, so a stall
    shows."""
    w1 = w.rec["w1"]
    first = [r.token_times[0] if r.token_times and r.token_times[0] <= w1
             else None for r in w.rec["due"]]
    t = [(w1 if f is None else f) - r.due
         for f, r in zip(first, w.rec["due"])]
    w.info(f"ttft_p90_ms over {len(t)} requests, "
           f"{sum(f is None for f in first)} censored")
    v = percentile(t, 90)
    return None if v is None else 1e3 * v


def round_mfu(w) -> Optional[float]:
    """Model FLOPs of the traced window's rounds over the device time of
    their co-located programs times the chip's bf16 peak, in %."""
    if w.trace is None:
        return None
    n, dev_s = w.trace.module_time(ROUND_PROGRAM)
    rounds = w.rec["rounds"]
    if not n or not rounds:
        seen = sorted({m[0] for m in w.trace.modules})[:8]
        w.info(f"colo_round_mfu: no {ROUND_PROGRAM} program traced; "
               f"modules seen {seen}")
        return None
    if n != len(rounds):
        w.info(f"colo_round_mfu: {n} {ROUND_PROGRAM} programs traced for "
               f"{len(rounds)} rounds")
    flops = sum(w.cell.arch.counts.round_flops(w.cell, r) for r in rounds)
    return 100.0 * flops / (dev_s * w.peaks["bf16_flops_per_s"])
