"""The program's own host spans (``repro.obs``), put beside the device trace.

The program records each host step it takes on ``time.perf_counter``, the
clock of the window's ends ``rec["w0"]`` and ``rec["w1"]``. The trace holds
the same window as its "window" span. A line through those two pairs of
points maps one clock onto the other, a difference in rate included. Each
instant of device idle (``Reduced.idle_gaps``) is then charged to the
innermost program span open at that instant; an instant no program span
holds is "outside". The harness's own split (``Reduced.idle_by_span``)
charges each whole gap to its own span at the gap's midpoint instead.

A program without ``repro.obs`` has no spans: the readers then return None.
"""

from __future__ import annotations

import importlib
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROUND_PARTS = ("sched.pick", "engine.round", "colo.round", "colo.compile")
ADMIT = "engine.admit"


def window_records(w) -> Optional[List]:
    """The program's span records that overlap the window, or None (with
    an info line) where the program keeps none or its ring no longer holds
    the whole window."""
    try:
        ring = importlib.import_module("repro.obs").RING
    except ImportError:
        w.info("program spans: the program has no repro.obs ring")
        return None
    recs = ring.records(w.rec["w0"], w.rec["w1"])
    if recs is None:
        w.info("program spans: the ring no longer holds the whole window")
    return recs


def to_trace_clock(w, t: float) -> float:
    """A time on ``time.perf_counter`` on the trace's clock, through the
    window's two ends."""
    w0, w1 = w.rec["w0"], w.rec["w1"]
    a, b = w.trace.window
    return a + (t - w0) * (b - a) / (w1 - w0)


def innermost(spans: Sequence[Tuple[float, float, int]]
              ) -> List[Tuple[float, float, int]]:
    """Sorted, disjoint (start, end, key) pieces of time, each with the
    innermost span open over it. Spans nest (one thread); a span that
    outlives the one around it is cut at that one's end."""
    out: List[Tuple[float, float, int]] = []
    stack: List[Tuple[float, int]] = []          # (end, key)
    cur = -np.inf

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, key = stack.pop()
            if end > cur:
                out.append((cur, end, key))
                cur = end

    for a, b, key in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack and a > cur:
            out.append((cur, a, stack[-1][1]))
        cur = max(cur, a)
        stack.append((min(b, stack[-1][0]) if stack else b, key))
    close_until(np.inf)
    return out


def charge(gaps: Sequence[Tuple[float, float]],
           pieces: Sequence[Tuple[float, float, int]]
           ) -> Tuple[Dict[int, float], float]:
    """Idle seconds of each key over sorted, disjoint gaps, and the idle
    that no piece holds."""
    idle: Dict[int, float] = defaultdict(float)
    outside, j = 0.0, 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        held, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, key = pieces[k]
            over = min(b, e) - max(a, s)
            if over > 0:
                idle[key] += over
                held += over
            k += 1
        outside += (b - a) - held
    return idle, outside


def idle_by_record(w, recs) -> Tuple[Dict[int, float], float]:
    """Device idle in the traced window charged to each record (by index
    into ``recs``) where it is the innermost open span, and the idle
    outside every program span; seconds."""
    spans = [(to_trace_clock(w, r.t0), to_trace_clock(w, r.t1), i)
             for i, r in enumerate(recs)]
    return charge(sorted(w.trace.idle_gaps), innermost(spans))


def in_window(w, r) -> bool:
    return w.rec["w0"] <= r.t1 <= w.rec["w1"]


def idle_split(recs, idle, kinds) -> Dict[str, float]:
    """Idle seconds by span name, over the records whose name is one of
    ``kinds`` or starts with one of them and a dot."""
    out: Dict[str, float] = defaultdict(float)
    for i, v in idle.items():
        name = recs[i].name
        if any(name == k or name.startswith(k + ".") for k in kinds):
            out[name] += v
    return dict(out)


def describe(split: Dict[str, float], parent: str) -> str:
    """``split`` in ms, largest first; ``parent``'s own share as "self"."""
    return ", ".join(
        f"{'self' if n == parent else n.removeprefix(parent + '.')} "
        f"{1e3 * v:.3f} ms"
        for n, v in sorted(split.items(), key=lambda kv: -kv[1]))


def round_idle_ms(w) -> Optional[float]:
    """Device idle inside the window's ``sched.pick`` and ``engine.round``
    spans (children included), over the window's rounds, in ms."""
    if w.trace is None:
        return None
    recs = window_records(w)
    if recs is None:
        return None
    rounds = sum(1 for r in recs if r.name == "engine.round"
                 and in_window(w, r))
    if not rounds:
        w.info("round_idle_ms: no engine.round span in the window")
        return None
    idle, outside = idle_by_record(w, recs)
    split = idle_split(recs, idle, ROUND_PARTS)
    total = sum(split.values())
    harness = sum(w.trace.idle_by_span.get(k, 0.0)
                  for k in ("round", "pick"))
    w.info(f"round_idle_ms: {1e3 * total:.3f} ms over {rounds} rounds "
           f"({len(w.rec['rounds'])} in the harness's records); harness's "
           f"idle in round + pick {1e3 * harness:.3f} ms; by innermost "
           f"span: {describe(split, 'engine.round')}; outside every "
           f"program span {1e3 * outside:.3f} ms")
    return 1e3 * total / rounds


def admit_idle_ms(w) -> Optional[float]:
    """Device idle inside the window's ``engine.admit`` spans (children
    included), over the window's admissions, in ms."""
    if w.trace is None:
        return None
    recs = window_records(w)
    if recs is None:
        return None
    admitted = sum(1 for r in recs if r.name == ADMIT and in_window(w, r)
                   and r.attrs.get("admitted"))
    if not admitted:
        w.info("admit_idle_ms: no admission in the window")
        return None
    idle, _ = idle_by_record(w, recs)
    split = idle_split(recs, idle, (ADMIT,))
    total = sum(split.values())
    w.info(f"admit_idle_ms: {1e3 * total:.3f} ms over {admitted} "
           f"admissions ({len(w.rec['admissions'])} in the harness's "
           f"records); harness's idle in admit "
           f"{1e3 * w.trace.idle_by_span.get('admit', 0.0):.3f} ms; by "
           f"innermost span: {describe(split, ADMIT)}")
    return 1e3 * total / admitted


def predictor_pairs(w, recs) -> List[Tuple[float, float]]:
    """(predicted, measured) seconds for each window round: the
    ``predicted_s`` of the ``sched.pick`` that decided it, and the host
    duration of its ``engine.round``."""
    pairs, pick = [], None
    for r in sorted(recs, key=lambda r: r.t0):
        if r.name == "sched.pick":
            pick = r
        elif r.name == "engine.round":
            if pick is not None and in_window(w, r):
                pairs.append((float(pick.attrs["predicted_s"]),
                              r.t1 - r.t0))
            pick = None
    return pairs


def predictor_abs_err_pct(w) -> Optional[float]:
    """Median over the window's rounds of |predicted - measured| /
    measured, in %."""
    recs = window_records(w)
    if recs is None:
        return None
    pairs = predictor_pairs(w, recs)
    if not pairs:
        w.info("predictor_abs_err_pct: no decided round in the window")
        return None
    rel = np.array([(p - m) / m for p, m in pairs])
    w.info(f"predictor_abs_err_pct over {len(rel)} rounds: signed median "
           f"{100 * float(np.median(rel)):.3f}%, under-predicted in "
           f"{100 * float(np.mean(rel < 0)):.3f}% of rounds; predicted "
           f"median {1e3 * float(np.median([p for p, _ in pairs])):.3f} ms, "
           f"measured median "
           f"{1e3 * float(np.median([m for _, m in pairs])):.3f} ms")
    return 100.0 * float(np.median(np.abs(rel)))
