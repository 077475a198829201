"""Reduction of a JAX profiler trace to what the per-layer metrics read.

Device events come from the TPU planes' "XLA Ops" line (one event per
operation executed) and "XLA Modules" line (one per program run); host
spans are the harness's own ``TraceAnnotation`` names, on the same clock.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HOST_SPANS = ("window", "generate", "admit", "pick", "round", "wait")


@dataclasses.dataclass
class Reduced:
    n_devices: int
    window: Tuple[float, float]            # seconds, trace clock
    busy_s: float                          # mean over devices
    op_s: Dict[str, float]                 # op name -> device seconds
    modules: List[Tuple[str, float, float]]  # (name, start, seconds)
    idle_by_span: Dict[str, float]
    idle_gaps: List[Tuple[float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_time(self, prefix: str) -> float:
        return sum(v for k, v in self.op_s.items() if k.startswith(prefix))

    def module_time(self, prefix: str) -> Tuple[int, float]:
        ev = [d for n, _, d in self.modules if n.startswith(prefix)]
        return len(ev), sum(ev)

    def breakdown(self, top: int = 10) -> Dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the trace
    names each op by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals: Sequence[Tuple[float, float]]):
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce(path: Path, device_prefix: str = "/device:TPU:") -> Reduced:
    """Read one ``.xplane.pb``. The window is the harness's "window" span;
    only device time inside it counts."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    win = [(a, b) for n, a, b in spans if n == "window"]
    if not win or not devices:
        raise ValueError(f"{path}: no window span or no {device_prefix} "
                         f"plane")
    w0, w1 = min(a for a, _ in win), max(b for _, b in win)
    op_s: Dict[str, float] = defaultdict(float)
    modules: List[Tuple[str, float, float]] = []
    busy_total, first_busy = 0.0, None
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if b <= w0 or a >= w1:
                    continue
                a, b = max(a, w0), min(b, w1)
                if line.name == "XLA Ops":
                    op_s[op_name(ev.name)] += b - a
                    ivs.append((a, b))
                else:
                    modules.append((ev.name, a, b - a))
        busy = _union(ivs)
        busy_total += sum(b - a for a, b in busy)
        if first_busy is None:
            first_busy = busy
    gaps, prev = [], w0
    for a, b in first_busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    by_kind: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for n, a, b in spans:
        if n != "window":
            by_kind[n].append((a, b))
    for v in by_kind.values():
        v.sort()
    idle: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        owner = _innermost(by_kind, 0.5 * (a + b))
        idle[owner or "other"] += b - a
    return Reduced(len(devices), (w0, w1), busy_total / len(devices),
                   dict(op_s), modules, dict(idle), gaps)


def _innermost(by_kind: Dict[str, List[Tuple[float, float]]], t: float
               ) -> Optional[str]:
    """The shortest host span holding t (spans of one kind never overlap:
    the loop is one thread)."""
    best = None
    for name, ivs in by_kind.items():
        i = bisect.bisect_right(ivs, (t, float("inf"))) - 1
        if i >= 0 and ivs[i][0] <= t <= ivs[i][1]:
            d = ivs[i][1] - ivs[i][0]
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else None
