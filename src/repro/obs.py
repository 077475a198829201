"""Host spans of the serving path: the program's only tracing.

``span(name, **attrs)`` marks one step of the host's work. It opens a
``jax.profiler.TraceAnnotation``, so the step shows under its bare name in
any profiler trace, on the device trace's clock, for xprof or Perfetto. On
exit it appends ``Record(name, t0, t1, parent, attrs)`` to one process-wide
ring of fixed size on ``time.perf_counter``; ``parent`` is the name of the
span that was open around it (the serving loop is one thread). The ring is
always on: a span costs a few microseconds with no profiler running.

Span names, and the attributes that tie them together::

    engine.admit {rid, prompt_len, admitted}
        .cache  .prefill  .insert  .first_token
    engine.round {bs}
        .inputs  .step [colo.round {k} [colo.compile {k}]]  .pull  .commit
    sched.pick {bs, k, predicted_s, reason}   # decides the next engine.round

``records(t0, t1)`` gives the records of a stretch of time, ``summary()``
count, total, p50 and p95 per name.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# the busiest serving loop (long prompts, ~5 admissions and ~16 rounds a
# second: ~140 spans a second) fills it in about eight minutes; at ~0.4 kB a
# record the full ring holds ~26 MB
CAPACITY = 1 << 16


class Record(NamedTuple):
    name: str
    t0: float                   # time.perf_counter()
    t1: float
    parent: Optional[str]       # the enclosing open span
    attrs: Dict


class Ring:
    """The newest ``capacity`` records, in the order the spans closed
    (end time order: a span closes after the spans it holds). Kept as
    plain tuples; a reader gets ``Record``s."""

    def __init__(self, capacity: int = CAPACITY):
        self.buf: collections.deque = collections.deque(maxlen=capacity)

    def __len__(self) -> int:
        return len(self.buf)

    def records(self, t0: float = -np.inf, t1: float = np.inf
                ) -> Optional[List[Record]]:
        """Records that overlap [t0, t1]; None where the ring is full and
        its oldest record ends after t0, so that it may have dropped some
        of them."""
        buf = self.buf
        if len(buf) == buf.maxlen and buf[0][2] > t0:
            return None
        return [Record._make(r) for r in buf if r[2] >= t0 and r[1] <= t1]

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per span name: count, total seconds, p50 and p95 in ms."""
        by: Dict[str, List[float]] = collections.defaultdict(list)
        for r in self.buf:
            by[r[0]].append(r[2] - r[1])
        return {n: {"count": len(d), "total_s": float(np.sum(d)),
                    "p50_ms": 1e3 * float(np.percentile(d, 50)),
                    "p95_ms": 1e3 * float(np.percentile(d, 95))}
                for n, d in sorted(by.items())}


RING = Ring()
_open: List[str] = []           # names of the spans open now, innermost last


class span:
    """``with span(name, **attrs) as s:``; ``s.set(**attrs)`` adds the
    attributes known only inside the span."""

    __slots__ = ("name", "attrs", "_ann", "_t0", "_parent")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        self._parent = _open[-1] if _open else None
        _open.append(self.name)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.pop()
        RING.buf.append((self.name, self._t0, t1, self._parent,
                          self.attrs))
        return False


def records(t0: float = -np.inf, t1: float = np.inf
            ) -> Optional[List[Record]]:
    return RING.records(t0, t1)


def summary() -> Dict[str, Dict[str, float]]:
    return RING.summary()
