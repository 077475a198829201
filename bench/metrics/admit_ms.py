"""Mean host-clock time of ServingEngine.try_admit over the window's
admissions (prefill, slot insert, first token pulled to the host)."""

import numpy as np


def read(w):
    a = w.rec["admissions"]
    return 1e3 * float(np.mean([x.t1 - x.t0 for x in a])) if a else None
