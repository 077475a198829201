"""Mean gap between consecutive output tokens: every gap of every request
that ends inside the window, summed, over their count."""

import numpy as np


def read(w):
    g = w.rec["gaps"]
    return 1e3 * float(np.mean(g)) if g else None
