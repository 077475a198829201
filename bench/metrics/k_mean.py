"""Mean finetune quantum k that QoSScheduler.pick chose over the window's
decode rounds."""

import numpy as np


def read(w):
    r = w.rec["rounds"]
    return float(np.mean([x.k for x in r])) if r else None
