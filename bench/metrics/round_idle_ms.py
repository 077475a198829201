"""Device idle inside the program's sched.pick and engine.round spans over
the traced window, per round, in ms (``harness/program_spans.py``)."""

from harness import program_spans


def read(w):
    return program_spans.round_idle_ms(w)
