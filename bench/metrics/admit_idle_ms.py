"""Device idle inside the program's engine.admit spans over the traced
window, per admission, in ms (``harness/program_spans.py``)."""

from harness import program_spans


def read(w):
    return program_spans.admit_idle_ms(w)
