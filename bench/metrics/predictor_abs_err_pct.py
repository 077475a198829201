"""Median over the window's rounds of |predicted - measured| / measured, in
%: predicted_s of the sched.pick span that decided the round against the
host duration of its engine.round span (``harness/program_spans.py``)."""

from harness import program_spans


def read(w):
    return program_spans.predictor_abs_err_pct(w)
