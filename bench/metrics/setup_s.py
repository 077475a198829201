"""Seconds from the process's start to the window's opening: weights,
finetune state, compiles or compile-cache loads, and the pre-window
stretch of traffic."""


def read(w):
    return w.setup_s
