"""Compilations (compile-cache loads included) inside the window."""


def read(w):
    return w.cell.window_compiles
