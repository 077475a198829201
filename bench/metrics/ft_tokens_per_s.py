"""Finetune tokens advanced in the window per second: finetune units
completed in the window's rounds x tokens per unit (micro_batch x seq_len x
accum / units per iteration), over the window's seconds."""


def read(w):
    units = sum(r.k for r in w.rec["rounds"])
    return units * w.cell.tokens_per_unit / w.rec["window_s"]
