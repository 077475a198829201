"""Sweep of offered rates for one cell, to find its knee: the highest rate
the system sustains with no growing backlog. On the chip:

    python3 bench/knee.py --workload <config>.<traffic> --seconds <s> \
        RATE [RATE ...]

For each rate, in one process: the cell's set-up and pre-window stretch,
then a window of ``--seconds`` at that rate, with the cell's traffic file
and seed 1. Prints one JSON line per rate: requests due, admitted and
finished in the window, the backlog (due and not admitted) at its close,
the median time to first token of the window's first and second halves,
decode tokens and finetune units per second, the mean round and the mean
number of active slots. No output is checked. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import json
import sys

import numpy as np

import run as RUN  # noqa: F401  (puts bench/ and src/ on sys.path)


def summary(cell, rec) -> dict:
    w0, w1 = rec["w0"], rec["w1"]
    due = rec["due"]
    mid = 0.5 * (w0 + w1)
    ttft = lambda rs: 1e3 * float(np.median([
        (r.token_times[0] if r.token_times and r.token_times[0] <= w1
         else w1) - r.due for r in rs])) if rs else None
    rounds = rec["rounds"]
    return {
        "due": len(due),
        "admitted": sum(1 for r in due if r.admitted is not None
                        and r.admitted <= w1),
        "finished": sum(1 for r in due if r.finished is not None
                        and r.finished <= w1),
        "backlog_at_close": sum(1 for r in due if r.admitted is None
                                or r.admitted > w1),
        "ttft_p50_first_half_ms": ttft([r for r in due if r.due < mid]),
        "ttft_p50_second_half_ms": ttft([r for r in due if r.due >= mid]),
        "decode_tokens_per_s": len(rec["gaps"]) / rec["window_s"],
        "ft_units_per_s": sum(r.k for r in rounds) / rec["window_s"],
        "round_ms": 1e3 * float(np.mean([r.t1 - r.t0 for r in rounds]))
        if rounds else None,
        "active_slots": float(np.mean([len(r.contexts) for r in rounds]))
        if rounds else None,
        "failed": [r.failed for r in due if r.failed],
    }


def main(argv=None) -> int:
    import argparse

    import jax
    from harness import cell as C, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("FAIL: needs a TPU", file=sys.stderr)
        return 1
    RUN.use_compile_cache(jax)
    bench = spec.Bench()
    config, traffic = args.workload.split(".", 1)  # need not be a cell yet
    conf, traffic = bench.config(config), bench.traffic(traffic)
    arch = bench.arch(conf)
    compiles = C.CompileCounter()
    for rate in args.rates:
        t0 = C.clock()
        cell = C.Cell(conf, arch, traffic, rate, 1, args.seconds, compiles)
        w0, w1 = cell.serve()
        out = {"rate_per_s": rate,
               **summary(cell, C.window_records(cell, w0, w1)),
               "seconds": C.clock() - t0}
        cell.free_program()
        del cell
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
