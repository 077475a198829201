"""Readings that set a cell's limits, on the chip at the cell's own size:

    python3 bench/control.py --workload <cell> --seconds <s> [--serve-only] \
        SEED [SEED ...]

For each seed, in one process: a run of the cell (set-up, pre-window and a
window of ``--seconds`` at the cell's load), then, with the program's state
freed, each number the check compares, read for

* ``program``: what the timed path produced (as ``run.py`` reads it);
* ``fp8``: the control, the reference in the program's place with every
  matrix product's operands rounded to float8 e4m3: one precision step
  below the bfloat16 the configuration serves in. For serving, the token
  it puts first at each position of the program's prompts and tokens;
* ``half_batch``: the finetune reference on the first row of each
  two-row micro-batch, its mean taken over that row (a planted fault).

A state left unchanged reads 1 in ``ft_change_gap`` by construction and is
not run. ``--serve-only`` reads ``serve_gap`` alone. Each seed's readings
are printed as one JSON line. The benchmark's
own runs never run this. Each line also gives the window's end-to-end
metrics (``setup_s`` aside).
"""

from __future__ import annotations

import json
import sys
from types import SimpleNamespace

import run as RUN  # noqa: F401  (puts bench/ and src/ on sys.path)


def readings(cell, rec, serve_only: bool = False) -> dict:
    """Frees the program's state, then reads every number the check
    compares for the program, the fp8 control and the half-batch fault
    (with ``serve_only``, the serving number alone)."""
    from harness import check

    dm = cell.arch.reference.dims(cell.cfg)
    cell.free_program()
    out = {"program": {}, "fp8": {}, "half_batch": {}}
    sample = check.serving_sample(cell, rec)
    out["sample_requests"] = len(sample)
    out["sample_tokens"] = sum(len(r.served) for r in sample)
    params = cell.serving_weights()
    out["program"]["serve_gap"] = check.serve_reading(
        cell, dm, params, sample)[0]
    out["fp8"]["serve_gap"] = check.serve_reading(
        cell, dm, params, sample, lowp="fp8")[0]
    del params
    if serve_only:
        return out
    params = cell.finetune_weights()
    n = cell.check_iters
    if len(cell.snapshots["losses"]) >= n:
        ref = check.reference_train(cell, dm, params)
        opt = cell.conf["finetune"]["optimizer"]
        cands = {
            "program": check.program_trace(cell.snapshots, n, opt),
            "fp8": check.reference_train(cell, dm, params, lowp="fp8"),
            "half_batch": check.reference_train(cell, dm, params,
                                                rows=slice(0, 1)),
        }
        for name, cand in cands.items():
            got = check.ft_compare(ref, cand, cell.adapters0)
            out[name].update({k: v for k, v in got.items()
                              if k.startswith("ft_")})
            out[name]["losses"] = got["cand_losses"]
        out["ref_losses"] = got["ref_losses"]
    return out


def main(argv=None) -> int:
    import argparse

    import jax
    from harness import cell as C, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--serve-only", action="store_true")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("FAIL: needs a TPU", file=sys.stderr)
        return 1
    RUN.use_compile_cache(jax)
    bench = spec.Bench()
    entry = bench.cell(args.workload)
    conf = bench.config(entry["config"])
    traffic = bench.traffic(entry["traffic"])
    compiles = C.CompileCounter()
    for seed in args.seeds:
        t0 = C.clock()
        cell = C.Cell(conf, bench.arch(conf), traffic,
                      bench.rate(args.workload), seed, args.seconds, compiles)
        w0, w1 = cell.serve()
        rec = C.window_records(cell, w0, w1)
        failed = [r.failed for r in rec["due"] if r.failed]
        ctx = SimpleNamespace(cell=cell, rec=rec, trace=None, peaks=None,
                              setup_s=None, info=lambda s: None)
        window = {m["name"]: bench.metric_module(m["name"]).read(ctx)
                  for m in bench.metrics_for(args.workload, False)
                  if m["name"] != "setup_s"}
        out = {"seed": seed, "failed": failed, "window": window,
               "rounds": len(rec["rounds"]),
               **readings(cell, rec, args.serve_only)}
        out["seconds"] = C.clock() - t0
        print(json.dumps(out), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
