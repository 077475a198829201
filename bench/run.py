"""Benchmark entry point: one run of one cell on the chip it is started on.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): weights and finetune data made on the device
from the seed, the finetune state, every co-located variant k = 0..k_max,
one prefill program per prompt length the traffic uses, and a pre-window
stretch of the same traffic that fills the batch and runs the finetune job
through the iterations the check compares. Then the window: ``--seconds`` of
open-loop traffic through the program's own per-round calls. With
``--trace 1`` the window runs under the JAX profiler and the run reports the
cell's per-layer metrics; otherwise its end-to-end metrics. Then, with the
program's state freed, the plain reference checks what the window served and
what the finetune job computed.

The last line of stdout is one JSON object; the last lines of stderr are
the numbers compared, each beside its limit. Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

# The persistent compile cache lives at a fixed path inside the checkout,
# whatever the environment says, so that only a checkout's first run of a
# cell compiles and two checkouts share nothing.
CACHE_DIR = REPO / ".jax_cache"


class NoChip(RuntimeError):
    pass


def use_compile_cache(jax) -> None:
    """Every program, however quick to compile, goes to the cache."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, bench=None, require_tpu: bool = True, compiles=None,
        info=print):
    """One run; returns (result dict, compared lines)."""
    import jax
    import numpy as np
    from harness import cell as C, check, spec

    bench = bench or spec.Bench()
    entry = bench.cell(args.workload)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform} "
                     f"({dev.device_kind})")
    if len(devs) < entry["chips"]:
        raise NoChip(f"the cell asks for {entry['chips']} chips; JAX "
                     f"found {len(devs)}")
    peaks = bench.peaks(dev.device_kind)
    use_compile_cache(jax)
    compiles = compiles or C.CompileCounter()
    info(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
         f"workload {args.workload} seed {args.seed} seconds {args.seconds} "
         f"trace {args.trace}")

    conf = bench.config(entry["config"])
    traffic = bench.traffic(entry["traffic"])
    cell = C.Cell(conf, bench.arch(conf), traffic, bench.rate(args.workload),
                  args.seed, args.seconds, compiles)

    trace_dir = None
    reduced = None

    def pre_open():
        nonlocal trace_dir
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans and device ops only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def post_close():
        if args.trace:
            jax.profiler.stop_trace()

    w0, w1 = cell.serve(pre_open=pre_open, post_close=post_close)
    setup_s = w0 - T_START
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    if trace_dir:
        from harness import trace as TR
        try:
            reduced = TR.reduce(TR.find_xplane(Path(trace_dir)))
            mods = {}
            for name, _, d in reduced.modules:
                mods[name] = mods.get(name, 0.0) + d
            top = sorted(mods.items(), key=lambda kv: -kv[1])[:8]
            info("device programs by time: " + ", ".join(
                f"{n} {s:.4f} s" for n, s in top))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    rec = C.window_records(cell, w0, w1)
    due = rec["due"]
    late = [r.seen - r.due for r in due if r.seen is not None]
    info(f"set-up {setup_s:.3f} s (compiles {cell.window_compiles0}, "
         f"{compiles.seconds:.2f} s); window {rec['window_s']:.3f} s; "
         f"requests due {len(due)}, admitted "
         f"{sum(r.admitted is not None for r in due)}, finished "
         f"{sum(r.finished is not None for r in due)}; rounds "
         f"{len(rec['rounds'])}; token gaps {len(rec['gaps'])}; "
         f"window compiles {cell.window_compiles}")
    if late:
        info(f"generator lateness over {len(late)} requests: mean "
             f"{1e3 * float(np.mean(late)):.3f} ms, max "
             f"{1e3 * float(np.max(late)):.3f} ms")
    ctx = SimpleNamespace(cell=cell, rec=rec, trace=reduced, peaks=peaks,
                          setup_s=setup_s, info=info)
    metrics = {}
    for m in bench.metrics_for(args.workload, bool(args.trace)):
        v = bench.metric_module(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ------------------------------------------------------------ check --
    readings, wrong = correctness(cell, rec, check, C, info)
    limits = conf["limits"]
    over = check.judge(readings, limits)
    for r in due:
        if r.rid in wrong and not r.failed:
            r.failed = "wrong"
    failed = [r for r in due if r.failed]
    for r in failed[:5]:
        info(f"failed request {r.rid}: {r.failed}")
    iters_ok = len(cell.snapshots["losses"]) >= cell.check_iters
    correct = not over and not failed and iters_ok and bool(readings)
    compared = {k: {"value": readings.get(k), "limit": v}
                for k, v in limits.items()}
    result = {
        "correct": correct,
        "attempted": len(due),
        "failed": len(failed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": mem_peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = compared
    lines = [f"check {k}: {v['value']} limit {v['limit']}"
             for k, v in compared.items()]
    return result, lines


def correctness(cell, rec, check, C, info):
    """Readings of the served tokens and the finetune iterations against
    the reference, run once the program's state is freed: the serving copy
    of the weights is dropped before the finetune check. Returns the
    readings and the rids of sampled requests over the serving limit."""
    dm = cell.arch.reference.dims(cell.cfg)
    cell.free_program()
    readings = {}
    t0 = C.clock()
    sample = check.serving_sample(cell, rec)
    wrong = set()
    if sample:
        params = cell.serving_weights()
        readings["serve_gap"], wrong = check.serve_reading(cell, dm, params,
                                                           sample)
        del params
    info(f"serving check: {len(sample)} finished requests, "
         f"{sum(len(r.served) for r in sample)} served tokens, longest "
         f"{max((len(r.served) for r in sample), default=0)}; "
         f"{C.clock() - t0:.2f} s")
    t0 = C.clock()
    ft = check.finetune_reading(cell, dm, cell.finetune_weights())
    if ft is not None:
        for k in ("ft_loss_gap", "ft_grad_gap", "ft_change_gap"):
            readings[k] = ft[k]
        info(f"finetune check: {cell.check_iters} iterations, losses "
             f"program {ft['cand_losses']} reference {ft['ref_losses']}; "
             f"leaves compared {ft['leaves_compared']} of {ft['leaves']}; "
             f"{C.clock() - t0:.2f} s")
    return readings, wrong


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, lines = run(args, info=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
