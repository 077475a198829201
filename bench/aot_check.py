"""Compile a configuration's co-located k = k_max program and its largest
prefill for a described TPU v5e, with no chip attached, and print
``memory_analysis`` beside the weights, cache and finetune state the
program holds. Needs the TPU compiler, not a TPU:

    JAX_PLATFORMS=cpu python3 bench/aot_check.py qwen2_5_7b_l8 [...]
"""

from __future__ import annotations

import functools
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import cell as C, spec  # noqa: E402

GB = 1e9


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def check(name: str, bench: spec.Bench) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    # the host's backend is the CPU, which would pick the kernels'
    # interpret mode; the described chip compiles them for Mosaic
    ops._interpret = lambda: False
    prog = C.program_modules()
    conf = bench.config(name)
    W = bench.arch(conf).weights
    cfg = W.model_config(conf, prog)
    sv, ft = conf["serving"], conf["finetune"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), t)

    params = put(jax.eval_shape(
        lambda k: W.make_params(cfg, k), jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(functools.partial(
        prog.MD.init_cache, cfg, sv["slots"], sv["s_max"])))
    pc = prog.P.PeftConfig(micro_batch=ft["micro_batch"],
                           seq_len=ft["seq_len"], accum=ft["accum"],
                           n_stage=ft["n_stage"])
    data = {k: jax.ShapeDtypeStruct((pc.n_stage, pc.micro_batch,
                                     pc.seq_len), jnp.int32)
            for k in ("tokens", "labels")}
    ft_state = put(jax.eval_shape(
        lambda p, d: prog.P.init_ft_state(cfg, pc, p, jax.random.PRNGKey(2),
                                          d), params, data))
    print(f"{name}: weights {_bytes(params) / GB:.3f} GB per copy, KV cache "
          f"{_bytes(cache) / GB:.3f} GB, finetune state "
          f"{_bytes(ft_state) / GB:.3f} GB", flush=True)

    k = conf["scheduler"]["k_max"]
    runner = prog.ColocatedRunner(cfg, params, cfg, params, pc, k_max=k,
                                  use_kernels=True)
    tok = jax.ShapeDtypeStruct((sv["slots"],), jnp.int32, sharding=one)
    t0 = time.perf_counter()
    comp = runner.lower(k, tok, tok, cache, ft_state).compile()
    _report(f"co-located k={k}", comp, time.perf_counter() - t0)
    kernel = "tpu_custom_call" in comp.as_text()
    print(f"  decode kernel in the program: {kernel}", flush=True)

    longest = max(bench.traffic(t)["prompt"]["cap"]
                  for t in _traffics(bench, name))
    one_cache = put(jax.eval_shape(functools.partial(
        prog.MD.init_cache, cfg, 1, sv["s_max"])))
    batch = {"tokens": jax.ShapeDtypeStruct((1, longest), jnp.int32,
                                            sharding=one)}
    t0 = time.perf_counter()
    comp = jax.jit(lambda p, b, c: prog.MD.prefill(p, cfg, b, c)).lower(
        params, batch, one_cache).compile()
    _report(f"prefill of {longest} tokens", comp, time.perf_counter() - t0)


def _traffics(bench, config):
    return sorted({w["traffic"] for w in bench.benchmark["workloads"]
                   if w["config"] == config})


def _report(what: str, comp, secs: float) -> None:
    m = comp.memory_analysis()
    print(f"  {what}: compiled in {secs:.1f} s; arguments "
          f"{m.argument_size_in_bytes / GB:.3f} GB, outputs "
          f"{m.output_size_in_bytes / GB:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / GB:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.3f} GB", flush=True)


if __name__ == "__main__":
    jax.config.update("jax_enable_compilation_cache", False)
    b = spec.Bench()
    for n in sys.argv[1:] or [c["name"] for c in b.benchmark["configs"]]:
        check(n, b)
