"""Serving driver: a decode instance, optionally co-located with a PEFT
(LoRA) finetune job (Harli).

``run()`` is the entry point that ``main()`` and ``chip_smoke.py`` share.
On a TPU it runs a published config at its published widths, cut in depth
only (``--layers``), with the Pallas decode kernel (``--use-kernels``). On the
CPU it runs the reduced ``--smoke`` configs, the kernels in interpret mode.
Paper-scale co-location numbers come from benchmarks/ (cost-model
simulator), not from this driver.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
      --requests 12 --colocate
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-7b --layers 8 \\
      --slots 8 --s-max 1024 --requests 8 --colocate --k-max 2 --use-kernels
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.colocation import ColocatedRunner
from repro.core.costmodel import CostModel, InstanceSpec
from repro.core.predictor import TwoStageLatencyPredictor
from repro.core.scheduler import QoSScheduler, SchedulerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.training import peft as P
from repro.training.data import DataConfig, Prefetcher, SyntheticCorpus


def model_config(arch: str, smoke: bool = False, layers: int = 0
                 ) -> ModelConfig:
    """The published (or smoke) config, cut to ``layers`` layers when given.
    The cut changes depth only, never a width."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    if layers:
        if not 0 < layers <= cfg.num_layers:
            raise ValueError(f"--layers {layers} outside 1..{cfg.num_layers} "
                             f"for {cfg.name}")
        cfg = dataclasses.replace(cfg, num_layers=layers)
    return cfg


# one program per config: the f32 draws fuse into their bf16 casts instead
# of each materializing on the device beside the weights made so far
_init_params = jax.jit(MD.init_params, static_argnums=0)


@dataclasses.dataclass
class ServeResult:
    cfg: ModelConfig
    engine: ServingEngine
    requests: List[Request]
    runner: Optional[ColocatedRunner]
    compiled: Dict[int, jax.stages.Compiled]   # co-located variants by k
    ft_state: Optional[dict]
    units_done: int
    setup_s: float       # weights, finetune state, co-located AOT compiles
    wall_s: float        # the round loop, prefill compiles included


def run(arch: str, *, ft_arch: str = "", smoke: bool = False,
        layers: int = 0, requests: int = 12, slots: int = 4,
        s_max: int = 128, prompt_lens: Sequence[int] = tuple(range(8, 24)),
        new_tokens: Sequence[int] = tuple(range(4, 12)),
        colocate: bool = False, k_max: int = 6, ft_seq: int = 32,
        use_kernels: bool = False) -> ServeResult:
    """Serve ``requests`` requests (prompt and output lengths drawn from
    ``prompt_lens`` / ``new_tokens``) on one instance; with ``colocate``, a
    LoRA job on its own copy of the (``ft_arch``) model runs k layer-units
    per decode round, k picked by the QoS scheduler. Weights, prompts and
    lengths are random, drawn from seed 0."""
    enable_compile_cache()
    t0 = time.perf_counter()
    cfg = model_config(arch, smoke, layers)
    params = _init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=slots, s_max=s_max,
                        enc_len=16 if cfg.enc_layers else 0,
                        use_kernels=use_kernels)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, arrival=i * 0.05,
                    prompt_len=int(rng.choice(prompt_lens)),
                    max_new_tokens=int(rng.choice(new_tokens)))
            for i in range(requests)]

    runner = sched = ft_state = None
    compiled: Dict[int, jax.stages.Compiled] = {}
    if colocate:
        cfg_ft = model_config(ft_arch or arch, smoke, layers)
        params_ft = _init_params(cfg_ft, jax.random.PRNGKey(1))
        pc = P.PeftConfig(micro_batch=2, seq_len=ft_seq, accum=1)
        pf = Prefetcher(SyntheticCorpus(DataConfig(
            cfg_ft.vocab_size, ft_seq, pc.micro_batch,
            enc_frames=16 if cfg_ft.enc_layers else 0,
            d_model=cfg_ft.d_model)).batches(), pc.n_stage)
        ft_state = P.init_ft_state(cfg_ft, pc, params_ft,
                                   jax.random.PRNGKey(2), pf.stacked())
        runner = ColocatedRunner(cfg, params, cfg_ft, params_ft, pc,
                                 k_max=k_max, use_kernels=use_kernels)
        tok = jnp.zeros((slots,), jnp.int32)
        compiled = runner.precompile(tok, tok, eng.cache, ft_state)
        pred = TwoStageLatencyPredictor(k_max=k_max)
        # smoke widths would price a toy: the predictor always sees the
        # published widths at this run's depth
        pred.fit_from_costmodel(CostModel(model_config(arch, layers=layers),
                                          InstanceSpec(tp=2)))
        sched = QoSScheduler(pred, SchedulerConfig(k_max=k_max))
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    pending = sorted(reqs, key=lambda r: r.arrival)
    qi = rounds = units_done = 0
    while rounds < 3000:
        while qi < len(pending):
            r = pending[qi]
            toks = rng.integers(0, cfg.vocab_size, size=r.prompt_len,
                                dtype=np.int32)
            if eng.try_admit(r, toks, eng._stub_extras(r)):
                qi += 1
            else:
                break
        active = eng.active_requests()
        if not active and qi >= len(pending):
            break
        if runner is not None and active:
            ctx = sum(r.context_len for r in active) / len(active)
            k = sched.pick(len(active), ctx, ft_ready=True,
                           ft_units_available=k_max).k

            def colocated(tokens, positions, cache, k=k):
                nonlocal ft_state
                logits, cache, ft_state = runner.run_round(
                    k, tokens, positions, cache, ft_state)
                return logits, cache

            eng.decode_round(colocated)
            units_done += k
        else:
            eng.decode_round()
        rounds += 1
    wall_s = time.perf_counter() - t0
    return ServeResult(cfg, eng, reqs, runner, compiled, ft_state,
                       units_done, setup_s, wall_s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--ft-arch", default="")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep N layers, widths unchanged")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128,
                    help="cache length per slot; a multiple of 64 (the "
                    "kernel page) with --use-kernels")
    ap.add_argument("--colocate", action="store_true")
    ap.add_argument("--k-max", type=int, default=6)
    ap.add_argument("--use-kernels", action="store_true")
    args = ap.parse_args()

    res = run(args.arch, ft_arch=args.ft_arch, smoke=args.smoke,
              layers=args.layers, requests=args.requests, slots=args.slots,
              s_max=args.s_max, colocate=args.colocate, k_max=args.k_max,
              use_kernels=args.use_kernels)
    m = res.engine.metrics
    full = model_config(args.arch, args.smoke).num_layers
    print(f"arch={res.cfg.name} layers={res.cfg.num_layers} of {full} "
          f"rounds={m.decode_rounds} tokens={m.tokens_out} "
          f"prefills={m.prefills} setup={res.setup_s:.1f}s "
          f"wall={res.wall_s:.1f}s")
    if res.runner is not None:
        print(f"colocated finetune units executed: {res.units_done} "
              f"(iterations: {int(res.ft_state['iter'])}, "
              f"last loss: {float(res.ft_state['last_loss']):.4f})")


if __name__ == "__main__":
    main()
