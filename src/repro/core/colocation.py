"""Colocated step builder — the TPU analogue of GreenContext SM partitioning.

One jitted XLA program per quantum level k fuses the decode step with k
finetune layer-units. Inside a single program, XLA's scheduler interleaves
the finetune matmuls (MXU-bound) with decode's weight/KV streaming
(DMA-bound) — temporal multiplexing of the same resources the paper splits
spatially. The scheduler dispatches among the precompiled variants each
round, which is the preemption mechanism: k=0 *is* "inference preempts all".

Correctness invariant (tested): running the fused program must be bit-
equivalent to running decode_step and k unit_steps separately.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax

from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.training import peft as P


class ColocatedRunner:
    """Holds the per-quantum compiled variants for one (decode, finetune)
    pair on one instance.

    Both models' weights are arguments of every variant, never closed over:
    a closed-over array is lowered as a program constant, which would embed
    gigabytes of weights in each of the k_max+1 programs and in their
    compile-cache keys. Only the KV cache and the finetune state are
    donated."""

    def __init__(self, cfg_inf: ModelConfig, params_inf,
                 cfg_ft: ModelConfig, params_ft, pc: P.PeftConfig,
                 k_max: int = 10, use_kernels: bool = False,
                 donate: bool = True):
        self.cfg_inf = cfg_inf
        self.cfg_ft = cfg_ft
        self.pc = pc
        self.k_max = k_max
        self._params_inf = params_inf
        self._params_ft = params_ft
        self._use_kernels = use_kernels
        self._compiled: Dict[int, jax.stages.Compiled] = {}
        self._donate = donate

    def _build(self, k: int) -> Callable:
        cfg, cfg_ft, pc = self.cfg_inf, self.cfg_ft, self.pc
        use_kernels = self._use_kernels

        def step(params_inf, params_ft, tokens, positions, cache, ft_state):
            logits, cache = MD.decode_step(params_inf, cfg, tokens, positions,
                                           cache, use_kernels=use_kernels)
            unit_step = P.make_unit_step(cfg_ft, pc, params_ft)
            ft_state = P.run_units(unit_step, ft_state, k)
            return logits, cache, ft_state

        donate = (4, 5) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _clamp(self, k: int) -> int:
        return max(0, min(k, self.k_max))

    def run_round(self, k: int, tokens, positions, cache, ft_state):
        """Run variant k, compiling it first if precompile did not."""
        k = self._clamp(k)
        if k not in self._compiled:
            self._compiled[k] = self.lower(
                k, tokens, positions, cache, ft_state).compile()
        return self._compiled[k](self._params_inf, self._params_ft, tokens,
                                 positions, cache, ft_state)

    def lower(self, k: int, tokens, positions, cache, ft_state):
        """Lower variant k for these arguments (arrays or shape structs;
        the weights are the runner's own)."""
        return self._build(self._clamp(k)).lower(
            self._params_inf, self._params_ft, tokens, positions, cache,
            ft_state)

    def precompile(self, tokens, positions, cache, ft_state,
                   ks: Optional[list] = None) -> Dict[int, jax.stages.Compiled]:
        """AOT-compile the quantum variants (startup, off the critical path)
        into the executables run_round calls."""
        for k in (ks if ks is not None else range(self.k_max + 1)):
            k = self._clamp(k)
            self._compiled[k] = self.lower(
                k, tokens, positions, cache, ft_state).compile()
        return dict(self._compiled)


def make_ft_only_step(cfg_ft: ModelConfig, params_ft, pc: P.PeftConfig,
                      units: int):
    """Free-running finetune burst (bs=0 rounds / SeparateMode instance).
    The weights are an argument of the jitted burst (see ColocatedRunner)."""

    @jax.jit
    def burst(params, ft_state):
        return P.run_units(P.make_unit_step(cfg_ft, pc, params), ft_state,
                           units)

    return functools.partial(burst, params_ft)
