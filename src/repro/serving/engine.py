"""Continuous-batching decode engine (real-compute path).

This is the decode *instance* of the disaggregated deployment (paper §2.1):
prefill runs out-of-band (a separate instance; here a jitted prefill call),
decode proceeds in rounds over a fixed slot array with continuous batching.
Harli's scheduler hooks the round boundary (``decode_round(step)``) to
co-schedule finetune layer-units; the discrete-event counterpart used for
paper-scale experiments lives in core/simulator.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving.kv_cache import PageTableManager, spec_for
from repro.serving.request import Phase, Request


@dataclasses.dataclass
class EngineMetrics:
    decode_rounds: int = 0
    tokens_out: int = 0
    prefills: int = 0
    rejected_admissions: int = 0


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 s_max: int = 256, enc_len: int = 0, use_kernels: bool = False,
                 page_tokens: int = 16, num_pages: Optional[int] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.s_max = s_max
        self.enc_len = enc_len
        self.rng = np.random.default_rng(seed)
        self.cache = MD.init_cache(cfg, max_slots, s_max, enc_len=enc_len)
        self.metrics = EngineMetrics()
        # page accounting (Harli's allocator plugs in via set_usable)
        npages = num_pages or max_slots * (-(-s_max // page_tokens))
        self.pages = PageTableManager(spec_for(cfg, npages, page_tokens),
                                      max_slots, -(-s_max // page_tokens))
        self.slots: List[Optional[Request]] = [None] * max_slots
        self.last_token = np.zeros((max_slots,), np.int32)
        # cache entries each slot's prefill wrote: frontend + prompt tokens
        self.prefilled = np.zeros((max_slots,), np.int32)

        # named for the programs they show as in a profile: jit_prefill,
        # jit__insert_slot, jit_decode
        def prefill(p, b, c):
            return MD.prefill(p, cfg, b, c)

        def decode(p, t, q, c):
            return MD.decode_step(p, cfg, t, q, c, use_kernels=use_kernels)

        self._prefill = jax.jit(prefill)
        self._insert = jax.jit(_insert_slot, donate_argnums=0)
        # the cache is donated, as to _insert: decode writes each new token
        # into it in place, where a kept input would cost a whole copy
        self._decode = jax.jit(decode, donate_argnums=3)

    # ------------------------------------------------------------- admit --
    def try_admit(self, req: Request, prompt_tokens: np.ndarray,
                  extras: Optional[Dict] = None) -> bool:
        with obs.span("engine.admit", rid=req.rid,
                      prompt_len=req.prompt_len) as sp:
            slot = next((i for i, s in enumerate(self.slots) if s is None),
                        None)
            n_front = (len(extras["frontend"]) if extras and "frontend" in
                       extras and self.cfg.frontend != "none" else 0)
            if slot is None or not self.pages.admit(slot,
                                                    n_front + req.prompt_len):
                self.metrics.rejected_admissions += 1
                sp.set(admitted=False)
                return False
            req.slot, req.phase = slot, Phase.PREFILLING
            self.slots[slot] = req
            self.prefilled[slot] = n_front + req.prompt_len
            with obs.span("engine.admit.cache"):
                one_cache = MD.init_cache(self.cfg, 1, self.s_max,
                                          enc_len=self.enc_len)
            with obs.span("engine.admit.prefill"):
                batch = {"tokens": jnp.asarray(prompt_tokens[None, :])}
                if extras:
                    batch.update({k: jnp.asarray(v)[None]
                                  for k, v in extras.items()})
                logits, one_cache = self._prefill(self.params, batch,
                                                  one_cache)
            with obs.span("engine.admit.insert"):
                self.cache = self._insert(self.cache, one_cache,
                                          jnp.int32(slot))
            with obs.span("engine.admit.first_token"):
                # the host waits here for the prefill and the insert
                tok = int(jnp.argmax(logits[0]))
            self.last_token[slot] = tok
            req.generated = 1
            req.phase = Phase.DECODING
            self.metrics.prefills += 1
            self.metrics.tokens_out += 1
            sp.set(admitted=True)
            return True

    # ------------------------------------------------------------- rounds --
    def active_requests(self) -> List[Request]:
        return [r for r in self.slots if r is not None and
                r.phase == Phase.DECODING]

    def decode_round(self, step: Optional[Callable] = None) -> Dict[int, int]:
        """One decode step over all active slots. Returns {rid: token}.

        ``step(tokens, positions, cache) -> (logits, cache)`` replaces the
        engine's own decode program; the co-located runner passes its fused
        decode + k finetune units variant here."""
        active = [(i, r) for i, r in enumerate(self.slots)
                  if r is not None and r.phase == Phase.DECODING]
        if not active:
            return {}
        if step is None:
            step = functools.partial(self._decode, self.params)
        with obs.span("engine.round", bs=len(active)):
            with obs.span("engine.round.inputs"):
                tokens = jnp.asarray(self.last_token)
                positions = np.zeros((self.max_slots,), np.int32)
                for i, r in active:
                    # the newest token (not yet in the cache) goes right
                    # after the prefilled entries and the generated - 1
                    # decoded before it, so each slot's cache stays
                    # contiguous from 0
                    positions[i] = self.prefilled[i] + r.generated - 1
                positions = jnp.asarray(positions)
            with obs.span("engine.round.step"):
                logits, self.cache = step(tokens, positions, self.cache)
            with obs.span("engine.round.pull"):
                # the host waits here for the round's device work
                next_tokens = np.asarray(jnp.argmax(logits, axis=-1),
                                         np.int32)
            with obs.span("engine.round.commit"):
                return self._commit(active, next_tokens)

    def _commit(self, active, next_tokens) -> Dict[int, int]:
        """Page extends, token bookkeeping and releases after a round."""
        out: Dict[int, int] = {}
        self.metrics.decode_rounds += 1
        for i, r in active:
            if not self.pages.extend(r.slot, 1):
                continue  # memory pressure: request stalls this round
            self.last_token[i] = next_tokens[i]
            r.generated += 1
            self.metrics.tokens_out += 1
            out[r.rid] = int(next_tokens[i])
            if r.generated >= r.max_new_tokens or \
                    self.prefilled[i] + r.generated >= self.s_max - 1:
                r.phase = Phase.DONE
                self.pages.release(r.slot)
                self.slots[i] = None
        return out

    # ---------------------------------------------------------------- run --
    def run_trace(self, reqs: List[Request], vocab: Optional[int] = None,
                  max_rounds: int = 10_000) -> EngineMetrics:
        """Drive the engine to completion in round-order (arrival order)."""
        vocab = vocab or self.cfg.vocab_size
        pending = sorted(reqs, key=lambda r: r.arrival)
        qi = 0
        rounds = 0
        while rounds < max_rounds:
            while qi < len(pending):
                r = pending[qi]
                toks = self.rng.integers(0, vocab, size=r.prompt_len,
                                         dtype=np.int32)
                extras = self._stub_extras(r)
                if self.try_admit(r, toks, extras):
                    qi += 1
                else:
                    break
            if not self.active_requests() and qi >= len(pending):
                break
            self.decode_round()
            rounds += 1
        return self.metrics

    def _stub_extras(self, req: Request) -> Optional[Dict]:
        cfg = self.cfg
        if cfg.frontend == "vision" and cfg.frontend_tokens:
            return {"frontend": self.rng.normal(
                size=(cfg.frontend_tokens, cfg.d_model)).astype(np.float32)}
        if cfg.enc_layers:
            return {"enc_frames": self.rng.normal(
                size=(max(self.enc_len, 1), cfg.d_model)).astype(np.float32)}
        return None


def _insert_slot(cache, one_cache, slot):
    """Write a one-request cache into batch row ``slot``. The scanned stack
    carries the layer axis first, so its batch axis is 1; the unrolled
    pre/post layers have batch at axis 0."""
    def at(axis):
        return lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis)
    return {"pre": jax.tree.map(at(0), cache["pre"], one_cache["pre"]),
            "scan": jax.tree.map(at(1), cache["scan"], one_cache["scan"]),
            "post": jax.tree.map(at(0), cache["post"], one_cache["post"])}
