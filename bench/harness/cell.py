"""One run of a cell: set-up, the measured window, and the records the
metrics read.

The window drives the program's own per-round calls, as
``launch/serve.run``'s loop makes them: ``ServingEngine.try_admit`` for each
request whose due time has come, ``QoSScheduler.pick`` for the finetune
quantum k, and ``ServingEngine.decode_round(step)`` with ``step`` being
``ColocatedRunner.run_round(k, ...)``. The predictor is built and fitted as
``serve.run`` fits it. What differs from ``serve.run`` is the traffic: an
open loop whose requests are timed from their due time.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import data as D, traffic as T

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    rid: int
    due: float                  # absolute, clock()
    prompt: np.ndarray
    max_new_tokens: int
    window: bool
    seen: Optional[float] = None     # when the loop generated it
    admitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    served: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    failed: str = ""            # "" | raised | refused | short | wrong
    req: object = None          # the program's Request


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    k: int
    unit0: int                  # finetune unit index before the round
    contexts: List[int]         # keys each active slot attended to


@dataclasses.dataclass
class Admission:
    t0: float
    t1: float


class CompileCounter:
    """Backend compilations (persistent-cache loads included) seen by this
    process, from JAX's monitoring events. Register once per process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration_secs: float, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration_secs


# a host span on the profiler's clock, named for the loop's layer
Span = jax.profiler.TraceAnnotation


class Cell:
    """Everything one run builds, in the order set-up builds it."""

    def __init__(self, conf: Dict, arch, traffic: Dict, rate: float,
                 seed: int, seconds: float, compiles: CompileCounter):
        prog = self.P = program_modules()
        self.conf, self.arch, self.traffic = conf, arch, traffic
        self.seed, self.seconds = seed, seconds
        self.compiles = compiles
        sv, ft, sc = conf["serving"], conf["finetune"], conf["scheduler"]
        self.cfg = arch.weights.model_config(conf, prog)
        self.slots, self.s_max = sv["slots"], sv["s_max"]
        self.k_max = sc["k_max"]
        self.rate = rate
        self.plan = T.schedule(traffic, self.rate, seconds, seed, self.s_max)
        rng = np.random.default_rng([seed, 3])
        self.prompts = [rng.integers(0, self.cfg.vocab_size, r.prompt_len,
                                     dtype=np.int32) for r in self.plan]

        self.pc = prog.P.PeftConfig(micro_batch=ft["micro_batch"],
                                    seq_len=ft["seq_len"], accum=ft["accum"],
                                    n_stage=ft["n_stage"])
        opt = dataclasses.asdict(self.pc.opt)
        if opt != ft["optimizer"]:
            raise ValueError(f"the program's AdamW {opt} is not the "
                             f"file's {ft['optimizer']}")
        self.upi = prog.P.units_per_iteration(self.cfg, self.pc.accum)
        self.tokens_per_unit = (self.pc.micro_batch * self.pc.seq_len
                                * self.pc.accum / self.upi)
        self.check_iters = conf["check"]["iterations"]
        if self.check_iters * self.pc.accum > self.pc.n_stage:
            raise ValueError("n_stage must hold a distinct micro-batch for "
                             "every micro-batch the check compares")

        # weights, adapters and finetune data: the benchmark's, from the seed
        self.params = self.serving_weights()
        self.params_ft = self.finetune_weights()
        self.adapters0 = arch.weights.make_adapters(self.cfg,
                                                    D.jax_key(seed, 2))
        self.data = D.make_ft_data(self.cfg.vocab_size, self.pc.n_stage,
                                   self.pc.micro_batch, self.pc.seq_len, seed)

        # the Pallas kernels on in both programs, as a deployment serves
        self.eng = prog.ServingEngine(self.cfg, self.params,
                                      max_slots=self.slots, s_max=self.s_max,
                                      use_kernels=True)
        st = prog.P.init_ft_state(self.cfg, self.pc, self.params_ft,
                                  D.jax_key(seed, 2), self.data)
        st["adapters"] = jax.tree.map(jnp.copy, self.adapters0)
        self.ft_state = st
        self.runner = prog.ColocatedRunner(self.cfg, self.params, self.cfg,
                                           self.params_ft, self.pc,
                                           k_max=self.k_max,
                                           use_kernels=True)
        tok = jnp.zeros((self.slots,), jnp.int32)
        self.runner.precompile(tok, tok, self.eng.cache, self.ft_state)
        pred = prog.TwoStageLatencyPredictor(k_max=self.k_max)
        # as serve.run: the published widths at this run's depth
        published = dataclasses.replace(prog.get_config(conf["arch"]),
                                        num_layers=self.cfg.num_layers)
        pred.fit_from_costmodel(prog.CostModel(published,
                                               prog.InstanceSpec(tp=2)))
        self.sched = prog.QoSScheduler(pred, prog.SchedulerConfig(
            qos_s=sc["qos_s"], k_max=self.k_max))
        self._warm_prefill()

        self.reqs: List[Req] = []
        self.rounds: List[Round] = []
        self.admissions: List[Admission] = []
        self.units_done = 0
        self.by_rid: Dict[int, Req] = {}
        self.snapshots: Dict = {"losses": []}

    # ------------------------------------------------------------ set-up --
    def _warm_prefill(self):
        """One prefill program per prompt length the traffic uses, the slot
        insert and the eager ops around them, as try_admit calls them."""
        eng, MD = self.eng, self.P.MD
        for n in sorted({r.prompt_len for r in self.plan}):
            batch = {"tokens": jnp.zeros((1, n), jnp.int32)}
            one = MD.init_cache(self.cfg, 1, self.s_max)
            logits, one = eng._prefill(eng.params, batch, one)
            eng.cache = eng._insert(eng.cache, one, jnp.int32(0))
            int(jnp.argmax(logits[0]))
        jax.block_until_ready(eng.cache)

    # -------------------------------------------------------------- loop --
    def _colocated(self, k: int) -> Callable:
        def step(tokens, positions, cache):
            logits, cache, self.ft_state = self.runner.run_round(
                k, tokens, positions, cache, self.ft_state)
            return logits, cache
        return step

    def _snapshot(self):
        """After the round that ran iteration n's optimizer unit: its loss,
        AdamW's first moment after step 1, the adapters after the last
        iteration compared. Later units of iteration n + 1 change none of
        these, and these copies are pulled before the next round donates
        the state."""
        n = len(self.snapshots["losses"]) + 1
        st = self.ft_state
        self.snapshots["losses"].append(float(st["last_loss"]))
        if n == 1:
            self.snapshots["m1"] = jax.device_get(st["opt"]["m"])
        if n == self.check_iters:
            self.snapshots["adapters"] = jax.device_get(st["adapters"])

    def run_loop(self, t_stop: float, last: int, until_iters: int = 0):
        """Serve until ``t_stop`` (and, with ``until_iters``, until that
        many finetune iterations are done, while requests are in flight).
        Only planned requests before index ``last`` arrive."""
        eng, sched = self.eng, self.sched
        Phase = self.P.Phase
        queue = [r for r in self.reqs if r.admitted is None and not r.failed]
        while True:
            now = clock()
            if now >= t_stop and self.units_done // self.upi >= until_iters:
                break
            with Span("generate"):
                while self._next < last and self.plan_abs[self._next] <= now:
                    p = self.plan[self._next]
                    r = Req(p.rid, self.plan_abs[self._next],
                            self.prompts[p.rid], p.max_new_tokens, p.window,
                            seen=now)
                    r.req = self.P.Request(rid=p.rid, arrival=r.due,
                                           prompt_len=p.prompt_len,
                                           max_new_tokens=p.max_new_tokens)
                    self.reqs.append(r)
                    self.by_rid[r.rid] = r
                    queue.append(r)
                    self._next += 1
            while queue:
                r = queue[0]
                t0 = clock()
                with Span("admit"):
                    try:
                        ok = eng.try_admit(r.req, r.prompt)
                    except Exception as e:  # the program raised on it
                        r.failed = f"raised: {type(e).__name__}: {e}"
                        queue.pop(0)
                        continue
                t1 = clock()
                if ok:
                    self.admissions.append(Admission(t0, t1))
                    r.admitted = t1
                    r.token_times.append(t1)
                    r.served.append(int(eng.last_token[r.req.slot]))
                    queue.pop(0)
                elif any(s is None for s in eng.slots):
                    r.failed = "refused"   # a free slot, and still refused
                    queue.pop(0)
                else:
                    break                  # waits for a slot
            active = eng.active_requests()
            if not active:
                if clock() >= t_stop:
                    break
                nxt = self.plan_abs[self._next] if self._next < last \
                    else t_stop
                wait = min(nxt, t_stop) - clock()
                if wait > 0:
                    with Span("wait"):
                        time.sleep(wait)
                continue
            t0 = clock()
            with Span("pick"):
                ctx = sum(a.context_len for a in active) / len(active)
                k = sched.pick(len(active), ctx, ft_ready=True,
                               ft_units_available=self.k_max).k
            t1 = clock()
            live = [self.by_rid[a.rid] for a in active]
            contexts = [int(eng.prefilled[a.slot] + a.generated)
                        for a in active]
            unit0 = self.units_done % self.upi
            with Span("round"):
                out = eng.decode_round(self._colocated(k))
            t2 = clock()
            self.rounds.append(Round(t1, t2, k, unit0, contexts))
            before = self.units_done // self.upi
            self.units_done += k
            for r in live:
                if r.rid in out:
                    r.token_times.append(t2)
                    r.served.append(out[r.rid])
                if r.req.phase == Phase.DONE:
                    r.finished = t2
                    if r.req.generated < r.max_new_tokens:
                        r.failed = (f"short: {r.req.generated} of "
                                    f"{r.max_new_tokens} tokens")
            done = self.units_done // self.upi
            while before < done and \
                    len(self.snapshots["losses"]) < self.check_iters:
                before += 1
                self._snapshot()

    def serve(self, pre_open: Callable[[], None] = lambda: None,
              post_close: Callable[[], None] = lambda: None):
        """The pre-window stretch (part of set-up), then the window.
        Returns the window's (start, end) on the host clock."""
        pre = self.traffic["pre_window_s"]
        n_pre = sum(1 for p in self.plan if not p.window)
        t_base = clock()
        self._next = 0
        self.plan_abs = [t_base + p.due for p in self.plan]
        self.run_loop(t_base + pre, n_pre, until_iters=self.check_iters)
        pre_open()
        # the window's arrivals keep their offsets from its opening
        w0 = clock()
        shift = w0 - (t_base + pre)
        self.plan_abs = [t + (shift if p.window else 0.0)
                         for t, p in zip(self.plan_abs, self.plan)]
        self.window_compiles0 = self.compiles.n
        with Span("window"):
            self.run_loop(w0 + self.seconds, len(self.plan))
        w1 = clock()
        self.window_compiles = self.compiles.n - self.window_compiles0
        post_close()
        return w0, w1

    # ----------------------------------------------------------- teardown --
    def free_program(self):
        """Drop everything the program holds on the device, weights
        included: the reference remakes each copy from the seed when it
        needs it, so only one is resident at a time."""
        self.eng = self.runner = self.ft_state = None
        self.params = self.params_ft = None
        gc.collect()

    def serving_weights(self):
        return self.arch.weights.make_params(self.cfg, D.jax_key(self.seed, 0))

    def finetune_weights(self):
        return self.arch.weights.make_params(self.cfg, D.jax_key(self.seed, 1))


def program_modules():
    """The system under test, imported in one place."""
    from types import SimpleNamespace
    from repro.configs import get_config, smoke_config
    from repro.core.colocation import ColocatedRunner
    from repro.core.costmodel import CostModel, InstanceSpec
    from repro.core.predictor import TwoStageLatencyPredictor
    from repro.core.scheduler import QoSScheduler, SchedulerConfig
    from repro.models import model as MD
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Phase, Request
    from repro.training import peft as P
    return SimpleNamespace(**locals())


def window_records(cell: Cell, w0: float, w1: float) -> Dict:
    """What the metric readers see of one window."""
    rounds = [r for r in cell.rounds if w0 <= r.t1 <= w1]
    gaps = [b - a for r in cell.reqs
            for a, b in zip(r.token_times, r.token_times[1:]) if w0 <= b <= w1]
    # every request due in the window, those the loop had not yet picked up
    # when it closed included (no token: censored, not failed)
    due = [cell.by_rid.get(p.rid) or Req(p.rid, cell.plan_abs[p.rid],
                                         cell.prompts[p.rid],
                                         p.max_new_tokens, True)
           for p in cell.plan if p.window]
    return {"w0": w0, "w1": w1, "window_s": w1 - w0, "rounds": rounds,
            "admissions": [a for a in cell.admissions if w0 <= a.t1 <= w1],
            "gaps": gaps, "due": due}

