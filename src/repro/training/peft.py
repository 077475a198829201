"""PEFT (LoRA) finetune engine with *layer-wise scheduling units* (paper §6.1).

The paper splits each finetune iteration into per-layer forward/backward
submodels so the scheduler can interleave ~10 ms units between decode tokens.
PyTorch needed explicit submodel surgery for this; in JAX we express the whole
iteration as a state machine whose ``unit_step`` executes exactly one unit via
``lax.switch`` — every unit has the same state signature, so a colocated
program can run ``k`` units per decode round with ``k`` chosen by the
scheduler (core/colocation.py).

Unit sequence for one iteration (accum microbatches, L scanned layers):
  per microbatch: EMBED(+pre fwd) | L x FWD(layer i) | HEAD(loss, post bwd)
                  | L x BWD(layer j) | EMBED_BWD(pre bwd + data advance)
  then:           OPT (AdamW on accumulated adapter grads)

Backward units recompute their layer's forward from the saved layer-input
residual under ``jax.vjp`` (layer-granular activation checkpointing — the
JAX-idiomatic equivalent of the paper's "retain activations in GPU memory",
chosen because it also bounds the co-located memory footprint, §4.3).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import lora as LR
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass(frozen=True)
class PeftConfig:
    micro_batch: int = 2          # paper §8.2: micro-batched to bs=2
    seq_len: int = 1024
    accum: int = 8                # minibatch 16 = 8 x 2 (paper baseline bs)
    n_stage: int = 2              # host-staged microbatch ring depth
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


# ===================================================== full train step ====
def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    use_kernels: bool = False, remat: bool = True):
    """One-shot PEFT train step (grads wrt adapters only) — the ``train_4k``
    dry-run cell and the standalone finetune driver use this."""

    def train_step(params, adapters, opt_state, batch):
        def loss_of(ad):
            loss, metrics = MD.loss_fn(params, cfg, batch, adapters=ad,
                                       use_kernels=use_kernels, remat=remat)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(adapters)
        new_adapters, new_opt = adamw_update(opt_cfg, grads, opt_state,
                                             adapters)
        metrics = dict(metrics, loss=loss)
        return new_adapters, new_opt, metrics

    return train_step


# ===================================================== layer-unit engine ==
def n_units_per_mb(cfg: ModelConfig) -> int:
    _, _, n_scan, _ = MD._plan(cfg)
    return 2 * n_scan + 3


def units_per_iteration(cfg: ModelConfig, accum: int) -> int:
    return accum * n_units_per_mb(cfg) + 1


def init_ft_state(cfg: ModelConfig, pc: PeftConfig, params, key,
                  staged: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    """staged: {"tokens": (n_stage, B, S), "labels": ...} from data.Prefetcher."""
    _, _, n_scan, _ = MD._plan(cfg)
    B, S, d = pc.micro_batch, pc.seq_len, cfg.d_model
    adapters = MD.init_adapters(cfg, key)
    zeros_like_f32 = lambda t: jax.tree.map(
        lambda p: jnp.zeros_like(p, jnp.float32), t)
    state = {
        "adapters": adapters,
        "opt": adamw_init(adapters),
        "grads": zeros_like_f32(adapters),
        "x": jnp.zeros((B, S, d), jnp.bfloat16),
        "residuals": jnp.zeros((n_scan + 1, B, S, d), jnp.bfloat16),
        "data": {k: jnp.asarray(v) for k, v in staged.items()},
        "data_idx": jnp.zeros((), jnp.int32),
        "unit_idx": jnp.zeros((), jnp.int32),
        "loss": jnp.zeros((), jnp.float32),
        "last_loss": jnp.zeros((), jnp.float32),
        "iter": jnp.zeros((), jnp.int32),
        "consumed": jnp.zeros((), jnp.int32),
    }
    if cfg.enc_layers:
        se = staged["enc_frames"].shape[2]
        state["enc_out"] = jnp.zeros((B, se, d), jnp.bfloat16)
    return state


def make_unit_step(cfg: ModelConfig, pc: PeftConfig, params):
    """Build ``unit_step(state) -> state`` executing exactly one unit."""
    pre_kinds, scan_kind, n_scan, post_kinds = MD._plan(cfg)
    scale = LR.lora_scale(cfg)
    upm = n_units_per_mb(cfg)
    total_units = units_per_iteration(cfg, pc.accum)

    def positions(B, S):
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def current_batch(state):
        idx = state["data_idx"] % pc.n_stage
        return {k: v[idx] for k, v in state["data"].items()}

    # ---------------- front stack (embed + pre layers [+ encoder]) -------
    def front(state, pre_ads):
        batch = current_batch(state)
        b = dict(tokens=batch["tokens"])
        if "frontend" in batch:
            b["frontend"] = batch["frontend"]
        x, pos, off = MD._embed_inputs(params, cfg, b)
        enc_out = None
        if cfg.enc_layers:
            enc_out = MD._encode(params, cfg,
                                 {"enc_frames": batch["enc_frames"]})
        for i, kd in enumerate(pre_kinds):
            ad = LR.as_pairs(pre_ads[i]) if pre_ads else None
            x, _, _ = MD.apply_layer(params["pre"][i], x, pos, cfg, kd,
                                     mode="full", lora=ad, scale=scale,
                                     enc_out=enc_out)
        return x, pos, enc_out

    def u_embed(state):
        pre_ads = state["adapters"]["pre"] if pre_kinds else None
        x, _, enc_out = front(state, pre_ads)
        state = dict(state)
        state["x"] = x.astype(jnp.bfloat16)
        state["residuals"] = state["residuals"].at[0].set(
            x.astype(jnp.bfloat16))
        if cfg.enc_layers and enc_out is not None:
            state["enc_out"] = enc_out.astype(jnp.bfloat16)
        return state

    # ---------------- one scanned layer, fwd ------------------------------
    def layer_fwd(x, i, ad_scan, state):
        lp = jax.tree.map(lambda t: t[i], params["scan"])
        ad = LR.as_pairs(jax.tree.map(lambda t: t[i], ad_scan))
        pos = positions(*x.shape[:2])
        enc_out = state.get("enc_out")
        y, _, _ = MD.apply_layer(lp, x, pos, cfg, scan_kind, mode="full",
                                 lora=ad, scale=scale,
                                 enc_out=None if enc_out is None
                                 else enc_out.astype(x.dtype))
        return y

    def u_fwd(state):
        u = state["unit_idx"] % upm
        i = u - 1
        x = state["x"]
        y = layer_fwd(x, i, state["adapters"]["scan"], state)
        state = dict(state)
        state["x"] = y.astype(jnp.bfloat16)
        state["residuals"] = state["residuals"].at[i + 1].set(
            y.astype(jnp.bfloat16))
        return state

    # ---------------- head: post layers + loss; bwd to x ------------------
    def head_loss(x, post_ads, state):
        batch = current_batch(state)
        pos = positions(*x.shape[:2])
        for i, kd in enumerate(post_kinds):
            ad = LR.as_pairs(post_ads[i]) if post_ads else None
            x, _, _ = MD.apply_layer(params["post"][i], x, pos, cfg, kd,
                                     mode="full", lora=ad, scale=scale)
        h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        labels = batch["labels"]
        mask = batch.get("mask")
        return L.chunked_softmax_xent(
            h[:, :-1], table, labels[:, 1:],
            None if mask is None else mask[:, 1:])

    def u_head(state):
        x = state["x"]
        post_ads = state["adapters"]["post"] if post_kinds else None

        if post_kinds:
            (loss), vjp = jax.vjp(
                lambda xx, aa: head_loss(xx, aa, state), x, post_ads)
            dx, dpost = vjp(jnp.ones((), loss.dtype))
            new_grads = list(state["grads"]["post"])
            for i in range(len(post_kinds)):
                new_grads[i] = jax.tree.map(
                    lambda g, d: g + d.astype(jnp.float32),
                    state["grads"]["post"][i], dpost[i])
            grads = dict(state["grads"], post=new_grads)
        else:
            loss, vjp = jax.vjp(lambda xx: head_loss(xx, None, state), x)
            (dx,) = vjp(jnp.ones((), loss.dtype))
            grads = state["grads"]
        state = dict(state, grads=grads)
        state["x"] = dx.astype(jnp.bfloat16)
        state["loss"] = state["loss"] + loss / pc.accum
        return state

    # ---------------- one scanned layer, bwd ------------------------------
    def u_bwd(state):
        u = state["unit_idx"] % upm
        i = 2 * n_scan + 1 - u                    # layer index, descending
        x_in = state["residuals"][i]
        dy = state["x"]
        ad_i = jax.tree.map(lambda t: t[i], state["adapters"]["scan"])

        def f(xx, aa):
            lp = jax.tree.map(lambda t: t[i], params["scan"])
            pos = positions(*xx.shape[:2])
            enc_out = state.get("enc_out")
            y, _, _ = MD.apply_layer(lp, xx, pos, cfg, scan_kind, mode="full",
                                     lora=LR.as_pairs(aa), scale=scale,
                                     enc_out=None if enc_out is None
                                     else enc_out.astype(xx.dtype))
            return y

        _, vjp = jax.vjp(f, x_in, ad_i)
        dx, dad = vjp(dy.astype(jnp.bfloat16))
        grads_scan = jax.tree.map(
            lambda g, d: g.at[i].add(d.astype(jnp.float32)),
            state["grads"]["scan"], dad)
        state = dict(state, grads=dict(state["grads"], scan=grads_scan))
        state["x"] = dx.astype(jnp.bfloat16)
        return state

    # ---------------- pre-stack bwd + microbatch bookkeeping --------------
    def u_embed_bwd(state):
        state = dict(state)
        if pre_kinds:
            dy = state["x"]

            def f(pre_ads):
                x, _, _ = front(state, pre_ads)
                return x

            _, vjp = jax.vjp(f, state["adapters"]["pre"])
            (dpre,) = vjp(dy.astype(jnp.bfloat16))
            new_grads = [jax.tree.map(lambda g, d: g + d.astype(jnp.float32),
                                      state["grads"]["pre"][i], dpre[i])
                         for i in range(len(pre_kinds))]
            state["grads"] = dict(state["grads"], pre=new_grads)
        state["data_idx"] = state["data_idx"] + 1
        state["consumed"] = state["consumed"] + 1
        return state

    # ---------------- optimizer ------------------------------------------
    def u_opt(state):
        new_ad, new_opt = adamw_update(pc.opt, state["grads"], state["opt"],
                                       state["adapters"])
        state = dict(state)
        state["adapters"] = new_ad
        state["opt"] = new_opt
        state["grads"] = jax.tree.map(
            lambda g: jnp.zeros_like(g), state["grads"])
        state["last_loss"] = state["loss"]
        state["loss"] = jnp.zeros((), jnp.float32)
        state["iter"] = state["iter"] + 1
        return state

    def scoped(kind, unit):
        # names the unit's ops ft_unit.<kind> in the program's metadata,
        # where a profile can tell the kinds apart
        def run(state):
            with jax.named_scope(f"ft_unit.{kind}"):
                return unit(state)
        return run

    branches = [scoped(kind, unit) for kind, unit in (
        ("embed", u_embed), ("fwd", u_fwd), ("head", u_head),
        ("bwd", u_bwd), ("embed_bwd", u_embed_bwd), ("opt", u_opt))]

    def branch_id(unit_idx):
        u = unit_idx % upm
        is_opt = unit_idx >= pc.accum * upm
        b = jnp.where(u == 0, 0,
            jnp.where(u <= n_scan, 1,
            jnp.where(u == n_scan + 1, 2,
            jnp.where(u <= 2 * n_scan + 1, 3, 4))))
        return jnp.where(is_opt, 5, b).astype(jnp.int32)

    def unit_step(state):
        b = branch_id(state["unit_idx"])
        state = jax.lax.switch(b, branches, state)
        state["unit_idx"] = (state["unit_idx"] + 1) % total_units
        return state

    return unit_step


def run_units(unit_step, state, k: int):
    """Run k units (k static — compiled per quantum level)."""
    if k <= 0:
        return state
    def body(s, _):
        return unit_step(s), None
    state, _ = jax.lax.scan(body, state, None, length=k)
    return state
