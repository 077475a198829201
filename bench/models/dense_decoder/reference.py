"""Plain reference of a dense Qwen-style decoder and its LoRA train step.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written from the
published architecture (RMSNorm, rotate-half RoPE, GQA causal attention,
optional per-head q/k RMSNorm, SwiGLU MLP, untied output head), with no
cache, no kernel and no batching of requests. It imports nothing of the
program and takes only the weights, adapters and tokens that the benchmark
made. Departure from the published Qwen2.5: no q/k/v bias (the program has
none; the benchmark's weights are those of a model whose biases are 0).

``lowp="fp8"`` rounds both operands of every matrix product to float8 e4m3
(per-tensor scale) before the float32 product, forward and, through those
rounded operands, backward: the control, one precision step below the
bfloat16 the configurations serve in.

A configuration names this file as its ``reference``. What the check calls:
``dims(cfg)``, the static description of the model; ``served_gaps`` over
one served sequence; and ``train``, the LoRA iterations.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

PREC = jax.lax.Precision.HIGHEST
F32 = jnp.float32


class Dims(NamedTuple):
    L: int
    d: int
    H: int
    KV: int
    hd: int
    ff: int
    V: int
    eps: float
    theta: float
    qk_norm: bool
    lora_scale: float


def dims(cfg) -> Dims:
    """The reference's description of the program's ModelConfig: numbers
    only, nothing the program computed."""
    return Dims(cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, cfg.d_ff, cfg.vocab_size, float(cfg.norm_eps),
                float(cfg.rope_theta), bool(cfg.qk_norm),
                cfg.lora.alpha / cfg.lora.rank)


def _lowp(x, lowp):
    if lowp is None:
        return x
    if lowp != "fp8":
        raise ValueError(f"unknown precision {lowp!r}")
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    # the value is q; gradients pass straight through, since a cotangent
    # cast to float8 unscaled would flush to zero
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, lowp):
    return jnp.matmul(_lowp(x, lowp), _lowp(w, lowp), precision=PREC)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, n, hd), positions 0..S-1; rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, lowp, q_block=512):
    """Causal GQA attention, one block of queries at a time.
    q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H * hd)."""
    B, S, H, hd = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qb = min(q_block, S)
    nb = S // qb
    kpos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", _lowp(qi, lowp), _lowp(k, lowp),
                       precision=PREC) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _lowp(p, lowp), _lowp(v, lowp),
                          precision=PREC)

    o = jax.lax.map(block, jnp.arange(nb))          # (nb, B, qb, H, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd)
    return o


def _layer(dm: Dims, lp, ad, x, lowp):
    lp = jax.tree.map(lambda t: t.astype(F32), lp)
    B, S, _ = x.shape

    def proj(h, w, name):
        y = _mm(h, w, lowp)
        if ad is not None and name in ad:
            a, b = ad[name]["a"].astype(F32), ad[name]["b"].astype(F32)
            y = y + dm.lora_scale * _mm(_mm(h, a, lowp), b, lowp)
        return y

    at = lp["attn"]
    h = _rms(x, lp["ln1"], dm.eps)
    q = proj(h, at["wq"], "q").reshape(B, S, dm.H, dm.hd)
    k = proj(h, at["wk"], "k").reshape(B, S, dm.KV, dm.hd)
    v = proj(h, at["wv"], "v").reshape(B, S, dm.KV, dm.hd)
    if dm.qk_norm:
        q = _rms(q, at["q_norm"], dm.eps)
        k = _rms(k, at["k_norm"], dm.eps)
    q, k = _rope(q, dm.theta), _rope(k, dm.theta)
    x = x + proj(_attention(q, k, v, lowp), at["wo"], "o")
    h = _rms(x, lp["ln2"], dm.eps)
    mlp = lp["mlp"]
    act = jax.nn.silu(proj(h, mlp["gate"], "gate")) * proj(h, mlp["up"], "up")
    return x + proj(act, mlp["down"], "down")


def hidden(dm: Dims, params, adapters, tokens, lowp=None, remat=False):
    """Final normed hidden states, (B, S, d), for token rows (B, S)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)

    def body(x, xs):
        lp, ad = xs
        return _layer(dm, lp, ad, x, lowp), None

    if remat:
        body = jax.checkpoint(body)
    ads = None if adapters is None else adapters["scan"]
    x, _ = jax.lax.scan(body, x, (params["scan"], ads))
    return _rms(x, params["final_norm"].astype(F32), dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "lowp"))
def served_logits(dm: Dims, params, tokens, rows, lowp=None):
    """Logits (n, V) at positions ``rows`` of one sequence ``tokens`` (S,)."""
    h = hidden(dm, params, None, tokens[None], lowp)[0]
    return _mm(h[rows], params["unembed"].astype(F32).T, lowp)


@functools.partial(jax.jit, static_argnames=("dm", "lowp"))
def served_gaps(dm: Dims, params, tokens, rows, served, lowp=None):
    """For each position in ``rows``: how far the reference logit of the
    token ``served`` there lies below the reference's best. With ``lowp``,
    the token is the one the low-precision forward puts first."""
    ref = served_logits(dm, params, tokens, rows)
    if lowp is not None:
        served = jnp.argmax(served_logits(dm, params, tokens, rows, lowp), -1)
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


def _loss(dm: Dims, adapters, params, tokens, labels, lowp):
    h = hidden(dm, params, adapters, tokens, lowp, remat=True)[:, :-1]
    logits = _mm(h, params["unembed"].astype(F32).T, lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("dm", "lowp"))
def micro_batch_grad(dm: Dims, adapters, params, tokens, labels, lowp=None):
    """(loss, adapter gradient) of one micro-batch's mean next-token loss."""
    return jax.value_and_grad(_loss, argnums=1)(dm, adapters, params, tokens,
                                                labels, lowp)


def adamw(opt: Dict, adapters, grads, m, v, t: int):
    """One AdamW step as the configuration states it: gradients clipped to
    global norm ``grad_clip``, linear warm-up of the learning rate, bias-
    corrected moments. Returns (adapters, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    m = jax.tree.map(lambda m_, g: opt["b1"] * m_ + (1 - opt["b1"]) * g,
                     m, grads)
    v = jax.tree.map(lambda v_, g: opt["b2"] * v_ + (1 - opt["b2"]) * g * g,
                     v, grads)
    lr = opt["lr"] * min(t / max(opt["warmup_steps"], 1), 1.0)
    bc1, bc2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
    adapters = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                 + opt["eps"])
        - lr * opt["weight_decay"] * p, adapters, m, v)
    return adapters, m, v, grads


def train(dm: Dims, opt: Dict, params, adapters, data: Dict, accum: int,
          steps: int, lowp: Optional[str] = None, rows=None):
    """``steps`` LoRA iterations of ``accum`` micro-batches each, taken in
    order from ``data`` ((n, B, S) tokens and labels); the gradient of an
    iteration is the mean of its micro-batches'. ``rows`` keeps only those
    rows of each micro-batch. Returns the losses, the first step's clipped
    gradient, the gradient norms per leaf of each step, and the adapters
    after the last step."""
    adapters = jax.tree.map(lambda x: x.astype(F32), adapters)
    m = jax.tree.map(jnp.zeros_like, adapters)
    v = jax.tree.map(jnp.zeros_like, adapters)
    losses, first_grad, grad_norms = [], None, []
    for t in range(1, steps + 1):
        total, loss = None, 0.0
        for j in range(accum):
            i = (t - 1) * accum + j
            tok, lab = data["tokens"][i], data["labels"][i]
            if rows is not None:
                tok, lab = tok[rows], lab[rows]
            lo, g = micro_batch_grad(dm, adapters, params, jnp.asarray(tok),
                                     jnp.asarray(lab), lowp)
            loss += float(lo) / accum
            total = g if total is None else jax.tree.map(jnp.add, total, g)
        grads = jax.tree.map(lambda g: g / accum, total)
        adapters, m, v, clipped = adamw(opt, adapters, grads, m, v, t)
        grad_norms.append(jax.tree.map(lambda g: float(jnp.linalg.norm(g)),
                                       clipped))
        if t == 1:
            first_grad = clipped
        losses.append(loss)
    return losses, first_grad, grad_norms, adapters
