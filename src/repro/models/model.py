"""Model assembly for all assigned families.

Params layout (pytree):
  {"embed": (V, d), "final_norm": (d,), ["unembed": (V, d)],
   "pre":  [layer, ...]     # unrolled leading layers (deepseek dense head)
   "scan": layer_stack,      # homogeneous stack, leading axis = n_scan
   "post": [layer, ...],     # unrolled trailing layers (hybrid remainder)
   ["enc": {"scan": enc_stack, "final_norm": (d,)}],
   ["mtp": {...}]}

Layers are scanned with ``jax.lax.scan`` (keeps HLO small at 61-layer scale);
hybrid models scan a ("r","r","a") *superblock*. Caches/adapters mirror the
same pre/scan/post structure so they scan together with params.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import lora as LR
from repro.models import moe as M
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.models.config import ModelConfig

Params = Dict[str, Any]

MOE_AUX_COEF = 0.01
MTP_COEF = 0.3


# ===================================================================== init
def _mlp_init(key, cfg, dtype, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    return {
        "gate": (jax.random.normal(k1, (d, ff)) * s).astype(dtype),
        "up": (jax.random.normal(k2, (d, ff)) * s).astype(dtype),
        "down": (jax.random.normal(k3, (ff, d)) * ff ** -0.5).astype(dtype),
    }


def _attn_init(key, cfg, dtype):
    return A.mla_init(key, cfg, dtype) if cfg.mla else A.attn_init(key, cfg, dtype)


def layer_init(key, cfg: ModelConfig, kind: str, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    ones = jnp.ones((d,), dtype)
    if kind == "ssm":
        return {"ln1": ones, "ssm": SSM.ssm_init(key, cfg, dtype)}
    if kind == "rglru":
        k1, k2 = jax.random.split(key)
        return {"ln1": ones, "rg": RG.rglru_init(k1, cfg, dtype),
                "ln2": ones, "mlp": _mlp_init(k2, cfg, dtype)}
    if kind == "moe":
        k1, k2 = jax.random.split(key)
        return {"ln1": ones, "attn": _attn_init(k1, cfg, dtype),
                "ln2": ones, "moe": M.moe_init(k2, cfg, dtype)}
    if kind == "hybrid_block":
        ks = jax.random.split(key, len(cfg.hybrid_pattern))
        return {f"sub{i}": layer_init(
                    ks[i], cfg, "rglru" if ch == "r" else "attn", dtype)
                for i, ch in enumerate(cfg.hybrid_pattern)}
    if kind == "dec":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"ln1": ones, "attn": _attn_init(k1, cfg, dtype),
                "lnx": ones, "xattn": A.attn_init(k2, cfg, dtype),
                "ln2": ones, "mlp": _mlp_init(k3, cfg, dtype)}
    # "attn" (dense decoder layer) and "enc" (bidirectional encoder layer)
    k1, k2 = jax.random.split(key)
    return {"ln1": ones, "attn": _attn_init(k1, cfg, dtype),
            "ln2": ones, "mlp": _mlp_init(k2, cfg, dtype)}


def _stack_init(key, cfg, kind, n, dtype):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer_init(k, cfg, kind, dtype))(keys)


def _plan(cfg: ModelConfig):
    """(pre_kinds, scan_kind, n_scan, post_kinds) — how depth is laid out."""
    if cfg.family == "hybrid" and cfg.hybrid_pattern:
        plen = len(cfg.hybrid_pattern)
        n_blocks = cfg.num_layers // plen
        rem = cfg.num_layers - n_blocks * plen
        post = ["rglru" if cfg.hybrid_pattern[i] == "r" else "attn"
                for i in range(rem)]
        return [], "hybrid_block", n_blocks, post
    if cfg.family == "ssm":
        return [], "ssm", cfg.num_layers, []
    if cfg.family in ("encdec", "audio") and cfg.cross_attention:
        return [], "dec", cfg.num_layers, []
    if cfg.moe:
        return ["attn"] * cfg.first_dense_layers, "moe", cfg.scanned_layers, []
    return [], "attn", cfg.num_layers, []


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Params:
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)
    keys = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.vocab_size
    p: Params = {
        "embed": (jax.random.normal(keys[0], (V, d)) * d ** -0.5).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
        "pre": [layer_init(k, cfg, kd, dtype) for k, kd in
                zip(jax.random.split(keys[1], max(len(pre_kinds), 1)), pre_kinds)],
        "scan": _stack_init(keys[2], cfg, scan_kind, n_scan, dtype),
        "post": [layer_init(k, cfg, kd, dtype) for k, kd in
                 zip(jax.random.split(keys[3], max(len(post_kinds), 1)), post_kinds)],
    }
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(keys[4], (V, d)) * d ** -0.5
                        ).astype(dtype)
    if cfg.enc_layers:
        p["enc"] = {"scan": _stack_init(keys[5], cfg, "enc", cfg.enc_layers, dtype),
                    "final_norm": jnp.ones((d,), dtype)}
    if cfg.mtp:
        k1, k2 = jax.random.split(keys[6])
        p["mtp"] = {"norm_h": jnp.ones((d,), dtype),
                    "norm_e": jnp.ones((d,), dtype),
                    "proj": (jax.random.normal(k1, (2 * d, d)) * (2 * d) ** -0.5
                             ).astype(dtype),
                    "layer": layer_init(k2, cfg, "attn", dtype)}
    return p


def init_adapters(cfg: ModelConfig, key) -> Params:
    """LoRA adapters mirroring pre/scan/post (fp32 leaves)."""
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)
    keys = jax.random.split(key, 4)
    if scan_kind == "hybrid_block":
        ks = jax.random.split(keys[1], len(cfg.hybrid_pattern))
        scan_ad = {f"sub{i}": LR.init_layer_adapters(
                       ks[i], cfg, "rglru" if ch == "r" else "attn", n_scan)
                   for i, ch in enumerate(cfg.hybrid_pattern)}
    else:
        kind = {"dec": "attn"}.get(scan_kind, scan_kind)
        scan_ad = LR.init_layer_adapters(keys[1], cfg, kind, n_scan)
    return {
        "pre": [LR.init_layer_adapters(k, cfg, kd)
                for k, kd in zip(jax.random.split(keys[0], max(len(pre_kinds), 1)),
                                 pre_kinds)],
        "scan": scan_ad,
        "post": [LR.init_layer_adapters(k, cfg, kd)
                 for k, kd in zip(jax.random.split(keys[2], max(len(post_kinds), 1)),
                                  post_kinds)],
    }


# ==================================================================== cache
def layer_cache(cfg: ModelConfig, kind: str, batch: int, s_max: int,
                enc_len: int = 0, dtype=jnp.bfloat16):
    if kind == "ssm":
        return SSM.make_ssm_state(cfg, batch)
    if kind == "rglru":
        return RG.make_rglru_state(cfg, batch)
    if kind == "hybrid_block":
        return {f"sub{i}": layer_cache(
                    cfg, "rglru" if ch == "r" else "attn", batch, s_max,
                    enc_len, dtype)
                for i, ch in enumerate(cfg.hybrid_pattern)}
    if kind == "dec":
        c = {"self": A.make_cache(cfg, batch, s_max, dtype)}
        c["xk"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["xv"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        return c
    window = _layer_window(cfg, kind)
    return A.make_cache(cfg, batch, s_max, dtype, window=window,
                        quantized=cfg.kv_quant)


def _layer_window(cfg: ModelConfig, kind: str) -> int:
    if cfg.family == "hybrid":
        return cfg.local_window
    if cfg.attn_type == "swa":
        return cfg.window
    return 0


def init_cache(cfg: ModelConfig, batch: int, s_max: int, enc_len: int = 0,
               dtype=jnp.bfloat16) -> Params:
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)

    def stack(kind):
        one = layer_cache(cfg, kind, batch, s_max, enc_len, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_scan,) + x.shape), one)

    return {
        "pre": [layer_cache(cfg, kd, batch, s_max, enc_len, dtype)
                for kd in pre_kinds],
        "scan": stack(scan_kind),
        "post": [layer_cache(cfg, kd, batch, s_max, enc_len, dtype)
                 for kd in post_kinds],
    }


# ============================================================ layer apply
def apply_layer(lp: Params, x, positions, cfg: ModelConfig, kind: str, *,
                mode: str,                       # "full" | "prefill" | "decode"
                cache=None, lora=None, scale: float = 0.0,
                enc_out=None, decode_attn_fn=None, use_kernels=False):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "hybrid_block":
        new_cache = {} if cache is not None else None
        for i, ch in enumerate(cfg.hybrid_pattern):
            sub = "rglru" if ch == "r" else "attn"
            c = None if cache is None else cache[f"sub{i}"]
            x, nc, a = apply_layer(
                lp[f"sub{i}"], x, positions, cfg, sub, mode=mode, cache=c,
                lora=None if lora is None else lora.get(f"sub{i}"),
                scale=scale, decode_attn_fn=decode_attn_fn,
                use_kernels=use_kernels)
            if new_cache is not None:
                new_cache[f"sub{i}"] = nc
            aux += a
        return x, new_cache, aux

    lora_d = lora  # callers pass pairs-form ({name: (A, B)}, possibly nested)

    if kind == "ssm":
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        if mode == "decode":
            out, nc = SSM.ssm_decode(lp["ssm"], h, cache, cfg)
        else:
            out, nc = SSM.ssm_prefill(lp["ssm"], h, cfg, state=cache,
                                      use_kernel=use_kernels)
        out = _parallel_lora(h, out, lora_d, "ssm_io", scale)
        return x + out, nc, aux

    if kind == "rglru":
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        if mode == "decode":
            out, nc = RG.rglru_decode(lp["rg"], h, cache, cfg)
        else:
            out, nc = RG.rglru_forward(lp["rg"], h, cfg, state=cache)
        out = _parallel_lora(h, out, lora_d, "rg_io", scale)
        x = x + out
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.glu_mlp(h, lp["mlp"]["gate"], lp["mlp"]["up"],
                          lp["mlp"]["down"], act=cfg.act,
                          lora=lora_d, lora_scale=scale)
        return x, nc, aux

    # --- attention-bearing layers ("attn", "moe", "enc", "dec") ----------
    window = _layer_window(cfg, kind)
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla:
        if mode == "decode":
            attn_out, nc = A.mla_decode(lp["attn"], h, positions, cache, cfg,
                                        lora=lora_d, lora_scale=scale)
        else:
            attn_out, nc = A.mla_prefill(lp["attn"], h, positions, cfg,
                                         cache=cache, lora=lora_d,
                                         lora_scale=scale)
    elif kind == "enc":
        q, k, v = A._project_qkv(lp["attn"], h, cfg, lora_d, scale)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.flash_attention(q, k, v, causal=False,
                              soft_cap=cfg.logits_soft_cap)
        attn_out = A._out_proj(lp["attn"], o, cfg, lora_d, scale)
        nc = None
    elif mode == "decode":
        self_cache = cache["self"] if kind == "dec" else cache
        attn_out, nc_self = A.attn_decode(
            lp["attn"], h, positions, self_cache, cfg, window=window,
            lora=lora_d, lora_scale=scale, decode_attn_fn=decode_attn_fn)
        nc = {"self": nc_self, "xk": cache["xk"], "xv": cache["xv"]} \
            if kind == "dec" else nc_self
    else:
        self_cache = cache["self"] if (kind == "dec" and cache is not None) \
            else cache
        attn_out, nc_self = A.attn_prefill(
            lp["attn"], h, positions, cfg, window=window, cache=self_cache,
            lora=lora_d, lora_scale=scale)
        nc = {"self": nc_self} if kind == "dec" else nc_self
    x = x + attn_out

    if kind == "dec":                       # cross attention
        h = L.rms_norm(x, lp["lnx"], cfg.norm_eps)
        x_out, xk, xv = _cross_attention(lp["xattn"], h, cfg, mode,
                                         cache, enc_out)
        x = x + x_out
        if isinstance(nc, dict) and cache is not None:
            nc["xk"], nc["xv"] = xk, xv

    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if kind == "moe":
        router_type = "sigmoid" if cfg.mla else "softmax"
        mo, maux = M.moe_forward(lp["moe"], h, cfg, router_type=router_type,
                                 lora=lora_d, lora_scale=scale)
        x = x + mo
        aux += maux["lb_loss"]
    else:
        x = x + L.glu_mlp(h, lp["mlp"]["gate"], lp["mlp"]["up"],
                          lp["mlp"]["down"], act=cfg.act,
                          lora=lora_d, lora_scale=scale)
    return x, nc, aux


def _cross_attention(p, h, cfg, mode, cache, enc_out):
    """Decoder->encoder attention; K/V cached at prefill."""
    B = h.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,do->bso", h, p["wq"].astype(h.dtype)
                   ).reshape(B, -1, H, hd)
    if enc_out is not None:                         # prefill: build K/V
        xk = jnp.einsum("bsd,do->bso", enc_out, p["wk"].astype(h.dtype)
                        ).reshape(B, -1, KV, hd)
        xv = jnp.einsum("bsd,do->bso", enc_out, p["wv"].astype(h.dtype)
                        ).reshape(B, -1, KV, hd)
    else:
        xk, xv = cache["xk"], cache["xv"]
    o = L.flash_attention(q, xk, xv, causal=False)
    o = o.reshape(B, -1, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(h.dtype))
    return out, xk, xv


def _parallel_lora(h, out, lora_d, name, scale):
    """Parallel low-rank adapter on a mixer block's I/O path."""
    if lora_d and name in lora_d:
        a, b = lora_d[name]
        out = out + scale * jnp.einsum(
            "...r,rd->...d", jnp.einsum("...d,dr->...r", h, a.astype(h.dtype)),
            b.astype(h.dtype))
    return out


# ================================================================= drivers
def _kinds(cfg: ModelConfig):
    return _plan(cfg)


def _embed_inputs(params, cfg: ModelConfig, batch: Dict):
    """Token (+frontend) embedding. Returns (x, positions, text_offset)."""
    tokens = batch["tokens"]
    x = L.embed(tokens, params["embed"])
    offset = 0
    if cfg.frontend != "none" and batch.get("frontend") is not None:
        fe = batch["frontend"].astype(x.dtype)       # (B, P, d) stub embeds
        x = jnp.concatenate([fe, x], axis=1)
        offset = fe.shape[1]
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, ("batch", "seq_sp", None))
    return x, positions, offset


def _encode(params, cfg: ModelConfig, batch: Dict, use_kernels=False):
    """Run the (bidirectional) encoder over stub frame embeddings."""
    enc_in = batch["enc_frames"].astype(params["embed"].dtype)  # (B, Se, d)
    B, Se, _ = enc_in.shape
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    x = constrain(enc_in, ("batch", "seq_sp", None))

    def body(h, lp):
        h, _, _ = apply_layer(lp, h, positions, cfg, "enc", mode="full",
                              use_kernels=use_kernels)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc"]["scan"])
    return L.rms_norm(x, params["enc"]["final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, batch: Dict, *,
            adapters=None, use_kernels: bool = False, remat: bool = False,
            return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward (train / eval). Returns (logits, aux_loss);
    with return_hidden=True returns the normed final hidden states instead
    of logits (loss_fn fuses the projection into chunked CE)."""
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)
    x, positions, offset = _embed_inputs(params, cfg, batch)
    scale = LR.lora_scale(cfg)
    enc_out = None
    if cfg.enc_layers:
        enc_out = _encode(params, cfg, batch, use_kernels)

    aux = jnp.zeros((), jnp.float32)
    for i, kd in enumerate(pre_kinds):
        ad = None if adapters is None else LR.as_pairs(adapters["pre"][i])
        x, _, a = apply_layer(params["pre"][i], x, positions, cfg, kd,
                              mode="full", lora=ad, scale=scale,
                              enc_out=enc_out, use_kernels=use_kernels)
        aux += a

    def body(carry, xs):
        h, aux_c = carry
        lp, ad_stacked = xs
        ad = None if ad_stacked is None else _pairs_from_sliced(ad_stacked)
        h, _, a = apply_layer(lp, h, positions, cfg, scan_kind, mode="full",
                              lora=ad, scale=scale, enc_out=enc_out,
                              use_kernels=use_kernels)
        return (h, aux_c + a), None

    body_fn = jax.checkpoint(body) if remat else body
    scan_ad = None if adapters is None else adapters["scan"]
    if scan_ad is None:
        (x, aux), _ = jax.lax.scan(
            lambda c, lp: body_fn(c, (lp, None)), (x, aux), params["scan"])
    else:
        (x, aux), _ = jax.lax.scan(body_fn, (x, aux), (params["scan"], scan_ad))

    for i, kd in enumerate(post_kinds):
        ad = None if adapters is None else LR.as_pairs(adapters["post"][i])
        x, _, a = apply_layer(params["post"][i], x, positions, cfg, kd,
                              mode="full", lora=ad, scale=scale,
                              enc_out=enc_out, use_kernels=use_kernels)
        aux += a

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x[:, offset:], aux
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.lm_logits(x[:, offset:], table)
    return logits, aux


def _pairs_from_sliced(ad_sliced) -> Dict:
    """Stacked adapters arrive in scan with the layer axis already consumed."""
    return {k: ((v["a"], v["b"]) if isinstance(v, dict) and set(v) == {"a", "b"}
                else _pairs_from_sliced(v))
            for k, v in ad_sliced.items()}


def loss_fn(params, cfg: ModelConfig, batch: Dict, *, adapters=None,
            use_kernels=False, remat: bool = True):
    """Cross-entropy (+ MoE aux + MTP) loss for (PEFT) training.

    The final projection is fused into a chunked CE (never materializes the
    (B, S, V) logits — decisive for non-16-divisible vocabs like seamless's
    256206, which would otherwise replicate a 537GB tensor)."""
    hidden, aux = forward(params, cfg, batch, adapters=adapters,
                          use_kernels=use_kernels, remat=remat,
                          return_hidden=True)
    labels = batch["labels"]
    mask = batch.get("mask")
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    ce = L.chunked_softmax_xent(
        hidden[:, :-1], table, labels[:, 1:],
        None if mask is None else mask[:, 1:])
    total = ce + MOE_AUX_COEF * aux / max(cfg.num_layers, 1)
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp and "mtp" in params:
        mtp_ce = _mtp_loss(params, cfg, batch, None)
        total = total + MTP_COEF * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return total, metrics


def _mtp_loss(params, cfg, batch, logits):
    """DeepSeek-V3 multi-token prediction: predict t+2 from (h_t, emb_{t+1}).
    Approximated at the head: reuse final logits' hidden via embeddings."""
    mp = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    emb_next = L.embed(tokens[:, 1:], params["embed"])
    # hidden proxy: embed current token through the shared table (cheap MTP
    # variant; the trunk layer provides the model capacity)
    h = L.embed(tokens[:, :-1], params["embed"])
    h = jnp.concatenate([L.rms_norm(h, mp["norm_h"], cfg.norm_eps),
                         L.rms_norm(emb_next, mp["norm_e"], cfg.norm_eps)],
                        axis=-1)
    h = jnp.einsum("bsd,do->bso", h, mp["proj"].astype(h.dtype))
    positions = jnp.broadcast_to(jnp.arange(S - 1, dtype=jnp.int32), (B, S - 1))
    h, _, _ = apply_layer(mp["layer"], h, positions, cfg, "attn", mode="full")
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    mtp_logits = L.lm_logits(h, table)
    return L.cross_entropy(mtp_logits[:, :-1], labels[:, 2:])


def prefill(params, cfg: ModelConfig, batch: Dict, cache, *,
            use_kernels: bool = False):
    """Prompt processing: forward + cache fill. Returns (last_logits, cache)."""
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)
    x, positions, offset = _embed_inputs(params, cfg, batch)
    enc_out = _encode(params, cfg, batch, use_kernels) if cfg.enc_layers else None

    new_cache = {"pre": [], "post": []}
    for i, kd in enumerate(pre_kinds):
        x, nc, _ = apply_layer(params["pre"][i], x, positions, cfg, kd,
                               mode="prefill", cache=cache["pre"][i],
                               enc_out=enc_out, use_kernels=use_kernels)
        new_cache["pre"].append(nc)

    # the stacked cache is a loop CARRY updated in place (aliasable with the
    # donated input cache) — emitting it as scan ys would materialize a
    # second full cache buffer (and XLA pads the accumulation in f32)
    def body(carry, lp):
        h, cstack, i = carry
        lc = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
            t, i, 0, keepdims=False), cstack)
        h, nc, _ = apply_layer(lp, h, positions, cfg, scan_kind,
                               mode="prefill", cache=lc, enc_out=enc_out,
                               use_kernels=use_kernels)
        cstack = jax.tree.map(
            lambda t, n: jax.lax.dynamic_update_index_in_dim(
                t, n.astype(t.dtype), i, 0), cstack, nc)
        return (h, cstack, i + 1), None

    (x, scan_cache, _), _ = jax.lax.scan(
        body, (x, cache["scan"], jnp.zeros((), jnp.int32)), params["scan"])
    new_cache["scan"] = scan_cache

    for i, kd in enumerate(post_kinds):
        x, nc, _ = apply_layer(params["post"][i], x, positions, cfg, kd,
                               mode="prefill", cache=cache["post"][i],
                               enc_out=enc_out, use_kernels=use_kernels)
        new_cache["post"].append(nc)

    x = L.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.lm_logits(x, table)[:, 0]
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, tokens, positions, cache, *,
                use_kernels: bool = False, decode_attn_fn=None):
    """One decode token. tokens/positions: (B,). Returns (logits (B,V), cache)."""
    pre_kinds, scan_kind, n_scan, post_kinds = _plan(cfg)
    x = L.embed(tokens[:, None], params["embed"])     # (B, 1, d)
    x = constrain(x, ("batch", None, None))
    # The paged kernel covers full-attention bf16 caches only: ring-buffered
    # (windowed) layers and int8 caches have no kernel yet, so they keep the
    # reference decode_attn_ref.
    if decode_attn_fn is None and use_kernels and not cfg.kv_quant and \
            _layer_window(cfg, scan_kind) == 0:
        from repro.kernels import ops as kops
        decode_attn_fn = kops.decode_attention

    new_cache = {"pre": [], "post": []}
    for i, kd in enumerate(pre_kinds):
        x, nc, _ = apply_layer(params["pre"][i], x, positions, cfg, kd,
                               mode="decode", cache=cache["pre"][i],
                               decode_attn_fn=decode_attn_fn,
                               use_kernels=use_kernels)
        new_cache["pre"].append(nc)

    # cache as in-place-updated carry (see prefill note)
    def body(carry, lp):
        h, cstack, i = carry
        lc = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
            t, i, 0, keepdims=False), cstack)
        h, nc, _ = apply_layer(lp, h, positions, cfg, scan_kind, mode="decode",
                               cache=lc, decode_attn_fn=decode_attn_fn,
                               use_kernels=use_kernels)
        cstack = jax.tree.map(
            lambda t, n: jax.lax.dynamic_update_index_in_dim(
                t, n.astype(t.dtype), i, 0), cstack, nc)
        return (h, cstack, i + 1), None

    (x, scan_cache, _), _ = jax.lax.scan(
        body, (x, cache["scan"], jnp.zeros((), jnp.int32)), params["scan"])
    new_cache["scan"] = scan_cache

    for i, kd in enumerate(post_kinds):
        x, nc, _ = apply_layer(params["post"][i], x, positions, cfg, kd,
                               mode="decode", cache=cache["post"][i],
                               decode_attn_fn=decode_attn_fn,
                               use_kernels=use_kernels)
        new_cache["post"].append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.lm_logits(x, table)[:, 0]
    return logits, new_cache
