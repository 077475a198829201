"""Attention layers: GQA/MHA (+ qk_norm, SWA/local windows) and MLA.

Two execution paths per layer:
  * prefill/train: chunked flash attention over the whole sequence
  * decode: one-token attention against a KV cache (dense jnp fallback here;
    the Pallas kernel in kernels/decode_attention is swapped in by ops.py)

Caches use per-request absolute positions so continuous batching works:
  full cache : k/v (B, S_max, KV, hd), kv_pos (B, S_max) int32 (-1 = empty)
  SWA cache  : same but S_max = window, ring-buffer indexed by pos % window
  MLA cache  : c_kv (B, S_max, kv_rank), k_rope (B, S_max, rope_dim), kv_pos
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = Dict[str, jax.Array]


# ------------------------------------------------------------------ init ---
def attn_init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, H * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, KV * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, KV * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H * hd, d)) * (H * hd) ** -0.5).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def mla_init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, H = cfg.d_model, cfg.num_heads
    qr, kr = cfg.mla_q_rank, cfg.mla_kv_rank
    nd, rd, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    return {
        "wq_a": (jax.random.normal(ks[0], (d, qr)) * s).astype(dtype),
        "q_norm": jnp.ones((qr,), dtype),
        "wq_b": (jax.random.normal(ks[1], (qr, H * (nd + rd))) * qr ** -0.5).astype(dtype),
        "wkv_a": (jax.random.normal(ks[2], (d, kr + rd)) * s).astype(dtype),
        "kv_norm": jnp.ones((kr,), dtype),
        "wkv_b": (jax.random.normal(ks[3], (kr, H * (nd + vd))) * kr ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[4], (H * vd, d)) * (H * vd) ** -0.5).astype(dtype),
    }


def make_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16,
               window: int = 0, quantized: bool = False
               ) -> Dict[str, jax.Array]:
    """Empty per-layer cache (without the leading layer axis).

    quantized=True stores K/V as int8 with per-token f32 scales (beyond-
    paper: halves the decode memory term; scales fold into the softmax
    weights at read time — see decode_attn_ref). MLA latent caches stay
    bf16 (already 8x smaller than MHA)."""
    eff = min(s_max, window) if window else s_max
    if cfg.mla:
        return {
            "c_kv": jnp.zeros((batch, eff, cfg.mla_kv_rank), dtype),
            "k_rope": jnp.zeros((batch, eff, cfg.mla_rope_dim), dtype),
            "kv_pos": jnp.full((batch, eff), -1, jnp.int32),
        }
    kv_dt = jnp.int8 if quantized else dtype
    c = {
        "k": jnp.zeros((batch, eff, cfg.num_kv_heads, cfg.head_dim), kv_dt),
        "v": jnp.zeros((batch, eff, cfg.num_kv_heads, cfg.head_dim), kv_dt),
        "kv_pos": jnp.full((batch, eff), -1, jnp.int32),
    }
    if quantized:
        c["k_scale"] = jnp.zeros((batch, eff), jnp.float32)
        c["v_scale"] = jnp.zeros((batch, eff), jnp.float32)
    return c


def _quantize_tok(x):
    """Per-token symmetric int8: x (B, S, KV, hd) -> (q, scale (B, S))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(2, 3))
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale[:, :, None, None]), -127, 127)
    return q.astype(jnp.int8), scale


# ------------------------------------------------------------- GQA paths ---
def _project_qkv(p: Params, x, cfg: ModelConfig, lora, lora_scale):
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def proj(w, name, n_out):
        y = jnp.einsum("bsd,do->bso", x, w.astype(x.dtype))
        if lora is not None and name in lora:
            a, b = lora[name]
            y = y + lora_scale * jnp.einsum(
                "bsr,ro->bso", jnp.einsum("bsd,dr->bsr", x, a.astype(x.dtype)),
                b.astype(x.dtype))
        return y.reshape(B, S, n_out, hd)

    q = proj(p["wq"], "q", H)
    k = proj(p["wk"], "k", KV)
    v = proj(p["wv"], "v", KV)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _out_proj(p: Params, o, cfg: ModelConfig, lora, lora_scale):
    B, S = o.shape[:2]
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    y = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(o.dtype))
    if lora is not None and "o" in lora:
        a, b = lora["o"]
        y = y + lora_scale * jnp.einsum(
            "bsr,rd->bsd", jnp.einsum("bsh,hr->bsr", o, a.astype(o.dtype)),
            b.astype(o.dtype))
    return constrain(y, ("batch", "seq_sp", None))


def attn_prefill(p: Params, x, positions, cfg: ModelConfig, *,
                 window: int = 0, cache: Optional[Dict] = None,
                 lora=None, lora_scale: float = 0.0):
    """Full-sequence attention. positions: (B, S) absolute. Returns (out, cache)."""
    q, k, v = _project_qkv(p, x, cfg, lora, lora_scale)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    o = L.flash_attention(q, k, v, causal=True, window=window,
                          q_offset=positions[:, 0])
    out = _out_proj(p, o, cfg, lora, lora_scale)
    new_cache = None
    if cache is not None:
        # match the cache's (seq-sharded, heads-replicated) layout BEFORE
        # the write — otherwise GSPMD falls back to full rematerialization
        # of the cache write (observed as an involuntary-remat warning)
        kw = constrain(k, ("batch", "seq_sp", None, None))
        vw = constrain(v, ("batch", "seq_sp", None, None))
        new_cache = _cache_write_prefill(cache, kw, vw, positions, window)
    return out, new_cache


def _cache_write_bulk(cache, k, v, positions, window):
    """Write a token chunk into the cache (ring-buffered when windowed)."""
    S_max = cache["k"].shape[1]
    slots = positions % S_max if window else positions
    B = k.shape[0]
    bidx = jnp.arange(B)[:, None]
    out = dict(cache)
    if "k_scale" in cache:                       # int8 KV
        kq, ks = _quantize_tok(k)
        vq, vs = _quantize_tok(v)
        out["k"] = cache["k"].at[bidx, slots].set(kq)
        out["v"] = cache["v"].at[bidx, slots].set(vq)
        out["k_scale"] = cache["k_scale"].at[bidx, slots].set(ks)
        out["v_scale"] = cache["v_scale"].at[bidx, slots].set(vs)
    else:
        out["k"] = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    out["kv_pos"] = cache["kv_pos"].at[bidx, slots].set(positions)
    return out


def _cache_write_prefill(cache, k, v, positions, window):
    """Contiguous prefill cache write via dynamic_update_slice.

    Prefill positions are arange-contiguous per request (prompt processing),
    so the write is a slice update — a batched scatter here makes GSPMD
    all-gather the ENTIRE seq-sharded cache per layer (observed: 2 x 16GiB
    f32 all-gathers per layer in the 32k-prefill dry-run).
    Ring-buffered (SWA) caches keep only the last `W` tokens: two slice
    updates split at the (static) wrap point."""
    S_max = cache["k"].shape[1]
    B, S = k.shape[:2]
    kd, vd = cache["k"].dtype, cache["v"].dtype

    def dus(buf, upd, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, upd, start, axis=1)

    quant = "k_scale" in cache
    if quant:
        k, k_sc = _quantize_tok(k)
        v, v_sc = _quantize_tok(v)
        kd = vd = jnp.int8

    if not window:
        out = dict(cache)
        out["k"] = dus(cache["k"], k[:, :S_max].astype(kd), 0)
        out["v"] = dus(cache["v"], v[:, :S_max].astype(vd), 0)
        out["kv_pos"] = dus(cache["kv_pos"], positions[:, :S_max], 0)
        if quant:
            out["k_scale"] = dus(cache["k_scale"], k_sc[:, :S_max], 0)
            out["v_scale"] = dus(cache["v_scale"], v_sc[:, :S_max], 0)
        return out
    # ring buffer: last W tokens; token p lives in slot p % W
    W = S_max
    if S <= W:
        return _cache_write_bulk(cache, k, v, positions, window) if not quant \
            else _ring_quant_fallback(cache, k, k_sc, v, v_sc, positions,
                                      window)
    kt, vt, pt = k[:, -W:], v[:, -W:], positions[:, -W:]
    split = S % W               # static wrap point
    first = W - split

    def write(buf, t):
        buf = dus(buf, t[:, :first].astype(buf.dtype), split)
        if split:
            buf = dus(buf, t[:, first:].astype(buf.dtype), 0)
        return buf

    out = dict(cache)
    out["k"] = write(cache["k"], kt)
    out["v"] = write(cache["v"], vt)
    out["kv_pos"] = write(cache["kv_pos"], pt)
    if quant:
        out["k_scale"] = write(cache["k_scale"], k_sc[:, -W:])
        out["v_scale"] = write(cache["v_scale"], v_sc[:, -W:])
    return out


def _ring_quant_fallback(cache, kq, k_sc, vq, v_sc, positions, window):
    S_max = cache["k"].shape[1]
    slots = positions % S_max
    bidx = jnp.arange(kq.shape[0])[:, None]
    out = dict(cache)
    out["k"] = cache["k"].at[bidx, slots].set(kq)
    out["v"] = cache["v"].at[bidx, slots].set(vq)
    out["k_scale"] = cache["k_scale"].at[bidx, slots].set(k_sc)
    out["v_scale"] = cache["v_scale"].at[bidx, slots].set(v_sc)
    out["kv_pos"] = cache["kv_pos"].at[bidx, slots].set(positions)
    return out


def attn_decode(p: Params, x, positions, cache: Dict, cfg: ModelConfig, *,
                window: int = 0, lora=None, lora_scale: float = 0.0,
                decode_attn_fn: Optional[Callable] = None):
    """One-token decode. x: (B, 1, d); positions: (B,). Returns (out, cache).

    Sharding note: the KV cache is SEQUENCE-sharded on the model axis (SPMD
    flash-decode). q/k/v for the new token are tiny, so they are kept
    replicated on the model axis — scores then inherit the cache's seq
    sharding and the softmax/combine reduce with small all-reduces instead
    of gathering the (huge) cache."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg, lora, lora_scale)
    q = constrain(q, ("batch", None, None, None))
    k = constrain(k, ("batch", None, None, None))
    v = constrain(v, ("batch", None, None, None))
    q = L.apply_rope(q, positions[:, None], cfg.rope_theta)
    k = L.apply_rope(k, positions[:, None], cfg.rope_theta)
    cache = _cache_write_bulk(cache, k, v, positions[:, None], window)
    kc, vc, kv_pos = cache["k"], cache["v"], cache["kv_pos"]
    if decode_attn_fn is None:
        decode_attn_fn = decode_attn_ref
    o = decode_attn_fn(q[:, 0], kc, vc, kv_pos, positions, window,
                       scales=(cache.get("k_scale"), cache.get("v_scale")))
    out = _out_proj(p, o[:, None], cfg, lora, lora_scale)
    return out, cache


def decode_attn_ref(q, kc, vc, kv_pos, positions, window: int = 0,
                    scale: Optional[float] = None, scales=None):
    """Dense decode attention oracle. q: (B, H, hd); cache (B, S, KV, hd).

    int8 caches (scales=(k_scale, v_scale), per-token f32): the dequant
    scales fold into the scores / softmax weights — the cache itself is
    never dequantized to a wide buffer."""
    B, H, hd = q.shape
    KV = kc.shape[2]
    g = H // KV
    scale = scale if scale is not None else hd ** -0.5
    quant = scales is not None and scales[0] is not None
    out_dtype = q.dtype if quant else vc.dtype
    qr = q.reshape(B, KV, g, hd)
    if quant:
        s = jnp.einsum("bkgh,bskh->bkgs", qr.astype(jnp.bfloat16),
                       kc.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        s = s * scales[0][:, None, None, :] * scale
    else:
        s = jnp.einsum("bkgh,bskh->bkgs", qr, kc,
                       preferred_element_type=jnp.float32) * scale
    valid = (kv_pos >= 0) & (kv_pos <= positions[:, None])
    if window > 0:
        valid &= kv_pos > positions[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    pmax = jnp.where(jnp.isneginf(pmax), 0.0, pmax)
    e = jnp.exp(s - pmax)
    e = jnp.where(valid[:, None, None, :], e, 0.0)
    if quant:
        ew = e * scales[1][:, None, None, :]          # fold v dequant scale
        o = jnp.einsum("bkgs,bskh->bkgh", ew.astype(jnp.bfloat16),
                       vc.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    else:
        # cast the (small) softmax weights down to the cache dtype instead
        # of the cache up to f32 — XLA hoists a loop-invariant cache->f32
        # convert out of the layer scan otherwise (full extra cache copy)
        o = jnp.einsum("bkgs,bskh->bkgh", e.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
    o = o / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)[..., 0][..., None]
    return o.reshape(B, H, hd).astype(out_dtype)


# -------------------------------------------------------------- MLA paths ---
def mla_prefill(p: Params, x, positions, cfg: ModelConfig, *,
                cache: Optional[Dict] = None, lora=None, lora_scale: float = 0.0):
    B, S, d = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    kr = cfg.mla_kv_rank

    cq = L.rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)),
                    p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,ro->bso", cq, p["wq_b"].astype(x.dtype))
    if lora is not None and "q" in lora:
        a, b = lora["q"]
        q = q + lora_scale * jnp.einsum(
            "bsr,ro->bso", jnp.einsum("bsc,cr->bsr", cq, a.astype(x.dtype)),
            b.astype(x.dtype))
    q = q.reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv, k_rope = ckv[..., :kr], ckv[..., kr:]
    c_kv = L.rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    kv = jnp.einsum("bsr,ro->bso", c_kv, p["wkv_b"].astype(x.dtype))
    kv = kv.reshape(B, S, H, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rd))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_full = constrain(q_full, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "heads", None))
    v = constrain(v, ("batch", None, "heads", None))
    scale = (nd + rd) ** -0.5
    o = L.flash_attention(q_full, k, v, causal=True, scale=scale,
                          q_offset=positions[:, 0])
    o = o.reshape(B, S, H * vd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(x.dtype))
    if lora is not None and "o" in lora:
        a, b = lora["o"]
        out = out + lora_scale * jnp.einsum(
            "bsr,rd->bsd", jnp.einsum("bsh,hr->bsr", o, a.astype(x.dtype)),
            b.astype(x.dtype))
    out = constrain(out, ("batch", "seq_sp", None))
    new_cache = None
    if cache is not None:
        # contiguous prefill write (see _cache_write_prefill)
        S_max = cache["c_kv"].shape[1]
        c_kv_w = constrain(c_kv, ("batch", "seq_sp", None))

        def dus(buf, upd):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, upd[:, :S_max].astype(buf.dtype), 0, axis=1)

        new_cache = {
            "c_kv": dus(cache["c_kv"], c_kv_w),
            "k_rope": dus(cache["k_rope"], k_rope),
            "kv_pos": dus(cache["kv_pos"], positions),
        }
    return out, new_cache


def mla_decode(p: Params, x, positions, cache: Dict, cfg: ModelConfig, *,
               lora=None, lora_scale: float = 0.0):
    """Absorbed-matmul MLA decode: attention runs in the latent space, so the
    cache stays (kv_rank + rope_dim) per token — the paper-relevant memory win."""
    B = x.shape[0]
    H = cfg.num_heads
    nd, rd, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    kr = cfg.mla_kv_rank

    cq = L.rms_norm(jnp.einsum("bd,dr->br", x[:, 0], p["wq_a"].astype(x.dtype)),
                    p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("br,ro->bo", cq, p["wq_b"].astype(x.dtype))
    if lora is not None and "q" in lora:
        a, b = lora["q"]
        q = q + lora_scale * (cq @ a.astype(x.dtype)) @ b.astype(x.dtype)
    # two-step resharding: first materialize q col-sharded (the natural dot
    # output), THEN replicate. A direct replicate-constraint makes GSPMD
    # all-gather the (q_rank x H*(nd+rd)) WEIGHT — 576MB vs 6MB per layer.
    q = constrain(q, ("batch", "ff"))
    # keep the one-token q replicated on the model axis: the latent cache is
    # sequence-sharded and scores must inherit THAT sharding (flash-decode)
    q = constrain(q, ("batch", None))
    q = q.reshape(B, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = L.apply_rope(q_rope[:, None], positions[:, None],
                          cfg.rope_theta)[:, 0]

    ckv = jnp.einsum("bd,dr->br", x[:, 0], p["wkv_a"].astype(x.dtype))
    c_kv, k_rope = ckv[..., :kr], ckv[..., kr:]
    c_kv = L.rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, None, None, :], positions[:, None],
                          cfg.rope_theta)[:, 0, 0]

    bidx = jnp.arange(B)
    cache = {
        "c_kv": cache["c_kv"].at[bidx, positions].set(c_kv.astype(cache["c_kv"].dtype)),
        "k_rope": cache["k_rope"].at[bidx, positions].set(
            k_rope.astype(cache["k_rope"].dtype)),
        "kv_pos": cache["kv_pos"].at[bidx, positions].set(positions),
    }

    # Absorb W_kv_b's key half into q: q_lat (B, H, kv_rank)
    wkv_b = p["wkv_b"].reshape(kr, H, nd + vd).astype(x.dtype)
    w_k = wkv_b[..., :nd]                                   # (kr, H, nd)
    w_v = wkv_b[..., nd:]                                   # (kr, H, vd)
    q_lat = constrain(jnp.einsum("bhn,rhn->bhr", q_nope, w_k),
                      ("batch", None, None))
    scale = (nd + rd) ** -0.5
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, cache["c_kv"],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope, cache["k_rope"],
                      preferred_element_type=jnp.float32)) * scale
    valid = (cache["kv_pos"] >= 0) & (cache["kv_pos"] <= positions[:, None])
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    pmax = jnp.where(jnp.isneginf(pmax), 0.0, pmax)
    e = jnp.exp(s - pmax)
    e = jnp.where(valid[:, None, :], e, 0.0)
    o_lat = jnp.einsum("bhs,bsr->bhr", e.astype(cache["c_kv"].dtype),
                       cache["c_kv"], preferred_element_type=jnp.float32)
    o_lat = o_lat / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), w_v)
    o = o.reshape(B, H * vd)
    out = o @ p["wo"].astype(x.dtype)
    if lora is not None and "o" in lora:
        a, b = lora["o"]
        out = out + lora_scale * (o @ a.astype(x.dtype)) @ b.astype(x.dtype)
    return out[:, None], cache
