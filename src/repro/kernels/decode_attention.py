"""Paged GQA flash-decode attention — the decode-phase hot-spot kernel.

TPU-native design (DESIGN.md §6): the KV cache lives in a paged pool; the
page table is scalar-prefetched into SMEM so each grid step's BlockSpec
index_map dereferences the *physical* page — the kernel never gathers pages
through HBM-to-HBM copies (the GPU paged-attention trick mapped onto Pallas'
prefetch mechanism). Online-softmax accumulation runs in VMEM scratch across
the page-grid dimension.

One grid step takes a whole page with all of its KV heads: a block of
``(1, ptok, KV, hd)`` keeps the pool's trailing ``(KV, hd)`` dims whole, which
is what the TPU lowering requires of the second-minor dim (a one-head block
``(1, ptok, 1, hd)`` is refused). The body loops statically over the KV
heads; the q-heads of one KV head (GQA group) are processed together so the
MXU sees a (g x page_tokens) matmul per head.

Layout:
  q           (B, H, hd)
  k/v pages   (P, ptok, KV, hd)      one layer's pool
  page_table  (B, n_pages) int32     physical page per logical block
  lengths     (B,) int32             tokens valid per sequence
Grid: (B, n_pages) — page dim innermost, scratch carries (m, l, acc) per KV
head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(page_table, lengths, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ptok: int, kv_heads: int,
            scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths[b]
    live = (page_table[b, p] >= 0) & (p * ptok < length)

    @pl.when(live)
    def _body():
        for h in range(kv_heads):
            k = k_ref[0, :, h, :]                          # (ptok, hd)
            v = v_ref[0, :, h, :]                          # (ptok, hd)
            q = q_ref[0, h].astype(k.dtype)                # (g, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (g, ptok)
            pos = p * ptok + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - m_new)
            e = jnp.where(pos < length, e, 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(e, axis=1, keepdims=True)
            # weights go down to the cache dtype (as decode_attn_ref does),
            # the product accumulates in f32
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(p == n_pages - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, interpret: bool = True):
    """q: (B, H, hd); k/v_pages: (P, ptok, KV, hd); page_table: (B, n_pages);
    lengths: (B,). Returns (B, H, hd)."""
    B, H, hd = q.shape
    P, ptok, KV, _ = k_pages.shape
    n_pages = page_table.shape[1]
    g = H // KV
    scale = scale if scale is not None else hd ** -0.5
    qr = q.reshape(B, KV, g, hd)

    def q_map(b, p, pt, ln):
        return (b, 0, 0, 0)

    def kv_map(b, p, pt, ln):
        # pages past the last live one map to that page: an unchanged block
        # index is not fetched again, so a step moves only the live pages
        last = jnp.maximum((ln[b] - 1) // ptok, 0)
        return (jnp.maximum(pt[b, jnp.minimum(p, last)], 0), 0, 0, 0)

    gspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, KV, g, hd), q_map),
            pl.BlockSpec((1, ptok, KV, hd), kv_map),
            pl.BlockSpec((1, ptok, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, g, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ptok=ptok, kv_heads=KV, scale=scale),
        grid_spec=gspec,
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, lengths, qr, k_pages, v_pages)
    return out.reshape(B, H, hd)
