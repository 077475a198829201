"""Jit'd public wrappers for the Pallas kernels.

TPU is the target; on any other backend the kernels execute in interpret
mode (Python evaluation of the kernel body) so correctness is validated
everywhere. Wrappers own the layout plumbing: padding to tile multiples,
(B, S, d) <-> (M, K) reshapes, and the dense-cache adapter used by
model.decode_step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import lora_matmul as _lm
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    """Interpret mode off the TPU (CPU tests). On the chip the kernels lower
    through Mosaic; chip_smoke.py checks the compiled program for it."""
    return jax.default_backend() != "tpu"


# ------------------------------------------------------- decode attention --
@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None):
    return _da.paged_decode_attention(q, k_pages, v_pages, page_table,
                                      lengths, scale=scale,
                                      interpret=_interpret())


def decode_attention(q, kc, vc, kv_pos, positions, window: int = 0,
                     scale=None, page_tokens: int = 64, scales=None):
    """Dense-cache adapter matching attention.decode_attn_ref's signature so
    model.decode_step can swap the kernel in: treats each slot's contiguous
    cache as pages of `page_tokens`. A full-attention slot holds positions
    0..positions[b] in order, so its valid length is positions + 1.

    q: (B, H, hd); kc/vc: (B, S, KV, hd); kv_pos: (B, S); positions: (B,).
    Raises on what the kernel cannot take (no silent reference fallback):
    ring-buffered windowed caches, a cache length that is not a multiple of
    `page_tokens`, and int8 caches with per-token scales.
    """
    B, S, KV, hd = kc.shape
    if window > 0:
        raise ValueError(
            f"decode_attention kernel has no sliding-window variant "
            f"(window={window}); windowed layers use decode_attn_ref")
    if S % page_tokens:
        raise ValueError(
            f"cache length {S} is not a multiple of the kernel page size "
            f"{page_tokens}")
    if scales is not None and scales[0] is not None:
        raise ValueError("decode_attention kernel reads bf16/f32 pages; "
                         "int8 caches with per-token scales use "
                         "decode_attn_ref")
    n_pages = S // page_tokens
    k_pages = kc.reshape(B * n_pages, page_tokens, KV, hd)
    v_pages = vc.reshape(B * n_pages, page_tokens, KV, hd)
    page_table = jnp.arange(B * n_pages, dtype=jnp.int32).reshape(B, n_pages)
    lengths = positions + 1
    return _da.paged_decode_attention(q, k_pages, v_pages, page_table,
                                      lengths, scale=scale,
                                      interpret=_interpret())


# ------------------------------------------------------------ LoRA matmul --
def lora_matmul(x, w, a, b, scale: float, block_m: int = 128,
                block_n: int = 128, block_k: int = 512):
    """x: (..., K); w: (K, N); a: (K, r); b: (r, N) -> (..., N).
    Pads M/N/K to tile multiples; r stays as-is (kept in VMEM)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]
    bm = min(block_m, max(M, 8))
    bn = min(block_n, N)
    bk = min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        xm = jnp.pad(xm, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    ap = jnp.pad(a, ((0, pk), (0, 0))) if pk else a
    bp = jnp.pad(b, ((0, 0), (0, pn))) if pn else b
    y = _lm.lora_matmul(xm, wp, ap, bp, scale, block_m=bm, block_n=bn,
                        block_k=bk, interpret=_interpret())
    return y[:M, :N].reshape(*lead, N)


# ---------------------------------------------------------------- SSD scan --
def ssd_scan(xs, dt, A, Bt, Ct, chunk: int, h0=None, nhb: int = 8):
    """Chunked SSD scan matching models.ssm.ssd_chunked's contract.
    xs: (B, S, nh, hd); dt: (B, S, nh) (softplus applied); A: (nh,) (<0);
    Bt/Ct: (B, S, ds). Returns y (B, S, nh, hd) f32, hT (B, nh, hd, ds) f32.
    """
    B, S, nh, hd = xs.shape
    ds = Bt.shape[-1]
    c = min(chunk, S)
    n = -(-S // c)
    pad = n * c - S
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bt = jnp.pad(Bt, ((0, 0), (0, pad), (0, 0)))
        Ct = jnp.pad(Ct, ((0, 0), (0, pad), (0, 0)))
    if h0 is None:
        h0 = jnp.zeros((B, nh, hd, ds), jnp.float32)
    while nh % nhb:
        nhb //= 2
    y, ht = _ssd.ssd_scan_chunked(
        xs.reshape(B, n, c, nh, hd), dt.reshape(B, n, c, nh), A,
        Bt.reshape(B, n, c, ds), Ct.reshape(B, n, c, ds), h0,
        nhb=max(nhb, 1), interpret=_interpret())
    return y.reshape(B, n * c, nh, hd)[:, :S], ht
