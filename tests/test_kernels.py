"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus hypothesis-driven paged layouts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # tier-1 container has none
    from _hyp_fallback import given, settings, strategies as st

from repro.configs import smoke_config
from repro.kernels import ops, ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.lora_matmul import lora_matmul as lora_kernel
from repro.kernels.ref import (lora_matmul_ref, paged_decode_attention_ref,
                               ssd_sequential_ref)
from repro.models import model as MD
from repro.models.ssm import ssd_chunked


# ---------------------------------------------------- decode attention ----
@pytest.mark.parametrize("B,H,KV,hd,ptok,npg,dtype", [
    (2, 8, 2, 64, 32, 4, jnp.float32),
    (3, 4, 4, 32, 16, 3, jnp.float32),
    (1, 16, 1, 128, 64, 2, jnp.float32),     # MQA, TPU-aligned head dim
    (2, 8, 2, 64, 32, 4, jnp.bfloat16),
])
def test_paged_decode_attention(B, H, KV, hd, ptok, npg, dtype, key):
    P = npg * B + 2
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, ptok, KV, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, ptok, KV, hd)).astype(dtype)
    pt = jax.random.permutation(ks[3], P)[:B * npg].reshape(B, npg)
    pt = pt.astype(jnp.int32).at[0, -1].set(-1)
    lengths = jax.random.randint(ks[4], (B,), 1, npg * ptok).astype(jnp.int32)
    out = paged_decode_attention(q, kp, vp, pt, lengths, interpret=True)
    expect = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 3), npg=st.integers(1, 4),
       ptok=st.sampled_from([8, 16]), seed=st.integers(0, 2 ** 16))
def test_paged_decode_attention_hypothesis(B, npg, ptok, seed):
    key = jax.random.PRNGKey(seed)
    H, KV, hd = 4, 2, 16
    P = B * npg + 1
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, ptok, KV, hd))
    vp = jax.random.normal(ks[2], (P, ptok, KV, hd))
    pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    lengths = jax.random.randint(ks[3], (B,), 1, npg * ptok).astype(jnp.int32)
    out = paged_decode_attention(q, kp, vp, pt, lengths, interpret=True)
    expect = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("arch", ["qwen2.5-7b", "qwen3-8b", "llama3-8b"])
def test_decode_step_kernel_matches_reference(arch, key):
    """model.decode_step through the kernel equals the decode_attn_ref path
    on the same cache, for each configuration whose decode takes the kernel
    (qwen3: qk-norm): GQA with 2 q heads per KV head, a cache of two 64-token
    pages, slots that end inside the second page, exactly at its start, and
    inside the first. The kernel reads every layer through the page table
    of the stacked cache. Float32 weights and cache, so only the order of
    the sums differs."""
    cfg = smoke_config(arch)
    assert cfg.q_per_kv > 1 and cfg.num_layers > 1
    params = MD.init_params(cfg, key, dtype=jnp.float32)
    B, S, plen = 3, 128, 100
    cache = MD.init_cache(cfg, B, S, dtype=jnp.float32)
    toks = jax.random.randint(key, (B, plen), 0, cfg.vocab_size)
    _, cache = MD.prefill(params, cfg, {"tokens": toks}, cache)
    positions = jnp.array([plen, 64, 7], jnp.int32)
    out = {uk: MD.decode_step(params, cfg, toks[:, 0], positions, cache,
                              use_kernels=uk)
           for uk in (True, False)}
    for a, b in zip(jax.tree.leaves(out[True]), jax.tree.leaves(out[False])):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch,use_kernels", [
    ("qwen2.5-7b", True),
    ("qwen2.5-7b", False),
    ("mixtral-8x7b", False),       # ring-buffered window: the slice path
    ("qwen2.5-7b-int8", False),    # int8 K/V with per-token scales
])
def test_decode_step_writes_only_the_new_token(arch, use_kernels, key):
    """A decode step changes exactly the entries [layer, b, slot of
    positions[b]] of the stacked K/V cache, in every layer and slot, and
    leaves every other element bit-identical."""
    int8 = arch.endswith("-int8")
    cfg = smoke_config(arch.removesuffix("-int8"))
    if int8:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    params = MD.init_params(cfg, key, dtype=jnp.float32)
    B, S, plen = 3, 128, 40
    cache = MD.init_cache(cfg, B, S, dtype=jnp.float32)
    toks = jax.random.randint(key, (B, plen), 0, cfg.vocab_size)
    _, cache = MD.prefill(params, cfg, {"tokens": toks}, cache)
    positions = jnp.array([plen, 63, 64], jnp.int32)
    _, new = MD.decode_step(params, cfg, toks[:, -1], positions, cache,
                            use_kernels=use_kernels)
    old, new = cache["scan"], new["scan"]
    n_layers, _, n_slots = old["kv_pos"].shape
    assert n_layers > 1
    assert (n_slots < S) == (cfg.window > 0) and ("k_scale" in old) == int8
    written = np.zeros(old["kv_pos"].shape, bool)
    written[:, np.arange(B), np.asarray(positions) % n_slots] = True
    for name in old:
        a, b = np.asarray(old[name]), np.asarray(new[name])
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a[~written], b[~written], err_msg=name)
        changed = (a[written] != b[written]).reshape(n_layers * B, -1)
        assert changed.any(axis=1).all(), name
    np.testing.assert_array_equal(
        np.asarray(new["kv_pos"])[:, np.arange(B),
                                  np.asarray(positions) % n_slots],
        np.broadcast_to(positions, (n_layers, B)))


@pytest.mark.parametrize("case", ["unaligned_length", "int8_scales",
                                  "window"])
def test_decode_attention_rejects_what_the_kernel_cannot_take(case):
    B, H, KV, hd = 2, 4, 2, 16
    S = 100 if case == "unaligned_length" else 128
    dtype = jnp.int8 if case == "int8_scales" else jnp.bfloat16
    kc = jnp.zeros((B, S, KV, hd), dtype)
    kw = {}
    if case == "int8_scales":
        kw["scales"] = (jnp.ones((B, S)), jnp.ones((B, S)))
    if case == "window":
        kw["window"] = 32
    with pytest.raises(ValueError):
        ops.decode_attention(jnp.zeros((B, H, hd), jnp.bfloat16), kc, kc,
                             jnp.zeros((B, S), jnp.int32),
                             jnp.zeros((B,), jnp.int32), **kw)


# --------------------------------------------------------- lora matmul ----
@pytest.mark.parametrize("M,K,N,r,dtype", [
    (64, 128, 96, 8, jnp.float32),
    (128, 512, 256, 16, jnp.float32),
    (37, 200, 130, 4, jnp.float32),          # ragged -> padded path
    (128, 256, 128, 16, jnp.bfloat16),
])
def test_lora_matmul(M, K, N, r, dtype, key):
    ks = jax.random.split(key, 4)
    x = (jax.random.normal(ks[0], (M, K)) * 0.1).astype(dtype)
    w = (jax.random.normal(ks[1], (K, N)) * 0.1).astype(dtype)
    a = (jax.random.normal(ks[2], (K, r)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[3], (r, N)) * 0.1).astype(dtype)
    y = ops.lora_matmul(x, w, a, b, 2.0)
    expect = lora_matmul_ref(x, w, a, b, 2.0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_lora_matmul_batched_input(key):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (2, 5, 64)) * 0.1
    w = jax.random.normal(ks[1], (64, 48)) * 0.1
    a = jax.random.normal(ks[2], (64, 4)) * 0.1
    b = jax.random.normal(ks[3], (4, 48)) * 0.1
    y = ops.lora_matmul(x, w, a, b, 1.5)
    expect = lora_matmul_ref(x.reshape(10, 64), w, a, b, 1.5).reshape(2, 5, 48)
    np.testing.assert_allclose(y, expect, atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------- ssd scan ----
@pytest.mark.parametrize("B,S,nh,hd,ds,chunk", [
    (2, 32, 8, 16, 32, 8),
    (1, 50, 4, 8, 16, 16),                  # ragged tail chunk
    (2, 64, 16, 32, 64, 32),
])
def test_ssd_scan_kernel(B, S, nh, hd, ds, chunk, key):
    ks = jax.random.split(key, 5)
    xs = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bt = jax.random.normal(ks[3], (B, S, ds)) * 0.3
    Ct = jax.random.normal(ks[4], (B, S, ds)) * 0.3
    y, ht = ops.ssd_scan(xs, dt, A, Bt, Ct, chunk)
    yr, htr = ssd_sequential_ref(xs, dt, A, Bt, Ct)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(ht, htr, atol=2e-3, rtol=2e-3)


def test_ssd_chunked_ref_matches_sequential(key):
    """The jnp chunked reference itself must equal the recurrence."""
    ks = jax.random.split(key, 5)
    B, S, nh, hd, ds = 2, 40, 4, 8, 16
    xs = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bt = jax.random.normal(ks[3], (B, S, ds)) * 0.3
    Ct = jax.random.normal(ks[4], (B, S, ds)) * 0.3
    y, ht = ssd_chunked(xs, dt, A, Bt, Ct, 8)
    yr, htr = ssd_sequential_ref(xs, dt, A, Bt, Ct)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(ht, htr, atol=2e-3, rtol=2e-3)


def test_ssd_scan_with_initial_state(key):
    ks = jax.random.split(key, 6)
    B, S, nh, hd, ds = 1, 24, 4, 8, 16
    xs = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bt = jax.random.normal(ks[3], (B, S, ds)) * 0.3
    Ct = jax.random.normal(ks[4], (B, S, ds)) * 0.3
    h0 = jax.random.normal(ks[5], (B, nh, hd, ds)) * 0.2
    y, ht = ops.ssd_scan(xs, dt, A, Bt, Ct, 8, h0=h0)
    yr, htr = ssd_sequential_ref(xs, dt, A, Bt, Ct, h0=h0)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(ht, htr, atol=2e-3, rtol=2e-3)
