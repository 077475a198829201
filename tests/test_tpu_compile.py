"""The main-path Pallas kernels compile for TPU v5e at real widths.

Each test compiles one kernel (``interpret=False``) ahead of time for one
chip of a described v5e 2x2 topology, with no chip attached: what the
Mosaic lowering refuses (block shapes, VMEM use) fails here. Nothing runs,
so these say nothing about results or speed. The topology is described in
a fixture, not at import, so every test worker collects the same tests and
only the one that runs this file loads the TPU library.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.lora_matmul import lora_matmul
from repro.models import model as MD


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("heads,kv_heads", [
    (28, 4),                                  # qwen2.5-7b
    (32, 8),                                  # llama3-8b / qwen3-8b
])
def test_paged_decode_attention_compiles(one_chip, no_compile_cache, heads,
                                         kv_heads):
    B, n_pages, ptok, hd = 8, 16, 64, 128
    bf16 = jnp.bfloat16
    _compile(functools.partial(paged_decode_attention, interpret=False),
             one_chip,
             ((B, heads, hd), bf16),
             ((B * n_pages, ptok, kv_heads, hd), bf16),
             ((B * n_pages, ptok, kv_heads, hd), bf16),
             ((B, n_pages), jnp.int32),
             ((B,), jnp.int32))


def test_lora_matmul_compiles(one_chip, no_compile_cache):
    # qwen2.5-7b up-projection over a finetune micro-batch of 2 x 1024
    M, K, N, r = 2048, 3584, 18944, 16
    bf16 = jnp.bfloat16
    _compile(functools.partial(lora_matmul, scale=2.0, interpret=False),
             one_chip,
             ((M, K), bf16), ((K, N), bf16), ((K, r), bf16), ((r, N), bf16))


# an array-valued HLO instruction: name, result shape, opcode, operands
_INSTRUCTION = re.compile(
    r"(%[\w.-]+) = (\w+\[[\d,]*\])(?:\{[^}]*\})? ([\w-]+)\(([^)]*)\)")


def test_decode_step_writes_the_stacked_cache_in_place(one_chip,
                                                       no_compile_cache,
                                                       monkeypatch):
    """decode_step at qwen2.5-7b widths (2 layers, 8 slots x 1024) with the
    kernel on and the cache donated, as the engine and the co-located runner
    call it: no instruction holds a whole layer's K or V, so the layer scan
    neither slices a layer out of the stacked cache nor writes one back."""
    # the host's backend is the CPU, which would pick the kernel's interpret
    # mode; the described chip compiles it for Mosaic
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2.5-7b"), num_layers=2)
    B, S = 8, 1024
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(functools.partial(MD.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(functools.partial(MD.init_cache, cfg, B,
                                                     S)))
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, q, c: MD.decode_step(p, cfg, t, q, c, use_kernels=True),
        donate_argnums=3).lower(params, tok, tok, cache).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    instructions = [(m[1], m[2], m[3], re.findall(r"%[\w.-]+", m[4]))
                    for m in _INSTRUCTION.finditer(hlo)]
    shape_of = {name: shape for name, shape, _, _ in instructions}

    def one_layer(shape):        # bf16[8,1024,4,128], unit dims aside
        dtype, dims = shape.rstrip("]").split("[")
        return dtype == "bf16" and [int(d) for d in dims.split(",") if d
                                    and d != "1"] == [B, S, cfg.num_kv_heads,
                                                      cfg.head_dim]

    # slice-done ends a move of the stack into the chip's faster memory,
    # layer by layer, which the compiler makes where the stack fits there
    # (two layers here, not eight of 32 x 4096): no part of the layer scan
    assert [n for n, shape, op, _ in instructions
            if one_layer(shape) and op != "slice-done"] == []
    assert [n for n, _, op, args in instructions
            if op == "dynamic-update-slice" and len(args) > 1
            and one_layer(shape_of.get(args[1], "[]"))] == []
    layer_bytes = B * S * cfg.num_kv_heads * cfg.head_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
