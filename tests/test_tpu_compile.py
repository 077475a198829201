"""The main-path Pallas kernels compile for TPU v5e at real widths.

Each test compiles one kernel (``interpret=False``) ahead of time for one
chip of a described v5e 2x2 topology, with no chip attached: what the
Mosaic lowering refuses (block shapes, VMEM use) fails here. Nothing runs,
so these say nothing about results or speed. The topology is described in
a fixture, not at import, so every test worker collects the same tests and
only the one that runs this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.lora_matmul import lora_matmul


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
