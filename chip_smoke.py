"""Chip smoke test: the co-located decode + LoRA finetune path on one TPU.

Drives ``launch/serve.run`` (ServingEngine + ColocatedRunner +
QoSScheduler) once with qwen2.5-7b at its published widths, cut in depth
only, from random weights made from a seed. Decode attention runs through
the Pallas kernel, and a LoRA job on its own qwen2.5-7b copy runs k
layer-units inside each decode round. The script fails unless

  * every request finishes with all of its tokens;
  * at least one whole finetune iteration completes with a finite loss;
  * every compiled co-located program holds the Mosaic kernel
    (``tpu_custom_call``);
  * decode logits through the kernel agree with the reference attention
    (``decode_attn_ref``) on the cache the run left behind.

    python chip_smoke.py

It needs a TPU: elsewhere it exits non-zero and prints no result. The last
line of stdout is one JSON object naming the device. Compiled programs are
kept in ``$JAX_COMPILATION_CACHE_DIR``, or in ``.jax_cache/`` at the root
of the checkout where that is unset, so a second run starts warm.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as MD  # noqa: E402
from repro.models.attention import decode_attn_ref  # noqa: E402
from repro.serving.request import Phase  # noqa: E402

ARCH = "qwen2.5-7b"
# Two 8-layer copies (serving + finetune) hold 2 x 5.91 GB of bf16 weights;
# with the KV cache, the finetune state and the co-located program's
# temporaries that is about 12.9 GB of the chip's 16 GiB (AOT
# memory_analysis for TPU v5e).
LAYERS = 8
# One prompt near s_max (seed 0 draws it once): 960 + 31 decoded tokens fill
# all sixteen 64-token pages of a slot, so the kernel check reads a whole
# slot, rescale across every page included.
RUN = dict(requests=8, slots=8, s_max=1024, prompt_lens=(128, 256, 960),
           new_tokens=(32,), colocate=True, k_max=2, ft_seq=1024,
           use_kernels=True)
PAGE_TOKENS = 64          # ops.decode_attention's page
# Kernel and reference both read bf16 K/V, score in f32 and round the
# softmax weights to bf16 before the PV product, but the kernel rescales
# them page by page (online softmax) where the reference normalises once,
# so the two round differently by up to one bf16 ulp, and every layer
# passes that on through bf16 activations and random weights. How far that
# moves the logits is measured in the same run: the reference again, with
# its attention in float32 at full matmul precision (no rounding of the
# weights at all), gives the noise floor. The kernel must sit within
# NOISE_FACTOR floors of the reference: a kernel exactly as far from the
# float32 attention as the reference, with errors of its own, sits about
# sqrt(2) floors away, hence 1.4. A wrong page or head mapping, or a broken
# rescale, moves the logits far more than rounding does. A one-token hole in
# the cache is subtler (3.2 floors in 24-token contexts on the smoke config,
# 1.3 in 100-token ones, on the CPU); tests/test_serving.py checks that the
# engine's cache has none.
NOISE_FACTOR = 1.4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts the executables this process obtains (persistent-cache hits
    included) with their seconds, and the persistent cache's hits and
    writes, from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration_secs

    def _event(self, event: str, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == _CACHE_WRITE_EVENT:
            self.cache_writes += 1


def rel_l2(x, ref) -> float:
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def attn_f32(q, kc, vc, kv_pos, positions, window=0, scale=None,
             scales=None, layer=None):
    """decode_attn_ref on float32 copies of its inputs: no bf16 rounding of
    the softmax weights. The TPU's default precision for a float32 matmul
    rounds its inputs to bf16, which would make this the reference again,
    hence "highest"."""
    f32 = lambda x: x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return decode_attn_ref(f32(q), f32(kc), f32(vc), kv_pos, positions,
                               window, scale=scale, layer=layer
                               ).astype(q.dtype)


def kernel_vs_reference(res):
    """Decode one more token in every slot from the cache the run left,
    through the kernel, the reference attention and the reference in
    float32. Returns the kernel path's compiled program, the three logits
    (as float32 NumPy arrays) and the longest context read."""
    eng, cfg = res.engine, res.cfg
    # positions 0..p-1 of a slot hold its tokens; the next one is written at p
    positions = jnp.max(eng.cache["scan"]["kv_pos"][0], axis=1) + 1
    args = (eng.params, jnp.asarray(eng.last_token), positions, eng.cache)
    paths = {"kernel": dict(use_kernels=True), "reference": {},
             "reference_f32": dict(decode_attn_fn=attn_f32)}
    compiled, logits = {}, {}
    for name, kw in paths.items():
        compiled[name] = jax.jit(lambda p, t, q, c, kw=kw: MD.decode_step(
            p, cfg, t, q, c, **kw)[0]).lower(*args).compile()
        logits[name] = np.asarray(compiled[name](*args), np.float32)
    return compiled["kernel"], logits, int(positions.max()) + 1


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}", flush=True)

    res = serve.run(ARCH, layers=LAYERS, **RUN)
    cfg = res.cfg
    print(f"cut: {ARCH} num_layers {cfg.num_layers} of "
          f"{serve.model_config(ARCH).num_layers} (depth only; d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size} as published), both copies",
          flush=True)
    print(f"set-up: {res.setup_s:.2f} s (weights, finetune state, "
          f"{len(res.compiled)} co-located variants); compiles "
          f"{counter.compiles} taking {counter.compile_s:.2f} s; persistent "
          f"cache hits {counter.cache_hits}, writes {counter.cache_writes}",
          flush=True)

    m = res.engine.metrics
    want = sum(r.max_new_tokens for r in res.requests)
    print(f"served: {len(res.requests)} requests, {m.tokens_out} tokens "
          f"(wanted {want}), {m.decode_rounds} decode rounds, {m.prefills} "
          f"prefills; finetune units run {res.units_done}, iterations "
          f"{int(res.ft_state['iter'])}, last loss "
          f"{float(res.ft_state['last_loss']):.4f}", flush=True)
    unfinished = [r.rid for r in res.requests
                  if r.phase != Phase.DONE or r.generated != r.max_new_tokens]
    if unfinished or m.tokens_out != want:
        fail(f"requests {unfinished} did not finish ({m.tokens_out} of "
             f"{want} tokens)")
    if int(res.ft_state["iter"]) < 1:
        fail("no finetune iteration completed")
    if not math.isfinite(float(res.ft_state["last_loss"])):
        fail("finetune loss is not finite")
    no_kernel = [k for k, c in res.compiled.items()
                 if "tpu_custom_call" not in c.as_text()]
    if no_kernel:
        fail(f"co-located variants k={no_kernel} hold no tpu_custom_call")
    print(f"kernel: tpu_custom_call in co-located variants "
          f"k={sorted(res.compiled)}", flush=True)

    kern_prog, logits, longest = kernel_vs_reference(res)
    if "tpu_custom_call" not in kern_prog.as_text():
        fail("the kernel decode program holds no tpu_custom_call")
    if longest <= RUN["s_max"] - PAGE_TOKENS:
        fail(f"the longest context checked, {longest} tokens, leaves the "
             f"last page of a {RUN['s_max']}-token slot unread")
    if not all(np.isfinite(x).all() for x in logits.values()):
        fail("decode logits are not finite")
    ref = logits["reference"]
    err, floor = (rel_l2(logits[k], ref) for k in ("kernel", "reference_f32"))
    print(f"kernel vs reference logits {ref.shape}, longest context "
          f"{longest} of {RUN['s_max']}: relative L2 {err:.3e}; "
          f"noise floor (float32 attention vs reference) {floor:.3e}; "
          f"tolerance {NOISE_FACTOR} x floor = {NOISE_FACTOR * floor:.3e}",
          flush=True)
    if not err <= NOISE_FACTOR * floor:
        fail("kernel and reference decode logits disagree")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}"
          f"; serve loop wall {res.wall_s:.2f} s; script wall "
          f"{time.perf_counter() - t_start:.2f} s; compiles {counter.compiles}"
          f" taking {counter.compile_s:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
