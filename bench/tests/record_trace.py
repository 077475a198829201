"""Record the small profiler trace that test_trace.py reduces, on a TPU:

    python3 bench/tests/record_trace.py <out_dir>

A "wait", then two "round"s, each a jitted matmul and a call of the paged
decode-attention kernel, each followed by an "admit" span in which the
device idles; all inside a "window" span. Writes <out_dir>/fixture.xplane.pb and prints the planes
and lines the trace holds."""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(out: Path) -> None:
    from repro.kernels import ops
    B, S, H, KV, hd = 4, 256, 8, 2, 128
    q = jnp.ones((B, H, hd), jnp.bfloat16)
    kc = jnp.ones((B, S, KV, hd), jnp.bfloat16)
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    positions = jnp.array([255, 100, 10, 0], jnp.int32)
    attn = jax.jit(lambda q, k, p, pos: ops.decode_attention(q, k, k, p, pos))
    mm = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((attn(q, kc, kv_pos, positions), mm(x)))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # as run.py traces
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("wait"):
            time.sleep(0.01)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("round"):
                jax.block_until_ready((mm(x), attn(q, kc, kv_pos, positions)))
            with jax.profiler.TraceAnnotation("admit"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "fixture.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(src))
    for plane in pd.planes:
        for line in plane.lines:
            ev = list(line.events)
            names = sorted({e.name for e in ev})[:12]
            print(f"plane {plane.name!r} line {line.name!r}: {len(ev)} "
                  f"events; {names}")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
