"""TPU v5e hardware constants used by the roofline cost model, the latency
predictor's ground-truth simulator, and the dry-run roofline analysis.

These are published peaks, not measurements. The cost model, roofline and
predictor fits derive from them; nothing here says how fast the real path
(``chip_smoke.py``, ``launch/serve.py``) runs on a chip.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bytes: float            # HBM capacity per chip
    hbm_bw: float               # bytes/s HBM bandwidth per chip
    ici_bw_per_link: float      # bytes/s per ICI link
    ici_links: int              # links per chip in a 2D torus
    host_dma_bw: float          # bytes/s host<->HBM (weight-window swapping)
    vmem_bytes: float           # VMEM per core (Pallas tiling budget)
    mxu_tile: int               # MXU systolic dimension (128x128)


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    host_dma_bw=32e9,
    vmem_bytes=128 * 1024**2,
    mxu_tile=128,
)

# The paper evaluates on Ada6000/A100; kept for the paper-figure benchmarks that
# reason about the GPU baseline (Fig. 1/4 reproduction uses the same roofline
# methodology with these constants to show the shape of the curves).
ADA6000 = ChipSpec(
    name="ada6000",
    peak_flops_bf16=182.5e12,
    hbm_bytes=48 * 1024**3,
    hbm_bw=960e9,
    ici_bw_per_link=0.0,
    ici_links=0,
    host_dma_bw=32e9,
    vmem_bytes=0.0,
    mxu_tile=16,
)

DEFAULT_CHIP = TPU_V5E

# Mesh shapes for the production dry-run (see launch/mesh.py).
SINGLE_POD_SHAPE = (16, 16)            # ("data", "model") = 256 chips
MULTI_POD_SHAPE = (2, 16, 16)          # ("pod", "data", "model") = 512 chips
