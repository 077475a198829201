"""A cell made from new data files alone, at smoke widths, for CPU tests.

``make_root(tmp)`` writes a configuration, a traffic mix, a peaks table
keyed by the CPU's device kind and a BENCHMARK.json naming one cell into
``tmp``, beside a copy of the benchmark's metric readers and architecture
modules."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "smoke_qwen3.smoke_mix"
RATE = 4.0

CONFIG = {
    "name": "smoke_qwen3", "smoke": True,
    "source": "https://huggingface.co/Qwen/Qwen3-8B/blob/main/config.json",
    "arch": "qwen3-8b",
    "weights": "models/dense_decoder/weights.py",
    "reference": "models/dense_decoder/reference.py",
    "counts": "models/dense_decoder/counts.py", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "tie_word_embeddings": False,
    "reduced": ["hidden_size", "num_hidden_layers"],
    "serving": {"slots": 4, "s_max": 256},
    "finetune": {"micro_batch": 2, "seq_len": 64, "accum": 2, "n_stage": 4,
                 "lora_rank": 4, "lora_alpha": 32.0,
                 "targets": ["q", "k", "v", "o", "gate", "up", "down"],
                 "optimizer": {"lr": 1e-4, "b1": 0.9, "b2": 0.999,
                               "eps": 1e-8, "weight_decay": 0.0,
                               "grad_clip": 1.0, "warmup_steps": 10}},
    "scheduler": {"k_max": 2, "qos_s": 0.04},
    "check": {"iterations": 2, "sample_requests": 4},
    "limits": {"serve_gap": 0.05, "ft_loss_gap": 1e-3, "ft_grad_gap": 0.05,
               "ft_change_gap": 0.05},
}

TRAFFIC = {
    "arrival": {"shape": 1.0, "amplitude": 0.0, "period_s": 20.0},
    "prompt": {"median": 48, "sigma": 0.5, "cap": 128, "round_to": 64},
    "output": {"median": 12, "sigma": 0.5, "cap": 40},
    "pre_window_s": 1.0,
    "population_seed": 5,
}


def make_root(tmp: Path, config=None, traffic=None) -> Path:
    tmp = Path(tmp)
    for d in ("configs", "traffic", "cells"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "models"):
        shutil.copytree(BENCH / d, tmp / d, dirs_exist_ok=True)
    (tmp / "configs" / "smoke_qwen3.json").write_text(
        json.dumps(config or CONFIG))
    (tmp / "traffic" / "smoke_mix.json").write_text(
        json.dumps(traffic or TRAFFIC))
    (tmp / "cells" / f"{CELL}.json").write_text(json.dumps(
        {"rate_per_s": RATE}))
    (tmp / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 2**34}}))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    real["workloads"] = [{"name": CELL, "config": "smoke_qwen3",
                          "traffic": "smoke_mix", "chips": 1,
                          "why": "smoke"}]
    for m in real["end_to_end"] + real["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(real))
    return tmp


def run_smoke(tmp: Path, compiles, seed: int = 3_000_000_001,
              seconds: float = 2.0, config=None, traffic=None):
    """One run of the smoke cell on the CPU (the chip check skipped);
    returns (result, compared lines, info lines)."""
    import run as RUN
    from harness import spec
    root = make_root(tmp, config, traffic)
    args = RUN.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    lines = []
    res, compared = RUN.run(args, bench=spec.Bench(root,
                                                   root / "BENCHMARK.json"),
                            require_tpu=False, compiles=compiles,
                            info=lines.append)
    return res, compared, lines
