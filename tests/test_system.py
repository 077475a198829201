"""End-to-end system behaviour: real-compute co-located serving on a smoke
config — the whole Harli stack (engine + colocated runner + scheduler +
predictor) driving actual XLA programs."""

import pytest

from repro.configs import smoke_config
from repro.core.colocation import make_ft_only_step
from repro.launch import serve
from repro.models import model as MD
from repro.serving.request import Phase
from repro.training import peft as P
from repro.training.data import DataConfig, Prefetcher, SyntheticCorpus


@pytest.mark.slow
@pytest.mark.parametrize("use_kernels", [False, True])
def test_colocated_serving_end_to_end(use_kernels, monkeypatch):
    """launch/serve.run, the entry point chip_smoke.py drives on a TPU, at
    smoke size: every request finishes with all of its tokens and the
    fused programs make finetune progress (kernels in interpret mode)."""
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    res = serve.run("llama3-8b", smoke=True, requests=5, slots=3, s_max=128,
                    prompt_lens=range(6, 14), new_tokens=(5,),
                    colocate=True, k_max=4, ft_seq=16,
                    use_kernels=use_kernels)
    reqs = res.requests
    assert all(r.phase == Phase.DONE and r.generated == 5 for r in reqs)
    assert res.engine.metrics.tokens_out == 5 * len(reqs)
    assert sorted(res.compiled) == [0, 1, 2, 3, 4]
    assert res.units_done > 0, "no finetune units were co-scheduled"
    # finetune made real progress inside the fused programs
    ft_state = res.ft_state
    assert int(ft_state["iter"]) >= 1 or int(ft_state["unit_idx"]) > 0


def test_ft_only_burst(key):
    cfg = smoke_config("qwen3-8b")
    params = MD.init_params(cfg, key)
    pc = P.PeftConfig(micro_batch=2, seq_len=12, accum=1)
    pf = Prefetcher(SyntheticCorpus(
        DataConfig(cfg.vocab_size, 12, 2, seed=1)).batches(), pc.n_stage)
    state = P.init_ft_state(cfg, pc, params, key, pf.stacked())
    burst = make_ft_only_step(cfg, params, pc, units=3)
    s2 = burst(state)
    assert int(s2["unit_idx"]) == 3
