"""Serving substrate: engine continuous batching, page-table manager,
trace generator statistics."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # tier-1 container has none
    from _hyp_fallback import given, settings, strategies as st

from repro import obs
from repro.core.colocation import ColocatedRunner
from repro.models import model as MD
from repro.models.config import LoRAConfig, ModelConfig
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagePoolSpec, PageTableManager
from repro.serving.request import Request
from repro.serving.trace import TraceConfig, controlled_load, generate
from repro.training import peft as P
from repro.training.data import DataConfig, Prefetcher, SyntheticCorpus


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=256)
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_continuous_batching(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_slots=4, s_max=64)
    reqs = [Request(rid=i, arrival=i * 0.01, prompt_len=8 + i,
                    max_new_tokens=6) for i in range(6)]
    t0 = time.perf_counter()
    m = eng.run_trace(reqs)
    rounds = [r for r in obs.records(t0) if r.name == "engine.round"]
    assert m.prefills == 6
    assert m.tokens_out == 6 * 6
    assert len(rounds) == m.decode_rounds
    assert max(r.attrs["bs"] for r in rounds) == 4        # slots saturate
    assert all(r.phase.value == "done" for r in reqs)


def test_engine_and_colocated_round_spans():
    """An admission and a co-located round each leave one span with its
    steps as children, in order; the runner's span names its quantum."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=128,
                      lora=LoRAConfig(rank=4))
    key = jax.random.PRNGKey(0)
    params = MD.init_params(cfg, key)
    pc = P.PeftConfig(micro_batch=2, seq_len=8, accum=1)
    pf = Prefetcher(SyntheticCorpus(DataConfig(128, 8, 2)).batches(), 2)
    ft = P.init_ft_state(cfg, pc, params, key, pf.stacked())
    eng = ServingEngine(cfg, params, max_slots=2, s_max=32)
    runner = ColocatedRunner(cfg, params, cfg, params, pc, k_max=2)
    tok = jnp.zeros((2,), jnp.int32)
    runner.precompile(tok, tok, eng.cache, ft, ks=[2])

    def step(tokens, positions, cache):
        nonlocal ft
        logits, cache, ft = runner.run_round(2, tokens, positions, cache, ft)
        return logits, cache

    t0 = time.perf_counter()
    req = Request(rid=41, arrival=0.0, prompt_len=6, max_new_tokens=4)
    assert eng.try_admit(req, np.arange(6, dtype=np.int32))
    out = eng.decode_round(step)
    recs = obs.records(t0)
    assert set(out) == {41}

    def children(parent):
        kids = [r for r in recs if r.parent == parent.name
                and parent.t0 <= r.t0 and r.t1 <= parent.t1]
        return [r.name for r in sorted(kids, key=lambda r: r.t0)]

    (admit,) = [r for r in recs if r.name == "engine.admit"]
    assert admit.attrs == {"rid": 41, "prompt_len": 6, "admitted": True}
    assert children(admit) == ["engine.admit.cache", "engine.admit.prefill",
                               "engine.admit.insert",
                               "engine.admit.first_token"]
    (rnd,) = [r for r in recs if r.name == "engine.round"]
    assert rnd.attrs == {"bs": 1} and rnd.t0 >= admit.t1
    assert children(rnd) == ["engine.round.inputs", "engine.round.step",
                             "engine.round.pull", "engine.round.commit"]
    (colo,) = [r for r in recs if r.name == "colo.round"]
    assert colo.attrs == {"k": 2} and colo.parent == "engine.round.step"
    assert not [r for r in recs if r.name == "colo.compile"]


def _assert_no_holes(cfg, params, n_front):
    eng = ServingEngine(cfg, params, max_slots=2, s_max=64)
    reqs = [Request(rid=i, arrival=0.0, prompt_len=5 + 3 * i,
                    max_new_tokens=6) for i in range(2)]
    eng.run_trace(reqs)
    kv_pos = np.asarray(eng.cache["scan"]["kv_pos"][0])
    for r in reqs:
        # frontend + prompt entries, then the decoded tokens; the last token
        # is returned only
        n = n_front + r.context_len - 1
        np.testing.assert_array_equal(kv_pos[r.slot, :n], np.arange(n))
        assert (kv_pos[r.slot, n:] == -1).all()


def test_engine_cache_has_no_holes(tiny):
    """Each decode round writes the newest token right after the cached
    context, so a slot holds positions 0..n-1 in order (the decode kernel
    reads a slot as its first positions + 1 entries)."""
    _assert_no_holes(*tiny, n_front=0)


def test_engine_cache_has_no_holes_behind_frontend():
    """With a vision frontend, prefill caches the patch embeddings before
    the prompt; decode continues after both."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=256,
                      frontend="vision", frontend_tokens=4)
    _assert_no_holes(cfg, MD.init_params(cfg, jax.random.PRNGKey(0)),
                     n_front=4)


def test_engine_memory_pressure_rejects(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_slots=4, s_max=64, num_pages=4,
                        page_tokens=16)
    r = Request(rid=0, arrival=0.0, prompt_len=60, max_new_tokens=4)
    ok = eng.try_admit(r, np.arange(60, dtype=np.int32) % 256)
    assert ok
    r2 = Request(rid=1, arrival=0.0, prompt_len=60, max_new_tokens=4)
    t0 = time.perf_counter()
    assert not eng.try_admit(r2, np.arange(60, dtype=np.int32) % 256)
    assert eng.metrics.rejected_admissions == 1
    (rec,) = obs.records(t0)
    assert rec.name == "engine.admit" and rec.attrs == {
        "rid": 1, "prompt_len": 60, "admitted": False}


# ------------------------------------------------------- page tables ------
@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["admit", "extend", "release"]),
                          st.integers(0, 7), st.integers(1, 40)),
                min_size=1, max_size=60))
def test_page_table_invariants(ops):
    spec = PagePoolSpec(n_layers=2, num_pages=32, page_tokens=8,
                        kv_heads=2, head_dim=16)
    mgr = PageTableManager(spec, max_slots=8, max_pages_per_seq=8)
    for op, slot, n in ops:
        if op == "admit" and slot not in mgr.tables:
            mgr.admit(slot, n)
        elif op == "extend" and slot in mgr.tables:
            mgr.extend(slot, n)
        elif op == "release":
            mgr.release(slot)
        # no page owned twice
        owned = [p for pages in mgr.tables.values() for p in pages]
        assert len(owned) == len(set(owned))
        assert len(owned) + len(mgr.free) == spec.num_pages
        for s, pages in mgr.tables.items():
            assert len(pages) >= -(-mgr.lengths[s] // spec.page_tokens)


def test_page_table_usable_cap():
    spec = PagePoolSpec(n_layers=2, num_pages=16, page_tokens=8,
                        kv_heads=2, head_dim=16)
    mgr = PageTableManager(spec, 4, 8)
    mgr.set_usable(2)                 # allocator lent the rest to finetune
    assert mgr.admit(0, 16)
    assert not mgr.admit(1, 8)        # over the usable cap
    mgr.set_usable(16)
    assert mgr.admit(1, 8)


# ------------------------------------------------------------- traces -----
def test_trace_statistics():
    reqs = generate(TraceConfig(duration_s=600, mean_rps=5.3, seed=0))
    n = len(reqs)
    assert 0.6 * 5.3 * 600 < n < 1.6 * 5.3 * 600
    prompts = np.array([r.prompt_len for r in reqs])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert 500 < np.median(prompts) < 2000       # lognormal around 1024
    assert 60 < np.median(outs) < 300
    arr = np.diff([r.arrival for r in reqs])
    assert np.std(arr) > np.mean(arr)            # burstier than Poisson


def test_trace_deterministic():
    a = generate(TraceConfig(duration_s=60, seed=7))
    b = generate(TraceConfig(duration_s=60, seed=7))
    assert [(r.arrival, r.prompt_len) for r in a] == \
        [(r.arrival, r.prompt_len) for r in b]


def test_controlled_load_phases():
    reqs = controlled_load(phases=((8, 30.0), (42, 30.0)), output_len=200)
    t = np.array([r.arrival for r in reqs])
    early = ((t >= 5) & (t < 30)).sum() / 25.0
    late = ((t >= 35) & (t < 60)).sum() / 25.0
    assert late > 3 * early                      # heavy phase is heavier


def test_paged_pool_roundtrip_matches_dense(key):
    """paged_write + the Pallas paged kernel reproduce dense decode
    attention through a page-table indirection."""
    import jax.numpy as jnp
    from repro.kernels.ops import paged_decode_attention
    from repro.models.attention import decode_attn_ref
    from repro.serving.kv_cache import PagePoolSpec, PageTableManager, \
        paged_write

    spec = PagePoolSpec(n_layers=1, num_pages=12, page_tokens=8,
                        kv_heads=2, head_dim=16, dtype=jnp.float32)
    pool = spec.alloc()
    mgr = PageTableManager(spec, max_slots=3, max_pages_per_seq=4)
    lengths = [11, 19, 5]
    for slot, ln in enumerate(lengths):
        assert mgr.admit(slot, ln)
    table = jnp.asarray(mgr.table_array([0, 1, 2]))

    ks = jax.random.split(key, 2 * max(lengths))
    dense_k = np.zeros((3, 32, 2, 16), np.float32)
    dense_v = np.zeros((3, 32, 2, 16), np.float32)
    for pos in range(max(lengths)):
        kn = jax.random.normal(ks[2 * pos], (3, 2, 16))
        vn = jax.random.normal(ks[2 * pos + 1], (3, 2, 16))
        # clamp inactive slots to their last valid position; their writes
        # are overwritten by nothing (position already written) but the
        # final pass below only trusts positions < length
        positions = jnp.asarray([min(pos, ln - 1) for ln in lengths],
                                jnp.int32)
        pool = paged_write(pool, table, 0, positions, kn, vn)
        for s_ in range(3):
            p_ = min(pos, lengths[s_] - 1)
            dense_k[s_, p_] = np.asarray(kn[s_])
            dense_v[s_, p_] = np.asarray(vn[s_])

    q = jax.random.normal(key, (3, 4, 16))
    lens = jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(q, pool[0, 0], pool[0, 1], table, lens)

    kv_pos = np.full((3, 32), -1, np.int32)
    for s_, ln in enumerate(lengths):
        kv_pos[s_, :ln] = np.arange(ln)
    ref = decode_attn_ref(q, jnp.asarray(dense_k), jnp.asarray(dense_v),
                          jnp.asarray(kv_pos),
                          jnp.asarray([ln - 1 for ln in lengths], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
