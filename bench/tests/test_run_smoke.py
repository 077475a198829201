"""Whole runs of a cell made from new data files alone, at smoke widths on
the CPU: the harness finds it by name, serves it, and the check passes;
then the same run with the timed path broken underneath, once per fault the
cell can have, and the check fails."""

import copy

import pytest

import smoke


@pytest.fixture(scope="module")
def compiles():
    from harness.cell import CompileCounter
    return CompileCounter()


@pytest.fixture(scope="module")
def sound(tmp_path_factory, compiles):
    return smoke.run_smoke(tmp_path_factory.mktemp("sound"), compiles)


def test_new_cell_from_data_files_runs_and_is_correct(sound):
    res, compared, info = sound
    assert res["correct"], (compared, info)
    assert res["failed"] == 0 and res["attempted"] == 8
    assert set(res["metrics"]) == {"ft_tokens_per_s", "tpot_mean_ms",
                                   "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert [c.split(":")[0] for c in compared] == [
        "check serve_gap", "check ft_loss_gap", "check ft_grad_gap",
        "check ft_change_gap"]


def test_requests_in_flight_at_the_close_are_not_failed(tmp_path,
                                                        compiles):
    import re
    # outputs of about 100 tokens: those due late in the window are still
    # decoding when it closes
    tr = copy.deepcopy(smoke.TRAFFIC)
    tr["output"] = {"median": 100, "sigma": 0.05, "cap": 120}
    res, _, info = smoke.run_smoke(tmp_path, compiles, traffic=tr)
    line = next(s for s in info if s.startswith("set-up"))
    due, finished = (int(x) for x in re.search(
        r"requests due (\d+), admitted \d+, finished (\d+)", line).groups())
    assert finished < due == res["attempted"]
    assert res["failed"] == 0


def test_a_short_output_counts_as_failed(tmp_path, compiles, monkeypatch):
    from repro.serving import engine as E
    from repro.serving.request import Phase
    real = E.ServingEngine.decode_round

    def cut_short(self, step=None):
        out = real(self, step)
        for i, r in enumerate(self.slots):
            if r is not None and r.rid % 2 == 0 and r.generated >= 2:
                r.phase = Phase.DONE          # ends it without saying so
                self.pages.release(r.slot)
                self.slots[i] = None
        return out

    monkeypatch.setattr(E.ServingEngine, "decode_round", cut_short)
    res, _, info = smoke.run_smoke(tmp_path, compiles)
    assert res["failed"] >= 1 and not res["correct"]
    assert any("short:" in s for s in info)


def test_fault_state_unchanged(tmp_path, compiles, monkeypatch):
    from repro.training import peft
    monkeypatch.setattr(peft, "run_units", lambda unit_step, state, k: state)
    res, compared, _ = smoke.run_smoke(tmp_path, compiles)
    assert not res["correct"]
    assert res["checks"]["ft_loss_gap"]["value"] > \
        res["checks"]["ft_loss_gap"]["limit"]


def test_fault_half_the_batch(tmp_path, compiles, monkeypatch):
    from repro.models import layers
    real = layers.chunked_softmax_xent

    def half(x, table, labels, mask=None, chunk=256):
        n = x.shape[0] // 2
        return real(x[:n], table, labels[:n],
                    None if mask is None else mask[:n], chunk)

    monkeypatch.setattr(layers, "chunked_softmax_xent", half)
    res, compared, _ = smoke.run_smoke(tmp_path, compiles)
    assert not res["correct"], compared


def test_fault_token_altered_where_produced(tmp_path, compiles, monkeypatch):
    from repro.serving import engine as E
    real = E.ServingEngine.decode_round

    def altered(self, step=None):
        out = real(self, step)
        for i, r in enumerate(self.slots):
            if r is not None and r.rid in out and r.generated == 3:
                tok = (out[r.rid] + 1) % self.cfg.vocab_size
                out[r.rid] = tok
                self.last_token[i] = tok
        return out

    monkeypatch.setattr(E.ServingEngine, "decode_round", altered)
    res, compared, _ = smoke.run_smoke(tmp_path, compiles)
    assert not res["correct"]
    assert res["checks"]["serve_gap"]["value"] > \
        res["checks"]["serve_gap"]["limit"]


def test_control_fails_the_check_where_the_program_passes(tmp_path,
                                                          compiles):
    """control.py's readings of the smoke cell: the program's are within
    every limit, and the fp8 control and the half-batch fault each exceed
    at least one."""
    import control
    from harness import cell as C, check, spec
    root = smoke.make_root(tmp_path)
    b = spec.Bench(root, root / "BENCHMARK.json")
    e = b.cell(smoke.CELL)
    conf = b.config(e["config"])
    cell = C.Cell(conf, b.arch(conf), b.traffic(e["traffic"]),
                  b.rate(smoke.CELL), 3_000_000_007, 2.0, compiles)
    w0, w1 = cell.serve()
    got = control.readings(cell, C.window_records(cell, w0, w1))
    limits = conf["limits"]
    assert got["sample_tokens"] > 0
    assert check.judge(got["program"], limits) == []
    assert check.judge(got["fp8"], limits)
    half = {k: v for k, v in got["half_batch"].items() if k in limits}
    assert [k for k, v in half.items() if v > limits[k]]
