"""The `zamba2-7b-18l-serve-failover` cell without the chip, on the CPU at
its published widths but three layers deep (each a hybrid layer, so both
shared blocks are present and block 0 is applied twice; the 18 layers'
weights and the float32 reference's copy would not fit a test's memory
and minute), with one batch of two sequences after one of warm-up: a sound
run is correct; the run with its decode returning the state unchanged is
not; and the fp8 control fails the cell's limits where the program passes
them."""
import dataclasses

import pytest

import serve_cases as sc
from bench.drivers import serve_batches
from repro.configs import get_arch

CELL = "zamba2-7b-18l-serve-failover"
DEPTH = {"n_layers": 3, "block_pattern": "hhh"}
TRAFFIC = {"batch": 2, "prompt_len": 32, "new_tokens": 5, "kill_at_token": 3,
           "prompt_pool": 1, "warmup_batches": 1, "check_requests": 2}


@pytest.fixture(autouse=True)
def shallow(monkeypatch):
    """The cell's server three layers deep."""
    cfg = dataclasses.replace(get_arch("zamba2-7b-18l"), **DEPTH)
    monkeypatch.setattr(sc.program_serve, "get_arch", lambda name: cfg)


def run(seed=sc.SEEDS[0], control=False):
    cell = sc.harness.load_cell(CELL)
    cell.config = {**cell.config, "model": {**cell.model, **DEPTH}}
    cell.traffic = {**cell.traffic, **TRAFFIC}
    return serve_batches.serve(cell, seed, 0.0, False, n_batches=1,
                               control=control)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] > 0 and e2e["itl_p95_ms"] > 0


def test_state_unchanged_is_not_correct(monkeypatch):
    sc.plant(monkeypatch, "state_unchanged")
    out = run()
    assert out["correct"] is False, out["checks"]


def test_control_fails_where_the_program_passes():
    """Each number the cell compares is under its limit for the program's
    served tokens and over it for the fp8 control's."""
    out = run(sc.SEEDS[1], control=True)
    assert out["correct"] is False, out["checks"]
    got, ctl = out["readings"], out["control_readings"]
    for number, lim in sc.harness.load_cell(CELL).limits.items():
        assert got[number] <= lim["limit"] < ctl[number], (number, got, ctl)
