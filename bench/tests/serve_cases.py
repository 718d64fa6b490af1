"""Shared by the serving cells' CPU tests: a cell's run without the chip,
at its configuration's published widths but with small traffic (two
batches of two sequences), checked against the cell's own limits, and the
faults the timed path can have, planted in the program's decode step."""
import sys
from pathlib import Path

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.drivers import serve_batches  # noqa: E402
from repro.launch import serve as program_serve  # noqa: E402

SMALL = {"whisper-tiny-serve-failover": dict(prompt_len=4, new_tokens=24),
         "xlstm350m-serve-failover": dict(prompt_len=128, new_tokens=16)}
SEEDS = [2**31 + 99, 12345]


def run_small(name, seed=SEEDS[0], control=False):
    """Two batches of the cell, every request checked."""
    cell = harness.load_cell(name)
    cell.traffic = {**cell.traffic, **SMALL[name], "batch": 2,
                    "kill_at_token": 3, "prompt_pool": 2,
                    "warmup_batches": 1, "check_requests": 4}
    return serve_batches.serve(cell, seed, 0.0, False, n_batches=2,
                               control=control)


def _state_unchanged(decode):
    def f(params, cache, tokens, pos):
        logits, _ = decode(params, cache, tokens, pos)
        return logits, cache
    return f


def _half_batch(decode):
    def f(params, cache, tokens, pos):
        logits, cache = decode(params, cache, tokens, pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache
    return f


def _token_altered(decode):
    def f(params, cache, tokens, pos):
        logits, cache = decode(params, cache, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), cache
    return f


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


def plant(monkeypatch, fault: str) -> None:
    """Every server built after this decodes through the fault."""
    make = program_serve.make_decode_step

    def broken(run):
        decode, model = make(run)
        return FAULTS[fault](decode), model

    monkeypatch.setattr(program_serve, "make_decode_step", broken)


def sound_run_is_correct(name):
    out = run_small(name)
    assert out["correct"], out["checks"]
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] > 0 and e2e["itl_p95_ms"] > 0


def control_fails_where_the_program_passes(name):
    """With the fp8 control in the served tokens' place the run is not
    correct: each number the cell compares is under its limit for the
    program's served tokens and over it for the control's."""
    limits = harness.load_cell(name).limits
    for seed in SEEDS:
        out = run_small(name, seed, control=True)
        assert out["correct"] is False, (seed, out["checks"])
        got, ctl = out["readings"], out["control_readings"]
        for number, lim in limits.items():
            assert got[number] <= lim["limit"], (seed, number, got)
            assert ctl[number] > lim["limit"], (seed, number, ctl)
            assert out["checks"][number]["value"] == ctl[number]
