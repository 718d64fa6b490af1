"""The `xlstm350m-serve-failover` cell without the chip, on the CPU at its published
widths with two batches of two sequences: a sound run is correct; the
run with its decode returning the state unchanged, serving half of the
batch and copying it into the other half, or altering the token it
produces is not; and the fp8 control fails the cell's limit where the
program passes it."""
import pytest

import serve_cases as sc

CELL = "xlstm350m-serve-failover"


def test_sound_run_is_correct():
    sc.sound_run_is_correct(CELL)


@pytest.mark.parametrize("fault", sorted(sc.FAULTS))
def test_broken_decode_is_not_correct(fault, monkeypatch):
    sc.plant(monkeypatch, fault)
    out = sc.run_small(CELL)
    assert not out["correct"], out["checks"]


def test_control_fails_where_the_program_passes():
    sc.control_fails_where_the_program_passes(CELL)
