"""The reduction of the program's own spans: the idle time charged piece
by piece to the innermost span, on a hand-made trace with ``repro.*``
spans nested in the harness's ``bench.*`` ones; the layers' readings; and
agreement with ``bench/trace_reduce.py`` on the window's idle time."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).parent))

from bench import program_spans, trace_reduce  # noqa: E402
from test_bench_trace_reduce import MS, RECORDED, _synthetic  # noqa: E402

LAYER_READINGS = ["idle_share.entry_points", "idle_share.ft_driver",
                  "idle_share.workload", "promote_ms"]


def _with_program_spans():
    """The hand-made trace with the program's own spans inside
    ``bench.batch``; the generate span carries its metadata in the name,
    as an older profiler writes it, and the start span begins before the
    window."""
    host, device = _synthetic()
    plane, lines = host
    program = ("python", [
        ("repro.ft.start", -5 * MS, 8 * MS),
        ("repro.serve.generate#batch=1#", 5 * MS, 90 * MS),
        ("repro.ft.step", 20 * MS, 40 * MS),
        ("repro.workload.decode", 30 * MS, 10 * MS),
        ("repro.ft.recover.promote", 70 * MS, 10 * MS)])
    return [(plane, lines + [program]), device]


def test_program_spans_charge_idle_time_piece_by_piece():
    r = program_spans.reduce_planes(_with_program_spans())
    prog = r["program"]
    # idle [0, 10), [25, 60), [90, 95) (ms), split at the edges of the
    # innermost span pieces:
    # [0, 3) start, [3, 5) outside, [5, 20) generate, [20, 30) step,
    # [30, 40) decode, [40, 60) step, [60, 70) generate, [70, 80)
    # promote, [80, 95) generate, [95, 100) outside
    want = {
        "repro.ft.start": (1, 3, 3, 3),
        "(outside)": (0, 0, 7, 2),
        "repro.serve.generate": (1, 90, 40, 5 + 5),
        "repro.ft.step": (1, 40, 30, 5 + 20),
        "repro.workload.decode": (1, 10, 10, 10),
        "repro.ft.recover.promote": (1, 10, 10, 0),
    }
    assert set(prog) == set(want)
    for name, (count, seconds, self_ms, idle_ms) in want.items():
        assert prog[name]["count"] == count, name
        assert prog[name]["seconds"] == pytest.approx(seconds / 1e3), name
        assert prog[name]["self_s"] == pytest.approx(self_ms / 1e3), name
        assert prog[name]["idle_s"] == pytest.approx(idle_ms / 1e3), name
    # the pieces hold the whole window, the idle parts the whole idle time
    assert sum(p["self_s"] for p in prog.values()) == \
        pytest.approx(r["window_s"])
    assert sum(p["idle_s"] for p in prog.values()) == \
        pytest.approx(r["idle_s"])


def test_program_spans_leave_the_harness_reduction_as_it_was():
    """``repro.*`` spans change none of the ``bench.*`` labels."""
    assert trace_reduce.reduce_planes(_with_program_spans()) == \
        trace_reduce.reduce_planes(_synthetic())


@pytest.mark.parametrize("planes", [_synthetic, _with_program_spans,
                                    lambda: trace_reduce.read_xplane(
                                        RECORDED)],
                         ids=["synthetic", "program_spans", "recorded_v5e"])
def test_window_and_idle_time_agree_with_the_harness(planes):
    planes = planes()
    mine = program_spans.reduce_planes(planes)
    theirs = trace_reduce.reduce_planes(planes)
    assert mine["window_s"] == pytest.approx(theirs["window_s"])
    assert mine["idle_s"] == pytest.approx(
        theirs["window_s"] - theirs["busy_s"], rel=1e-6)


@pytest.mark.parametrize("name", LAYER_READINGS)
def test_readings_are_none_without_program_spans(name):
    # the recorded trace predates the program's spans
    for planes in (_synthetic(), trace_reduce.read_xplane(RECORDED)):
        r = program_spans.reduce_planes(planes)
        assert r["program"] == {}
        assert program_spans.readings(r)[name] is None


@pytest.mark.parametrize("name,want", [
    ("idle_share.entry_points", 10.0),
    ("idle_share.ft_driver", 3 + 25 + 0),
    ("idle_share.workload", 10.0),
    ("promote_ms", 10.0),
])
def test_readings_on_program_spans(name, want):
    r = program_spans.reduce_planes(_with_program_spans())
    assert program_spans.readings(r)[name] == pytest.approx(want)


def test_layer_shares_and_outside_add_up_to_the_idle_share():
    got = program_spans.readings(
        program_spans.reduce_planes(_with_program_spans()))
    parts = sum(got[f"idle_share.{layer}"]
                for layer in ("entry_points", "ft_driver", "workload",
                              "outside"))
    assert parts == pytest.approx(got["idle_share.all"])
    assert got["idle_share.all"] == pytest.approx(50.0)


def test_a_trace_without_a_window_or_a_device_is_refused():
    host, device = _with_program_spans()
    with pytest.raises(ValueError):
        program_spans.reduce_planes([device])
    with pytest.raises(ValueError):
        program_spans.reduce_planes([host])


def test_the_run_goes_through_the_harness_and_restores_it(monkeypatch):
    """``main`` runs ``bench/run.py``'s ``main`` with ``--trace 1``; the
    harness's reduction is put back after the run."""
    from bench import run
    seen = []

    def fake_main(argv):
        seen.append(argv)
        return 2                     # no chip: no result

    monkeypatch.setattr(run, "main", fake_main)
    before = trace_reduce.reduce_trace
    assert program_spans.main(["--workload", "w", "--seed", "1"]) == 2
    assert seen == [["--workload", "w", "--seed", "1", "--trace", "1"]]
    assert trace_reduce.reduce_trace is before
