"""The trace reduction: exact on a hand-made trace, and sound on a small
trace recorded on a TPU v5e (one whisper-tiny serving batch of two
sequences, six new tokens, a failover at token three, with the serving
harness's host spans)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "whisper_serve_v5e.xplane.pb.gz"
MS = 1_000_000


def _synthetic():
    host = ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * MS),
        ("bench.batch", 6 * MS, 90 * MS),
        ("bench.decode", 40 * MS, 10 * MS),
        ("other", 0, 100 * MS)])])
    device = ("/device:TPU:0", [
        ("XLA Modules", [("jit_decode_step(7)", 10 * MS, 20 * MS),
                         ("jit_decode_step(7)", 60 * MS, 30 * MS),
                         ("jit_prefill_step(3)", 95 * MS, 10 * MS)]),
        ("XLA Ops", [("fusion.1", 10 * MS, 15 * MS),
                     ("fusion.2", 20 * MS, 5 * MS),    # nests in fusion.1
                     ("fusion.1", 60 * MS, 30 * MS),
                     ("copy", 95 * MS, 10 * MS)])])    # straddles the end
    return [host, device]


def test_busy_idle_programs_and_gaps_on_a_known_trace():
    r = trace_reduce.reduce_planes(_synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [10, 25) + [60, 90) + [95, 100) = 50 ms
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["programs"]["jit_decode_step"] == {"count": 2,
                                                "seconds": pytest.approx(0.05)}
    assert r["programs"]["jit_prefill_step"]["seconds"] == \
        pytest.approx(0.005)
    ops = dict(r["breakdown"]["device_ops"])
    # fusion.2 nests in fusion.1 at 20 ms: fusion.1 keeps 10 of its first
    # 15 ms, then 30 ms in the second decode call
    assert ops["jit_decode_step/fusion.1"] == pytest.approx(0.040)
    assert ops["jit_decode_step/fusion.2"] == pytest.approx(0.005)
    # half of the copy lies inside the window
    assert ops["jit_prefill_step/copy"] == pytest.approx(0.005)
    # gaps [0,10) host, [25,60) mid 42.5 in decode, [90,95) mid 92.5 in
    # batch
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"host": pytest.approx(0.010),
                    "decode": pytest.approx(0.035),
                    "batch": pytest.approx(0.005)}


def test_a_trace_without_a_window_or_a_device_is_refused():
    host, device = _synthetic()
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([device])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([host])


def test_recorded_v5e_trace():
    r = trace_reduce.reduce_planes(trace_reduce.read_xplane(RECORDED))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    decode = [p for name, p in r["programs"].items() if "decode_step" in name]
    assert decode and decode[0]["count"] > 0
    assert 0 < decode[0]["seconds"] < r["busy_s"]
    idle = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
