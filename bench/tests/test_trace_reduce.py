"""The trace reduction, on a short trace recorded on a TPU v5e
(``data/sat_small.xplane.pb.xz``: ``audio.serve.sat`` with a 0.25-s
window, xz-compressed) and on hand-made intervals."""
import lzma
import os

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "sat_small.xplane.pb.xz")


def test_union_gaps_and_attribution():
    busy = tr._union([(0, 10), (5, 20), (30, 40), (50, 60)], 0, 55)
    assert busy == [[0, 20], [30, 40], [50, 55]]
    gaps = tr._gaps(busy, 0, 55)
    assert gaps == [(20, 30), (40, 50)]
    spans = [("bench.tick_launch", 18, 45), ("bench.refine", 22, 26)]
    idle = tr._attribute(gaps, spans)
    assert idle["bench.refine"] == pytest.approx(4e-9)
    assert idle["bench.tick_launch"] == pytest.approx((6 + 5) * 1e-9)
    assert idle[tr.NO_SPAN] == pytest.approx(5e-9)


def test_program_names():
    assert tr.program_name("jit__unknown(123)") == "split_stage"
    assert tr.program_name("jit_wire_roundtrip(7)") == "jit_wire_roundtrip"


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData
    with open(TRACE, "rb") as f:
        red = tr.reduce(ProfileData.from_serialized_xspace(
            lzma.decompress(f.read())))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.25, rel=0.05)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(red["idle_by_span"].values())
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    for name in ("split_stage", "jit_wire_roundtrip", "jit__take"):
        assert red["programs"][name] > 0
    assert sum(red["programs"].values()) >= red["busy_s"]
    assert "bench.tick_launch" in red["idle_by_span"]
    bd = red["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
