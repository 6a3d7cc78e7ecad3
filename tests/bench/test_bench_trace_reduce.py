"""``bench/trace_reduce.py`` on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_trace.py``: three rounds of
a 90 us matmul in ``bench.step``, 20 ms of ``bench.wait`` and a short
``bench.host``), and on hand-made spans."""
from pathlib import Path

import pytest

import tiny_cell  # noqa: F401  (puts bench/ on the path)
import trace_reduce as TR

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"


def test_recorded_chip_trace():
    host, dev = TR.load(str(TRACE))
    assert [n for n, _, _ in host] == ["bench.step", "bench.wait",
                                       "bench.host"] * 3
    assert list(dev) == ["/device:TPU:0"]
    r = TR.reduce(host, dev)
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0.06 < r["window_s"] < 0.08
    # at most the three 90 us matmuls (the first one lands before the
    # first host span on the trace's clock and is clipped off)
    assert 0 < r["busy_s"] <= 3 * 90.3e-6
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["device_ops"][0][0] == "%fusion = bf16[]"
    label, idle = r["idle_gaps"][0]
    assert label == "bench.wait" and idle > 0.05
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_hand_made_spans():
    host = [("bench.step", 0, 100), ("bench.host", 100, 120),
            ("bench.wait", 120, 200)]
    dev = {"/device:TPU:0": [("%a = f32[8]{0}", 10, 30),
                             ("%b = f32[8]{0}", 20, 50),
                             ("%a = f32[8]{0}", 150, 160),
                             ("%c = f32[8]{0}", 190, 260)]}
    phases = [("engine.dispatch", 0, 60), ("engine.sample", 60, 100)]
    r = TR.reduce(host, dev, phases=phases)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((40 + 10 + 10) * 1e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"%a = f32[8]": 30e-9, "%b = f32[8]": 30e-9, "%c = f32[8]": 10e-9})
    assert dict(r["idle_gaps"]) == pytest.approx({
        "engine.dispatch": 20e-9, "engine.sample": 40e-9,
        "bench.host": 20e-9, "bench.wait": 60e-9})
    assert TR.reduce([], dev) is None
    assert TR.reduce(host, {"/device:TPU:0": []}) is None


def test_step_offset():
    host = [("bench.step", 5_000_000_100, 0), ("bench.wait", 1, 2),
            ("bench.step", 6_000_000_300, 0)]
    assert TR.step_offset_ns(host, [2.0, 3.0]) in (3e9 + 100, 3e9 + 300)
    assert TR.step_offset_ns(host, []) is None
