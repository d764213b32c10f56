"""Trace reduction on hand-made intervals with a known answer."""
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Interval as I


def _trace():
    t = tr.Trace()
    t.devices["/device:TPU:0"] = {
        "ops": [I("fusion.1", 1.0, 2.0, "jit__prefill_impl"),
                I("fusion.2", 1.5, 3.0, "jit__prefill_impl"),
                I("dot.3", 5.0, 6.0, "jit__engine_step_impl"),
                I("dot.3", 6.5, 7.0, "jit__engine_step_impl"),
                I("early", 0.0, 0.6, "x")],            # clipped to the window
        "modules": [I("jit__prefill_impl(7)", 1.0, 3.0),
                    I("jit__engine_step_impl(9)", 5.0, 6.0),
                    I("jit__engine_step_impl(9)", 6.5, 7.0)],
    }
    t.host = [I("bench.traced", 0.5, 8.0),
              I("bench.step", 0.5, 7.5), I("bench.wait", 3.0, 5.0),
              I("engine.fill", 0.7, 3.1), I("bench.record", 7.5, 8.0)]
    return t


def test_busy_modules_ops_and_gaps():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(7.5)
    # union: [0.5,0.6] + [1,3] + [5,6] + [6.5,7] = 0.1 + 2 + 1 + 0.5
    assert red["busy_s"] == pytest.approx(3.6)
    assert tr.module_time(red, "_prefill_impl") == (1, pytest.approx(2.0))
    assert tr.module_time(red, "_engine_step_impl") == (2, pytest.approx(1.5))
    assert tr.module_time(red, "_nothing") is None
    ops = dict((k, v) for k, v in red["device_ops"])
    assert ops["jit__engine_step_impl:dot.3"] == pytest.approx(1.5)
    assert ops["x:early"] == pytest.approx(0.1)
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    # each gap goes to the innermost span open at its midpoint:
    # 0.6-1 (fill), 3-5 (wait), 6-6.5 (step), 7-8 (midpoint 7.5: record)
    assert gaps == {"engine.fill": pytest.approx(0.4),
                    "bench.wait": pytest.approx(2.0),
                    "bench.step": pytest.approx(0.5),
                    "bench.record": pytest.approx(1.0)}
    assert sum(gaps.values()) == pytest.approx(7.5 - 3.6)


def test_no_device_ops_reads_nothing():
    t = _trace()
    t.devices.clear()
    assert tr.reduce(t) is None


def test_union_merges_and_clips():
    got = tr.union([I("a", 0, 2), I("b", 1, 3), I("c", 5, 9)], 1.5, 6)
    assert got == [[1.5, 3], [5, 6]]
