"""bench/program_trace.py: the first-token split and the step times on
hand-made inputs, and one tiny traced run on the CPU."""
from types import SimpleNamespace as NS

import pytest

from bench import program_trace
from bench.tests import tiny


def _req(admitted, first):
    return NS(admitted_at=admitted, first_token_at=first)


def test_ttft_split_parts_add_up_to_the_wait():
    steps = [NS(t1=1.0, seen=[(0, 0)]), NS(t1=2.0, seen=[(0, 1)]),
             NS(t1=3.0, seen=[(0, 3), (1, 1)]), NS(t1=4.0, seen=[(2, 1)])]
    first = program_trace.first_deliveries(steps)
    assert first == {0: 2.0, 1: 3.0, 2: 4.0}
    reqs = [_req(1.25, 1.75), _req(2.0, 2.5), _req(3.5, 3.75)]
    got = program_trace.ttft_split(reqs, [1.0, 1.5, 3.0], first, 0.5, 2.9)
    # request 2 arrives after the window's close; 0: 1000 = 250 + 500 +
    # 250 ms, 1: 1500 = 500 + 500 + 500 ms
    assert got["tail_mean"] == pytest.approx(
        {"ttft": 1500, "queue": 500, "prefill": 500, "hold": 500})
    assert got["p90"]["ttft"] == pytest.approx(1450)
    assert program_trace.ttft_split(reqs, [9.0] * 3, first, 0, 5) is None


def test_step_ms_inside_and_outside_the_trace():
    steps = [NS(t0=0.0, t1=0.1, n_active=2), NS(t0=1.0, t1=1.3, n_active=2),
             NS(t0=1.4, t1=1.5, n_active=0), NS(t0=3.0, t1=3.2, n_active=1)]
    got = program_trace.step_ms(steps, 0.9, 2.0)
    assert got == {"in_trace": pytest.approx(300.0),
                   "outside": pytest.approx(150.0)}
    assert program_trace.step_ms(steps, None, None) is None


def test_a_tiny_traced_run_through_the_driver():
    """The chat cell cut to CPU size, driven as bench/run.py drives it:
    correct, nothing compiled in the window, the wait split adds up."""
    got = program_trace.one_run(tiny.cell("sun100m.chat"), 2200001042, 2.0,
                                tiny.peaks_for("TPU v5 lite"))
    assert got["correct"] and got["compiles_in_window"] == 0
    tail = got["ttft_split"]["tail_mean"]
    assert tail["queue"] + tail["prefill"] + tail["hold"] == \
        pytest.approx(tail["ttft"])
