"""Per-layer readers on a hand-made run context: known values, and nothing
read where the trace holds nothing."""
import pytest

from bench import cells

RED = {"window_s": 4.0, "busy_s": 3.0,
       "modules": {"jit__prefill_impl": [2, 0.5],
                   "jit__engine_step_impl": [10, 2.0]}}
CTX = {"trace": RED, "decode_block": 8, "window_s": 40.0,
       "peak": {"bf16_flops": 2e14}, "model_flops": 8e14,
       "occupancy": 0.5, "traced_blocks": 10, "traced_least_s": 0.5}
WANT = {"prefill_ms.chat": 250.0, "decode_substep_ms.chat": 25.0,
        "device_idle.chat": 25.0, "device_idle.batch": 25.0,
        "decode_roofline": 25.0, "serve_mfu.batch": 10.0,
        "occupancy.batch": 50.0}
ALL = [m["name"] for m in cells.load_benchmark()["per_layer"]]


def test_every_metric_has_a_known_answer_here():
    assert sorted(WANT) == sorted(ALL)


@pytest.mark.parametrize("name", ALL)
def test_reader_value(name):
    assert cells.metric_reader(name)(CTX) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [n for n in ALL if n not in
                                  ("serve_mfu.batch", "occupancy.batch")])
def test_reader_without_trace_reads_nothing(name):
    assert cells.metric_reader(name)(dict(CTX, trace=None)) is None


def test_roofline_compares_means_per_block():
    # 9 host blocks against 10 device programs: mean against mean
    ctx = dict(CTX, traced_blocks=9, traced_least_s=0.45)
    assert cells.metric_reader("decode_roofline")(ctx) == pytest.approx(25.0)
    assert cells.metric_reader("decode_roofline")(
        dict(CTX, traced_blocks=0)) is None
