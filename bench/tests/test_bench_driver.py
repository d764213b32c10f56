"""The serving driver (bench/drivers/serve_engine.py): the program's own
records reduced over the window, counts taken by the configuration's
family, and one tiny traced run on the CPU whose context carries them."""
import sys
import time
from types import ModuleType
from types import SimpleNamespace as NS

import pytest

from bench import cells
from bench.drivers import serve_engine
from bench.tests import tiny


def _req(admitted):
    return NS(admitted_at=admitted)


def test_queue_waits_count_admissions_in_the_window_from_the_schedule():
    reqs = [_req(1.5), _req(2.5), _req(None), _req(9.0)]
    got = serve_engine.queue_waits(reqs, [1.0, 2.0, 3.0, 4.0], 2.0, 8.0)
    assert got == [pytest.approx(0.5)]


def test_counts_in_window():
    a = {"prefill_tokens": 10, "prefill_slot_tokens": 100, "tokens": 7}
    b = {"prefill_tokens": 25, "prefill_slot_tokens": 400, "tokens": 7}
    assert serve_engine.counts_in_window((a, 4), (b, 4)) == {
        "prefill_tokens": 15, "prefill_slot_tokens": 300, "tokens": 0}
    assert serve_engine.counts_in_window(None, (b, 4)) is None


def test_compiles_in_window():
    assert serve_engine.compiles_in_window(({}, 3), ({}, 5)) == 2
    assert serve_engine.compiles_in_window(({}, -1), ({}, -1)) is None
    assert serve_engine.compiles_in_window(None, ({}, 5)) is None


def test_replay_takes_counts_from_the_configurations_family(monkeypatch):
    fam = ModuleType("bench.counts.made_up")
    fam.prefill_flops = lambda cfg, p: 100 * p
    fam.token_flops = lambda cfg, c: 1
    fam.decode_substep = lambda cfg, contexts: (len(contexts), 0)
    monkeypatch.setitem(sys.modules, "bench.counts.made_up", fam)
    run_ = {"reqs": [NS(prompt=[0] * 5)], "arrivals": [0.0],
            "steps": [serve_engine.Step(0.0, 1.0, 1, [(0, 1)]),
                      serve_engine.Step(1.0, 2.0, 1, [(0, 3)])]}
    peak = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    serve_engine.replay(run_, {"family": "made_up"}, peak)
    first, second = run_["steps"]
    # the prompt of 5 at the first token; tokens 2 and 3 after it, one row
    # in each of two sub-steps
    assert (first.model_flops, first.substeps) == (500, 0)
    assert (second.model_flops, second.substeps, second.least_s) == \
        (2, 2, 2.0)


def test_a_tiny_traced_run_keeps_the_programs_records():
    """The chat cell cut to CPU size: the run context carries the counters'
    growth over the window, the queue waits and the compiles in it, and the
    readers of the program's records find them."""
    c = tiny.cell("sun100m.chat")
    out = serve_engine.run(c, 2200001041, 2.0, True, time.perf_counter(),
                           tiny.peaks_for("TPU v5 lite"))
    ctx = out["ctx"]
    assert all(v["value"] <= v["limit"] for v in out["checks"].values())
    grown = ctx["counters"]
    assert grown["tokens"] > 0 and grown["prefill_calls"] > 0
    assert 0 < grown["prefill_tokens"] <= grown["prefill_slot_tokens"]
    assert ctx["queue_waits"] and min(ctx["queue_waits"]) >= 0
    assert ctx["compiles_in_window"] == 0
    for name in ("queue_wait_p90_ms.chat", "prefill_useful.chat"):
        assert cells.metric_reader(name)(ctx) is not None
