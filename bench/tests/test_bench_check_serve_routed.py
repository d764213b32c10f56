"""`correct` on a tiny cell of the routed serving driver
(bench/drivers/serve_routed.py): sound runs pass; the fp8 control and each
fault the serving cells can have come out as not correct.

The cells are every workload of BENCHMARK.json that this driver runs, each
cut to its configuration's bench/tests/tiny_sizes/<config>.json, which
holds both of its limits there and how many requests to compare."""
import json

import jax
import numpy as np
import pytest

from bench import cells
from bench.drivers import serve_engine, serve_routed
from bench.tests import tiny
from bench.tests.test_bench_check import (_half_batch, _state_unchanged,
                                          _token_altered)

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
         if cells.resolve(w["name"]).traffic["driver"] == "serve_routed"]


def _cell(name):
    """tiny.cell, with the share's limit and a sample of requests large
    enough for a share (bench/tests/tiny_sizes/<config>.json)."""
    c = tiny.cell(name)
    sizes = json.loads((tiny.SIZES / f"{c.config['name']}.json").read_text())
    c.limits["off_best_share"]["limit"] = sizes["off_best_share"]
    c.traffic["check"]["sample"] = sizes["check_sample"]
    return c


def test_the_routed_driver_has_a_cell():
    assert CELLS


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name):
    res = tiny.run(_cell(name), seconds=4.0, control=True)
    info, checks = res["check_info"], res["checks"]
    # the program's own tokens hold every limit ...
    assert info["program_off_best_share"] <= \
        checks["off_best_share"]["limit"], info
    assert info["program_widest_gap"] <= checks["widest_gap"]["limit"], info
    assert checks["token_count"]["value"] == 0, checks
    assert info["tokens_compared"] >= 150, info
    # ... and the control, in the program's place, makes the run not
    # correct by the share of its tokens off the reference's best
    assert checks["off_best_share"]["value"] == \
        info["control_off_best_share"]
    assert checks["off_best_share"]["value"] > \
        checks["off_best_share"]["limit"], checks
    assert not res["correct"], res
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()
    res = tiny.run(_cell(name))
    assert not res["correct"], res


@pytest.mark.parametrize("name", CELLS)
def test_share_counts_the_gaps_above_the_margin(name, monkeypatch):
    """Over made-up gaps: the share counts those above `above` and not
    those at or below it; the widest gap and the sample are serve_engine's."""
    c = _cell(name)
    above = c.limits["off_best_share"]["above"]
    gaps = np.array([0.0, above, above * 1.5, 3.0], np.float32)

    class Ref:
        @staticmethod
        def make_weights(cfg, seed):
            return None

        @staticmethod
        def served_gaps(w, cfg, p, g, max_len, control):
            n = len(g)
            out = {"gap": gaps[:n]}
            if control:
                out["control_gap"] = np.full(n, above * 2, np.float32)
            return out

    monkeypatch.setattr(cells, "reference", lambda family: Ref)
    fin = [([1, 2], [3, 4, 5, 6], 4) for _ in range(3)]
    checks, info = serve_routed.check(c, 7, fin, 128)
    assert checks["off_best_share"]["value"] == pytest.approx(0.5)
    assert checks["widest_gap"]["value"] == pytest.approx(3.0)
    assert info["requests_compared"] == 3
    ctl, cinfo = serve_routed.check(c, 7, fin, 128, control=True)
    assert ctl["off_best_share"]["value"] == 1.0
    assert cinfo["program_off_best_share"] == pytest.approx(0.5)
    old, _ = serve_engine.check(c, 7, fin, 128)
    assert old["widest_gap"] == checks["widest_gap"]
