"""`correct` on a tiny cell: sound runs pass; the fp8 control and each fault
the serving cells can have come out as not correct.

The cells are every workload of BENCHMARK.json that the serving driver
runs, each cut to its configuration's bench/tests/tiny_sizes/<config>.json:
a new serving cell gets these tests by its entry and that file alone. A
cell under another driver brings its control and faults in a test file of
its own, bench/tests/test_bench_check_<driver>.py."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells
from bench.drivers import serve_engine
from bench.tests import tiny
from repro.serving import ServingEngine

WORKLOADS = cells.load_benchmark()["workloads"]
DRIVERS = {w["name"]: cells.resolve(w["name"]).traffic["driver"]
           for w in WORKLOADS}
CELLS = [name for name, d in DRIVERS.items() if d == "serve_engine"]


def test_every_cell_has_tiny_sizes():
    assert tiny.missing_sizes({w["config"] for w in WORKLOADS}) == []


def test_a_config_without_tiny_sizes_fails_the_check(tmp_path):
    (tmp_path / "a.json").write_text("{}")
    assert tiny.missing_sizes(["a", "b"], tmp_path) == ["b"]


def test_every_cell_has_a_check_test():
    here = Path(__file__).parent
    assert [name for name, d in DRIVERS.items() if d != "serve_engine" and
            not (here / f"test_bench_check_{d}.py").is_file()] == []


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_control_is_not(name):
    # a 4 s window: on a loaded CPU 2 s can finish too few requests to
    # compare the tokens asserted below
    res = tiny.run(tiny.cell(name), seconds=4.0, control=True)
    info, checks = res["check_info"], res["checks"]
    # the program's own tokens hold every limit ...
    assert info["program_widest_gap"] <= checks["widest_gap"]["limit"], info
    assert checks["token_count"]["value"] == 0, checks
    assert info["tokens_compared"] >= 50, info
    # ... and the control, in the program's place, makes the run not correct
    assert checks["widest_gap"]["value"] == info["control_widest_gap"]
    assert not res["correct"], res
    assert list(res)[-1] == "checks"


def _token_altered(monkeypatch):
    orig = ServingEngine._sample

    def bad(self, logits, keys, temps):
        return (orig(self, logits, keys, temps) + 1) % logits.shape[-1]
    monkeypatch.setattr(ServingEngine, "_sample", bad)


def _state_unchanged(monkeypatch):
    orig = ServingEngine._engine_step_impl

    def bad(self, params, cache, state):
        _, _, toks, emit, done = orig(self, params, cache, state)
        return cache, state, toks, emit, done       # the step moves nothing
    monkeypatch.setattr(ServingEngine, "_engine_step_impl", bad)


def _half_batch(monkeypatch):
    orig = ServingEngine._engine_step_impl

    def bad(self, params, cache, state):
        cache2, state2, toks, emit, done = orig(self, params, cache, state)
        keep = (jnp.arange(emit.shape[0]) % 2 == 0)[:, None]
        return cache2, state2, toks, emit & keep, done
    monkeypatch.setattr(ServingEngine, "_engine_step_impl", bad)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()
    res = tiny.run(tiny.cell(name))
    assert not res["correct"], res


def test_control_check_judges_the_control_in_the_programs_place():
    c = tiny.cell("sun100m.chat")
    rng = np.random.default_rng(0)
    fin = [(rng.integers(0, 500, 10).tolist(),
            rng.integers(0, 500, 6).tolist(), 6) for _ in range(4)]
    plain, pinfo = serve_engine.check(c, 7, fin, 128)
    ctl, cinfo = serve_engine.check(c, 7, fin, 128, control=True)
    assert plain["widest_gap"]["value"] == pinfo["program_widest_gap"]
    assert cinfo["program_widest_gap"] == pinfo["program_widest_gap"]
    assert ctl["widest_gap"]["value"] == cinfo["control_widest_gap"]
    assert "control_widest_gap" not in pinfo
    assert plain["token_count"] == ctl["token_count"] == {"value": 0,
                                                          "limit": 0}
