"""Every cell resolves to its files by name; the command refuses a CPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import cells
from bench.tests import tiny

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = cells.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(name):
    c = cells.resolve(name)
    assert c.end_to_end and c.per_layer
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert cells.driver(c.traffic["driver"]).run
    assert cells.reference(c.config["family"]).served_gaps
    assert cells.counts(c.config["family"]).decode_substep
    assert tiny.missing_sizes([c.config["name"]]) == []
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in [e["name"] for e in c.end_to_end]
    assert c.limits["widest_gap"]["limit"] > 0


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + \
        BENCH["per_layer"]
    assert all(NAME.match(x["name"]) for x in named)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sun100m.chat",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and not p.stdout.strip()


def test_cache_is_the_programs_helper_on_the_bench_directory(tmp_path,
                                                              monkeypatch):
    import jax

    from bench import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        run.enable_cache(tmp_path / "cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(
            tmp_path / "cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert (tmp_path / "cache").is_dir()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
