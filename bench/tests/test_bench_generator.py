"""The generator gives each seed the same work, and a seed the same inputs."""
import numpy as np
import pytest

from bench import cells, generator

MIXES = ["chat", "batch"]


def _traffic(mix):
    w = [w for w in cells.load_benchmark()["workloads"]
         if w["traffic"] == mix][0]
    return cells.resolve(w["name"]).traffic


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_inputs(mix):
    a = generator.make(_traffic(mix), 2 ** 33 + 5, 40.0, 1000)
    b = generator.make(_traffic(mix), 2 ** 33 + 5, 40.0, 1000)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.arrival_s, b.arrival_s)
    for i in (0, 7, len(a) - 1):
        assert np.array_equal(a.prompt(i), b.prompt(i))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_not_in_work(mix):
    t = _traffic(mix)
    a = generator.make(t, 1, 40.0, 1000)
    b = generator.make(t, 2, 40.0, 1000)
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert a.arrival_s[-1] == pytest.approx(b.arrival_s[-1])
    assert not np.array_equal(a.prompt(0), b.prompt(0))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_their_clip(mix):
    t = _traffic(mix)
    w = generator.make(t, 3, 40.0, 1000)
    for key, got in (("prompt_len", w.prompt_lens),
                     ("output_len", w.output_lens)):
        assert got.min() >= t[key]["min"] and got.max() <= t[key]["max"]
        assert abs(np.median(got) - t[key]["median"]) < 0.2 * t[key]["median"]
    eng = t["engine"]
    assert w.prompt_lens.max() < eng["max_len"]
    assert (w.prompt_lens + w.output_lens).max() <= eng["max_len"]


@pytest.mark.parametrize("mix", MIXES)
def test_each_block_of_requests_holds_the_same_lengths(mix):
    t = _traffic(mix)
    a = generator.make(t, 11, 40.0, 1000)
    b = generator.make(t, 12, 40.0, 1000)
    k = generator.block(t)
    for s in range(0, len(a) - k, k):
        assert sorted(a.prompt_lens[s:s + k]) == sorted(b.prompt_lens[s:s + k])
        assert sorted(a.output_lens[s:s + k]) == sorted(b.output_lens[s:s + k])
