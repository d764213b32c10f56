"""Per-layer readers on hand-made run contexts with a known answer, and
nothing read where the context holds nothing.

Every per-layer metric of BENCHMARK.json brings its known answer in a file
of its own, bench/tests/answers/<metric>.json: a run context (`context`)
and the value its reader has to give on it (`value`). A listed metric
without one fails here; a new metric adds its file and edits no test."""
import json
from pathlib import Path

import pytest

from bench import cells

ANSWERS = Path(__file__).parent / "answers"
LISTED = cells.load_benchmark()["per_layer"]
ALL = [m["name"] for m in LISTED]
TRACED = [m["name"] for m in LISTED if m["source"] == "device_trace"]


def missing_answers(names, where=ANSWERS):
    """The metrics among `names` that have no answer file in `where`."""
    return [n for n in names if not (where / f"{n}.json").is_file()]


def answer(name):
    return json.loads((ANSWERS / f"{name}.json").read_text())


def test_every_metric_has_a_known_answer_here():
    assert missing_answers(ALL) == []


def test_a_listed_metric_without_an_answer_fails_the_check(tmp_path):
    (tmp_path / "a_ms.chat.json").write_text("{}")
    assert missing_answers(["a_ms.chat", "b_ms.chat"], tmp_path) == \
        ["b_ms.chat"]
    assert missing_answers(ALL + ["made_up_ms.chat"]) == ["made_up_ms.chat"]


@pytest.mark.parametrize("name", ALL)
def test_reader_value(name):
    want = answer(name)
    assert cells.metric_reader(name)(want["context"]) == \
        pytest.approx(want["value"])


@pytest.mark.parametrize("name", TRACED)
def test_reader_without_trace_reads_nothing(name):
    ctx = dict(answer(name)["context"], trace=None)
    assert cells.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", ALL)
def test_reader_on_an_empty_context_reads_nothing(name):
    assert cells.metric_reader(name)({}) is None


def test_roofline_compares_means_per_block():
    # 9 host blocks against 10 device programs: mean against mean
    ctx = answer("decode_roofline")["context"]
    read = cells.metric_reader("decode_roofline")
    assert read(dict(ctx, traced_blocks=9, traced_least_s=0.45)) == \
        pytest.approx(25.0)
    assert read(dict(ctx, traced_blocks=0)) is None
