"""Tails, time per output token and window rates on hand-made timelines."""
import pytest

from bench import e2e
from bench.e2e import Timeline as T


def test_percentile_is_linear_between_order_statistics():
    assert e2e.percentile(range(101), 95) == pytest.approx(95.0)
    assert e2e.percentile([1.0, 2.0], 95) == pytest.approx(1.95)
    assert e2e.percentile([1.0, 2.0, 3.0], 90) == pytest.approx(2.8)


def test_ttft_counts_due_requests_and_censors_at_close():
    tls = [T(0.5, [(1.0, 1)]),           # due before the window: left out
           T(1.0, [(1.25, 1), (2.0, 8)]),
           T(2.0, [(2.5, 1)]),
           T(9.0, [])]                   # no token by the close (10)
    assert e2e.ttft(tls, 1.0, 10.0) == pytest.approx([0.25, 0.5, 1.0])


def test_tpot_uses_tokens_after_the_first_delivery_in_the_window():
    tls = [T(0.0, [(1.0, 1), (1.5, 8), (2.0, 8)]),   # 1 s / 16 tokens
           T(0.0, [(0.5, 1), (3.0, 8), (3.4, 4)]),   # first in window: 3.0
           T(0.0, [(3.0, 1)])]                       # one delivery: out
    assert e2e.tpot(tls, 1.0, 5.0) == pytest.approx([1 / 16, 0.4 / 4])


def test_block_size_alone_does_not_move_tpot():
    one = T(0.0, [(0.0, 1)] + [(0.01 * k, 1) for k in range(1, 17)])
    eight = T(0.0, [(0.0, 1), (0.08, 8), (0.16, 8)])
    assert e2e.tpot([one], 0, 1)[0] == pytest.approx(e2e.tpot([eight], 0, 1)[0])


def test_tokens_in_window():
    tls = [T(0.0, [(0.5, 1), (1.0, 8), (2.0, 8)]), T(0.0, [(2.5, 3)])]
    assert e2e.tokens_in(tls, 1.0, 2.0) == 16
