"""The schedule is a function of the traffic file and the seed alone, and
every seed offers the same work."""

import numpy as np
import pytest

from chipbench import traffic
from chipbench.tests import tiny

SEEDS = (3, 2**31 + 12345)


@pytest.mark.parametrize("mix", [tiny.CHAT, tiny.FULL])
def test_same_seed_same_schedule(mix):
    a, b = (traffic.schedule(mix, 256, SEEDS[1], 4.0) for _ in range(2))
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert all(np.array_equal(x, y) for x, y in zip(a.tokens, b.tokens))


@pytest.mark.parametrize("mix", [tiny.CHAT, tiny.FULL])
def test_seeds_share_the_work_in_another_order(mix):
    a, b = (traffic.schedule(mix, 256, s, 4.0) for s in SEEDS)
    n = a.n_window
    assert n == b.n_window
    assert not np.array_equal(a.prompt_lens[:n], b.prompt_lens[:n])
    # whole blocks hold each length once, so every seed's window holds the
    # same lengths
    whole = n - n % traffic.LENGTH_VALUES
    for x, y in ((a.prompt_lens, b.prompt_lens),
                 (a.output_lens, b.output_lens)):
        assert sorted(x[:whole]) == sorted(y[:whole])
    assert np.allclose(sorted(np.diff(a.due[:n], prepend=0)),
                       sorted(np.diff(b.due[:n], prepend=0)))


def test_open_loop_window():
    s = traffic.schedule(tiny.CHAT, 256, SEEDS[0], 4.0)
    rate = tiny.CHAT["arrivals"]["rate"]
    assert s.n_window == round(rate * 4.0)
    assert 0 < s.due[0] and s.due[s.n_window - 1] < 4.0 <= s.due[s.n_window]
    assert np.all(np.diff(s.due) > 0)
    assert len(s) - s.n_window >= rate * traffic.DRAIN_SECONDS


def test_prompt_lengths_take_fixed_values():
    s = traffic.schedule(tiny.CHAT, 256, SEEDS[0], 4.0)
    values = traffic.prompt_values(tiny.CHAT)
    assert len(values) <= traffic.LENGTH_VALUES
    assert set(s.prompt_lens) <= set(values)
    assert all(len(t) == p for t, p in zip(s.tokens, s.prompt_lens))
    assert all(t.max() < 256 for t in s.tokens)
