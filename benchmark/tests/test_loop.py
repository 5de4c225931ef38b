"""The closed loop on a clock of its own: whole passes over the mix, the
pass in flight at the bell finishing, a failed query counted."""

import pytest

from harness import loop, stats


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def driver(clock, cost, fail=()):
    def run_one(query, k):
        clock.t += cost[query]
        if (query, k) in fail:
            raise RuntimeError("boom")
        return [(query, k)]
    return run_one


def test_window_closes_at_the_end_of_the_pass_in_flight():
    clock = Clock()
    answers, elapsed, pos = loop.closed(
        driver(clock, {"a": 1.0, "b": 3.0}), ["a", "b"], pool=2,
        seconds=9.5, clock=clock)
    # the bell falls inside the third pass: it finishes, 12 s and 6 queries
    assert (len(answers), elapsed, pos) == (6, 12.0, 6)
    assert [(a.query, a.set_index) for a in answers] == [
        ("a", 0), ("b", 0), ("a", 1), ("b", 1), ("a", 0), ("b", 0)]
    assert [a.seconds for a in answers] == [1.0, 3.0] * 3


def test_cycles_and_where_the_stream_goes_on():
    clock = Clock()
    run_one = driver(clock, {"a": 1.0, "b": 1.0})
    first, _, pos = loop.closed(run_one, ["a", "b"], 4, cycles=2, clock=clock)
    assert len(first) == 4 and pos == 4
    more, _, pos = loop.closed(run_one, ["a", "b"], 4, seconds=1.5,
                               start_at=pos, clock=clock)
    assert [(a.query, a.set_index) for a in more] == [("a", 2), ("b", 2)]
    with pytest.raises(ValueError):
        loop.closed(run_one, ["a"], 1, clock=clock)


def test_a_query_that_raises_is_counted_and_the_loop_goes_on():
    clock = Clock()
    answers, _, _ = loop.closed(
        driver(clock, {"a": 1.0}, fail={("a", 1)}), ["a"], 2, cycles=4,
        clock=clock)
    assert [a.error is None for a in answers] == [True, False, True, False]
    assert answers[1].rows is None and "boom" in answers[1].error


def test_statistics():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile(xs, 0) == 1.0 and stats.percentile(xs, 100) == 5.0
    assert stats.percentile([7.0], 95) == 7.0
    # quartiles as statistics.quantiles gives them: 1.5 and 4.5
    assert stats.spread(xs) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
