"""The measured window on a job that only counts, with a clock by hand: how
many units are in flight, which completions make the rate, and what a unit
that raises leaves behind."""

import pytest

from perfbench import window


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class CountingJob:
    """Issuing takes 1 s of host time and a unit is ready 10 s after the one
    before it (or after its own issue, if the device was idle)."""

    def __init__(self, clock, fail_at=None):
        self.clock, self.fail_at = clock, fail_at
        self.log, self.device_free_at, self.issued = [], 0.0, 0

    def make_batch(self):
        return self.issued

    def issue(self, batch):
        if batch == self.fail_at:
            raise RuntimeError("out of memory")
        self.issued += 1
        self.clock.now += 1.0
        self.device_free_at = max(self.device_free_at, self.clock.now) + 10.0
        self.log.append(("issue", batch))
        return (batch, self.device_free_at)

    def wait(self, handle):
        batch, ready_at = handle
        self.clock.now = max(self.clock.now, ready_at)
        self.log.append(("wait", batch))


def test_two_in_flight_and_the_rate_between_first_and_last_completion():
    clock = Clock()
    job = CountingJob(clock)
    res = window.run_window(job, in_flight=2, seconds=45, clock=clock)
    # the second unit is issued before the first is waited for, and so on
    assert job.log[:5] == [("issue", 0), ("issue", 1), ("wait", 0), ("issue", 2), ("wait", 1)]
    # completions at 11, 21, 31, ...: the window stops issuing once 45 s are up, then drains
    assert res.done_at == [11.0, 21.0, 31.0, 41.0, 51.0, 61.0]
    assert res.attempted == 6 and res.error is None and res.failed == 0
    assert res.units_per_s() == res.median_units_per_s() == pytest.approx(5 / 50.0)  # not 6 / 61
    assert res.stall_share() == pytest.approx(0.0)
    assert res.dispatch_s == [1.0] * 6


def test_a_closed_loop_waits_for_each_reply():
    clock = Clock()
    job = CountingJob(clock)
    res = window.run_window(job, in_flight=1, units=3, clock=clock)
    assert job.log == [("issue", 0), ("wait", 0), ("issue", 1), ("wait", 1), ("issue", 2), ("wait", 2)]
    assert res.done_at == [11.0, 22.0, 33.0] and res.units_per_s() == pytest.approx(1 / 11.0)


def test_a_stall_counts_in_the_rate_and_shows_beside_it():
    res = window.WindowResult(done_at=[0.0, 1.0, 2.0, 3.0, 8.0, 9.0])  # one interval of 5 s among 1 s ones
    assert res.units_per_s() == pytest.approx(5 / 9.0)  # nothing is pruned
    assert res.median_units_per_s() == pytest.approx(1.0)
    assert res.stall_share() == pytest.approx(4 / 9.0)


def test_a_unit_that_raises_ends_the_window_and_is_counted():
    clock = Clock()
    res = window.run_window(CountingJob(clock, fail_at=3), in_flight=2, seconds=1000, clock=clock)
    assert res.error == "RuntimeError: out of memory"
    assert res.attempted == 4 and res.failed == 2  # the one that raised and the one still in flight
    assert len(res.done_at) == 2


def test_fewer_than_two_completions_give_no_rate():
    clock = Clock()
    assert window.run_window(CountingJob(clock), in_flight=1, units=1, clock=clock).units_per_s() is None
