"""The window's arithmetic on a scripted driver and a scripted clock: what
the three end-to-end metrics are taken over."""

import numpy as np
import pytest

from benchmark import harness

TRAFFIC = {"epochs_per_call": 5, "max_epochs": 30,
           "target": {"quality": "q", "at_most": 1.0}}


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class Scripted:
    """Quality falls by 0.1 an epoch from ``start``; a call takes 1 s."""
    quality_scale = 1.0

    def __init__(self, clock, start=3.25, stuck=False):
        self.clock, self.start, self.stuck = clock, start, stuck

    def initial(self):
        return 0

    def call(self, epochs_done):
        self.clock.now += 1.0
        q = self.start - 0.1 * (epochs_done + np.arange(1, 6))
        if self.stuck:
            q = np.full(5, 9.0)
        return epochs_done + 5, q


def test_jobs_calls_and_epochs_are_all_counted():
    clock = Clock()
    win = harness.run_window(Scripted(clock), TRAFFIC, 12.5, harness.Spans(),
                             clock=clock)
    # 3.25 - 0.1 e <= 1.0 first at e = 23: a job is 5 calls, 25 epochs
    assert [e for _, e in win.jobs] == [23, 23]
    # the third job is cut by the window's end after its third call: its
    # epochs count for the rate, and it is neither finished nor failed
    assert len(win.call_s) == 13 and win.epochs == 65
    assert win.failed == 0 and win.seconds == pytest.approx(13.0)
    e2e = harness.end_to_end(win, samples_per_epoch=1000, setup_s=2.0)
    assert e2e["samples_per_s"] == pytest.approx(65 * 1000 / 13.0)
    assert e2e["time_to_target_s"] == pytest.approx(10.0 / 2)
    assert e2e["call_ms_p95"] == pytest.approx(1000.0)
    assert e2e["setup_s"] == 2.0


def test_a_job_that_never_meets_its_target_fails_at_max_epochs():
    clock = Clock()
    win = harness.run_window(Scripted(clock, stuck=True), TRAFFIC, 7.0,
                             harness.Spans(), clock=clock)
    assert win.jobs == [] and win.failed == 1
    assert win.epochs == 35          # 30 to the verdict, one more call to 7 s
    assert "time_to_target_s" not in harness.end_to_end(win, 1, 1.0)


def test_a_short_window_still_sees_one_job_to_its_verdict():
    clock = Clock()
    win = harness.run_window(Scripted(clock), TRAFFIC, 0.5, harness.Spans(),
                             clock=clock)
    assert len(win.jobs) == 1 and win.epochs == 25


def test_a_stall_moves_the_tail_and_the_rate():
    clock = Clock()
    driver = Scripted(clock)
    real = driver.call
    calls = []

    def stalling(state):
        calls.append(1)
        if len(calls) == 4:
            clock.now += 9.0
        return real(state)

    driver.call = stalling
    win = harness.run_window(driver, TRAFFIC, 12.0, harness.Spans(),
                             clock=clock)
    assert win.call_s == pytest.approx([1.0, 1.0, 1.0, 10.0, 1.0])
    e2e = harness.end_to_end(win, 1, 1.0)
    assert e2e["call_ms_p95"] > 5000.0
    assert e2e["samples_per_s"] == pytest.approx(25 / 14.0)
    assert e2e["time_to_target_s"] == pytest.approx(14.0)


def test_nan_quality_meets_no_target():
    clock = Clock()
    driver = Scripted(clock)
    driver.call = lambda s: (clock.__setattr__("now", clock.now + 1.0)
                             or s + 5, np.full(5, np.nan))
    win = harness.run_window(driver, TRAFFIC, 3.0, harness.Spans(), clock=clock)
    assert win.jobs == [] and win.failed == 1
