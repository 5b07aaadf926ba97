"""The step loop keeps a fixed number of steps in flight and counts all
the work and all the time of the window; the memory reading adds the
runtime's reserve for temporaries."""
import time

import pytest

from bench import harness, steps


@pytest.mark.parametrize("seconds", [0.0, 0.05])
def test_step_loop_finishes_every_step_it_dispatches_in_order(seconds):
    started, finished, in_flight = [], [], []

    def dispatch(n):
        started.append(n)
        in_flight.append(len(started) - len(finished))
        time.sleep(0.005)
        return n

    n, elapsed = steps.run(dispatch, finished.append, seconds)
    assert started == finished == list(range(n))
    assert n >= 1 and elapsed >= seconds
    assert max(in_flight) <= steps.DEPTH


def test_memory_peak_adds_the_reserve_for_temporaries():
    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"peak_bytes_in_use": 3, "peak_bytes_reserved": 11}),
             Chip({"peak_bytes_in_use": 20}), Chip(None)]
    assert harness.memory_peak(chips) == 20
    assert harness.memory_peak(chips[:1]) == 14
