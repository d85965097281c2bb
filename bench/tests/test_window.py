"""The window's arithmetic: rates over all calls and all the window's time,
and a window that ends at a call boundary."""
import pytest

from harness import window


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_closed_loop_ends_at_call_boundary():
    clock = Clock()

    def run_one(i):
        clock.t += 4.0            # every call takes 4 s of host time
        return 6, 6 * 1000

    calls = window.closed_loop(run_one, 10.0, clock)
    assert len(calls) == 3        # 4, 8 < 10, 12 >= 10: the third call ends it
    assert window.window_s(calls) == pytest.approx(12.0)
    assert window.lane_cycles_per_s(calls) == pytest.approx(18000 / 12.0)
    assert window.point_s(calls) == pytest.approx(12.0 / 18)


def test_rate_counts_time_between_calls():
    calls = [window.Call(0.0, 1.0, 1, 1000), window.Call(3.0, 4.0, 1, 1000)]
    # the host's 2 s between the calls are part of the window
    assert window.lane_cycles_per_s(calls) == pytest.approx(2000 / 4.0)
    assert window.point_s(calls) == pytest.approx(2.0)


def test_one_call_at_least():
    clock = Clock()
    calls = window.closed_loop(lambda i: (1, 10), 0.0, clock)
    assert len(calls) == 1


def test_context_per_call_keeps_window_spans_only():
    calls = [window.Call(10.0, 12.0, 1, 100), window.Call(12.0, 14.0, 1, 100)]
    spans = [("launch", 10.5, 11.5), ("launch", 12.5, 13.0),
             ("build", 10.0, 10.2), ("launch", 5.0, 6.0)]   # last: set-up
    ctx = window.Context(calls, spans)
    assert ctx.per_call("launch") == pytest.approx(0.75)
    assert ctx.per_call("build", "pack") == pytest.approx(0.1)
    assert ctx.lane_cycles == 200


def test_device_cycles_count_the_traced_calls_only():
    calls = [window.Call(0.0, 2.0, 6, 6000), window.Call(2.0, 4.0, 6, 5000)]
    assert window.Context(calls, [], {}, 1).traced_lane_cycles == 6000
    assert window.Context(calls, [], {}, 2).traced_lane_cycles == 11000
    assert window.Context(calls, []).traced_lane_cycles == 0
