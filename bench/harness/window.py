"""The measured window: a closed loop with one client, and its arithmetic.

The client submits its next call when the last one returns, and stops
submitting once ``seconds`` have passed, so the window ends at a call
boundary.  Every rate is taken over all calls and all the time of the
window, the host's work between calls included.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable


@dataclasses.dataclass(frozen=True)
class Call:
    t0: float            # host clock when the call was submitted
    t1: float            # host clock when its results were back
    points: int          # design points the call returned
    lane_cycles: int     # simulated cycles summed over its real lanes


def closed_loop(run_one: Callable[[int], tuple[int, int]], seconds: float,
                clock: Callable[[], float] = time.perf_counter) -> list[Call]:
    """Call ``run_one(i)`` for i = 0, 1, ... until ``seconds`` have passed.

    ``run_one`` returns (points, lane_cycles) of call ``i``.  At least one
    call is made.
    """
    calls: list[Call] = []
    start = clock()
    while True:
        t0 = clock()
        points, lane_cycles = run_one(len(calls))
        calls.append(Call(t0, clock(), points, lane_cycles))
        if clock() - start >= seconds:
            return calls


def window_s(calls: list[Call]) -> float:
    """From the first call's submission to the last call's return."""
    return calls[-1].t1 - calls[0].t0


def lane_cycles_per_s(calls: list[Call]) -> float:
    return sum(c.lane_cycles for c in calls) / window_s(calls)


def point_s(calls: list[Call]) -> float:
    return window_s(calls) / sum(c.points for c in calls)


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads: the window's calls, the harness's
    host spans inside it, and the reduced device trace of its first
    ``traced_calls`` calls (``None`` when the run was not traced)."""

    calls: list[Call]
    spans: list[tuple[str, float, float]]     # (layer, start s, end s)
    trace: dict | None = None
    traced_calls: int = 0

    def per_call(self, *layers: str) -> float:
        """Host seconds per call spent in the named layers."""
        lo, hi = self.calls[0].t0, self.calls[-1].t1
        total = sum(b - a for n, a, b in self.spans
                    if n in layers and a >= lo and b <= hi)
        return total / len(self.calls)

    @property
    def lane_cycles(self) -> int:
        return sum(c.lane_cycles for c in self.calls)

    @property
    def traced_lane_cycles(self) -> int:
        return sum(c.lane_cycles for c in self.calls[:self.traced_calls])
