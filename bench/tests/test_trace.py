"""The reduction from a profiler trace to busy time, idle gaps and the
top device operations."""
import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6          # ns per ms


def _synthetic():
    spans = [("call", 0, 100 * MS), ("build", 0, 10 * MS),
             ("pack", 10 * MS, 20 * MS), ("launch", 20 * MS, 90 * MS),
             ("metrics", 90 * MS, 100 * MS),
             ("call", 110 * MS, 200 * MS), ("launch", 120 * MS, 190 * MS)]
    ops = {"/device:TPU:0": [("while", 22 * MS, 60 * MS),
                             ("fusion.1", 60 * MS, 61 * MS),
                             ("while", 65 * MS, 89 * MS),
                             ("fusion.2", 92 * MS, 93 * MS),
                             ("while", 121 * MS, 189 * MS)]}
    return trace.Trace(ops, spans)


def test_merge_unions_overlaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_window_and_gaps():
    red = trace.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(0.200)
    busy = (38 + 1 + 24 + 1 + 68) * 1e-3
    assert red["busy_s"] == pytest.approx(busy)
    assert red["busy_s_total"] == pytest.approx(busy)
    assert red["devices"] == 1
    assert red["device_ops"][0] == ["while", pytest.approx(0.130)]
    # gaps, longest first, each named by the span holding its middle
    assert red["idle_gaps"] == [
        ["between_calls", pytest.approx(0.028)],   # 93-121 ms
        ["pack", pytest.approx(0.022)],            # 0-22 ms, middle 11 ms
        ["call", pytest.approx(0.011)],            # 189-200 ms
        ["launch", pytest.approx(0.004)],          # 61-65 ms
        ["metrics", pytest.approx(0.003)]]         # 89-92 ms


def test_reduce_averages_devices():
    tr = _synthetic()
    tr.device_ops["/device:TPU:1"] = [("while", 0, 50 * MS)]
    red = trace.reduce(tr)
    assert red["devices"] == 2
    assert red["busy_s_total"] == pytest.approx(0.132 + 0.050)
    assert red["busy_s"] == pytest.approx((0.132 + 0.050) / 2)


def test_reduce_needs_calls_and_device_ops():
    tr = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace({}, tr.host_spans))
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(tr.device_ops, []))


def test_busy_from_program_executions_where_traced():
    tr = _synthetic()
    # one program execution spans both while loops and the op between
    tr.device_modules = {"/device:TPU:0": [("run_mapped", 22 * MS, 89 * MS),
                                           ("run_mapped", 121 * MS, 189 * MS)]}
    red = trace.reduce(tr)
    assert red["busy_s"] == pytest.approx((67 + 68) * 1e-3)
    # operation times still come from the op line
    assert red["device_ops"][0] == ["while", pytest.approx(0.130)]
