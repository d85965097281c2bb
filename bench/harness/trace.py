"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took the most time.

The trace is the ``.xplane.pb`` file that ``jax.profiler`` writes.  Device
planes are named ``/device:<kind>:<n>``; the host spans the harness marks
with ``TraceAnnotation`` (``bench.call``, ``bench.build``, ...) sit in the
host plane on the same clock.  The traced window runs from the start of the
first ``bench.call`` span to the end of the last.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os

from harness.program import SPAN_PREFIX

TOP = 10
OP_LINES = ("XLA Ops",)            # one event per device operation
MODULE_LINES = ("XLA Modules",)    # one event per program execution


@dataclasses.dataclass
class Trace:
    # device plane name -> [(op name, start ns, end ns)]
    device_ops: dict[str, list[tuple[str, float, float]]]
    # host spans of the harness: [(name, start ns, end ns)]
    host_spans: list[tuple[str, float, float]]
    # device plane name -> [(program name, start ns, end ns)]; busy time
    # is taken from these where the trace has them, since a program runs
    # its while loop on the device between its ops
    device_modules: dict[str, list[tuple[str, float, float]]] = \
        dataclasses.field(default_factory=dict)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_prefix: str = SPAN_PREFIX) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for names, out in ((OP_LINES, device_ops),
                               (MODULE_LINES, modules)):
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for ln in plane.lines if ln.name in names
                          for e in ln.events]
                if events:
                    out[plane.name] = events
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name[len(span_prefix):], e.start_ns,
                           e.start_ns + e.duration_ns)
                          for e in ln.events if e.name.startswith(span_prefix)]
    return Trace(device_ops, sorted(spans, key=lambda s: s[1]), modules)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _span_at(spans, t: float) -> str:
    """The innermost harness span (not ``call``) holding time ``t``."""
    best = None
    for name, a, b in spans:
        if name != "call" and a <= t < b and (best is None
                                              or b - a < best[1] - best[0]):
            best = (a, b, name)
    if best is not None:
        return best[2]
    return "call" if any(a <= t < b for n, a, b in spans if n == "call") \
        else "between_calls"


def reduce(tr: Trace) -> dict:
    """busy_s (mean over devices), window_s, and the breakdown lists."""
    calls = [(a, b) for n, a, b in tr.host_spans if n == "call"]
    if not calls:
        raise ValueError("the trace holds no bench.call span")
    busy_src = tr.device_modules or tr.device_ops
    if not busy_src:
        raise ValueError("the trace holds no device operation")
    lo, hi = calls[0][0], calls[-1][1]
    op_time: dict[str, float] = {}
    for ops in (tr.device_ops or tr.device_modules).values():
        for n, a, b in ops:
            if b > lo and a < hi:
                op_time[n] = op_time.get(n, 0.0) + (min(b, hi) - max(a, lo))
    busy, gaps = [], []
    for dev, events in sorted(busy_src.items()):
        merged = merge(_clip([(a, b) for _, a, b in events], lo, hi))
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += heapq.nlargest(TOP, ((b - a, a, b) for a, b in
                                     zip(edges[::2], edges[1::2]) if b > a))
    gaps = [(_span_at(tr.host_spans, (a + b) / 2), t)
            for t, a, b in heapq.nlargest(TOP, gaps)]
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_total": sum(busy) * ns,
        "window_s": (hi - lo) * ns,
        "devices": len(busy),
        "device_ops": [[n, t * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, t * ns] for n, t in gaps],
    }
