"""Host spans, counters and device stage scopes of the sweep path.

Host side: ``span(name, **counters)`` times a block on ``time.perf_counter``
and marks it for the profiler with a ``TraceAnnotation`` named
``repro.<name>``, so a trace puts each device idle gap down to the span
that was open.  Each finished span is kept as a ``Span`` record in a
bounded in-memory ring (``MAXLEN``); ``snapshot()`` returns the records,
``reset()`` clears them.  Counters are the span's attributes: the block
may add to the dict the ``with`` statement yields.  Off the profiler a
span costs two clock reads, one append and an inactive TraceMe.

Device side: ``scope(name)`` is ``jax.named_scope`` restricted to the
names in ``SCOPES``.  It only adds ``op_name`` metadata to the operations
traced inside it; the compiled program is otherwise unchanged.
``staged`` runs a function under a chain of sequential scopes, one per
stage of the cycle step.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time
from typing import NamedTuple

import jax

PREFIX = "repro."          # profiler name of a span: PREFIX + name
MAXLEN = 1 << 14           # records kept; the oldest fall out first

# device stage scopes: the stages of ``simulator.make_step`` as its code
# numbers them, then the living-channel window update (which runs first in
# a cycle), the lossy channel's work inside forwarding (per-link tables,
# pacing, CRC, ARQ rewind and drop, air delivery), and the scopes of
# ``chunked.run_chunked``
STEP_STAGES = ("step.arrive", "step.vc_claim", "step.forward", "step.phase",
               "step.memory", "step.inject", "step.rx_sleep", "step.window",
               "step.phy")
DRIVER_SCOPES = ("driver.cycle", "driver.drain_check", "driver.finalize")
SCOPES = STEP_STAGES + DRIVER_SCOPES


class Span(NamedTuple):
    name: str
    t0: float          # host perf_counter seconds
    t1: float
    attrs: dict        # the span's counters


_records: collections.deque[Span] = collections.deque(maxlen=MAXLEN)


@contextlib.contextmanager
def span(name: str, **counters):
    """Time the block as span ``name``; yields its counters to add to."""
    attrs = dict(counters)
    with jax.profiler.TraceAnnotation(PREFIX + name):
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            _records.append(Span(name, t0, time.perf_counter(), attrs))


def snapshot() -> list[Span]:
    """The kept records, in the order their spans started."""
    return sorted(_records, key=lambda s: s.t0)


def reset() -> None:
    _records.clear()


def scope(name: str):
    """``jax.named_scope(name)`` for one of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}")
    return jax.named_scope(name)


class _Stages:
    """Sequential scopes: each call closes the open scope, opens the next."""

    def __init__(self):
        self._open = None

    def __call__(self, name: str) -> None:
        self.close()
        self._open = scope(name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def staged(fn):
    """``fn(*args, stage)`` called as ``fn(*args)``; ``stage(name)`` moves
    the ops traced after it into scope ``name`` until the next stage."""
    @functools.wraps(fn)
    def wrapped(*args):
        stage = _Stages()
        try:
            return fn(*args, stage)
        finally:
            stage.close()
    return wrapped
