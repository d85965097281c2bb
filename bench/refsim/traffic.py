"""The reference's own uniform random traffic (arXiv:1709.07529 §IV.B).

Each core offers packets by a Bernoulli process: in every cycle of the
budget it starts a packet with probability ``load / pkt_flits``.  A
packet goes to a memory stack with probability ``p_mem``, the stack drawn
uniformly; otherwise to a uniformly drawn core other than its source.
A core keeps its first ``k = max(8, ceil(cycles / pkt_flits) + 8)``
packets, in order of birth.

The draws come from ``numpy.random.default_rng(seed)`` in this order,
each as one array over all cores: the arrivals ``[cores, cycles]``, then
``[cores, k]`` each of the memory coin, the stack and the other core.
That order is part of the traffic's definition: the same seed gives the
same packets as the program's generator, which is written apart.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

NO_PKT = np.int32(2**31 - 1)     # a packet slot that stays empty


@dataclasses.dataclass
class TrafficTable:
    """Per source core, ``k`` packet slots: birth cycle and destination
    switch."""

    src_switch: np.ndarray   # [cores] switch of each source core
    births: np.ndarray       # [cores, k] cycle, NO_PKT where no packet
    dests: np.ndarray        # [cores, k] destination switch
    offered_load: float      # flits/cycle/core offered

    @property
    def n_sources(self) -> int:
        return len(self.src_switch)

    @property
    def k(self) -> int:
        return self.births.shape[1]


def uniform_random(core_switches, mem_switches, load: float, p_mem: float,
                   cycles: int, pkt_flits: int, seed: int) -> TrafficTable:
    """The table of ``len(core_switches)`` cores over ``cycles`` cycles."""
    cores = [int(s) for s in core_switches]
    stacks = [int(s) for s in mem_switches]
    n = len(cores)
    p_pkt = min(1.0, load / pkt_flits)
    k = max(8, math.ceil(cycles / pkt_flits) + 8)
    rng = np.random.default_rng(seed)
    arrivals = rng.random((n, cycles)) < p_pkt
    to_mem = rng.random((n, k)) < p_mem
    stack = rng.integers(0, len(stacks), (n, k))
    other = rng.integers(0, n - 1, (n, k))

    births = np.full((n, k), NO_PKT, np.int32)
    dests = np.zeros((n, k), np.int32)
    for i in range(n):
        born = [t for t in range(cycles) if arrivals[i, t]][:k]
        births[i, :len(born)] = born
        for j in range(k):
            if to_mem[i, j]:
                dests[i, j] = stacks[stack[i, j]]
            else:
                c = int(other[i, j])        # the c-th core, skipping i
                dests[i, j] = cores[c + 1 if c >= i else c]
    return TrafficTable(np.asarray(cores, np.int32), births, dests,
                        offered_load=p_pkt * pkt_flits)
