"""High-level experiment drivers for the paper's evaluations (§IV.B-D).

Two APIs:

- ``run_point``: simulate one (system, fabric, traffic) point.  Kept as the
  simple entry point; internally it is a batch of one.
- ``run_sweep_batched``: simulate a whole grid of points (a figure's worth)
  in as few XLA launches as possible.  Points are grouped by padded bucket
  shape; within a candidate group the pack dims are *harmonized* (every
  point re-packed with the group's max dims as floors — padding is
  semantically inert) so that, e.g., three fabrics of the same system size
  share one launch.  Each group runs through ``simulator.run_batch`` —
  one ``lax.map`` scan, sharded across host devices when available — and
  metrics come back through the vmapped ``metrics.compute_metrics_batch``.

Grouping rules (see README "Batched sweeps"): points can share a group iff
they have the same number of traffic sources N (padded shapes [N, K] only
harmonize over K).  Everything else — fabric, topology, loads, seeds, PHY
values, MAC mode, medium, cycle budget, warm-up — is traced data and
batches freely.  Since the drain-aware chunked driver (ISSUE 5) the cycle
budget is per-lane traced data (``SimStatic.cycles``), so points that
differ only in ``sim.cycles`` merge into one launch and one compile; each
lane freezes exactly at its own budget, and lanes whose traffic drains
early stop simulating entirely.  Trace points (``SweepPoint(trace=...)``,
see ``workloads``) follow the same rules: one trace emitted on the three
fabrics keeps N constant by construction, so a whole trace-figure row is
one launch; multicast-group and phase dims (M, P) harmonize like the rest.
(``mem_on``/``phy_on`` still split groups — they select different
compiled steps, which the defensive shape_key split below enforces.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Sequence

from repro.core import simulator, spans, traffic
from repro.core.constants import DEFAULT_PHY, Fabric, PhyParams, SimParams
from repro.core.metrics import Metrics, compute_metrics_batch
from repro.core.routing import compute_routing
from repro.core.topology import Topology, build_xcym

HARMONIZED_DIMS = ("B", "S", "R", "K", "M", "P", "Y", "BK")


@functools.lru_cache(maxsize=64)
def _cached_system(n_chips: int, n_mem: int, fabric: Fabric, phy: PhyParams,
                   wireless_weight: float):
    topo = build_xcym(n_chips, n_mem, fabric, phy)
    rt = compute_routing(topo, wireless_weight=wireless_weight)
    return topo, rt


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One evaluation point of a figure grid (run_point's argument list).

    ``trace`` switches the point from synthetic open-loop traffic to a
    phase-barrier ML workload trace (``workloads.Trace``), lowered
    fabric-aware by ``traffic.from_trace``; ``load``/``p_mem``/``app``
    are ignored for trace points.

    ``mem`` (a ``memory.MemSweepSpec``) switches the point to closed-loop
    memory traffic: request/reply round trips against the in-package
    stacks, gated at ``dram.max_outstanding`` per core.  ``closed_loop``
    applies the same reinterpretation to ``app`` MMP traffic (its
    ``p_mem`` packets become round-trip reads; ``dram`` optionally
    overrides the stack timing).

    ``phy_spec`` (a ``phy.PhySweepSpec``) turns the ideal wireless
    medium into the lossy channel: per-link SNR/BER-derived rates, CRC
    retransmission and drops.  Wireline fabrics ignore it (they run the
    exact ideal program), so a quality sweep can span all three fabrics
    in one grid.
    """

    n_chips: int
    n_mem: int
    fabric: Fabric
    load: float = 0.0
    p_mem: float = 0.2
    phy: PhyParams = DEFAULT_PHY
    sim: SimParams = dataclasses.field(default_factory=SimParams)
    app: str | None = None
    trace: object | None = None
    mem: object | None = None
    closed_loop: bool = False
    dram: object | None = None
    phy_spec: object | None = None
    wireless_weight: float = 3.0
    name: str | None = None


def _build_point(p: SweepPoint):
    """Host-side construction: topology, routing, traffic table, label."""
    topo, rt = _cached_system(p.n_chips, p.n_mem, p.fabric, p.phy,
                              p.wireless_weight)
    if p.trace is not None:
        tt = traffic.from_trace(topo, p.trace, p.phy.pkt_flits,
                                p.phy.flit_bits, dram=p.dram)
        label = p.name or f"{topo.name}/{p.trace.name}"
        return topo, rt, tt, label
    if p.mem is not None:
        from repro.memory import closed_loop_uniform
        tt = closed_loop_uniform(
            topo, p.mem.load, p.sim.cycles, p.phy.pkt_flits,
            dram=p.mem.dram, read_frac=p.mem.read_frac,
            hot_stack_frac=p.mem.hot_stack_frac, seed=p.sim.seed)
        label = p.name or (f"{topo.name}/memcl/load={p.mem.load}"
                           f"/mo={p.mem.dram.max_outstanding}")
        return topo, rt, tt, label
    if p.app is None:
        tt = traffic.uniform_random(topo, p.load, p.p_mem, p.sim.cycles,
                                    p.phy.pkt_flits, seed=p.sim.seed)
    else:
        tt = traffic.application(topo, traffic.APP_MODELS[p.app],
                                 p.sim.cycles, p.phy.pkt_flits,
                                 seed=p.sim.seed, load_scale=p.load,
                                 closed_loop=p.closed_loop, dram=p.dram)
    label = p.name or f"{topo.name}/load={p.load}/p_mem={p.p_mem}" \
        + (f"/{p.app}" if p.app else "") \
        + ("/closed" if p.closed_loop else "") \
        + (f"/phy:{p.phy_spec.policy}@{p.phy_spec.link_budget_db}dB"
           if p.phy_spec is not None else "") \
        + (f"/drift={p.phy_spec.drift_amp_db}dB"
           if p.phy_spec is not None and p.phy_spec.drift_amp_db > 0
           else "") \
        + ("/resel" if p.phy_spec is not None and p.phy_spec.reselect
           else "")
    return topo, rt, tt, label


def run_sweep_batched(points: Sequence[SweepPoint],
                      cycles: int | None = None,
                      devices: int | None = None,
                      driver: str = "chunked") -> list[Metrics]:
    """Simulate a grid of points in as few XLA launches as possible.

    Returns one ``Metrics`` per point, in input order.  Results are equal
    (bitwise, not merely allclose) to ``[run_point(...) for each point]``:
    batching only changes how many points ride in one launch, never the
    per-point program.  ``driver="monolithic"`` forces the fixed-length
    scan oracle (see ``simulator.run_batch``) — used by
    ``benchmarks/simspeed`` and the chunked-execution tests.

    Host spans (``spans``): ``run_sweep_batched`` around ``sweep.build``,
    ``sweep.harmonize``, then per group ``sweep.pack``, and per launch
    ``run_batch`` and ``compute_metrics_batch`` (their own spans).
    """
    with spans.span("run_sweep_batched"):
        with spans.span("sweep.build"):
            built = [_build_point(p) for p in points]
        with spans.span("sweep.harmonize"):
            natural = [simulator.pack_dims(topo, tt)
                       for topo, _, tt, _ in built]
            # group by N sources (cycle budgets are traced per-lane data
            # and batch freely); harmonize pack dims within a group
            groups: dict[tuple, list[int]] = {}
            for i, (p, (_, _, tt, _)) in enumerate(zip(points, built)):
                key = (tt.n_sources,)
                groups.setdefault(key, []).append(i)
            floors = [{d: max(natural[i][d] for i in idxs)
                       for d in HARMONIZED_DIMS} for idxs in groups.values()]

        results: list[Metrics | None] = [None] * len(points)
        for idxs, floor in zip(groups.values(), floors):
            with spans.span("sweep.pack"):
                packed = {}
                for i in idxs:
                    topo, rt, tt, _ = built[i]
                    packed[i] = simulator.pack(topo, rt, tt, points[i].phy,
                                               points[i].sim, floors=floor,
                                               phy_spec=points[i].phy_spec)
                # harmonized dims should unify shapes; split defensively
                by_shape: dict[tuple, list[int]] = {}
                for i in idxs:
                    by_shape.setdefault(packed[i].shape_key(), []).append(i)
            for sub in by_shape.values():
                pss = [packed[i] for i in sub]
                st = simulator.run_batch(pss, cycles=cycles, devices=devices,
                                         driver=driver)
                ms = compute_metrics_batch(
                    pss, st, [built[i][3] for i in sub],
                    [built[i][2].offered_load for i in sub], cycles=cycles)
                for i, m in zip(sub, ms):
                    results[i] = m
    return results  # type: ignore[return-value]


def run_point(
    n_chips: int,
    n_mem: int,
    fabric: Fabric,
    load: float,
    p_mem: float = 0.2,
    phy: PhyParams = DEFAULT_PHY,
    sim: SimParams = SimParams(),
    app: str | None = None,
    mem: object | None = None,
    closed_loop: bool = False,
    dram: object | None = None,
    phy_spec: object | None = None,
    wireless_weight: float = 3.0,
    name: str | None = None,
) -> Metrics:
    """Simulate one (system, fabric, traffic) point and return §IV metrics.

    Implemented as a batch of one through the batched sweep engine.
    """
    return run_sweep_batched([SweepPoint(
        n_chips=n_chips, n_mem=n_mem, fabric=fabric, load=load, p_mem=p_mem,
        phy=phy, sim=sim, app=app, mem=mem, closed_loop=closed_loop,
        dram=dram, phy_spec=phy_spec, wireless_weight=wireless_weight,
        name=name)])[0]


def saturation_bandwidth(n_chips: int, n_mem: int, fabric: Fabric,
                         p_mem: float = 0.2, **kw) -> Metrics:
    """Peak achievable bandwidth: drive at max load, report delivered."""
    return run_point(n_chips, n_mem, fabric, load=1.0, p_mem=p_mem, **kw)


def latency_sweep(n_chips: int, n_mem: int, fabric: Fabric,
                  loads: Iterable[float], p_mem: float = 0.2,
                  **kw) -> list[Metrics]:
    """Latency-vs-load curve for one fabric, batched into one launch."""
    return run_sweep_batched([
        SweepPoint(n_chips=n_chips, n_mem=n_mem, fabric=fabric, load=l,
                   p_mem=p_mem, **kw) for l in loads])
