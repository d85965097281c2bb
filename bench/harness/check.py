"""The check that decides ``correct``: the window's lanes against the
plain reference (``refsim``), leaf by leaf and metric by metric.

Each sampled lane is rebuilt by the reference from the same plain point
data: its topology, routing and channel tables, its own traffic generator
and the seed.  The reference packs the lane at its natural sizes, runs
every cycle of the budget with one fixed-length scan, and reduces the
final state to metrics with plain numpy.  The program's lane is cut to
those natural sizes before it is compared, so how the program groups,
pads or lays out its lanes is not part of the check.  Then:

- ``int_mismatches``: integer and boolean state leaves that both sides
  declare, and integer metrics, that differ from the reference.  Exact.
  A leaf the reference declares in an encoding of its own
  (``engine.OWN_ENCODING``) is left out; the program's early stop
  (``drain_cycle``) has no counterpart in a run of every cycle and has to
  lie between the warm-up and the budget.
- ``float_rel_gap``: the largest relative gap of a float leaf or a float
  metric.  The program reduces its energies in float32 on the device, the
  reference in float64.

The control is the reference in the program's place with its metrics
reduced in bfloat16, the precision below the float32 that the
configuration states.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from harness.grid import phy_params_kwargs

CHECKS = ("int_mismatches", "float_rel_gap", "lanes_checked")
DRIVER_STOP = "drain_cycle"


@functools.lru_cache(maxsize=16)
def _system(n_chips: int, n_mem: int, fabric: str, phy, wireless_weight):
    from refsim import constants, routing, topology
    topo = topology.build_xcym(n_chips, n_mem,
                               constants.Fabric[fabric.upper()], phy)
    return topo, routing.compute_routing(topo,
                                         wireless_weight=wireless_weight)


def check_config(config: dict) -> None:
    """The configuration's settings must be the reference's."""
    from refsim import constants, engine, rates
    got = {"num_vcs": engine.V, "buf_depth": engine.DEPTH,
           "traffic_pattern": "uniform_random"}
    if config.get("channel"):
        got["rate_table_gbps"] = [e.gbps for e in rates.DEFAULT_RATE_TABLE]
        got["reselect_every_cycles"] = constants.WINDOW_CYCLES
    want = dict(config, **(config.get("channel") or {}))
    bad = {k: (v, want[k]) for k, v in got.items() if v != want[k]}
    if bad:
        raise ValueError(f"the reference cannot run this configuration "
                         f"(reference, configuration): {bad}")


def reference_lane(p: dict, config: dict, dtype=np.float64):
    """The reference's final state (numpy leaves, natural sizes) and
    metrics of one point of ``grid.call_points``."""
    from refsim import channel, constants, engine, metrics, traffic

    phy = constants.PhyParams(**phy_params_kwargs(config))
    sim = constants.SimParams(
        cycles=p["cycles"], warmup=p["warmup"],
        mac=constants.MacMode[config["mac"].upper()],
        sleepy_rx=config["sleepy_rx"], seed=p["seed"])
    topo, rt = _system(config["n_chips"], config["n_mem"], p["fabric"], phy,
                       config["wireless_weight"])
    if topo.n_cores != config["n_cores"]:
        raise ValueError(f"{topo.n_cores} cores, the configuration states "
                         f"{config['n_cores']}")
    tt = traffic.uniform_random(
        np.flatnonzero(topo.is_core), np.flatnonzero(topo.is_mem),
        p["load"], p["p_mem"], p["cycles"], phy.pkt_flits, p["seed"])
    spec = None
    if p["phy"] is not None:
        q = p["phy"]
        spec = channel.PhySweepSpec(
            link_budget_db=q["link_budget_db"], policy=q["policy"],
            max_retx=q["max_retx"], seed=q["seed"],
            channel=channel.ChannelParams(
                pl_exp=q["pl_exp"], d0_mm=q["d0_mm"],
                sigma_shadow_db=q["sigma_shadow_db"]),
            drift_amp_db=q["drift_amp_db"], drift_period=q["drift_period"],
            reselect=q["reselect"])
    ps = engine.pack(topo, rt, tt, phy, sim, phy_spec=spec)
    st = engine.run(ps)
    state = {f: np.asarray(getattr(st, f)) for f in st._fields}
    return state, metrics.lane_metrics(ps, state, tt.offered_load, dtype)


def _value_gap(a, b, ints: list, path: str) -> float:
    """Largest float gap between two metric values; integer misses go to
    ``ints``."""
    if isinstance(b, dict):
        if not isinstance(a, dict) or set(a) != set(b):
            ints.append(path)
            return 0.0
        return max((_value_gap(a[k], b[k], ints, f"{path}.{k}") for k in b),
                   default=0.0)
    if isinstance(b, (bool, int, np.integer, np.bool_)):
        if a != b:
            ints.append(path)
        return 0.0
    return _rel_gap(np.asarray(a, np.float64), np.asarray(b, np.float64))


def _rel_gap(a: np.ndarray, b: np.ndarray) -> float:
    if a.shape != b.shape:
        return math.inf
    both_nan = np.isnan(a) & np.isnan(b)
    scale = np.maximum(np.abs(a), np.abs(b))
    gap = np.where(both_nan | (a == b), 0.0,
                   np.abs(a - b) / np.where(scale > 0, scale, 1.0))
    gap = np.where(np.isnan(gap), math.inf, gap)
    return float(gap.max(initial=0.0))


def _natural(got: np.ndarray, want: np.ndarray):
    """``got`` cut to ``want``'s shape, or None where it cannot be."""
    if got.ndim != want.ndim or any(g < w for g, w in
                                    zip(got.shape, want.shape)):
        return None
    return got[tuple(slice(0, w) for w in want.shape)]


def compare_lane(state: dict, m, ref_state: dict, ref_m: dict,
                 warmup: int) -> tuple[list, float]:
    """(integer leaves and metrics that differ, largest float gap).

    ``m`` is the program's ``Metrics``, or a dict with the same names."""
    from refsim import engine
    ints: list[str] = []
    gap = 0.0
    for f in sorted(set(state) & set(ref_state) - set(engine.OWN_ENCODING)):
        got, want = np.asarray(state[f]), ref_state[f]
        if f == DRIVER_STOP:
            if not warmup <= int(got) <= int(want):
                ints.append(f"state.{f}")
            continue
        cut = _natural(got, want)
        if cut is None:
            ints.append(f"state.{f}")
        elif np.issubdtype(want.dtype, np.floating):
            gap = max(gap, _rel_gap(cut.astype(np.float64),
                                    want.astype(np.float64)))
        elif not np.array_equal(cut, want):
            ints.append(f"state.{f}")
    get = m.get if isinstance(m, dict) else (lambda k: getattr(m, k, None))
    for name, want in ref_m.items():
        got = get(name)
        if got is None:
            ints.append(f"metrics.{name}")
            continue
        gap = max(gap, _value_gap(got, want, ints, f"metrics.{name}"))
    return ints, gap


def judge(lanes: list[tuple[list, float]], limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    mism = sum(len(ints) for ints, _ in lanes)
    gap = max((g for _, g in lanes), default=math.inf)
    checks = {
        "int_mismatches": {"value": mism, "limit": limits["int_mismatches"],
                           "rule": "<="},
        "float_rel_gap": {"value": gap, "limit": limits["float_rel_gap"],
                          "rule": "<="},
        "lanes_checked": {"value": len(lanes),
                          "limit": limits["lanes_checked"], "rule": ">="},
    }
    ok = all(c["value"] <= c["limit"] if c["rule"] == "<="
             else c["value"] >= c["limit"] for c in checks.values())
    return ok, checks
