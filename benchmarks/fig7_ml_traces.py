"""ML workload traces through the cycle-accurate engine (new fig7).

Runs >= 3 model configs x 3 fabrics of phase-barrier collective traces
(``src/repro/workloads``) through ``run_sweep_batched`` — all nine points
share one bucket shape (same source count and cycle budget), so the whole
figure is a single batched XLA launch per host device group.

Reported per point: trace completion (phases done / cycles), delivered
bandwidth, energy per bit with the link/switch/ctrl/rx breakdown, and the
wireless broadcast counters (channel occupancies vs receptions).  The
cycle-accurate link energy is cross-checked against the analytic
``fabric.price_traffic`` total using the topology-derived spec
(``fabric.spec_from_topology``); the run fails loudly if any completed
point disagrees by more than 2x — the acceptance gate for the trace
subsystem (tests pin the same bound on a smaller trace).

A compiled-HLO trace (real XLA collectives from a jitted sharded step) is
included when the host exposes >= 2 XLA devices (benchmarks/__init__
splits the CPU); the big configs use the synthetic DNN-layer generator —
compiling a 405B-class step on CPU is not feasible, which is exactly what
``workloads.synthetic`` is for.
"""
from __future__ import annotations

import numpy as np

from repro.configs.base import get_config
from repro.core import traffic
from repro.core.constants import Fabric, SimParams
from repro.core.metrics import collective_summary
from repro.core.sweep import SweepPoint, run_sweep_batched
from repro.core.topology import build_xcym
from repro.interconnect.fabric import (FabricSpec, price_table,
                                       price_traffic, spec_from_topology)
from repro.workloads.hlo import trace_from_hlo
from repro.workloads.mapping import DeviceMap
from repro.workloads.synthetic import synthetic_dnn_trace

from benchmarks.common import emit

MODELS = ("gemma-7b", "mixtral-8x22b", "llama3-405b")
FABRICS = (Fabric.WIRELESS, Fabric.INTERPOSER, Fabric.SUBSTRATE)
N_CHIPS, N_MEM = 4, 4
N_DEV = 16                  # 4 devices per chip: TP in-chip, DP across
TARGET_PKTS = 120           # representative scale per trace
CYCLES = 96_000             # cross-chip DP rings are slow on serial I/O
SIM = SimParams(cycles=CYCLES, warmup=0)


def _autoscale(tr, pkt_bytes: float = 256.0):
    """Scale payload bytes so the emitted table has ~TARGET_PKTS packets."""
    total = tr.bytes_total()
    n_msgs = sum(len(p.messages) for p in tr.phases)
    want = max(TARGET_PKTS, n_msgs) * pkt_bytes
    return tr.scaled(want / max(total, 1.0))


def _compiled_trace(dm: DeviceMap):
    """Trace from a real compiled sharded step (None if single-device)."""
    import jax
    import jax.numpy as jnp

    if jax.device_count() < 2:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("d",))

    def stepfn(x, w):
        y = jnp.tanh(x @ w)
        return jax.lax.pmean(y, "d"), jax.lax.psum(y @ w.T, "d")

    n = 64
    sh = NamedSharding(mesh, P("d", None))
    x = jax.ShapeDtypeStruct((len(jax.devices()) * 4, n), jnp.float32, sharding=sh)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32)
    fn = jax.shard_map(stepfn, mesh=mesh,
                       in_specs=(P("d", None), P(None, None)),
                       out_specs=(P("d", None), P(None, None)))
    hlo = jax.jit(fn).lower(x, w).compile().as_text()
    tr = trace_from_hlo(hlo, dm, name="compiled:psum-step")
    return _autoscale(tr) if tr.n_phases else None


def main() -> None:
    wl_topo = build_xcym(N_CHIPS, N_MEM, Fabric.WIRELESS)
    dm = DeviceMap(wl_topo, N_DEV)

    traces = []
    for name in MODELS:
        tr = _autoscale(synthetic_dnn_trace(
            get_config(name), dm, tokens=2048, n_layers_cap=1))
        traces.append((name, tr))
    # one-shot-forced variant: every collective as single-hop multicasts —
    # the schedule a broadcast medium favors (wl_tx vs wl_rx shows the
    # shared channel crossed once per flit, received by the whole group)
    traces.append(("gemma-7b-oneshot", _autoscale(synthetic_dnn_trace(
        get_config("gemma-7b"), dm, tokens=2048, n_layers_cap=1,
        schedule="oneshot"))))
    ct = _compiled_trace(dm)
    if ct is not None:
        traces.append(("compiled", ct))
    for name, tr in traces:
        emit(f"fig7.trace,{name},{tr.describe()}")

    points, metas = [], []
    for name, tr in traces:
        for fab in FABRICS:
            points.append(SweepPoint(N_CHIPS, N_MEM, fab, trace=tr, sim=SIM,
                                     name=f"{name}/{fab.name.lower()}"))
            metas.append((name, tr, fab))
    ms = run_sweep_batched(points)

    emit("fig7,point,done_phases,cycles,GB_delivered,pj_bit,links_pj_bit,"
         "analytic_pj_bit,ratio,uniform_pj_bit,wl_tx,wl_rx,drain_cycle")
    worst = 0.0
    phy = points[0].phy
    for (name, tr, fab), m in zip(metas, ms):
        topo = build_xcym(N_CHIPS, N_MEM, fab)
        bits = max(m.flits_delivered, 1) * phy.flit_bits
        links_pj_bit = m.energy_breakdown["links"] / bits
        # analytic comparator: the emitted table priced along its actual
        # forwarding paths.  Routing it through price_traffic is an
        # identity on pj/bit — kept deliberately so the published number
        # is literally fabric.price_traffic's output on the trace spec.
        tt = traffic.from_trace(topo, tr, phy.pkt_flits)
        _tot, pj_bit = price_table(topo, tt, phy.pkt_flits, phy.flit_bits)
        spec = FabricSpec(f"trace:{m.name}", pj_bit, 16.0, 1.0)
        analytic_pj_bit = price_traffic(bits / 8, 1, spec).energy_mj \
            * 1e9 / bits
        ratio = links_pj_bit / max(analytic_pj_bit, 1e-12)
        if m.trace_done:
            worst = max(worst, max(ratio, 1 / ratio))
        # uniform-traffic pricing, for locality context only
        uniform = spec_from_topology(topo).pj_per_bit
        emit(f"fig7,{m.name},{m.phases_done}/{m.n_phases},"
             f"{m.trace_cycles},{bits/8e9:.6f},{m.energy_pj_bit:.2f},"
             f"{links_pj_bit:.2f},{analytic_pj_bit:.2f},{ratio:.2f},"
             f"{uniform:.2f},{m.wl_tx_flits},{m.wl_rx_flits},"
             f"{m.drain_cycle}")

    # per-collective timing on the wireless fabric, one line per model
    for (name, tr, fab), m in zip(metas, ms):
        if fab != Fabric.WIRELESS or not m.phases_done:
            continue
        tt = traffic.from_trace(build_xcym(N_CHIPS, N_MEM, fab), tr,
                                points[0].phy.pkt_flits)
        for lab, rec in collective_summary(m, tt.phase_labels).items():
            emit(f"fig7.collective,{name},{lab},{rec['cycles']},"
                 f"{rec['flits']},{rec['phases']}")

    done = sum(m.trace_done for m in ms)
    emit(f"fig7.check,traces_completed,{done}/{len(ms)}")
    emit(f"fig7.check,worst_analytic_ratio,{worst:.2f}")
    if done < len(ms):
        raise SystemExit("fig7: some traces did not complete; raise CYCLES")
    if worst > 2.0:
        raise SystemExit(
            f"fig7: cycle-vs-analytic link energy ratio {worst:.2f} > 2x")


if __name__ == "__main__":
    main()
