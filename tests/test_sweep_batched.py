"""Batched sweep engine: batched == sequential, grouping, shape safety."""
import numpy as np
import pytest

from repro.core import simulator
from repro.core.constants import Fabric, SimParams
from repro.core.sweep import SweepPoint, run_point, run_sweep_batched

SIM = SimParams(cycles=512, warmup=128)


def _assert_metrics_equal(b, s):
    assert b.name == s.name
    assert b.pkts_delivered == s.pkts_delivered
    assert b.flits_delivered == s.flits_delivered
    assert b.flits_injected == s.flits_injected
    assert b.throughput == s.throughput
    if np.isnan(s.avg_pkt_latency):
        assert np.isnan(b.avg_pkt_latency)
    else:
        assert np.isclose(b.avg_pkt_latency, s.avg_pkt_latency, rtol=1e-7)
    assert np.isclose(b.avg_pkt_energy_pj, s.avg_pkt_energy_pj, rtol=1e-6)
    for k in s.energy_breakdown:
        assert np.isclose(b.energy_breakdown[k], s.energy_breakdown[k],
                          rtol=1e-6)


def test_batched_equals_sequential_grid():
    """2 fabrics x 2 loads: one harmonized batch == a run_point loop."""
    pts = [SweepPoint(4, 4, fab, load=load, sim=SIM)
           for fab in (Fabric.WIRELESS, Fabric.INTERPOSER)
           for load in (0.1, 0.6)]
    batched = run_sweep_batched(pts)
    for p, b in zip(pts, batched):
        s = run_point(p.n_chips, p.n_mem, p.fabric, p.load, p_mem=p.p_mem,
                      sim=p.sim)
        _assert_metrics_equal(b, s)


def test_mixed_bucket_shapes_split_groups():
    """Different system sizes (different source counts) and app traffic
    (different K) in one call: groups split / harmonize, results match."""
    pts = [
        SweepPoint(4, 4, Fabric.WIRELESS, load=0.3, sim=SIM),
        SweepPoint(8, 4, Fabric.WIRELESS, load=0.3, sim=SIM),   # other N
        SweepPoint(4, 4, Fabric.INTERPOSER, load=0.3, sim=SIM),
        SweepPoint(4, 4, Fabric.WIRELESS, load=1.0, sim=SIM,
                   app="canneal"),                               # other K
    ]
    batched = run_sweep_batched(pts)
    for p, b in zip(pts, batched):
        s = run_point(p.n_chips, p.n_mem, p.fabric, p.load, p_mem=p.p_mem,
                      sim=p.sim, app=p.app)
        _assert_metrics_equal(b, s)


def test_run_batch_rejects_mismatched_shapes():
    from repro.core import traffic
    from repro.core.routing import compute_routing
    from repro.core.topology import build_xcym

    pss = []
    for nc in (4, 8):
        topo = build_xcym(nc, 4, Fabric.WIRELESS)
        rt = compute_routing(topo)
        tt = traffic.uniform_random(topo, 0.2, 0.2, SIM.cycles, 64)
        pss.append(simulator.pack(topo, rt, tt, topo.phy, SIM))
    with pytest.raises(ValueError, match="harmonized"):
        simulator.run_batch(pss, cycles=SIM.cycles)


def test_pack_floors_only_raise_dims():
    from repro.core import traffic
    from repro.core.routing import compute_routing
    from repro.core.topology import build_xcym

    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    tt = traffic.uniform_random(topo, 0.2, 0.2, SIM.cycles, 64)
    nat = simulator.pack(topo, rt, tt, topo.phy, SIM)
    grown = simulator.pack(topo, rt, tt, topo.phy, SIM,
                           floors={k: v + 64 for k, v in nat.dims.items()})
    for k in nat.dims:
        assert grown.dims[k] >= nat.dims[k] + 64
    # padding is inert: same dynamics on the grown shapes
    a = simulator.run(nat, cycles=SIM.cycles)
    b = simulator.run(grown, cycles=SIM.cycles)
    assert int(a.flits_del) == int(b.flits_del)
    assert int(a.pkts_del) == int(b.pkts_del)
    assert float(a.lat_sum) == float(b.lat_sum)


def _packed(case: str) -> simulator.PackedSim:
    from repro.core import sweep
    from repro.memory import MemSweepSpec
    fabric = {"substrate": Fabric.SUBSTRATE,
              "interposer": Fabric.INTERPOSER}.get(case, Fabric.WIRELESS)
    mem = MemSweepSpec(load=0.3) if case == "mem_on" else None
    p = SweepPoint(4, 4, fabric, load=0.3, sim=SIM, mem=mem)
    topo, rt, tt, _ = sweep._build_point(p)
    ps = simulator.pack(topo, rt, tt, p.phy, p.sim)
    if case == "floors":
        ps = simulator.pack(topo, rt, tt, p.phy, p.sim,
                            floors={k: v + 64 for k, v in ps.dims.items()})
    return ps


@pytest.mark.parametrize("case", ["substrate", "interposer", "wireless",
                                  "mem_on", "floors"])
def test_pack_lays_injection_buffers_out_as_one_block(case):
    """The step moves values between sources and their injection buffers
    as one block at ``inj_buf[0]``: source n feeds buffer Lw + n, and no
    other buffer names a source."""
    ps = _packed(case)
    assert ps.mem_on == (case == "mem_on")
    if case == "floors":
        assert ps.B >= _packed("wireless").B + 64
    Lw, N = ps.Lw, ps.n_inj
    inj_src = np.asarray(ps.ss.inj_src)
    assert inj_src.shape == (ps.B,) and Lw + N <= ps.B
    assert np.array_equal(inj_src[Lw:Lw + N], np.arange(N))
    assert (np.delete(inj_src, np.arange(Lw, Lw + N)) == -1).all()
    assert np.array_equal(np.asarray(ps.ss.inj_buf), Lw + np.arange(N))


def test_batched_mixed_fabrics_share_one_launch(monkeypatch):
    """The three fabrics place their injection block at different Lw, yet
    ride in one launch, and each lane equals its own run_point."""
    launches = []
    run_batch = simulator.run_batch

    def spy(pss, **kw):
        launches.append([int(ps.ss.inj_buf[0]) for ps in pss])
        return run_batch(pss, **kw)

    monkeypatch.setattr(simulator, "run_batch", spy)
    pts = [SweepPoint(4, 4, fab, load=0.6, sim=SIM) for fab in Fabric]
    batched = run_sweep_batched(pts)
    assert len(launches) == 1 and len(set(launches[0])) == 3, launches
    for p, b in zip(pts, batched):
        s = run_point(p.n_chips, p.n_mem, p.fabric, p.load, p_mem=p.p_mem,
                      sim=p.sim)
        _assert_metrics_equal(b, s)
