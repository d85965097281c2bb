"""Fig. 9 (new): the lossy in-package channel — goodput, retransmission
cost and energy of per-link rate adaptation vs fixed-rate baselines,
swept over channel quality (ISSUE 4).

Every point packs a ``PhySweepSpec``: the per-(src WI, dst WI) SNR map
(path loss from WI placement + seeded shadowing) selects a rate per link
under one of three policies —

  adaptive   the "engineer the channel and adapt to it" per-link pick
             (fastest rate whose expected retransmissions keep goodput
             ahead; Timoneda et al. 2019),
  fixed:0    the paper's 16 Gbps everywhere (aggressive: retransmits and
             drops on weak links),
  fixed:-1   4 Gbps everywhere (conservative: reliable but slow)

— and the engines run CRC-checked ARQ over the resulting PER table.
The grid is channel quality (link budget dB) x policy x all three
fabrics, in ONE batched launch.

Hard checks (the run fails loudly if any is violated):

1. **adaptive goodput >= both fixed policies at every quality point**,
   measured as ``wl_air_eff`` — delivered payload flits per cycle of
   channel occupancy (with a 2% sampling margin where the policies
   nearly coincide).  Air efficiency is the *policy-attributable*
   goodput: the per-packet CRC outcome of a given (packet, link, rate)
   is a fixed hash, so this ratio isolates the rate choice.  Wall-clock
   goodput additionally bakes in arbitration/queueing chaos — two runs
   differing in two links' rates reshuffle every interleaving — and is
   therefore gated in aggregate:
2. **summed over the quality sweep, adaptive wall-clock goodput beats
   both fixed policies** (the margins are tens of percent; measured
   per-point values are reported as data).
3. **wireline fabrics are unaffected**: every substrate/interposer
   metric must be bit-identical across the three policies.

Living-channel extension (ISSUE 6): a second sweep ages the channel —
``drift_amp_db`` scales a seeded per-link thermal-cycle SNR walk — and
compares, at every drift amplitude,

  online     per-window in-scan rate re-selection (``reselect=True``),
  static     the one-shot host selection left alone while the channel
             drifts underneath it,
  fixed:0 / fixed:-1   the rate-blind baselines

with the hard ordering **online >= static >= every fixed** on air
efficiency at every amplitude.  A fig7-style one-shot multicast
all-reduce trace also runs over the lossy channel — broadcast ARQ
(worst-member group retransmission) replaced the old "multicast tables
rejected" guard, and the trace must complete with nothing dropped.

Output lands in ``BENCH_fig9_phy.json`` (CI artifact).  ``FIG9_SMOKE=1``
shrinks the grid for CI wall-clock (one drift amplitude and the
broadcast-ARQ trace are always kept).
"""
import json
import os

from repro.core.constants import DEFAULT_PHY, Fabric, SimParams
from repro.core.sweep import SweepPoint, run_sweep_batched
from repro.phy import PhySweepSpec

from benchmarks.common import FABRICS, emit

JSON_PATH = "BENCH_fig9_phy.json"
SMOKE = bool(os.environ.get("FIG9_SMOKE"))
BUDGETS_DB = [15.0, 19.0] if SMOKE else [13.0, 15.0, 17.0, 19.0, 22.0, 26.0]
POLICIES = ("adaptive", "fixed:0", "fixed:-1")
LOAD = 0.5
SIM = SimParams(cycles=1500 if SMOKE else 6000,
                warmup=300 if SMOKE else 1000)
N_CHIPS, N_MEM = 4, 4
# living-channel sweep: aging amplitude (dB) x selection arm at one
# mid-sweep link budget
DRIFT_BUDGET_DB = 19.0
DRIFT_AMPS_DB = [4.0] if SMOKE else [0.0, 2.0, 4.0, 6.0]
DRIFT_ARMS = ("online", "static", "fixed:0", "fixed:-1")


def _drift_spec(arm: str, amp: float) -> PhySweepSpec:
    policy = "adaptive" if arm in ("online", "static") else arm
    return PhySweepSpec(link_budget_db=DRIFT_BUDGET_DB, policy=policy,
                        drift_amp_db=amp, reselect=(arm == "online"))


def _mc_trace_lossy(rec: dict) -> bool:
    """fig7 one-shot multicast all-reduce over the lossy channel.

    Before ISSUE 6 this configuration raised at pack time ("multicast
    tables rejected"); now broadcast ARQ carries it.  The trace must
    close every phase barrier (no wedge) and deliver every payload (no
    silent drops at this budget).
    """
    from repro.core import simulator, traffic
    from repro.core.metrics import compute_metrics
    from repro.core.routing import compute_routing
    from repro.core.topology import build_xcym
    from repro.workloads.mapping import DeviceMap
    from repro.workloads.schedules import expand_collective
    from repro.workloads.trace import Trace

    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    dm = DeviceMap(topo, 16)
    phases = expand_collective("all-reduce", 512.0, 16, dm,
                               schedule="oneshot", label="ar")
    tt = traffic.from_trace(topo, Trace("oneshot-ar", 16, phases),
                            DEFAULT_PHY.pkt_flits)
    sim = SimParams(cycles=8000, warmup=0)
    spec = PhySweepSpec(link_budget_db=22.0, max_retx=3)
    ps = simulator.pack(topo, rt, tt, DEFAULT_PHY, sim, phy_spec=spec)
    st = simulator.run(ps)
    m = compute_metrics(ps, st, "fig7-oneshot-ar/phy", 0.0)
    ok = m.trace_done and m.wl_dropped_payload == 0
    emit(f"fig9.mc_trace,oneshot-ar@22dB,phases={m.phases_done}/"
         f"{m.n_phases},dropped_payload={m.wl_dropped_payload},"
         f"retx={m.wl_nacks},{ok}")
    rec["mc_trace_phases_done"] = m.phases_done
    rec["mc_trace_n_phases"] = m.n_phases
    rec["mc_trace_dropped_payload"] = m.wl_dropped_payload
    rec["mc_trace_done"] = bool(ok)
    return ok


def main(json_path: str = JSON_PATH) -> None:
    points, meta = [], []
    for budget in BUDGETS_DB:
        for pol in POLICIES:
            for fab in FABRICS:
                points.append(SweepPoint(
                    N_CHIPS, N_MEM, fab, load=LOAD, p_mem=0.2, sim=SIM,
                    phy_spec=PhySweepSpec(link_budget_db=budget,
                                          policy=pol)))
                meta.append((budget, pol, fab))
    ms = run_sweep_batched(points)
    by = {m: r for m, r in zip(meta, ms)}

    emit("fig9,point,budget_db,policy,throughput,goodput_gbps,air_eff,"
         "retx_rate,dropped,retx_energy_share,pj_bit,rate_hist")
    rec: dict = {"grid_points": len(points), "cycles": SIM.cycles,
                 "budgets_db": BUDGETS_DB, "load": LOAD}
    for (budget, pol, fab), m in zip(meta, ms):
        hist = ";".join(f"{k}:{v}" for k, v in m.wl_rate_hist.items())
        emit(f"fig9,{m.name},{budget},{pol},{m.throughput:.4f},"
             f"{m.wl_goodput_gbps:.1f},{m.wl_air_eff:.4f},"
             f"{m.wl_retx_rate:.3f},{m.wl_dropped},"
             f"{m.retx_energy_share:.3f},{m.energy_pj_bit:.2f},{hist}")
        if fab == Fabric.WIRELESS:
            key = f"b{budget:g}_{pol}"
            rec[key + "_goodput_gbps"] = m.wl_goodput_gbps
            rec[key + "_air_eff"] = m.wl_air_eff
            rec[key + "_throughput"] = m.throughput
            rec[key + "_retx_rate"] = m.wl_retx_rate
            rec[key + "_dropped"] = m.wl_dropped
            rec[key + "_pj_bit"] = m.energy_pj_bit

    # hard check 1: per-link adaptation dominates both fixed policies at
    # every channel-quality point on air efficiency (see docstring)
    adapt_ok = True
    agg = {pol: 0.0 for pol in POLICIES}
    for budget in BUDGETS_DB:
        ma = by[(budget, "adaptive", Fabric.WIRELESS)]
        agg["adaptive"] += ma.wl_goodput_gbps
        for pol in POLICIES[1:]:
            mf = by[(budget, pol, Fabric.WIRELESS)]
            agg[pol] += mf.wl_goodput_gbps
            ok = ma.wl_air_eff >= mf.wl_air_eff * 0.98
            adapt_ok &= ok
            emit(f"fig9.check,adaptive_air_eff_ge_{pol},budget={budget},"
                 f"{ma.wl_air_eff:.4f}>={mf.wl_air_eff:.4f},{ok}")
    # hard check 2: summed over the sweep, wall-clock goodput too
    agg_ok = all(agg["adaptive"] >= agg[pol] for pol in POLICIES[1:])
    emit(f"fig9.check,adaptive_aggregate_goodput,"
         f"{agg['adaptive']:.0f}>=max({agg['fixed:0']:.0f},"
         f"{agg['fixed:-1']:.0f}),{agg_ok}")
    rec["aggregate_goodput_gbps"] = {k: round(v, 1) for k, v in agg.items()}

    # hard check 3: the PHY is a wireless subsystem — wireline fabrics
    # must be bit-identical across policies
    wired_ok = True
    for budget in BUDGETS_DB:
        for fab in (Fabric.SUBSTRATE, Fabric.INTERPOSER):
            base = by[(budget, POLICIES[0], fab)]
            for pol in POLICIES[1:]:
                m = by[(budget, pol, fab)]
                wired_ok &= (m.flits_delivered == base.flits_delivered
                             and m.avg_pkt_latency == base.avg_pkt_latency
                             and m.avg_pkt_energy_pj
                             == base.avg_pkt_energy_pj)
    emit(f"fig9.check,adaptive_goodput_dominates,{adapt_ok}")
    emit(f"fig9.check,wireline_unaffected,{wired_ok}")
    rec["adaptive_dominates"] = bool(adapt_ok)
    rec["aggregate_dominates"] = bool(agg_ok)
    rec["wireline_unaffected"] = bool(wired_ok)

    # ---- living-channel sweep (ISSUE 6): drift amplitude x selection arm
    dpoints, dmeta = [], []
    for amp in DRIFT_AMPS_DB:
        for arm in DRIFT_ARMS:
            dpoints.append(SweepPoint(
                N_CHIPS, N_MEM, Fabric.WIRELESS, load=LOAD, p_mem=0.2,
                sim=SIM, phy_spec=_drift_spec(arm, amp)))
            dmeta.append((amp, arm))
    dms = run_sweep_batched(dpoints)
    dby = {m: r for m, r in zip(dmeta, dms)}
    emit("fig9.drift,point,amp_db,arm,air_eff,goodput_gbps,resel,"
         "retx_rate,pj_bit,rate_hist")
    for (amp, arm), m in zip(dmeta, dms):
        hist = ";".join(f"{k}:{v}" for k, v in m.wl_rate_hist.items())
        emit(f"fig9.drift,{m.name},{amp},{arm},{m.wl_air_eff:.4f},"
             f"{m.wl_goodput_gbps:.1f},{m.wl_resel},{m.wl_retx_rate:.3f},"
             f"{m.energy_pj_bit:.2f},{hist}")
        key = f"drift{amp:g}_{arm}"
        rec[key + "_air_eff"] = m.wl_air_eff
        rec[key + "_goodput_gbps"] = m.wl_goodput_gbps
        rec[key + "_resel"] = m.wl_resel
    # hard check 4, at EVERY drift amplitude (same 2% sampling margin as
    # check 1): online re-selection >= the static one-shot pick AND >=
    # both fixed rates — tracking the channel never loses to any frozen
    # policy.  The static pick must also keep beating fixed:0 (both
    # commit to window-0 information; the adaptive mix degrades more
    # gracefully than the greedy fastest rate).  static vs fixed:-1 is
    # deliberately NOT ordered: at large amplitudes the stale pick loses
    # to max-robustness — that decay is the figure's motivation for
    # in-scan re-selection, not a regression.
    drift_ok = True
    for amp in DRIFT_AMPS_DB:
        mo = dby[(amp, "online")]
        mst = dby[(amp, "static")]
        ok = mo.wl_air_eff >= mst.wl_air_eff * 0.98
        drift_ok &= ok
        emit(f"fig9.check,online_air_eff_ge_static,amp={amp},"
             f"{mo.wl_air_eff:.4f}>={mst.wl_air_eff:.4f},{ok}")
        for arm in ("fixed:0", "fixed:-1"):
            mf = dby[(amp, arm)]
            ok = mo.wl_air_eff >= mf.wl_air_eff * 0.98
            drift_ok &= ok
            emit(f"fig9.check,online_air_eff_ge_{arm},amp={amp},"
                 f"{mo.wl_air_eff:.4f}>={mf.wl_air_eff:.4f},{ok}")
        mf0 = dby[(amp, "fixed:0")]
        ok = mst.wl_air_eff >= mf0.wl_air_eff * 0.98
        drift_ok &= ok
        emit(f"fig9.check,static_air_eff_ge_fixed:0,amp={amp},"
             f"{mst.wl_air_eff:.4f}>={mf0.wl_air_eff:.4f},{ok}")
    rec["drift_ordering_holds"] = bool(drift_ok)

    # ---- broadcast ARQ over the living channel (ISSUE 6)
    mc_ok = _mc_trace_lossy(rec)
    with open(json_path, "w") as f:
        json.dump({k: round(v, 4) if isinstance(v, float) else v
                   for k, v in rec.items()}, f, indent=1, sort_keys=True)
    emit(f"fig9,json,{json_path}")
    if not adapt_ok:
        raise SystemExit(
            "fig9: adaptive air efficiency fell below a fixed-rate policy")
    if not agg_ok:
        raise SystemExit(
            "fig9: adaptive aggregate goodput fell below a fixed policy")
    if not wired_ok:
        raise SystemExit("fig9: a wireline fabric was affected by the PHY")
    if not drift_ok:
        raise SystemExit(
            "fig9: online re-selection lost to a frozen policy (or the "
            "static pick to fixed:0) under drift")
    if not mc_ok:
        raise SystemExit(
            "fig9: the one-shot multicast all-reduce did not complete "
            "cleanly over the lossy channel")


if __name__ == "__main__":
    main()
