"""The reference's metrics of one lane, reduced with plain numpy from its
final state, by the definitions of arXiv:1709.07529 §IV.

- throughput: flits delivered per cycle per core over the measured
  window, the cycles after warm-up; bandwidth per core is that times the
  flit's bits and the clock.
- average packet latency: birth to tail ejection, over packets born after
  warm-up.
- energy: every link traversal pays its link's energy per bit, every
  switch traversal the switch's, every control packet of the wireless MAC
  its flits at the wireless energy per bit, and every receiver its idle
  or sleep power per cycle; over the lossy channel every wireless flit
  attempt pays its rate's energy per bit instead.  Average packet energy
  is the total over packets delivered, energy per bit the total over bits
  delivered.

Only open-loop points with no trace phases are covered: the benchmark's
configurations have neither memory round trips nor phases.  Arithmetic is
in float64, or in ``dtype`` (the control's lower precision).
"""
from __future__ import annotations

import numpy as np


def lane_metrics(ps, st: dict, offered_load: float,
                 dtype=np.float64) -> dict:
    """The lane's metrics, by the names the program's ``Metrics`` uses."""
    if ps.mem_on or int(np.asarray(ps.ss.phase_need).sum()):
        raise ValueError("the reference metrics cover open-loop points only")

    def f(x):
        return np.asarray(x, np.float64).astype(dtype)

    phy = ps.phy
    bits = phy.flit_bits
    window = int(st["cycles_run"]) - ps.sim.warmup
    flits = int(st["flits_del"])
    pkts = int(st["pkts_del"])
    lat_pkts = int(st["lat_pkts"])

    links = (f(st["counts_into"]) * f(ps.ss.b_epb)).sum() * f(bits)
    switch = f(st["count_switch"]) * f(bits) * f(phy.e_switch_pj_bit)
    ctrl = f(st["ctrl_count"]) * f(phy.ctrl_packet_flits * bits) \
        * f(phy.e_wireless_pj_bit)
    rx = f(st["awake_cycles"]) * f(phy.rx_idle_pj_cycle) \
        + f(st["sleep_cycles"]) * f(phy.rx_sleep_pj_cycle)
    breakdown = {"links": float(links), "switch": float(switch),
                 "ctrl": float(ctrl), "rx": float(rx)}
    energy = links + switch + ctrl + rx

    out = {}
    pl = ps.phy_link
    if pl is not None:
        if ps.drift_on or ps.reselect:
            # the link's rate moves from window to window: each attempt
            # is counted under the rate entry that carried it
            att = np.asarray(st["wl_rate_flits"], np.int64)
            fail = np.asarray(st["wl_rate_fail"], np.int64)
            wl = (f(att) * f(pl.epb_r)).sum() * f(bits)
            wl_fail = (f(fail) * f(pl.epb_r)).sum() * f(bits)
            air = (f(att) * f(pl.serv_r)).sum()
            hist = {e.name: int(att[r] - fail[r])
                    for r, e in enumerate(pl.table) if att[r] > fail[r]}
        else:
            att = np.asarray(st["wl_pair_flits"], np.int64)
            fail = np.asarray(st["wl_fail_flits"], np.int64)
            wl = (f(att) * f(pl.epb)).sum() * f(bits)
            wl_fail = (f(fail) * f(pl.epb)).sum() * f(bits)
            air = (f(att) * f(pl.serv)).sum()
            hist = {}
            for r, e in enumerate(pl.table):
                n = int(((att - fail) * (pl.rate_idx == r)).sum())
                if n:
                    hist[e.name] = n
        delivered = int((att - fail).sum())
        energy = energy + wl
        breakdown["wl"] = float(wl)
        wl_pkts = int(st["wl_pkts"])
        out.update(
            wl_goodput_gbps=float(f(st["wl_rx_flits"]) * f(bits)
                                  * f(phy.clock_ghz) / f(window)),
            wl_air_cycles=float(air),
            wl_air_eff=float(f(delivered) / max(air, f(1))),
            wl_retx_rate=int(st["wl_nacks"]) / max(wl_pkts, 1),
            wl_pkts=wl_pkts, wl_nacks=int(st["wl_nacks"]),
            wl_dropped=int(st["pkts_dropped"]),
            wl_dropped_payload=int(st["wl_drop_flits"]),
            wl_rate_hist=hist, wl_resel=int(st["wl_resel"]),
            retx_energy_share=float(wl_fail / max(wl, f(1e-12))))

    thr = f(flits) / f(window) / f(ps.n_cores)
    out.update(
        offered_load=offered_load,
        throughput=float(thr),
        bw_gbps_core=float(thr * f(bits) * f(phy.clock_ghz)),
        avg_pkt_latency=(float(f(st["lat_sum"]) / f(lat_pkts)) if lat_pkts
                         else float("nan")),
        avg_pkt_energy_pj=float(energy / f(max(pkts, 1))),
        energy_pj_bit=float(energy / f(max(flits * bits, 1))),
        pkts_delivered=pkts, flits_delivered=flits,
        flits_injected=int(st["flits_inj"]),
        energy_breakdown=breakdown,
        wl_tx_flits=int(st["wl_tx_flits"]), wl_rx_flits=int(st["wl_rx_flits"]),
        cycles_run=int(st["cycles_run"]))
    return out
