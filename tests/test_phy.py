"""Lossy-channel PHY property tests (ISSUE 4).

Three layers, matching the subsystem's structure:

- host math (``phy.channel`` / ``phy.rates``): BER monotone in distance
  and non-increasing in rate robustness; PER in [0, 1]; adaptive
  selection never expects less goodput than any fixed rate.
- CRC/ARQ reference (``phy.retx``): the deterministic hash agrees
  between numpy and jax, outcomes are monotone in link quality, and the
  per-packet attempt prediction matches the bounded-ARQ definition.
- engines: retransmission counts conserve packets (injected air
  crossings == delivered + in-flight + dropped-at-max-retx, predicted
  exactly by the host reference), and ``phy_spec=None`` points are
  byte-identical to the committed goldens (the phy-off program is the
  pre-PHY program).
"""
import json
import pathlib

import numpy as np
import pytest

try:  # the property subset needs hypothesis; engine tests run regardless
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                       # pragma: no cover
    HAVE_HYP = False

from repro.core.constants import DEFAULT_PHY, Fabric, SimParams  # noqa: E402
from repro.core.topology import build_xcym  # noqa: E402
from repro.phy import (DEFAULT_RATE_TABLE, ChannelParams, PhySweepSpec,
                       crc_fail, crc_hash, link_tables, reference_attempts,
                       select_rates)  # noqa: E402
from repro.phy.channel import ber_from_snr, link_snr_db, per_packet  # noqa: E402
from repro.phy.rates import expected_goodput, rate_per_matrix  # noqa: E402


# ------------------------------------------- host math (hypothesis subset)

if HAVE_HYP:
    @given(st.floats(0.5, 60.0), st.floats(1.0, 4.0),
           st.floats(0.0, 30.0))
    def test_ber_monotone_in_distance(d_mm, gain, budget):
        """Farther links (lower SNR) never have lower BER."""
        ch = ChannelParams(sigma_shadow_db=0.0)
        snr_near = budget - ch.pl_exp * 10 * np.log10(max(d_mm, ch.d0_mm))
        snr_far = budget - ch.pl_exp * 10 * np.log10(
            max(d_mm * 2, ch.d0_mm))
        assert ber_from_snr(snr_far, gain) \
            >= ber_from_snr(snr_near, gain) - 1e-18

    @given(st.floats(-10.0, 30.0))
    def test_ber_nonincreasing_in_robustness(snr_db):
        """More robust (higher-gain, slower) rates never have higher BER."""
        bers = [float(ber_from_snr(snr_db, e.gain))
                for e in DEFAULT_RATE_TABLE]
        assert all(b2 <= b1 + 1e-18 for b1, b2 in zip(bers, bers[1:]))

    @given(st.floats(-10.0, 30.0), st.integers(64, 4096))
    def test_per_is_probability(snr_db, bits):
        p = per_packet(ber_from_snr(snr_db, 1.0), bits)
        assert 0.0 <= p <= 1.0

    @given(st.integers(0, 2**31 - 1), st.integers(0, 10),
           st.integers(0, 2**16 - 1), st.integers(0, 2**16 - 1))
    def test_crc_outcomes_monotone_in_link_quality(uid, att, q1, q2):
        """Lowering PER only turns failures into passes (same draw)."""
        lo, hi = sorted((q1, q2))
        f_lo = bool(crc_fail(1, uid, att, np.int32(lo)))
        f_hi = bool(crc_fail(1, uid, att, np.int32(hi)))
        assert (not f_lo) or f_hi

    @given(st.integers(0, 2**20), st.integers(0, 2**16 - 1),
           st.integers(1, 6))
    @settings(max_examples=50)
    def test_reference_attempts_definition(uid, perq, max_retx):
        att, deliv = reference_attempts(5, uid, perq, max_retx)
        att, deliv = int(att), bool(deliv)
        fails = [bool(crc_fail(5, uid, a, np.int32(perq)))
                 for a in range(max_retx)]
        if deliv:
            assert fails[:att - 1] == [True] * (att - 1) \
                and not fails[att - 1]
        else:
            assert att == max_retx and all(fails)


def test_ber_monotone_grid():
    """Deterministic fallback for the monotonicity properties."""
    d = np.linspace(0.5, 60.0, 200)
    ch = ChannelParams(sigma_shadow_db=0.0)
    for gain in (1.0, 2.0, 4.0):
        snr = 20.0 - ch.pl_exp * 10 * np.log10(np.maximum(d, ch.d0_mm))
        ber = ber_from_snr(snr, gain)
        assert (np.diff(ber) >= -1e-18).all()
    snr = np.linspace(-10, 30, 200)
    prev = None
    for e in DEFAULT_RATE_TABLE:
        ber = ber_from_snr(snr, e.gain)
        assert ((ber >= 0) & (ber <= 0.5)).all()
        if prev is not None:
            assert (ber <= prev + 1e-18).all()
        prev = ber


def test_adaptive_selection_dominates_fixed_in_expectation():
    """The per-link pick maximizes expected goodput over table entries.

    Selection argmaxes the ``GP_SCALE``-quantized goodput integers (the
    same integers the in-scan re-selection uses, so the two picks agree
    bitwise), so no fixed entry can beat the pick by more than one
    quantization step."""
    from repro.phy.rates import GP_SCALE
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    for budget in (12.0, 16.0, 20.0, 26.0):
        snr = link_snr_db(topo, PhySweepSpec(link_budget_db=budget))
        per_r = rate_per_matrix(snr, 2048)
        gp = expected_goodput(per_r)
        idx = select_rates(per_r)
        ii, jj = np.meshgrid(*(np.arange(n) for n in idx.shape),
                             indexing="ij")
        chosen = gp[idx, ii, jj]
        assert (chosen >= gp.max(axis=0) - 1.0 / GP_SCALE).all()


def test_link_tables_wireline_is_none():
    topo = build_xcym(4, 4, Fabric.INTERPOSER)
    assert link_tables(topo, DEFAULT_PHY, PhySweepSpec()) is None


def test_link_tables_deterministic():
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    a = link_tables(topo, DEFAULT_PHY, PhySweepSpec(seed=3))
    b = link_tables(topo, DEFAULT_PHY, PhySweepSpec(seed=3))
    c = link_tables(topo, DEFAULT_PHY, PhySweepSpec(seed=4))
    assert np.array_equal(a.perq, b.perq) and np.array_equal(a.serv, b.serv)
    assert not np.array_equal(a.perq, c.perq)


# ------------------------------------------------------------ CRC reference

def test_crc_hash_numpy_jax_agree():
    jnp = pytest.importorskip("jax.numpy")
    uid = np.arange(512, dtype=np.int32)
    att = np.repeat(np.arange(8, dtype=np.int32), 64)
    hn = np.asarray(crc_hash(9, uid, att))
    hj = np.asarray(crc_hash(jnp.uint32(9), jnp.asarray(uid),
                             jnp.asarray(att)))
    assert np.array_equal(hn, hj)


# ----------------------------------------------------------------- engines

def _lossy_state(budget, policy="adaptive", cycles=600, load=0.5,
                 max_retx=3, seed=2, birth_cycles=None):
    from repro.core import simulator, traffic
    from repro.core.routing import compute_routing
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    sim = SimParams(cycles=cycles, warmup=0)
    tt = traffic.uniform_random(topo, load, 0.3, birth_cycles or cycles,
                                64, seed=seed)
    spec = PhySweepSpec(link_budget_db=budget, policy=policy,
                        max_retx=max_retx)
    ps = simulator.pack(topo, rt, tt, DEFAULT_PHY, sim, phy_spec=spec)
    return ps, simulator.run(ps)


def _phantom_flits(ps, stt):
    """Flits mid-flight inside a doomed (CRC-failing) air attempt.

    A failing attempt's flits leave the sender's occupancy as they are
    transmitted but never enter the receiver pipe; until the tail NACK
    rewinds the sender they are accounted nowhere.  The CRC hash makes
    them host-predictable from the final state.
    """
    src = np.asarray(stt.pkt_src)
    act_wl = (src >= 0) & np.asarray(stt.out_is_wl)
    if not act_wl.any():
        return 0
    ss = ps.ss
    ws = np.clip(np.asarray(ss.b_wi), 0, len(np.asarray(ss.wl_perq)) - 1)
    wd = np.clip(np.asarray(stt.out_wo), 0, 15)
    perq = np.asarray(ss.wl_perq)[ws[:, None], wd]
    uid = np.clip(src, 0, None) * 65536 + np.asarray(stt.pkt_idx)
    fail = np.asarray(crc_fail(int(ps.phy_link.spec.seed), uid,
                               np.asarray(stt.attempt), perq))
    return int(np.where(act_wl & fail, np.asarray(stt.sent), 0).sum())


def test_packet_conservation_with_drops():
    """Injected == delivered + in-flight + in-doomed-attempt + dropped."""
    from repro.core.metrics import inflight_flits
    ps, stt = _lossy_state(15.0, cycles=700, max_retx=2)
    dropped_flits = int(stt.pkts_dropped) * DEFAULT_PHY.pkt_flits
    # a dropped packet's flits vanish at its sender WI buffer; everything
    # else is ejected, in a buffer/pipe, or mid-way through an attempt
    # the CRC already doomed
    assert int(stt.flits_inj) == int(stt.flits_del) \
        + inflight_flits(stt) + _phantom_flits(ps, stt) + dropped_flits
    assert int(stt.pkts_dropped) > 0          # the point exercised drops


def test_packet_conservation_at_drain():
    """With the network drained the identity needs no phantom term."""
    from repro.core.metrics import inflight_flits
    ps, stt = _lossy_state(15.0, cycles=4000, load=0.1, max_retx=2,
                           birth_cycles=900, seed=9)
    assert inflight_flits(stt) == 0
    assert int(stt.flits_inj) == int(stt.flits_del) \
        + int(stt.pkts_dropped) * DEFAULT_PHY.pkt_flits
    assert int(stt.pkts_dropped) > 0


def test_attempt_counters_match_host_reference():
    """Engine NACK/drop/attempt totals == the host ARQ prediction, exactly.

    The CRC outcome of every (packet, attempt) is a deterministic hash
    and the air link every packet uses is fixed by routing, so once the
    network fully drains, the engine's counters must equal
    ``reference_attempts`` summed over the packets that cross the air.
    """
    from repro.core.metrics import inflight_flits
    max_retx = 3
    ps, stt = _lossy_state(16.0, cycles=4000, load=0.1, max_retx=max_retx,
                           seed=6, birth_cycles=900)
    assert inflight_flits(stt) == 0, "network must drain for exact totals"
    topo, rt, ss = ps.topo, ps.rt, ps.ss
    qh = np.asarray(stt.q_head)
    bt = np.asarray(ss.births)
    for n in range(bt.shape[0]):      # every generated packet was injected
        assert (bt[n, qh[n]:] == np.int32(2**31 - 1)).all()
    Lw, Wp = topo.n_links, len(topo.wl_pairs)
    births = np.asarray(ss.births)
    dests = np.asarray(ss.dests)
    src_sw = np.asarray(ss.src_switch)
    # every born packet was injected (the run drained); find its air link
    # by walking the routing tables host-side
    nacks = drops = crossings = 0
    N, K = births.shape
    for n in range(N):
        for k in range(K):
            if births[n, k] == np.int32(2**31 - 1):
                continue
            cur, dst = int(src_sw[n]), int(dests[n, k])
            for _ in range(64):
                if cur == dst:
                    break
                o = int(rt.next_out[cur, dst])
                if Lw <= o < Lw + Wp:
                    ws, wd = (int(x) for x in topo.wl_pairs[o - Lw])
                    uid = n * 65536 + k
                    att, deliv = reference_attempts(
                        int(ps.phy_link.spec.seed), uid,
                        int(ps.phy_link.perq[ws, wd]), max_retx)
                    crossings += 1
                    nacks += int(att) - int(deliv)
                    drops += int(~deliv)
                    cur = int(topo.wi_switch[wd])
                else:
                    cur = int(topo.link_dst[o])
    assert crossings > 0 and nacks > 0
    assert int(stt.wl_nacks) == nacks
    assert int(stt.pkts_dropped) == drops
    assert int(stt.wl_pkts) == crossings - drops
    # failing attempts always transmit whole packets (store-and-forward)
    plen = DEFAULT_PHY.pkt_flits
    fail = np.asarray(stt.wl_fail_flits)
    assert (fail % plen == 0).all()
    assert int(fail.sum()) == nacks * plen


def test_phy_off_points_byte_identical_to_goldens():
    """phy_spec=None runs the exact pre-PHY program: the committed
    goldens (generated before this subsystem existed) must match under
    the golden rule -- integer counters exact, derived floats at
    rel=1e-6."""
    from repro.core.sweep import run_point
    gdir = pathlib.Path(__file__).parent / "goldens"
    golden = json.loads((gdir / "wireless_4c4m_load02.json").read_text())
    m = run_point(n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, load=0.2,
                  p_mem=0.2, phy_spec=None,
                  sim=SimParams(cycles=1500, warmup=300, seed=0))
    want = golden["metrics"]
    assert m.pkts_delivered == want["pkts_delivered"]
    assert m.flits_delivered == want["flits_delivered"]
    assert m.flits_injected == want["flits_injected"]
    # the energy is an f32 sum reduced on the device; its last bits
    # depend on the backend's reduction order
    assert m.avg_pkt_energy_pj == pytest.approx(want["avg_pkt_energy_pj"],
                                                rel=1e-6)
    assert m.avg_pkt_latency == pytest.approx(want["avg_pkt_latency"],
                                              rel=1e-6)


def test_wireline_ignores_phy_spec():
    """A PhySweepSpec on a wireline fabric changes nothing, bitwise."""
    from repro.core.sweep import run_point
    sim = SimParams(cycles=800, warmup=200, seed=1)
    kw = dict(n_chips=4, n_mem=4, fabric=Fabric.INTERPOSER, load=0.4,
              p_mem=0.2, sim=sim)
    a = run_point(**kw)
    b = run_point(phy_spec=PhySweepSpec(link_budget_db=10.0), **kw)
    assert a.flits_delivered == b.flits_delivered
    assert a.avg_pkt_latency == b.avg_pkt_latency
    assert a.avg_pkt_energy_pj == b.avg_pkt_energy_pj


def test_adaptive_goodput_beats_fixed():
    """The fig9 invariant at one point: adaptive air efficiency
    (delivered payload per cycle of channel occupancy — the
    policy-attributable goodput) >= both fixed policies."""
    out = {}
    for pol in ("adaptive", "fixed:0", "fixed:-1"):
        ps, stt = _lossy_state(17.0, policy=pol, cycles=800, seed=4)
        pf = np.asarray(stt.wl_pair_flits, np.float64)
        ff = np.asarray(stt.wl_fail_flits, np.float64)
        out[pol] = (pf - ff).sum() / max((pf * ps.phy_link.serv).sum(), 1.0)
    assert out["adaptive"] >= out["fixed:0"] * 0.98
    assert out["adaptive"] >= out["fixed:-1"] * 0.98


def test_clean_channel_has_no_retx():
    ps, stt = _lossy_state(40.0, cycles=500)
    assert int(stt.wl_nacks) == 0 and int(stt.pkts_dropped) == 0
    assert int(stt.wl_pkts) > 0


def test_closed_loop_drops_release_window_and_reply_channel():
    """ARQ drops under closed-loop memory leak nothing: the requester's
    max_outstanding credit comes back on the drop and the dropped
    request's tombstoned reply slot is skipped by the stack's in-order
    reply channel — after the births stop, every window drains to zero
    and no reply row wedges behind a dead slot."""
    from repro.core import simulator
    from repro.core.routing import compute_routing
    from repro.memory import DramTimingParams, closed_loop_uniform
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    dram = DramTimingParams(max_outstanding=4)
    tt = closed_loop_uniform(topo, 0.15, 800, DEFAULT_PHY.pkt_flits,
                             dram=dram, seed=3)
    sim = SimParams(cycles=8000, warmup=0)
    spec = PhySweepSpec(link_budget_db=14.0, max_retx=2)
    ps = simulator.pack(topo, rt, tt, DEFAULT_PHY, sim, phy_spec=spec)
    stt = simulator.run(ps)
    assert int(stt.pkts_dropped) > 0          # drops happened
    assert bool(np.asarray(stt.dead).any())   # including dropped requests
    # all windows fully credited back; no slot still active
    assert (np.asarray(stt.outst) == 0).all()
    assert (np.asarray(stt.pkt_src) < 0).all()
    # every reply row consumed its whole queue (tombstones skipped)
    qh = np.asarray(stt.q_head)
    bt = np.asarray(ps.ss.births)
    rdy = np.asarray(stt.rdy)
    dead = np.asarray(stt.dead)
    NO = np.int32(2**31 - 1)
    live = (bt != NO) | (rdy != NO) | dead
    for n in range(bt.shape[0]):
        assert not live[n, qh[n]:].any(), f"row {n} wedged at {qh[n]}"
