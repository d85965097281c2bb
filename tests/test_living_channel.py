"""Broadcast ARQ + living-channel tests (ISSUE 6).

Four layers:

- trace accounting: ARQ-exhausted drops credit the phase barrier (once
  per group member), so a drop-heavy trace *completes and drains early*
  instead of wedging — while the metrics still report the loss
  (``trace_done`` is False, ``wl_dropped_payload`` > 0).  This is the
  silent-data-loss regression pin: before ISSUE 6 the same point ran its
  whole cycle budget with ``cur_phase`` stuck and reported a "finished"
  trace.
- host math (``phy.living``): the seeded thermal-cycle walk is a unit
  offset (symmetric, deterministic, exactly its knots every
  ``drift_period`` windows) and drifted link quality is monotone in the
  aging amplitude ``drift_amp_db``.
- broadcast CRC: the group outcome (threshold = max over member PERs,
  same hash draw) fails whenever any member copy individually fails —
  the all-or-nothing group NACK is sound.
- engines: on a *static* channel, in-scan re-selection is a bitwise
  no-op — the window argmax re-derives the host pick from the same
  quantized integers, so turning ``reselect`` on changes nothing but
  the program shape.
"""
import numpy as np
import pytest

try:  # the property subset needs hypothesis; the rest runs regardless
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                       # pragma: no cover
    HAVE_HYP = False

import jax.numpy as jnp

from repro.core import simulator, traffic
from repro.core.constants import DEFAULT_PHY, Fabric, SimParams
from repro.core.routing import compute_routing
from repro.core.sweep import SweepPoint, run_sweep_batched
from repro.core.topology import build_xcym
from repro.phy import PhySweepSpec, crc_fail, drift_unit, window_tables
from repro.phy.rates import (SNR_Q, drift_amp_q, link_tables, per_q,
                             rate_per_matrix)
from repro.workloads.trace import Trace, mcast, p2p, phase

_TRACE = Trace("living", 8, [
    phase([mcast(0, (2, 3, 4, 5, 6, 7), 2048.0),
           mcast(4, (0, 1, 2, 3), 1024.0)], label="c0:all-reduce"),
    phase([p2p(1, 6, 512.0), p2p(6, 1, 512.0)], label="c1:permute"),
    phase([mcast(2, (0, 6), 512.0), mcast(5, (0, 1, 6, 7), 512.0)],
          label="c2:bcast"),
])


# ------------------------------------------------- drop-credited barriers

def test_arq_exhausted_drops_credit_phase_barrier():
    """A drop-heavy multicast trace completes, drains early, and the
    metrics say so honestly: every phase closed (drops credit the
    barrier once per group member), the engine froze before the cycle
    budget, and ``trace_done`` refuses to call the run complete because
    payload was lost on the air."""
    [m] = run_sweep_batched([SweepPoint(
        n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, trace=_TRACE,
        sim=SimParams(cycles=20000, warmup=0),
        phy_spec=PhySweepSpec(link_budget_db=13.0, max_retx=2))])
    assert m.wl_dropped > 0, "the point must exercise ARQ exhaustion"
    assert m.wl_dropped_payload > 0
    assert m.phases_done == m.n_phases > 0       # barrier credited
    assert 0 < m.drain_cycle < 20000             # early drain, no wedge
    assert not m.trace_done                      # ... but not "done"


def test_clean_channel_trace_is_done():
    """Same trace, clean channel: no drops, and ``trace_done`` holds."""
    [m] = run_sweep_batched([SweepPoint(
        n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, trace=_TRACE,
        sim=SimParams(cycles=4000, warmup=0),
        phy_spec=PhySweepSpec(link_budget_db=30.0))])
    assert m.wl_dropped == 0 and m.wl_dropped_payload == 0
    assert m.phases_done == m.n_phases > 0
    assert m.trace_done


# ------------------------------------------------- host math (drift walk)

def _living_static(drift_amp=4.0, seed=2):
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    sim = SimParams(cycles=256, warmup=0)
    tt = traffic.uniform_random(topo, 0.3, 0.3, sim.cycles, 64, seed=11)
    spec = PhySweepSpec(link_budget_db=17.0, drift_amp_db=drift_amp,
                        seed=seed)
    return simulator.pack(topo, rt, tt, DEFAULT_PHY, sim,
                          phy_spec=spec).ss


def test_drift_unit_is_a_symmetric_unit_walk():
    def unit(win):        # the walk as a fraction of its full scale
        u = np.asarray(drift_unit(2, jnp.int32(win), jnp.int32(8)))
        return u.astype(np.int64) / (8 << 24)

    u0, u5 = unit(0), unit(5)
    for u in (u0, u5):
        assert ((u >= 0.0) & (u < 1.0)).all()
        assert np.array_equal(u, u.T)            # reciprocal channel
    assert not np.array_equal(u0, u5)            # the channel moves
    # between knots the walk is the exact lerp of its endpoints
    k0, k1, mid = unit(8), unit(16), unit(12)
    assert np.array_equal(mid, k0 + (k1 - k0) * 0.5)


def test_drift_db_q_is_the_exact_scaled_floor():
    """The split int32 product equals floor(amp * u / (period << 24))."""
    from repro.phy.living import drift_db_q
    rng = np.random.default_rng(0)
    for period in (1, 8, 127):
        u = rng.integers(0, period << 24, 4096)
        amp = rng.integers(0, 1 << 15, 4096)
        got = np.asarray(drift_db_q(jnp.asarray(amp, jnp.int32),
                                    jnp.asarray(u, jnp.int32),
                                    jnp.int32(period)))
        want = [a * x // (period << 24) for a, x in zip(amp.tolist(),
                                                        u.tolist())]
        assert got.tolist() == want


@pytest.mark.parametrize("win", [0, 5, 13])
def test_drifted_perq_matches_host_per_chain(win):
    """The device path (integer walk, fixed-point SNR, table gather)
    equals the host PER chain evaluated at the same fixed-point SNR."""
    ss = _living_static()
    _, _, perq = window_tables(ss, ss.wl_rate0, jnp.int32(win), True, False)
    period = int(ss.wl_drift_period)
    u = np.asarray(drift_unit(ss.phy_seed, jnp.int32(win),
                              jnp.int32(period))).astype(np.int64)
    snr_q = (np.asarray(ss.wl_snr_q)
             - int(ss.wl_drift_amp_q) * u // (period << 24))
    per_r = rate_per_matrix(snr_q / SNR_Q,
                            DEFAULT_PHY.pkt_flits * DEFAULT_PHY.flit_bits)
    want = np.take_along_axis(per_q(per_r), np.asarray(ss.wl_rate0)[None],
                              axis=0)[0]
    n = int(ss.n_wi)
    off = ~np.eye(n, dtype=bool)
    assert np.array_equal(np.asarray(perq)[:n, :n][off], want[:n, :n][off])


@pytest.mark.parametrize("spec", [
    PhySweepSpec(drift_amp_db=4.0, drift_period=128),
    PhySweepSpec(drift_amp_db=512.0)], ids=["period", "amplitude"])
def test_drift_knobs_beyond_int32_range_are_rejected(spec):
    with pytest.raises(ValueError):
        link_tables(build_xcym(4, 4, Fabric.WIRELESS), DEFAULT_PHY, spec)


def test_drifted_link_quality_monotone_in_amplitude_grid():
    """Deterministic fallback: more aging never improves any link."""
    ss = _living_static()
    prev = None
    for amp in (0.0, 2.0, 4.0, 8.0):
        sa = ss._replace(wl_drift_amp_q=jnp.int32(drift_amp_q(amp)))
        _, _, perq = window_tables(sa, ss.wl_rate0, jnp.int32(3),
                                   True, False)
        perq = np.asarray(perq)
        if prev is not None:
            assert (perq >= prev).all(), f"amp={amp} improved a link"
        prev = perq


if HAVE_HYP:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 255), st.floats(0.0, 6.0), st.floats(0.0, 6.0))
    def test_drifted_link_quality_monotone_in_amplitude(win, a1, a2):
        ss = _living_static()
        lo, hi = sorted((a1, a2))
        out = []
        for amp in (lo, hi):
            sa = ss._replace(wl_drift_amp_q=jnp.int32(drift_amp_q(amp)))
            _, _, perq = window_tables(sa, ss.wl_rate0, jnp.int32(win),
                                       True, False)
            out.append(np.asarray(perq))
        assert (out[1] >= out[0]).all()

    @given(st.integers(0, 2**20), st.integers(0, 10),
           st.lists(st.integers(0, 2**16 - 1), min_size=1, max_size=7))
    def test_group_crc_fail_dominates_members(uid, att, perqs):
        """Group threshold = max member PER: the group NACKs whenever
        any member copy would individually fail (same hash draw), so
        all-or-nothing delivery never silently loses one member."""
        group = bool(crc_fail(7, uid, att, np.int32(max(perqs))))
        members = [bool(crc_fail(7, uid, att, np.int32(q)))
                   for q in perqs]
        assert group == any(members)


# -------------------------------------------- reselect no-op when static

def test_reselect_is_bitwise_noop_on_static_channel():
    """With ``drift_amp_db == 0`` the window argmax re-derives the host
    selection from the same quantized-goodput integers: zero
    re-selections and bitwise-identical dynamics (every state field
    whose shape survives the living-program padding)."""
    topo = build_xcym(4, 4, Fabric.WIRELESS)
    rt = compute_routing(topo)
    sim = SimParams(cycles=600, warmup=0)
    tt = traffic.uniform_random(topo, 0.6, 0.3, sim.cycles, 64, seed=21)
    base = dict(link_budget_db=17.0, max_retx=3)
    a = simulator.run(simulator.pack(
        topo, rt, tt, DEFAULT_PHY, sim,
        phy_spec=PhySweepSpec(**base)))
    b = simulator.run(simulator.pack(
        topo, rt, tt, DEFAULT_PHY, sim,
        phy_spec=PhySweepSpec(reselect=True, **base)))
    assert int(b.wl_resel) == 0
    assert int(b.flits_inj) > 0 and int(b.wl_nacks) > 0
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        if x.shape != y.shape:       # living-program placeholder padding
            continue
        assert np.array_equal(x, y), f"field {f} diverged under reselect"
