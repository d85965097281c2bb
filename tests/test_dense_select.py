"""Dense arbitration (``core/arbitrate``) equals the gather forms bitwise.

The cycle step finds arbitration winners and reads small tables with
gathers on the CPU and with dense compare-and-reduce on the TPU.  Here,
on the CPU: each dense helper equals its gather twin on states drawn at
random over real packed systems; the pack-time membership masks hold the
candidate tables' sets; and a whole run with every dense helper forced in
(the module's selector patched inside the test) ends in the same state,
leaf for leaf, as the gather run.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arbitrate, simulator, sweep
from repro.core.constants import (DEFAULT_PHY, DEFAULT_SIM, EJ_WAYS, RXWMAX,
                                  WMAX, Fabric)
from repro.core.sweep import SweepPoint
from test_tpu_compile import _point

V = simulator.V
SEEDS = (0, 1)


@functools.lru_cache(maxsize=None)
def _system(kind: str) -> simulator.PackedSim:
    """A packed 4C4M point: one of ``test_tpu_compile._point``'s (the ideal
    crossbar with 4 rx sub-channels, memory requests forced to their
    channel's ejection way, the living channel, multicast), or the
    ``matching`` or ``single`` medium (one sub-channel, sender cap on)."""
    if kind in ("matching", "single"):
        phy = dataclasses.replace(DEFAULT_PHY, wireless_medium=kind)
        p = SweepPoint(n_chips=4, n_mem=4, fabric=Fabric.WIRELESS,
                       sim=DEFAULT_SIM, load=0.5, p_mem=0.2, phy=phy)
    else:
        p = _point(kind)
    topo, rt, tt, _ = sweep._build_point(p)
    return simulator.pack(topo, rt, tt, p.phy, p.sim, phy_spec=p.phy_spec)


SYSTEMS = ("ideal", "matching", "single", "mem_on", "multicast_trace")


class _Draw:
    """A random cycle's per-slot operands over a packed system.

    Codes are unique (a random priority permutation, as in the step); a
    slot's target is mostly one its buffer may contend for, else any
    buffer id or the eject/padding id ``B``; the multicast table is
    random with every group non-empty."""

    def __init__(self, ps: simulator.PackedSim, seed: int):
        rng = np.random.default_rng(seed)
        ss = ps.ss
        B = ps.B
        NC = B * V
        self.B, self.S = B, int(ss.cands.shape[0])
        self.M = int(ss.mc_member.shape[0])
        flat = np.arange(NC).reshape(B, V)
        score = rng.permutation(NC).reshape(B, V)
        self.bigc = NC * (NC + 1)
        self.need = rng.random((B, V)) < 0.6
        self.code = np.where(self.need, score * (NC + 1) + flat,
                             self.bigc).astype(np.int32)
        cand_w, cand_r = np.asarray(ss.cand_w), np.asarray(ss.cand_r)
        key = rng.integers(0, B + 1, (B, V))
        for b in range(B):
            tw = np.nonzero(cand_w[:, b])[0]
            tr = int(ss.rx0) + np.nonzero(cand_r[:, b])[0]
            pool = np.concatenate([tw, tr])
            if len(pool):
                pick = rng.random(V) < 0.7
                key[b, pick] = rng.choice(pool, int(pick.sum()))
        self.key = key.astype(np.int32)
        member = rng.random((self.M, WMAX)) < 0.3
        member[np.arange(self.M), rng.integers(0, WMAX, self.M)] = True
        self.ss = ss._replace(mc_member=jnp.asarray(member))
        self.mcf = np.where(rng.random((B, V)) < 0.2,
                            rng.integers(0, self.M, (B, V)), -1) \
            .astype(np.int32)
        self.ej = rng.random((B, V)) < 0.3
        ways = np.asarray(ss.b_ej_ways)[:, None]
        memrq = rng.random((B, V)) < 0.3
        self.way = np.where(memrq, rng.integers(0, EJ_WAYS, (B, V)) % ways,
                            np.arange(V)[None] % ways).astype(np.int32)
        capped = self.need & (rng.random((B, V)) < 0.5) \
            & bool(ss.wl_sender_cap)
        self.cap_code = np.where(capped, self.code, self.bigc) \
            .astype(np.int32)
        self.rng = rng


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _winners_pair(gather, dense, d: _Draw, *args):
    g = np.asarray(gather(d.ss, *args))
    _same(g, dense(d.ss, *args))
    # a real contest: some targets have a winner, some none
    assert (g < d.bigc).any() and (g == d.bigc).any()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_wired_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    _winners_pair(arbitrate.wired_winners_gather,
                  arbitrate.wired_winners_dense, d, d.code, d.key)


@pytest.mark.parametrize("sub", [False, True])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_rx_winners(kind, seed, sub):
    ps = _system(kind)
    assert int(ps.ss.rxw) == (1 if kind in ("matching", "single") else 4)
    d = _Draw(ps, seed)
    _winners_pair(lambda *a: arbitrate.rx_winners_gather(*a, sub),
                  lambda *a: arbitrate.rx_winners_dense(*a, sub),
                  d, d.code, d.key, d.mcf)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_eject_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    _winners_pair(arbitrate.eject_winners_gather,
                  arbitrate.eject_winners_dense, d, d.code, d.ej, d.way)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", ["ideal", "matching", "single"])
def test_cap_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    g = arbitrate.cap_winners_gather(d.ss, d.cap_code)
    _same(g, arbitrate.cap_winners_dense(d.ss, d.cap_code))
    if kind == "ideal":        # crossbar, no sender cap: nothing contends
        assert (np.asarray(g) == d.bigc).all()
    else:
        assert (np.asarray(g) < d.bigc).any()


@pytest.mark.parametrize("kind", SYSTEMS)
def test_small_table_lookups(kind):
    d = _Draw(_system(kind), 7)
    rng, B, S, M = d.rng, d.B, d.S, d.M
    ss = d.ss
    mcid = rng.integers(0, M, (B, V)).astype(np.int32)
    _same(arbitrate.member_gather(ss, mcid),
          arbitrate.member_dense(ss, mcid))
    free = jnp.asarray(rng.random((B, V)) < 0.5)
    ob = rng.integers(0, B, (B, V)).astype(np.int32)
    _same(arbitrate.target_free_gather(ss, free, ob),
          arbitrate.target_free_dense(ss, free, ob))
    for table in (jnp.asarray(d.code[:, 0]), jnp.asarray(d.need[:, 0])):
        _same(arbitrate.take_gather(table, ob),
              arbitrate.take_dense(table, ob))
    win2_ej = rng.integers(0, d.bigc + 1, (EJ_WAYS, S)).astype(np.int32)
    win2_wl = rng.integers(0, d.bigc + 1, (RXWMAX, WMAX)).astype(np.int32)
    win2_w = rng.integers(0, d.bigc + 1, B).astype(np.int32)
    r_mine = rng.integers(0, RXWMAX, (B, 1)).astype(np.int32)
    args = (win2_ej, win2_wl, win2_w, d.way,
            rng.integers(0, S, (B, V)).astype(np.int32), r_mine,
            rng.integers(0, WMAX, (B, V)).astype(np.int32), ob,
            d.ej, rng.random((B, V)) < 0.3)
    _same(arbitrate.slot_winner_gather(*args),
          arbitrate.slot_winner_dense(*args))
    _same(arbitrate.rx_row_gather(win2_wl, r_mine, V),
          arbitrate.rx_row_dense(win2_wl, r_mine, V))


@pytest.mark.parametrize("kind", SYSTEMS)
def test_pack_masks_hold_the_candidate_sets(kind):
    ps = _system(kind)
    ss = ps.ss
    B, S = ps.B, int(ss.cands.shape[0])
    cands, candr = np.asarray(ss.cands), np.asarray(ss.candr)
    src_sw = np.asarray(ss.b_src_sw)

    def sets(table):
        return np.stack([np.isin(np.arange(B), row[row < B])
                         for row in table])

    np.testing.assert_array_equal(np.asarray(ss.cand_s), sets(cands))
    np.testing.assert_array_equal(np.asarray(ss.cand_r), sets(candr))
    np.testing.assert_array_equal(np.asarray(ss.cand_w),
                                  sets(cands[src_sw]))
    # padding: the dummy switch, WIs past n_wi and buffers fed by no
    # switch (injection, rx, pad rows) hold no candidate
    n_sw, n_wi = ps.topo.n_switches, ps.topo.n_wi
    assert n_sw < S and n_wi < WMAX and (src_sw == S - 1).any()
    assert not np.asarray(ss.cand_s)[n_sw:].any()
    assert not np.asarray(ss.cand_r)[n_wi:].any()
    assert not np.asarray(ss.cand_w)[src_sw == S - 1].any()
    assert (cands == B).any() and (candr == B).any()


def test_on_tpu_runs_the_gather_form_on_the_cpu():
    got = jax.jit(lambda x: arbitrate.on_tpu(lambda y: y + 1,
                                             lambda y: y - 1, x))(0)
    assert int(got) == -1


def _run(ps, cycles: int):
    st = simulator.init_state(
        *simulator._state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
        living=ps.drift_on or ps.reselect, R=int(ps.ss.wl_serv_r.shape[0]))
    # stats from cycle 0, so every counter is compared too
    ss = ps.ss._replace(cycles=jnp.int32(cycles), warmup=jnp.int32(0))
    # a fresh jit per call: the run traces again under the patched selector
    return jax.jit(lambda s, t: simulator._chunk_point(
        s, t, ps.B, ps.mem_on, ps.phy_on, simulator.CHUNK_CYCLES,
        ps.drift_on, ps.reselect))(ss, st)


@pytest.mark.parametrize("kind,cycles", [
    ("ideal", 300), ("multicast_trace", 600), ("mem_on", 300),
    ("phy_drift_reselect", 300)])
def test_whole_run_dense_equals_gather(kind, cycles, monkeypatch):
    """The first multicast reaches the air after about 450 cycles, so the
    trace runs 600."""
    ps = _system(kind)
    ref = _run(ps, cycles)
    monkeypatch.setattr(arbitrate, "on_tpu",
                        lambda dense, gather, *args: dense(*args))
    got = _run(ps, cycles)
    for name, a, b in zip(ref._fields, ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert int(ref.flits_del) > 0 and int(ref.wl_tx_flits) > 0
    if kind == "multicast_trace":
        assert int(ref.wl_rx_flits) > int(ref.wl_tx_flits)
