"""Dense arbitration (``core/arbitrate``) equals gather forms bitwise.

The cycle step finds arbitration winners and reads small tables by dense
compare-and-reduce over static candidate masks.  The plain reference
here is the step's original form: gathers through candidate index tables
(the masks' rows as buffer ids, padded with ``B``) and a masked ``min``;
a slot's table row read by index.  Pinned: each helper equals its
reference on states drawn at random over real packed systems; the
pack-time masks hold the candidate sets of the topology; a whole run
with every helper replaced by its reference ends in the same state, leaf
for leaf, as the program's own run; and ``take_rows``, the one gather of
the fields read at one index, equals indexing each field on its own.
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
B_ROWS = 320     # buffers of the 4C4M point: the step's index length


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


# ---- the reference: gather forms ------------------------------------------

def _cand_tables(ss):
    """``cands`` ``[S, CS]``: the buffers feeding each switch; ``candr``
    ``[W, CR]``: the buffers able to transmit to each receiver; as buffer
    ids padded with ``B``."""
    B = ss.cand_s.shape[1]

    def ids(mask):
        rows = [np.nonzero(r)[0] for r in np.asarray(mask)]
        out = np.full((len(rows), max(1, *map(len, rows))), B, np.int32)
        for i, r in enumerate(rows):
            out[i, :len(r)] = r
        return jnp.asarray(out)

    return ids(ss.cand_s), ids(ss.cand_r)


def _bigc(code):
    nc = code.shape[0] * code.shape[1]
    return jnp.int32(nc * (nc + 1))


def take_gather(table, idx):
    return table[idx]


def member_gather(ss, mcid_c):
    return ss.mc_member[mcid_c]


def target_free_gather(ss, free_mask, ob_c0):
    return ss.b_is_rx[ob_c0], free_mask[ob_c0]


def slot_winner_gather(win2_ej, win2_wl, win2_w, way, owo_s, r_mine, owo_w,
                       ob_c, out_is_ej, out_is_wl):
    return jnp.where(
        out_is_ej, win2_ej[way, owo_s],
        jnp.where(out_is_wl, win2_wl[r_mine, owo_w], win2_w[ob_c]))


def rx_row_gather(win2_wl, r_mine, V):
    r_bv = jnp.broadcast_to(r_mine, (r_mine.shape[0], V))[:, :, None]
    warr = jnp.arange(WMAX, dtype=jnp.int32)
    return win2_wl[r_bv, warr[None, None, :]]


def wired_winners_gather(ss, code, key, cands):
    B, V = code.shape
    S = cands.shape[0]
    varr = jnp.arange(V, dtype=jnp.int32)
    cw = cands[jnp.clip(ss.b_src_sw, 0, S - 1)]          # [B, CS]
    cw_ok = (cw < B)[:, :, None]                         # [B, CS, 1]
    idx_w = jnp.clip(cw, 0, B - 1)[:, :, None] * V + varr[None, None, :]
    tgt_ids = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    m_w = cw_ok & (key.reshape(-1)[idx_w] == tgt_ids)
    return jnp.where(m_w, code.reshape(-1)[idx_w],
                     _bigc(code)).min(axis=(1, 2))


def _idx(table, B, V):
    ok = (table < B)[:, :, None]                         # [T, C, 1]
    tc = jnp.clip(table, 0, B - 1)
    varr = jnp.arange(V, dtype=jnp.int32)
    return ok, tc, tc[:, :, None] * V + varr[None, None, :]  # [T, C, V]


def rx_winners_gather(ss, code, key, mcf, sub, candr):
    B, V = code.shape
    M = ss.mc_member.shape[0]
    warr = jnp.arange(WMAX, dtype=jnp.int32)
    cr_ok, crc, idx_r = _idx(candr, B, V)
    rx_tgt = (ss.rx0 + warr)[:, None, None]
    g_mcf = mcf.reshape(-1)[idx_r]
    memb_r = (g_mcf >= 0) & ss.mc_member[
        jnp.clip(g_mcf, 0, M - 1), warr[:, None, None]]
    m_r = cr_ok & ((key.reshape(-1)[idx_r] == rx_tgt) | memb_r)
    g_code = code.reshape(-1)[idx_r]
    if not sub:
        return jnp.where(m_r, g_code, _bigc(code)).min(axis=(1, 2))
    r_cand = (ss.b_wi[crc] % jnp.maximum(ss.rxw, 1))[:, :, None]
    return jnp.where(
        m_r[None] & (r_cand[None]
                     == jnp.arange(RXWMAX)[:, None, None, None]),
        g_code[None], _bigc(code)).min(axis=(2, 3))      # [RXW, W]


def eject_winners_gather(ss, code, ej, way, cands):
    B, V = code.shape
    cs_ok, _, idx_s = _idx(cands, B, V)
    m_ej = cs_ok & ej.reshape(-1)[idx_s]
    return jnp.where(
        m_ej[None] & (way.reshape(-1)[idx_s][None]
                      == jnp.arange(EJ_WAYS)[:, None, None, None]),
        code.reshape(-1)[idx_s][None], _bigc(code)).min(axis=(2, 3))


def cap_winners_gather(ss, cap_code, cands):
    B, V = cap_code.shape
    S = cands.shape[0]
    cs_ok, _, idx_s = _idx(cands, B, V)
    w_sw = jnp.clip(ss.wi_sw, 0, S - 1)
    return jnp.where(cs_ok[w_sw], cap_code.reshape(-1)[idx_s[w_sw]],
                     _bigc(cap_code)).min(axis=(1, 2))


def _gather_forms(ss) -> dict:
    """The reference of each ``core/arbitrate`` helper, by its name and
    with its signature, over the candidate tables of ``ss``'s masks."""
    cands, candr = _cand_tables(ss)
    return {
        "take": take_gather, "member": member_gather,
        "target_free": target_free_gather,
        "slot_winner": slot_winner_gather, "rx_row": rx_row_gather,
        "wired_winners": lambda ss, code, key: wired_winners_gather(
            ss, code, key, cands),
        "rx_winners": lambda ss, code, key, mcf, sub: rx_winners_gather(
            ss, code, key, mcf, sub, candr),
        "eject_winners": lambda ss, code, ej, way: eject_winners_gather(
            ss, code, ej, way, cands),
        "cap_winners": lambda ss, cap_code: cap_winners_gather(
            ss, cap_code, cands),
    }


# ---- helper pairs ----------------------------------------------------------

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
        self.B, self.S = B, int(ss.cand_s.shape[0])
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
        self.ref = _gather_forms(self.ss)


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _winners_pair(name, d: _Draw, *args, **kw):
    g = np.asarray(d.ref[name](d.ss, *args, **kw))
    _same(g, getattr(arbitrate, name)(d.ss, *args, **kw))
    return g


def _contested(g, d: _Draw):
    """A real contest: some targets have a winner, some none."""
    assert (g < d.bigc).any() and (g == d.bigc).any()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_wired_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    _contested(_winners_pair("wired_winners", d, d.code, d.key), d)


@pytest.mark.parametrize("sub", [False, True])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_rx_winners(kind, seed, sub):
    ps = _system(kind)
    assert int(ps.ss.rxw) == (1 if kind in ("matching", "single") else 4)
    d = _Draw(ps, seed)
    _contested(_winners_pair("rx_winners", d, d.code, d.key, d.mcf,
                             sub=sub), d)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", SYSTEMS)
def test_eject_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    _contested(_winners_pair("eject_winners", d, d.code, d.ej, d.way), d)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", ["ideal", "matching", "single"])
def test_cap_winners(kind, seed):
    d = _Draw(_system(kind), seed)
    g = _winners_pair("cap_winners", d, d.cap_code)
    if kind == "ideal":        # crossbar, no sender cap: nothing contends
        assert (g == d.bigc).all()
    else:
        assert (g < d.bigc).any()


@pytest.mark.parametrize("kind", SYSTEMS)
def test_small_table_lookups(kind):
    d = _Draw(_system(kind), 7)
    rng, B, S, M = d.rng, d.B, d.S, d.M
    ss = d.ss
    mcid = rng.integers(0, M, (B, V)).astype(np.int32)
    _same(member_gather(ss, mcid), arbitrate.member(ss, mcid))
    free = jnp.asarray(rng.random((B, V)) < 0.5)
    ob = rng.integers(0, B, (B, V)).astype(np.int32)
    _same(target_free_gather(ss, free, ob),
          arbitrate.target_free(ss, free, ob))
    for table in (jnp.asarray(d.code[:, 0]), jnp.asarray(d.need[:, 0])):
        _same(take_gather(table, ob), arbitrate.take(table, ob))
    win2_ej = rng.integers(0, d.bigc + 1, (EJ_WAYS, S)).astype(np.int32)
    win2_wl = rng.integers(0, d.bigc + 1, (RXWMAX, WMAX)).astype(np.int32)
    win2_w = rng.integers(0, d.bigc + 1, B).astype(np.int32)
    r_mine = rng.integers(0, RXWMAX, (B, 1)).astype(np.int32)
    args = (win2_ej, win2_wl, win2_w, d.way,
            rng.integers(0, S, (B, V)).astype(np.int32), r_mine,
            rng.integers(0, WMAX, (B, V)).astype(np.int32), ob,
            d.ej, rng.random((B, V)) < 0.3)
    _same(slot_winner_gather(*args), arbitrate.slot_winner(*args))
    _same(rx_row_gather(win2_wl, r_mine, V),
          arbitrate.rx_row(win2_wl, r_mine, V))


def _extremes(dtype, shape, rng):
    """Values of ``dtype`` that reach both ends of its range and -1."""
    if dtype == jnp.bool_:
        return jnp.asarray(rng.random(shape) < 0.5)
    info = np.iinfo(dtype)
    x = rng.integers(info.min, info.max, shape, endpoint=True)
    x.flat[:3] = (info.min, info.max, -1)
    return jnp.asarray(x.astype(dtype))


@pytest.mark.parametrize("idx_shape", [(B_ROWS,), (B_ROWS, V)],
                         ids=["B", "BV"])
@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.int8, jnp.int16,
                                   jnp.int32], ids=lambda d: d.__name__)
def test_take_rows_equals_per_field_indexing(dtype, idx_shape):
    """One gather of stacked rows gives each field's own ``f[idx]``: the
    field under test sits among fields of every other dtype, one with a
    trailing axis, and the indices reach the first and last rows."""
    rng = np.random.default_rng(11)
    rows = 37
    fields = [_extremes(dtype, (rows,), rng),
              _extremes(jnp.int32, (rows, V), rng),
              _extremes(jnp.bool_, (rows,), rng),
              _extremes(jnp.int8, (rows, 3), rng),
              _extremes(dtype, (rows, V), rng)]
    idx = rng.integers(0, rows, idx_shape).astype(np.int32)
    idx.flat[0], idx.flat[-1] = 0, rows - 1
    got = jax.jit(arbitrate.take_rows)(jnp.asarray(idx), *fields)
    assert len(got) == len(fields)
    for f, g in zip(fields, got):
        _same(np.asarray(f)[idx], g)


@pytest.mark.parametrize("kind", SYSTEMS)
def test_pack_masks_hold_the_candidate_sets(kind):
    """Buffers are laid out as wired links, injection buffers, rx
    buffers; each feeds the switch at its link's end, its source's
    switch or its WI's switch."""
    ps = _system(kind)
    ss, topo = ps.ss, ps.topo
    B, S = ps.B, int(ss.cand_s.shape[0])
    n_sw, n_wi, Lw = topo.n_switches, topo.n_wi, topo.n_links
    feeds = np.concatenate([topo.link_dst, np.asarray(ss.src_switch),
                            topo.wi_switch]).astype(int)
    sets = np.zeros((S, B), bool)
    for b, s in enumerate(feeds):
        sets[s, b] = True
    rx_sets = np.zeros((WMAX, B), bool)
    for src_wi, dst_wi in topo.wl_pairs:
        rx_sets[int(dst_wi)] |= sets[int(topo.wi_switch[int(src_wi)])]
    wired = np.zeros((B, B), bool)
    wired[:Lw] = sets[topo.link_src]
    np.testing.assert_array_equal(np.asarray(ss.cand_s), sets)
    np.testing.assert_array_equal(np.asarray(ss.cand_r), rx_sets)
    np.testing.assert_array_equal(np.asarray(ss.cand_w), wired)
    # padding: the dummy switch, WIs past n_wi and buffers fed by no
    # switch (injection, rx, pad rows) hold no candidate
    src_sw = np.asarray(ss.b_src_sw)
    assert n_sw < S and n_wi < WMAX and (src_sw == S - 1).any()
    assert not np.asarray(ss.cand_s)[n_sw:].any()
    assert not np.asarray(ss.cand_r)[n_wi:].any()
    assert not np.asarray(ss.cand_w)[src_sw == S - 1].any()


# ---- whole runs --------------------------------------------------------------

def _run(ps, cycles: int):
    st = simulator.init_state(
        *simulator._state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
        living=ps.drift_on or ps.reselect, R=int(ps.ss.wl_serv_r.shape[0]))
    # stats from cycle 0, so every counter is compared too
    ss = ps.ss._replace(cycles=jnp.int32(cycles), warmup=jnp.int32(0))
    # a fresh jit per call: the run traces again under patched helpers
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
    got = _run(ps, cycles)
    traced = set()

    def counted(name, fn):
        def call(*args, **kw):
            traced.add(name)
            return fn(*args, **kw)
        return call

    for name, fn in _gather_forms(ps.ss).items():
        monkeypatch.setattr(arbitrate, name, counted(name, fn))
    ref = _run(ps, cycles)
    assert traced == set(_gather_forms(ps.ss))
    for name, a, b in zip(ref._fields, ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert int(ref.flits_del) > 0 and int(ref.wl_tx_flits) > 0
    if kind == "multicast_trace":
        assert int(ref.wl_rx_flits) > int(ref.wl_tx_flits)
