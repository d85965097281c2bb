"""Arbitration winners and small-table lookups of the cycle step, in two
exact forms: gathers for XLA:CPU, dense compare-and-reduce for the TPU.

Every cycle the step (``simulator.make_step``) finds, per arbitration
target, the contending (buffer, vc) slot with the least priority code,
then lets each slot read its target's winner and a few rows of small
tables.  Each search and lookup here has two forms:

- *gather* (``*_gather``): read the contenders out of the static
  candidate tables ``SimStatic.cands``/``candr`` and take a masked
  ``min``; read a slot's table row by index.  XLA:CPU runs these as
  cheap loops and runs the dense forms several times slower.
- *dense* (``*_dense``): one masked ``min`` over every slot, limited to
  each target's contenders by the static membership masks
  ``SimStatic.cand_w``/``cand_r``/``cand_s``; a one-hot compare and
  reduce over a table's rows, with a row of booleans first packed into
  the bits of one int32.  The TPU runs a gather whose indices are only
  known at run time one element at a time, and these as vector work.
  Per-slot operands are transposed to ``[V, B]`` first, so the long
  buffer axis is the minor one.

``on_tpu`` picks the form by the platform the step is lowered for, so a
compiled program holds one form and no conditional.  Priority codes are
unique, so both forms find the same winner, and every result is bitwise
equal (``tests/test_dense_select.py``).  The gather forms are the step's
original code.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from repro.core.constants import EJ_WAYS, RXWMAX, WMAX

# the ``SimStatic`` leaves the forms read; a form is handed only those of
# its pair, so no leaf becomes an argument of a program that reads none
_READS = ("b_is_rx", "b_src_sw", "b_wi", "cands", "candr", "cand_w",
          "cand_r", "cand_s", "mc_member", "rx0", "rxw", "wi_sw")
Tables = collections.namedtuple("Tables", _READS,
                                defaults=(None,) * len(_READS))


def _tables(ss, *names) -> Tables:
    return Tables(**{n: getattr(ss, n) for n in names})


def on_tpu(dense, gather, *args):
    """``dense(*args)`` in a program lowered for the TPU, else
    ``gather(*args)``; the branch is chosen at lowering time."""
    return jax.lax.platform_dependent(*args, tpu=dense, default=gather)


def _bigc(code: jnp.ndarray) -> jnp.ndarray:
    """The no-winner code of a ``[B, V]`` code grid (see ``make_step``)."""
    nc = code.shape[0] * code.shape[1]
    return jnp.int32(nc * (nc + 1))


def take_dense(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for a 1-D table and in-range ``idx``, as a one-hot
    compare and reduce over the table's entries."""
    hot = idx[..., None] == jnp.arange(table.shape[0], dtype=idx.dtype)
    if table.dtype == jnp.bool_:
        return (hot & table).any(axis=-1)
    return jnp.where(hot, table, jnp.zeros((), table.dtype)).sum(
        axis=-1, dtype=table.dtype)


def _bits(rows: jnp.ndarray) -> jnp.ndarray:
    """Pack each row of a ``[R, n]`` bool table (n <= 31) into an int32."""
    weights = jnp.left_shift(jnp.int32(1),
                             jnp.arange(rows.shape[1], dtype=jnp.int32))
    return jnp.where(rows, weights, 0).sum(axis=1, dtype=jnp.int32)


def _unbits(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """``[..., n]`` bools from the low ``n`` bits of an int32 array."""
    return (jnp.right_shift(x[..., None], jnp.arange(n, dtype=jnp.int32))
            & 1) == 1


# ---- small-table lookups ---------------------------------------------------

def take_gather(table, idx):
    return table[idx]


def take(table, idx):
    """``table[idx]`` of a 1-D table with in-range indices."""
    return on_tpu(take_dense, take_gather, table, idx)


def member_gather(ss, mcid_c):
    return ss.mc_member[mcid_c]


def member_dense(ss, mcid_c):
    return _unbits(take_dense(_bits(ss.mc_member), mcid_c),
                   ss.mc_member.shape[1])


def member(ss, mcid_c):
    """``[..., W]`` receiver-WI set of each (clipped) multicast group id."""
    return on_tpu(member_dense, member_gather, _tables(ss, "mc_member"),
                  mcid_c)


def target_free_gather(ss, free_mask, ob_c0):
    return ss.b_is_rx[ob_c0], free_mask[ob_c0]


def target_free_dense(ss, free_mask, ob_c0):
    V = free_mask.shape[1]
    row = take_dense(_bits(free_mask) | jnp.left_shift(
        ss.b_is_rx.astype(jnp.int32), V), ob_c0)
    return ((row >> V) & 1) == 1, _unbits(row, V)


def target_free(ss, free_mask, ob_c0):
    """Per slot: is its target buffer an rx buffer, and which of the
    target's VCs are free (``[B, V]``, ``[B, V, V]``)."""
    return on_tpu(target_free_dense, target_free_gather,
                  _tables(ss, "b_is_rx"), free_mask, ob_c0)


def slot_winner_gather(win2_ej, win2_wl, win2_w, way, owo_s, r_mine, owo_w,
                       ob_c, out_is_ej, out_is_wl):
    return jnp.where(
        out_is_ej, win2_ej[way, owo_s],
        jnp.where(out_is_wl, win2_wl[r_mine, owo_w], win2_w[ob_c]))


def slot_winner_dense(win2_ej, win2_wl, win2_w, way, owo_s, r_mine, owo_w,
                      ob_c, out_is_ej, out_is_wl):
    S = win2_ej.shape[1]
    W = win2_wl.shape[1]
    w_ej = take_dense(win2_ej.reshape(-1), way * S + owo_s)
    w_wl = take_dense(win2_wl.reshape(-1), r_mine * W + owo_w)
    return jnp.where(out_is_ej, w_ej,
                     jnp.where(out_is_wl, w_wl, take_dense(win2_w, ob_c)))


def slot_winner(*args):
    """The winning forward code of each slot's own target: its (switch,
    ejection way), its (rx sub-channel, receiver) or its wired buffer."""
    return on_tpu(slot_winner_dense, slot_winner_gather, *args)


def rx_row_gather(win2_wl, r_mine, V):
    r_bv = jnp.broadcast_to(r_mine, (r_mine.shape[0], V))[:, :, None]
    warr = jnp.arange(WMAX, dtype=jnp.int32)
    return win2_wl[r_bv, warr[None, None, :]]


def rx_row_dense(win2_wl, r_mine, V):
    hot = r_mine[:, 0][:, None, None] == jnp.arange(
        win2_wl.shape[0], dtype=r_mine.dtype)[None, :, None]
    row = jnp.where(hot, win2_wl[None], 0).sum(axis=1, dtype=jnp.int32)
    return jnp.broadcast_to(row[:, None, :],
                            (row.shape[0], V, row.shape[1]))


def rx_row(win2_wl, r_mine, V: int):
    """``[B, V, W]``: every receiver's air winner on each sender
    buffer's own sub-channel ``r_mine`` (``[B, 1]``)."""
    return on_tpu(lambda a, r: rx_row_dense(a, r, V),
                  lambda a, r: rx_row_gather(a, r, V), win2_wl, r_mine)


# ---- winner searches --------------------------------------------------------
# ``code``/``key`` etc. are per-slot ``[B, V]`` grids; slot j = b * V + v.

def wired_winners_gather(ss, code, key):
    B, V = code.shape
    S = ss.cands.shape[0]
    varr = jnp.arange(V, dtype=jnp.int32)
    cw = ss.cands[jnp.clip(ss.b_src_sw, 0, S - 1)]       # [B, CS]
    cw_ok = (cw < B)[:, :, None]                         # [B, CS, 1]
    idx_w = jnp.clip(cw, 0, B - 1)[:, :, None] * V + varr[None, None, :]
    tgt_ids = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    # the gathered tensors go through optimization_barrier so XLA
    # materializes them once instead of re-running the gather inside
    # every fused consumer
    g_w = jax.lax.optimization_barrier(
        (code.reshape(-1)[idx_w], key.reshape(-1)[idx_w]))
    m_w = cw_ok & (g_w[1] == tgt_ids)
    return jnp.where(m_w, g_w[0], _bigc(code)).min(axis=(1, 2))


def wired_winners_dense(ss, code, key):
    B = code.shape[0]
    tgt = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    m = ss.cand_w[:, None, :] & (key.T[None] == tgt)     # [B, V, B]
    return jnp.where(m, code.T[None], _bigc(code)).min(axis=(1, 2))


def wired_winners(ss, code, key):
    """``[B]`` least code among the slots whose target buffer (``key``)
    is each wired buffer, drawn from the buffers feeding its switch."""
    return on_tpu(wired_winners_dense, wired_winners_gather,
                  _tables(ss, "cands", "b_src_sw", "cand_w"), code, key)


def _rx_idx(ss, B, V):
    cr_ok = (ss.candr < B)[:, :, None]                   # [W, CR, 1]
    crc = jnp.clip(ss.candr, 0, B - 1)
    varr = jnp.arange(V, dtype=jnp.int32)
    idx_r = crc[:, :, None] * V + varr[None, None, :]    # [W, CR, V]
    return cr_ok, crc, idx_r


def rx_winners_gather(ss, code, key, mcf, sub):
    B, V = code.shape
    M = ss.mc_member.shape[0]
    warr = jnp.arange(WMAX, dtype=jnp.int32)
    cr_ok, crc, idx_r = _rx_idx(ss, B, V)
    rx_tgt = (ss.rx0 + warr)[:, None, None]
    g_r = jax.lax.optimization_barrier(
        (code.reshape(-1)[idx_r], key.reshape(-1)[idx_r],
         mcf.reshape(-1)[idx_r]))
    memb_r = (g_r[2] >= 0) & ss.mc_member[
        jnp.clip(g_r[2], 0, M - 1), warr[:, None, None]]
    m_r = cr_ok & ((g_r[1] == rx_tgt) | memb_r)          # [W, CR, V]
    if not sub:
        return jnp.where(m_r, g_r[0], _bigc(code)).min(axis=(1, 2))
    r_cand = (ss.b_wi[crc] % jnp.maximum(ss.rxw, 1))[:, :, None]
    return jnp.where(
        m_r[None] & (r_cand[None]
                     == jnp.arange(RXWMAX)[:, None, None, None]),
        g_r[0][None], _bigc(code)).min(axis=(2, 3))      # [RXW, W]


def rx_winners_dense(ss, code, key, mcf, sub):
    M = ss.mc_member.shape[0]
    warr = jnp.arange(WMAX, dtype=jnp.int32)[:, None, None]
    mbits = jnp.where(
        mcf >= 0, take_dense(_bits(ss.mc_member), jnp.clip(mcf, 0, M - 1)),
        0)                                               # [B, V]
    memb = ((mbits.T[None] >> warr) & 1) == 1            # [W, V, B]
    m = ss.cand_r[:, None, :] & ((key.T[None] == ss.rx0 + warr) | memb)
    if not sub:
        return jnp.where(m, code.T[None], _bigc(code)).min(axis=(1, 2))
    r_b = ss.b_wi % jnp.maximum(ss.rxw, 1)               # [B]
    rarr = jnp.arange(RXWMAX, dtype=jnp.int32)[:, None, None, None]
    return jnp.where(m[None] & (r_b == rarr), code.T[None, None],
                     _bigc(code)).min(axis=(2, 3))       # [RXW, W]


def rx_winners(ss, code, key, mcf, sub: bool):
    """Least code per wireless receiver (``[W]``), or with ``sub`` per
    (rx sub-channel, receiver) (``[RXWMAX, W]``; a sender's sub-channel
    is its WI id mod ``rxw``).  Contenders are the slots able to transmit
    to the receiver that target its rx buffer (``key``) or, multicast
    (``mcf`` >= 0 is the group), have it among their members."""
    return on_tpu(lambda *a: rx_winners_dense(*a, sub),
                  lambda *a: rx_winners_gather(*a, sub),
                  _tables(ss, "candr", "rx0", "mc_member", "b_wi", "rxw",
                          "cand_r"), code, key, mcf)


def _sw_idx(ss, B, V):
    cs_ok = (ss.cands < B)[:, :, None]                   # [S, CS, 1]
    csc = jnp.clip(ss.cands, 0, B - 1)
    varr = jnp.arange(V, dtype=jnp.int32)
    idx_s = csc[:, :, None] * V + varr[None, None, :]    # [S, CS, V]
    return cs_ok, idx_s


def eject_winners_gather(ss, code, ej, way):
    B, V = code.shape
    cs_ok, idx_s = _sw_idx(ss, B, V)
    way_s = way.reshape(-1)[idx_s]                       # [S, CS, V]
    g_s = jax.lax.optimization_barrier(
        (code.reshape(-1)[idx_s], ej.reshape(-1)[idx_s]))
    m_ej = cs_ok & g_s[1]
    return jnp.where(
        m_ej[None] & (way_s[None]
                      == jnp.arange(EJ_WAYS)[:, None, None, None]),
        g_s[0][None], _bigc(code)).min(axis=(2, 3))      # [EJ, S]


def eject_winners_dense(ss, code, ej, way):
    earr = jnp.arange(EJ_WAYS, dtype=jnp.int32)[:, None, None]
    c = jnp.where(ej.T[None] & (way.T[None] == earr), code.T[None],
                  _bigc(code))                           # [EJ, V, B]
    return jnp.where(ss.cand_s[None, :, None, :], c[:, None],
                     _bigc(code)).min(axis=(2, 3))       # [EJ, S]


def eject_winners(ss, code, ej, way):
    """``[EJ_WAYS, S]`` least code per (ejection way, switch) among the
    ejecting slots (``ej``) of the buffers feeding each switch."""
    return on_tpu(eject_winners_dense, eject_winners_gather,
                  _tables(ss, "cands", "cand_s"), code, ej, way)


def cap_winners_gather(ss, cap_code):
    B, V = cap_code.shape
    S = ss.cands.shape[0]
    cs_ok, idx_s = _sw_idx(ss, B, V)
    cT_ok = cs_ok[jnp.clip(ss.wi_sw, 0, S - 1)]          # [W, CS, 1]
    idx_t = idx_s[jnp.clip(ss.wi_sw, 0, S - 1)]          # [W, CS, V]
    return jnp.where(
        cT_ok, jax.lax.optimization_barrier(cap_code.reshape(-1)[idx_t]),
        _bigc(cap_code)).min(axis=(1, 2))


def cap_winners_dense(ss, cap_code):
    S = ss.cand_s.shape[0]
    hot = jnp.clip(ss.wi_sw, 0, S - 1)[:, None, None] == jnp.arange(
        S, dtype=jnp.int32)[None, :, None]
    cand_t = (hot & ss.cand_s[None]).any(axis=1)         # [W, B]
    return jnp.where(cand_t[:, None, :], cap_code.T[None],
                     _bigc(cap_code)).min(axis=(1, 2))


def cap_winners(ss, cap_code):
    """``[W]`` least ``cap_code`` among the slots of the buffers feeding
    each WI's switch (the wireless sender cap)."""
    return on_tpu(cap_winners_dense, cap_winners_gather,
                  _tables(ss, "cands", "wi_sw", "cand_s"), cap_code)
