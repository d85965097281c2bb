"""Arbitration winners and small-table lookups of the cycle step, as dense
compare-and-reduce.

Every cycle the step (``simulator.make_step``) finds, per arbitration
target, the contending (buffer, vc) slot with the least priority code,
then lets each slot read its target's winner and a few rows of small
tables.  The contenders of each target are given by static membership
masks that ``pack`` builds from the topology: ``SimStatic.cand_s[s]``
holds the buffers feeding switch ``s``, ``cand_w[b]`` those feeding the
switch that sends into buffer ``b``, and ``cand_r[w]`` those able to
transmit to wireless receiver ``w``.

A search is one masked ``min`` over every slot, limited to each target's
contenders by its mask; a lookup is a one-hot compare and reduce over the
table's rows, with a row of booleans first packed into the bits of one
int32.  The work is dense because the TPU runs a gather whose indices
are only known at run time one element at a time, and these as vector
work.  Per-slot operands are transposed to ``[V, B]`` first, so the long
buffer axis is the minor one.  Priority codes are unique, so each target
has at most one winner (``tests/test_dense_select.py`` pins every helper
against gather forms of the same searches).

Reads of the step's large per-slot fields at a run-time index (a winner's
slot, a target buffer, a feeding slot) stay gathers, and ``take_rows``
makes the fields read at one index a single gather of their stacked rows.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.constants import EJ_WAYS, RXWMAX, WMAX


def _bigc(code: jnp.ndarray) -> jnp.ndarray:
    """The no-winner code of a ``[B, V]`` code grid (see ``make_step``)."""
    nc = code.shape[0] * code.shape[1]
    return jnp.int32(nc * (nc + 1))


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

def take(table, idx):
    """``table[idx]`` of a 1-D table with in-range indices."""
    hot = idx[..., None] == jnp.arange(table.shape[0], dtype=idx.dtype)
    if table.dtype == jnp.bool_:
        return (hot & table).any(axis=-1)
    return jnp.where(hot, table, jnp.zeros((), table.dtype)).sum(
        axis=-1, dtype=table.dtype)


def take_rows(idx, *fields) -> tuple:
    """``tuple(f[idx] for f in fields)`` with one gather.

    The fields share their leading axis.  Each is cast to int32 (bool,
    int8 and int16 widen without loss) and its trailing axes flattened;
    stacked, they make one ``[C, rows]`` table, fetched along its minor
    axis at ``idx`` at once, and each field's part of the result is cut
    off the major axis and cast back to its dtype.  The TPU pays for a
    gather per index it fetches, not per column, so fields read at one
    index cost one gather, not one each; with the fields on the major
    axis, cutting them apart moves no data across lanes.
    """
    assert all(f.dtype == jnp.bool_ or (
        jnp.issubdtype(f.dtype, jnp.signedinteger) and f.dtype.itemsize <= 4)
        for f in fields), [f.dtype for f in fields]
    rows = fields[0].shape[0]
    cols = [f.reshape(rows, -1).astype(jnp.int32) for f in fields]
    got = jnp.take(jnp.concatenate(cols, axis=1).T, idx, axis=1)
    out, at = [], 0
    for f, c in zip(fields, cols):
        n = c.shape[1]
        out.append(jnp.moveaxis(got[at:at + n], 0, -1)
                   .reshape(idx.shape + f.shape[1:]).astype(f.dtype))
        at += n
    return tuple(out)


def member(ss, mcid_c):
    """``[..., W]`` receiver-WI set of each (clipped) multicast group id."""
    return _unbits(take(_bits(ss.mc_member), mcid_c), ss.mc_member.shape[1])


def target_free(ss, free_mask, ob_c0):
    """Per slot: is its target buffer an rx buffer, and which of the
    target's VCs are free (``[B, V]``, ``[B, V, V]``)."""
    V = free_mask.shape[1]
    row = take(_bits(free_mask) | jnp.left_shift(
        ss.b_is_rx.astype(jnp.int32), V), ob_c0)
    return ((row >> V) & 1) == 1, _unbits(row, V)


def slot_winner(win2_ej, win2_wl, win2_w, way, owo_s, r_mine, owo_w, ob_c,
                out_is_ej, out_is_wl):
    """The winning forward code of each slot's own target: its (switch,
    ejection way), its (rx sub-channel, receiver) or its wired buffer."""
    S = win2_ej.shape[1]
    W = win2_wl.shape[1]
    w_ej = take(win2_ej.reshape(-1), way * S + owo_s)
    w_wl = take(win2_wl.reshape(-1), r_mine * W + owo_w)
    return jnp.where(out_is_ej, w_ej,
                     jnp.where(out_is_wl, w_wl, take(win2_w, ob_c)))


def rx_row(win2_wl, r_mine, V: int):
    """``[B, V, W]``: every receiver's air winner on each sender
    buffer's own sub-channel ``r_mine`` (``[B, 1]``)."""
    hot = r_mine[:, 0][:, None, None] == jnp.arange(
        win2_wl.shape[0], dtype=r_mine.dtype)[None, :, None]
    row = jnp.where(hot, win2_wl[None], 0).sum(axis=1, dtype=jnp.int32)
    return jnp.broadcast_to(row[:, None, :],
                            (row.shape[0], V, row.shape[1]))


# ---- winner searches --------------------------------------------------------
# ``code``/``key`` etc. are per-slot ``[B, V]`` grids; slot j = b * V + v.

def wired_winners(ss, code, key):
    """``[B]`` least code among the slots whose target buffer (``key``)
    is each wired buffer, drawn from the buffers feeding its switch."""
    B = code.shape[0]
    tgt = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    m = ss.cand_w[:, None, :] & (key.T[None] == tgt)     # [B, V, B]
    return jnp.where(m, code.T[None], _bigc(code)).min(axis=(1, 2))


def rx_winners(ss, code, key, mcf, sub: bool):
    """Least code per wireless receiver (``[W]``), or with ``sub`` per
    (rx sub-channel, receiver) (``[RXWMAX, W]``; a sender's sub-channel
    is its WI id mod ``rxw``).  Contenders are the slots able to transmit
    to the receiver that target its rx buffer (``key``) or, multicast
    (``mcf`` >= 0 is the group), have it among their members."""
    M = ss.mc_member.shape[0]
    warr = jnp.arange(WMAX, dtype=jnp.int32)[:, None, None]
    mbits = jnp.where(
        mcf >= 0, take(_bits(ss.mc_member), jnp.clip(mcf, 0, M - 1)),
        0)                                               # [B, V]
    memb = ((mbits.T[None] >> warr) & 1) == 1            # [W, V, B]
    m = ss.cand_r[:, None, :] & ((key.T[None] == ss.rx0 + warr) | memb)
    if not sub:
        return jnp.where(m, code.T[None], _bigc(code)).min(axis=(1, 2))
    r_b = ss.b_wi % jnp.maximum(ss.rxw, 1)               # [B]
    rarr = jnp.arange(RXWMAX, dtype=jnp.int32)[:, None, None, None]
    return jnp.where(m[None] & (r_b == rarr), code.T[None, None],
                     _bigc(code)).min(axis=(2, 3))       # [RXW, W]


def eject_winners(ss, code, ej, way):
    """``[EJ_WAYS, S]`` least code per (ejection way, switch) among the
    ejecting slots (``ej``) of the buffers feeding each switch."""
    earr = jnp.arange(EJ_WAYS, dtype=jnp.int32)[:, None, None]
    c = jnp.where(ej.T[None] & (way.T[None] == earr), code.T[None],
                  _bigc(code))                           # [EJ, V, B]
    return jnp.where(ss.cand_s[None, :, None, :], c[:, None],
                     _bigc(code)).min(axis=(2, 3))       # [EJ, S]


def cap_winners(ss, cap_code):
    """``[W]`` least ``cap_code`` among the slots of the buffers feeding
    each WI's switch (the wireless sender cap)."""
    S = ss.cand_s.shape[0]
    hot = jnp.clip(ss.wi_sw, 0, S - 1)[:, None, None] == jnp.arange(
        S, dtype=jnp.int32)[None, :, None]
    cand_t = (hot & ss.cand_s[None]).any(axis=1)         # [W, B]
    return jnp.where(cand_t[:, None, :], cap_code.T[None],
                     _bigc(cap_code)).min(axis=(1, 2))
