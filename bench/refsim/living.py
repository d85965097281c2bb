"""In-scan living-channel updates: SNR drift and rate re-selection.

The static PHY of the lossy PHY froze the channel at pack time: one SNR map,
one host-side rate-selection pass, constant per-pair PER/service tables
for the whole run.  Real in-package links age — thermal cycling of the
package changes the standing-wave pattern of the cavity and with it
every link's effective SNR ("Engineer the Channel and Adapt to it",
Timoneda et al. 2019).  This module is the *single* implementation both
engines call at window boundaries (``constants.WINDOW_CYCLES``);
like ``rates.pack_link_state`` it is shared on purpose — the dual-engine
invariant pins the two step *formulations*, and a pure elementwise
window function cannot be formulated twice without inviting drift.

- ``drift_unit``: the seeded thermal-cycle walk.  One knot per
  ``drift_period`` windows per unordered link (the channel is
  reciprocal), drawn from the same counter-based murmur3 hash the ARQ
  CRC uses — no RNG state in the carry — and linearly interpolated
  between knots.  Values lie in ``[0, 1)``, held exactly as integers;
  the sweep knob ``drift_amp_db`` scales them, so drifted SNR is
  *monotone non-increasing in the aging amplitude* by construction (the
  property tests pin this).
- ``window_tables``: per-window PER thresholds, goodput estimates and
  (under ``reselect``) the per-link argmax over the rate table.  On a
  static channel (``drift_amp_db == 0``) it reads the host-packed
  integer tables ``wl_perq_r`` / ``wl_gp_q`` — the *same* integers the
  host selection pass argmaxed over — so in-scan re-selection is a
  bitwise no-op vs the one-shot program.  Under drift the drifted SNR
  is computed in fixed point (``rates.SNR_Q`` steps per dB) and indexes
  host-built PER / goodput tables (``rates.snr_lut``).  Everything on
  the device is integer arithmetic and gathers, so every backend — XLA
  on the CPU or on a TPU, either engine — derives the same integers.
  Evaluating the PER chain in f32 on the device does not: the last bits
  of ``power``/``exp``/``log1p`` differ between backends, and one ulp
  flips a quantized threshold and, through it, CRC outcomes.
- ``make_window_fn``: closes over the static flags and returns the
  ``window_fn(st, t)`` the step applies (via ``lax.cond`` on the window
  boundary).
"""
from __future__ import annotations

import jax.numpy as jnp

from refsim.constants import WINDOW_CYCLES, WMAX
from refsim.rates import SNR_LUT_LO, SNR_Q
from refsim.retx import crc_hash

# Domain-separation constant: the drift walk and the CRC draw share the
# packed ``phy_seed`` but must be independent streams.
DRIFT_SEED = 0xD51F7EED


def drift_unit(phy_seed, win, period):
    """[WMAX, WMAX] int32 aging offsets for scan window ``win``.

    The offset is ``drift_unit(...) / (period << 24)``, in ``[0, 1)``;
    ``period <= 127`` keeps it within int32.  Symmetric (one walk per
    unordered link, mirrored — the physical channel is reciprocal) and
    deterministic in ``(phy_seed, win, period)``.  Knots sit every
    ``period`` windows, each the hash's top 24 bits; between knots the
    offset is the exact linear interpolation, so the walk is slow on the
    scale of a scan window, as thermal cycling is.
    """
    i32 = jnp.int32
    ids = jnp.arange(WMAX, dtype=i32)
    lid = (jnp.minimum(ids[:, None], ids[None, :]) * WMAX
           + jnp.maximum(ids[:, None], ids[None, :]))
    dseed = jnp.uint32(phy_seed) ^ jnp.uint32(DRIFT_SEED)
    k = (win // period).astype(i32)

    def knot(kk):
        return (crc_hash(dseed, lid, kk) >> jnp.uint32(8)).astype(i32)

    h0, h1 = knot(k), knot(k + 1)
    return h0 * period + (h1 - h0) * (win % period)


def drift_db_q(amp_q, u, period):
    """``floor(amp_q * u / (period << 24))``, exact in int32.

    The SNR loss on the ``rates.SNR_Q`` grid for an amplitude ``amp_q <
    2**15`` and a walk value ``u`` of ``drift_unit``: ``u`` is split
    into 16-bit halves so that no product exceeds 31 bits.
    """
    hi, lo = u >> 16, u & 0xFFFF
    return (amp_q * hi + ((amp_q * lo) >> 16)) // (period << 8)


def window_tables(ss, rate_prev, win, drift_on: bool, reselect: bool):
    """Per-window ``(rate, serv, perq)`` [WMAX, WMAX] int32 tables.

    ``ss`` is either engine's ``SimStatic`` (the fields read here are
    shared by construction); ``rate_prev`` is the carry's current
    per-link rate-table entry.  Static python flags pick the program:

    - ``drift_on``: look the PER thresholds and quantized goodput of
      the drifted fixed-point SNR up in the host-built grid tables;
      otherwise read the host-packed integer tables — bitwise the
      integers ``rates.select_rates`` argmaxed over.
    - ``reselect``: per-link argmax over the quantized goodput (first
      maximum — ties break toward the faster entry, exactly like the
      host pass); otherwise keep ``rate_prev`` (the channel still
      drifts under the *static* selection — the fig9 "adaptive-static"
      arm).
    """
    i32 = jnp.int32
    if drift_on:
        u = drift_unit(ss.phy_seed, win, ss.wl_drift_period)
        snr_q = ss.wl_snr_q - drift_db_q(ss.wl_drift_amp_q, u,
                                         ss.wl_drift_period)
        idx = jnp.clip(snr_q - SNR_LUT_LO * SNR_Q, 0,
                       ss.wl_perq_lut.shape[1] - 1)
        perq_r, gp_q = ss.wl_perq_lut[:, idx], ss.wl_gp_lut[:, idx]
    else:
        perq_r, gp_q = ss.wl_perq_r, ss.wl_gp_q
    if reselect:
        rate = jnp.argmax(gp_q, axis=0).astype(i32)
    else:
        rate = rate_prev
    perq = jnp.take_along_axis(perq_r, rate[None], axis=0)[0]
    serv = ss.wl_serv_r[rate]
    return rate, serv, perq


def make_window_fn(ss, drift_on: bool, reselect: bool):
    """Window-boundary update ``window_fn(st, t) -> st`` for one engine.

    Fires at every ``t % WINDOW_CYCLES == 0``.  Refreshes the carry's dynamic link tables
    (``wl_serv_d`` / ``wl_perq_d`` / ``wl_rate_d``) for the window
    containing cycle ``t`` and counts re-selections (``wl_resel``) over
    the valid off-diagonal links.  At window 0 the previous rate is the
    host selection (``ss.wl_rate0``) — the zero-initialized carry is
    never read.  A pure function of the window index.
    """
    i32 = jnp.int32

    ids = jnp.arange(WMAX, dtype=i32)

    def fn(st, t):
        win = (t // jnp.int32(WINDOW_CYCLES)).astype(i32)
        prev = jnp.where(win == 0, ss.wl_rate0, st.wl_rate_d)
        rate, serv, perq = window_tables(ss, prev, win, drift_on, reselect)
        valid = ids < ss.n_wi
        live = valid[:, None] & valid[None, :] \
            & (ids[:, None] != ids[None, :])
        changed = live & (rate != prev)
        return st._replace(
            wl_rate_d=rate, wl_serv_d=serv, wl_perq_d=perq,
            wl_resel=st.wl_resel + changed.astype(i32).sum())

    return fn
