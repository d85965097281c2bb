"""Drain-aware chunked execution driver shared by BOTH engines (ISSUE 5).

The monolithic execution model — one ``lax.scan`` over a static number of
cycles — makes every point pay its full static budget: a trace that drains
at cycle 2k of a 96k-cycle budget still simulates 96k cycles, and the scan
length is a *compile* parameter, so sweep points that differ only in
budget cannot share a launch.  This module replaces that driver with an
outer ``lax.while_loop`` over fixed-size scan chunks:

- **Traced budgets.**  The cycle budget lives in ``SimStatic.cycles``
  (a traced scalar), so one compiled program serves every budget and
  ``sweep`` no longer splits groups on cycle count.  Inside a chunk each
  cycle is wrapped in ``lax.cond(t < cycles, step, identity)`` — a lane
  whose budget ends mid-chunk freezes *exactly* at its budget, so stats
  are bitwise-identical to a monolithic scan of ``cycles`` steps.
- **Early exit.**  Between chunks a cheap ``drain_done`` predicate checks
  whether the lane can ever change again: no packet in any (buffer, vc)
  slot, empty arrival pipes, no active injection burst, no future
  effective birth (including closed-loop reply births via ``rdy`` and
  tombstoned ``dead`` slots), all outstanding-transaction windows back to
  zero, all trace phases closed, and all busy-until clocks expired.  Once
  true, every remaining cycle is the identity on the whole state except
  the receiver awake/sleep accounting — which is exactly computable:
  ``n_wi`` awake (or asleep, under sleepy receivers) integer cycles per
  remaining cycle.  The driver exits the loop and adds that remainder in
  closed form, so an early-exited lane is *bitwise* equal to the full
  fixed-length run (the goldens pin this).
- **Donation.**  The whole state rides the while carry (XLA keeps it
  in-place across chunks), and the engines' jitted drivers donate the
  freshly initialized state buffer into the loop.

The predicate requires ``t0 >= warmup`` so the closed-form remainder is
uniformly post-warmup, and checks the *head* injection slot per source:
births are consumed strictly in order, so if every head slot's effective
birth (``min(births, rdy)`` for memory tables) is the ``NO_PKT``
sentinel and the head is not a tombstoned reply slot, no source can ever
inject again.

``drain_cycle`` records where the loop actually stopped (chunk
granularity; == budget when the lane never drained early) and
``cycles_run`` the lane's semantic budget — ``metrics`` normalizes by
the latter instead of a host-side constant, and ``benchmarks/simspeed``
reports the former as the per-lane drain point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.spans import scope
from repro.core.traffic import NO_PKT

# Cycles per inner scan chunk.  Small enough that a drained lane stops
# quickly (and the final partial chunk wastes little), large enough that
# the between-chunk predicate and while_loop bookkeeping are noise.
CHUNK_CYCLES = 128


def drain_done(ss, st, t0: jnp.ndarray, mem_on: bool) -> jnp.ndarray:
    """True iff no future cycle can change the state (except awake/sleep).

    Works on both engines' (SimStatic, SimState) NamedTuples — the field
    names it touches are shared by construction.  ``mem_on`` is the same
    static flag that compiled the closed-loop path: with it off, ``rdy``
    and ``dead`` are slimmed placeholders and must not be read.
    """
    i32 = jnp.int32
    no_pkts = ~(st.pkt_src >= 0).any()
    pipes_empty = ~(st.pipe != 0).any()
    no_inj = ~(st.inj_vc >= 0).any()
    N, K = ss.births.shape
    n_ar = jnp.arange(N, dtype=i32)
    qh = jnp.clip(st.q_head, 0, K - 1)
    open_slot = st.q_head < K
    idle_head = ss.births[n_ar, qh] >= jnp.int32(NO_PKT)
    if mem_on:
        # a reply slot births when the bank model writes its ``rdy``; a
        # tombstoned head would still advance q_head (the dead-slot skip)
        idle_head &= st.rdy[n_ar, qh] >= jnp.int32(NO_PKT)
        idle_head &= ~st.dead[n_ar, qh]
    no_births = (~open_slot | idle_head).all()
    outst_zero = (st.outst == 0).all()
    phases_done = (ss.n_phases == 0) | (st.cur_phase >= ss.n_phases)
    # busy receivers would keep the sleepy-rx accounting awake
    quiet = (st.busy_until <= t0).all() & (st.wl_busy_until <= t0)
    return (no_pkts & pipes_empty & no_inj & no_births & outst_zero
            & phases_done & quiet & (t0 >= ss.warmup))


def _finalize(ss, st, stop: jnp.ndarray):
    """Close the books for cycles in [stop, cycles): awake/sleep remainder.

    After ``drain_done`` the only per-cycle accumulation left in either
    step is the receiver wake/sleep accounting (all of it post-warmup,
    since the predicate requires ``t0 >= warmup``); everything else is
    event-driven and there are no events.  Integer arithmetic — exact.
    """
    cycles = ss.cycles
    rem = jnp.maximum(cycles - stop, 0).astype(jnp.int32)
    awake_pc = jnp.where(ss.sleepy, 0, ss.n_wi).astype(jnp.int32)
    return st._replace(
        awake_cycles=st.awake_cycles + awake_pc * rem,
        sleep_cycles=st.sleep_cycles + (ss.n_wi - awake_pc) * rem,
        cycles_run=cycles.astype(jnp.int32),
        drain_cycle=jnp.minimum(stop, cycles).astype(jnp.int32))


def run_chunked(step, ss, st, mem_on: bool, chunk: int = CHUNK_CYCLES,
                window_fn=None):
    """Drive ``step`` to the lane's traced budget with early drain exit.

    ``step(ss, st, t) -> st`` is either engine's compiled cycle step; the
    returned state is bitwise-equal to a monolithic ``lax.scan`` of
    ``ss.cycles`` steps (plus the ``cycles_run``/``drain_cycle`` driver
    metadata, which the monolithic driver also fills).

    ``window_fn(st, t) -> st`` is the living-channel boundary update the
    step applies at every ``t % CHUNK_CYCLES == 0`` (``phy.living`` —
    the window cadence is this fixed semantic constant, NOT the driver's
    execution ``chunk``, so custom chunk sizes and the monolithic oracle
    agree on when the channel moves).  A pure function of the window
    index, touching only the dynamic link tables and the re-selection
    counter.  A drained lane exits the loop before its remaining
    boundaries fire, but a monolithic scan of the same budget still
    fires them — so the driver *replays* the boundaries in
    ``[stop, cycles)`` here, keeping chunked == monolithic bitwise for
    living points too (the rest of the drained state is untouched by
    construction: the update writes no packet, stat or phase field).
    """
    i32 = jnp.int32
    cycles = ss.cycles.astype(i32)

    def one_cycle(s, t):
        # per-cycle freeze: a lane whose budget ends mid-chunk stops
        # accumulating exactly at its budget (lax.cond, not where: under
        # lax.map the predicate is a plain scalar, so XLA skips the body)
        with scope("driver.cycle"):
            return jax.lax.cond(t < cycles, lambda x: step(ss, x, t),
                                lambda x: x, s), None

    def body(carry):
        s, t0 = carry
        s, _ = jax.lax.scan(one_cycle, s, t0 + jnp.arange(chunk, dtype=i32))
        return s, t0 + i32(chunk)

    def cond(carry):
        s, t0 = carry
        with scope("driver.drain_check"):
            return (t0 < cycles) & ~drain_done(ss, s, t0, mem_on)

    st, t0 = jax.lax.while_loop(cond, body, (st, i32(0)))
    with scope("driver.finalize"):
        if window_fn is not None:
            # first window boundary the in-step cond did NOT fire: cycles
            # in [0, t0) all executed, so that is the first multiple of
            # the window cadence >= t0
            W = i32(CHUNK_CYCLES)
            tb = ((t0 + W - 1) // W) * W
            st, _ = jax.lax.while_loop(
                lambda c: c[1] < cycles,
                lambda c: (window_fn(c[0], c[1]), c[1] + W),
                (st, tb))
        return _finalize(ss, st, t0)
