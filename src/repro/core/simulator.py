"""Cycle-accurate flit-level simulator for multichip NoCs (paper §IV).

Implements wormhole switching with virtual channels (8 VCs x 16-flit input
buffers), credit-equivalent backpressure, forwarding-table routing, the
paper's control-packet wireless MAC with partial packet transmission
(§III.D), and sleepy receivers [17] — all as one vectorized cycle step
scanned over time with ``jax.lax.scan``.

Data model
----------
Everything is link-centric.  A *buffer* is the input buffer at the
downstream end of a directed link.  Buffers come in three groups:

    [0, Lw)               wired links  (buffer id == routing link id)
    [Lw, Lw+Ninj)         injection links (core -> its switch)
    [Lw+Ninj, ...+n_wi)   wireless rx buffers (one per WI; all senders share)

Per (buffer, vc) state carries the *current packet*: identity, destination,
routing decision (made once, at VC-claim time = header), a claimed output VC,
and received/sent flit counters; occupancy is ``rcvd - sent``.  Flits in
flight on a link live in a short arrival pipe (shift register) that models
the 3-stage switch pipeline + wire/serializer latency.

Wireless medium (DESIGN.md §7): the control-packet MAC is modeled as
output arbitration over the air, a control packet preceding every packet's
burst (and keeping non-addressed receivers asleep [17]).  Concurrency is
selected by ``PhyParams.wireless_medium``:

  crossbar  every WI pair is an independent virtual channel (idealized
            multi-channel medium; required for the paper's reported
            bandwidth/latency results; default),
  matching  one stream per receiver plus one flit/cycle per sender,
  single    the strict shared 16 Gbps channel of §III.B (one flit in the
            air per ``serv_wl`` cycles) — physics-faithful ablation.

TOKEN mode additionally requires a whole buffered packet before
transmission [7] (and therefore packet-deep WI buffers).

Trace extensions (ISSUE 2; see traffic.py "Trace tables")
---------------------------------------------------------
*Multicast delivery*: a packet whose table slot encodes a multicast group
(``dests = -(1+m)``) routes to the group's anchor WI and, at the air hop,
claims a VC at EVERY member rx buffer (all-or-nothing, same rotating
arbitration), then transmits each flit once — one shared-channel
occupancy — while every member copy receives it via the ``src_of``
inverse map.  Copies continue as ordinary unicasts to their per-WI
destinations (``mc_dst``).  Transmit energy is counted once per broadcast
(only the lowest-member "primary" copy increments ``counts_into``);
``wl_tx_flits``/``wl_rx_flits`` count occupancies vs receptions.

*Phase barriers*: packets carry a phase id; injection is gated on the
packet's phase being open, and a phase closes when its expected ejection
count (``phase_need``) is reached — traces are dependency-ordered, not
open-loop.  ``phase_end``/``phase_flits`` feed the per-phase metrics.
With ``n_phases == 0`` and no groups the step reduces bitwise to the
open-loop unicast engine (goldens pin this).

Closed-loop memory (ISSUE 3; see traffic.py "Memory tables")
------------------------------------------------------------
Memory tables turn the stacks from one-way sinks into request/reply
round trips.  Per-slot packet *lengths* (``lens``) replace the global
packet length (short read requests / write acks, full-size data).  A
read/write request's final ejection way at the stack is forced to its
pseudo-channel (``mem_ch``) — the four ejection ways ARE the stack's
four channel ports — so per-(switch, way) ejection arbitration admits
at most one request per (stack, channel) per cycle.  On tail ejection
the request enters the channel's bank model (``memory.model``): service
starts at ``max(t+1, bank_busy)``, lasts ``t_row_hit``/``t_row_miss``
by row-buffer comparison, and the completion cycle is written (via an
elementwise one-assignment min, no scatter) into the ``rdy`` birth of
the paired pre-allocated reply slot; the stack's per-channel source row
then injects the reply in slot order (in-order per-channel response
queue).  Cores are capped at ``max_outstanding`` in-flight transactions
(injection gated on ``outst``, credited back when the reply/ack tail
ejects at the requester — located through the per-(switch, way)
ejection-winner table, again gather-only).  ``amat_*``/``mem_*``
counters feed AMAT, per-stack bandwidth and the queue/bank/network
delay breakdown in ``metrics``.  The whole path is compiled only when
the table has memory ops (static ``mem_on``); open-loop points run the
exact pre-memory program and stay byte-identical.

Lossy PHY (ISSUE 4; see repro.phy)
----------------------------------
With a ``PhySweepSpec`` packed in (static ``phy_on``), the air is no
longer ideal: every (src WI, dst WI) link carries a statically selected
rate (per-link ``wireless_flit_cycles`` and energy from ``phy.rates``)
and a quantized packet error rate.  The wireless hop becomes CRC-checked
ARQ: the sender holds the whole packet (packet-deep WI buffers, like the
token MAC), each attempt streams all flits — charging channel occupancy,
per-pair pacing (``pair_busy``) and transmit energy (``wl_pair_flits``)
— and the CRC outcome is drawn from a counter-based deterministic hash
of ``(seed, packet, attempt)`` against the link's PER threshold
(``phy.retx``).  Failing attempts deliver nothing to the receiver
(``wl_fail_flits`` counts their wasted flits); a NACK on the tail
rewinds the sender for the next attempt, and a packet failing
``max_retx`` attempts is dropped (sender slot and receiver VC freed,
``pkts_dropped``).  A unicast air flit reaches the receiver VC its
sender names, found through the (sub-channel, receiver) air winners
rather than ``src_of``: when a drifting channel moves a link's PER
threshold inside an attempt, a receiver can forward its packet and hand
the VC on while the old sender still streams into it (as the reference
engine's scatter does).  Receivers are store-and-forward under ``rx_hold``:
an rx-buffer slot neither claims its downstream VC nor forwards until
the whole packet has arrived (the CRC check completes at the tail).

``rx_hold`` is also set (without the lossy path) whenever the table has
multicast groups: it breaks the one-shot all-reduce livelock where a
mid-stream multicast copy held a downstream VC while waiting for air
flits whose sender was blocked on another copy of the same group — a
cyclic hold-and-wait the all-or-nothing group backpressure closed.  With
store-and-forward receivers a granted downstream VC always drains from
locally buffered flits, so the cycle cannot form.

Simplifications (documented in DESIGN.md): instant credit return; one VC
allocation per target buffer per cycle; time-rotating (round-robin
equivalent) arbitration priority; an input link's VCs may forward to
distinct outputs in the same cycle.  Lossy-PHY simplifications: CRC
outcome known sender-side at the tail (instant NACK, like the instant
credit return); failing attempts keep non-crossbar receivers busy but do
not wake sleepy crossbar receivers.  Under closed-loop memory, an
ARQ-dropped request/reply loses its transaction's data (no timeout
layer), but the drop is observed sender-side, so the requester's
``max_outstanding`` window is credited back immediately and a dropped
request's pre-allocated reply slot is tombstoned (``dead``) — the
stack's in-order reply channel skips it rather than wedging behind a
birth that will never come.

Execution strategy (this file's performance core)
-------------------------------------------------
The cycle step is written entirely with *masked min-reductions, small
gathers and elementwise ops* — no scatters and no segment ops.
Arbitration (VC claims, output ports, the wireless sender cap) is resolved
target-side over **static candidate masks** built at pack time from the
topology: ``cand_s[s]`` marks the buffers feeding switch ``s``,
``cand_w[b]`` those feeding the switch that sends into buffer ``b`` and
``cand_r[w]`` the buffers that can transmit to wireless receiver ``w``.
Each contending slot gets a unique priority code
``score * (B*V+1) + slot_id`` (scores are a rotating permutation, so codes
never tie) and the winner per target is a masked ``min``.  Flit delivery is
inverted the same way through ``SimState.src_of``: each (buffer, vc) knows
which upstream slot feeds it, so arrivals are gathers, not scatters.

This matters because XLA:CPU executes scatters and segment ops as serial
per-update loops that dominate the cycle cost.

The TPU, in turn, fetches the elements of a gather whose indices are
known only at run time one at a time (7-11 ns each on a v5e).  So the
winner searches and small-table lookups (``core/arbitrate``) have one
form on both backends, with no such gather: a search is one masked
``min`` over every (buffer, vc) slot, limited to each target's
contenders by its mask; a slot reads its target's winner, its multicast
members or its target's free VCs by a one-hot compare and reduce over
the small table's rows.  The remaining ``[B, V]``-sized reads at a
run-time index (a winner's slot, a target buffer, a feeding slot) stay
gathers, and the TPU pays for a gather per index it fetches, whatever
the number of fields.  So the fields read at one index are fetched by
one gather of the fields stacked as int32 rows (``arbitrate.take_rows``):
one at the feeding slot ``src_of`` for every delivery read, one at the
claimed downstream VC ``(out_buf, out_vc)`` for its state and one at the
target buffer for its depth and link, one at the VC-claim winner, one
at the routing output, and on the lossy path one at the sender WI's row
of the per-pair tables and one at the air winners.

Injection moves values between the sources and their injection buffers
as one block, with no gather and one form on both backends: ``pack``
lays the buffers out at ``[Lw, Lw+N)`` (source n feeds buffer Lw + n),
so a ``dynamic_update_slice`` at ``Lw = inj_buf[0]`` spreads a
per-source vector onto ``[B]`` and a ``dynamic_slice`` reads the
block's rows back.  The TPU expands a small gather ``x[nb]`` into one
scalar extract and ``[B]``-wide select per source, 64 to a read, which
held about a third of the ideal step; the slices are cheap on XLA:CPU
too.  ``Lw`` is traced per lane, so lanes of different fabrics share a
program (a ``vmap`` over lanes would turn its per-lane start into a
scatter).

The batched sweep engine
(`run_batch`, used by ``sweep.run_sweep_batched``) runs N sweep points of
the same bucket shape as one XLA launch (``lax.map`` over the stacked
batch — bitwise-identical per-point programs) and shards groups across
host devices with ``jax.pmap`` when more than one is available.
``simulator_ref`` preserves the original scatter/segment engine as a
differential-testing oracle (see tests/test_engine_equivalence.py).

Compile sharing: every topology-dependent quantity is a *padded, traced
array argument*, so one XLA compilation serves all topologies, fabrics and
traffic tables of the same bucket shape.  ``pack(..., floors=...)`` lets
callers raise the padded dims so heterogeneous points (e.g. different
fabrics) land on one shape and can share a batch.

Drain-aware chunked execution (ISSUE 5; see core/chunked.py)
------------------------------------------------------------
The default driver is no longer one monolithic ``lax.scan(cycles)`` but
an outer ``lax.while_loop`` over ``CHUNK_CYCLES``-sized scan chunks with
a between-chunk drain predicate: a lane whose traffic has fully drained
(trace phases closed, closed-loop windows back to zero, no future
births) exits early and the remaining cycles' awake/sleep accounting is
added in closed form — bitwise-identical to the fixed-length run.  The
cycle budget is traced (``SimStatic.cycles``), so points that differ
only in budget share one compile and one batch; each lane freezes
exactly at its own budget via a per-cycle ``lax.cond``.  The scan carry
is slimmed: small-enum fields (VC indices, ARQ attempts, the arrival
pipes, injection burst counters) are i8/i16, and the closed-loop /
lossy-PHY state blocks collapse to placeholder scalars when their path
is not compiled (``mem_on``/``phy_on`` are already in the shape key).
The jitted drivers donate the freshly initialized state into the loop.
``run(..., driver="monolithic")`` keeps the old single-scan driver as a
differential oracle for tests and ``benchmarks/simspeed``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import arbitrate, chunked, spans
from repro.core.chunked import CHUNK_CYCLES
from repro.core.constants import (EJ_WAYS, RXWMAX, WMAX, LinkClass, MacMode,
                                  PhyParams, SimParams)
from repro.core.routing import RoutingTables
from repro.core.topology import Topology
from repro.core.traffic import NO_PKT, TrafficTable
from repro.memory.model import MEM_CH, DEFAULT_DRAM
from repro.phy.living import make_window_fn
from repro.phy.retx import crc_fail as _crc_fail

V = 8            # virtual channels per port (paper §IV)
DEPTH = 16       # buffer depth in flits (paper §IV)
DMAX = 12        # arrival-pipe depth >= max link latency
assert MEM_CH == EJ_WAYS, "pseudo-channels must map 1:1 onto ejection ways"


def _bucket(n: int, q: int) -> int:
    return int(np.ceil(max(n, 1) / q) * q)


class SimStatic(NamedTuple):
    """Padded, device-resident topology/routing/traffic description."""

    # buffers
    b_dst: jnp.ndarray        # [B] dst switch (dummy rows -> S_pad-1)
    b_serv: jnp.ndarray      # [B] cycles between flits INTO this buffer
    b_lat: jnp.ndarray       # [B] forward -> arrival latency (>=1)
    b_epb: jnp.ndarray       # [B] pJ/bit of the link feeding this buffer
    b_depth: jnp.ndarray     # [B] buffer depth in flits
    b_wi: jnp.ndarray        # [B] WI id at the buffer's switch (-1 none)
    b_is_rx: jnp.ndarray     # [B] bool: wireless rx buffer
    b_ej_ways: jnp.ndarray   # [B] parallel ejection channels at dst switch
    b_src_sw: jnp.ndarray    # [B] switch transmitting into this buffer
    #                          (dummy S_pad-1 for injection/rx/pad rows)
    inj_src: jnp.ndarray     # [B] source id whose injection buffer this is (-1)
    # routing
    next_out: jnp.ndarray    # [S, S] routing output id
    o_buf: jnp.ndarray       # [R] target buffer id (dummy B for eject/pad)
    o_wo: jnp.ndarray        # [R] arbitration key: wired -> link id,
    #                          eject -> switch id, wireless -> dst WI id
    o_is_wl: jnp.ndarray     # [R] bool wireless pair link
    o_is_ej: jnp.ndarray     # [R] bool ejection
    # arbitration candidate masks (static per topology, core/arbitrate)
    cand_w: jnp.ndarray      # [B, B] bool: b' feeds the switch sending into b
    cand_r: jnp.ndarray      # [W, B] bool: b' can transmit to rx WI w
    cand_s: jnp.ndarray      # [S, B] bool: b' feeds switch s
    wi_sw: jnp.ndarray       # [W] switch of each WI (dummy S_pad-1)
    rxw: jnp.ndarray         # scalar int32: rx sub-channels per WI (>=1)
    # wireless
    n_wi: jnp.ndarray        # scalar int32
    rx0: jnp.ndarray         # scalar int32: first rx buffer id
    # injection + traffic
    inj_buf: jnp.ndarray     # [N] injection buffer id per source
    src_switch: jnp.ndarray  # [N] switch of each source
    births: jnp.ndarray      # [N, K]
    dests: jnp.ndarray       # [N, K]
    # scalars (traced => shared compile)
    pkt_len: jnp.ndarray     # int32
    warmup: jnp.ndarray      # int32
    cycles: jnp.ndarray      # int32 per-lane cycle budget (traced: budgets
    #                          batch freely; the chunked driver loops on it)
    serv_wl: jnp.ndarray     # int32 rx service cycles per flit
    lat_wl: jnp.ndarray      # int32
    ctrl_cycles: jnp.ndarray  # int32 control-packet duration
    mac_token: jnp.ndarray   # bool: whole-packet token MAC [7]
    wl_sender_cap: jnp.ndarray  # bool: one flit/cycle per transmitting WI
    wl_single: jnp.ndarray   # bool: strict single shared channel
    wl_rx_busy: jnp.ndarray  # bool: serialize each receiver (non-crossbar)
    sleepy: jnp.ndarray      # bool
    # trace tables: phase barriers + multicast groups (see traffic.py).
    # For non-trace traffic these are all-zero/empty-semantics and the
    # step reduces bitwise to the unicast open-loop engine.
    phases: jnp.ndarray      # [N, K] phase id per packet slot
    phase_need: jnp.ndarray  # [P] ejections closing each phase
    n_phases: jnp.ndarray    # scalar int32 (0 = open-loop, no gating)
    mc_member: jnp.ndarray   # [M, WMAX] bool: receiver-WI set per group
    mc_dst: jnp.ndarray      # [M, WMAX] final dst switch of the copy at WI w
    mc_route: jnp.ndarray    # [M] pre-air routing anchor switch
    mc_prim: jnp.ndarray     # [M] lowest member WI (energy-primary copy)
    # memory tables: closed-loop request/reply (see traffic.py).  Inert
    # (lens == pkt_len, mem_op == 0) for open-loop tables; the step only
    # compiles the closed-loop path when ``mem_on`` is set.
    lens: jnp.ndarray        # [N, K] per-slot packet length in flits
    mem_op: jnp.ndarray      # [N, K] MEM_* op code (0 = none)
    mem_ch: jnp.ndarray      # [N, K] pseudo-channel of a request
    mem_bank: jnp.ndarray    # [N, K] bank within the channel
    mem_row: jnp.ndarray     # [N, K] DRAM row (row-buffer hit detection)
    reply_row: jnp.ndarray   # [N, K] paired reply source row (-1)
    reply_slot: jnp.ndarray  # [N, K] paired reply slot in that row (-1)
    req_src: jnp.ndarray     # [N, K] requester row to credit (reply slots)
    req_birth: jnp.ndarray   # [N, K] request birth cycle (reply slots)
    stack_sw: jnp.ndarray    # [Y] stack base-logic-die switch (pad S-1)
    t_row_hit: jnp.ndarray   # scalar i32: open-row service cycles
    t_row_miss: jnp.ndarray  # scalar i32: closed-row service cycles
    max_outst: jnp.ndarray   # scalar i32: per-core in-flight cap
    # lossy PHY tables (ISSUE 4; see repro.phy).  Inert unless the
    # static ``phy_on`` flag compiles the ARQ path; ``rx_hold`` is also
    # raised (alone) for multicast tables — store-and-forward receivers
    # (the one-shot all-reduce livelock fix, see module docstring).
    # Multicast tables run broadcast ARQ over the same per-pair tables
    # (ISSUE 6): group service/PER threshold = max over the member links.
    wl_serv: jnp.ndarray     # [WMAX, WMAX] flit cycles per (src, dst) WI
    wl_perq: jnp.ndarray     # [WMAX, WMAX] 16-bit PER threshold per link
    rx_hold: jnp.ndarray     # bool: rx slots hold whole packets
    max_retx: jnp.ndarray    # scalar i32: ARQ attempt bound per packet
    phy_seed: jnp.ndarray    # scalar u32: CRC hash seed
    ctrl_flits: jnp.ndarray  # scalar i32: control-packet length in flits
    # living-channel tables (ISSUE 6; see repro.phy.living).  Placeholder
    # shapes unless the point is living (SNR drift and/or in-scan rate
    # re-selection) — the static ``living`` flag compiles the window
    # updates, and the dynamic carry tables replace wl_serv/wl_perq.
    wl_rate0: jnp.ndarray    # [WMAX, WMAX] i32 host-selected rate entry
    wl_snr_q: jnp.ndarray    # [WMAX, WMAX] i32 undrifted SNR, 1/SNR_Q dB
    wl_serv_r: jnp.ndarray   # [R] i32 flit cycles per rate entry
    wl_perq_r: jnp.ndarray   # [R, WMAX, WMAX] i32 PER threshold per entry
    wl_gp_q: jnp.ndarray     # [R, WMAX, WMAX] i32 quantized goodput
    wl_perq_lut: jnp.ndarray  # [R, L] i32 PER threshold on the SNR grid
    wl_gp_lut: jnp.ndarray   # [R, L] i32 quantized goodput on the SNR grid
    wl_drift_amp_q: jnp.ndarray  # i32 aging amplitude, 1/SNR_Q dB (0 = static)
    wl_drift_period: jnp.ndarray  # i32 windows between drift knots


class SimState(NamedTuple):
    # per (buffer, vc)
    pkt_src: jnp.ndarray      # [B, V] int32, -1 = free
    pkt_idx: jnp.ndarray      # [B, V]
    pkt_dst: jnp.ndarray      # [B, V]
    born: jnp.ndarray         # [B, V]
    out_o: jnp.ndarray        # [B, V] routing output id
    out_buf: jnp.ndarray      # [B, V]
    out_wo: jnp.ndarray       # [B, V]
    out_is_wl: jnp.ndarray    # [B, V] bool
    out_is_ej: jnp.ndarray    # [B, V] bool
    out_vc: jnp.ndarray       # [B, V] int32, -1 = unallocated
    phase2: jnp.ndarray       # [B, V] bool: packet already crossed wireless
    rcvd: jnp.ndarray         # [B, V]
    sent: jnp.ndarray         # [B, V]
    src_of: jnp.ndarray       # [B, V] flat upstream slot feeding this vc (-1)
    mc_id: jnp.ndarray        # [B, V] multicast group id (-1 = unicast)
    attempt: jnp.ndarray      # [B, V] ARQ attempt of the wireless hop
    pipe: jnp.ndarray         # [B, V, DMAX]
    busy_until: jnp.ndarray   # [B]
    wl_busy_until: jnp.ndarray  # scalar: shared-channel mode
    pair_busy: jnp.ndarray    # [WMAX, WMAX] per-(src, dst) WI busy-until
    # injection
    q_head: jnp.ndarray       # [N]
    inj_vc: jnp.ndarray       # [N]
    inj_pushed: jnp.ndarray   # [N]
    # phase barrier (trace tables)
    cur_phase: jnp.ndarray    # scalar: currently open phase
    phase_del: jnp.ndarray    # scalar: ejections in the open phase
    phase_end: jnp.ndarray    # [P] completion cycle + 1 (0 = not done)
    phase_flits: jnp.ndarray  # [P] flits delivered while phase was open
    # closed-loop memory dynamics (memory tables)
    rdy: jnp.ndarray          # [N, K] reply birth cycle (NO_PKT = ungated)
    dead: jnp.ndarray         # [N, K] bool: tombstoned reply slot — its
    #                           request was ARQ-dropped; injection skips it
    outst: jnp.ndarray        # [N] in-flight memory transactions
    bank_busy: jnp.ndarray    # [Y, CH, BK] bank busy-until cycle
    bank_row: jnp.ndarray     # [Y, CH, BK] open row per bank (-1 = closed)
    # closed-loop memory stats
    outst_peak: jnp.ndarray   # [N] max in-flight ever (cap assertion)
    amat_sum: jnp.ndarray     # f32: read round-trip cycles (birth->reply)
    amat_pkts: jnp.ndarray
    mem_reads: jnp.ndarray    # [Y] read requests serviced
    mem_writes: jnp.ndarray   # [Y] writes serviced
    mem_row_hits: jnp.ndarray  # [Y] open-row hits
    mem_q_sum: jnp.ndarray    # [Y] f32: bank queue-wait cycles
    mem_svc_sum: jnp.ndarray  # [Y] f32: bank service cycles
    mem_flits: jnp.ndarray    # [Y] data flits served (replies + writes)
    # stats (post-warmup)
    flits_inj: jnp.ndarray
    flits_del: jnp.ndarray
    pkts_del: jnp.ndarray
    lat_sum: jnp.ndarray      # float32
    lat_pkts: jnp.ndarray
    counts_into: jnp.ndarray  # [B] link-traversal events
    count_switch: jnp.ndarray
    ctrl_count: jnp.ndarray
    wl_tx_flits: jnp.ndarray  # wireless flit *transmissions* (sender side)
    wl_rx_flits: jnp.ndarray  # wireless flit receptions (multicast: copies)
    awake_cycles: jnp.ndarray
    sleep_cycles: jnp.ndarray
    # lossy-PHY stats (zero unless phy_on)
    wl_pair_flits: jnp.ndarray  # [WMAX, WMAX] flit attempts per link
    wl_fail_flits: jnp.ndarray  # [WMAX, WMAX] flits of CRC-failing attempts
    wl_pkts: jnp.ndarray      # packets that crossed the air (CRC pass)
    wl_nacks: jnp.ndarray     # failed attempts (NACK events)
    pkts_dropped: jnp.ndarray  # packets dropped at max_retx
    wl_drop_flits: jnp.ndarray  # payload flits lost to ARQ drops (x group
    #                             members for multicast — undelivered
    #                             receptions, mirroring wl_rx_flits)
    mem_drop_reads: jnp.ndarray  # read round trips lost to ARQ drops
    # living-channel dynamics (placeholder shapes unless ``living``):
    # the current per-pair link tables, refreshed per scan window
    wl_serv_d: jnp.ndarray    # [WMAX, WMAX] i32 current flit cycles
    wl_perq_d: jnp.ndarray    # [WMAX, WMAX] i32 current PER threshold
    wl_rate_d: jnp.ndarray    # [WMAX, WMAX] i32 current rate entry
    wl_resel: jnp.ndarray     # scalar: in-scan rate re-selections
    wl_rate_flits: jnp.ndarray  # [R] flit attempts per rate entry
    wl_rate_fail: jnp.ndarray   # [R] failing-attempt flits per rate entry
    # driver metadata (filled by the chunked/monolithic drivers, not the
    # step): the lane's semantic cycle budget and where the outer loop
    # actually stopped (chunk granularity; == budget without early drain)
    cycles_run: jnp.ndarray   # scalar i32
    drain_cycle: jnp.ndarray  # scalar i32


def init_state(B: int, N: int, P: int = 1, K: int = 1, Y: int = 1,
               BK: int = 1, mem_on: bool = False,
               phy_on: bool = False, living: bool = False,
               R: int = 1) -> SimState:
    """Zero state.  Carry slimming (ISSUE 5): small-enum per-slot fields
    are i8/i16 (both engines agree, so the differential tests compare
    bitwise), and the closed-loop memory / lossy-PHY / living-channel
    state blocks shrink to placeholder scalars when their path is not
    compiled — the step only reads them under the matching static flag,
    and ``mem_on`` / ``phy_on`` / ``living`` are already part of the
    batch shape key.  The living dynamic tables start zeroed: the window
    update fires at ``t == 0`` before any read (window 0 seeds the rate
    from the host selection, ``SimStatic.wl_rate0``)."""
    i32, i16, i8 = jnp.int32, jnp.int16, jnp.int8

    def zBV():
        # a fresh buffer per leaf: the jitted drivers donate the state,
        # and XLA rejects donating one aliased buffer twice
        return jnp.zeros((B, V), i32)

    NK = (N, K) if mem_on else (1, 1)
    YCB = (Y, MEM_CH, BK) if mem_on else (1, 1, 1)
    WW = (WMAX, WMAX) if phy_on else (1, 1)
    WWL = (WMAX, WMAX) if living else (1, 1)
    RL = (R,) if living else (1,)
    return SimState(
        pkt_src=jnp.full((B, V), -1, i32), pkt_idx=zBV(), pkt_dst=zBV(),
        born=zBV(), out_o=zBV(), out_buf=zBV(), out_wo=zBV(),
        out_is_wl=jnp.zeros((B, V), bool), out_is_ej=jnp.zeros((B, V), bool),
        out_vc=jnp.full((B, V), -1, i8),
        phase2=jnp.zeros((B, V), bool), rcvd=zBV(), sent=zBV(),
        src_of=jnp.full((B, V), -1, i32), mc_id=jnp.full((B, V), -1, i32),
        attempt=jnp.zeros((B, V), i16),
        pipe=jnp.zeros((B, V, DMAX), i8), busy_until=jnp.zeros((B,), i32),
        wl_busy_until=jnp.int32(0),
        pair_busy=jnp.zeros(WW, i32),
        q_head=jnp.zeros((N,), i32), inj_vc=jnp.full((N,), -1, i8),
        inj_pushed=jnp.zeros((N,), i16),
        cur_phase=jnp.int32(0), phase_del=jnp.int32(0),
        phase_end=jnp.zeros((P,), i32), phase_flits=jnp.zeros((P,), i32),
        rdy=jnp.full(NK, NO_PKT, i32),
        dead=jnp.zeros(NK, bool), outst=jnp.zeros((N,), i32),
        bank_busy=jnp.zeros(YCB, i32),
        bank_row=jnp.full(YCB, -1, i32),
        outst_peak=jnp.zeros((N,), i32),
        amat_sum=jnp.float32(0), amat_pkts=jnp.int32(0),
        mem_reads=jnp.zeros((Y,), i32), mem_writes=jnp.zeros((Y,), i32),
        mem_row_hits=jnp.zeros((Y,), i32),
        mem_q_sum=jnp.zeros((Y,), jnp.float32),
        mem_svc_sum=jnp.zeros((Y,), jnp.float32),
        mem_flits=jnp.zeros((Y,), i32),
        flits_inj=jnp.int32(0), flits_del=jnp.int32(0), pkts_del=jnp.int32(0),
        lat_sum=jnp.float32(0), lat_pkts=jnp.int32(0),
        counts_into=jnp.zeros((B,), i32), count_switch=jnp.int32(0),
        ctrl_count=jnp.int32(0),
        wl_tx_flits=jnp.int32(0), wl_rx_flits=jnp.int32(0),
        awake_cycles=jnp.int32(0), sleep_cycles=jnp.int32(0),
        wl_pair_flits=jnp.zeros(WW, i32),
        wl_fail_flits=jnp.zeros(WW, i32),
        wl_pkts=jnp.int32(0), wl_nacks=jnp.int32(0),
        pkts_dropped=jnp.int32(0),
        wl_drop_flits=jnp.int32(0), mem_drop_reads=jnp.int32(0),
        wl_serv_d=jnp.zeros(WWL, i32), wl_perq_d=jnp.zeros(WWL, i32),
        wl_rate_d=jnp.zeros(WWL, i32), wl_resel=jnp.int32(0),
        wl_rate_flits=jnp.zeros(RL, i32), wl_rate_fail=jnp.zeros(RL, i32),
        cycles_run=jnp.int32(0), drain_cycle=jnp.int32(0),
    )


def _route_fields(ss: SimStatic, at_switch: jnp.ndarray, dst: jnp.ndarray):
    """Gather routing decision for packets at `at_switch` going to `dst`."""
    oo = ss.next_out[at_switch, dst]
    return (oo,) + arbitrate.take_rows(oo, ss.o_buf, ss.o_wo, ss.o_is_wl,
                                       ss.o_is_ej)


def make_step(B: int, mem_on: bool = False, phy_on: bool = False,
              drift_on: bool = False, reselect: bool = False):
    """Build the per-cycle transition function (shapes baked in).

    Scatter-free: arbitration winners are found by masked min over static
    candidate sets using unique priority codes (``core/arbitrate``);
    delivery uses the ``src_of`` inverse map, except that with ``phy_on``
    unicast air flits reach the rx buffers through the (sub-channel,
    receiver) air winners (see module docstring).  ``mem_on`` (static)
    compiles the closed-loop memory path — bank model, reply gating,
    outstanding-transaction cap, per-slot packet lengths; ``phy_on``
    (static) compiles the lossy-channel ARQ path — per-link rates and
    pacing, CRC retransmission, drops.  ``drift_on``/``reselect``
    (static, imply ``phy_on``) compile the living-channel path: the
    per-pair tables are read from the carry and refreshed at scan-window
    boundaries by ``phy.living.make_window_fn`` (SNR aging walk and/or
    in-scan rate re-selection).  With everything off the program is
    exactly the open-loop ideal-channel step.
    """
    living = drift_on or reselect
    assert not living or phy_on, "living channel requires the ARQ path"
    NC = B * V
    NCp1 = NC + 1
    assert NC * (NC + 1) < 2**31, \
        f"B={B}: priority codes would overflow int32 (B*V must be < 46341)"
    BIGC = jnp.int32(NC * NCp1)
    flat2d = jnp.arange(NC, dtype=jnp.int32).reshape(B, V)
    varr = jnp.arange(V, dtype=jnp.int32)
    vcol = varr[None, :]
    classA = (jnp.arange(V) < V // 2)                        # [V]
    b_ids = jnp.arange(B, dtype=jnp.int32)

    @spans.staged
    def step(ss: SimStatic, st: SimState, t: jnp.ndarray,
             stage) -> SimState:
        stage("step.arrive")
        i32 = jnp.int32
        t = t.astype(i32)
        post = (t >= ss.warmup).astype(i32)
        if living:
            stage("step.window")
            # living channel: refresh the dynamic per-pair link tables at
            # every scan-window boundary (cadence = CHUNK_CYCLES, a fixed
            # semantic constant — not the driver's execution chunk).  The
            # drain-aware driver replays the remaining boundaries after
            # an early exit (chunked.run_chunked), so chunked and
            # monolithic execution stay bitwise-equal.
            wfn = make_window_fn(ss, drift_on, reselect)
            st = jax.lax.cond(t % i32(CHUNK_CYCLES) == 0,
                              lambda s: wfn(s, t), lambda s: s, st)
            stage("step.arrive")
        rot = t % NC
        S = ss.next_out.shape[0]
        M = ss.mc_member.shape[0]
        P = ss.phase_need.shape[0]
        warr = jnp.arange(WMAX, dtype=i32)
        rx_ids = jnp.clip(ss.rx0 + warr, 0, B - 1)           # [W]

        # ---- 1. arrivals -------------------------------------------------
        arrive = st.pipe[:, :, 0]
        rcvd = st.rcvd + arrive
        pipe = jnp.concatenate(
            [st.pipe[:, :, 1:], jnp.zeros((B, V, 1), st.pipe.dtype)], axis=2)

        active = st.pkt_src >= 0
        occ = jnp.where(active, rcvd - st.sent, 0)

        # ---- 2a. output-VC claims ---------------------------------------
        stage("step.vc_claim")
        # one new downstream-VC allocation per target buffer per cycle.
        # VC classes break wormhole cycles (see module docstring): packets
        # before their wireless hop claim VCs [0, V/2), after it [V/2, V);
        # rx buffers admit any VC; pure-wired fabrics see phase2=False
        # everywhere, i.e. V/2 VCs per class as in classic escape schemes.
        free_mask = st.pkt_src < 0                               # [B, V]
        ob_c0 = jnp.clip(st.out_buf, 0, B - 1)
        tgt_rx, free_tgt = arbitrate.target_free(ss, free_mask, ob_c0)
        allowed = jnp.where(tgt_rx[..., None], True,
                            jnp.where(st.phase2[..., None], ~classA, classA))
        free_ok = free_tgt & allowed                             # [B, V, V]
        has_free_c = free_ok.any(axis=-1)
        first_free_c = jnp.argmax(free_ok, axis=-1).astype(i32)  # [B, V]
        # multicast senders (group id set, air hop ahead): need a VC at
        # EVERY member rx buffer — the claim is all-or-nothing.  A copy
        # (phase2 set at rx install) never re-triggers multicast semantics.
        is_mc = (st.mc_id >= 0) & st.out_is_wl & ~st.phase2 & active
        mcid_c = jnp.clip(st.mc_id, 0, M - 1)
        member = arbitrate.member(ss, mcid_c)                    # [B, V, W]
        free_any_rx = free_mask[rx_ids].any(axis=1)              # [W]
        free_all_mc = jnp.where(member, free_any_rx[None, None, :],
                                True).all(axis=-1)               # [B, V]
        # store-and-forward receivers (rx_hold): a slot living in an rx
        # buffer only claims its downstream VC once the whole packet has
        # arrived — the CRC check completes at the tail, and a granted
        # VC then always drains from local flits (livelock fix).
        Nn0, Kk0 = ss.phases.shape
        plen0 = ss.lens[jnp.clip(st.pkt_src, 0, Nn0 - 1),
                        jnp.clip(st.pkt_idx, 0, Kk0 - 1)] \
            if mem_on else ss.pkt_len
        hold0_ok = ~(ss.rx_hold & ss.b_is_rx[:, None]) | (rcvd >= plen0)
        need_base = active & (st.out_vc < 0) & ~st.out_is_ej & (occ > 0) \
            & (st.out_buf < B) & hold0_ok
        need_uni = need_base & ~is_mc & has_free_c
        need_mc = need_base & is_mc & free_all_mc
        need = need_uni | need_mc
        score = (flat2d - rot) % NC                              # unique/slot
        code = jnp.where(need, score * NCp1 + flat2d, BIGC)
        mcf0 = jnp.where(is_mc, st.mc_id, -1)

        # winner (min code) per wired target buffer: contenders live at the
        # buffers feeding the target's transmitting switch
        win_code_w = arbitrate.wired_winners(ss, code, st.out_buf)
        # winner per wireless rx target: contenders at sender WI switches;
        # a multicast contends at every member receiver simultaneously
        win_code_r = arbitrate.rx_winners(ss, code, st.out_buf, mcf0,
                                          sub=False)

        rx_slot = jnp.clip(b_ids - ss.rx0, 0, WMAX - 1)
        win_code = jnp.where(ss.b_is_rx, win_code_r[rx_slot], win_code_w)
        has_win = win_code < BIGC                                # [B]
        wsrc = jnp.where(has_win, win_code % NCp1, 0)            # flat slot
        # source side: my claim won iff my code is the target's winning
        # code; a multicast claim stands only if it won EVERY member
        win_all_mc = jnp.where(
            member, win_code_r[None, None, :] == code[:, :, None],
            True).all(axis=-1)                                   # [B, V]
        win_uni = need_uni & (arbitrate.take(win_code, ob_c0) == code)
        win_mc = need_mc & win_all_mc
        win = win_uni | win_mc

        # the winner's fields per target buffer ([B]), in one gather
        (w_ffc, w_src, w_idx, w_dst, w_born, w_ph2, w_mcid, w_mc,
         w_group_ok) = arbitrate.take_rows(wsrc, *(a.reshape(-1) for a in (
             first_free_c, st.pkt_src, st.pkt_idx, st.pkt_dst, st.born,
             st.phase2, st.mc_id, mcf0, win_all_mc)))

        # target side: suppress a partial multicast winner (nobody claims
        # that buffer this cycle), and deliver each member copy to its own
        # per-WI destination from the group table
        has_win_eff = has_win & ((w_mc < 0) | w_group_ok)
        vfree_self = jnp.argmax(free_mask, axis=-1).astype(i32)  # [B]
        vstar = jnp.where(ss.b_is_rx, vfree_self, w_ffc)
        claimed = has_win_eff[:, None] & (vstar[:, None] == vcol)  # [B, V]
        mc_dst_w = ss.mc_dst[jnp.clip(w_mc, 0, M - 1), rx_slot]  # [B]
        dst_w = jnp.where(ss.b_is_rx & (w_mc >= 0),
                          jnp.clip(mc_dst_w, 0, S - 1), w_dst)
        d_oo, d_ob, d_owo, d_owl, d_oej = _route_fields(ss, ss.b_dst, dst_w)

        def upd(old, val_b):
            return jnp.where(claimed, val_b[:, None], old)

        pkt_src = upd(st.pkt_src, w_src)
        pkt_idx = upd(st.pkt_idx, w_idx)
        pkt_dst = upd(st.pkt_dst, dst_w)
        born = upd(st.born, w_born)
        out_o = upd(st.out_o, d_oo.astype(i32))
        out_buf = upd(st.out_buf, d_ob.astype(i32))
        out_wo = upd(st.out_wo, d_owo.astype(i32))
        out_is_wl = upd(st.out_is_wl, d_owl)
        out_is_ej = upd(st.out_is_ej, d_oej)
        out_vc = jnp.where(claimed, -1, st.out_vc)
        phase2 = upd(st.phase2, w_ph2 | ss.b_is_rx)
        mc_id = upd(st.mc_id, w_mcid)
        attempt = jnp.where(claimed, 0, st.attempt)
        rcvd = jnp.where(claimed, 0, rcvd)
        sent = jnp.where(claimed, 0, st.sent)
        src_of = upd(st.src_of, wsrc)
        # upstream learns its allocated VC (multicast: sentinel "granted";
        # delivery is receiver-side via src_of, no per-member VC needed)
        out_vc = jnp.where(win_uni, first_free_c.astype(out_vc.dtype), out_vc)
        out_vc = jnp.where(win_mc, 0, out_vc)

        active = pkt_src >= 0
        occ = jnp.where(active, rcvd - sent, 0)

        # per-slot packet attributes, gathered from the [N, K] tables via
        # (pkt_src, pkt_idx) — same scheme the phase gather uses.  With
        # mem_on off the global packet length stands in and ejection ways
        # stay vc-assigned: the exact open-loop program.
        Nn, Kk = ss.phases.shape
        psrc_c = jnp.clip(pkt_src, 0, Nn - 1)
        pidx_c = jnp.clip(pkt_idx, 0, Kk - 1)
        way_bv = vcol % ss.b_ej_ways[:, None]                    # [B, V]
        if mem_on:
            plen_bv, op_all, ch_all, rb = arbitrate.take_rows(
                psrc_c * Kk + pidx_c, *(a.reshape(-1) for a in (
                    ss.lens, ss.mem_op, ss.mem_ch, ss.req_birth)))  # [B, V]
            op_bv = jnp.where(active, op_all, 0)
            memrq_bv = (op_bv == 1) | (op_bv == 2)
            ch_bv = jnp.clip(ch_all, 0, EJ_WAYS - 1)
            # a request's ejection way IS its pseudo-channel: per-way
            # arbitration then admits one request per (stack, ch)/cycle
            way_bv = jnp.where(memrq_bv & out_is_ej,
                               ch_bv % ss.b_ej_ways[:, None], way_bv)
        else:
            plen_bv = ss.pkt_len

        # ---- 2b. forwarding: wired links, ejection, wireless -------------
        stage("step.forward")
        inflight = pipe.sum(axis=2)                              # [B, V]
        ob_c = jnp.clip(out_buf, 0, B - 1)
        ovc_c = jnp.clip(out_vc, 0, V - 1)
        # the claimed downstream VC's state in one gather, the target
        # buffer's depth and link in another
        rcvd_d, sent_d, infl_d = arbitrate.take_rows(
            ob_c * V + ovc_c, rcvd.reshape(-1), sent.reshape(-1),
            inflight.reshape(-1))
        depth_d, busy_d, lat_d, serv_d = arbitrate.take_rows(
            ob_c, ss.b_depth, st.busy_until, ss.b_lat, ss.b_serv)
        occ_down = rcvd_d - sent_d
        space = depth_d - occ_down - infl_d
        link_free = busy_d <= t
        # multicast sender: backpressure is the MINIMUM over its member
        # copies (located via the src_of inverse map on the rx region) —
        # a broadcast flit flies only when every member can accept it
        is_mc = (mc_id >= 0) & out_is_wl & ~phase2 & active      # [B, V]
        mcid_c = jnp.clip(mc_id, 0, M - 1)
        member = arbitrate.member(ss, mcid_c)                    # [B, V, W]
        srcof_rx, occ_rx, infl_rx, depth_rx, busy_rx = arbitrate.take_rows(
            rx_ids, src_of, occ, inflight, ss.b_depth, st.busy_until)
        cp = srcof_rx[None, None, :, :] \
            == flat2d[:, :, None, None]                          # [B,V,W,V]
        BIGS = jnp.int32(1 << 30)
        cp_space = jnp.where(
            cp, (depth_rx[:, None] - occ_rx - infl_rx)[None, None],
            BIGS).min(axis=-1)                                   # [B, V, W]
        cp_space = jnp.where(cp.any(axis=-1), cp_space, 0)       # no copy yet
        space_mc = jnp.where(member, cp_space, BIGS).min(axis=-1)
        space = jnp.where(is_mc, space_mc, space)
        busy_rx_ok = busy_rx <= t                                # [W]
        lf_mc = jnp.where(member, busy_rx_ok[None, None, :],
                          True).all(axis=-1)
        link_free = jnp.where(is_mc, lf_mc, link_free)
        # token MAC: wireless transmission only once the whole packet is here
        whole = rcvd >= plen_bv
        wl_ok = ~out_is_wl | ~ss.mac_token | whole
        # single-channel mode: nothing flies while the channel is busy
        wl_ch_free = ~ss.wl_single | (st.wl_busy_until <= t)
        wl_ok &= ~out_is_wl | wl_ch_free
        # crossbar medium: receivers are not serialized
        link_free |= out_is_wl & ~ss.wl_rx_busy
        # store-and-forward receivers: rx slots forward only whole packets
        hold_ok = ~(ss.rx_hold & ss.b_is_rx[:, None]) | whole
        if phy_on:
            stage("step.phy")
            # lossy PHY: the sender holds the whole packet (ARQ needs it
            # for retransmission), the (src, dst) WI pair paces at the
            # link's selected rate, and the current attempt's CRC
            # outcome is a deterministic hash — known sender-side, so
            # failing attempts occupy the channel but deliver nothing.
            # Living points read the per-window dynamic tables instead of
            # the packed static ones (refreshed by the update above).
            serv_tab = st.wl_serv_d if living else ss.wl_serv
            perq_tab = st.wl_perq_d if living else ss.wl_perq
            # the sender WI's rows of the per-pair tables, one gather;
            # each slot's own pair is then picked by a one-hot compare
            ws_b = jnp.clip(ss.b_wi, 0, WMAX - 1)                # [B]
            wd_bv = jnp.clip(out_wo, 0, WMAX - 1)                # [B, V]
            serv_row, perq_row, pb_row = arbitrate.take_rows(
                ws_b, serv_tab, perq_tab, st.pair_busy)          # [B, W]
            wd_hot = wd_bv[..., None] == warr                    # [B, V, W]

            def at_wd(row):
                return jnp.where(wd_hot, row[:, None, :], 0).sum(axis=-1)

            serv_wl_bv = at_wd(serv_row)                         # [B, V]
            perq_bv = at_wd(perq_row)
            # broadcast ARQ (ISSUE 6): a multicast attempt is paced and
            # CRC-checked against its WORST member link — group service
            # time and PER threshold are the max over member links.  The
            # hash draw below is link-independent, so per-member
            # outcomes are comonotone: "any member fails" is exactly
            # "the worst member fails", i.e. worst-link group
            # retransmission with all-or-nothing delivery to the set.
            serv_mc = jnp.where(member, serv_row[:, None, :],
                                0).max(axis=-1)                  # [B, V]
            perq_mc = jnp.where(member, perq_row[:, None, :],
                                0).max(axis=-1)
            serv_wl_bv = jnp.where(is_mc, serv_mc, serv_wl_bv)
            perq_bv = jnp.where(is_mc, perq_mc, perq_bv)
            pb_ok = at_wd(pb_row) <= t
            wl_ok &= ~out_is_wl | (whole & pb_ok)
            # packet uid is padding-independent (pkt_idx < 2^16 always),
            # so batched and single-point runs draw identical outcomes
            uid = psrc_c * 65536 + pidx_c
            fail_bv = _crc_fail(ss.phy_seed, uid, attempt, perq_bv)
            stage("step.forward")
        elig = active & (occ > 0) & wl_ok & hold_ok \
            & (out_is_ej | ((out_vc >= 0) & (space > 0) & link_free))
        code2 = jnp.where(elig, score * NCp1 + flat2d, BIGC)
        mcf = jnp.where(is_mc, mc_id, -1)

        # wired-output winners: one flit per link per cycle
        win2_w = arbitrate.wired_winners(ss, code2, out_buf)
        # multi-channel ejection: memory stacks sink `b_ej_ways` flits/cycle
        # (4-channel DRAM stacks, paper §IV); cores sink one.  A slot's
        # ejection "way" is vc % ways (memory requests: their channel);
        # one winner per (switch, way).
        win2_ej = arbitrate.eject_winners(ss, code2, out_is_ej, way_bv)
        # wireless rx sub-channels: receiver w serves `rxw` concurrent
        # streams; a sender's stream is its WI id mod rxw.  A multicast
        # contends at every member receiver (on its own sub-channel) and
        # transmits only if it wins ALL of them — a single transmission
        # delivered to the whole receiver set.
        rxw = jnp.maximum(ss.rxw, 1)
        win2_wl = arbitrate.rx_winners(ss, code2, out_buf, mcf, sub=True)

        owo_s = jnp.clip(out_wo, 0, S - 1)                       # eject: switch
        owo_w = jnp.clip(out_wo, 0, WMAX - 1)                    # wl: dst WI
        r_mine = jnp.clip(ss.b_wi[:, None] % rxw, 0, RXWMAX - 1)
        win2_mine = arbitrate.slot_winner(
            win2_ej, win2_wl, win2_w, way_bv, owo_s, r_mine, owo_w, ob_c,
            out_is_ej, out_is_wl)
        wl_all2 = jnp.where(
            member, arbitrate.rx_row(win2_wl, r_mine, V) == code2[:, :, None],
            True).all(axis=-1)                                   # [B, V]
        fwd = elig & jnp.where(is_mc, wl_all2, code2 == win2_mine)

        # wireless sender-side cap: one flit per transmitting WI per cycle
        # (and one WI total in single-channel mode); no-op for the crossbar
        # medium
        capped = fwd & out_is_wl & ss.wl_sender_cap
        win3 = arbitrate.cap_winners(ss, jnp.where(capped, code2, BIGC))
        my3 = jnp.where(ss.wl_single, win3.min(),
                        win3[jnp.clip(ss.b_wi, 0, WMAX - 1)][:, None])
        fwd &= ~capped | (code2 == my3)
        is_wl_fwd = fwd & out_is_wl

        sent = sent + fwd.astype(i32)
        if phy_on:
            stage("step.phy")
            # CRC check on the tail of every air attempt: NACK rewinds
            # the sender (the whole packet is still buffered), the
            # bounded-ARQ loser is dropped — sender slot and the claimed
            # receiver VC are freed below, nothing was delivered.
            first_wl_phy = is_wl_fwd & (sent == 1)   # pre-rewind header
            raw_tail = fwd & (sent >= plen_bv)
            fail_tail = raw_tail & out_is_wl & fail_bv
            retx_m = fail_tail & (attempt + 1 < ss.max_retx)
            drop = fail_tail & ~retx_m
            tail = raw_tail & ~fail_tail
            sent = jnp.where(retx_m, sent - plen_bv, sent)
            attempt = jnp.where(retx_m, attempt + 1, attempt)
            wl_nacks = st.wl_nacks + post * fail_tail.sum().astype(i32)
            wl_pkts = st.wl_pkts \
                + post * (tail & out_is_wl).sum().astype(i32)
            pkts_dropped = st.pkts_dropped + post * drop.sum().astype(i32)
            # a drop's ejection(s) will never happen: count the lost
            # payload (once per member copy for multicast, mirroring
            # wl_rx_flits) so metrics can flag the trace incomplete
            member_cnt = jnp.where(is_mc, member.sum(axis=-1), 1) \
                .astype(i32)
            wl_drop_flits = st.wl_drop_flits + post * jnp.where(
                drop, plen_bv * member_cnt, 0).sum().astype(i32)
            stage("step.forward")
        else:
            tail = fwd & (sent >= plen_bv)
            wl_nacks, wl_pkts = st.wl_nacks, st.wl_pkts
            pkts_dropped = st.pkts_dropped
            wl_drop_flits = st.wl_drop_flits
        ej = fwd & out_is_ej

        # ejection stats
        flits_del = st.flits_del + post * ej.sum().astype(i32)
        tail_ej = tail & out_is_ej
        lat_ok = tail_ej & (born >= ss.warmup)
        pkts_del = st.pkts_del + post * tail_ej.sum().astype(i32)
        lat_sum = st.lat_sum + post * jnp.where(
            lat_ok, (t - born + 1).astype(jnp.float32), 0.0).sum()
        lat_pkts = st.lat_pkts + post * lat_ok.sum().astype(i32)

        # ---- phase barrier bookkeeping (trace tables; raw counts — the
        # dependency structure must not depend on the stats warm-up)
        stage("step.phase")
        phv = ss.phases[psrc_c, pidx_c]                          # [B, V]
        phase_del = st.phase_del \
            + (tail_ej & (phv == st.cur_phase)).sum().astype(i32)
        if phy_on:
            # ARQ-exhaustion drop: the ejection(s) this packet owed the
            # open phase will never happen — credit them now (one per
            # member copy for multicast, matching the trace table's
            # per-member phase_need) so a lossy trace closes its
            # barriers and drains instead of wedging forever (ISSUE 6)
            phase_del = phase_del + jnp.where(
                drop & (phv == st.cur_phase), member_cnt, 0) \
                .sum().astype(i32)
        parr = jnp.arange(P, dtype=i32)
        phase_flits = st.phase_flits + jnp.where(
            parr == st.cur_phase, ej.sum().astype(i32), 0)
        in_trace = (ss.n_phases > 0) & (st.cur_phase < ss.n_phases)
        needed = ss.phase_need[jnp.clip(st.cur_phase, 0, P - 1)]
        complete = in_trace & (phase_del >= needed)
        phase_end = jnp.where((parr == st.cur_phase) & complete,
                              t + 1, st.phase_end)
        cur_phase = st.cur_phase + complete.astype(i32)
        phase_del = jnp.where(complete, 0, phase_del)

        # ---- closed-loop memory: bank model + reply gating (mem tables)
        stage("step.memory")
        rdy, outst, dead = st.rdy, st.outst, st.dead
        bank_busy, bank_row = st.bank_busy, st.bank_row
        amat_sum, amat_pkts = st.amat_sum, st.amat_pkts
        mem_reads, mem_writes = st.mem_reads, st.mem_writes
        mem_row_hits = st.mem_row_hits
        mem_q_sum, mem_svc_sum = st.mem_q_sum, st.mem_svc_sum
        mem_flits = st.mem_flits
        if mem_on:
            f32 = jnp.float32
            NOPKT = jnp.int32(NO_PKT)
            Yp, _, BKp = bank_busy.shape
            psrcf = pkt_src.reshape(-1)
            pidxf = pkt_idx.reshape(-1)
            tailf = tail.reshape(-1)
            # (a) request arrivals: the ejection winner at (stack switch,
            # way=channel) is the unique request entering (stack, ch)
            # this cycle; everything below is gathers + elementwise
            # one-assignment updates over the [Y, CH(, BK)] grids.
            code_yc = win2_ej[:, jnp.clip(ss.stack_sw, 0, S - 1)].T
            valid = code_yc < BIGC                               # [Y, CH]
            slot_yc = jnp.where(valid, code_yc % NCp1, 0)
            src_w, idx_w, tail_w = arbitrate.take_rows(
                slot_yc, psrcf, pidxf, tailf)                    # [Y, CH]
            n_w = jnp.clip(src_w, 0, Nn - 1)
            k_w = jnp.clip(idx_w, 0, Kk - 1)
            op_w, bank_w, row_w, rrow, rslot, len_w = arbitrate.take_rows(
                n_w * Kk + k_w, *(a.reshape(-1) for a in (
                    ss.mem_op, ss.mem_bank, ss.mem_row, ss.reply_row,
                    ss.reply_slot, ss.lens)))
            opw = jnp.where(valid & tail_w, op_w, 0)
            is_rq = (opw == 1) | (opw == 2)
            bank_w = jnp.clip(bank_w, 0, BKp - 1)
            bb = jnp.take_along_axis(
                bank_busy, bank_w[:, :, None], axis=2)[:, :, 0]
            br = jnp.take_along_axis(
                bank_row, bank_w[:, :, None], axis=2)[:, :, 0]
            hit = is_rq & (br == row_w)
            svc = jnp.where(hit, ss.t_row_hit, ss.t_row_miss)
            start = jnp.maximum(t + 1, bb)
            done = start + svc                                   # [Y, CH]
            oneh = jnp.arange(BKp)[None, None, :] == bank_w[:, :, None]
            updm = is_rq[:, :, None] & oneh
            bank_busy = jnp.where(updm, done[:, :, None], bank_busy)
            bank_row = jnp.where(updm, row_w[:, :, None], bank_row)
            # reply birth: one-assignment min into the paired slot's rdy
            rrow = jnp.clip(rrow, 0, Nn - 1)
            rslot = jnp.clip(rslot, 0, Kk - 1)
            rflat = jnp.where(is_rq, rrow * Kk + rslot, -1).reshape(-1)
            m_rdy = jnp.arange(Nn * Kk, dtype=i32)[:, None] == rflat[None]
            val = jnp.where(m_rdy, done.reshape(-1)[None], NOPKT).min(axis=1)
            rdy = jnp.minimum(rdy, val.reshape(Nn, Kk))
            # per-stack service stats
            rd_w = is_rq & (opw == 1)
            wr_w = is_rq & (opw == 2)
            mem_reads = mem_reads + post * rd_w.sum(1).astype(i32)
            mem_writes = mem_writes + post * wr_w.sum(1).astype(i32)
            mem_row_hits = mem_row_hits + post * hit.sum(1).astype(i32)
            postf = post.astype(f32)
            mem_q_sum = mem_q_sum + postf * jnp.where(
                is_rq, (start - (t + 1)).astype(f32), 0.0).sum(1)
            mem_svc_sum = mem_svc_sum + postf * jnp.where(
                is_rq, svc.astype(f32), 0.0).sum(1)
            data_w = jnp.where(rd_w, ss.lens[rrow, rslot],
                               jnp.where(wr_w, len_w, 0))
            mem_flits = mem_flits + post * data_w.sum(1).astype(i32)
            # (b) reply/ack completion at the requester: AMAT + credit
            is_rep = tail_ej & ((op_all == 3) | (op_all == 4))
            amat_ok = is_rep & (op_all == 3) & (rb >= ss.warmup)
            amat_sum = amat_sum + post * jnp.where(
                amat_ok, (t - rb + 1).astype(f32), 0.0).sum()
            amat_pkts = amat_pkts + post * amat_ok.sum().astype(i32)
            # outstanding credit: the requester's switch saw at most one
            # ejection tail per way; check each winner against req_src
            code_ns = win2_ej[:, jnp.clip(ss.src_switch, 0, S - 1)]
            v_ns = code_ns < BIGC                                # [EJ, N]
            slot_ns = jnp.where(v_ns, code_ns % NCp1, 0)
            rep_s, src_s, idx_s = arbitrate.take_rows(
                slot_ns, is_rep.reshape(-1), psrcf, pidxf)       # [EJ, N]
            rep_ns = v_ns & rep_s
            req_ns = ss.req_src[jnp.clip(src_s, 0, Nn - 1),
                                jnp.clip(idx_s, 0, Kk - 1)]
            Narr = jnp.arange(ss.src_switch.shape[0], dtype=i32)
            dec = (rep_ns & (req_ns == Narr[None, :])).sum(0).astype(i32)
            outst = outst - dec

        # ---- 2b, continued: delivery downstream --------------------------
        stage("step.forward")
        # non-eject: deliver downstream via the src_of inverse map — each
        # target (buffer, vc) gathers from the unique upstream slot feeding
        # it (identity-checked against out_buf/out_vc to survive slot reuse).
        # Over the lossy channel, unicast air flits into rx buffers come
        # instead from the (sub-channel, receiver) air winners (below).
        if phy_on:
            stage("step.phy")
            # per-link rate: serialization and control-packet time follow
            # the (src, dst) WI pair's selected rate from the PHY table
            first_wl = first_wl_phy
            ctrl_bv = jnp.maximum(1, ss.ctrl_flits * serv_wl_bv)
            lat_wl_bv = (ss.lat_wl - ss.serv_wl) + serv_wl_bv
            stage("step.forward")
        else:
            first_wl = is_wl_fwd & (sent == 1)   # header => control packet
            ctrl_bv = ss.ctrl_cycles
            lat_wl_bv = ss.lat_wl
            serv_wl_bv = ss.serv_wl
        lat_t = jnp.where(out_is_wl, lat_wl_bv, lat_d) \
            + jnp.where(first_wl & ~ss.wl_rx_busy, ctrl_bv, 0)
        serv_t = jnp.where(out_is_wl, serv_wl_bv, serv_d) \
            + jnp.where(first_wl, ctrl_bv, 0)

        sv = jnp.clip(src_of, 0, NC - 1)
        feed_f = (is_mc, out_buf, out_vc, mc_id, fwd, out_is_wl, lat_t,
                  serv_t, tail)
        if phy_on:
            stage("step.phy")
            # failing attempts occupy the channel/receiver but deliver
            # nothing; the dropped packet's receiver VC is freed below
            deliver = fwd & ~(out_is_wl & fail_bv)
            feed_f += (deliver, drop)
            stage("step.forward")
        # the feeding slot's fields, in one gather at sv
        (f_mc, f_ob, f_ovc, f_mcid, f_fwd, f_wl, f_lat, f_serv, f_tail,
         *f_phy) = arbitrate.take_rows(sv, *(x.reshape(-1) for x in feed_f))
        # unicast identity: the upstream slot still targets me at my VC.
        # multicast copy identity: my feeder is a multicast-air sender of
        # my own group (one transmission fans out to every member copy).
        ident_uni = (src_of >= 0) & ~f_mc & (f_ob == b_ids[:, None]) \
            & (f_ovc == vcol)
        ident_mc = (src_of >= 0) & f_mc & ss.b_is_rx[:, None] \
            & (mc_id >= 0) & (f_mcid == mc_id)
        ident = ident_uni | ident_mc
        if phy_on:
            # unicast air flits reach the rx buffers sender-side (below)
            feed = ident_mc | (ident_uni & ~ss.b_is_rx[:, None])
        else:
            feed = ident
        incoming_any = feed & f_fwd                              # [B, V]
        busy_until = st.busy_until
        if phy_on:
            stage("step.phy")
            f_deliver, f_drop = f_phy
            incoming = feed & f_deliver
            # a unicast air flit lands in the receiver VC its sender
            # names (out_buf, out_vc), whoever holds that VC now: src_of
            # names only its newest feeder (module docstring, Lossy PHY).
            # Every such sender is the air winner of its (sub-channel,
            # receiver) cell.
            def at_rx(x):    # [W, ...] per receiver -> its rx buffer's row
                pad = jnp.zeros((B + WMAX,) + x.shape[1:], x.dtype)
                at = (ss.rx0,) + (jnp.int32(0),) * (x.ndim - 1)
                return jax.lax.dynamic_update_slice(pad, x, at)[:B]

            wa_ok = win2_wl < BIGC                               # [RXW, W]
            wa = jnp.where(wa_ok, win2_wl % NCp1, 0)
            # the air winners' fields ([RXW, W]), in one gather at wa
            wa_f = (fwd, out_is_wl, fail_bv, drop, serv_t, is_mc, ovc_c,
                    lat_t, wd_bv, jnp.broadcast_to(ss.b_wi[:, None], (B, V)))
            if mem_on:
                wa_f += (pkt_src, pkt_idx)
            (a_fwd, a_wl, fail_a, drop_a, serv_a, a_mc, a_ovc, a_lat, a_wd,
             a_wi, *a_pkt) = arbitrate.take_rows(
                 wa, *(x.reshape(-1) for x in wa_f))

            on_air = wa_ok & a_fwd & a_wl
            air = on_air & ~a_mc
            air_in = air & ~fail_a
            air_vc = a_ovc[:, :, None] == varr                   # [RXW, W, V]
            air_d = jnp.clip(a_lat - 1, 0, DMAX - 1)
            air_pipe = (air_in[:, :, None, None] & air_vc[:, :, :, None]
                        & (air_d[:, :, None, None] == jnp.arange(DMAX))
                        ).sum(axis=0).astype(pipe.dtype)         # [W, V, D]
            pipe = pipe + at_rx(air_pipe)
            air_n = at_rx(air_in.sum(axis=0).astype(i32))        # [B]
            rx_dropped = (ident_mc & f_drop) | at_rx(
                ((air & drop_a)[:, :, None] & air_vc).any(axis=0))
            air_ser = air & ss.wl_rx_busy
            busy_until = jnp.where(
                at_rx(air_ser.any(axis=0)),
                t + at_rx(jnp.where(air_ser, serv_a, 0).sum(axis=0)),
                busy_until)
            stage("step.forward")
        else:
            incoming = incoming_any
        d_in = jnp.clip(f_lat - 1, 0, DMAX - 1)
        pipe = pipe + (incoming[:, :, None]
                       & (jnp.arange(DMAX) == d_in[:, :, None])
                       ).astype(pipe.dtype)
        # crossbar: wireless winners do not serialize the receiver
        ser_in = incoming_any & (~f_wl | ss.wl_rx_busy)
        busy_until = jnp.where(
            ser_in.any(axis=1),
            t + jnp.where(ser_in, f_serv, 0).sum(axis=1), busy_until)
        wl_busy_until = jnp.where(
            is_wl_fwd.any(),
            t + (jnp.where(is_wl_fwd, serv_t, 0)).max(), st.wl_busy_until)
        # transmit energy is paid once per broadcast: only the group's
        # primary copy (lowest member WI) counts the wireless traversal
        prim_buf = ss.rx0 + ss.mc_prim[mcid_c]                   # [B, V]
        count_ok = ~((mc_id >= 0) & ss.b_is_rx[:, None]
                     & (b_ids[:, None] != prim_buf))
        counts_into = st.counts_into \
            + post * (incoming & count_ok).sum(axis=1).astype(i32)
        count_switch = st.count_switch + post * fwd.sum().astype(i32)
        ctrl_count = st.ctrl_count + post * first_wl.sum().astype(i32)
        wl_tx_flits = st.wl_tx_flits + post * is_wl_fwd.sum().astype(i32)
        wl_rx_flits = st.wl_rx_flits \
            + post * (incoming & ss.b_is_rx[:, None]).sum().astype(i32)
        mem_drop_reads = st.mem_drop_reads
        wl_rate_flits = st.wl_rate_flits
        wl_rate_fail = st.wl_rate_fail
        if phy_on:
            stage("step.phy")
            # the unicast air flits delivered sender-side (above)
            counts_into = counts_into + post * air_n
            wl_rx_flits = wl_rx_flits + post * air_n.sum()
            # per-(src WI, dst WI) pacing + energy counters, scatter-free:
            # the (sub-channel, receiver) air winner is unique, so each
            # pair sees at most one transmission per cycle — a masked
            # one-assignment over the [W, W] grid (cf. the memory path's
            # per-(stack, channel) ejection winners).  A multicast winner
            # appears in EVERY member receiver's column; the air/pair
            # accounting anchors it on the routed (sender, anchor) pair
            # once — the own-column check is a no-op for unicast, whose
            # winning column IS its destination.  Sender WI ws reads the
            # winners' fields above on its own sub-channel ws % rxw.
            ws_ids = jnp.arange(WMAX, dtype=i32)[:, None]        # [W, 1]
            mine = (jnp.clip(ws_ids % rxw, 0, RXWMAX - 1)
                    == jnp.arange(RXWMAX))[:, :, None]           # [W, RXW, 1]

            def by_sender(x):    # [RXW, W] -> [W, W]
                if x.dtype == jnp.bool_:
                    return (mine & x[None]).any(axis=1)
                return jnp.where(mine, x[None], 0).sum(axis=1)

            txp = by_sender(on_air & (a_wd == warr)) \
                & (by_sender(a_wi) == ws_ids)
            failp = txp & by_sender(fail_a)
            pair_busy = jnp.where(txp, t + by_sender(serv_a), st.pair_busy)
            wl_pair_flits = st.wl_pair_flits + post * txp.astype(i32)
            wl_fail_flits = st.wl_fail_flits + post * failp.astype(i32)
            if living:
                # per-rate-entry attempt counters: when the pair's entry
                # moves mid-run the per-pair counters no longer identify
                # a single rate, so metrics needs the exact [R] split
                # (attributed to the anchor pair's current entry)
                rhot = jnp.arange(wl_rate_flits.shape[0],
                                  dtype=i32)[:, None, None] \
                    == st.wl_rate_d[None]
                wl_rate_flits = wl_rate_flits + post * jnp.where(
                    rhot & txp[None], 1, 0).sum(axis=(1, 2))
                wl_rate_fail = wl_rate_fail + post * jnp.where(
                    rhot & failp[None], 1, 0).sum(axis=(1, 2))
            if mem_on:
                # ARQ drop of a memory request/reply: the sender observes
                # the drop (instant NACK), so the requester's outstanding
                # window is credited back immediately, and a dropped
                # *request's* pre-allocated reply slot is tombstoned so
                # the stack's in-order reply channel skips it instead of
                # wedging behind a birth that will never come.  Every
                # drop is an air-pair winner, so the [W, W] grid sees
                # each one exactly once (gather style; the reference
                # engine scatters the same updates).
                d_on = txp & by_sender(drop_a)                   # [W, W]
                nd = jnp.clip(by_sender(a_pkt[0]), 0, Nn - 1)
                kd = jnp.clip(by_sender(a_pkt[1]), 0, Kk - 1)
                op_d, rq_d, rr_d, rs_d = arbitrate.take_rows(
                    nd * Kk + kd, *(a.reshape(-1) for a in (
                        ss.mem_op, ss.req_src, ss.reply_row,
                        ss.reply_slot)))                         # [W, W]
                opd = jnp.where(d_on, op_d, 0)
                is_rqd = (opd == 1) | (opd == 2)
                is_repd = (opd == 3) | (opd == 4)
                tgt_d = jnp.where(
                    is_rqd, nd,
                    jnp.where(is_repd, jnp.clip(rq_d, 0, Nn - 1), -1))
                Nar = jnp.arange(Nn, dtype=i32)
                outst = outst - (tgt_d[None] == Nar[:, None, None]) \
                    .sum(axis=(1, 2)).astype(i32)
                rrd = jnp.clip(rr_d, 0, Nn - 1)
                rsd = jnp.clip(rs_d, 0, Kk - 1)
                dflat = jnp.where(is_rqd, rrd * Kk + rsd, -1).reshape(-1)
                dead = dead | (jnp.arange(Nn * Kk, dtype=i32)[:, None]
                               == dflat[None]).any(1).reshape(Nn, Kk)
                # lost read round trips: a dropped read request or read
                # reply means the requester never sees its data
                mem_drop_reads = mem_drop_reads + post * (
                    d_on & ((opd == 1) | (opd == 3))).sum().astype(i32)
            stage("step.forward")
        else:
            pair_busy = st.pair_busy
            wl_pair_flits = st.wl_pair_flits
            wl_fail_flits = st.wl_fail_flits
        # the feeding packet's tail has been sent: the link is quiet again
        src_of = jnp.where(ident & f_tail, -1, src_of)

        # free VCs whose tail left (phy: also ARQ-dropped senders and
        # the receiver VCs their claims held)
        freed = tail
        if phy_on:
            freed = tail | drop | rx_dropped
            src_of = jnp.where(rx_dropped, -1, src_of)
        pkt_src = jnp.where(freed, -1, pkt_src)
        out_vc = jnp.where(freed, -1, out_vc)
        out_is_wl = jnp.where(freed, False, out_is_wl)
        out_is_ej = jnp.where(freed, False, out_is_ej)

        # ---- 3. injection -------------------------------------------------
        stage("step.inject")
        N, K = ss.births.shape
        n_ar = jnp.arange(N, dtype=i32)
        qh = jnp.clip(st.q_head, 0, K - 1)
        birth_n = ss.births[n_ar, qh]
        # source n feeds buffer Lw + n (pack): the two trade values as that
        # block, at the traced Lw; rows outside it are masked by n_valid
        lw = ss.inj_buf[0]
        nb = jnp.clip(ss.inj_src, 0, N - 1)                     # [B]
        n_valid = ss.inj_src >= 0

        def blk(x):                                  # [B, ...] -> [N, ...]
            return jax.lax.dynamic_slice_in_dim(x, lw, N)

        def gn(x):                       # [N] -> [B]: x[n] at Lw + n, else 0
            return jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros(B, x.dtype), x, lw, 0)

        ifree = (blk(pkt_src) < 0) & classA[None, :]            # [N, V]
        ihas = ifree.any(axis=1)
        ivc = jnp.argmax(ifree, axis=1).astype(i32)
        # phase gate: a packet injects only once its phase is open
        ph_ok = (ss.n_phases == 0) | (ss.phases[n_ar, qh] <= cur_phase)
        if mem_on:
            # reply slots are born when the bank model services their
            # request (rdy); requests gate on the in-flight window
            birth_n = jnp.minimum(birth_n, rdy[n_ar, qh])
            opq = ss.mem_op[n_ar, qh]
            is_tx = (opq == 1) | (opq == 2)
            ph_ok &= ~is_tx | (outst < ss.max_outst)
        can_new = (st.inj_vc < 0) & (st.q_head < K) & (birth_n <= t) \
            & ihas & ph_ok
        # multicast slots encode the group as dests = -(1 + m); the packet
        # routes to the group's anchor and fans out at the air hop
        dst_raw = ss.dests[n_ar, qh]
        mcv_n = jnp.where(dst_raw < 0, -(dst_raw + 1), -1)      # [N]
        dst_n = jnp.where(
            dst_raw < 0, ss.mc_route[jnp.clip(mcv_n, 0, M - 1)], dst_raw)
        r_oo, r_ob, r_owo, r_owl, r_oej = _route_fields(
            ss, ss.src_switch, dst_n)
        icl = (n_valid & gn(can_new))[:, None] & (gn(ivc)[:, None] == vcol)

        def iupd(old, val_n):
            return jnp.where(icl, gn(val_n)[:, None], old)

        pkt_src = jnp.where(icl, nb[:, None], pkt_src)
        pkt_idx = iupd(pkt_idx, st.q_head)
        pkt_dst = iupd(pkt_dst, dst_n)
        born = iupd(born, birth_n)
        out_o = iupd(out_o, r_oo.astype(i32))
        out_buf = iupd(out_buf, r_ob.astype(i32))
        out_wo = iupd(out_wo, r_owo.astype(i32))
        out_is_wl = iupd(out_is_wl, r_owl)
        out_is_ej = iupd(out_is_ej, r_oej)
        out_vc = jnp.where(icl, -1, out_vc)
        phase2 = jnp.where(icl, False, phase2)
        mc_id = iupd(mc_id, mcv_n)
        attempt = jnp.where(icl, 0, attempt)
        rcvd = jnp.where(icl, 0, rcvd)
        sent = jnp.where(icl, 0, sent)
        src_of = jnp.where(icl, -1, src_of)
        inj_vc = jnp.where(can_new, ivc.astype(st.inj_vc.dtype), st.inj_vc)
        inj_pushed = jnp.where(can_new, 0, st.inj_pushed)
        q_head = st.q_head + can_new.astype(i32)
        if mem_on and phy_on:
            # tombstoned reply slots (request ARQ-dropped) never birth:
            # advance past them so the in-order channel keeps flowing
            skip = (st.inj_vc < 0) & (st.q_head < K) & dead[n_ar, qh]
            q_head = q_head + skip.astype(i32)
        outst_peak = st.outst_peak
        if mem_on:
            outst = outst + (can_new & is_tx).astype(i32)
            outst_peak = jnp.maximum(outst_peak, outst)

        # push one flit/cycle/core while there is space (cores write straight
        # into their injection buffer — no pipe, so no src_of either)
        iv_c = jnp.clip(inj_vc, 0, V - 1)
        iocc = jnp.where(iv_c[:, None] == vcol, blk(rcvd - sent), 0).sum(1)
        can_push = (inj_vc >= 0) & (iocc < blk(ss.b_depth))
        pushc = (n_valid & gn(can_push))[:, None] & (gn(iv_c)[:, None] == vcol)
        rcvd = rcvd + pushc.astype(i32)
        inj_pushed = inj_pushed + can_push.astype(inj_pushed.dtype)
        flits_inj = st.flits_inj + post * can_push.sum().astype(i32)
        # the source's current packet sits at q_head - 1 (claims advance
        # the head); its per-slot length ends the push burst
        plen_cur = ss.lens[n_ar, jnp.clip(q_head - 1, 0, K - 1)] \
            if mem_on else ss.pkt_len
        done = can_push & (inj_pushed >= plen_cur)
        inj_vc = jnp.where(done, -1, inj_vc)

        # ---- 4. receiver wake/sleep accounting ([17]) ---------------------
        stage("step.rx_sleep")
        rx_ids = ss.rx0 + jnp.arange(WMAX, dtype=i32)
        rx_arr, rx_bu = arbitrate.take_rows(
            jnp.clip(rx_ids, 0, B - 1), arrive.sum(axis=1), busy_until)
        rx_got = rx_arr > 0
        rx_busy = rx_bu > t
        rx_active = (rx_got | rx_busy) & (jnp.arange(WMAX) < ss.n_wi)
        n_rx_on = rx_active.sum().astype(i32)
        awake = jnp.where(ss.sleepy, n_rx_on, ss.n_wi)
        awake_cycles = st.awake_cycles + post * awake
        sleep_cycles = st.sleep_cycles + post * (ss.n_wi - awake)

        return SimState(
            pkt_src=pkt_src, pkt_idx=pkt_idx, pkt_dst=pkt_dst, born=born,
            out_o=out_o, out_buf=out_buf, out_wo=out_wo, out_is_wl=out_is_wl,
            out_is_ej=out_is_ej, out_vc=out_vc, phase2=phase2,
            rcvd=rcvd, sent=sent, src_of=src_of, mc_id=mc_id,
            attempt=attempt, pipe=pipe, busy_until=busy_until,
            wl_busy_until=wl_busy_until, pair_busy=pair_busy,
            q_head=q_head, inj_vc=inj_vc, inj_pushed=inj_pushed,
            cur_phase=cur_phase, phase_del=phase_del, phase_end=phase_end,
            phase_flits=phase_flits,
            rdy=rdy, dead=dead, outst=outst,
            bank_busy=bank_busy, bank_row=bank_row,
            outst_peak=outst_peak, amat_sum=amat_sum, amat_pkts=amat_pkts,
            mem_reads=mem_reads, mem_writes=mem_writes,
            mem_row_hits=mem_row_hits, mem_q_sum=mem_q_sum,
            mem_svc_sum=mem_svc_sum, mem_flits=mem_flits,
            flits_inj=flits_inj, flits_del=flits_del, pkts_del=pkts_del,
            lat_sum=lat_sum, lat_pkts=lat_pkts, counts_into=counts_into,
            count_switch=count_switch, ctrl_count=ctrl_count,
            wl_tx_flits=wl_tx_flits, wl_rx_flits=wl_rx_flits,
            awake_cycles=awake_cycles, sleep_cycles=sleep_cycles,
            wl_pair_flits=wl_pair_flits, wl_fail_flits=wl_fail_flits,
            wl_pkts=wl_pkts, wl_nacks=wl_nacks, pkts_dropped=pkts_dropped,
            wl_drop_flits=wl_drop_flits, mem_drop_reads=mem_drop_reads,
            wl_serv_d=st.wl_serv_d, wl_perq_d=st.wl_perq_d,
            wl_rate_d=st.wl_rate_d, wl_resel=st.wl_resel,
            wl_rate_flits=wl_rate_flits, wl_rate_fail=wl_rate_fail,
            cycles_run=st.cycles_run, drain_cycle=st.drain_cycle,
        )

    return step


def _scan_point(ss: SimStatic, st: SimState, cycles: int, B: int,
                mem_on: bool, phy_on: bool = False,
                drift_on: bool = False,
                reselect: bool = False) -> SimState:
    """Monolithic driver: one fixed-length scan (the pre-ISSUE-5 model).

    Kept as a differential oracle: ``tests/test_chunked_exec.py`` and
    ``benchmarks/simspeed.py`` pin the chunked driver against it.  The
    living-channel window updates fire inside the step, so this driver
    needs no boundary replay.
    """
    step = make_step(B, mem_on, phy_on, drift_on, reselect)

    def body(carry, t):
        return step(ss, carry, t), None

    final, _ = jax.lax.scan(body, st, jnp.arange(cycles, dtype=jnp.int32))
    return final._replace(cycles_run=jnp.int32(cycles),
                          drain_cycle=jnp.int32(cycles))


def _chunk_point(ss: SimStatic, st: SimState, B: int, mem_on: bool,
                 phy_on: bool, chunk: int, drift_on: bool = False,
                 reselect: bool = False) -> SimState:
    """Chunked driver: while_loop to the lane's traced ``ss.cycles``."""
    wfn = make_window_fn(ss, drift_on, reselect) \
        if (drift_on or reselect) else None
    return chunked.run_chunked(
        make_step(B, mem_on, phy_on, drift_on, reselect), ss, st,
        mem_on, chunk, window_fn=wfn)


@jax.jit
def _batch_of_one(st: SimState) -> SimState:
    """A single lane's state with a batch axis of one, in one dispatch
    (an eager reshape per leaf costs a dispatch each)."""
    return jax.tree_util.tree_map(lambda x: x[None], st)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7),
                   donate_argnums=(1,))
def _run_one(ss: SimStatic, st: SimState, B: int,
             mem_on: bool = False, phy_on: bool = False,
             chunk: int = CHUNK_CYCLES, drift_on: bool = False,
             reselect: bool = False) -> SimState:
    return _chunk_point(ss, st, B, mem_on, phy_on, chunk, drift_on,
                        reselect)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7),
                   donate_argnums=(1,))
def _run_mapped(ss: SimStatic, st: SimState, B: int,
                mem_on: bool = False, phy_on: bool = False,
                chunk: int = CHUNK_CYCLES, drift_on: bool = False,
                reselect: bool = False) -> SimState:
    """Sequentially map the per-point driver over a stacked batch.

    ``lax.map`` (not ``vmap``): each point's computation is the *identical*
    program to the single-point path — bitwise-equal results — and on
    XLA:CPU, where every batched op scales linearly anyway, a vmapped step
    only adds lowering overhead.  The batch win comes from one dispatch for
    the whole group and from sharding groups across devices
    (`_run_pmapped`).  Under ``lax.map`` each lane's while_loop runs
    sequentially, so every lane stops at its own drain/budget — early
    exit needs no cross-lane agreement.
    """
    return jax.lax.map(
        lambda args: _chunk_point(args[0], args[1], B, mem_on, phy_on,
                                  chunk, drift_on, reselect),
        (ss, st))


@functools.partial(jax.pmap, static_broadcasted_argnums=(2, 3, 4, 5, 6, 7),
                   donate_argnums=(1,))
def _run_pmapped(ss: SimStatic, st: SimState, B: int,
                 mem_on: bool = False, phy_on: bool = False,
                 chunk: int = CHUNK_CYCLES, drift_on: bool = False,
                 reselect: bool = False) -> SimState:
    return jax.lax.map(
        lambda args: _chunk_point(args[0], args[1], B, mem_on, phy_on,
                                  chunk, drift_on, reselect),
        (ss, st))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _run_one_mono(ss: SimStatic, st: SimState, cycles: int, B: int,
                  mem_on: bool = False, phy_on: bool = False,
                  drift_on: bool = False,
                  reselect: bool = False) -> SimState:
    return _scan_point(ss, st, cycles, B, mem_on, phy_on, drift_on,
                       reselect)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _run_mapped_mono(ss: SimStatic, st: SimState, cycles: int, B: int,
                     mem_on: bool = False, phy_on: bool = False,
                     drift_on: bool = False,
                     reselect: bool = False) -> SimState:
    return jax.lax.map(
        lambda args: _scan_point(args[0], args[1], cycles, B, mem_on,
                                 phy_on, drift_on, reselect),
        (ss, st))


@functools.partial(jax.pmap, static_broadcasted_argnums=(2, 3, 4, 5, 6, 7))
def _run_pmapped_mono(ss: SimStatic, st: SimState, cycles: int, B: int,
                      mem_on: bool = False, phy_on: bool = False,
                      drift_on: bool = False,
                      reselect: bool = False) -> SimState:
    return jax.lax.map(
        lambda args: _scan_point(args[0], args[1], cycles, B, mem_on,
                                 phy_on, drift_on, reselect),
        (ss, st))


# --------------------------------------------------------------------------
# host-side packing
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PackedSim:
    ss: SimStatic
    B: int
    n_cores: int
    Lw: int
    n_inj: int
    topo: Topology
    rt: RoutingTables
    phy: PhyParams
    sim: SimParams
    dims: dict = dataclasses.field(default_factory=dict)
    mem_on: bool = False      # closed-loop memory path compiled in
    phy_on: bool = False      # lossy-channel ARQ path compiled in
    drift_on: bool = False    # living channel: SNR aging walk compiled in
    reselect: bool = False    # living channel: in-scan rate re-selection
    phy_link: object = None   # phy.PhyLinkInfo (host-side, for metrics)

    def shape_key(self) -> tuple:
        """Hashable signature of every padded array shape (batch grouping).

        ``mem_on``/``phy_on``/``drift_on``/``reselect`` are part of the
        key: each selects a different compiled step, so open-loop,
        closed-loop, lossy-channel and living-channel points never share
        a batch (the placeholder shapes alone cannot distinguish the two
        living flags).
        """
        return (("mem_on", self.mem_on), ("phy_on", self.phy_on),
                ("drift_on", self.drift_on),
                ("reselect", self.reselect)) + tuple(
            (k, np.shape(v)) for k, v in self.ss._asdict().items())


def pack_dims(topo: Topology, tt: TrafficTable,
              b_bucket: int = 64, s_bucket: int = 8, r_bucket: int = 64,
              k_bucket: int = 32) -> dict:
    """Natural (floor-less) padded dims of a point, without packing it.

    Cheap (a few numpy reductions): lets ``sweep.run_sweep_batched`` compute
    a group's harmonized floors first and then call ``pack`` exactly once
    per point.  Must mirror the dim arithmetic in ``pack``.
    """
    Lw = topo.n_links
    n_inj = tt.n_sources
    n_wi = topo.n_wi
    Wp = len(topo.wl_pairs)
    dram = getattr(tt, "dram", None)
    return {
        "B": _bucket(Lw + n_inj + n_wi, b_bucket),
        "S": _bucket(topo.n_switches + 1, s_bucket),
        "R": _bucket(Lw + Wp + topo.n_switches, r_bucket),
        "K": _bucket(tt.k, k_bucket),
        "M": _bucket(getattr(tt, "n_mc", 0), 8),
        "P": _bucket(getattr(tt, "n_phases", 0), 8),
        "Y": _bucket(topo.n_mem, 4),
        "BK": _bucket(dram.n_banks if dram is not None else 1, 8),
    }


def pack(topo: Topology, rt: RoutingTables, tt: TrafficTable,
         phy: PhyParams, sim: SimParams,
         b_bucket: int = 64, s_bucket: int = 8, r_bucket: int = 64,
         k_bucket: int = 32, floors: dict | None = None,
         phy_spec=None) -> PackedSim:
    """Pack a (topology, routing, traffic) point into padded device arrays.

    ``floors`` maps dim names (``sweep.HARMONIZED_DIMS``) to minimum
    padded sizes, letting heterogeneous points be harmonized
    onto one bucket shape so they can share an XLA compile *and* a batch
    (see ``sweep.run_sweep_batched``).  Padding is semantically inert.

    ``phy_spec`` (a ``phy.PhySweepSpec``) turns on the lossy-channel ARQ
    path on fabrics with wireless interfaces; wireline fabrics (and
    ``phy_spec=None``) run the exact ideal-channel program.
    """
    from repro.phy.rates import drift_amp_q, pack_link_state
    fl = floors or {}
    Lw = topo.n_links
    n_inj = tt.n_sources
    n_wi = topo.n_wi
    B = max(_bucket(Lw + n_inj + n_wi, b_bucket), fl.get("B", 0))
    S = max(_bucket(topo.n_switches + 1, s_bucket), fl.get("S", 0))
    Wp = len(topo.wl_pairs)
    R = max(_bucket(Lw + Wp + topo.n_switches, r_bucket), fl.get("R", 0))
    medium = phy.wireless_medium
    RXW = max(1, int(phy.wireless_rx_streams)) if medium == "crossbar" else 1
    assert RXW <= RXWMAX, \
        f"wireless_rx_streams={RXW} exceeds simulator cap {RXWMAX}"
    N = n_inj
    K = max(_bucket(tt.k, k_bucket), fl.get("K", 0))
    assert n_wi <= WMAX

    # per-buffer attributes
    b_dst = np.full(B, S - 1, np.int32)
    b_serv = np.ones(B, np.int32)
    b_lat = np.ones(B, np.int32)
    b_epb = np.zeros(B, np.float32)
    b_depth = np.full(B, DEPTH, np.int32)
    b_wi = np.full(B, -1, np.int32)
    b_is_rx = np.zeros(B, bool)
    b_ej_ways = np.ones(B, np.int32)
    b_src_sw = np.full(B, S - 1, np.int32)
    inj_src = np.full(B, -1, np.int32)

    cls = topo.link_cls
    pipe_stages = phy.switch_stages
    serv_map = {
        int(LinkClass.MESH): 1,
        int(LinkClass.INTERPOSER): phy.interposer_flit_cycles,
        int(LinkClass.SERIAL): phy.serial_flit_cycles,
        int(LinkClass.WIDEIO): phy.wideio_flit_cycles,
    }
    for l in range(Lw):
        c = int(cls[l])
        b_dst[l] = topo.link_dst[l]
        b_src_sw[l] = topo.link_src[l]
        b_serv[l] = serv_map[c]
        b_lat[l] = pipe_stages + serv_map[c]
        mm = float(topo.link_mm[l])
        if c == int(LinkClass.MESH):
            b_epb[l] = phy.e_wire_pj_bit_mm * mm
        elif c == int(LinkClass.INTERPOSER):
            b_epb[l] = phy.e_wire_pj_bit_mm * mm + phy.e_ubump_pj_bit
        elif c == int(LinkClass.SERIAL):
            b_epb[l] = phy.e_serial_pj_bit
        elif c == int(LinkClass.WIDEIO):
            b_epb[l] = phy.e_wideio_pj_bit
    for n in range(n_inj):
        b = Lw + n
        b_dst[b] = tt.src_switch[n]
        inj_src[b] = n
    rx0 = Lw + n_inj
    serv_wl = phy.wireless_flit_cycles
    for w in range(n_wi):
        b = rx0 + w
        b_dst[b] = topo.wi_switch[w]
        b_lat[b] = pipe_stages + serv_wl
        b_epb[b] = phy.e_wireless_pj_bit
        b_is_rx[b] = True
    # sender WI of any buffer whose switch hosts a WI
    for b in range(rx0 + n_wi):   # rx buffers may relay (phase-2 hops)
        w = topo.wi_of_switch[b_dst[b]] if b_dst[b] < topo.n_switches else -1
        b_wi[b] = w
    # 4-channel memory stacks eject up to 4 flits/cycle
    for b in range(B):
        if b_dst[b] < topo.n_switches and topo.is_mem[b_dst[b]]:
            b_ej_ways[b] = EJ_WAYS
    if sim.mac == MacMode.TOKEN and n_wi:
        # token MAC [7] transmits whole packets only => WI-adjacent buffers
        # must hold a full packet (the buffer overhead the paper's
        # control-packet MAC removes, §III.D)
        wi_set = set(int(x) for x in topo.wi_switch)
        for b in range(rx0):
            if int(b_dst[b]) in wi_set:
                b_depth[b] = max(int(b_depth[b]), phy.pkt_flits)

    # lossy PHY (ISSUE 4): per-(src, dst)-WI rate/PER tables; inert when
    # the spec is absent or the fabric has no wireless medium.  The
    # shared helper mutates b_depth/b_epb (store-and-forward deepening,
    # rx epb zeroing) identically for both engines.
    pli, phy_on, rx_hold = pack_link_state(
        topo, phy, tt, phy_spec, b_dst, b_depth, b_epb, rx0)
    # living channel (ISSUE 6): SNR drift and/or in-scan rate
    # re-selection compile the window-update path and embed the
    # per-entry tables; static points keep (1, 1) placeholders
    drift_on = bool(phy_on and phy_spec.drift_amp_db > 0.0)
    reselect = bool(phy_on and phy_spec.reselect)
    living = drift_on or reselect

    # arbitration candidate sets: buffers feeding each switch ...
    in_bufs: list[list[int]] = [[] for _ in range(S)]
    for b in range(rx0 + n_wi):
        if b_dst[b] < topo.n_switches:
            in_bufs[int(b_dst[b])].append(b)
    # ... and buffers able to transmit to each wireless receiver
    senders: list[list[int]] = [[] for _ in range(WMAX)]
    for p in range(Wp):
        src_wi = int(topo.wl_pairs[p, 0])
        dst_wi = int(topo.wl_pairs[p, 1])
        senders[dst_wi].append(int(topo.wi_switch[src_wi]))
    cr_lists = [sorted({b for s in set(sw) for b in in_bufs[s]})
                for sw in senders]
    wi_sw = np.full(WMAX, S - 1, np.int32)
    wi_sw[:n_wi] = topo.wi_switch
    # as [target, buffer] masks; padding rows (the dummy switch S-1, WIs
    # past n_wi) hold no candidate
    cand_s = np.zeros((S, B), bool)
    for s in range(topo.n_switches):
        cand_s[s, in_bufs[s]] = True
    cand_w = cand_s[b_src_sw]
    cand_r = np.zeros((WMAX, B), bool)
    for w in range(n_wi):
        cand_r[w, cr_lists[w]] = True

    # routing lookup tables
    next_out = np.full((S, S), 0, np.int32)
    next_out[:topo.n_switches, :topo.n_switches] = rt.next_out
    o_buf = np.full(R, B, np.int32)
    o_wo = np.full(R, 0, np.int32)
    o_is_wl = np.zeros(R, bool)
    o_is_ej = np.zeros(R, bool)
    for o in range(Lw):
        o_buf[o] = o
        o_wo[o] = o               # wired arbitration key: the link itself
    for p in range(Wp):
        o = Lw + p
        dst_wi = int(topo.wl_pairs[p, 1])
        o_buf[o] = rx0 + dst_wi
        o_wo[o] = dst_wi          # wireless arbitration key: the receiver
        o_is_wl[o] = True
    for s in range(topo.n_switches):
        o = Lw + Wp + s
        o_wo[o] = s               # ejection arbitration key: the switch
        o_is_ej[o] = True
    assert rt.n_outputs == Lw + Wp + topo.n_switches

    births = np.full((N, K), NO_PKT, np.int32)
    births[:, :tt.k] = tt.births
    dests = np.zeros((N, K), np.int32)
    dests[:, :tt.k] = tt.dests

    # trace tables: phase barriers + multicast groups (all-zero semantics
    # for the synthetic open-loop generators)
    Pn = tt.n_phases
    Mn = tt.n_mc
    P = max(_bucket(Pn, 8), fl.get("P", 0))
    M = max(_bucket(Mn, 8), fl.get("M", 0))
    phases = np.zeros((N, K), np.int32)
    phase_need = np.zeros(P, np.int32)
    mc_member = np.zeros((M, WMAX), bool)
    mc_dst = np.zeros((M, WMAX), np.int32)
    mc_route = np.zeros(M, np.int32)
    mc_prim = np.zeros(M, np.int32)
    if Pn:
        phases[:, :tt.k] = tt.phases
        phase_need[:Pn] = tt.phase_need
    if Mn:
        mc_member[:Mn] = tt.mc_member
        mc_dst[:Mn] = np.clip(tt.mc_dst, 0, None)    # -1 pad, member-masked
        mc_route[:Mn] = tt.mc_route
        mc_prim[:Mn] = np.argmax(tt.mc_member, axis=1)
        assert tt.mc_member.shape[1] == WMAX
        assert tt.mc_member[:Mn].any(axis=1).all(), "empty multicast group"

    # memory tables (closed-loop request/reply; inert for open-loop tables)
    mem_on = getattr(tt, "mem_op", None) is not None
    dram = (getattr(tt, "dram", None) or DEFAULT_DRAM) if mem_on \
        else DEFAULT_DRAM
    Y = max(_bucket(topo.n_mem, 4), fl.get("Y", 0))
    BK = max(_bucket(dram.n_banks if mem_on else 1, 8), fl.get("BK", 0))
    lens = np.full((N, K), phy.pkt_flits, np.int32)
    mem_op = np.zeros((N, K), np.int32)
    mem_ch = np.zeros((N, K), np.int32)
    mem_bank = np.zeros((N, K), np.int32)
    mem_row = np.zeros((N, K), np.int32)
    reply_row = np.full((N, K), -1, np.int32)
    reply_slot = np.full((N, K), -1, np.int32)
    req_src = np.full((N, K), -1, np.int32)
    req_birth = np.full((N, K), NO_PKT, np.int32)
    if mem_on:
        assert dram.n_banks <= BK
        lens[:, :tt.k] = tt.lens
        mem_op[:, :tt.k] = tt.mem_op
        mem_ch[:, :tt.k] = tt.mem_ch
        mem_bank[:, :tt.k] = tt.mem_bank
        mem_row[:, :tt.k] = tt.mem_row
        reply_row[:, :tt.k] = tt.reply_row
        reply_slot[:, :tt.k] = tt.reply_slot
        req_src[:, :tt.k] = tt.req_src
        req_birth[:, :tt.k] = tt.req_birth
    stack_sw = np.full(Y, S - 1, np.int32)
    stack_sw[:topo.n_mem] = np.nonzero(topo.is_mem)[0]
    max_outst = dram.max_outstanding if mem_on else 2**30

    ctrl_cycles = max(1, phy.ctrl_packet_flits * serv_wl)

    ss = SimStatic(
        b_dst=jnp.asarray(b_dst), b_serv=jnp.asarray(b_serv),
        b_lat=jnp.asarray(b_lat), b_epb=jnp.asarray(b_epb),
        b_depth=jnp.asarray(b_depth), b_wi=jnp.asarray(b_wi),
        b_is_rx=jnp.asarray(b_is_rx),
        b_ej_ways=jnp.asarray(b_ej_ways),
        b_src_sw=jnp.asarray(b_src_sw), inj_src=jnp.asarray(inj_src),
        next_out=jnp.asarray(next_out),
        o_buf=jnp.asarray(o_buf), o_wo=jnp.asarray(o_wo),
        o_is_wl=jnp.asarray(o_is_wl), o_is_ej=jnp.asarray(o_is_ej),
        cand_w=jnp.asarray(cand_w), cand_r=jnp.asarray(cand_r),
        cand_s=jnp.asarray(cand_s),
        wi_sw=jnp.asarray(wi_sw), rxw=jnp.int32(RXW),
        n_wi=jnp.int32(n_wi), rx0=jnp.int32(rx0),
        inj_buf=jnp.asarray(Lw + np.arange(N, dtype=np.int32)),
        src_switch=jnp.asarray(tt.src_switch.astype(np.int32)),
        births=jnp.asarray(births), dests=jnp.asarray(dests),
        pkt_len=jnp.int32(phy.pkt_flits), warmup=jnp.int32(sim.warmup),
        cycles=jnp.int32(sim.cycles),
        serv_wl=jnp.int32(serv_wl),
        lat_wl=jnp.int32(pipe_stages + serv_wl),
        ctrl_cycles=jnp.int32(ctrl_cycles),
        mac_token=jnp.asarray(sim.mac == MacMode.TOKEN),
        wl_sender_cap=jnp.asarray(medium != "crossbar"),
        wl_single=jnp.asarray(medium == "single"),
        wl_rx_busy=jnp.asarray(medium != "crossbar"),
        sleepy=jnp.asarray(bool(sim.sleepy_rx)),
        phases=jnp.asarray(phases), phase_need=jnp.asarray(phase_need),
        n_phases=jnp.int32(Pn),
        mc_member=jnp.asarray(mc_member), mc_dst=jnp.asarray(mc_dst),
        mc_route=jnp.asarray(mc_route), mc_prim=jnp.asarray(mc_prim),
        lens=jnp.asarray(lens), mem_op=jnp.asarray(mem_op),
        mem_ch=jnp.asarray(mem_ch), mem_bank=jnp.asarray(mem_bank),
        mem_row=jnp.asarray(mem_row),
        reply_row=jnp.asarray(reply_row),
        reply_slot=jnp.asarray(reply_slot),
        req_src=jnp.asarray(req_src), req_birth=jnp.asarray(req_birth),
        stack_sw=jnp.asarray(stack_sw),
        t_row_hit=jnp.int32(dram.t_row_hit),
        t_row_miss=jnp.int32(dram.t_row_miss),
        max_outst=jnp.int32(max_outst),
        wl_serv=jnp.asarray(pli.serv if phy_on
                            else np.ones((WMAX, WMAX), np.int32)),
        wl_perq=jnp.asarray(pli.perq if phy_on
                            else np.zeros((WMAX, WMAX), np.int32)),
        rx_hold=jnp.asarray(rx_hold),
        max_retx=jnp.int32(phy_spec.max_retx if phy_on else 1),
        phy_seed=jnp.uint32(phy_spec.seed if phy_on else 0),
        ctrl_flits=jnp.int32(phy.ctrl_packet_flits),
        wl_rate0=jnp.asarray(pli.rate_idx if living
                             else np.zeros((1, 1), np.int32)),
        wl_snr_q=jnp.asarray(pli.snr_q if drift_on
                             else np.zeros((1, 1), np.int32)),
        wl_serv_r=jnp.asarray(pli.serv_r if living
                              else np.ones(1, np.int32)),
        wl_perq_r=jnp.asarray(pli.perq_r if living
                              else np.zeros((1, 1, 1), np.int32)),
        wl_gp_q=jnp.asarray(pli.gp_q if living
                            else np.zeros((1, 1, 1), np.int32)),
        wl_perq_lut=jnp.asarray(pli.perq_lut if drift_on
                                else np.zeros((1, 1), np.int32)),
        wl_gp_lut=jnp.asarray(pli.gp_lut if drift_on
                              else np.zeros((1, 1), np.int32)),
        wl_drift_amp_q=jnp.int32(drift_amp_q(phy_spec.drift_amp_db)
                                 if phy_on else 0),
        wl_drift_period=jnp.int32(max(1, phy_spec.drift_period)
                                  if phy_on else 1),
    )
    dims = {"B": B, "S": S, "R": R, "K": K,
            "M": M, "P": P, "Y": Y, "BK": BK}
    return PackedSim(ss=ss, B=B, n_cores=topo.n_cores, Lw=Lw,
                     n_inj=n_inj, topo=topo, rt=rt, phy=phy, sim=sim,
                     dims=dims, mem_on=mem_on, phy_on=phy_on,
                     drift_on=drift_on, reselect=reselect, phy_link=pli)


# --------------------------------------------------------------------------
# batched execution
# --------------------------------------------------------------------------

def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_state_batch(G: int, B: int, N: int, P: int = 1, K: int = 1,
                     Y: int = 1, BK: int = 1, mem_on: bool = False,
                     phy_on: bool = False, living: bool = False,
                     R: int = 1) -> SimState:
    st = init_state(B, N, P, K, Y, BK, mem_on, phy_on, living, R)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (G,) + x.shape), st)


def _state_dims(ps: PackedSim) -> tuple:
    """(B, N, P, K, Y, BK) for ``init_state`` from a packed point."""
    N, K = ps.ss.births.shape
    return (ps.B, int(N), int(ps.ss.phase_need.shape[0]), int(K),
            int(ps.ss.stack_sw.shape[0]), ps.dims.get("BK", 1))


def _budgeted(ps: PackedSim, cycles: int | None) -> SimStatic:
    """The point's static tables with an optional budget override."""
    if cycles is None:
        return ps.ss
    return ps.ss._replace(cycles=jnp.int32(cycles))


def run_batch(pss: Sequence[PackedSim], cycles: int | None = None,
              devices: int | None = None, driver: str = "chunked",
              chunk: int = CHUNK_CYCLES) -> SimState:
    """Run N same-bucket-shape points as one batched launch.

    Returns a ``SimState`` whose leaves carry a leading batch axis, ordered
    as ``pss``.  All points must share every padded array shape (use
    ``pack(..., floors=...)`` to harmonize); cycle budgets and warm-ups
    are traced per-lane data and may differ freely.  ``cycles`` overrides
    every lane's budget when given.

    When the host exposes several XLA devices (e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU), the
    batch is sharded across them with ``pmap``; the remainder is padded by
    repeating the last point and sliced off afterwards.  A batch of one
    takes the plain single-point path, so ``run_batch([ps]) == run(ps)``
    bitwise.

    ``driver="monolithic"`` selects the fixed-length single-scan driver
    (all lanes must then share one budget) — the differential oracle the
    chunked default is pinned against.

    Host spans (``spans``): ``run_batch`` around ``run_batch.init``
    (tables and initial state), ``run_batch.dispatch`` (the jitted driver
    call, any compile included; again after the wait where a batch of
    one gets its batch axis or the shards are joined) and
    ``run_batch.wait`` (``block_until_ready`` on the driver's output).
    """
    if not pss:
        raise ValueError("run_batch needs at least one point")
    key0 = pss[0].shape_key()
    for ps in pss[1:]:
        if ps.shape_key() != key0:
            raise ValueError(
                "run_batch requires identical padded shapes; got "
                f"{ps.dims} vs {pss[0].dims} — pack with harmonized floors")
    mono = driver == "monolithic"
    if mono:
        budgets = {int(cycles or ps.sim.cycles) for ps in pss}
        if len(budgets) != 1:
            raise ValueError(
                "monolithic driver needs one shared cycle budget; got "
                f"{sorted(budgets)}")
        mono_cycles = budgets.pop()
    B = pss[0].B
    sdims = _state_dims(pss[0])
    mem_on = pss[0].mem_on
    phy_on = pss[0].phy_on
    drift_on = pss[0].drift_on
    reselect = pss[0].reselect
    living = drift_on or reselect
    Rr = int(pss[0].ss.wl_serv_r.shape[0])
    G = len(pss)
    with spans.span("run_batch"):
        if G == 1:
            with spans.span("run_batch.init"):
                ss = pss[0].ss if mono else _budgeted(pss[0], cycles)
                st = init_state(*sdims, mem_on=mem_on, phy_on=phy_on,
                                living=living, R=Rr)
            with spans.span("run_batch.dispatch"):
                out = _run_one_mono(ss, st, mono_cycles, B, mem_on,
                                    phy_on, drift_on, reselect) if mono \
                    else _run_one(ss, st, B, mem_on, phy_on, chunk,
                                  drift_on, reselect)
            with spans.span("run_batch.wait"):
                jax.block_until_ready(out)
            # the batch axis goes on after the wait: an op on the driver's
            # output would itself hold the host while the device runs
            with spans.span("run_batch.dispatch"):
                return jax.block_until_ready(_batch_of_one(out))
        D = devices if devices is not None else jax.local_device_count()
        D = min(D, G)
        with spans.span("run_batch.init"):
            ss = _tree_stack([_budgeted(ps, cycles) for ps in pss])
            st = init_state_batch(G, *sdims, mem_on=mem_on, phy_on=phy_on,
                                  living=living, R=Rr)
            if D > 1:
                Gp = int(np.ceil(G / D) * D)
                if Gp != G:
                    pad = jax.tree_util.tree_map(
                        lambda x: jnp.repeat(x[-1:], Gp - G, axis=0), ss)
                    ss = jax.tree_util.tree_map(
                        lambda a, b: jnp.concatenate([a, b]), ss, pad)
                    st = init_state_batch(Gp, *sdims, mem_on=mem_on,
                                          phy_on=phy_on, living=living, R=Rr)
                ss = jax.tree_util.tree_map(
                    lambda x: x.reshape((D, Gp // D) + x.shape[1:]), ss)
                st = jax.tree_util.tree_map(
                    lambda x: x.reshape((D, Gp // D) + x.shape[1:]), st)
        with spans.span("run_batch.dispatch"):
            if D > 1:
                out = _run_pmapped_mono(ss, st, mono_cycles, B, mem_on,
                                        phy_on, drift_on, reselect) \
                    if mono else _run_pmapped(ss, st, B, mem_on, phy_on,
                                              chunk, drift_on, reselect)
            else:
                out = _run_mapped_mono(ss, st, mono_cycles, B, mem_on,
                                       phy_on, drift_on, reselect) \
                    if mono else _run_mapped(ss, st, B, mem_on, phy_on,
                                             chunk, drift_on, reselect)
        with spans.span("run_batch.wait"):
            jax.block_until_ready(out)
        if D == 1:
            return out
        # the shards are joined and the padding cut after the wait, as
        # the batch of one's axis is
        with spans.span("run_batch.dispatch"):
            return jax.block_until_ready(jax.tree_util.tree_map(
                lambda x: x.reshape((Gp,) + x.shape[2:])[:G], out))


def run(ps: PackedSim, cycles: int | None = None, driver: str = "chunked",
        chunk: int = CHUNK_CYCLES) -> SimState:
    """Single-point API (a batch of one; same step program as batches).

    ``driver="monolithic"`` runs the fixed-length scan oracle instead of
    the drain-aware chunked while_loop (results are bitwise-equal; only
    ``drain_cycle`` may differ — the oracle never exits early).
    """
    living = ps.drift_on or ps.reselect
    st = init_state(*_state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
                    living=living, R=int(ps.ss.wl_serv_r.shape[0]))
    if driver == "monolithic":
        return jax.block_until_ready(
            _run_one_mono(ps.ss, st, int(cycles or ps.sim.cycles), ps.B,
                          ps.mem_on, ps.phy_on, ps.drift_on, ps.reselect))
    return jax.block_until_ready(
        _run_one(_budgeted(ps, cycles), st, ps.B, ps.mem_on, ps.phy_on,
                 chunk, ps.drift_on, ps.reselect))
