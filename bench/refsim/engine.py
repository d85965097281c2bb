"""The benchmark's plain reference engine: a frozen copy of the repository's
reference scatter/segment simulator (``src/repro/core/simulator_ref.py``)
with its helpers copied beside it, so it imports nothing of the program
under test.  It runs one lane at its natural sizes, by the fixed-length
scan alone: the program's drain-aware early exit and closed-form drain
accounting have no counterpart here, so the check holds them to a run of
every cycle.

Reference scatter/segment implementation of the flit simulator.

This is the original engine, kept as a *differential-testing oracle* for
``simulator.py``'s scatter-free rewrite: both engines must produce bitwise-
identical dynamics (tests/test_engine_equivalence.py asserts this across
fabrics, media, MAC modes and system sizes).  It is also the baseline that
``benchmarks.simspeed`` reports speedups against.  It is NOT used by the
sweep/benchmark paths — do not extend it; extend ``simulator.py`` and keep
this file frozen unless the simulated semantics themselves change.

Semantics extension: multicast delivery over the wireless medium
and trace phase barriers were added to BOTH engines — here in the original
scatter/segment style (segment-min arbitration + scatter installs, with the
receiver-side fan-out threaded through an engine-internal ``mc_src``
pointer), in ``simulator.py`` in candidate-table/gather style — so the
differential tests pin the new paths from two independent formulations.

Semantics extension: closed-loop memory request/reply round
trips with the per-stack DRAM bank model (see simulator.py "Closed-loop
memory" and memory/model.py) — here in scatter style: request arrivals
scatter into the ``[Y, CH, BK]`` bank state and ``rdy`` reply births
(``.at[].min``/``.set`` with drop-mode out-of-bounds masking),
outstanding-window credits scatter-add into ``outst``; ``simulator.py``
instead locates the unique per-(stack, channel) and per-(switch, way)
ejection winners through its candidate tables and updates with masked
elementwise min — two independent formulations, pinned bitwise-equal.

Semantics extension: the lossy-channel PHY — per-(src, dst)-WI
rates/PER, CRC retransmission with bounded attempts, per-pair pacing and
drop accounting — plus store-and-forward receivers (``rx_hold``, also
the one-shot multicast all-reduce livelock fix) were added to BOTH
engines: here with ``.at[].set/.add`` scatters over the ``[WMAX, WMAX]``
pair grids, in ``simulator.py`` via the air-winner tables — two
independent formulations, pinned bitwise-equal.

Semantics extension: broadcast ARQ and the living channel.
Multicast tables now run over the lossy PHY — a group attempt is paced
and CRC-checked against its worst member link, retransmitted as a group
on NACK, and its drops credit the phase barrier and free every member
copy.  Drift/re-selection points refresh the per-pair link tables at
scan-window boundaries via the shared ``phy.living`` window update and
split the attempt counters per rate entry — here with masked scatters,
in ``simulator.py`` via one-hot gathers, pinned bitwise-equal.

Original module docstring follows.

Cycle-accurate flit-level simulator for multichip NoCs (paper §IV).

Implements wormhole switching with virtual channels (8 VCs x 16-flit input
buffers), credit-equivalent backpressure, forwarding-table routing, the
paper's control-packet wireless MAC with partial packet transmission
(§III.D), and sleepy receivers [17] — all as one vectorized cycle step
driven by one fixed-length ``jax.lax.scan``.

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

Simplifications (documented in DESIGN.md): instant credit return; one VC
allocation per target buffer per cycle; time-rotating (round-robin
equivalent) arbitration priority; an input link's VCs may forward to
distinct outputs in the same cycle.

Compile sharing: every topology-dependent quantity is a *padded, traced
array argument*, so one XLA compilation serves all topologies, fabrics and
traffic tables of the same bucket shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from refsim.constants import (WINDOW_CYCLES, WMAX, LinkClass, MacMode,
                              PhyParams, SimParams)
from refsim.routing import RoutingTables
from refsim.topology import Topology
from refsim.traffic import NO_PKT, TrafficTable
from refsim.dram import MEM_CH, DEFAULT_DRAM
from refsim.living import make_window_fn
from refsim.retx import crc_fail as _crc_fail

V = 8            # virtual channels per port (paper §IV)
DEPTH = 16       # buffer depth in flits (paper §IV)
DMAX = 12        # arrival-pipe depth >= max link latency

# state leaves held in this engine's own encoding: ``out_wo`` is a lookup
# of the packed ``o_wo`` table, whose ejection slots this engine strides
# by way; the route itself is ``out_o``, which is compared
OWN_ENCODING = ("out_wo",)


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
    s_pad: jnp.ndarray       # scalar: padded switch count (eject slot stride)
    # routing
    next_out: jnp.ndarray    # [S, S] routing output id
    o_buf: jnp.ndarray       # [R] target buffer id (dummy B for eject/pad)
    o_wo: jnp.ndarray        # [R] output arbitration slot (Wout = drop)
    o_is_wl: jnp.ndarray     # [R] bool wireless pair link
    o_is_ej: jnp.ndarray     # [R] bool ejection
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
    cycles: jnp.ndarray      # int32 per-lane cycle budget (traced)
    serv_wl: jnp.ndarray     # int32 rx service cycles per flit
    lat_wl: jnp.ndarray      # int32
    ctrl_cycles: jnp.ndarray  # int32 control-packet duration
    mac_token: jnp.ndarray   # bool: whole-packet token MAC [7]
    wl_sender_cap: jnp.ndarray  # bool: one flit/cycle per transmitting WI
    wl_single: jnp.ndarray   # bool: strict single shared channel
    wl_rx_busy: jnp.ndarray  # bool: serialize each receiver (non-crossbar)
    sleepy: jnp.ndarray      # bool
    # trace tables (phase barriers + multicast groups; see simulator.py)
    phases: jnp.ndarray      # [N, K]
    phase_need: jnp.ndarray  # [P]
    n_phases: jnp.ndarray    # scalar int32 (0 = open-loop)
    mc_member: jnp.ndarray   # [M, WMAX] bool
    mc_dst: jnp.ndarray      # [M, WMAX]
    mc_route: jnp.ndarray    # [M]
    mc_prim: jnp.ndarray     # [M]
    # memory tables (closed-loop request/reply; see simulator.py)
    lens: jnp.ndarray        # [N, K] per-slot packet length in flits
    mem_op: jnp.ndarray      # [N, K] MEM_* op code (0 = none)
    mem_ch: jnp.ndarray      # [N, K]
    mem_bank: jnp.ndarray    # [N, K]
    mem_row: jnp.ndarray     # [N, K]
    reply_row: jnp.ndarray   # [N, K]
    reply_slot: jnp.ndarray  # [N, K]
    req_src: jnp.ndarray     # [N, K]
    req_birth: jnp.ndarray   # [N, K]
    stack_of: jnp.ndarray    # [S] stack index of a switch (-1 = not a stack)
    t_row_hit: jnp.ndarray   # scalar i32
    t_row_miss: jnp.ndarray  # scalar i32
    max_outst: jnp.ndarray   # scalar i32
    # lossy PHY tables (see simulator.py).  Multicast tables run
    # broadcast ARQ over the same per-pair tables: group
    # service/PER threshold = max over the member links.
    wl_serv: jnp.ndarray     # [WMAX, WMAX]
    wl_perq: jnp.ndarray     # [WMAX, WMAX]
    rx_hold: jnp.ndarray     # bool
    max_retx: jnp.ndarray    # scalar i32
    phy_seed: jnp.ndarray    # scalar u32
    ctrl_flits: jnp.ndarray  # scalar i32
    # living-channel tables (see simulator.py / repro.phy.living)
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
    mc_id: jnp.ndarray        # [B, V] multicast group id (-1 = unicast)
    mc_src: jnp.ndarray       # [B, V] engine-internal: flat sender slot
    #                           feeding this multicast copy (-1); plays the
    #                           role simulator.py's src_of plays for copies
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
    cur_phase: jnp.ndarray    # scalar
    phase_del: jnp.ndarray    # scalar
    phase_end: jnp.ndarray    # [P]
    phase_flits: jnp.ndarray  # [P]
    # closed-loop memory dynamics + stats (names match simulator.py so the
    # differential tests compare them field by field)
    rdy: jnp.ndarray          # [N, K]
    dead: jnp.ndarray         # [N, K] bool: tombstoned reply slots
    outst: jnp.ndarray        # [N]
    bank_busy: jnp.ndarray    # [Y, CH, BK]
    bank_row: jnp.ndarray     # [Y, CH, BK]
    outst_peak: jnp.ndarray   # [N]
    amat_sum: jnp.ndarray     # f32
    amat_pkts: jnp.ndarray
    mem_reads: jnp.ndarray    # [Y]
    mem_writes: jnp.ndarray   # [Y]
    mem_row_hits: jnp.ndarray  # [Y]
    mem_q_sum: jnp.ndarray    # [Y] f32
    mem_svc_sum: jnp.ndarray  # [Y] f32
    mem_flits: jnp.ndarray    # [Y]
    # stats (post-warmup)
    flits_inj: jnp.ndarray
    flits_del: jnp.ndarray
    pkts_del: jnp.ndarray
    lat_sum: jnp.ndarray      # float32
    lat_pkts: jnp.ndarray
    counts_into: jnp.ndarray  # [B] link-traversal events
    count_switch: jnp.ndarray
    ctrl_count: jnp.ndarray
    wl_tx_flits: jnp.ndarray
    wl_rx_flits: jnp.ndarray
    awake_cycles: jnp.ndarray
    sleep_cycles: jnp.ndarray
    # lossy-PHY stats (zero unless phy_on; names match simulator.py)
    wl_pair_flits: jnp.ndarray  # [WMAX, WMAX]
    wl_fail_flits: jnp.ndarray  # [WMAX, WMAX]
    wl_pkts: jnp.ndarray
    wl_nacks: jnp.ndarray
    pkts_dropped: jnp.ndarray
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
    # the budget, and the cycle the run stopped at (here always the budget)
    cycles_run: jnp.ndarray   # scalar i32
    drain_cycle: jnp.ndarray  # scalar i32


def init_state(B: int, N: int, P: int = 1, K: int = 1, Y: int = 1,
               BK: int = 1, mem_on: bool = False,
               phy_on: bool = False, living: bool = False,
               R: int = 1) -> SimState:
    """Zero state; same carry slimming as ``simulator.init_state`` (the
    differential tests compare the two engines' states field by field)."""
    i32, i16, i8 = jnp.int32, jnp.int16, jnp.int8

    def zBV():
        # a fresh buffer per leaf: the jitted driver donates the state,
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
        mc_id=jnp.full((B, V), -1, i32), mc_src=jnp.full((B, V), -1, i32),
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
    return oo, ss.o_buf[oo], ss.o_wo[oo], ss.o_is_wl[oo], ss.o_is_ej[oo]


def make_step(B: int, Wout: int, RXW: int = 1, mem_on: bool = False,
              phy_on: bool = False, drift_on: bool = False,
              reselect: bool = False):
    """Build the per-cycle transition function (shapes baked in).

    ``mem_on`` (static) compiles the closed-loop memory path in scatter
    style; ``phy_on`` the lossy-channel ARQ path; with both off the
    program is exactly the ideal open-loop step.
    ``drift_on``/``reselect`` (static, imply ``phy_on``) compile the
    living-channel path: the shared window update of
    ``phy.living.make_window_fn`` refreshes the per-pair link tables at
    scan-window boundaries (SNR aging walk and/or in-scan rate
    re-selection).
    """
    living = drift_on or reselect
    assert not living or phy_on, "living channel requires the ARQ path"
    NC = B * V
    BIG = jnp.int32(4 * NC)
    flat2d = jnp.arange(NC, dtype=jnp.int32).reshape(B, V)
    b_ids = jnp.arange(B, dtype=jnp.int32)
    RXWMAX = 4

    def step(ss: SimStatic, st: SimState, t: jnp.ndarray) -> SimState:
        i32 = jnp.int32
        t = t.astype(i32)
        post = (t >= ss.warmup).astype(i32)
        if living:
            # living channel: refresh the dynamic per-pair link tables at
            # every window boundary (cadence = WINDOW_CYCLES, a fixed
            # semantic constant of the configuration).
            wfn = make_window_fn(ss, drift_on, reselect)
            st = jax.lax.cond(t % i32(WINDOW_CYCLES) == 0,
                              lambda s: wfn(s, t), lambda s: s, st)
        rot = t % NC
        S = ss.next_out.shape[0]
        M = ss.mc_member.shape[0]
        P = ss.phase_need.shape[0]
        warr = jnp.arange(WMAX, dtype=i32)
        rx_ids = jnp.clip(ss.rx0 + warr, 0, B - 1)               # [W]
        rx_slot = jnp.clip(b_ids - ss.rx0, 0, WMAX - 1)          # [B]
        vcol0 = jnp.arange(V, dtype=i32)[None, :]

        # ---- 1. arrivals -------------------------------------------------
        arrive = st.pipe[:, :, 0]
        rcvd = st.rcvd + arrive
        pipe = jnp.concatenate(
            [st.pipe[:, :, 1:], jnp.zeros((B, V, 1), st.pipe.dtype)],
            axis=2)

        active = st.pkt_src >= 0
        occ = jnp.where(active, rcvd - st.sent, 0)

        # ---- 2a. output-VC claims ---------------------------------------
        # one new downstream-VC allocation per target buffer per cycle.
        # VC classes break wormhole cycles (see module docstring): packets
        # before their wireless hop claim VCs [0, V/2), after it [V/2, V);
        # rx buffers admit any VC; pure-wired fabrics see phase2=False
        # everywhere, i.e. V/2 VCs per class as in classic escape schemes.
        free_mask = st.pkt_src < 0                               # [B, V]
        ob_c0 = jnp.clip(st.out_buf, 0, B - 1)
        classA = (jnp.arange(V) < V // 2)                        # [V]
        tgt_rx = ss.b_is_rx[ob_c0]                               # [B, V]
        allowed = jnp.where(tgt_rx[..., None], True,
                            jnp.where(st.phase2[..., None], ~classA, classA))
        free_ok = free_mask[ob_c0] & allowed                     # [B, V, V]
        has_free_c = free_ok.any(axis=-1)
        first_free_c = jnp.argmax(free_ok, axis=-1).astype(i32)  # [B, V]
        # multicast senders: all-or-nothing claim at every member rx buffer
        is_mc = (st.mc_id >= 0) & st.out_is_wl & ~st.phase2 & active
        mcid_c = jnp.clip(st.mc_id, 0, M - 1)
        member = ss.mc_member[mcid_c]                            # [B, V, W]
        free_any_rx = free_mask[rx_ids].any(axis=1)              # [W]
        free_all_mc = jnp.where(member, free_any_rx[None, None, :],
                                True).all(axis=-1)               # [B, V]
        # store-and-forward receivers (rx_hold; see simulator.py): rx
        # slots claim their downstream VC only with the whole packet in
        Nn0, Kk0 = ss.phases.shape
        plen0 = ss.lens[jnp.clip(st.pkt_src, 0, Nn0 - 1),
                        jnp.clip(st.pkt_idx, 0, Kk0 - 1)] \
            if mem_on else ss.pkt_len
        hold0_ok = ~(ss.rx_hold & ss.b_is_rx[:, None]) | (rcvd >= plen0)
        need_base = active & (st.out_vc < 0) & ~st.out_is_ej & (occ > 0) \
            & (st.out_buf < B) & hold0_ok
        need_uni = need_base & ~is_mc & has_free_c
        need_mc = need_base & is_mc & free_all_mc
        score_all = (flat2d - rot) % NC
        tb = jnp.where(need_uni, st.out_buf, B)
        score = jnp.where(need_uni, score_all, BIG)
        segmin = jax.ops.segment_min(score.reshape(-1), tb.reshape(-1),
                                     num_segments=B + 1)
        # multicast contenders: masked min per member receiver, combined
        # with the unicast segment minima into the per-rx-buffer winner
        score_mc = jnp.where(need_mc, score_all, BIG)
        mc_min = jnp.where(member & need_mc[..., None],
                           score_mc[..., None], BIG).min(axis=(0, 1))  # [W]
        win_code_rx = jnp.minimum(segmin[rx_ids], mc_min)        # [W]
        comb_b = jnp.where(ss.b_is_rx, win_code_rx[rx_slot], segmin[:B])
        win = need_uni & (score == comb_b[ob_c0]) & (score < BIG)
        win_all_mc = jnp.where(
            member, win_code_rx[None, None, :] == score_mc[:, :, None],
            True).all(axis=-1)                                   # [B, V]
        win_mc = need_mc & win_all_mc

        # scatter claim into downstream (b_t, v_t); OOB indices are dropped
        b_t = jnp.where(win, st.out_buf, B).reshape(-1)
        v_t = first_free_c.reshape(-1)
        nb = ss.b_dst[ob_c0]
        d_oo, d_ob, d_owo, d_owl, d_oej = _route_fields(ss, nb, st.pkt_dst)

        def claim(arr, val):
            return arr.at[b_t, v_t].set(val.reshape(-1), mode="drop")

        pkt_src = claim(st.pkt_src, st.pkt_src)
        pkt_idx = claim(st.pkt_idx, st.pkt_idx)
        pkt_dst = claim(st.pkt_dst, st.pkt_dst)
        born = claim(st.born, st.born)
        out_o = claim(st.out_o, d_oo.astype(i32))
        out_buf = claim(st.out_buf, d_ob.astype(i32))
        out_wo = claim(st.out_wo, d_owo.astype(i32))
        out_is_wl = claim(st.out_is_wl, d_owl)
        out_is_ej = claim(st.out_is_ej, d_oej)
        out_vc = claim(st.out_vc, jnp.full((B, V), -1, st.out_vc.dtype))
        phase2 = claim(st.phase2, st.phase2 | tgt_rx)
        mc_id = claim(st.mc_id, st.mc_id)
        mc_src = claim(st.mc_src, jnp.full((B, V), -1, i32))
        attempt = claim(st.attempt, jnp.zeros((B, V), st.attempt.dtype))
        rcvd = claim(rcvd, jnp.zeros((B, V), i32))
        sent = claim(st.sent, jnp.zeros((B, V), i32))
        # upstream learns its allocated VC
        out_vc = jnp.where(win, v_t.reshape(B, V).astype(out_vc.dtype),
                           out_vc)

        # multicast copy install: receiver-side, one copy per member rx
        # buffer of the full-group winner, each addressed to its per-WI
        # destination from the group table
        mcs = jnp.where(member & need_mc[..., None],
                        score_mc[..., None], BIG)                # [B, V, W]
        mc_src_w = jnp.argmin(mcs.reshape(NC, WMAX), axis=0).astype(i32)
        grp_ok_w = win_all_mc.reshape(-1)[mc_src_w]              # [W]
        inst_w = (mc_min < BIG) & (mc_min < segmin[rx_ids]) & grp_ok_w
        vfree_w = jnp.argmax(free_mask[rx_ids], axis=1).astype(i32)  # [W]
        inst_b = ss.b_is_rx & inst_w[rx_slot]                    # [B]
        icl_mc = inst_b[:, None] & (vfree_w[rx_slot][:, None] == vcol0)
        sw_b = mc_src_w[rx_slot]                                 # [B]

        def gmc(a):
            return a.reshape(-1)[sw_b]                           # [B]

        copy_dst = jnp.clip(
            ss.mc_dst[jnp.clip(gmc(st.mc_id), 0, M - 1), rx_slot], 0, S - 1)
        c_oo, c_ob, c_owo, c_owl, c_oej = _route_fields(
            ss, ss.b_dst, copy_dst)

        def mupd(old, val_b):
            return jnp.where(icl_mc, val_b[:, None], old)

        pkt_src = mupd(pkt_src, gmc(st.pkt_src))
        pkt_idx = mupd(pkt_idx, gmc(st.pkt_idx))
        pkt_dst = mupd(pkt_dst, copy_dst)
        born = mupd(born, gmc(st.born))
        out_o = mupd(out_o, c_oo.astype(i32))
        out_buf = mupd(out_buf, c_ob.astype(i32))
        out_wo = mupd(out_wo, c_owo.astype(i32))
        out_is_wl = jnp.where(icl_mc, c_owl[:, None], out_is_wl)
        out_is_ej = jnp.where(icl_mc, c_oej[:, None], out_is_ej)
        out_vc = jnp.where(icl_mc, -1, out_vc)
        phase2 = jnp.where(icl_mc, True, phase2)
        mc_id = mupd(mc_id, gmc(st.mc_id))
        mc_src = mupd(mc_src, sw_b)
        attempt = jnp.where(icl_mc, 0, attempt)
        rcvd = jnp.where(icl_mc, 0, rcvd)
        sent = jnp.where(icl_mc, 0, sent)
        # multicast sender: "granted" sentinel (delivery is receiver-side)
        out_vc = jnp.where(win_mc, 0, out_vc)

        active = pkt_src >= 0
        occ = jnp.where(active, rcvd - sent, 0)

        # per-slot packet attributes gathered from the [N, K] tables (see
        # simulator.py): lengths, memory op codes, ejection-way override
        Nn, Kk = ss.phases.shape
        psrc_c = jnp.clip(pkt_src, 0, Nn - 1)
        pidx_c = jnp.clip(pkt_idx, 0, Kk - 1)
        way_bv = vcol0 % ss.b_ej_ways[:, None]                   # [B, V]
        if mem_on:
            plen_bv = ss.lens[psrc_c, pidx_c]
            op_bv = jnp.where(active, ss.mem_op[psrc_c, pidx_c], 0)
            memrq_bv = (op_bv == 1) | (op_bv == 2)
            ch_bv = jnp.clip(ss.mem_ch[psrc_c, pidx_c], 0, MEM_CH - 1)
            way_bv = jnp.where(memrq_bv & out_is_ej,
                               ch_bv % ss.b_ej_ways[:, None], way_bv)
        else:
            plen_bv = ss.pkt_len

        # ---- 2b. forwarding: wired links, ejection, wireless -------------
        inflight = pipe.sum(axis=2)                              # [B, V]
        ob_c = jnp.clip(out_buf, 0, B - 1)
        ovc_c = jnp.clip(out_vc, 0, V - 1)
        occ_down = rcvd[ob_c, ovc_c] - sent[ob_c, ovc_c]
        space = ss.b_depth[ob_c] - occ_down - inflight[ob_c, ovc_c]
        link_free = jnp.take(st.busy_until, ob_c) <= t
        # multicast sender: backpressure is the MIN over its member copies
        # (located via the engine-internal mc_src pointer on the rx region)
        is_mc2 = (mc_id >= 0) & out_is_wl & ~phase2 & active     # [B, V]
        mcid_c2 = jnp.clip(mc_id, 0, M - 1)
        member2 = ss.mc_member[mcid_c2]                          # [B, V, W]
        mcs_rx = mc_src[rx_ids]                                  # [W, V]
        occ_rx = occ[rx_ids]
        infl_rx = inflight[rx_ids]
        depth_rx = ss.b_depth[rx_ids]                            # [W]
        cp = mcs_rx[None, None, :, :] \
            == flat2d[:, :, None, None]                          # [B,V,W,V]
        BIGS = jnp.int32(1 << 30)
        cp_space = jnp.where(
            cp, (depth_rx[:, None] - occ_rx - infl_rx)[None, None],
            BIGS).min(axis=-1)                                   # [B, V, W]
        cp_space = jnp.where(cp.any(axis=-1), cp_space, 0)
        space_mc = jnp.where(member2, cp_space, BIGS).min(axis=-1)
        space = jnp.where(is_mc2, space_mc, space)
        busy_rx_ok = jnp.take(st.busy_until, rx_ids) <= t        # [W]
        lf_mc = jnp.where(member2, busy_rx_ok[None, None, :],
                          True).all(axis=-1)
        link_free = jnp.where(is_mc2, lf_mc, link_free)
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
            # lossy PHY (see simulator.py): ARQ senders hold the whole
            # packet, pairs pace at the link rate, CRC outcome is the
            # deterministic (seed, packet, attempt) hash.  Living points
            # read the per-window dynamic tables instead of the packed
            # static ones (refreshed by the update above).
            serv_tab = st.wl_serv_d if living else ss.wl_serv
            perq_tab = st.wl_perq_d if living else ss.wl_perq
            ws_b = jnp.clip(ss.b_wi, 0, WMAX - 1)                # [B]
            ws_bv = ws_b[:, None]                                # [B, 1]
            wd_bv = jnp.clip(out_buf - ss.rx0, 0, WMAX - 1)      # [B, V]
            serv_wl_bv = serv_tab[ws_bv, wd_bv]                  # [B, V]
            perq_bv = perq_tab[ws_bv, wd_bv]
            # broadcast ARQ: a multicast attempt is paced and
            # CRC-checked against its WORST member link — group service
            # time and PER threshold are the max over member links.  The
            # hash draw below is link-independent, so per-member
            # outcomes are comonotone: "any member fails" is exactly
            # "the worst member fails", i.e. worst-link group
            # retransmission with all-or-nothing delivery to the set.
            serv_mcg = jnp.where(member2, serv_tab[ws_b][:, None, :],
                                 0).max(axis=-1)                 # [B, V]
            perq_mcg = jnp.where(member2, perq_tab[ws_b][:, None, :],
                                 0).max(axis=-1)
            serv_wl_bv = jnp.where(is_mc2, serv_mcg, serv_wl_bv)
            perq_bv = jnp.where(is_mc2, perq_mcg, perq_bv)
            pb_ok = st.pair_busy[ws_bv, wd_bv] <= t
            wl_ok &= ~out_is_wl | (whole & pb_ok)
            uid = psrc_c * 65536 + pidx_c
            fail_bv = _crc_fail(ss.phy_seed, uid, attempt,
                                perq_bv)                         # [B, V]
        elig = active & (occ > 0) & wl_ok & hold_ok \
            & (out_is_ej | ((out_vc >= 0) & (space > 0) & link_free))
        # multi-channel ejection: memory stacks sink `b_ej_ways` flits/cycle
        # (4-channel DRAM stacks, paper SIV); cores sink one.  The way is
        # vc % ways (memory requests: their pseudo-channel, via way_bv)
        vcol = jnp.arange(V, dtype=i32)[None, :]
        wo_base = jnp.where(out_is_ej,
                            out_wo + way_bv * ss.s_pad,
                            out_wo)
        wo = jnp.where(elig & ~is_mc2, wo_base, Wout)
        score2_all = (flat2d - rot) % NC
        score2 = jnp.where(elig, score2_all, BIG)
        segmin2 = jax.ops.segment_min(score2.reshape(-1), wo.reshape(-1),
                                      num_segments=Wout + 1)
        # multicast air winners: masked min per (sub-channel, receiver),
        # combined with the unicast slot minima; a multicast flies only if
        # it is the winner at EVERY member receiver
        rarr = jnp.arange(RXWMAX, dtype=i32)
        r_b = jnp.broadcast_to(ss.b_wi[:, None] % RXW, (B, V))   # [B, V]
        r_bc = jnp.clip(r_b, 0, RXWMAX - 1)
        mc_sc = jnp.where(is_mc2 & elig, score2_all, BIG)        # [B, V]
        mask4 = member2[None] & (r_bc[None, :, :, None]
                                 == rarr[:, None, None, None])   # [R,B,V,W]
        mc_min2 = jnp.where(mask4, mc_sc[None, :, :, None],
                            BIG).min(axis=(1, 2))                # [RXW, W]
        # every wireless sender's receiver slot id, reconstructed from its
        # own out_wo (slot = base + dst_wi*RXW + r): anchor = out_buf - rx0
        anchor = jnp.clip(out_buf - ss.rx0, 0, WMAX - 1)         # [B, V]
        slot_w = out_wo[:, :, None] \
            + (warr[None, None, :] - anchor[:, :, None]) * RXW   # [B, V, W]
        comb_w = jnp.minimum(
            segmin2[jnp.clip(slot_w, 0, Wout)],
            mc_min2[r_bc[:, :, None], warr[None, None, :]])      # [B, V, W]
        wl_all2 = jnp.where(member2, comb_w == score2_all[:, :, None],
                            True).all(axis=-1)                   # [B, V]
        mc_at_mine = mc_min2[r_bc, anchor]                       # [B, V]
        fwd_uni = elig & ~is_mc2 \
            & (score2 == segmin2[jnp.clip(wo, 0, Wout)]) & (score2 < BIG) \
            & (~out_is_wl | (score2 < mc_at_mine))
        fwd = fwd_uni | (elig & is_mc2 & wl_all2)

        # wireless sender-side cap: one flit per transmitting WI per cycle
        # (and one WI total in single-channel mode); no-op for the crossbar
        # medium
        is_wl_fwd = fwd & out_is_wl
        capped = is_wl_fwd & ss.wl_sender_cap
        snd = jnp.where(capped,
                        jnp.where(ss.wl_single, 0, ss.b_wi[:, None]), WMAX)
        segmin3 = jax.ops.segment_min(score2.reshape(-1), snd.reshape(-1),
                                      num_segments=WMAX + 1)
        keep = ~capped | (score2 == segmin3[jnp.clip(snd, 0, WMAX)])
        fwd &= keep
        is_wl_fwd = fwd & out_is_wl

        sent = sent + fwd.astype(i32)
        if phy_on:
            # CRC on the tail of every air attempt (see simulator.py):
            # NACK rewinds the sender, bounded-ARQ losers are dropped
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
            member_cnt = jnp.where(is_mc2, member2.sum(axis=-1), 1) \
                .astype(i32)
            wl_drop_flits = st.wl_drop_flits + post * jnp.where(
                drop, plen_bv * member_cnt, 0).sum().astype(i32)
        else:
            tail = fwd & (sent >= plen_bv)
            wl_nacks, wl_pkts = st.wl_nacks, st.wl_pkts
            pkts_dropped = st.pkts_dropped
            wl_drop_flits = st.wl_drop_flits
        ej = fwd & out_is_ej
        nej = fwd & ~out_is_ej

        # ejection stats
        flits_del = st.flits_del + post * ej.sum().astype(i32)
        tail_ej = tail & out_is_ej
        lat_ok = tail_ej & (born >= ss.warmup)
        pkts_del = st.pkts_del + post * tail_ej.sum().astype(i32)
        lat_sum = st.lat_sum + post * jnp.where(
            lat_ok, (t - born + 1).astype(jnp.float32), 0.0).sum()
        lat_pkts = st.lat_pkts + post * lat_ok.sum().astype(i32)

        # ---- phase barrier bookkeeping (trace tables; raw counts)
        phv = ss.phases[psrc_c, pidx_c]                          # [B, V]
        phase_del = st.phase_del \
            + (tail_ej & (phv == st.cur_phase)).sum().astype(i32)
        if phy_on:
            # ARQ-exhaustion drop: the ejection(s) this packet owed the
            # open phase will never happen — credit them now (one per
            # member copy for multicast, matching the trace table's
            # per-member phase_need) so a lossy trace closes its
            # barriers and drains instead of wedging forever
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

        # ---- closed-loop memory: bank model + reply gating, scatter style
        rdy, outst, dead = st.rdy, st.outst, st.dead
        bank_busy, bank_row = st.bank_busy, st.bank_row
        amat_sum, amat_pkts = st.amat_sum, st.amat_pkts
        mem_reads, mem_writes = st.mem_reads, st.mem_writes
        mem_row_hits = st.mem_row_hits
        mem_q_sum, mem_svc_sum = st.mem_q_sum, st.mem_svc_sum
        mem_flits = st.mem_flits
        if mem_on:
            f32 = jnp.float32
            Yp, _, BKp = bank_busy.shape
            # (a) request arrivals: every tail-ejected read/write enters
            # its (stack, channel, bank); way arbitration guarantees at
            # most one per (stack, channel) per cycle, so plain scatters
            # are conflict-free
            y_bv = jnp.broadcast_to(
                ss.stack_of[jnp.clip(ss.b_dst, 0, S - 1)][:, None], (B, V))
            is_rq = tail_ej & memrq_bv & (y_bv >= 0)             # [B, V]
            yc = jnp.clip(y_bv, 0, Yp - 1)
            bank_bv = jnp.clip(ss.mem_bank[psrc_c, pidx_c], 0, BKp - 1)
            row_bv = ss.mem_row[psrc_c, pidx_c]
            bb = bank_busy[yc, ch_bv, bank_bv]
            br = bank_row[yc, ch_bv, bank_bv]
            hit = is_rq & (br == row_bv)
            svc = jnp.where(hit, ss.t_row_hit, ss.t_row_miss)
            start = jnp.maximum(t + 1, bb)
            done = start + svc                                   # [B, V]
            ty = jnp.where(is_rq, yc, Yp).reshape(-1)
            bank_busy = bank_busy.at[
                ty, ch_bv.reshape(-1), bank_bv.reshape(-1)].set(
                done.reshape(-1), mode="drop")
            bank_row = bank_row.at[
                ty, ch_bv.reshape(-1), bank_bv.reshape(-1)].set(
                row_bv.reshape(-1), mode="drop")
            # reply birth into the paired slot's rdy
            rrow_c = jnp.clip(ss.reply_row[psrc_c, pidx_c], 0, Nn - 1)
            rslot_c = jnp.clip(ss.reply_slot[psrc_c, pidx_c], 0, Kk - 1)
            trow = jnp.where(is_rq, rrow_c, Nn).reshape(-1)
            rdy = rdy.at[trow, rslot_c.reshape(-1)].min(
                done.reshape(-1), mode="drop")
            # per-stack service stats
            rd_m = is_rq & (op_bv == 1)
            wr_m = is_rq & (op_bv == 2)
            postf = post.astype(f32)
            mem_reads = mem_reads.at[
                jnp.where(rd_m, yc, Yp).reshape(-1)].add(post, mode="drop")
            mem_writes = mem_writes.at[
                jnp.where(wr_m, yc, Yp).reshape(-1)].add(post, mode="drop")
            mem_row_hits = mem_row_hits.at[
                jnp.where(hit, yc, Yp).reshape(-1)].add(post, mode="drop")
            mem_q_sum = mem_q_sum.at[ty].add(
                (postf * (start - (t + 1)).astype(f32)).reshape(-1),
                mode="drop")
            mem_svc_sum = mem_svc_sum.at[ty].add(
                (postf * svc.astype(f32)).reshape(-1), mode="drop")
            data_bv = jnp.where(rd_m, ss.lens[rrow_c, rslot_c],
                                jnp.where(wr_m, plen_bv, 0))
            mem_flits = mem_flits.at[ty].add(
                (post * data_bv).reshape(-1), mode="drop")
            # (b) reply/ack completion at the requester: AMAT + credit
            is_rep = tail_ej & ((op_bv == 3) | (op_bv == 4))
            rb = ss.req_birth[psrc_c, pidx_c]
            amat_ok = is_rep & (op_bv == 3) & (rb >= ss.warmup)
            amat_sum = amat_sum + post * jnp.where(
                amat_ok, (t - rb + 1).astype(f32), 0.0).sum()
            amat_pkts = amat_pkts + post * amat_ok.sum().astype(i32)
            rq_t = jnp.where(is_rep, ss.req_src[psrc_c, pidx_c], Nn)
            outst = outst.at[rq_t.reshape(-1)].add(-1, mode="drop")

        # non-eject: schedule arrival downstream, occupy link / rx / channel
        if phy_on:
            first_wl = first_wl_phy
            ctrl_bv = jnp.maximum(1, ss.ctrl_flits * serv_wl_bv)
            lat_wl_bv = (ss.lat_wl - ss.serv_wl) + serv_wl_bv
            # failing attempts occupy the channel but deliver nothing
            nej_del = nej & ~(out_is_wl & fail_bv)
        else:
            first_wl = is_wl_fwd & (sent == 1)   # header => control packet
            ctrl_bv = ss.ctrl_cycles
            lat_wl_bv = ss.lat_wl
            serv_wl_bv = ss.serv_wl
            nej_del = nej
        lat_t = jnp.where(out_is_wl, lat_wl_bv, ss.b_lat[ob_c]) \
            + jnp.where(first_wl & ~ss.wl_rx_busy, ctrl_bv, 0)
        serv_t = jnp.where(out_is_wl, serv_wl_bv, ss.b_serv[ob_c]) \
            + jnp.where(first_wl, ctrl_bv, 0)
        nb_t = jnp.where(nej_del & ~is_mc2, out_buf, B).reshape(-1)
        nv_t = ovc_c.reshape(-1)
        nd_t = jnp.clip(lat_t - 1, 0, DMAX - 1).reshape(-1)
        pipe = pipe.at[nb_t, nv_t, nd_t].add(1, mode="drop")
        # multicast fan-out: receiver-side — every member copy of a
        # transmitting group receives the flit (one air occupancy, D pipes)
        svm = jnp.clip(mc_src, 0, NC - 1)
        is_mc2_f = is_mc2.reshape(-1)
        ident_mc = (mc_src >= 0) & is_mc2_f[svm] & ss.b_is_rx[:, None] \
            & (mc_id >= 0) & (mc_id.reshape(-1)[svm] == mc_id)
        inc_any_mc = ident_mc & fwd.reshape(-1)[svm]             # [B, V]
        if phy_on:
            # broadcast ARQ: a failing group attempt occupies the channel
            # and the member receivers but delivers to none of them
            # (all-or-nothing — the shared hash fails every member at
            # once); the fan-out below uses the delivery-gated mask
            inc_mc = ident_mc & nej_del.reshape(-1)[svm]
        else:
            inc_mc = inc_any_mc
        d_in_mc = jnp.clip(lat_t.reshape(-1)[svm] - 1, 0, DMAX - 1)
        pipe = pipe + (inc_mc[:, :, None]
                       & (jnp.arange(DMAX) == d_in_mc[:, :, None])
                       ).astype(pipe.dtype)
        # crossbar: wireless winners do not serialize the receiver
        bu_t = jnp.where(nej & ~is_mc2 & (~out_is_wl | ss.wl_rx_busy),
                         out_buf, B).reshape(-1)
        busy_until = st.busy_until.at[bu_t].set(
            (t + serv_t).reshape(-1), mode="drop")
        ser_mc = inc_any_mc & ss.wl_rx_busy
        serv_mc = serv_t.reshape(-1)[svm]
        busy_until = jnp.where(
            ser_mc.any(axis=1),
            t + jnp.where(ser_mc, serv_mc, 0).sum(axis=1), busy_until)
        wl_busy_until = jnp.where(
            is_wl_fwd.any(),
            t + (jnp.where(is_wl_fwd, serv_t, 0)).max(), st.wl_busy_until)
        counts_into = st.counts_into.at[
            jnp.where(nej_del & ~is_mc2 & (post > 0), out_buf,
                      B).reshape(-1)].add(1, mode="drop")
        # broadcast energy is paid once: count only the primary member copy
        prim_buf = ss.rx0 + ss.mc_prim[mcid_c2]                  # [B, V]
        counts_into = counts_into + post * (
            inc_mc & (b_ids[:, None] == prim_buf)).sum(axis=1).astype(i32)
        count_switch = st.count_switch + post * fwd.sum().astype(i32)
        ctrl_count = st.ctrl_count + post * first_wl.sum().astype(i32)
        wl_tx_flits = st.wl_tx_flits + post * is_wl_fwd.sum().astype(i32)
        wl_rx_flits = st.wl_rx_flits + post * (
            (nej_del & ~is_mc2 & out_is_wl).sum() + inc_mc.sum()).astype(i32)
        # the feeding group's tail has been sent: detach the copies
        # (ARQ-dropped groups detach below, with their member copies
        # freed alongside the sender)
        mc_src = jnp.where(ident_mc & tail.reshape(-1)[svm], -1, mc_src)

        mem_drop_reads = st.mem_drop_reads
        wl_rate_flits = st.wl_rate_flits
        wl_rate_fail = st.wl_rate_fail
        if phy_on:
            # per-(src, dst) WI pacing + energy counters, scatter style:
            # at most one air transmission per pair per cycle, so the
            # scatters are conflict-free.  A multicast sender is one slot
            # with wd_bv = its anchor, so the air/pair accounting lands
            # on the routed (sender, anchor) pair once — matching the
            # gather engine's own-column anchor mask.
            ws_col = jnp.broadcast_to(
                jnp.clip(ss.b_wi, 0, WMAX - 1)[:, None], (B, V))
            pw_s = jnp.where(is_wl_fwd, ws_col, WMAX).reshape(-1)
            pw_d = wd_bv.reshape(-1)
            pair_busy = st.pair_busy.at[pw_s, pw_d].set(
                (t + serv_t).reshape(-1), mode="drop")
            wl_pair_flits = st.wl_pair_flits.at[pw_s, pw_d].add(
                post, mode="drop")
            pw_sf = jnp.where(is_wl_fwd & fail_bv, ws_col,
                              WMAX).reshape(-1)
            wl_fail_flits = st.wl_fail_flits.at[pw_sf, pw_d].add(
                post, mode="drop")
            if living:
                # per-rate-entry attempt counters: when the pair's entry
                # moves mid-run the per-pair counters no longer identify
                # a single rate, so metrics needs the exact [R] split
                # (attributed to the anchor pair's current entry)
                Rr = st.wl_rate_flits.shape[0]
                rt_bv = st.wl_rate_d[ws_col, wd_bv]              # [B, V]
                rt_t = jnp.where(is_wl_fwd, rt_bv, Rr).reshape(-1)
                wl_rate_flits = wl_rate_flits.at[rt_t].add(
                    post, mode="drop")
                rt_tf = jnp.where(is_wl_fwd & fail_bv, rt_bv,
                                  Rr).reshape(-1)
                wl_rate_fail = wl_rate_fail.at[rt_tf].add(
                    post, mode="drop")
            if mem_on:
                # ARQ drop of a memory request/reply: credit the
                # requester's window and tombstone a dropped request's
                # reply slot (see simulator.py) — scatter style; each
                # drop targets a distinct slot, so scatters are
                # conflict-free (outst uses duplicate-safe add)
                Nn2, Kk2 = ss.phases.shape
                is_rqd = drop & memrq_bv                         # [B, V]
                is_repd = drop & ((op_bv == 3) | (op_bv == 4))
                tgt_d = jnp.where(
                    is_rqd, psrc_c,
                    jnp.where(is_repd,
                              jnp.clip(ss.req_src[psrc_c, pidx_c],
                                       0, Nn2 - 1), Nn2))
                outst = outst.at[tgt_d.reshape(-1)].add(-1, mode="drop")
                rr_d = jnp.where(
                    is_rqd,
                    jnp.clip(ss.reply_row[psrc_c, pidx_c], 0, Nn2 - 1),
                    Nn2).reshape(-1)
                rs_d = jnp.clip(ss.reply_slot[psrc_c, pidx_c],
                                0, Kk2 - 1).reshape(-1)
                dead = dead.at[rr_d, rs_d].set(True, mode="drop")
                # lost read round trips: a dropped read request or read
                # reply means the requester never sees its data
                mem_drop_reads = mem_drop_reads + post * (
                    drop & ((op_bv == 1) | (op_bv == 3))).sum().astype(i32)
            # a dropped packet frees the receiver VC its claim held —
            # unicast via the (out_buf, out_vc) scatter; a dropped
            # multicast group frees EVERY member copy it installed (the
            # sender's out_vc is the "granted" sentinel, not a VC)
            db_t = jnp.where(drop & ~is_mc2, out_buf, B).reshape(-1)
            rx_dropped = jnp.zeros((B, V), bool).at[
                db_t, ovc_c.reshape(-1)].set(True, mode="drop")
            rx_dropped = rx_dropped | (ident_mc & drop.reshape(-1)[svm])
            mc_src = jnp.where(rx_dropped, -1, mc_src)
            freed = tail | drop | rx_dropped
        else:
            pair_busy = st.pair_busy
            wl_pair_flits = st.wl_pair_flits
            wl_fail_flits = st.wl_fail_flits
            freed = tail

        # free VCs whose tail left (phy: plus ARQ drops, both sides)
        pkt_src = jnp.where(freed, -1, pkt_src)
        out_vc = jnp.where(freed, -1, out_vc)
        out_is_wl = jnp.where(freed, False, out_is_wl)
        out_is_ej = jnp.where(freed, False, out_is_ej)
        active = pkt_src >= 0

        # ---- 3. injection -------------------------------------------------
        N, K = ss.births.shape
        n_ar = jnp.arange(N)
        qh = jnp.clip(st.q_head, 0, K - 1)
        birth_n = ss.births[n_ar, qh]
        ib = ss.inj_buf                                         # [N]
        ifree = (pkt_src[ib] < 0) & classA[None, :]             # [N, V]
        ihas = ifree.any(axis=1)
        ivc = jnp.argmax(ifree, axis=1).astype(i32)
        # phase gate: a packet injects only once its phase is open
        ph_ok = (ss.n_phases == 0) | (ss.phases[n_ar, qh] <= cur_phase)
        if mem_on:
            # reply slots are born by the bank model (rdy); requests gate
            # on the per-core in-flight window (see simulator.py)
            birth_n = jnp.minimum(birth_n, rdy[n_ar, qh])
            opq = ss.mem_op[n_ar, qh]
            is_tx = (opq == 1) | (opq == 2)
            ph_ok &= ~is_tx | (outst < ss.max_outst)
        can_new = (st.inj_vc < 0) & (st.q_head < K) & (birth_n <= t) \
            & ihas & ph_ok
        # multicast slots: dests = -(1 + m); route to the group's anchor
        dst_raw = ss.dests[n_ar, qh]
        mcv_n = jnp.where(dst_raw < 0, -(dst_raw + 1), -1)      # [N]
        dst_n = jnp.where(
            dst_raw < 0, ss.mc_route[jnp.clip(mcv_n, 0, M - 1)], dst_raw)
        r_oo, r_ob, r_owo, r_owl, r_oej = _route_fields(
            ss, ss.src_switch, dst_n)

        ib_t = jnp.where(can_new, ib, B)

        def iclaim(arr, val):
            return arr.at[ib_t, ivc].set(val, mode="drop")

        pkt_src = iclaim(pkt_src, n_ar.astype(i32))
        pkt_idx = iclaim(pkt_idx, st.q_head)
        pkt_dst = iclaim(pkt_dst, dst_n)
        born = iclaim(born, birth_n)
        out_o = iclaim(out_o, r_oo.astype(i32))
        out_buf = iclaim(out_buf, r_ob.astype(i32))
        out_wo = iclaim(out_wo, r_owo.astype(i32))
        out_is_wl = iclaim(out_is_wl, r_owl)
        out_is_ej = iclaim(out_is_ej, r_oej)
        out_vc = iclaim(out_vc, jnp.full((N,), -1, out_vc.dtype))
        phase2 = iclaim(phase2, jnp.zeros((N,), bool))
        mc_id = iclaim(mc_id, mcv_n)
        mc_src = iclaim(mc_src, jnp.full((N,), -1, i32))
        attempt = iclaim(attempt, jnp.zeros((N,), attempt.dtype))
        rcvd = iclaim(rcvd, jnp.zeros((N,), i32))
        sent = iclaim(sent, jnp.zeros((N,), i32))
        inj_vc = jnp.where(can_new, ivc.astype(st.inj_vc.dtype),
                           st.inj_vc)
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

        # push one flit/cycle/core while there is space
        iv_c = jnp.clip(inj_vc, 0, V - 1)
        iocc = rcvd[ib, iv_c] - sent[ib, iv_c]
        can_push = (inj_vc >= 0) & (iocc < ss.b_depth[ib])
        pb_t = jnp.where(can_push, ib, B)
        rcvd = rcvd.at[pb_t, iv_c].add(1, mode="drop")
        inj_pushed = inj_pushed + can_push.astype(inj_pushed.dtype)
        flits_inj = st.flits_inj + post * can_push.sum().astype(i32)
        # the source's current packet sits at q_head - 1 (claims advance
        # the head); its per-slot length ends the push burst
        plen_cur = ss.lens[n_ar, jnp.clip(q_head - 1, 0, K - 1)] \
            if mem_on else ss.pkt_len
        done = can_push & (inj_pushed >= plen_cur)
        inj_vc = jnp.where(done, -1, inj_vc)

        # ---- 4. receiver wake/sleep accounting ([17]) ---------------------
        rx_ids = ss.rx0 + jnp.arange(WMAX, dtype=i32)
        rx_got = jnp.take(arrive.sum(axis=1), jnp.clip(rx_ids, 0, B - 1)) > 0
        rx_busy = jnp.take(busy_until, jnp.clip(rx_ids, 0, B - 1)) > t
        rx_active = (rx_got | rx_busy) & (jnp.arange(WMAX) < ss.n_wi)
        n_rx_on = rx_active.sum().astype(i32)
        awake = jnp.where(ss.sleepy, n_rx_on, ss.n_wi)
        awake_cycles = st.awake_cycles + post * awake
        sleep_cycles = st.sleep_cycles + post * (ss.n_wi - awake)

        return SimState(
            pkt_src=pkt_src, pkt_idx=pkt_idx, pkt_dst=pkt_dst, born=born,
            out_o=out_o, out_buf=out_buf, out_wo=out_wo, out_is_wl=out_is_wl,
            out_is_ej=out_is_ej, out_vc=out_vc, phase2=phase2,
            rcvd=rcvd, sent=sent, mc_id=mc_id, mc_src=mc_src,
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


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _run_mono(ss: SimStatic, st: SimState, cycles: int, B: int,
              Wout: int, RXW: int = 1, mem_on: bool = False,
              phy_on: bool = False, drift_on: bool = False,
              reselect: bool = False) -> SimState:
    """Every cycle of the budget, one scan step each."""
    step = make_step(B, Wout, RXW, mem_on, phy_on, drift_on, reselect)

    def body(carry, t):
        return step(ss, carry, t), None

    final, _ = jax.lax.scan(body, st, jnp.arange(cycles, dtype=jnp.int32))
    return final._replace(cycles_run=jnp.int32(cycles),
                          drain_cycle=jnp.int32(cycles))


# --------------------------------------------------------------------------
# host-side packing
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PackedSim:
    ss: SimStatic
    B: int
    Wout: int
    n_cores: int
    Lw: int
    n_inj: int
    topo: Topology
    rt: RoutingTables
    phy: PhyParams
    sim: SimParams
    RXW: int = 1
    mem_on: bool = False
    Y: int = 1
    BK: int = 1
    phy_on: bool = False
    drift_on: bool = False    # living channel: SNR aging walk compiled in
    reselect: bool = False    # living channel: in-scan rate re-selection
    phy_link: object = None


def pack(topo: Topology, rt: RoutingTables, tt: TrafficTable,
         phy: PhyParams, sim: SimParams,
         b_bucket: int = 64, s_bucket: int = 8, r_bucket: int = 64,
         k_bucket: int = 32, phy_spec=None) -> PackedSim:
    from refsim.rates import drift_amp_q, pack_link_state
    Lw = topo.n_links
    n_inj = tt.n_sources
    n_wi = topo.n_wi
    B = _bucket(Lw + n_inj + n_wi, b_bucket)
    S = _bucket(topo.n_switches + 1, s_bucket)
    Wp = len(topo.wl_pairs)
    R = _bucket(Lw + Wp + topo.n_switches, r_bucket)
    medium = phy.wireless_medium
    # output arbitration slots: wired links + ejection (4 ways for memory
    # stacks) + wireless slots (crossbar: one per WI pair; matching/single:
    # one per receiver)
    EJ_WAYS = 4
    RXW = max(1, int(phy.wireless_rx_streams)) if medium == "crossbar" else 1
    n_wl_slots = WMAX * RXW
    Wout = _bucket(Lw + EJ_WAYS * S + n_wl_slots, b_bucket)
    N = n_inj
    K = _bucket(tt.k, k_bucket)
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
    rx0 = Lw + n_inj
    serv_wl = phy.wireless_flit_cycles
    for w in range(n_wi):
        b = rx0 + w
        b_dst[b] = topo.wi_switch[w]
        b_lat[b] = pipe_stages + serv_wl
        b_epb[b] = phy.e_wireless_pj_bit
        b_is_rx[b] = True
    # sender WI of any buffer whose switch hosts a WI
    for b in range(rx0):          # rx buffers themselves never send wireless
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

    # lossy PHY: the shared helper guarantees both engines
    # pack identical link state (see phy.rates.pack_link_state)
    pli, phy_on, rx_hold = pack_link_state(
        topo, phy, tt, phy_spec, b_dst, b_depth, b_epb, rx0)
    # living channel: SNR drift and/or in-scan rate
    # re-selection — static flags, part of the compiled program
    drift_on = bool(phy_on and phy_spec.drift_amp_db > 0.0)
    reselect = bool(phy_on and phy_spec.reselect)
    living = drift_on or reselect

    # routing lookup tables
    next_out = np.full((S, S), 0, np.int32)
    next_out[:topo.n_switches, :topo.n_switches] = rt.next_out
    o_buf = np.full(R, B, np.int32)
    o_wo = np.full(R, Wout, np.int32)
    o_is_wl = np.zeros(R, bool)
    o_is_ej = np.zeros(R, bool)
    for o in range(Lw):
        o_buf[o] = o
        o_wo[o] = o
    for p in range(Wp):
        o = Lw + p
        src_wi = int(topo.wl_pairs[p, 0])
        dst_wi = int(topo.wl_pairs[p, 1])
        o_buf[o] = rx0 + dst_wi
        # rx sub-channel slot: each receiver serves RXW concurrent streams
        slot = dst_wi * RXW + (src_wi % RXW)
        o_wo[o] = Lw + EJ_WAYS * S + slot
        o_is_wl[o] = True
    for s in range(topo.n_switches):
        o = Lw + Wp + s
        o_wo[o] = Lw + s          # base slot; step adds (vc % ways) * S
        o_is_ej[o] = True
    assert rt.n_outputs == Lw + Wp + topo.n_switches
    assert Lw + EJ_WAYS * S + n_wl_slots <= Wout + 1, (Lw, S, n_wl_slots, Wout)

    births = np.full((N, K), NO_PKT, np.int32)
    births[:, :tt.k] = tt.births
    dests = np.zeros((N, K), np.int32)
    dests[:, :tt.k] = tt.dests

    # trace tables (phase barriers + multicast groups)
    Pn = getattr(tt, "n_phases", 0)
    Mn = getattr(tt, "n_mc", 0)
    P = _bucket(Pn, 8)
    M = _bucket(Mn, 8)
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
        mc_dst[:Mn] = np.clip(tt.mc_dst, 0, None)
        mc_route[:Mn] = tt.mc_route
        mc_prim[:Mn] = np.argmax(tt.mc_member, axis=1)

    # memory tables (closed-loop request/reply; dims mirror simulator.pack
    # so the differential tests compare identically-shaped states)
    mem_on = getattr(tt, "mem_op", None) is not None
    dram = (getattr(tt, "dram", None) or DEFAULT_DRAM) if mem_on \
        else DEFAULT_DRAM
    Y = _bucket(topo.n_mem, 4)
    BK = _bucket(dram.n_banks if mem_on else 1, 8)
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
        lens[:, :tt.k] = tt.lens
        mem_op[:, :tt.k] = tt.mem_op
        mem_ch[:, :tt.k] = tt.mem_ch
        mem_bank[:, :tt.k] = tt.mem_bank
        mem_row[:, :tt.k] = tt.mem_row
        reply_row[:, :tt.k] = tt.reply_row
        reply_slot[:, :tt.k] = tt.reply_slot
        req_src[:, :tt.k] = tt.req_src
        req_birth[:, :tt.k] = tt.req_birth
    stack_of = np.full(S, -1, np.int32)
    for y, s in enumerate(np.nonzero(topo.is_mem)[0]):
        stack_of[int(s)] = y
    max_outst = dram.max_outstanding if mem_on else 2**30

    ctrl_cycles = max(1, phy.ctrl_packet_flits * serv_wl)

    ss = SimStatic(
        b_dst=jnp.asarray(b_dst), b_serv=jnp.asarray(b_serv),
        b_lat=jnp.asarray(b_lat), b_epb=jnp.asarray(b_epb),
        b_depth=jnp.asarray(b_depth), b_wi=jnp.asarray(b_wi),
        b_is_rx=jnp.asarray(b_is_rx),
        b_ej_ways=jnp.asarray(b_ej_ways), s_pad=jnp.int32(S),
        next_out=jnp.asarray(next_out),
        o_buf=jnp.asarray(o_buf), o_wo=jnp.asarray(o_wo),
        o_is_wl=jnp.asarray(o_is_wl), o_is_ej=jnp.asarray(o_is_ej),
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
        stack_of=jnp.asarray(stack_of),
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
    return PackedSim(ss=ss, B=B, Wout=Wout, n_cores=topo.n_cores, Lw=Lw,
                     n_inj=n_inj, topo=topo, rt=rt, phy=phy, sim=sim,
                     RXW=RXW, mem_on=mem_on, Y=Y, BK=BK, phy_on=phy_on,
                     drift_on=drift_on, reselect=reselect, phy_link=pli)


def run(ps: PackedSim) -> SimState:
    """The lane's final state after ``ps.sim.cycles`` cycles."""
    N, K = ps.ss.births.shape
    living = ps.drift_on or ps.reselect
    R = int(ps.ss.wl_serv_r.shape[0])
    st = init_state(ps.B, int(N), int(ps.ss.phase_need.shape[0]),
                    int(K), ps.Y, ps.BK, mem_on=ps.mem_on,
                    phy_on=ps.phy_on, living=living, R=R)
    return jax.block_until_ready(
        _run_mono(ps.ss, st, int(ps.sim.cycles), ps.B, ps.Wout, ps.RXW,
                  ps.mem_on, ps.phy_on, ps.drift_on, ps.reselect))
