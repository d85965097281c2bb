"""The one traffic generator: a traffic file and a configuration give each
call's grid of design points, as plain data.

A call is what an architect's script submits at once: one sweep grid
(``entry: "sweep"``) or one design point (``entry: "point"``).  The grid is
the product of the traffic file's fabrics, loads and channel arms with the
configuration's drift amplitudes.  Sizes never depend on the seed; the seed
only picks the traffic and channel draws, from a fixed rotation derived from
``--seed`` so that no two calls in a row are the same work.

The points are plain dictionaries so that the program and the plain
reference each build their own objects from them.
"""
from __future__ import annotations

import itertools

import numpy as np

SEED_MOD = 2**31       # PhySweepSpec.seed is packed as a uint32


def call_seeds(seed: int, rotation: int) -> list[int]:
    """The rotation of per-call seeds that ``--seed`` stands for."""
    if rotation < 1:
        raise ValueError(f"seed_rotation must be >= 1, got {rotation}")
    state = np.random.SeedSequence(int(seed)).generate_state(rotation,
                                                             np.uint64)
    return [int(s % SEED_MOD) for s in state]


def call_points(config: dict, traffic: dict, seed: int) -> list[dict]:
    """The design points of one call whose traffic seed is ``seed``."""
    entry = traffic["entry"]
    if entry not in ("sweep", "point"):
        raise ValueError(f"unknown entry {entry!r}")
    arms = traffic.get("arms") or [None]
    channel = config.get("channel")
    if (channel is None) != (arms == [None]):
        raise ValueError("channel arms need a configuration with a channel, "
                         "and a channel needs arms")
    amps = config.get("drift_amps_db") or [0.0]
    points = []
    for fabric, load, amp, arm in itertools.product(
            traffic["fabrics"], traffic["loads"], amps, arms):
        phy = None
        if arm is not None:
            phy = {"link_budget_db": channel["link_budget_db"],
                   "policy": arm["policy"], "reselect": bool(arm["reselect"]),
                   "drift_amp_db": float(amp),
                   "drift_period": channel["drift_period"],
                   "max_retx": channel["max_retx"], "seed": seed,
                   "pl_exp": channel["pl_exp"], "d0_mm": channel["d0_mm"],
                   "sigma_shadow_db": channel["sigma_shadow_db"]}
        points.append({"fabric": fabric, "load": float(load),
                       "p_mem": config["p_mem"], "cycles": config["cycles"],
                       "warmup": config["warmup"], "seed": seed, "phy": phy})
    if entry == "point" and len(points) != 1:
        raise ValueError(f"a point call holds one point, got {len(points)}")
    return points


def phy_params_kwargs(config: dict) -> dict:
    """The PhyParams fields the configuration states."""
    keys = ("clock_ghz", "flit_bits", "pkt_flits", "num_vcs", "buf_depth",
            "switch_stages", "wireless_medium", "wireless_rx_streams")
    return {k: config[k] for k in keys}


def sample_lanes(n_calls: int, lanes_per_call: int, chips: int, n: int,
                 seed: int) -> list[tuple[int, int]]:
    """(call, lane) pairs to check, drawn from ``seed``.

    The lanes of a call are cut into ``chips`` contiguous blocks, as a
    sharded launch places them, and the draw takes from every block in
    turn, so that each chip's share of the work is checked.
    """
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0DE]))
    per = -(-lanes_per_call // chips)
    blocks = [list(range(b * per, min((b + 1) * per, lanes_per_call)))
              for b in range(chips)]
    blocks = [b for b in blocks if b]
    pools = [[(c, lane) for c in range(n_calls) for lane in blk]
             for blk in blocks]
    for pool in pools:
        rng.shuffle(pool)
    picked: list[tuple[int, int]] = []
    while len(picked) < n and any(pools):
        for pool in pools:
            if pool and len(picked) < n:
                picked.append(pool.pop())
    return sorted(picked)
