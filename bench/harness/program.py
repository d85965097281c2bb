"""The system under test, seen from outside: its entry, and spans around
the calls into each of its layers.

Nothing here changes what the program computes.  ``Recorder`` wraps the
module attributes that ``repro.core.sweep.run_sweep_batched`` looks up at
call time (``_build_point``, ``simulator.pack``, ``simulator.run_batch``,
``compute_metrics_batch``), times each call on the host clock, marks it for
the profiler with a ``TraceAnnotation``, and keeps each launch's final state
for the check against the reference.
"""
from __future__ import annotations

import contextlib
import time

from harness.grid import phy_params_kwargs

# host spans show in the profiler's trace as bench.<layer>
SPAN_PREFIX = "bench."

# lowering events: one per jitted program that is traced and lowered,
# whether the backend then compiles it or fetches it from the cache
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def sweep_point(p: dict, config: dict):
    """The program's ``SweepPoint`` for one point of ``grid.call_points``."""
    from repro.core.constants import Fabric, MacMode, PhyParams, SimParams
    from repro.core.sweep import SweepPoint
    from repro.phy import ChannelParams, PhySweepSpec

    spec = None
    if p["phy"] is not None:
        q = p["phy"]
        spec = PhySweepSpec(
            link_budget_db=q["link_budget_db"], policy=q["policy"],
            max_retx=q["max_retx"], seed=q["seed"],
            channel=ChannelParams(pl_exp=q["pl_exp"], d0_mm=q["d0_mm"],
                                  sigma_shadow_db=q["sigma_shadow_db"]),
            drift_amp_db=q["drift_amp_db"], drift_period=q["drift_period"],
            reselect=q["reselect"])
    sim = SimParams(cycles=p["cycles"], warmup=p["warmup"],
                    mac=MacMode[config["mac"].upper()],
                    sleepy_rx=config["sleepy_rx"], seed=p["seed"])
    return SweepPoint(config["n_chips"], config["n_mem"],
                      Fabric[p["fabric"].upper()], load=p["load"],
                      p_mem=p["p_mem"], phy=PhyParams(**phy_params_kwargs(
                          config)), sim=sim, phy_spec=spec,
                      wireless_weight=config["wireless_weight"])


def check_constants(config: dict) -> None:
    """The program's compiled-in settings must be the configuration's."""
    from repro.core import chunked, simulator
    from repro.phy import rates
    got = {"num_vcs": simulator.V, "buf_depth": simulator.DEPTH,
           "traffic_pattern": "uniform_random"}
    if config.get("channel"):
        got["rate_table_gbps"] = [e.gbps for e in rates.DEFAULT_RATE_TABLE]
        got["reselect_every_cycles"] = chunked.CHUNK_CYCLES
    want = dict(config, **(config.get("channel") or {}))
    bad = {k: (v, want[k]) for k, v in got.items() if v != want[k]}
    if bad:
        raise RuntimeError(f"program settings differ from the configuration "
                           f"(program, configuration): {bad}")


class Recorder:
    """Host spans around the program's layers, and each launch's output."""

    def __init__(self, jax):
        from repro.core import simulator, sweep
        self.jax, self.sim, self.sweep = jax, simulator, sweep
        self.spans: list[tuple[str, float, float]] = []
        # per launch: the SweepPoint of each lane, and the final state
        self.launches: list[tuple[list, object]] = []
        self._point_of: dict[int, tuple] = {}     # id(table) -> (table, pt)
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._orig = {}

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _LOWERING_EVENT:
            self.lowerings += 1

    def reset(self) -> None:
        self.spans.clear()
        self.launches.clear()
        self._point_of.clear()

    def take_launches(self) -> list[tuple[list, object]]:
        """The launches since the last take, and forget them."""
        out = list(self.launches)
        self.launches.clear()
        self._point_of.clear()
        return out

    @contextlib.contextmanager
    def span(self, name: str):
        with self.jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def _wrap(self, owner, attr: str, name: str, after=None):
        inner = getattr(owner, attr)
        self._orig[(owner, attr)] = inner

        def wrapped(*args, **kw):
            with self.span(name):
                out = inner(*args, **kw)
            if after is not None:
                after(args, out)
            return out

        setattr(owner, attr, wrapped)

    # a launch's lanes are traced back to their points: _build_point(p)
    # makes the traffic table that pack(topo, rt, tt, ...) packs
    def _built(self, args, out) -> None:
        self._point_of[id(out[2])] = (out[2], args[0])

    def _packed(self, args, ps) -> None:
        self._point_of[id(ps)] = (ps, self._point_of[id(args[2])][1])

    def _launched(self, args, out) -> None:
        self.launches.append(([self._point_of[id(ps)][1] for ps in args[0]],
                              out))

    def install(self) -> None:
        self._wrap(self.sweep, "_build_point", "build", self._built)
        self._wrap(self.sim, "pack", "pack", self._packed)
        self._wrap(self.sim, "run_batch", "launch", self._launched)
        self._wrap(self.sweep, "compute_metrics_batch", "metrics")

    def uninstall(self) -> None:
        for (owner, attr), inner in self._orig.items():
            setattr(owner, attr, inner)
        self._orig.clear()


def run_call(entry: str, points: list, devices: int, cycles: int | None = None):
    """One call of the user's path; returns its ``Metrics``, in order."""
    from repro.core.sweep import run_point, run_sweep_batched
    if entry == "point" and cycles is None:
        (p,) = points
        return [run_point(p.n_chips, p.n_mem, p.fabric, p.load, p_mem=p.p_mem,
                          phy=p.phy, sim=p.sim, phy_spec=p.phy_spec,
                          wireless_weight=p.wireless_weight)]
    # a point warms through the function run_point calls, with the budget
    # override run_point lacks: a batch of one takes the same programs
    return run_sweep_batched(points, cycles=cycles,
                             devices=None if entry == "point" else devices)
