"""Smoke test of the simulator's main path on a TPU chip.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the sharded sweep only

Everything runs in this one process, which alone touches JAX.  Phases,
in order; the first that fails raises and the exit code is non-zero:

1. device -- the default backend must be a TPU.  There is no CPU
   fallback, so ``JAX_PLATFORMS=cpu python chip_smoke.py`` fails here.
2. goldens -- every committed point in ``tests/goldens/`` reruns through
   ``run_point`` on the chip with the case and sim parameters its file
   records.  Integer counters must match exactly and derived floats at
   ``rel=1e-6``: the energies are f32 sums reduced on the device, whose
   last bits depend on the reduction order of the backend.
3. host -- a static lossy-PHY point and a drifting, re-selecting
   living-channel point run on the chip and on the host CPU backend; every
   integer leaf of the final ``SimState`` must be equal.
4. main -- fig2's grid at the paper's budget and fig9's smoke grid through
   the suites ``python -m benchmarks.run`` calls; every ``*.check`` row
   they print must read ``True``.

``--four-chips`` runs only fig3's 21-lane latency grid, sharded over four
chips by ``run_batch``'s default ``pmap`` path and again with
``devices=1``.  Every ``SimState`` leaf must be bitwise equal, and the
sharded output must sit on four distinct devices.  Its budget is cut from
the paper's 10,000 cycles to 3,000 (1,000 warm-up): with ``devices=1`` the
21 lanes run one after another on one chip, about 25 s each at the paper
budget, while all four chips are held.

Each simulator launch prints its compile seconds, run seconds (the launch
ends in ``block_until_ready``) and simulated lane-cycles per second.  These
are single observations, not benchmark numbers.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

# compile work that JAX reports per jitted call (trace, lowering, backend
# compile or persistent-cache fetch): a launch's set-up seconds
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
REL = 1e-6


def say(*fields) -> None:
    print(",".join(str(f) for f in fields), flush=True)


def check_device(jax, want: int | None) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    say("device", f"platform={d.platform}", f"kind={d.device_kind}",
        f"count={len(devs)}", f"local={jax.local_device_count()}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (default backend is "
                 f"{d.platform!r}); this smoke test has no CPU fallback")
    if want is not None and len(devs) != want:
        sys.exit(f"chip_smoke: --four-chips needs {want} devices, "
                 f"found {len(devs)}")
    return info


class Launches:
    """Times every ``simulator.run_batch`` launch made inside ``watch``."""

    def __init__(self, jax, simulator):
        self.sim = simulator
        self.compile_s = 0.0
        self.states: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def watch(self, tag: str):
        import numpy as np
        inner = self.sim.run_batch

        def run_batch(pss, *args, **kw):
            c0, t0 = self.compile_s, time.perf_counter()
            out = inner(pss, *args, **kw)   # returns after block_until_ready
            wall = time.perf_counter() - t0
            comp = self.compile_s - c0
            run = wall - comp
            lane_cycles = int(np.asarray(out.cycles_run).sum())
            executed = int(np.asarray(out.drain_cycle).sum())
            say("launch", tag, f"lanes={len(pss)}",
                f"devices={kw.get('devices')}", f"compile_s={comp}",
                f"run_s={run}", f"lane_cycles={lane_cycles}",
                f"executed_cycles={executed}",
                f"lane_cycles_per_s={lane_cycles / run if run > 0 else 'inf'}",
                "one run, not a benchmark")
            self.states.append(out)
            return out

        self.sim.run_batch = run_batch
        try:
            yield self
        finally:
            self.sim.run_batch = inner


def _agree(got, want) -> bool:
    if isinstance(want, int) and not isinstance(want, bool):
        return int(got) == want
    return math.isclose(float(got), float(want), rel_tol=REL)


def phase_goldens(launches) -> None:
    """Rerun every committed golden on the chip and compare."""
    from repro.core.constants import Fabric, SimParams
    from repro.core.sweep import run_point
    from repro.memory import MemSweepSpec

    paths = sorted((ROOT / "tests" / "goldens").glob("*.json"))
    if not paths:
        raise RuntimeError("no goldens found under tests/goldens")
    for path in paths:
        golden = json.loads(path.read_text())
        kw = dict(golden["case"])
        kw["fabric"] = Fabric(kw["fabric"])
        if kw.pop("memcl", None):
            kw["mem"] = MemSweepSpec(load=kw.pop("load"))
            kw["load"] = 0.0
        with launches.watch(f"golden:{path.stem}"):
            m = run_point(sim=SimParams(**golden["sim"]), **kw)
        bad = []
        for key, want in golden["metrics"].items():
            if isinstance(want, dict):
                src = m.energy_breakdown if key == "energy_breakdown" \
                    else {k: getattr(m, k) for k in want}
                bad += [f"{key}.{k}={src[k]}!={v}" for k, v in want.items()
                        if not _agree(src[k], v)]
            elif not _agree(getattr(m, key), want):
                bad.append(f"{key}={getattr(m, key)}!={want}")
        if bad:
            raise AssertionError(f"golden {path.stem} differs on the chip: "
                                 + "; ".join(bad))
        say("golden", path.stem, "match")
    say("phase", "goldens", "ok", f"{len(paths)} goldens matched")


def phase_host(jax, launches) -> None:
    """Lossy and living channel: chip vs host CPU backend, integer-exact."""
    import numpy as np
    from repro.core.constants import Fabric, SimParams
    from repro.core.sweep import SweepPoint, run_sweep_batched
    from repro.phy import PhySweepSpec

    sim = SimParams(cycles=6000, warmup=1000)     # fig9's full budget
    points = {
        "static_phy": PhySweepSpec(link_budget_db=17.0),
        "drift_reselect": PhySweepSpec(link_budget_db=19.0,
                                       drift_amp_db=4.0, reselect=True),
    }
    chip, cpu = jax.devices()[0], jax.devices("cpu")[0]
    for name, spec in points.items():
        pt = SweepPoint(4, 4, Fabric.WIRELESS, load=0.5, p_mem=0.2, sim=sim,
                        phy_spec=spec)
        states = {}
        for dev in (chip, cpu):
            with jax.default_device(dev), \
                    launches.watch(f"host:{name}@{dev.platform}"):
                run_sweep_batched([pt])
            st = launches.states[-1]
            where = {d.platform for d in st.flits_del.devices()}
            if where != {dev.platform}:
                raise AssertionError(f"{name}: ran on {where}, "
                                     f"not {dev.platform}")
            states[dev.platform] = jax.device_get(st)
        a, b = states[chip.platform], states["cpu"]
        moved, floats = [], []
        for field in a._fields:
            x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
            if np.issubdtype(x.dtype, np.floating):
                if not np.array_equal(x, y):
                    rel = np.max(np.abs(x - y) / np.maximum(np.abs(y), 1e-30))
                    floats.append(f"{field}(rel {rel:.3g})")
            elif not np.array_equal(x, y):
                moved.append(f"{field}({int(np.sum(x != y))} of {x.size})")
        say("host", name, f"int_fields_differing={moved or 'none'}",
            f"float_fields_differing={floats or 'none'}")
        if moved:
            raise AssertionError(f"{name}: integer state differs between "
                                 f"{chip.platform} and cpu: {moved}")
    say("phase", "host", "ok", "lossy and living points match the CPU backend")


class _Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s: str) -> int:
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self) -> None:
        self.out.flush()


def _run_suite(name: str, fn, launches) -> None:
    tee = _Tee(sys.stdout)
    with launches.watch(name), contextlib.redirect_stdout(tee):
        fn()
    checks = [ln for ln in tee.buf.getvalue().splitlines()
              if ln.split(",", 1)[0].endswith(".check")]
    failed = [ln for ln in checks if ln.rsplit(",", 1)[-1].strip() != "True"]
    if not checks or failed:
        raise AssertionError(f"{name}: {len(checks)} checks, failed: "
                             f"{failed}")
    say("suite", name, "ok", f"{len(checks)} checks True")


def phase_main(launches) -> None:
    """fig2 (paper budget) and fig9's smoke grid via the benchmark suites."""
    os.environ["FIG9_SMOKE"] = "1"      # read when fig9 is imported
    from benchmarks import fig2_uniform, fig9_lossy_channel

    _run_suite("fig2", fig2_uniform.main, launches)
    with tempfile.TemporaryDirectory() as tmp:
        _run_suite("fig9", lambda: fig9_lossy_channel.main(
            json_path=os.path.join(tmp, "fig9.json")), launches)
    say("phase", "main", "ok", "fig2 and fig9 checks True")


def phase_four_chips(jax, launches) -> None:
    """fig3's grid sharded over 4 chips == the same grid on one device."""
    import numpy as np
    from benchmarks.common import FABRICS
    from benchmarks.fig3_latency import LOADS
    from repro.core import simulator
    from repro.core.constants import SimParams
    from repro.core.sweep import SweepPoint, run_sweep_batched

    sim = SimParams(cycles=3000, warmup=1000)   # cut budget: module docstring
    grid = [SweepPoint(4, 4, f, load=load, p_mem=0.2, sim=sim)
            for f in FABRICS for load in LOADS]
    shards = []
    inner = simulator._run_pmapped

    def run_pmapped(*args, **kw):
        out = inner(*args, **kw)
        shards.append([(s.device, s.data.shape)
                       for s in out.flits_del.addressable_shards])
        return out

    simulator._run_pmapped = run_pmapped
    try:
        with launches.watch("fig3@4chips"):
            run_sweep_batched(grid)
    finally:
        simulator._run_pmapped = inner
    sharded = launches.states[-1]
    with launches.watch("fig3@1device"):
        run_sweep_batched(grid, devices=1)
    single = launches.states[-1]

    if len(shards) != 1:
        raise AssertionError(f"expected one pmap launch, saw {len(shards)}")
    devs = {d for d, _ in shards[0]}
    say("four_chips", f"lanes={len(grid)}", f"shards={len(shards[0])}",
        f"distinct_devices={len(devs)}",
        f"shard_shapes={sorted({s for _, s in shards[0]})}")
    if len(devs) != 4 or any(s[0] != 1 for _, s in shards[0]):
        raise AssertionError(f"lanes not sharded over 4 devices: {shards[0]}")
    a, b = jax.device_get(sharded), jax.device_get(single)
    diff = [f for f in a._fields
            if not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f)))]
    if diff:
        raise AssertionError(f"4-chip state differs from devices=1: {diff}")
    say("phase", "four_chips", "ok",
        f"{len(a._fields)} SimState leaves bitwise equal, 4 devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fig3's grid sharded over four chips")
    args = ap.parse_args()

    import jax
    info = check_device(jax, 4 if args.four_chips else None)

    from benchmarks.common import use_compile_cache
    use_compile_cache()
    from repro.core import simulator
    launches = Launches(jax, simulator)

    if args.four_chips:
        phase_four_chips(jax, launches)
    else:
        phase_goldens(launches)
        phase_host(jax, launches)
        phase_main(launches)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
