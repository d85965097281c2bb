"""Readings of the check's control, on the chip, at a cell's own size.

    python3 bench/control.py --workload ideal_sweep --seeds 11 12 13

The control is the plain reference put in the program's place: the
reference engine runs the lanes a run with that seed would check, on the
default device (the chip), and its metrics are reduced in bfloat16, the
precision below the float32 the configuration states.  Each seed's lanes
are compared with the reference on the host CPU in float32, exactly as a
run's check compares the program; one JSON line per seed gives the
readings.  A sound comparison reads ``correct: false`` here.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (str(BENCH), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import check, grid  # noqa: E402
from harness.manifest import Manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    man = Manifest()
    wl = man.workload(args.workload)
    config, traffic = man.config(wl["config"]), man.traffic(wl["traffic"])
    limits = man.limits(wl["name"])
    chip, cpu = jax.devices()[0], jax.devices("cpu")[0]
    if chip.platform != "tpu":
        sys.exit(f"control: no TPU found (default backend {chip.platform!r})")

    n_points = len(grid.call_points(config, traffic, 0))
    n_calls = max(1, math.ceil(traffic["check_lanes"] / n_points))
    for seed in args.seeds:
        seeds = grid.call_seeds(seed, traffic["seed_rotation"])
        sample = grid.sample_lanes(n_calls, n_points, int(wl["chips"]),
                                   traffic["check_lanes"], seed)
        results = []
        for c, lane in sample:
            point = grid.call_points(config, traffic,
                                     seeds[c % len(seeds)])[lane]
            with jax.default_device(chip):
                ctl, ctl_m = check.reference_lane(point, config,
                                                  dtype=jnp.bfloat16)
            with jax.default_device(cpu):
                ref, ref_m = check.reference_lane(point, config)
            results.append(check.compare_lane(ctl, ctl_m, ref, ref_m,
                                              config["warmup"]))
        ok, checks = check.judge(results, limits)
        print(json.dumps({"workload": wl["name"], "seed": seed,
                          "correct": ok, "device": chip.device_kind,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
