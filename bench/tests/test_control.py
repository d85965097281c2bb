"""The control: the reference in the program's place, with its metrics
reduced in bfloat16, fails the check that sound runs pass."""
import jax.numpy as jnp
import pytest

from harness import check, grid
from harness.manifest import Manifest

import drive


@pytest.mark.parametrize("workload", ["ideal_sweep", "living_drift",
                                      "ideal_point"])
def test_control_fails(workload, tmp_path):
    man = drive.small_manifest(tmp_path)
    wl = man.workload(workload)
    config = man.config(wl["config"])
    traffic = man.traffic(wl["traffic"])
    seed = grid.call_seeds(77, traffic["seed_rotation"])[0]
    pts = grid.call_points(config, traffic, seed)
    want = [check.reference_lane(p, config) for p in pts]
    ctrl = [check.reference_lane(p, config, dtype=jnp.bfloat16) for p in pts]
    same = [check.compare_lane(s, m, s2, m2, config["warmup"])
            for (s, m), (s2, m2) in zip(want, want)]
    ctl = [check.compare_lane(s, m, s2, m2, config["warmup"])
           for (s, m), (s2, m2) in zip(ctrl, want)]
    limits = man.limits(workload)
    limits = dict(limits, lanes_checked=len(pts))
    assert check.judge(same, limits)[0]
    ok, checks = check.judge(ctl, limits)
    assert not ok
    assert checks["float_rel_gap"]["value"] > limits["float_rel_gap"]
    assert checks["int_mismatches"]["value"] == 0


def test_manifest_limits_exist():
    man = Manifest()
    for w in man.data["workloads"]:
        lim = man.limits(w["name"])
        assert set(lim) == set(check.CHECKS)
