"""The plain reference stands apart from the program it checks."""
import json

import numpy as np
import pytest

from harness import check
from harness.manifest import BENCH


def test_reference_imports_nothing_of_the_program():
    for path in (BENCH / "refsim").glob("*.py"):
        text = path.read_text()
        assert "import repro" not in text and "from repro" not in text, path


@pytest.mark.parametrize("load,seed", [(0.1, 3), (1.0, 2**31 - 5),
                                       (0.3, 77)])
def test_traffic_equals_the_program_generator(load, seed):
    from refsim import traffic as ref_traffic
    from repro.core import traffic
    from repro.core.constants import Fabric, PhyParams
    from repro.core.topology import build_xcym
    topo = build_xcym(4, 4, Fabric.WIRELESS, PhyParams())
    want = traffic.uniform_random(topo, load, 0.2, 700, 64, seed=seed)
    got = ref_traffic.uniform_random(
        np.flatnonzero(topo.is_core), np.flatnonzero(topo.is_mem), load, 0.2,
        700, 64, seed)
    np.testing.assert_array_equal(got.src_switch, want.src_switch)
    np.testing.assert_array_equal(got.births, want.births)
    np.testing.assert_array_equal(got.dests, want.dests)
    assert got.offered_load == want.offered_load


def test_configurations_are_the_reference_settings():
    for path in (BENCH / "configs").glob("*.json"):
        check.check_config(json.loads(path.read_text()))
    bad = json.loads((BENCH / "configs" / "xcym4c4m_living.json").read_text())
    bad["channel"] = dict(bad["channel"], reselect_every_cycles=64)
    with pytest.raises(ValueError, match="reselect_every_cycles"):
        check.check_config(bad)


def test_padding_is_cut_before_the_comparison():
    state = {"rcvd": np.arange(12).reshape(3, 4),
             "drain_cycle": np.int32(1000)}
    ref = {"rcvd": np.arange(12).reshape(3, 4)[:2, :3],
           "drain_cycle": np.int32(1000)}
    assert check.compare_lane(state, {}, ref, {}, 200) == ([], 0.0)
    short = {"rcvd": ref["rcvd"][:1], "drain_cycle": np.int32(1000)}
    assert check.compare_lane(short, {}, ref, {}, 200)[0] == ["state.rcvd"]
    early = dict(state, drain_cycle=np.int32(100))
    assert check.compare_lane(early, {}, ref, {}, 200)[0] == \
        ["state.drain_cycle"]
