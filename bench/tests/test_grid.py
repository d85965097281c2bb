"""The traffic generator and the check's sample."""
import json

from harness import grid
from harness.manifest import BENCH, Manifest


def test_call_seeds_fixed_and_in_range():
    a = grid.call_seeds(2**31 + 17, 8)
    assert a == grid.call_seeds(2**31 + 17, 8)
    assert len(set(a)) == 8
    assert all(0 <= s < grid.SEED_MOD for s in a)
    assert a != grid.call_seeds(2**31 + 18, 8)


def test_every_cell_has_fixed_sizes():
    man = Manifest()
    for w in man.data["workloads"]:
        config, traffic = man.config(w["config"]), man.traffic(w["traffic"])
        shapes = set()
        for s in grid.call_seeds(5, traffic["seed_rotation"]):
            pts = grid.call_points(config, traffic, s)
            shapes.add(tuple((p["fabric"], p["load"], p["cycles"],
                              json.dumps(p["phy"] and {k: v for k, v in
                                         p["phy"].items() if k != "seed"}))
                             for p in pts))
            assert all(p["seed"] == s for p in pts)
        assert len(shapes) == 1, w["name"]


def test_sample_covers_every_chip_block():
    picked = grid.sample_lanes(3, 21, 4, 8, seed=11)
    assert len(picked) == 8 == len(set(picked))
    blocks = {lane // 6 for _, lane in picked}
    assert blocks == {0, 1, 2, 3}
    assert picked == grid.sample_lanes(3, 21, 4, 8, seed=11)


def test_sample_of_points_spans_calls():
    picked = grid.sample_lanes(5, 1, 1, 3, seed=2)
    assert len({c for c, _ in picked}) == 3


def test_traffic_files_are_data():
    for p in (BENCH / "traffic").iterdir():
        assert p.suffix == ".json"
        json.loads(p.read_text())
