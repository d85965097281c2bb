"""Configurations, traffic mixes, limits and per-layer readers are found by
the names BENCHMARK.json gives them: adding one edits no existing file."""
import json
import shutil

from harness import window
from harness.manifest import BENCH, ROOT, Manifest


def _copy(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return bench


def test_added_files_are_found_by_name(tmp_path):
    bench = _copy(tmp_path)
    data = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "xcym4c4m_ideal.json").read_text())
    cfg["name"] = "xcym2c2m_ideal"
    (bench / "configs" / "xcym2c2m_ideal.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "wide.json").write_text(json.dumps(
        {"entry": "sweep", "why": "w", "fabrics": ["wireless"],
         "loads": [0.2, 0.4], "arms": None, "seed_rotation": 2,
         "check_lanes": 2}))
    (bench / "limits" / "new_cell.json").write_text(json.dumps(
        {"int_mismatches": 0, "float_rel_gap": 1e-5, "lanes_checked": 2}))
    (bench / "readers" / "calls_per_window.sweep.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")
    data["configs"].append({"name": "xcym2c2m_ideal", "source": "s",
                            "file": "bench/configs/xcym2c2m_ideal.json",
                            "reduced": [], "why": "w"})
    data["workloads"].append({"name": "new_cell", "config": "xcym2c2m_ideal",
                              "traffic": "wide", "chips": 1, "why": "w"})
    data["per_layer"].append({"name": "calls_per_window.sweep", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "launch",
                              "moves": "lane_cycles_per_s"})
    for m in data["end_to_end"]:
        if m["name"] == "lane_cycles_per_s":
            m["workloads"].append("new_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    man = Manifest(root=tmp_path, bench=bench)
    assert man.config("xcym2c2m_ideal")["name"] == "xcym2c2m_ideal"
    assert man.traffic(man.workload("new_cell")["traffic"])["loads"] == \
        [0.2, 0.4]
    assert man.limits("new_cell")["lanes_checked"] == 2
    names = [m["name"] for m in man.per_layer("new_cell")]
    assert "calls_per_window.sweep" in names       # no workloads key: moves
    assert "launch_s.sweep" not in names           # listed for other cells
    # the new metric reaches every cell reporting lane_cycles_per_s
    assert "calls_per_window.sweep" in [
        m["name"] for m in man.per_layer("ideal_sweep")]
    assert "calls_per_window.sweep" not in [
        m["name"] for m in man.per_layer("ideal_point")]
    ctx = window.Context([window.Call(0, 1, 1, 1)] * 3, [])
    assert man.reader("calls_per_window.sweep")(ctx) == 3.0


def test_every_named_file_exists():
    man = Manifest()
    for w in man.data["workloads"]:
        man.config(w["config"])
        man.traffic(w["traffic"])
        man.limits(w["name"])
        assert man.per_layer(w["name"]), w["name"]
        assert {m["name"] for m in man.end_to_end(w["name"])} >= {"setup_s"}
    for m in man.data["per_layer"]:
        assert callable(man.reader(m["name"]))


def test_readers_return_nothing_without_a_trace():
    man = Manifest()
    calls = [window.Call(0.0, 2.0, 6, 6000)]
    trace = {"busy_s": 1.5, "busy_s_total": 1.5, "window_s": 2.0}
    traced = window.Context(calls, [("launch", 0.1, 1.9)], trace, 1)
    plain = window.Context(calls, [("launch", 0.1, 1.9)], None)
    for m in man.data["per_layer"]:
        read = man.reader(m["name"])
        assert read(traced) is not None, m["name"]
        if m["source"] == "device_trace":
            assert read(plain) is None      # not traced: nothing to read
        else:
            assert read(plain) is not None


def test_one_reader_serves_both_kinds_of_cell():
    man = Manifest()
    assert man.reader("launch_s.sweep") is not None
    ctx = window.Context([window.Call(0.0, 2.0, 1, 1000)],
                         [("launch", 0.1, 1.9)])
    assert man.reader("launch_s.point")(ctx) == \
        man.reader("launch_s.sweep")(ctx)
