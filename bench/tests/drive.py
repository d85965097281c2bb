"""Drives whole benchmark runs on the CPU at a small budget, with the timed
path optionally broken beneath the harness.

    python3 bench/tests/drive.py <workload> <fault> <seed>

prints the run's result line.  Used by ``test_faults.py``; the harness's
look for a TPU is skipped, nothing else.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(BENCH), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness.manifest import Manifest  # noqa: E402

SMALL = {"cycles": 300, "warmup": 100}     # a budget a test run can hold
# cells kept in bench/ but not in BENCHMARK.json (PERF.md, Open questions):
# the four-chip sweep, and the living channel, whose program lanes depart
# from the reference past about a thousand cycles but agree at SMALL
EXTRA_CONFIGS = [{"name": "xcym4c4m_living",
                  "file": "bench/configs/xcym4c4m_living.json"}]
EXTRA_WORKLOADS = [
    {"name": "ideal_sweep_4chip", "config": "xcym4c4m_ideal",
     "traffic": "fig3_grid", "chips": 4, "why": "pmap over 4 chips"},
    {"name": "living_drift", "config": "xcym4c4m_living",
     "traffic": "drift_arms", "chips": 1, "why": "living channel and ARQ"}]


def small_manifest(tmp: pathlib.Path) -> Manifest:
    """The benchmark's manifest, with the cells kept aside added, and every
    configuration cut to ``SMALL``."""
    data = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for extra, key in ((EXTRA_CONFIGS, "configs"),
                       (EXTRA_WORKLOADS, "workloads")):
        names = {e["name"] for e in data[key]}
        data[key] += [e for e in extra if e["name"] not in names]
    for m in data["end_to_end"]:
        if m["name"] == "lane_cycles_per_s" and \
                "living_drift" not in m["workloads"]:
            m["workloads"].append("living_drift")
    for c in data["configs"]:
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        cfg.update(SMALL)
        path = tmp / c["file"]
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(root=tmp, bench=BENCH)


def _lanes(out, fn):
    """``out`` with leaf ``fn(leaf)`` applied to every batched leaf."""
    import jax
    return jax.tree_util.tree_map(fn, out)


def state_unchanged(inner):
    """The step leaves the state as it found it: no cycle advances."""
    @functools.wraps(inner)
    def run_batch(pss, cycles=None, **kw):
        return inner(pss, cycles=0, **kw)
    return run_batch


def half_batch(inner):
    """Only the first half of the lanes is simulated; the rest are copies."""
    @functools.wraps(inner)
    def run_batch(pss, *a, **kw):
        if len(pss) < 2:
            return inner(pss, *a, **kw)
        half = (len(pss) + 1) // 2
        out = inner(pss[:half], *a, **kw)
        idx = np.arange(len(pss)) % half
        return _lanes(out, lambda x: x[idx])
    return run_batch


def exchange_dropped(inner, chips: int = 4):
    """Lanes placed on chips other than the first never come back: they
    hold the first chip's first lane."""
    @functools.wraps(inner)
    def run_batch(pss, *a, **kw):
        out = inner(pss, *a, **kw)
        per = -(-len(pss) // chips)
        idx = np.where(np.arange(len(pss)) < per, np.arange(len(pss)), 0)
        return _lanes(out, lambda x: x[idx])
    return run_batch


def answer_altered(inner):
    """Each lane's delivered-flit counter is off by one where produced."""
    @functools.wraps(inner)
    def run_batch(pss, *a, **kw):
        out = inner(pss, *a, **kw)
        return out._replace(flits_del=out.flits_del + 1)
    return run_batch


FAULTS = {"none": None, "state_unchanged": state_unchanged,
          "half_batch": half_batch, "exchange_dropped": exchange_dropped,
          "answer_altered": answer_altered}


def traffic_altered(traffic):
    """The generator sends every packet to the next source's destination."""
    inner = traffic.uniform_random

    @functools.wraps(inner)
    def uniform_random(*a, **kw):
        tt = inner(*a, **kw)
        tt.dests = np.roll(tt.dests, 1, axis=0)
        return tt
    return "uniform_random", uniform_random


def latency_altered(sweep):
    """The metrics report each average latency one cycle long."""
    inner = sweep.compute_metrics_batch

    @functools.wraps(inner)
    def compute_metrics_batch(*a, **kw):
        ms = inner(*a, **kw)
        for m in ms:
            m.avg_pkt_latency += 1.0
        return ms
    return "compute_metrics_batch", compute_metrics_batch


# faults above the launch: (module, patch) replaced for the whole run
MODULE_FAULTS = {"traffic_altered": ("repro.core.traffic", traffic_altered),
                 "latency_altered": ("repro.core.sweep", latency_altered)}


def run(workload: str, fault: str, seed: int) -> dict:
    """One small run of ``workload``; returns its result line."""
    import contextlib
    import io
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_small_"))
    undo = None
    if fault in MODULE_FAULTS:
        name, patch = MODULE_FAULTS[fault]
        owner = importlib.import_module(name)
        attr, fn = patch(owner)
        undo = (owner, attr, getattr(owner, attr))
        setattr(owner, attr, fn)
    try:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            mod.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", "1", "--trace", "0"], require_tpu=False,
                     manifest=small_manifest(tmp), fault=FAULTS.get(fault))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        if undo is not None:
            setattr(*undo)
    return json.loads(out.getvalue().strip().splitlines()[-1])


if __name__ == "__main__":
    print(json.dumps(run(sys.argv[1], sys.argv[2], int(sys.argv[3]))))
