"""Benchmark of the 4C4M interconnect simulator on a TPU.

    python3 bench/run.py --workload ideal_sweep --seed 7 --seconds 10 --trace 0

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: a closed
loop with one client (an architect's script) that submits the cell's next
call, a sweep grid or a single design point, when the last one returns,
until ``--seconds`` have passed.  Set-up first warms every program the
window runs, through the same entry at a short cycle budget.  After the
window, a sample of the lanes it produced, drawn from ``--seed``, is checked
against the plain reference (``bench/refsim``) on the host CPU.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; the numbers the check compared come last, under ``checks``,
and again as the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import check, grid, program, window  # noqa: E402
from harness import trace as tracing  # noqa: E402
from harness.manifest import Manifest  # noqa: E402

# the persistent compilation cache, at a fixed place inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
# a traced run profiles the calls that start in the window's first this
# many seconds: the device records every operation of every simulated
# cycle, and a longer trace would outgrow the time a run may take
TRACE_SECONDS = 10.0


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*fields) -> None:
    print(" ".join(str(f) for f in fields), file=sys.stderr, flush=True)


def open_devices(jax, chips: int, require_tpu: bool):
    """The cell's devices; exits without a TPU or with too few chips."""
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        sys.exit(f"bench: no TPU found (default backend is {d.platform!r}); "
                 f"there is no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, found {len(devs)}")
    info = {"platform": d.platform, "kind": d.device_kind, "count": chips}
    return info, devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def program_lanes(records, sample) -> list:
    """Host copies of the sampled lanes: (point, state, metrics)."""
    import jax
    out = []
    for c, lane in sample:
        pts, sps, ms, launches = records[c]
        for lane_pts, st in launches:
            if sps[lane] in lane_pts:
                g = lane_pts.index(sps[lane])
                host = jax.device_get(st)
                state = {f: host[i][g] for i, f in enumerate(host._fields)}
                out.append((pts[lane], state, ms[lane]))
                break
        else:
            raise RuntimeError(f"call {c} lane {lane}: no launch ran it")
    return out


def check_lanes(jax, lanes, config, limits):
    """Compare program lanes with the reference on the host CPU;
    (correct, checks)."""
    results = []
    with jax.default_device(jax.devices("cpu")[0]):
        for point, state, m in lanes:
            ref_state, ref_m = check.reference_lane(point, config)
            results.append(check.compare_lane(state, m, ref_state, ref_m,
                                              config["warmup"]))
    bad = sorted({f for ints, _ in results for f in ints})
    if bad:
        say("check", "differing", ",".join(bad[:20]))
    return check.judge(results, limits)


def measure(jax, rec, entry: str, chips: int, config: dict, traffic: dict,
            args, log_dir: str | None):
    """Set-up, then the window: (setup_s, calls, per-call records, number
    of leading calls traced).

    ``rec.lowerings`` counts, afterwards, the programs lowered inside the
    window; when ``log_dir`` is set the profiler traces the calls that
    start in the window's first ``TRACE_SECONDS``.
    """
    from repro.core import chunked
    seeds = grid.call_seeds(args.seed, traffic["seed_rotation"])

    def points(i: int) -> list[dict]:
        return grid.call_points(config, traffic, seeds[i % len(seeds)])

    # every program the window runs, warmed through the same entry two
    # chunks past the warm-up: the metrics read a lane's latency sum only
    # where a packet born after the warm-up has arrived, and one chunk
    # left some lossy-channel lanes with none, so that read compiled in
    # the window
    warm = [program.sweep_point(p, config) for p in points(0)]
    with rec.span("call"):
        program.run_call(entry, warm, chips,
                         cycles=config["warmup"] + 2 * chunked.CHUNK_CYCLES)
    rec.reset()
    rec.lowerings = 0
    setup_s = time.perf_counter() - T_START
    say("setup_s", setup_s)

    records = []
    traced = {"on": False, "calls": 0}

    def run_one(i: int) -> tuple[int, int]:
        if traced["on"] and time.perf_counter() - start >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            traced["on"] = False
        traced["calls"] += traced["on"]
        pts = points(i)
        sps = [program.sweep_point(p, config) for p in pts]
        with rec.span("call"):
            ms = program.run_call(entry, sps, chips)
        records.append((pts, sps, ms, rec.take_launches()))
        return len(ms), sum(m.cycles_run for m in ms)

    if log_dir:
        jax.profiler.start_trace(log_dir)
        traced["on"] = True
    start = time.perf_counter()
    try:
        calls = window.closed_loop(run_one, args.seconds)
    finally:
        if traced["on"]:
            jax.profiler.stop_trace()
    return setup_s, calls, records, traced["calls"]


def main(argv=None, require_tpu: bool = True, manifest: Manifest | None = None,
         fault=None) -> int:
    """One run of one cell.  ``fault`` (tests only) wraps ``run_batch``."""
    args = parse(argv)
    man = manifest or Manifest()
    wl = man.workload(args.workload)
    config = man.config(wl["config"])
    traffic = man.traffic(wl["traffic"])
    limits = man.limits(wl["name"])
    entry, chips = traffic["entry"], int(wl["chips"])

    import jax
    info, devs = open_devices(jax, chips, require_tpu)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from repro.core import simulator
    program.check_constants(config)
    check.check_config(config)
    run_batch = simulator.run_batch
    if fault is not None:      # beneath the recorder, as a fault would be
        simulator.run_batch = fault(run_batch)
    rec = program.Recorder(jax)
    rec.install()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        setup_s, calls, records, traced = measure(
            jax, rec, entry, chips, config, traffic, args, log_dir)
        compiles = rec.lowerings
        say("compiles_in_window", compiles)
        result = {"correct": False, "attempted": sum(c.points for c in calls),
                  "failed": 0, "metrics": {}, "device": dict(
                      info, memory_peak_bytes=memory_peak(devs))}
        ctx = window.Context(calls, list(rec.spans), traced_calls=traced)
        if log_dir:
            path = tracing.find_xplane(log_dir)
            say("trace_bytes", os.path.getsize(path), "traced_calls", traced)
            red = tracing.reduce(tracing.load(path))
            ctx.trace = red
            result["device"].update(busy_s=red["busy_s"],
                                    window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            for m in man.per_layer(wl["name"]):
                value = man.reader(m["name"])(ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
        else:
            e2e = {"setup_s": setup_s,
                   "lane_cycles_per_s": window.lane_cycles_per_s(calls),
                   "point_s": window.point_s(calls)}
            for m in man.end_to_end(wl["name"]):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
        say("window", f"calls={len(calls)}",
            f"window_s={window.window_s(calls)}",
            f"points={result['attempted']}", f"lane_cycles={ctx.lane_cycles}")
    finally:
        rec.uninstall()
        simulator.run_batch = run_batch
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    # the check: the window's state is freed first, then the reference
    # runs lane by lane on the host CPU
    sample = grid.sample_lanes(len(records), len(records[0][0]), chips,
                               traffic["check_lanes"], args.seed)
    lanes = program_lanes(records, sample)
    del records, calls
    gc.collect()
    t_check = time.perf_counter()
    correct, checks = check_lanes(jax, lanes, config, limits)
    say("check_s", time.perf_counter() - t_check)
    result["correct"] = bool(correct)
    result["checks"] = checks
    for name, c in checks.items():
        say("check", name, f"value={c['value']}", f"limit{c['rule']}"
            f"{c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
