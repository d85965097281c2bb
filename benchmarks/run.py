"""Benchmark runner: one module per paper figure + ablations + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [--profile] [fig2 ... | all]

The first row, ``bench.device``, names the backend (platform, device
kind, count), so a CPU run is visibly a CPU run.  Each suite ends with a
one-line ``bench.summary`` row — wall-clock and simulated points per
second (from ``sweep.POINTS_RUN``) — so perf regressions are visible
directly in CI logs.  JAX's compilation cache is kept where
``benchmarks.common.use_compile_cache`` says.

``--profile`` wraps the FIRST selected suite in a ``jax.profiler`` trace
and writes it to ``profile_trace/`` (open with TensorBoard or Perfetto)
— the quickest way to see where a suite's wall clock goes (compile vs
launch vs the while_loop chunks).
"""
from __future__ import annotations

import sys
import time

PROFILE_DIR = "profile_trace"


def main() -> None:
    from benchmarks import (ablations, fig2_uniform, fig3_latency,
                            fig4_cc_traffic, fig5_mc_traffic, fig6_apps,
                            fig7_ml_traces, fig8_memory,
                            fig9_lossy_channel, simspeed)
    from benchmarks.common import device_row, use_compile_cache
    from repro.core import sweep
    use_compile_cache()
    suites = {
        "fig2": fig2_uniform.main,
        "fig3": fig3_latency.main,
        "fig4": fig4_cc_traffic.main,
        "fig5": fig5_mc_traffic.main,
        "fig6": fig6_apps.main,
        "fig7": fig7_ml_traces.main,
        "fig8": fig8_memory.main,
        "fig9": fig9_lossy_channel.main,
        "fig9_lossy_channel": fig9_lossy_channel.main,
        "ablations": ablations.main,
        "simspeed": simspeed.main,
    }
    try:
        from benchmarks import roofline
        suites["roofline"] = roofline.main
    except ImportError:
        pass

    args = sys.argv[1:] or ["all"]
    profile = "--profile" in args
    args = [a for a in args if a != "--profile"] or ["all"]
    picked = list(dict.fromkeys(suites)) if args == ["all"] else args
    if args == ["all"]:
        picked.remove("fig9_lossy_channel")     # alias of fig9
    print(device_row(), flush=True)
    for i, name in enumerate(picked):
        t0 = time.perf_counter()
        p0 = sweep.POINTS_RUN
        print(f"=== {name} ===", flush=True)
        if profile and i == 0:
            import jax
            with jax.profiler.trace(PROFILE_DIR):
                suites[name]()
            print(f"bench.profile,{name},{PROFILE_DIR}", flush=True)
        else:
            suites[name]()
        dt = time.perf_counter() - t0
        pts = sweep.POINTS_RUN - p0
        print(f"bench.summary,{name},wall_s={dt:.1f},points={pts},"
              f"points_per_s={pts / dt:.3f}" if pts else
              f"bench.summary,{name},wall_s={dt:.1f},points=0", flush=True)
        print(f"=== {name} done in {dt:.1f}s ===", flush=True)


if __name__ == "__main__":
    main()
