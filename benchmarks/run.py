"""Benchmark runner: one module per paper figure + ablations + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [fig2 ... | all]

The first row, ``bench.device``, names the backend (platform, device
kind, count), so a CPU run is visibly a CPU run.  Each suite ends with a
``=== <suite> done in <s>s ===`` line of wall-clock time.  JAX's
compilation cache is kept where ``benchmarks.common.use_compile_cache``
says.  Speed on the chip, with its device trace, is measured by
``bench/run.py`` (``--trace 1``), not here.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (ablations, fig2_uniform, fig3_latency,
                            fig4_cc_traffic, fig5_mc_traffic, fig6_apps,
                            fig7_ml_traces, fig8_memory,
                            fig9_lossy_channel, simspeed)
    from benchmarks.common import device_row, use_compile_cache
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
    picked = list(dict.fromkeys(suites)) if args == ["all"] else args
    if args == ["all"]:
        picked.remove("fig9_lossy_channel")     # alias of fig9
    print(device_row(), flush=True)
    for name in picked:
        t0 = time.perf_counter()
        print(f"=== {name} ===", flush=True)
        suites[name]()
        dt = time.perf_counter() - t0
        print(f"=== {name} done in {dt:.1f}s ===", flush=True)


if __name__ == "__main__":
    main()
