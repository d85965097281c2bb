"""Shared benchmark plumbing: CSV emission + paper-target checks."""
from __future__ import annotations

import os
import pathlib
import sys
import time

from repro.core.constants import Fabric, SimParams

FABRICS = [Fabric.SUBSTRATE, Fabric.INTERPOSER, Fabric.WIRELESS]
SIM = SimParams(cycles=10_000, warmup=1_000)   # paper §IV
CACHE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at one fixed place.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other path is set here.  Otherwise the cache is ``.jax_cache/`` at
    the repo root, so each run finds what earlier runs compiled.  Call it
    at start-up, before the first compile; importing the library never
    does.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def device_row() -> str:
    """One CSV row naming the backend the suites run on."""
    import jax
    d = jax.devices()[0]
    return (f"bench.device,platform={d.platform},kind={d.device_kind},"
            f"count={len(jax.devices())}")


def emit(row: str) -> None:
    print(row, flush=True)


def gain(new: float, base: float) -> float:
    """Percentage improvement of `new` over `base` (higher better)."""
    return 100.0 * (new / base - 1.0)


def reduction(new: float, base: float) -> float:
    """Percentage reduction of `new` vs `base` (lower better)."""
    return 100.0 * (1.0 - new / base)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
