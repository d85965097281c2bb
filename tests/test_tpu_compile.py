"""The cycle step compiles for a TPU v5e chip at the paper's system size.

Ahead-of-time compiles of the jitted chunked drivers for a described
``v5e:2x2`` topology (no chip attached): the TPU compiler must accept the
step program -- per-cycle ``lax.cond`` in a ``scan`` in a ``while_loop``,
i8/i16 carry leaves, u32 hash arithmetic, a donated state -- and one
launch must fit in a v5e chip's 16 GiB of HBM.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import math
import os
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import simulator, sweep
from repro.core.constants import DEFAULT_SIM, Fabric
from repro.core.sweep import SweepPoint

HBM_BYTES = 16 * 2**30       # one TPU v5e chip
LANES = 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    env = pytest.MonkeyPatch()
    if "TPU_LOG_DIR" not in os.environ:
        env.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        env.undo()
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    env.undo()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _mc_trace():
    from repro.core.topology import build_xcym
    from repro.workloads.mapping import DeviceMap
    from repro.workloads.schedules import expand_collective
    from repro.workloads.trace import Trace
    dm = DeviceMap(build_xcym(4, 4, Fabric.WIRELESS), 16)
    return Trace("oneshot-ar", 16, expand_collective(
        "all-reduce", 512.0, 16, dm, schedule="oneshot", label="ar"))


def _point(kind: str) -> SweepPoint:
    from repro.memory import MemSweepSpec
    from repro.phy import PhySweepSpec
    kw = dict(n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, sim=DEFAULT_SIM)
    if kind == "ideal":
        return SweepPoint(load=1.0, p_mem=0.2, **kw)
    if kind == "mem_on":
        return SweepPoint(mem=MemSweepSpec(load=0.3), **kw)
    if kind == "phy_drift_reselect":
        return SweepPoint(load=0.5, p_mem=0.2, **kw,
                          phy_spec=PhySweepSpec(link_budget_db=19.0,
                                                drift_amp_db=4.0,
                                                reselect=True))
    assert kind == "multicast_trace"
    return SweepPoint(trace=_mc_trace(), **kw)


@pytest.mark.parametrize("kind", ["ideal", "mem_on", "phy_drift_reselect",
                                  "multicast_trace"])
def test_step_compiles_for_v5e(one_chip, kind):
    p = _point(kind)
    topo, rt, tt, _ = sweep._build_point(p)
    ps = simulator.pack(topo, rt, tt, p.phy, p.sim, phy_spec=p.phy_spec)
    assert (ps.mem_on, ps.phy_on, ps.drift_on, ps.reselect) == {
        "ideal": (False, False, False, False),
        "mem_on": (True, False, False, False),
        "phy_drift_reselect": (False, True, True, True),
        "multicast_trace": (False, False, False, False)}[kind]
    if kind == "multicast_trace":
        assert int(ps.ss.mc_member.any(axis=1).sum()) > 0
    st = simulator.init_state(
        *simulator._state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
        living=ps.drift_on or ps.reselect, R=int(ps.ss.wl_serv_r.shape[0]))

    def lanes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            (LANES,) + x.shape, x.dtype, sharding=one_chip), tree)

    compiled = simulator._run_mapped.lower(
        lanes(ps.ss), lanes(st), ps.B, ps.mem_on, ps.phy_on,
        simulator.CHUNK_CYCLES, ps.drift_on, ps.reselect).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
            + mem.generated_code_size_in_bytes)
    assert 0 < need < HBM_BYTES, mem
    # the donated state comes back in place
    assert mem.alias_size_in_bytes > 0, mem


@functools.lru_cache(maxsize=None)
def _point_hlo(one_chip, driver: str, kind: str = "ideal") -> str:
    """Optimized HLO text of a 4C4M point's driver for a v5e."""
    p = _point(kind)
    topo, rt, tt, _ = sweep._build_point(p)
    ps = simulator.pack(topo, rt, tt, p.phy, p.sim, phy_spec=p.phy_spec)
    st = simulator.init_state(
        *simulator._state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
        living=ps.drift_on or ps.reselect, R=int(ps.ss.wl_serv_r.shape[0]))
    lead = (LANES,) if driver == "_run_mapped" else ()
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        lead + x.shape, x.dtype, sharding=one_chip), (ps.ss, st))
    return getattr(simulator, driver).lower(
        *args, ps.B, ps.mem_on, ps.phy_on, simulator.CHUNK_CYCLES,
        ps.drift_on, ps.reselect).compile().as_text()


def _gathers(hlo: str) -> list:
    """``(indices, step scope)`` of every gather in an HLO text: the
    indices it fetches (its output's elements over its slice's) and the
    innermost ``step.*`` scope of its ``op_name`` (``""`` outside one)."""
    found = re.findall(r"= \w+\[([\d,]*)\][^=]* gather\(.*?slice_sizes="
                       r"\{([\d,]*)\}.*?op_name=\"([^\"]*)\"", hlo)
    assert found, "no gather found at all: the pattern no longer matches"

    def size(dims):
        return math.prod(int(d) for d in dims.split(",") if d)

    return [(size(out) // size(sl),
             ([p for p in name.split("/") if p.startswith("step.")]
              or [""])[-1]) for out, sl, name in found]


@pytest.mark.parametrize("driver", ["_run_one", "_run_mapped"])
def test_step_scopes_survive_the_tpu_compile(one_chip, driver):
    """The TPU compiler keeps every ideal step stage and driver scope in
    the op_name metadata a device trace is split by."""
    from repro.core import spans
    hlo = _point_hlo(one_chip, driver)
    named = {part for path in re.findall(r'op_name="([^"]*)"', hlo)
             for part in path.split("/") if part in spans.SCOPES}
    assert named == set(spans.SCOPES) - {"step.memory", "step.window",
                                         "step.phy"}


def test_arbitration_has_no_large_gathers_on_the_tpu(one_chip):
    """On the TPU the step finds arbitration winners and reads small
    tables by dense compare-and-reduce (``core/arbitrate``): no gather
    under ``step.vc_claim`` or ``step.forward`` of the ideal step fetches
    4,096 indices or more.  The TPU pays for a gather per index it
    fetches, whatever the width of the rows; with the gather forms this
    compile held 19 gathers of 4,096 elements or more."""
    large = [g for g in _gathers(_point_hlo(one_chip, "_run_one"))
             if g[1] in ("step.vc_claim", "step.forward") and g[0] >= 4096]
    assert not large, large


@pytest.mark.parametrize("kind,most", [("ideal", 3),
                                       ("phy_drift_reselect", 4)])
def test_fields_read_at_one_index_share_one_gather_on_the_tpu(
        one_chip, kind, most):
    """The step reads the fields it needs at one index with one gather of
    their stacked rows (``arbitrate.take_rows``): at most ``most`` gathers
    of 2,048 indices or more under ``step.forward`` and ``step.phy``, and
    at most 6 gathers under ``step.vc_claim``.  With a gather per field
    this compile held 16 (ideal) and 21 (lossy) such large gathers, and
    16 under ``step.vc_claim``."""
    gathers = _gathers(_point_hlo(one_chip, "_run_one", kind))
    large = [g for g in gathers
             if g[1] in ("step.forward", "step.phy") and g[0] >= 2048]
    claim = [g for g in gathers if g[1] == "step.vc_claim"]
    assert len(large) <= most, large
    assert len(claim) <= 6, claim


def test_injection_has_no_expanded_gathers_on_the_tpu(one_chip):
    """Sources and their injection buffers trade values as one block at
    ``inj_buf[0]`` (``make_step``), not by gathers through ``inj_src``:
    the TPU expands each ``[N] -> [B]`` gather into 64 scalar extracts
    selected into a fusion of 64 or more operands.  With the gathers this
    compile held 13 such fusions and 4,898 ops under ``step.inject``."""
    hlo = _point_hlo(one_chip, "_run_one")
    inject = [line for line in hlo.splitlines()
              if "step.inject" in "".join(
                  re.findall(r'op_name="([^"]*)"', line)).split("/")]
    wide = [(m.group(1), m.group(2).count("%")) for m in (
        re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*? fusion\((.*?)\), kind=",
                 line) for line in inject) if m]
    assert wide, "no fusion found at all: the pattern no longer matches"
    assert not [w for w in wide if w[1] >= 64], wide
    assert len(inject) < 1500, len(inject)
