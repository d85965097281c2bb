"""Host spans, counters and device stage scopes of the sweep path."""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.core import simulator, spans, sweep
from repro.core.constants import DEFAULT_SIM, Fabric, SimParams
from repro.core.sweep import SweepPoint

SIM = SimParams(cycles=256, warmup=64)
IDEAL_SCOPES = {"step.arrive", "step.vc_claim", "step.forward", "step.phase",
                "step.inject", "step.rx_sleep", "driver.cycle",
                "driver.drain_check", "driver.finalize"}


def _scopes_in(hlo_text: str) -> set[str]:
    """The program scopes named in the text's op_name metadata."""
    return {part for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in path.split("/") if part in spans.SCOPES}


def _check_call(recs, n_points):
    """One call's records: nesting, order and counters; its lanes' Σ
    ``cycles_run`` and Σ ``drain_cycle``."""
    assert [r.t0 for r in recs] == sorted(r.t0 for r in recs)
    top, *inner = recs
    assert top.name == "run_sweep_batched"
    assert top.attrs == {}
    for r in inner:
        assert top.t0 <= r.t0 <= r.t1 <= top.t1, r.name
    # a batch of one adds its batch axis after the wait
    launch = ["run_batch.init", "run_batch.dispatch", "run_batch.wait"] \
        + ["run_batch.dispatch"] * (n_points == 1)
    assert [r.name for r in inner] == [
        "sweep.build", "sweep.harmonize", "sweep.pack", "run_batch",
        *launch, "compute_metrics_batch", "metrics.energy", "metrics.lanes"]
    by = {r.name: r for r in inner}
    for outer, names in (("run_batch", launch),
                         ("compute_metrics_batch", ["metrics.energy",
                                                    "metrics.lanes"])):
        o = by[outer]
        for r in inner:
            if r.name in names:
                assert o.t0 <= r.t0 <= r.t1 <= o.t1, (outer, r.name)
    assert by["run_batch"].attrs == {}
    # one launch of all the call's lanes, then their metrics
    assert by["run_batch"].t1 <= by["compute_metrics_batch"].t0
    assert set(by["compute_metrics_batch"].attrs) == {
        "budget_lane_cycles", "executed_lane_cycles"}
    return (by["compute_metrics_batch"].attrs["budget_lane_cycles"],
            by["compute_metrics_batch"].attrs["executed_lane_cycles"])


def test_sweep_spans_nest_and_count():
    pts = [SweepPoint(4, 4, fab, load=load, sim=SIM)
           for fab in (Fabric.WIRELESS, Fabric.INTERPOSER)
           for load in (0.1, 0.6)]
    spans.reset()
    ms = sweep.run_sweep_batched(pts)
    budget, executed = _check_call(spans.snapshot(), len(pts))
    assert budget == sum(m.cycles_run for m in ms) == len(pts) * SIM.cycles
    assert executed == sum(m.drain_cycle for m in ms) <= budget

    # a batch of one takes the single-lane path under the same spans
    spans.reset()
    m = sweep.run_point(4, 4, Fabric.SUBSTRATE, 0.2, sim=SIM)
    assert _check_call(spans.snapshot(), 1) == (m.cycles_run,
                                                m.drain_cycle)
    spans.reset()


def test_span_counters_and_exceptions():
    spans.reset()
    with pytest.raises(RuntimeError):
        with spans.span("outer", a=1) as counters:
            counters["b"] = 2
            raise RuntimeError("boom")
    (rec,) = spans.snapshot()
    assert rec.name == "outer" and rec.attrs == {"a": 1, "b": 2}
    assert rec.t1 >= rec.t0
    spans.reset()
    assert spans.snapshot() == []


def test_ring_stays_bounded():
    spans.reset()
    for i in range(spans.MAXLEN + 10):
        with spans.span("tick", i=i):
            pass
    recs = spans.snapshot()
    assert len(recs) == spans.MAXLEN
    assert recs[0].attrs == {"i": 10}          # the oldest fell out first
    assert recs[-1].attrs == {"i": spans.MAXLEN + 9}
    spans.reset()


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        spans.scope("step.nowhere")


def _ideal_args():
    p = SweepPoint(n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, load=1.0,
                   p_mem=0.2, sim=DEFAULT_SIM)
    topo, rt, tt, _ = sweep._build_point(p)
    ps = simulator.pack(topo, rt, tt, p.phy, p.sim)
    st = simulator.init_state(*simulator._state_dims(ps),
                              R=int(ps.ss.wl_serv_r.shape[0]))
    return ps, st


@pytest.mark.parametrize("driver", ["_run_one", "_run_mapped"])
def test_compiled_step_carries_every_ideal_scope(driver):
    ps, st = _ideal_args()
    args = (ps.ss, st)
    if driver == "_run_mapped":
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            (2,) + x.shape, x.dtype), args)
    fn = getattr(simulator, driver)
    hlo = fn.lower(*args, ps.B, False, False, simulator.CHUNK_CYCLES,
                   False, False).compile().as_text()
    assert _scopes_in(hlo) == IDEAL_SCOPES


@pytest.mark.parametrize("kind,stage", [("mem_on", "step.memory"),
                                        ("living", "step.window"),
                                        ("living", "step.phy")])
def test_optional_stages_are_scoped(kind, stage):
    from repro.memory import MemSweepSpec
    from repro.phy import PhySweepSpec
    kw = dict(n_chips=4, n_mem=4, fabric=Fabric.WIRELESS, sim=SIM)
    p = SweepPoint(mem=MemSweepSpec(load=0.3), **kw) if kind == "mem_on" \
        else SweepPoint(load=0.5, **kw, phy_spec=PhySweepSpec(
            link_budget_db=19.0, drift_amp_db=4.0, reselect=True))
    topo, rt, tt, _ = sweep._build_point(p)
    ps = simulator.pack(topo, rt, tt, p.phy, p.sim, phy_spec=p.phy_spec)
    st = simulator.init_state(
        *simulator._state_dims(ps), mem_on=ps.mem_on, phy_on=ps.phy_on,
        living=ps.drift_on or ps.reselect, R=int(ps.ss.wl_serv_r.shape[0]))
    text = simulator._run_one.lower(
        ps.ss, st, ps.B, ps.mem_on, ps.phy_on, simulator.CHUNK_CYCLES,
        ps.drift_on, ps.reselect).as_text(debug_info=True)
    names = {part for part in re.findall(r'"([^"]*)"', text)
             for part in part.split("/")}
    assert stage in names
    assert IDEAL_SCOPES <= names


def test_stage_scopes_change_metadata_only(monkeypatch):
    """Scopes name operations and nothing else: the ideal step's lowering,
    debug info aside, is the same with every scope turned off."""
    ps, st = _ideal_args()

    def lowered():
        # a new function each time: a jit of the same one reuses its trace
        fn = jax.jit(lambda *a: simulator._run_one.__wrapped__(*a),
                     static_argnums=(2, 3, 4, 5, 6, 7))
        return fn.lower(ps.ss, st, ps.B, False, False,
                        simulator.CHUNK_CYCLES, False, False).as_text()

    scoped = lowered()
    monkeypatch.setattr(spans, "scope",
                        lambda name: contextlib.nullcontext())
    assert lowered() == scoped


def test_air_counters_over_the_lossy_channel():
    """``compute_metrics_batch`` counts the flits put on the air and those
    of failing attempts over the lossy channel's lanes (ideal lanes keep
    neither counter: ``_check_call``)."""
    from repro.core.metrics import compute_metrics_batch
    from repro.phy import PhySweepSpec
    pss = []
    for policy in ("adaptive", "fixed:0"):
        p = SweepPoint(4, 4, Fabric.WIRELESS, load=0.5, sim=SIM,
                       phy_spec=PhySweepSpec(link_budget_db=16.0,
                                             policy=policy, max_retx=3,
                                             drift_amp_db=4.0))
        topo, rt, tt, _ = sweep._build_point(p)
        pss.append(simulator.pack(topo, rt, tt, p.phy, p.sim,
                                  phy_spec=p.phy_spec))
    st = simulator.run_batch(pss)
    spans.reset()
    compute_metrics_batch(pss, st, ["adaptive", "fixed:0"], [0.5, 0.5])
    (rec,) = [r for r in spans.snapshot()
              if r.name == "compute_metrics_batch"]
    spans.reset()
    air = int(np.asarray(st.wl_pair_flits).sum())
    fail = int(np.asarray(st.wl_fail_flits).sum())
    assert rec.attrs["air_flits"] == air
    assert rec.attrs["air_fail_flits"] == fail
    assert 0 < fail < air
