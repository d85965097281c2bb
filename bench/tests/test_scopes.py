"""The split of device time by the program's scopes, and the readers of
the program's host spans."""
import tempfile
import time

import numpy as np
import pytest

from harness import scopes, window

MS = 1e6          # ns per ms
STAGES = ("step.arrive", "step.vc_claim", "step.forward", "step.phase",
          "step.memory", "step.inject", "step.rx_sleep", "step.window")
DRIVER = ("driver.cycle", "driver.drain_check", "driver.finalize")
LOOP = "jit(run)/while/body/while/body"
CYCLE = LOOP + "/driver.cycle/cond"
STEP = CYCLE + "/branch_1_fun"


def _synthetic() -> scopes.ScopedTrace:
    ops = [  # (name, start ms, end ms, op_name path)
        ("while.1", 10, 80, "jit(run)/while"),          # lane loop
        ("while.2", 11, 79, "jit(run)/while/body/while"),  # chunks
        ("cond.1", 12, 40, CYCLE),                      # one guarded cycle
        ("fusion.1", 12, 20, STEP + "/step.forward/gather"),
        ("fusion.2", 22, 30, STEP + "/step.vc_claim/min"),
        ("ge.1", 30, 32, STEP + "/ge"),                 # no stage
        ("and.1", 41, 43, LOOP + "/driver.drain_check/and"),
        ("lt.1", 43, 44, LOOP + "/driver.cycle/lt"),    # the guard's test
        ("cond.2", 45, 60, CYCLE),
        ("fusion.1", 45, 55, STEP + "/step.forward/gather"),
        ("cond.3", 55, 58, STEP + "/step.window/cond"),  # nested container
        ("fusion.3", 55, 57, STEP + "/step.window/cond/branch_1_fun/add"),
        ("fusion.4", 82, 88, "jit(_energy_terms)/mul"),  # outside the loops
    ]
    ids: dict[str, int] = {}
    paths = []
    for name, *_, path in ops:
        if name not in ids:
            ids[name] = len(paths)
            paths.append(path)
    fused = [frozenset()] * len(paths)
    # fusion.1 also fused an instruction of step.vc_claim; fusion.2 only
    # instructions of its own stage
    fused[ids["fusion.1"]] = frozenset({STEP + "/step.forward/add",
                                        STEP + "/step.vc_claim/min"})
    fused[ids["fusion.2"]] = frozenset({STEP + "/step.vc_claim/min",
                                        STEP + "/step.vc_claim/ge"})
    return scopes.ScopedTrace(
        fused=fused,
        ops={"/device:TPU:0": (
            np.array([ids[n] for n, *_ in ops]),
            np.array([a * MS for _, a, _, _ in ops], float),
            np.array([b * MS for _, _, b, _ in ops], float))},
        paths=paths,
        modules={"/device:TPU:0": [("run", 10 * MS, 90 * MS)]},
        spans=[("run_batch", 5 * MS, 99 * MS),
               ("run_batch.init", 5 * MS, 10 * MS),
               ("run_batch.wait", 20 * MS, 99 * MS)],
        calls=[(0, 105 * MS)])


def test_leaves_stages_gap_and_driver():
    red = scopes.reduce(_synthetic(), STAGES, DRIVER)
    s = {k: v * 1e3 for k, v in red["parts_s"].items()}      # ms
    # leaves only: the while and cond containers are not summed, and a
    # name seen twice keeps the stage of its op_name
    assert s["step.forward"] == pytest.approx(8 + 10)
    assert s["step.vc_claim"] == pytest.approx(8)
    assert s["step.window"] == pytest.approx(2)
    assert s["step.arrive"] == s["step.inject"] == 0
    # inside the guard with no leaf: 20-22, 32-40 and 57-60 ms
    assert s["step_gap"] == pytest.approx(2 + 8 + 3)
    # a leaf in the guard with no stage
    assert s["unresolved"] == pytest.approx(2)
    assert red["unresolved_share"] == pytest.approx(2 / 30)
    # the drain check, the guard's test, and the rest of the loop time
    # outside the guards: 10-12, 40-41, 44-45, 60-80
    assert s["driver"] == pytest.approx(2 + 1 + (2 + 1 + 1 + 20))
    # the energy program and module time outside the loops: 80-90 ms
    assert s["other"] == pytest.approx(10)
    # together they are the busy time
    assert sum(s.values()) == pytest.approx(80)
    assert red["busy_s_total"] == pytest.approx(0.080)
    assert red["scoped"]


def test_fusions_of_more_than_one_stage():
    red = scopes.reduce(_synthetic(), STAGES, DRIVER)
    multi = {k: v * 1e3 for k, v in red["multi_stage_s"].items()}   # ms
    # fusion.1's two events, booked to step.forward, fused a vc_claim op
    assert multi["step.forward"] == pytest.approx(8 + 10)
    assert multi["step.vc_claim"] == 0
    staged = sum(red["parts_s"][s] for s in STAGES)
    assert red["multi_stage_share"] == pytest.approx(0.018 / staged)
    # without the fused instructions nothing spans two stages
    tr = _synthetic()
    tr.fused = []
    red = scopes.reduce(tr, STAGES, DRIVER)
    assert red["multi_stage_share"] == 0
    assert scopes.multi_stage(["a/step.inject/x"], [frozenset(
        {"a/step.inject/y", "a/driver.cycle/z"})], STAGES) == [False]


def test_ops_resolve_in_the_program_that_ran_them():
    fwd, claim = STEP + "/step.forward/gather", STEP + "/step.vc_claim/min"
    tables = {"jit_run(1)": ({"fusion.9": fwd}, {"fusion.9": frozenset()}),
              "jit_energy(2)": ({"fusion.9": claim}, {})}
    fallback = scopes.merged(tables)
    assert fallback[0] == {"fusion.9": ""}          # ambiguous by name
    event = "%fusion.9 = s32[8]{0} fusion(s32[8]{0} %p)"
    assert scopes.resolve(event, "jit_run(1)", tables, fallback) == \
        (fwd, frozenset(), True)
    assert scopes.resolve(event, "jit_energy(2)", tables, fallback)[0] \
        == claim
    # a program the trace keeps no HLO of, or none: by name alone
    assert scopes.resolve(event, None, tables, fallback) == \
        ("", frozenset(), False)
    assert scopes.resolve(event, "jit_other(1)", tables, fallback)[2] is \
        False
    # the one program of its name, under another id (a cached program)
    assert scopes.resolve(event, "jit_run(7)", tables, fallback) == \
        (fwd, frozenset(), True)
    # the program execution holding each op start
    mods = [("jit_run(1)", 10.0, 20.0), ("jit_energy(2)", 30.0, 40.0)]
    starts = np.array([5.0, 10.0, 15.0, 25.0, 30.0, 45.0])
    assert scopes._program_of(starts, mods).tolist() == \
        [-1, 0, 0, -1, 1, -1]


def test_a_trace_without_op_names_is_not_split():
    tr = _synthetic()
    tr.paths = [""] * len(tr.paths)
    red = scopes.reduce(tr, STAGES, DRIVER)
    assert not red["scoped"]
    assert sum(red["parts_s"].values()) == pytest.approx(0.080)


def test_idle_gaps_named_by_program_span():
    red = scopes.reduce(_synthetic(), STAGES, DRIVER)
    # longest first, each named by the innermost span open at its middle
    assert red["idle_gaps"] == [
        ["run_batch.wait", pytest.approx(0.015)],   # 90-105 ms, middle 97.5
        ["run_batch.init", pytest.approx(0.010)]]   # 0-10 ms, middle 5


def test_devices_add_up():
    tr = _synthetic()
    tr.ops["/device:TPU:1"] = tr.ops["/device:TPU:0"]
    tr.modules["/device:TPU:1"] = list(tr.modules["/device:TPU:0"])
    one = scopes.reduce(_synthetic(), STAGES, DRIVER)["parts_s"]
    two = scopes.reduce(tr, STAGES, DRIVER)["parts_s"]
    assert two == {k: pytest.approx(2 * v) for k, v in one.items()}


def test_op_path_by_instruction_name():
    fwd = STEP + "/step.forward/gather"
    by_name = ({"fusion.53": fwd, "fusion.2": ""}, {})   # "": two programs

    def path(event):
        return scopes.resolve(event, None, {}, by_name)[0]

    tpu_name = "%fusion.53 = s32[40960]{0} fusion(s32[4,16]{1,0} %p)"
    assert path(tpu_name) == fwd
    assert path("fusion.53") == fwd
    assert path("%fusion.2 = s32[8]{0} fusion()") == ""
    assert path("%copy.7 = s32[8]{0} copy(%p)") == ""
    assert scopes.innermost(STEP + "/step.window/cond",
                            STAGES + DRIVER) == "step.window"


def test_span_readers_count_the_window(monkeypatch):
    from repro.core import spans
    spans.reset()
    with spans.span("run_batch.init"):
        pass
    with spans.span("compute_metrics_batch") as c:
        c.update(executed_lane_cycles=500, budget_lane_cycles=600)
    recs = spans.snapshot()
    lo, hi = recs[0].t0, recs[-1].t1
    calls = [window.Call(lo, hi, 1, 600)]
    ctx = window.Context(calls, [])
    assert scopes.per_call(ctx, "run_batch.init") == \
        pytest.approx(recs[0].t1 - recs[0].t0)
    # records before the window are not counted
    late = window.Context([window.Call(recs[0].t1, hi, 1, 600)], [])
    assert scopes.per_call(late, "run_batch.init") == 0
    # the counters: executed over budget lane-cycles
    assert scopes.executed_share(ctx) == pytest.approx(500 / 600 * 100)
    early = window.Context([window.Call(lo, recs[0].t1, 1, 600)], [])
    assert scopes.executed_share(early) is None
    # a ring that dropped part of the window reads nothing
    monkeypatch.setattr(spans, "MAXLEN", len(recs))
    assert scopes.per_call(ctx, "run_batch.init") is None
    assert scopes.executed_share(ctx) is None
    monkeypatch.undo()
    # the device split needs a trace, and the program's scopes
    assert scopes.us_per_lane_cycle(ctx, "step.forward") is None
    monkeypatch.setattr(scopes, "program_spans", lambda: None)
    assert scopes.per_call(ctx, "run_batch.init") is None
    assert scopes.executed_share(ctx) is None
    assert scopes.reduction(ctx) is None
    spans.reset()


def test_op_names_from_the_trace_hlo(tmp_path):
    """Where op events carry no op_name, their HLO instruction is looked
    up in the HLO the profiler keeps in the trace (here a CPU trace)."""
    import jax
    import jax.numpy as jnp

    from harness import trace as tracing
    from jax.profiler import ProfileData
    from repro.core import spans

    @jax.jit
    def guarded_forward(x):
        with spans.scope("driver.cycle"):
            return jax.lax.cond(x.sum() > 0, lambda y: _forward(y),
                                lambda y: y, x)

    def _forward(y):
        with spans.scope("step.forward"):
            return jnp.sin(y) * 3 + jnp.cumsum(y)

    x = jnp.arange(256.0)
    guarded_forward(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    guarded_forward(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tracing.find_xplane(str(tmp_path))
    tables = scopes.hlo_tables(path)
    by_name = scopes.merged(tables)
    assert any(scopes.innermost(v, spans.SCOPES) == "step.forward"
               for v in by_name[0].values())
    # the CPU names its op events by bare instruction name, and says
    # which program ran them; every program the process compiled is in
    # the trace's HLO, so names are looked up in the program's own
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for ln in plane.lines for e in ln.events]
    ran = [(name, f"{st['hlo_module']}({st['program_id']})")
           for name, st in events
           if st.get("hlo_module") == "jit_guarded_forward"]
    resolved = [scopes.resolve(name, program, tables, by_name)
                for name, program in ran]
    assert resolved
    assert all(found for _, _, found in resolved)
    stages = {scopes.innermost(path_, spans.SCOPES)
              for path_, _, _ in resolved}
    assert "step.forward" in stages


def _trace_call(log_dir, f, x, pause=0.0):
    """One ``bench.call`` span around ``f(x)`` and a ``pause``, traced
    into ``log_dir``; the harness's record of it: (name, start, end) on
    perf_counter."""
    import jax
    jax.profiler.start_trace(str(log_dir))
    with jax.profiler.TraceAnnotation("bench.call"):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        time.sleep(pause)
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    return ("call", t0, t1)


def test_the_run_reads_its_own_trace(tmp_path, monkeypatch):
    """The split is read from the trace whose call spans are the run's
    traced calls, written after its window started; a stale trace or one
    of other calls gives nothing."""
    import jax
    import jax.numpy as jnp
    from repro.core import spans

    @jax.jit
    def f(x):
        with spans.scope("step.forward"):
            return jnp.sin(x) * 3 + jnp.cumsum(x)

    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    stale = _trace_call(tmp_path / "bench_trace_old", f, x, pause=0.05)
    time.sleep(0.05)
    call = _trace_call(tmp_path / "bench_trace_new", f, x)

    def ctx(rec):
        return window.Context([window.Call(rec[1], rec[2], 1, 100)], [rec],
                              trace={}, traced_calls=1)

    red = scopes.reduction(ctx(call))
    assert red is not None
    assert red["call_s"] == [pytest.approx(call[2] - call[1], abs=1e-3)]
    # the older trace holds a call as long as the stale one, but was
    # written before a window that starts with the new call
    assert scopes.reduction(ctx(stale)) is not None
    shifted = ("call", call[1], call[1] + stale[2] - stale[1])
    assert scopes.reduction(ctx(shifted)) is None
    # calls the trace does not hold
    longer = ("call", call[1], call[2] + 0.01)
    assert scopes.reduction(ctx(longer)) is None
    untraced = window.Context([window.Call(call[1], call[2], 1, 100)],
                              [call], trace=None)
    assert scopes.reduction(untraced) is None


def test_fused_instructions_from_the_trace_hlo(tmp_path):
    """A fusion's fused instructions keep their own op_names in the HLO
    the profiler keeps (here a CPU trace)."""
    import jax
    import jax.numpy as jnp

    from harness import trace as tracing
    from repro.core import spans

    @jax.jit
    def two_stages(x):
        with spans.scope("step.vc_claim"):
            y = jnp.sin(x) * 3
        with spans.scope("step.forward"):
            return jnp.cos(y) + 1

    x = jnp.arange(4096.0)
    two_stages(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    two_stages(x).block_until_ready()
    jax.profiler.stop_trace()
    tables = scopes.hlo_tables(tracing.find_xplane(str(tmp_path)))
    (op_names, fused), = [t for name, t in tables.items()
                          if name.startswith("jit_two_stages(")]
    assert fused, "no fusion in the trace's HLO"
    stages = {scopes.innermost(p, spans.STEP_STAGES)
              for held in fused.values() for p in held}
    assert {"step.vc_claim", "step.forward"} <= stages
    # the elementwise chain fuses into one op of both stages
    assert any(scopes.multi_stage([op_names.get(n, "")], [held],
                                  spans.STEP_STAGES) == [True]
               for n, held in fused.items())
