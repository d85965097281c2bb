"""Device time of a traced run split by the program's own scopes, and the
program's host spans and counters inside the window.

The program (``repro.core.spans``) names the stages of its cycle step and
driver with ``jax.named_scope`` (``step.*``, ``driver.*``), marks host
spans ``repro.<name>`` for the profiler, and keeps each span, with its
counters, in memory.  Where it has no such module, as before it had one,
every function here returns ``None``.

The reduction reads the traced run's ``.xplane.pb`` once more, after the
window, and caches it for every reader of the run:

- A leaf is a device op event that contains no other op event of its
  device; the ``while``/``cond`` containers are not summed.  A leaf's
  stage is the innermost scope on its ``op_name`` path, resolved once per
  distinct op name and program in the HLO protos the trace keeps of the
  program that ran it (``hlo_tables``, ``resolve``).  A fusion's
  ``op_name`` is one of its instructions' (usually the root's): where the
  instructions it fused come from more than one stage, its whole time is
  booked to that one.  The split says how much leaf time such fusions
  hold (``multi_stage_s``).
- ``step.*``: leaf time per stage.  ``step_gap``: busy time inside
  ``driver.cycle`` events that no leaf covers, the step's op issue and
  control.  ``driver``: ``driver.*`` leaves, and loop-nest busy time
  outside ``driver.cycle``.  ``unresolved``: leaves inside
  ``driver.cycle`` with no step stage.  ``other``: the rest of busy time
  (initial state, stacking, the energy program).  Together they are the
  device's busy time.
- Idle gaps are put down to the innermost ``repro.`` span open at their
  middle.

The trace read is the one of this run: written after the window started,
and holding the harness's ``bench.call`` spans of the traced calls, each
as long as the reader's context records it.
"""
from __future__ import annotations

import array
import dataclasses
import glob
import heapq
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

from harness import trace as tracing

PREFIX = "repro."           # host spans of the program in the trace
CALL = tracing.SPAN_PREFIX + "call"
TOP = 10


def program_spans():
    """``repro.core.spans``, or None where the program has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


@dataclasses.dataclass
class ScopedTrace:
    # device plane -> (op name ids, start ns, end ns), one entry per event
    ops: dict[str, tuple[np.ndarray, np.ndarray, np.ndarray]]
    # op name id -> the op's op_name path, "" where the trace gives none
    paths: list[str]
    # device plane -> [(program name, start ns, end ns)]
    modules: dict[str, list[tuple[str, float, float]]]
    # program host spans, prefix stripped: [(name, start ns, end ns)]
    spans: list[tuple[str, float, float]]
    # the harness's call spans: [(start ns, end ns)]
    calls: list[tuple[float, float]]
    # op name id -> the op_name paths of the instructions a fusion holds
    fused: list[frozenset[str]] = dataclasses.field(default_factory=list)
    # share of op name ids resolved in the HLO of the program that ran
    # them (the rest by name alone, over every program)
    own_program_share: float | None = None


def _pb_fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as memoryviews, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, value


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _first(buf, num: int):
    return next((v for n, v in _pb_fields(buf) if n == num), None)


def _ints(value) -> list[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _hlo_modules(path: str):
    """(program name, HLO module) of every program the process compiled,
    as the profiler keeps them in the trace's ``/host:metadata`` plane
    (TPU op events carry no op_name of their own).  The program name,
    ``jit_f(<id>)``, is the one the device's ``XLA Modules`` events carry.

    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map
    entry value 2); XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1.
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _pb_fields(space):
        if num != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        for num, entry in _pb_fields(plane):
            meta = _first(entry, 2) if num == 4 else None
            for n, stat in _pb_fields(meta or b""):
                proto = _first(stat, 6) if n == 5 else None
                module = _first(proto, 1) if proto is not None else None
                if module is not None:
                    yield bytes(_first(meta, 2) or b"").decode(), module


def _computations(module) -> dict[int, list[tuple[str, str, str, list]]]:
    """Computation id -> its instructions as (name, opcode, op_name or "",
    called computation ids).

    Field numbers: HloModuleProto.computations 3; HloComputationProto.id
    5, .instructions 2; HloInstructionProto.name 1, .opcode 2, .metadata
    7, .called_computation_ids 38; OpMetadata.op_name 2.
    """
    comps = {}
    for comp in (v for n, v in _pb_fields(module) if n == 3):
        insts, cid = [], 0          # a zero id is left out of the proto
        for n, v in _pb_fields(comp):
            if n == 5:
                cid = v
            elif n == 2:
                name = opcode = op_name = ""
                called = []
                for k, w in _pb_fields(v):
                    if k == 1:
                        name = bytes(w).decode()
                    elif k == 2:
                        opcode = bytes(w).decode()
                    elif k == 7:
                        op_name = bytes(_first(w, 2) or b"").decode()
                    elif k == 38:
                        called += _ints(w)
                insts.append((name, opcode, op_name, called))
        comps[cid] = insts
    return comps


def _module_tables(module) -> tuple[dict[str, str], dict[str, frozenset]]:
    """(op_names, fused) of one HLO module: instruction name -> op_name,
    and fusion name -> the op_names of the instructions it fused, nested
    computations included."""
    comps = _computations(module)
    held: dict[int, frozenset] = {}

    def names_in(cid: int) -> frozenset:
        if cid not in held:
            held[cid] = frozenset()       # a cycle adds nothing
            out = set()
            for _, _, op_name, called in comps.get(cid, ()):
                if op_name:
                    out.add(op_name)
                for c in called:
                    out |= names_in(c)
            held[cid] = frozenset(out)
        return held[cid]

    op_names: dict[str, str] = {}
    fused: dict[str, frozenset] = {}
    for insts in comps.values():
        for name, opcode, op_name, called in insts:
            if op_name:
                op_names[name] = op_name
            if opcode == "fusion":
                fused[name] = frozenset().union(*map(names_in, called))
    return op_names, fused


def hlo_tables(path: str) -> dict[str, tuple[dict, dict]]:
    """Program name -> (op_names, fused) of its HLO (``_module_tables``)."""
    return {name: _module_tables(module)
            for name, module in _hlo_modules(path)}


def merged(tables) -> tuple[dict[str, str], dict[str, frozenset]]:
    """The tables of all programs in one: a name that two programs give
    different op_names maps to "", and a fusion's fused op_names are the
    union over the programs that share its name."""
    op_names: dict[str, str] = {}
    fused: dict[str, frozenset] = {}
    for names, inner in tables.values():
        for name, op_name in names.items():
            op_names[name] = op_name if op_names.get(
                name, op_name) == op_name else ""
        for name, held in inner.items():
            fused[name] = fused.get(name, frozenset()) | held
    return op_names, fused


def _instruction(event_name: str) -> str:
    """The HLO instruction an op event names (``%fusion.53 = s32[...]
    fusion(...)`` on a TPU, ``fusion.53`` on the CPU)."""
    return event_name.split(" = ")[0].lstrip("%")


def program_tables(program: str | None, tables):
    """The tables of ``program`` (``jit_f(<id>)``), or of the one program
    of its name where the ids differ (a program loaded from the
    compilation cache can carry another id than its HLO); None where
    neither is found."""
    if program in tables:
        return tables[program]
    if program is None:
        return None
    base = program.split("(")[0]
    same = [t for name, t in tables.items() if name.split("(")[0] == base]
    return same[0] if len(same) == 1 else None


def resolve(event_name: str, program: str | None, tables,
            fallback) -> tuple[str, frozenset, bool]:
    """(op_name path, fused op_names, found in its own program) of an op
    event run by ``program``: from that program's tables where the trace
    keeps them, else from ``fallback`` (``merged``), where a name two
    programs share may be ambiguous."""
    instr = _instruction(event_name)
    own = program_tables(program, tables)
    names, fused = own if own is not None else fallback
    return (names.get(instr, ""), fused.get(instr, frozenset()),
            own is not None)


def _program_of(start: np.ndarray, mods) -> np.ndarray:
    """Per op start: the index in ``mods`` ([(name, start, end)] sorted
    by start) of the program execution that holds it, else -1."""
    if not mods:
        return np.full(len(start), -1)
    ms = np.array([a for _, a, _ in mods], np.float64)
    me = np.array([b for _, _, b in mods], np.float64)
    k = np.searchsorted(ms, start, side="right") - 1
    inside = (k >= 0) & (start <= me[np.maximum(k, 0)])
    return np.where(inside, k, -1)


def load(path: str) -> ScopedTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tables = hlo_tables(path)
    fallback = merged(tables)
    raw: dict[str, tuple] = {}       # plane -> (name ids, starts, ends)
    modules: dict[str, list] = {}
    names: dict[str, int] = {}
    spans, calls = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name in tracing.MODULE_LINES:
                    modules.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in ln.events)
                elif ln.name in tracing.OP_LINES:
                    # millions of events: the least work per event
                    nid, t0, dt = array.array("q"), array.array("d"), \
                        array.array("d")
                    for e in ln.events:
                        name = e.name
                        i = names.get(name)
                        if i is None:
                            i = names[name] = len(names)
                        nid.append(i)
                        t0.append(e.start_ns)
                        dt.append(e.duration_ns)
                    start = np.frombuffer(t0, np.float64)
                    raw[plane.name] = (np.frombuffer(nid, np.int64), start,
                                       start + np.frombuffer(dt, np.float64))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == CALL:
                        calls.append((e.start_ns, e.start_ns + e.duration_ns))
    # each distinct (program, op name) resolved once: key (program + 1)
    # * R + name id, program -1 where no program execution holds the op
    event_names, R = list(names), max(len(names), 1)
    programs: list[str] = []
    keys: dict[int, int] = {}
    paths: list[str] = []
    fused: list[frozenset] = []
    own = 0
    ops: dict[str, tuple] = {}
    for plane, (nid, a, b) in raw.items():
        mods = sorted(modules.get(plane, []), key=lambda m: m[1])
        prog_ids = []
        for n, _, _ in mods:
            if n not in programs:
                programs.append(n)
            prog_ids.append(programs.index(n))
        prog = np.array(prog_ids + [-1], np.int64)[_program_of(a, mods)]
        uniq, inverse = np.unique((prog + 1) * R + nid, return_inverse=True)
        local = np.empty(len(uniq), np.int64)
        for j, key in enumerate(uniq.tolist()):
            if key not in keys:
                keys[key] = len(paths)
                p, n = divmod(key, R)
                path_, held, found = resolve(
                    event_names[n], programs[p - 1] if p else None, tables,
                    fallback)
                paths.append(path_)
                fused.append(held)
                own += found
            local[j] = keys[key]
        ops[plane] = (local[inverse.reshape(-1)], a, b)
    return ScopedTrace(ops, paths, modules, sorted(spans, key=lambda s: s[1]),
                       sorted(calls), fused,
                       own / len(paths) if paths else None)


def innermost(path: str, names) -> str | None:
    """The last scope of ``names`` on an op_name path."""
    for part in reversed(path.split("/")):
        if part in names:
            return part
    return None


def _merge(a: np.ndarray, b: np.ndarray):
    """Union of [a, b) intervals sorted by ``a``: disjoint (starts, ends)."""
    if not len(a):
        return a, b
    reach = np.maximum.accumulate(b)
    new = np.ones(len(a), bool)
    new[1:] = a[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(a) - 1]
    return a[first], reach[last]


def _overlap(a, b, cs, ce) -> np.ndarray:
    """Per interval [a, b): its length inside the disjoint sorted cover
    (cs, ce), counting the one cover interval that holds its start."""
    out = np.zeros(len(a))
    if not len(cs):
        return out
    k = np.searchsorted(cs, a, side="right") - 1
    ok = k >= 0
    kk = k[ok]
    out[ok] = np.clip(np.minimum(b[ok], ce[kk]) - np.maximum(a[ok], cs[kk]),
                      0, None)
    return out


def _intersection(xs, xe, ys, ye) -> float:
    """Length of the intersection of two disjoint sorted interval sets."""
    total, i, j = 0.0, 0, 0
    xs, xe, ys, ye = (v.tolist() for v in (xs, xe, ys, ye))
    while i < len(xs) and j < len(ys):
        total += max(0.0, min(xe[i], ye[j]) - max(xs[i], ys[j]))
        if xe[i] < ye[j]:
            i += 1
        else:
            j += 1
    return total


def multi_stage(paths, fused, stages) -> list[bool]:
    """Per op name: whether its own op_name and those of the instructions
    it fused name more than one of ``stages``."""
    out = []
    for i, path in enumerate(paths):
        held = fused[i] if i < len(fused) else ()
        out.append(len({innermost(q, stages) for q in (path, *held)}
                       - {None}) > 1)
    return out


def split_device(ids, a, b, paths, busy, stages, driver_scopes,
                 mixed=None) -> tuple[dict, dict]:
    """One device's busy time split by scope, in ns.

    ``ids``, ``a``, ``b``: its op events (name id, start, end) clipped to
    the window; ``busy``: its merged busy intervals (starts, ends);
    ``mixed``: per op name, whether it fused instructions of more than
    one stage.  Returns a dict of ``stages`` plus ``step_gap``,
    ``driver``, ``unresolved`` and ``other``, and per stage the part of
    its leaf time held by such fusions.
    """
    names = tuple(stages) + tuple(driver_scopes)
    S, CYCLE, DRIVER, NONE = len(stages), len(stages), len(stages) + 1, \
        len(stages) + 2
    code = [NONE if w is None else names.index(w) if w in stages
            else CYCLE if w == "driver.cycle" else DRIVER
            for w in (innermost(p, names) for p in paths)]
    where = np.array(code + [NONE], np.int64)[ids]
    many = np.array(list(mixed or [False] * len(paths)) + [False])[ids]
    order = np.lexsort((-b, a))
    a, b, where, many = a[order], b[order], where[order], many[order]
    # a container holds the event after it; the rest are leaves
    cont = np.zeros(len(a), bool)
    cont[:-1] = (a[1:] < b[:-1]) & (b[1:] <= b[:-1])
    loop_s, loop_e = _merge(a[cont], b[cont])
    cyc = cont & (where == CYCLE)
    cyc_s, cyc_e = _merge(a[cyc], b[cyc])
    la, lb, lw, lm = a[~cont], b[~cont], where[~cont], many[~cont]
    # each leaf's time not already covered by an earlier leaf (async ops
    # may overlap)
    if len(la):
        reach = np.r_[-np.inf, np.maximum.accumulate(lb)[:-1]]
        la = np.maximum(la, reach)
    dur = np.clip(lb - la, 0, None)
    in_cycle = _overlap(la, lb, cyc_s, cyc_e)
    in_loop = _overlap(la, lb, loop_s, loop_e)
    staged = lw < S
    unresolved = ~staged & ((lw == CYCLE) | (lw == NONE)) \
        & (in_cycle > 0.5 * dur)
    driver = ~staged & ~unresolved & ((lw == CYCLE) | (lw == DRIVER)
                                      | (in_loop > 0.5 * dur))
    other = ~(staged | unresolved | driver)
    per_stage = np.bincount(lw[staged], weights=dur[staged], minlength=S)
    out = {st: float(per_stage[i]) for i, st in enumerate(stages)}
    both = staged & lm
    per_mixed = np.bincount(lw[both], weights=dur[both], minlength=S)
    mixed_out = {st: float(per_mixed[i]) for i, st in enumerate(stages)}
    bs, be = busy
    cycle_busy = _intersection(cyc_s, cyc_e, bs, be)
    loop_busy = _intersection(loop_s, loop_e, bs, be)
    out["step_gap"] = max(cycle_busy - float(in_cycle.sum()), 0.0)
    out["driver"] = float(dur[driver].sum()) + max(
        (loop_busy - cycle_busy) - float(in_loop.sum() - in_cycle.sum()),
        0.0)
    out["unresolved"] = float(dur[unresolved].sum())
    out["other"] = float(dur[other].sum())
    out["other"] += max(float((be - bs).sum()) - sum(out.values()), 0.0)
    return out, mixed_out


def _span_at(spans, t: float) -> str:
    """The innermost program span open at time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "none"


def reduce(st: ScopedTrace, stages, driver_scopes) -> dict:
    """Seconds summed over devices per part, the unresolved share of the
    leaf time inside ``driver.cycle``, the stage leaf time held by
    fusions of more than one stage, the longest idle gaps, and the
    traced calls' lengths."""
    if not st.calls:
        raise ValueError("the trace holds no call span")
    lo, hi = st.calls[0][0], st.calls[-1][1]
    names = tuple(stages) + tuple(driver_scopes)
    parts = dict.fromkeys(tuple(stages) + ("step_gap", "driver",
                                           "unresolved", "other"), 0.0)
    mixed = multi_stage(st.paths, st.fused, stages)
    multi = dict.fromkeys(stages, 0.0)
    busy_total, gaps = 0.0, []
    for dev in sorted(st.modules or st.ops):
        if st.modules:
            ev = sorted(tracing._clip([(a, b) for _, a, b in st.modules[dev]],
                                      lo, hi))
            ba, bb = np.array([a for a, _ in ev]), np.array([b for _, b in ev])
        ids, a, b = st.ops.get(dev, (np.zeros(0, np.int64), np.zeros(0),
                                     np.zeros(0)))
        keep = (np.minimum(b, hi) > np.maximum(a, lo))
        ids, a, b = ids[keep], np.maximum(a[keep], lo), np.minimum(b[keep], hi)
        if not st.modules:
            o = np.argsort(a, kind="stable")
            ba, bb = a[o], b[o]
        busy = _merge(ba, bb)
        busy_total += float((busy[1] - busy[0]).sum())
        dev_parts, dev_multi = split_device(ids, a, b, st.paths, busy,
                                            stages, driver_scopes, mixed)
        for k, v in dev_parts.items():
            parts[k] += v
        for k, v in dev_multi.items():
            multi[k] += v
        edges = [lo] + [x for ab in zip(*busy) for x in ab] + [hi]
        gaps += heapq.nlargest(TOP, ((e - s, s, e) for s, e in
                                     zip(edges[::2], edges[1::2]) if e > s))
    ns = 1e-9
    staged = sum(parts[s] for s in stages)
    in_cycle = staged + parts["unresolved"]
    return {
        # False where no op event named a scope: no split to read
        "scoped": any(innermost(p, names) for p in st.paths),
        "parts_s": {k: v * ns for k, v in parts.items()},
        "busy_s_total": busy_total * ns,
        "unresolved_share": parts["unresolved"] / in_cycle if in_cycle
        else None,
        "multi_stage_s": {k: v * ns for k, v in multi.items()},
        "multi_stage_share": sum(multi.values()) / staged if staged
        else None,
        "call_s": [(b - a) * ns for a, b in st.calls],
        "own_program_share": st.own_program_share,
        "idle_gaps": [[_span_at(st.spans, (a + b) / 2), t * ns]
                      for t, a, b in heapq.nlargest(TOP, gaps)],
    }


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:          # removed meanwhile
        return float("-inf")


def find_xplanes(since: float) -> list[str]:
    """Traces in ``bench_trace_*`` directories written at or after wall
    time ``since``, newest first."""
    out = []
    for d in glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*")):
        try:
            path = tracing.find_xplane(d)
        except FileNotFoundError:
            continue
        if _mtime(path) >= since:
            out.append(path)
    return sorted(out, key=_mtime, reverse=True)


def traced_call_s(ctx) -> list[float]:
    """Host seconds of the harness's call spans of the traced calls."""
    calls = sorted((a, b) for n, a, b in ctx.spans if n == "call")
    return [b - a for a, b in calls[:ctx.traced_calls]]


def _matches(red: dict, want: list[float], tol_s: float = 1e-3) -> bool:
    """Whether the trace's call spans are the traced calls of the run."""
    got = red["call_s"]
    return len(got) == len(want) > 0 and all(
        abs(g - w) <= tol_s for g, w in zip(got, want))


_cache: dict[str, dict | None] = {}


def _reduced(path: str, spans) -> dict | None:
    """The reduction of one trace file, loaded once per process."""
    if path not in _cache:
        # a boundary: a trace this reduction cannot read costs the run
        # these metrics, not its result line
        try:
            t0 = time.perf_counter()
            st = load(path)
            t1 = time.perf_counter()
            red = reduce(st, spans.STEP_STAGES, spans.DRIVER_SCOPES)
            red["load_s"] = t1 - t0
            red["reduce_s"] = time.perf_counter() - t1
            print("scopes", json.dumps(red), file=sys.stderr, flush=True)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            red = None
        _cache[path] = red
    return _cache[path]


def reduction(ctx) -> dict | None:
    """The reduced trace of the run ``ctx`` describes; None untraced,
    where the program has no scopes, or where no trace holds its traced
    calls."""
    spans = program_spans()
    if spans is None or ctx.trace is None or not ctx.traced_calls:
        return None
    want = traced_call_s(ctx)
    if len(want) != ctx.traced_calls:
        return None
    # the window's start on the wall clock: the run's trace is written
    # after it
    since = time.time() - (time.perf_counter() - ctx.calls[0].t0)
    for path in find_xplanes(since):
        red = _reduced(path, spans)
        if red is not None and _matches(red, want):
            return red
    return None


def window_records(ctx, calls=None):
    """The program's span records inside the window of ``calls`` (default
    every call of ``ctx``); None where the program keeps none, or where
    the ring has dropped part of the window."""
    spans = program_spans()
    if spans is None:
        return None
    calls = ctx.calls if calls is None else calls
    lo, hi = calls[0].t0, calls[-1].t1
    recs = spans.snapshot()
    if len(recs) >= spans.MAXLEN and min(r.t1 for r in recs) >= lo:
        return None
    return [r for r in recs if r.t0 >= lo and r.t1 <= hi]


def per_call(ctx, *names: str) -> float | None:
    """Host seconds per call in the program's spans ``names``."""
    recs = window_records(ctx)
    if recs is None:
        return None
    return sum(r.t1 - r.t0 for r in recs if r.name in names) / len(ctx.calls)


def us_per_lane_cycle(ctx, part: str) -> float | None:
    """Device µs of one part of the split, summed over devices, per lane-
    cycle the traced calls executed (Σ ``drain_cycle`` of real lanes)."""
    red = reduction(ctx)
    if red is None or not red["scoped"]:
        return None
    traced = window_records(ctx, ctx.calls[:ctx.traced_calls])
    executed, _ = lane_cycles(traced)
    if not executed:
        return None
    return red["parts_s"][part] / executed * 1e6


def lane_cycles(recs) -> tuple[int, int]:
    """(executed, budget) lane-cycles: the counters of the
    ``compute_metrics_batch`` records among ``recs``."""
    done = [r.attrs for r in recs or () if r.name == "compute_metrics_batch"]
    return (sum(a["executed_lane_cycles"] for a in done),
            sum(a["budget_lane_cycles"] for a in done))


def executed_share(ctx) -> float | None:
    """Percent of the window's lane-cycle budget its lanes executed
    (Σ ``drain_cycle`` ÷ Σ ``cycles_run``); under 100 where lanes
    drained early and the driver stopped them."""
    executed, budget = lane_cycles(window_records(ctx))
    return executed / budget * 100 if budget else None
