"""The program's own names in a profiler trace: the layer scope of each
device operation, and the host phases the program annotates.

Extends :mod:`bench.trace_reduce` and has its interface (``find_trace``,
``read``, ``reduce``): :func:`reduce` returns every key of
``trace_reduce.reduce`` with the same values, except that the host spans
that name idle gaps (``idle_gaps``, ``idle_by_span``) include the
program's ``repro.*`` annotations (``serving/telemetry.py``) beside the
harness's ``bench.*`` ones, and a gap goes to the span innermost for most
of it (:func:`reduce` says why).  It adds:

* ``scopes``: per program, the device seconds of each top-level
  ``jax.named_scope`` (``attention``, ``mlp``, ``lm_head``, ``sampler``,
  ...), ``unscoped`` for the rest.  Operations nest (a ``while`` spans the
  operations of its body), so each operation counts its *self* time: the
  instants at which it is the innermost operation running.  The scopes of
  a program therefore sum to the union of its operations' intervals.
* ``scope_calls``: per program and scope, the operation events counted.
* ``program_busy``: per program, the union of its operations'
  intervals, which its ``scopes`` sum to.
* ``idle_by_phase``: idle seconds (the window less the busy union) split
  instant by instant to the innermost host span then open, ``none``
  outside every span and ``in_program`` for bubbles inside a program run.
* ``top_op_scopes``: the scope of each operation in ``top_ops``.

An operation's scope comes from its HLO metadata, which
``jax.profiler.ProfileData`` does not expose: the device plane's
``event_metadata`` holds, per operation, a ``tf_op`` stat (the JAX name
stack, ``jit(_step_paged_impl)/while/body/closed_call/attention/...``)
and a ``program_id`` stat, the id in the module's name
(``jit__step_paged_impl(1808...)``).  :func:`op_metadata` reads them with
a minimal protobuf wire-format reader (no TensorFlow or protobuf
package), and they are joined to the operation events by (program id,
operation name).
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from pathlib import Path

from bench import trace_reduce
from bench.trace_reduce import DEVICE_PLANE, find_trace, op_name  # noqa: F401

PREFIXES = ("bench.", "repro.")
UNSCOPED = "unscoped"
# name-stack components that transformations and control flow add
CONTROL = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                     "remat", "rematted_computation", "scan", "pjit"})
_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")

# -- protobuf wire format ----------------------------------------------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>), .stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .uint64 = 3,
# .int64 = 4, .str = 5, .ref = 7; XStatMetadata.id = 1, .name = 2; map
# entries hold key = 1, value = 2.


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for varints, a ``(lo, hi)`` slice for
    length-delimited fields, None for fixed-width ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat_names(buf, plane) -> dict:
    names = {}
    for f, v in fields(buf, *plane):
        if f == 5:
            entry = dict(fields(buf, *v))
            if 2 in entry:
                meta = dict(fields(buf, *entry[2]))
                names[meta.get(1, entry.get(1))] = _text(buf, meta[2]) \
                    if 2 in meta else ""
    return names


def op_metadata(path) -> dict:
    """``{(program id, op name): name stack}`` (the ``tf_op`` stat less
    its op type) over the device planes of an ``.xplane.pb``; an operation
    without a ``tf_op`` (a copy the compiler added) is left out."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in fields(buf):
        if f != 1:
            continue
        name = next((_text(buf, v) for g, v in fields(buf, *plane) if g == 2),
                    "")
        if not DEVICE_PLANE.match(name):
            continue
        stat = _stat_names(buf, plane)
        for g, v in fields(buf, *plane):
            if g != 4:
                continue
            entry = dict(fields(buf, *v))
            if 2 not in entry:
                continue
            op, got = "", {}
            for h, w in fields(buf, *entry[2]):
                if h == 2:
                    op = op_name(_text(buf, w))
                elif h == 5:
                    got.update(_stat(buf, w, stat))
            if "tf_op" in got and "program_id" in got:
                # ``name stack:op type``; the type is often empty
                stack, sep, kind = got["tf_op"].rpartition(":")
                out[(int(got["program_id"]), op)] = \
                    stack if sep and "/" not in kind else got["tf_op"]
    return out


def _stat(buf, span, names) -> dict:
    key, value = None, None
    for f, v in fields(buf, *span):
        if f == 1:
            key = names.get(v)
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(buf, v)
        elif f == 7:
            value = names.get(v)
    return {key: value} if key in ("tf_op", "program_id") else {}


def scope_of(tf_op) -> str:
    """Top-level ``jax.named_scope`` of an op's name stack (the last
    component is the op): the first component after the program's
    ``jit(...)`` and control flow; ``unscoped`` where a library's nested
    ``jit(...)`` or a transformation comes first, or there is none."""
    if not tf_op:
        return UNSCOPED
    for i, part in enumerate(tf_op.split("/")[:-1]):
        if (i == 0 and part.startswith("jit(")) or part in CONTROL:
            continue
        return UNSCOPED if "(" in part else part
    return UNSCOPED


# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .opcode = 2, .metadata = 7 (OpMetadata.op_name = 2), .id = 35,
# .operand_ids = 36, .called_computation_ids = 38.
_BOUNDARY = frozenset({"while", "conditional", "call", "parameter"})
# a loop's slicing of its inputs and stacking of its outputs
_LOOP_OP = re.compile(r"/while/body/[^/]+$")


def _ints(buf, v) -> list:
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _instruction(buf, span) -> dict:
    d = {"name": "", "opcode": "", "op_name": "", "id": None,
         "operands": [], "called": []}
    for f, v in fields(buf, *span):
        if f == 1:
            d["name"] = _text(buf, v)
        elif f == 2:
            d["opcode"] = _text(buf, v)
        elif f == 7:
            d["op_name"] = next((_text(buf, w) for g, w in fields(buf, *v)
                                 if g == 2), "")
        elif f == 35:
            d["id"] = v
        elif f == 36:
            d["operands"] += _ints(buf, v)
        elif f == 38:
            d["called"] += _ints(buf, v)
    return d


def flow_scopes(buf, hlo_proto) -> dict:
    """``{instruction name: scope}`` for the plumbing of one program:
    instructions the compiler made (copies, converts and layout changes,
    with no name stack) and a loop's slicing of its inputs and stacking of
    its outputs (``.../while/body/<op>``).  Each takes the scope of the
    nearest instruction its result flows into, else of the nearest that
    flows into it; the walks pass only through plumbing, stay in the
    instruction's computation and stop at control flow.  What the program
    itself computes outside every scope (the embedding lookup, the norms)
    stays unscoped.  A fusion's own scope is that of the operations fused
    into it."""
    module = next((v for f, v in fields(buf, *hlo_proto) if f == 1), None)
    comps = {}          # computation id -> its instructions
    for f, v in (fields(buf, *module) if module is not None else ()):
        if f == 3:
            parts = list(fields(buf, *v))
            cid = next((w for g, w in parts if g == 5), None)
            comps[cid] = [_instruction(buf, w) for g, w in parts if g == 2]
    by_id = {d["id"]: d for c in comps.values() for d in c}
    comp_of = {d["id"]: cid for cid, c in comps.items() for d in c}
    fused = {c for d in by_id.values() if d["opcode"] == "fusion"
             for c in d["called"]}
    users = defaultdict(list)
    for d in by_id.values():
        for o in d["operands"]:
            users[o].append(d["id"])

    def own(d) -> str:
        scope = scope_of(d["op_name"])
        if scope == UNSCOPED and d["opcode"] == "fusion":
            inner = (scope_of(x["op_name"]) for c in d["called"]
                     for x in reversed(comps.get(c, [])))
            scope = next((x for x in inner if x != UNSCOPED), UNSCOPED)
        return scope

    def plumbing(d) -> bool:
        return not d["op_name"] or bool(_LOOP_OP.search(d["op_name"]))

    def nearest(d, step):
        seen, frontier = {d["id"]}, [d["id"]]
        while frontier and len(seen) < 256:
            nxt = []
            for x in frontier:
                for y in step(x):
                    if y in seen or y not in by_id or \
                            comp_of[y] != comp_of[d["id"]] or \
                            by_id[y]["opcode"] in _BOUNDARY:
                        continue
                    seen.add(y)
                    scope = own(by_id[y])
                    if scope != UNSCOPED:
                        return scope
                    if plumbing(by_id[y]):
                        nxt.append(y)

            frontier = nxt
        return None

    out = {}
    for cid, comp in comps.items():
        if cid in fused:
            continue
        for d in comp:
            if d["opcode"] in _BOUNDARY or not plumbing(d) or \
                    own(d) != UNSCOPED:
                continue
            scope = nearest(d, lambda x: users[x]) or \
                nearest(d, lambda x: by_id[x]["operands"])
            if scope:
                out[d["name"]] = scope
    return out


def hlo_flow(path) -> dict:
    """``{(program id, op name): scope}`` by :func:`flow_scopes` over the
    programs whose HLO the trace holds (its ``/host:metadata`` plane)."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in fields(buf):
        if f != 1:
            continue
        name = next((_text(buf, v) for g, v in fields(buf, *plane) if g == 2),
                    "")
        if name != "/host:metadata":
            continue
        stat = _stat_names(buf, plane)
        for g, v in fields(buf, *plane):
            if g != 4:
                continue
            entry = dict(fields(buf, *v))
            if 2 not in entry:
                continue
            module, proto = "", None
            for h, w in fields(buf, *entry[2]):
                if h == 2:
                    module = _text(buf, w)
                elif h == 5:
                    st = dict(fields(buf, *w))
                    if stat.get(st.get(1)) == "Hlo Proto" and 6 in st:
                        proto = st[6]
            pid = program_id(module)
            if proto is not None and pid is not None:
                out.update({(pid, op): s
                            for op, s in flow_scopes(buf, proto).items()})
    return out


# -- reading -----------------------------------------------------------------

def program_id(module_name: str):
    m = _PROGRAM_ID.search(module_name)
    return int(m.group(1)) if m else None


def read(path) -> dict:
    """:func:`trace_reduce.read`'s raw intervals with ``repro.*`` host
    spans too, and each chip's ``scoped_ops``: ``(start, end, program,
    scope)`` per operation."""
    from jax.profiler import ProfileData

    meta = op_metadata(path)
    flow = hlo_flow(path)
    pd = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns, e.end_ns, op_name(e.name))
                   for e in (lines[trace_reduce.OPS_LINE].events
                             if trace_reduce.OPS_LINE in lines else [])]
            mods = [(e.start_ns, e.end_ns, e.name)
                    for e in (lines[trace_reduce.MODULES_LINE].events
                              if trace_reduce.MODULES_LINE in lines else [])]
            chips.append({
                "name": plane.name, "ops": ops,
                "modules": [(a, b, trace_reduce.program_name(n))
                            for a, b, n in mods],
                "scoped_ops": scoped_ops(ops, mods, meta, flow)})
        else:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(PREFIXES)]
    return {"chips": chips, "spans": spans}


def scoped_ops(ops, modules, meta, flow=None) -> list:
    """``(start, end, program, scope, by_flow)`` of each op: its program
    is the module run that holds it (``?`` for none), its scope from the
    metadata of (that run's program id, op name), or where that names
    none from the dataflow (``flow``, :func:`hlo_flow`; ``by_flow``
    true)."""
    mods = sorted(modules)
    starts = [a for a, _, _ in mods]
    flow = flow or {}
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and mods[i][1] >= b:
            prog, pid = trace_reduce.program_name(mods[i][2]), \
                program_id(mods[i][2])
        else:
            prog, pid = "?", None
        scope = scope_of(meta.get((pid, name)))
        by_flow = scope == UNSCOPED and (pid, name) in flow
        out.append((a, b, prog, flow[(pid, name)] if by_flow else scope,
                    by_flow))
    return out


# -- reducing ----------------------------------------------------------------

def innermost(intervals) -> list:
    """Split the union of ``(start, end, key)`` intervals into segments
    ``(start, end, key)`` of the innermost interval open there: the one
    that started last (the shortest on a tie).  For nested intervals that
    is the self time of each; the segments' lengths sum to the union."""
    items = sorted((a, b, k) for a, b, k in intervals if b > a)
    points = sorted({t for a, b, _ in items for t in (a, b)})
    heap, out, i = [], [], 0
    for t0, t1 in zip(points, points[1:]):
        while i < len(items) and items[i][0] <= t0:
            a, b, k = items[i]
            heapq.heappush(heap, (-a, b, i, k))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            k = heap[0][3]
            if out and out[-1][2] == k and out[-1][1] == t0:
                out[-1] = (out[-1][0], t1, k)
            else:
                out.append((t0, t1, k))
    return out


def self_times(intervals) -> dict:
    """Self time per key of nested ``(start, end, key)`` intervals."""
    out = defaultdict(float)
    for a, b, k in innermost(intervals):
        out[k] += b - a
    return dict(out)


def _split(gap, segments, starts) -> dict:
    """Seconds of ``gap`` (ns) under each key of the sorted, disjoint
    ``segments``, ``none`` for the rest."""
    out, g0, g1 = defaultdict(float), gap[0], gap[1]
    j = max(bisect.bisect_right(starts, g0) - 1, 0)
    covered = 0
    while j < len(segments) and segments[j][0] < g1:
        a, b, key = segments[j]
        ov = min(b, g1) - max(a, g0)
        if ov > 0:
            out[key] += ov * 1e-9
            covered += ov
        j += 1
    if g1 - g0 > covered:
        out["none"] += (g1 - g0 - covered) * 1e-9
    return out


def reduce(raw: dict, top: int = 10) -> dict:
    """:func:`trace_reduce.reduce` of ``raw`` plus ``scopes``,
    ``scopes_by_flow``, ``scope_calls``, ``program_busy``,
    ``idle_by_phase`` and ``top_op_scopes`` (seconds, averaged over the
    chips).

    ``idle_gaps`` and ``idle_by_span`` keep their values, and each gap is
    named by the host span that is innermost for most of it: with the
    program's phases nested in the harness's spans, the span overlapping
    a gap most is the outer one whenever the gap crosses phases (the end
    of one step's ``post`` into the next step's ``admit``), so that rule
    would name every such gap ``bench.step_once``."""
    out = trace_reduce.reduce(raw, top)
    spans = raw["spans"]
    win = [(a, b) for name, a, b in spans if name == trace_reduce.WINDOW]
    chips = raw["chips"]
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        ends = [iv for c in chips for iv in c["ops"] + c["modules"]]
        lo, hi = min(a for a, _, _ in ends), max(b for _, b, _ in ends)
    n = len(chips)
    phases = innermost((a, b, name) for name, a, b in spans
                       if name != trace_reduce.WINDOW)
    phase_starts = [a for a, _, _ in phases]
    scopes = defaultdict(lambda: defaultdict(float))
    flowed = defaultdict(lambda: defaultdict(float))
    calls = defaultdict(lambda: defaultdict(int))
    programs = defaultdict(list)
    op_scope = {}
    idle = defaultdict(float)
    named_gaps = []
    for c in chips:
        scoped = c.get("scoped_ops", [])
        inside = [(max(a, lo), min(b, hi), (prog, scope, by_flow))
                  for a, b, prog, scope, by_flow in scoped
                  if min(b, hi) > max(a, lo)]
        for (prog, scope, by_flow), t in self_times(inside).items():
            scopes[prog][scope] += t * 1e-9 / n
            if by_flow:
                flowed[prog][scope] += t * 1e-9 / n
        for a, b, (prog, scope, _) in inside:
            calls[prog][scope] += 1
            programs[prog].append((a, b))
        for (_, _, name), (_, _, prog, scope, _) in zip(c["ops"], scoped):
            op_scope[f"{prog}/{name}"] = scope
        source = c["ops"] or c["modules"]
        busy = trace_reduce.union(trace_reduce.clip(
            [(a, b) for a, b, _ in source], lo, hi))
        mods = sorted(c["modules"])
        starts, ends = [a for a, _, _ in mods], [b for _, b, _ in mods]
        for g in trace_reduce.gaps(busy, lo, hi):
            i = bisect.bisect_right(starts, g[0]) - 1
            if i >= 0 and ends[i] >= g[1]:
                share = {"in_program": (g[1] - g[0]) * 1e-9}
            else:
                share = _split(g, phases, phase_starts)
            for key, t in share.items():
                idle[key] += t / n
            named_gaps.append(((g[1] - g[0]) * 1e-9,
                               max(share, key=share.get)))
    by_span = defaultdict(float)
    for t, name in named_gaps:
        by_span[name] += t / n
    named_gaps.sort(key=lambda g: -g[0])
    out["idle_gaps"] = [[name, t] for t, name in named_gaps[:top]]
    out["idle_by_span"] = dict(sorted(by_span.items(), key=lambda kv: -kv[1]))
    out["scopes"] = {p: dict(sorted(v.items(), key=lambda kv: -kv[1]))
                     for p, v in scopes.items()}
    out["scopes_by_flow"] = {p: dict(v) for p, v in flowed.items()}
    out["scope_calls"] = {p: {s: k // n for s, k in v.items()}
                          for p, v in calls.items()}
    out["program_busy"] = {p: sum(b - a for a, b in trace_reduce.union(v))
                           * 1e-9 / n for p, v in programs.items()}
    out["idle_by_phase"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    out["top_op_scopes"] = {name: op_scope.get(name, UNSCOPED)
                            for name, _ in out["top_ops"]}
    return out


# -- what the per-layer readers read ------------------------------------------

# the scheduler's decode step, DecodeEngine._step_paged_impl (not the
# forced or speculative variants, whose names end alike)
DECODE = re.compile(r"(?<![A-Za-z])_step_paged_impl")


def decode_scope_s(run, scope: str):
    """``(seconds, runs)``: the self time of the decode program's
    operations under ``scope`` in the traced window, and the program's
    runs there; None without a scoped trace, a run or the scope."""
    scopes = (run.trace or {}).get("scopes")
    if not scopes:
        return None
    progs = [p for p in scopes if DECODE.search(p)]
    runs = sum(run.trace["program_calls"].get(p, 0) for p in progs)
    if not runs or not any(scope in scopes[p] for p in progs):
        return None
    return sum(scopes[p].get(scope, 0.0) for p in progs), runs


def decode_scope_ms(run, scope: str):
    """Device ms per decode step in ``scope``."""
    got = decode_scope_s(run, scope)
    return None if got is None else 1e3 * got[0] / got[1]
