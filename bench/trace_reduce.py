"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are
``/device:TPU:<n>``: their ``XLA Ops`` line holds one event per operation
that ran, their ``XLA Modules`` line one event per jitted program run.
Host spans are the harness's own ``jax.profiler.TraceAnnotation`` events,
whose names start with ``bench.``; the outermost ``bench.traced_window``
one, when present, bounds the window.

* busy: the union of operation intervals inside the window (overlapping
  operations count once), averaged over the chips; idle share is
  ``1 - busy / window``;
* device seconds per program, keyed by the program's name without its id
  suffix (``jit__step_paged_impl``);
* the operations that took most device time, named ``program/op``;
* idle gaps (the window less the busy union): a gap inside a program run
  is ``in_program``; any other is attributed to the ``bench.*`` span that
  overlaps it most (the innermost on a tie), or ``none``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "bench."
WINDOW = "bench.traced_window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name.strip()).strip()


def op_name(name: str) -> str:
    """An op event is named by its HLO line (``%fusion.3 = bf16[...]
    fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def find_trace(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Spans:
    """Host spans sorted by start, for overlap lookups."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [a for _, a, _ in self.spans]
        self.longest = max((b - a for _, a, b in self.spans), default=0)

    def attribute(self, gap) -> str:
        """Name of the span overlapping ``gap`` most; innermost on a tie."""
        best, key = "none", (0.0, 0.0)
        i = bisect.bisect_left(self.starts, gap[1]) - 1
        while i >= 0 and self.starts[i] >= gap[0] - self.longest:
            name, a, b = self.spans[i]
            ov = min(b, gap[1]) - max(a, gap[0])
            if ov > 0 and (ov, -(b - a)) > key:
                best, key = name, (ov, -(b - a))
            i -= 1
        return best


def read(path) -> dict:
    """Raw intervals (ns) of one trace: per-chip ops and programs, and the
    harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns, e.end_ns, op_name(e.name))
                   for e in (lines[OPS_LINE].events if OPS_LINE in lines else [])]
            mods = [(e.start_ns, e.end_ns, program_name(e.name))
                    for e in (lines[MODULES_LINE].events
                              if MODULES_LINE in lines else [])]
            chips.append({"name": plane.name, "ops": ops, "modules": mods})
        else:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(PREFIX)]
    return {"chips": chips, "spans": spans}


def reduce(raw: dict, top: int = 10) -> dict:
    """Device metrics of one trace (seconds), from :func:`read`'s output."""
    chips, spans = raw["chips"], raw["spans"]
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    win = [(a, b) for name, a, b in spans if name == WINDOW]
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        ends = [iv for c in chips for iv in c["ops"] + c["modules"]]
        lo, hi = min(a for a, _, _ in ends), max(b for _, b, _ in ends)
    attributed = Spans(s for s in spans if s[0] != WINDOW)
    busy_ns, programs, calls, ops_time = [], defaultdict(float), \
        defaultdict(int), defaultdict(float)
    all_gaps: list[tuple[float, str]] = []
    for c in chips:
        source = c["ops"] or c["modules"]
        busy = union(clip([(a, b) for a, b, _ in source], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        mods = sorted(c["modules"])
        starts = [a for a, _, _ in mods]
        for a, b, name in mods:
            if b > lo and a < hi:
                programs[name] += (min(b, hi) - max(a, lo)) * 1e-9
                calls[name] += 1
        for a, b, name in c["ops"]:
            if b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= b else "?"
            ops_time[f"{prog}/{name}"] += (min(b, hi) - max(a, lo)) * 1e-9
        ends = [b for _, b, _ in mods]
        for g in gaps(busy, lo, hi):
            # a gap inside a program run is a bubble between its operations
            i = bisect.bisect_right(starts, g[0]) - 1
            inside = i >= 0 and ends[i] >= g[1]
            all_gaps.append(((g[1] - g[0]) * 1e-9, "in_program" if inside
                             else attributed.attribute(g)))
    n = len(chips)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / n * 1e-9
    idle_by = defaultdict(float)
    for s, name in all_gaps:
        idle_by[name] += s / n
    all_gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": {k: v / n for k, v in programs.items()},
        "program_calls": {k: v // n for k, v in calls.items()},
        "top_ops": [[k, v / n] for k, v in sorted(
            ops_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, s] for s, name in all_gaps[:top]],
        "idle_by_span": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        "n_chips": n,
    }
