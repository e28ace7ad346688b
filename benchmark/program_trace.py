"""Reductions of the program's own spans (``span`` in
fluidframework_tpu/utils/telemetry.py), kept with the benchmark so that
every PR computes them the same way.

Each process the program arms writes one file: ``spans`` rows of
[name, start wall ns, duration ns, parent index, thread id], ``samples``
of [wall ns, value] per name, the window's counter deltas, and its
anchor, the wall ns at which the window was entered. In the process that
ran the profiler (the applier stage) arming also left a ``trace.window``
annotation on the profile at that moment; ``window_start_ns`` finds it.
The difference of the two maps wall ns onto the trace, for the stage's
spans and for the core's, which ran on the same host clock.

- ``reduce_program(doc, window)``: per span name, count, total, self
  time (durations less their children's) and p50/p99;
  ``program_summary(doc, window)`` adds the samples' statistics and the
  counters, for the readers. ``window_on_wall`` gives the benchmark's
  traced window on the files' clock: the program's own window ends a
  turn of the stage's loop after it, or later where the stage was held.
- ``idle_by_span(events, stage_doc, window_ns)``: every idle gap of the
  traced window (``events`` as ``trace.load_events`` returns them), not
  only the ten longest, attributed to the innermost stage span covering
  most of it; seconds per name.
- ``core_stalls(core_doc)``: the core's ten longest serving-loop stalls,
  each with the core span that covered most of it.

A program without spans writes no file; every function here takes None
or an empty file and then returns None. ``benchmark/trace.py`` is left
as it was: its ``bench.*`` reduction is what the accepted metrics read.
"""

from __future__ import annotations

import glob
import os

from benchmark.trace import WINDOW, _union

UNNAMED = "host.unannotated"
#: the annotation the program leaves on the profile where its spans'
#: window starts (``WINDOW_SPAN`` in utils/telemetry.py)
PROGRAM_WINDOW = "trace.window"


def _pct(sorted_vals, q: float):
    """Nearest rank, as benchmark.common.percentile, of sorted values."""
    if not sorted_vals:
        return None
    k = max(0, min(len(sorted_vals) - 1,
                   -(-int(q * len(sorted_vals)) // 100) - 1))
    return sorted_vals[k]


def window_start_ns(trace_dir: str) -> int | None:
    """Start of the program's ``trace.window`` annotation on the clock of
    the profile under ``trace_dir`` (the file ``trace.load_events``
    reads); None where there is none, as in a program without spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == PROGRAM_WINDOW:
                        return int(e.start_ns)
    return None


def wall_to_trace_ns(doc, window_ns) -> int | None:
    """What to add to a wall-clock ns to place it on the trace's clock:
    the window's start on the profile (``window_start_ns``) less the
    anchor of the process that ran the profiler."""
    wall = ((doc or {}).get("anchor") or {}).get("wall_ns")
    if window_ns is None or wall is None:
        return None
    return int(window_ns) - int(wall)


def window_on_wall(events, stage_doc, window_ns) -> tuple | None:
    """The benchmark's traced window (``bench.window`` of ``events``) as
    [lo, hi) in wall ns, the clock of both processes' files."""
    offset = wall_to_trace_ns(stage_doc, window_ns)
    wins = [(s, d) for n, s, d in (events or {}).get("host", ())
            if n == WINDOW]
    if offset is None or not wins:
        return None
    lo, dur = max(wins, key=lambda w: w[1])
    return lo - offset, lo + dur - offset


def reduce_program(doc, window=None) -> dict | None:
    """Per span name: count, total_ms, self_ms (durations less their
    children's), mean_ms, p50_ms and p99_ms, over the spans that closed
    in the program's window (not those cut at its end, listed under
    ``open``) and, with ``window`` ([lo, hi) wall ns), lie wholly in
    it."""
    spans = (doc or {}).get("spans")
    if not spans:
        return None
    cut = set(doc.get("open") or ())
    lo, hi = window or (-1, float("inf"))
    children = [0] * len(spans)
    for _n, _s, d, parent, _t in spans:
        if 0 <= parent < len(spans):
            children[parent] += d
    rows: dict = {}
    for i, (n, s, d, _p, _t) in enumerate(spans):
        if i not in cut and lo <= s and s + d <= hi:
            rows.setdefault(n, []).append((d, d - children[i]))
    out = {}
    for n, r in sorted(rows.items()):
        ds = sorted(d for d, _ in r)
        total = sum(ds)
        out[n] = {"count": len(ds), "total_ms": total / 1e6,
                  "self_ms": sum(x for _, x in r) / 1e6,
                  "mean_ms": total / len(ds) / 1e6,
                  "p50_ms": _pct(ds, 50) / 1e6,
                  "p99_ms": _pct(ds, 99) / 1e6}
    return out


def program_summary(doc, window=None) -> dict | None:
    """What the per-layer readers take from one process's file: its
    spans reduced and each sample's count/mean/p50/p99, in ``window``
    ([lo, hi) wall ns) where one is given; the counters' deltas and the
    count of each span name, over the program's whole window; and that
    window's length."""
    if not doc:
        return None
    lo, hi = window or (-1, float("inf"))
    samples = {}
    for n, rows in sorted((doc.get("samples") or {}).items()):
        vals = sorted(v for t, v in rows if lo <= t < hi)
        if vals:
            samples[n] = {"count": len(vals), "mean": sum(vals) / len(vals),
                          "p50": _pct(vals, 50), "p99": _pct(vals, 99)}
    counts: dict = {}
    for n, *_ in doc.get("spans") or ():
        counts[n] = counts.get(n, 0) + 1
    a = doc.get("anchor") or {}
    return {"spans": reduce_program(doc, window) or {}, "samples": samples,
            "counters": dict(doc.get("counters") or {}),
            "span_counts": counts, "overflow": doc.get("overflow", 0),
            "window_s": ((a["stop_wall_ns"] - a["wall_ns"]) / 1e9
                         if a.get("stop_wall_ns") else None)}


def _depths(spans) -> list:
    depth = [0] * len(spans)
    for i, (_n, _s, _d, parent, _t) in enumerate(spans):
        if 0 <= parent < i:  # a parent is entered, so stored, first
            depth[i] = depth[parent] + 1
    return depth


def _intervals(doc, offset: int, skip=()) -> list:
    """[(start, end, name, depth)] on the trace's clock, by start."""
    spans = doc["spans"]
    depth = _depths(spans)
    return sorted((s + offset, s + offset + d, n, depth[i])
                  for i, (n, s, d, _p, _t) in enumerate(spans)
                  if n not in skip)


def _cover(active, s: int, e: int) -> str:
    """The innermost span covering more than half of [s, e); failing
    that, the one covering most of it; failing that, UNNAMED."""
    best, key = UNNAMED, (False, 0, 0)
    half = (e - s) / 2
    for hs, he, n, depth in active:
        c = min(e, he) - max(s, hs)
        if c <= 0:
            continue
        k = (c > half, depth if c > half else 0, c)
        if k > key:
            best, key = n, k
    return best


def _attribute(intervals, gaps) -> list:
    """For each of the sorted, disjoint ``gaps``, its (name, ns)."""
    out, active, i = [], [], 0
    for s, e in gaps:
        while i < len(intervals) and intervals[i][0] < e:
            active.append(intervals[i])
            i += 1
        active = [iv for iv in active if iv[1] > s]
        out.append((_cover(active, s, e), e - s))
    return out


def idle_gaps(events) -> list | None:
    """Every idle gap [start, end) of the window on the first device
    that ran anything (the devices ``reduce`` averages over), sorted."""
    wins = [(s, d) for n, s, d in events["host"] if n == WINDOW]
    if not wins:
        return None
    lo, dur = max(wins, key=lambda w: w[1])
    hi = lo + dur
    for _name, dev in sorted(events["device"].items()):
        merged = _union([(s, d) for _, s, d in dev["ops"]], lo, hi)
        if not merged:
            continue
        gaps, prev = [], lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        return gaps
    return None


def idle_by_span(events, stage_doc, window_ns) -> dict | None:
    """Seconds of device idle per program span: every idle gap of the
    window goes to the innermost span of the chip's process (the stage)
    covering most of it, ``host.unannotated`` where none does.
    ``window_ns`` is ``window_start_ns`` of the run's profile."""
    offset = wall_to_trace_ns(stage_doc, window_ns)
    gaps = idle_gaps(events)
    if offset is None or gaps is None or not stage_doc.get("spans"):
        return None
    out: dict = {}
    for name, ns in _attribute(_intervals(stage_doc, offset), gaps):
        out[name] = out.get(name, 0.0) + ns / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


STALL = "frontend.loop_stall"


def core_stalls(core_doc, n: int = 10) -> list | None:
    """The ``n`` longest serving-loop stalls of the core: [name of the
    core span covering most of each, stall ms], longest first."""
    spans = (core_doc or {}).get("spans")
    if not spans:
        return None
    stalls = sorted(((s, s + d) for name, s, d, _p, _t in spans
                     if name == STALL), key=lambda g: g[0] - g[1])[:n]
    if not stalls:
        return []
    named = _attribute(_intervals(core_doc, 0, skip=(STALL,)),
                       sorted(stalls))
    return sorted(([name, ns / 1e6] for name, ns in named),
                  key=lambda r: -r[1])
