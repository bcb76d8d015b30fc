"""The serving engine's spans and counters in a traced run's profile.

The engine marks its host work with ``jax.profiler.TraceAnnotation``
spans whose names start with ``engine.``, in the same ``.xplane.pb`` as
the device planes and the harness's ``bench.*`` spans.  Each idle gap
of the device (as ``trace.py`` finds it: the parts of the
``bench.window`` span that no op of a chip covers) is cut at the engine
spans' boundaries, and each piece goes to the innermost engine span
open over it (the one that opened last; of two opened together, the
shorter), or to ``"none"``.

A traced ``engine.admit`` span (one admission sweep) carries as its
metadata the change of the engine's admission counters over the sweep
(``ADMIT_COUNTERS``); their sums over the sweeps inside the window are
the counters' change over the window.

A program that records no engine span gives an empty reading, and the
metrics that read it report nothing.  ``trace.py``'s summary and
breakdown do not read engine spans: what they report is the same with
and without them.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace as tr

__all__ = ["EngineSummary", "innermost_segments", "summarize",
           "summarize_file", "of_run"]

ENGINE_PREFIX = "engine."
ADMIT_SPAN = "engine.admit"
#: the admission counters an ``engine.admit`` span carries
ADMIT_COUNTERS = ("admitted", "queue_wait_s", "prefill_rows",
                  "prefill_tokens")
#: where idle falls outside every engine span
NO_ENGINE_SPAN = "none"
#: where the harness keeps a traced run's profile, one directory a cell
TRACE_ROOT = Path(__file__).resolve().parent.parent / ".bench_trace"


@dataclasses.dataclass
class EngineSummary:
    window_s: float
    idle: Dict[str, float]       # innermost engine span -> idle seconds
    n: Dict[str, int]            # engine span -> runs in the window
    counts: Dict[str, float]     # admission counter -> change in window

    def idle_under(self, names) -> Optional[float]:
        """Idle seconds under the spans ``names``; None where none of
        them ran in the window."""
        if not any(k in self.n for k in names):
            return None
        return sum(self.idle.get(k, 0.0) for k in names)


def innermost_segments(spans: List[Tuple[float, float, str]], lo: float,
                       hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut at the spans' boundaries: ``(start, end, name)``
    pieces in order, each named for the innermost span open over all of
    it, or :data:`NO_ENGINE_SPAN`."""
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    order = sorted(spans)
    out, heap, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            s, e, name = order[k]
            heapq.heappush(heap, (-s, e, k, name))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][3] if heap else NO_ENGINE_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _split_idle(idle: List[Tuple[float, float]],
                segments: List[Tuple[float, float, str]],
                into: Dict[str, float]) -> None:
    """Add each idle interval's overlap with each segment to ``into``
    under the segment's name, in seconds (both lists sorted)."""
    j = 0
    for s, e in idle:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            part = min(b, e) - max(a, s)
            if part > 0:
                into[name] += part * 1e-9
            k += 1


def summarize(planes) -> EngineSummary:
    """Reduce ``planes`` (as ``trace.summarize`` takes them; an event may
    also have ``.stats``, ``(name, value)`` pairs) over the
    ``bench.window`` span."""
    window = None
    spans: List[Tuple[float, float, str]] = []
    admits = []
    devices = []
    for plane in planes:
        if tr.CHIP_PLANE.fullmatch(plane.name):
            devices.append(plane)
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(ENGINE_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
                    if name == ADMIT_SPAN:
                        admits.append((ev.start_ns, ev))
                elif name == tr.WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"no {tr.WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no device plane in the trace")
    lo, hi = window
    spans = [c + (name,) for s, e, name in spans
             if (c := tr._clip(s, e, lo, hi)) is not None]
    n: Dict[str, int] = collections.Counter(name for _, _, name in spans)
    counts: Dict[str, float] = collections.defaultdict(float)
    for start, ev in admits:
        if lo <= start < hi:
            for k, v in getattr(ev, "stats", ()):
                if k in ADMIT_COUNTERS:
                    counts[k] += v
    segments = innermost_segments(spans, lo, hi)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        busy = [c for ev in (lines[tr.OPS_LINE].events
                             if tr.OPS_LINE in lines else ())
                if (c := tr._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  lo, hi)) is not None]
        _split_idle(tr.gaps(busy, lo, hi), segments, idle)
    chips = len(devices)
    return EngineSummary(window_s=(hi - lo) * 1e-9,
                         idle={k: v / chips for k, v in idle.items()},
                         n=dict(n), counts=dict(counts))


def summarize_file(path: str) -> EngineSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path).planes)


def newest_run_trace(root: Path) -> Optional[str]:
    """The newest profile the harness wrote under ``root``, or None."""
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of_run(x) -> Optional[EngineSummary]:
    """The engine's reading of the traced run whose per-layer metrics
    are being read (``x``: the harness's ``LayerInputs``, on which it is
    kept for the other readers): the newest profile under
    :data:`TRACE_ROOT`, taken only if its window is the one
    ``x.summary`` was read over.  Reading it logs the idle table to
    standard error."""
    if not hasattr(x, "engine_trace"):
        x.engine_trace = _read_newest(x.summary.window_s)
    return x.engine_trace


def _read_newest(window_s: float) -> Optional[EngineSummary]:
    path = newest_run_trace(TRACE_ROOT)
    if path is None:
        return None
    t0 = time.perf_counter()
    es = summarize_file(path)
    for name, sec in sorted(es.idle.items(), key=lambda kv: -kv[1]):
        print(f"engine_idle {name}: {sec:.4f} s in {es.n.get(name, 0)} "
              f"spans", file=sys.stderr)
    print(f"engine spans read in {time.perf_counter() - t0:.3f} s",
          file=sys.stderr, flush=True)
    return es if es.window_s == window_s else None
