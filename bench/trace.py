"""Reduce a profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``.  Device planes are those named
``/device:<platform>:<n>``; on each, the ``XLA Ops`` line holds one
event per operation run and the ``XLA Modules`` line one per program
run.  Other planes named ``/device:...`` (a TPU trace also holds
``/device:CUSTOM:Megascale Trace``) are no chip and are not read.
Host spans are the harness's ``jax.profiler.TraceAnnotation``
events whose names start with ``bench.``; the ``bench.window`` span
marks the measured window.

Busy time is the union of a chip's operation intervals inside the
window (an operation that lies partly outside counts for the part
inside); idle is the rest of the window.  Each idle gap is labelled
with the innermost harness span open at its midpoint.  Time per op
counts leaf ops only: a loop op (a ``while`` around a layer scan) that
encloses later ops is left out, so no second counts twice.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Summary", "union_length", "gaps", "short_name", "summarize",
           "summarize_file",
           "newest_trace", "breakdown"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: a chip's plane: ``/device:<platform>:<n>``
CHIP_PLANE = re.compile(r"/device:(?!CPU:)[A-Z]+:\d+")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                            # mean over the chips traced
    chips: int
    op_s: Dict[str, float]                   # op name -> device seconds
    module_s: Dict[str, float]               # program name -> seconds
    module_n: Dict[str, int]                 # program name -> runs
    module_op_s: Dict[Tuple[str, str], float]  # (program, op) -> seconds
    idle_by_span: Dict[str, float]           # host span -> idle seconds

    def program_seconds(self, part: str) -> Tuple[float, int]:
        """(device seconds, runs) of the programs whose name holds
        ``part``, summed over the chips traced and divided by their
        number."""
        s = sum(v for k, v in self.module_s.items() if part in k)
        n = sum(v for k, v in self.module_n.items() if part in k)
        return s / self.chips, n // max(1, self.chips)

    def op_seconds(self, part: str, program: Optional[str] = None) -> float:
        """Device seconds of the ops whose name holds ``part`` (inside
        programs whose name holds ``program``, where given), per chip."""
        if program is None:
            s = sum(v for k, v in self.op_s.items() if part in k)
        else:
            s = sum(v for (m, k), v in self.module_op_s.items()
                    if part in k and program in m)
        return s / self.chips


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Length covered by the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def short_name(name: str) -> str:
    """An op event's HLO instruction name: TPU traces name an op by its
    whole HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _label(spans: List[Tuple[float, float, str]], t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no harness span"


def summarize(planes, *, window: Optional[Tuple[float, float]] = None
              ) -> Summary:
    """Reduce ``planes`` (``ProfileData.planes``, or objects shaped
    alike: ``.name``, ``.lines`` with ``.name`` and ``.events`` with
    ``.name``, ``.start_ns``, ``.duration_ns``) to a :class:`Summary`.
    Times are in nanoseconds in the trace and seconds in the result.
    ``window``: ``(start_ns, end_ns)``; default: the ``bench.window``
    span."""
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if CHIP_PLANE.fullmatch(plane.name):
            devices.append(plane)
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if window is None:
        win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        window = win[0]
    lo, hi = window
    if not devices:
        raise ValueError("no device plane in the trace")
    op_s: Dict[str, float] = collections.defaultdict(float)
    module_s: Dict[str, float] = collections.defaultdict(float)
    module_n: Dict[str, int] = collections.defaultdict(int)
    module_op_s: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is None:
                continue
            mods.append((c[0], c[1], ev.name))
            module_s[ev.name] += (c[1] - c[0]) * 1e-9
            module_n[ev.name] += 1
        mods.sort()
        starts = [m[0] for m in mods]
        ops = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is not None:
                ops.append((c[0], -c[1], short_name(ev.name)))
        ops.sort()
        busy = [(s, -e) for s, e, _ in ops]
        for k, (s, e, name) in enumerate(ops):
            e = -e
            if k + 1 < len(ops) and ops[k + 1][0] < e and -ops[k + 1][1] <= e:
                continue        # a loop or call around later ops: not a leaf
            sec = (e - s) * 1e-9
            op_s[name] += sec
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
            module_op_s[(mod, name)] += sec
        busy_total += union_length(busy) * 1e-9
        for s, e in gaps(busy, lo, hi):
            idle_by_span[_label(inner, (s + e) / 2)] += (e - s) * 1e-9
    n = len(devices)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                   chips=n, op_s=dict(op_s), module_s=dict(module_s),
                   module_n=dict(module_n), module_op_s=dict(module_op_s),
                   idle_by_span={k: v / n for k, v in idle_by_span.items()})


def newest_trace(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def summarize_file(path: str, **kw) -> Summary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path).planes, **kw)


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line: the device ops
    that took most time and the idle time by what the host was doing,
    in seconds per chip."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / summary.chips] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
