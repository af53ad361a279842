"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
window, the device time of a named jitted program, the device operations
that took most time, and the idle gaps named by the benchmark's own spans.

Reads the trace with nothing but ``jax.profiler.ProfileData``.  What a v5e
trace looks like (looked at by hand, PR 23): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per device operation); one
plane ``/host:CPU`` whose thread lines hold the ``TraceAnnotation`` spans.
All events share one clock, in nanoseconds."""

from __future__ import annotations

import glob
import os
import re
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: "Iterable[Interval]") -> "List[Interval]":
    out: "List[Interval]" = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip(intervals: "Iterable[Interval]", lo: float, hi: float) -> "List[Interval]":
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: "Iterable[Interval]") -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: "List[Interval]", lo: float, hi: float) -> "List[Interval]":
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_seconds(ops: "List[Tuple[str, float, float]]") -> Dict[str, float]:
    """Seconds by operation, each event's own time only: the ``XLA Ops`` line
    nests (a ``while`` spans the operations of its body), and a sum of
    durations would count the body twice."""
    out: "Dict[str, float]" = {}
    stack: "List[List[Any]]" = []  # [label, end, own seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            label, _, own = stack.pop()
            out[label] = out.get(label, 0.0) + max(own, 0.0)

    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([op_label(name), b, b - a])
    close(float("inf"))
    return out


def op_label(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def load(path: str, span_prefix: str) -> Dict[str, Any]:
    """Events of one trace, in seconds: per chip the module runs and the
    device operations, and every host span whose name has the prefix."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips: "Dict[int, Dict[str, List[Any]]]" = {}
    spans: "List[Dict[str, Any]]" = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    chip["modules"] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                elif line.name == "XLA Ops":
                    chip["ops"] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append({
                            "name": e.name[len(span_prefix):], "thread": line.name,
                            "start": e.start_ns * 1e-9,
                            "end": (e.start_ns + e.duration_ns) * 1e-9,
                            "stats": dict(e.stats),
                        })
    return {"chips": chips, "spans": spans}


def name_gaps(
    idle: "List[Interval]", spans: "List[Dict[str, Any]]", outer: "Tuple[str, ...]",
) -> Dict[str, float]:
    """Seconds of idle time by what the host was doing: each gap is cut at
    every span boundary and each piece is named by the inner spans open
    then (``ring``, ``ring+fwdbwd`` when two groups share the chip), by the
    outer span when no inner one is, or ``(no span)``."""
    cuts = sorted({t for s in spans for t in (s["start"], s["end"])})
    out: "Dict[str, float]" = {}
    for lo, hi in idle:
        edges = [lo] + [t for t in cuts if lo < t < hi] + [hi]
        for a, b in zip(edges, edges[1:]):
            mid = 0.5 * (a + b)
            open_now = [s["name"] for s in spans if s["start"] <= mid < s["end"]]
            inner = sorted({n for n in open_now if n not in outer})
            label = "+".join(inner or sorted(set(open_now))) or "(no span)"
            out[label] = out.get(label, 0.0) + (b - a)
    return out


def reduce(
    trace: Dict[str, Any], chips_used: "List[int]", groups_on_chip: "Dict[int, List[int]]",
    outer: "Tuple[str, ...]" = ("step", "heal"),
) -> Dict[str, Any]:
    """The traced window is the span from the first outer span's start to the
    last one's end.  Busy time is the union of the device operations inside
    it; ``busy_s`` is the mean over the chips used."""
    steps = [s for s in trace["spans"] if s["name"] in outer]
    if not steps:
        raise ValueError("the trace holds none of the benchmark's step spans")
    lo, hi = min(s["start"] for s in steps), max(s["end"] for s in steps)
    busy_by_chip: "Dict[int, float]" = {}
    op_seconds: "Dict[str, float]" = {}
    idle_named: "Dict[str, float]" = {}
    modules: "Dict[str, List[float]]" = {}
    for chip in chips_used:
        events = trace["chips"].get(chip, {"modules": [], "ops": []})
        busy = union(clip(((a, b) for _, a, b in events["ops"]), lo, hi))
        busy_by_chip[chip] = total(busy)
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in events["ops"] if b > lo and a < hi]
        for label, seconds in self_seconds(inside).items():
            op_seconds[label] = op_seconds.get(label, 0.0) + seconds
        for name, a, b in events["modules"]:
            if a >= lo and b <= hi:
                modules.setdefault(name.split("(", 1)[0], []).append(b - a)
        mine = [s for s in trace["spans"]
                if s["stats"].get("group") in groups_on_chip.get(chip, [])]
        for label, seconds in name_gaps(gaps(busy, lo, hi), mine, outer).items():
            idle_named[label] = idle_named.get(label, 0.0) + seconds / len(chips_used)

    def top(d: Dict[str, float]) -> "List[List[Any]]":
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_by_chip.values()) / len(chips_used),
        "busy_by_chip": busy_by_chip,
        "module_seconds": modules,
        "breakdown": {"device_ops": top(op_seconds), "idle_gaps": top(idle_named)},
    }


class Tracer:
    """Starts and stops the profiler from the loop; host spans only from
    ``TraceAnnotation`` (the Python call tracer is off: it would slow the
    host path that is being measured).  Stopping writes the trace, which
    takes seconds on four chips: ``stop_later`` does it on a thread of its
    own so the group that asked does not sit out the steps that follow (a
    kill lands right after the traced steps), and ``stop`` waits for it."""

    def __init__(self, trace_dir: str) -> None:
        self.trace_dir = trace_dir
        self.running = False
        self._stopper: "Optional[threading.Thread]" = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.running = True

    def stop_later(self) -> None:
        self._stopper = threading.Thread(target=self.stop, name="trace-stop", daemon=True)
        self._stopper.start()

    def stop(self) -> None:
        import jax

        stopper = self._stopper
        if stopper is not None and stopper is not threading.current_thread():
            stopper.join()
        if self.running:
            self.running = False
            jax.profiler.stop_trace()
