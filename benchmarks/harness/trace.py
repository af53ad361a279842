"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
window, the device time of a named jitted program, every device operation
with its own time and the program's name for it, and the idle gaps named by
the benchmark's own spans.

Reads the trace with nothing but ``jax.profiler.ProfileData``.  What a v5e
trace looks like (looked at by hand, PR 23): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per device operation); one
plane ``/host:CPU`` whose thread lines hold the ``TraceAnnotation`` spans.
All events share one clock, in nanoseconds.  A device event is named by its
HLO instruction (``%checkpoint.20 = ...``) and carries no scope (its stats
are offsets only, looked at by hand in PR 26): what the program calls an
operation (``jax.named_scope``s, the jitted function, a ``pallas_call``'s
kernel) is read from the compiled executable's HLO instead (``hlo_names``)."""

from __future__ import annotations

import base64
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


def self_times(ops: "List[Tuple[str, float, float]]") -> "Dict[str, List[float]]":
    """``{label: [own seconds, events]}``, each event's own time only: the
    ``XLA Ops`` line nests (a ``while`` spans the operations of its body),
    and a sum of durations would count the body twice."""
    out: "Dict[str, List[float]]" = {}
    stack: "List[List[Any]]" = []  # [label, end, own seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            label, _, own = stack.pop()
            row = out.setdefault(label, [0.0, 0])
            row[0] += max(own, 0.0)
            row[1] += 1

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


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_HLO_KERNEL_BODY = re.compile(r'custom_call_target="tpu_custom_call".*"body":"([^"]*)"')


def _kernel_name(body_b64: str) -> Optional[str]:
    """The name of a ``pallas_call``'s kernel: the ``sym_name`` of the Mosaic
    module a TPU custom call carries, serialized, in its ``backend_config``."""
    from jax.extend.mlir import ir

    try:
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        attrs = ir.Module.parse(base64.b64decode(body_b64), ctx).operation.attributes
        return ir.StringAttr(attrs["sym_name"]).value
    except Exception:  # noqa: BLE001 - a body this jax cannot read names no kernel
        return None


def hlo_names(hlo_text: str) -> "Dict[str, Dict[str, Optional[str]]]":
    """``{label: {"op_name", "kernel"}}`` from a compiled executable's HLO
    (``compiled.as_text()``): what the program calls each instruction that a
    device event can be named by.  ``op_name`` is the path of
    ``jax.named_scope``s, transforms and the jitted function's name down to
    the primitive; ``kernel`` is a ``pallas_call``'s kernel, else None."""
    out: "Dict[str, Dict[str, Optional[str]]]" = {}
    for line in hlo_text.splitlines():
        head = _HLO_LINE.match(line)
        if not head:
            continue
        op_name = _HLO_OP_NAME.search(line)
        body = _HLO_KERNEL_BODY.search(line)
        if op_name or body:
            out[head.group(1)[:64]] = {
                "op_name": op_name.group(1) if op_name else None,
                "kernel": _kernel_name(body.group(1)) if body else None}
    return out


def module_of(modules: "List[Tuple[str, float, float]]", at: float) -> Optional[str]:
    """The jitted program whose run holds the instant ``at`` on this chip."""
    for name, a, b in modules:
        if a <= at < b:
            return name.split("(", 1)[0]
    return None


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
    names: "Optional[Dict[str, Dict[str, Dict[str, Optional[str]]]]]" = None,
) -> Dict[str, Any]:
    """The traced window is the span from the first outer span's start to the
    last one's end.  Busy time is the union of the device operations inside
    it; ``busy_s`` is the mean over the chips used.

    ``ops`` holds every device operation of the window, by the program whose
    run it lies in: ``module``, ``label``, its own ``seconds`` and ``calls``
    summed over the chips (``seconds`` add up to ``busy_s`` times the chips;
    a program ran ``len(module_seconds[module])`` times), and ``op_name`` and
    ``kernel`` where ``names[module]`` (``hlo_names`` of that program's
    executable) knows the label."""
    steps = [s for s in trace["spans"] if s["name"] in outer]
    if not steps:
        raise ValueError("the trace holds none of the benchmark's step spans")
    lo, hi = min(s["start"] for s in steps), max(s["end"] for s in steps)
    busy_by_chip: "Dict[int, float]" = {}
    op_times: "Dict[Tuple[Optional[str], str], List[float]]" = {}
    idle_named: "Dict[str, float]" = {}
    modules: "Dict[str, List[float]]" = {}
    for chip in chips_used:
        events = trace["chips"].get(chip, {"modules": [], "ops": []})
        busy = union(clip(((a, b) for _, a, b in events["ops"]), lo, hi))
        busy_by_chip[chip] = total(busy)
        by_module: "Dict[Optional[str], List[Tuple[str, float, float]]]" = {}
        for n, a, b in events["ops"]:
            if b > lo and a < hi:
                by_module.setdefault(module_of(events["modules"], a), []).append(
                    (n, max(a, lo), min(b, hi)))
        for module, inside in by_module.items():
            for label, (seconds, calls) in self_times(inside).items():
                row = op_times.setdefault((module, label), [0.0, 0])
                row[0] += seconds
                row[1] += calls
        for name, a, b in events["modules"]:
            if a >= lo and b <= hi:
                modules.setdefault(name.split("(", 1)[0], []).append(b - a)
        mine = [s for s in trace["spans"]
                if s["stats"].get("group") in groups_on_chip.get(chip, [])]
        for label, seconds in name_gaps(gaps(busy, lo, hi), mine, outer).items():
            idle_named[label] = idle_named.get(label, 0.0) + seconds / len(chips_used)

    def top(d: Dict[str, float]) -> "List[List[Any]]":
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    ops, shown = [], {}
    for (module, label), (seconds, calls) in sorted(op_times.items(), key=lambda kv: -kv[1][0]):
        known = (names or {}).get(module, {}).get(label, {})
        ops.append({"module": module, "label": label, "seconds": seconds, "calls": calls,
                    "op_name": known.get("op_name"), "kernel": known.get("kernel")})
        # the ledger keeps the ten largest: say what the program calls them
        what = known.get("kernel") or "/".join((known.get("op_name") or "").split("/")[-2:])
        key = f"{label} {what}".strip()[:64]
        shown[key] = shown.get(key, 0.0) + seconds / len(chips_used)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_by_chip.values()) / len(chips_used),
        "busy_by_chip": busy_by_chip,
        "module_seconds": modules,
        "ops": ops,
        "breakdown": {"device_ops": top(shown), "idle_gaps": top(idle_named)},
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
