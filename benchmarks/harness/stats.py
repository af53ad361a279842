"""From the loop's records to the end-to-end metrics.  A rate is taken over
all the work and all the time of the window; a percentile is nearest-rank over
every step that qualifies, with its sample count printed."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple


def nearest_rank(values: "List[float]", q: float) -> float:
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def median(values: "List[float]") -> float:
    ordered = sorted(values)
    n = len(ordered)
    return ordered[n // 2] if n % 2 else 0.5 * (ordered[n // 2 - 1] + ordered[n // 2])


def measured(records: "List[Dict[str, Any]]") -> "List[Dict[str, Any]]":
    return [r for r in records if r["measured"]]


def interval(records: "List[Dict[str, Any]]") -> Tuple[float, float]:
    """Start of the first measured step to the end of the last."""
    m = measured(records)
    return min(r["t_start"] for r in m), max(r["t_end"] for r in m)


def window_close(records: "List[Dict[str, Any]]", kills: "List[Dict[str, Any]]",
                 seconds: float) -> float:
    """The window closes ``seconds`` after the first measured step starts; a
    recovery still under way then is waited for, so that a window holds the
    whole of every failure it holds the start of."""
    t0, t1 = interval(records)
    recovered = [k["t_recovered"] for k in kills if k["t_recovered"] is not None]
    return min(t1, max([t0 + seconds] + recovered))


def share_inside(r: Dict[str, Any], close: float) -> float:
    """The part of a step's time that lies before the window's close: 1 for a
    step that ended inside, pro rata for the one that straddles the close."""
    if r["t_end"] <= close:
        return 1.0
    return max(0.0, (close - r["t_start"]) / (r["t_end"] - r["t_start"]))


def recovery_steps(records: "List[Dict[str, Any]]", kills: "List[Dict[str, Any]]") -> "set[int]":
    """The step numbers from each kill through the step its new incarnation
    first commits (the healing step): by number, not by the clock, so that
    the steps on either side count in every run."""
    out: "set[int]" = set()
    for k in kills:
        # a new incarnation learns its step number in the healing step itself
        healed = [r["step_after"] - 1 for r in healing(records) if r["group"] == k["group"]]
        if not healed:
            raise RuntimeError("a killed group had not recovered when the window closed")
        out.update(range(k["step"], min(healed) + 1))
    return out


def step_times(records: "List[Dict[str, Any]]", kills: "List[Dict[str, Any]]") -> "List[float]":
    """Wall time of every committed measured step number, the slowest
    group's, leaving out the steps of a recovery."""
    skip = recovery_steps(records, kills)
    by_step: "Dict[int, List[float]]" = {}
    for r in steady(records):
        if r["step"] not in skip:
            by_step.setdefault(r["step"], []).append(r["t_end"] - r["t_start"])
    return [max(by_step[step]) for step in sorted(by_step)]


def end_to_end(
    records: "List[Dict[str, Any]]", kills: "List[Dict[str, Any]]",
    tokens_per_group_step: int, setup_s: float, seconds: float,
) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """The end-to-end metrics a run can report (their units are
    ``BENCHMARK.json``'s), and the counts behind them.  ``recover_s`` and
    ``survivor_stall_s`` exist only where a group was killed.

    ``tokens_per_s`` is over the window of ``seconds``: the tokens of every
    group-step committed inside it, the step in flight at its close counted
    for the part of its time that lies inside, over the window's length.  The
    loop runs on to a step boundary all groups agree on; a rate over that
    longer interval would depend on how many whole steps happen to follow a
    recovery of fixed cost (a step more or less moved it by 3.5 % on four
    chips, PERF.md section 2), so it is printed beside the counts only."""
    t0, t1 = interval(records)
    close = window_close(records, kills, seconds)
    m = measured(records)
    trained = steady(records)
    times = step_times(records, kills)
    metrics = {
        "tokens_per_s": (tokens_per_group_step * sum(share_inside(r, close) for r in trained)
                         / (close - t0)),
        "step_p90_ms": 1e3 * nearest_rank(times, 0.9),
        "setup_s": setup_s,
    }
    if kills:
        metrics["recover_s"] = max(k["t_recovered"] - k["t_kill"] for k in kills)
        victims = {k["group"] for k in kills}
        stall = 0.0
        for g in {r["group"] for r in m} - victims:
            commits = sorted(r["t_end"] for r in m if r["group"] == g and r["committed"])
            stall = max([stall] + [b - a for a, b in zip(commits, commits[1:])])
        metrics["survivor_stall_s"] = stall
    counts = {
        "window_s": close - t0,
        "interval_s": t1 - t0,
        "tokens_per_s_to_the_last_step": len(trained) * tokens_per_group_step / (t1 - t0),
        "attempted": len(m),
        "failed": sum(1 for r in m if not r["committed"]),
        "group_steps_trained": len(trained),
        "recovery_steps": sorted(recovery_steps(records, kills)),
        "step_time_samples": len(times),
        "step_median_ms": 1e3 * median(times),
    }
    return metrics, counts


def steady(records: "List[Dict[str, Any]]") -> "List[Dict[str, Any]]":
    """Measured, committed steps of groups that trained on them."""
    return [r for r in records if r["measured"] and r["committed"] and r["participating"]]


def healing(records: "List[Dict[str, Any]]") -> "List[Dict[str, Any]]":
    """The new incarnations' healing steps, one per kill."""
    return [r for r in records if r["healed"] and r["committed"]]
