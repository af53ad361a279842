"""The comparison that decides ``correct``.  Every number compared is printed
beside its limit, in every run.

The limits come from the cell's file; how each was read on the chip (the
largest a sound run gave over the seeds, the smallest the lower-precision
control gave) is in PERF.md section 2."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np



def worst_norm_gap(mine: "Dict[str, np.ndarray]", ref: "Dict[str, np.ndarray]") -> Tuple[float, str]:
    """Worst leaf of |own norm - reference norm| over the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients are
    all but zero).  A leaf is one layer's slice of one weight."""
    if sorted(mine) != sorted(ref):
        raise ValueError("the two sides have different leaves")
    floor = float(np.median(np.concatenate([np.ravel(v) for v in ref.values()])))
    worst, where, by_name = 0.0, "", {}
    for name in sorted(ref):
        r, m = np.ravel(ref[name]).astype(np.float64), np.ravel(mine[name]).astype(np.float64)
        gap = np.abs(m - r) / np.maximum(r, floor)
        gap = np.where(np.isfinite(gap), gap, np.inf)
        at = int(np.argmax(gap))
        by_name[name] = round(float(gap[at]), 6)
        if gap[at] > worst:
            worst, where = float(gap[at]), f"{name}[{at}]"
    return worst, f"{where}; worst layer of each weight {by_name}"


def against_reference(first: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers of the set-up steps: each step's loss, the norm of
    the first gradient as the optimizer got it, and the norm of the
    parameters' change after the steps the reference followed."""
    loss_gap = max(
        abs(first["losses"][(step, g)] - want) / abs(want)
        for step, row in enumerate(ref["losses"]) for g, want in enumerate(row))
    grad_gap, grad_at = worst_norm_gap(first["grad0_norms"], ref["grad0_norms"])
    delta_gap, delta_at = worst_norm_gap(first["delta_norms"], ref["delta_norms"])
    print(f"correct: worst grad0 leaf {grad_at}, worst delta leaf {delta_at}", flush=True)
    return {"loss_gap": loss_gap, "grad0_norm_gap": grad_gap, "delta_norm_gap": delta_gap}


def trajectory(records: "List[Dict[str, Any]]", kills: "List[Dict[str, Any]]") -> Dict[str, float]:
    """What every step shows for free: finite losses, no error latched, all
    groups bitwise equal after every commit they share (the victim's first
    committed step and the last step among them), the same final step."""
    by_step: "Dict[int, List[Any]]" = {}
    for r in records:
        if r["committed"]:
            by_step.setdefault(r["step_after"], []).append(r["fingerprint"])
    final = {}
    for r in records:
        final[r["group"]] = max(final.get(r["group"], 0), r["step_after"])
    healed_groups = {r["group"] for r in records if r["healed"] and r["committed"]}
    return {
        "nonfinite_losses": float(sum(1 for r in records if not np.isfinite(r["loss"]))),
        "errors_latched": float(sum(1 for r in records if r["errored"] is not None)),
        "fingerprint_mismatches": float(sum(
            1 for fps in by_step.values() for fp in fps[1:] if fp != fps[0])),
        "final_step_spread": float(max(final.values()) - min(final.values())),
        "kills_not_healed": float(sum(1 for k in kills if k["group"] not in healed_groups)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> "Tuple[bool, Dict[str, Any]]":
    """Every number against its limit; a number without a limit, a limit
    without a number and a number that is not finite all fail.  Returns the
    verdict and, for the result's line, each number beside its limit (``None``
    where one is missing or not finite)."""
    verdict, compared = True, {}
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name, float("nan")), limits.get(name, float("nan"))
        ok = bool(np.isfinite(value) and np.isfinite(limit) and value <= limit)
        verdict = verdict and ok
        print(f"correct: {name} = {value!r} limit {limit!r} {'ok' if ok else 'FAIL'}", flush=True)
        compared[name] = {"value": float(value) if np.isfinite(value) else None,
                          "limit": float(limit) if np.isfinite(limit) else None, "ok": ok}
    return verdict, compared
