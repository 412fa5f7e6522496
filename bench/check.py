"""The comparison that decides ``correct``: every checked plan against the
plain reference (``reference.py``), after the window has closed.

Numbers compared, each against the limit its configuration file gives:

* ``unanswered`` — requests sent in the window that never got a plan with
  status ``optimal`` (an error, a non-optimal status, or no reply before the
  drain ended).  Limit 0.
* ``wrong_problem`` — checked plans whose echoed problem differs from the one
  sent.  Limit 0: the echo is an exact round trip.
* ``plan_gap.device`` / ``plan_gap.rescued`` — the largest relative gap
  between the makespan the reference replay gives the served fractions and
  the reference LP optimum; plans the engine certified on the device and
  plans rescued on the host are counted apart, so a rescue cannot hide a
  wrong device result.
* ``replay_gap`` — the largest relative gap between the makespan the service
  reports (its device replay, or the host replay of a rescue) and the
  reference replay of the same fractions.
* ``fraction_gap.p99`` — the 99th percentile over the checked plans of a
  plan's fraction gap: the largest distance from 1 of a load's fractions
  summed over the processors, or of a negative fraction from 0.  The
  percentile and not the largest: the largest over thousands of plans is
  set by a few ill-conditioned chains that the program solves within its
  own tolerances, the same on IEEE float64 as on the chip, and it overlaps
  the float32 control's rounding, while the bulk of the plans lies decades
  apart from it.
"""

from __future__ import annotations

import numpy as np

import reference

__all__ = ["NUMBERS", "optimum", "compare", "control_plans"]

NUMBERS = ("unanswered", "wrong_problem", "plan_gap.device",
           "plan_gap.rescued", "replay_gap", "fraction_gap.p99")

_SENT_KEYS = ("topology", "w", "z", "tau", "latency", "v_comm", "v_comp",
              "release", "return_ratio")


def _fraction_gap(gamma: np.ndarray) -> float:
    return float(max(np.max(np.abs(gamma.sum(axis=0) - 1.0)),
                     -min(float(gamma.min()), 0.0)))


def optimum(problem: dict) -> float:
    """The reference's least makespan of one problem."""
    return reference.solve(problem)[0]


def compare(problems: list, records: list, limits: dict,
            optima: dict | None = None) -> tuple:
    """(correct, {number: {"value", "limit"}}, counts) over the records of
    one run, every one sent in the window.  ``optima`` holds the reference
    optimum of problems already solved, by problem index."""
    vals = dict.fromkeys(NUMBERS, 0.0)
    fraction_gaps = []
    counts = {"sent": len(records), "checked": 0, "device": 0, "rescued": 0}
    optima = dict(optima or {})
    for rec in records:
        if rec.get("status") != "optimal" or "t_done" not in rec:
            vals["unanswered"] += 1
            continue
        plan = rec.get("plan")
        if plan is None:
            continue
        sent = problems[rec["problem"]]
        counts["checked"] += 1
        if any(plan["problem"].get(k) != sent[k] for k in _SENT_KEYS):
            vals["wrong_problem"] += 1
            continue
        gamma = np.asarray(plan["gamma"], dtype=np.float64)
        if rec["problem"] not in optima:
            optima[rec["problem"]] = optimum(sent)
        best = optima[rec["problem"]]
        replayed = reference.replay(sent, gamma)
        kind = "rescued" if rec["rescued"] else "device"
        counts[kind] += 1
        key = "plan_gap." + kind
        vals[key] = max(vals[key], abs(replayed - best) / best)
        vals["replay_gap"] = max(vals["replay_gap"],
                                 abs(plan["makespan"] - replayed) / replayed)
        fraction_gaps.append(_fraction_gap(gamma))
    if fraction_gaps:
        vals["fraction_gap.p99"] = float(np.quantile(fraction_gaps, 0.99))
    numbers = {k: {"value": vals[k], "limit": limits[k]} for k in NUMBERS}
    correct = counts["checked"] > 0 and all(
        v["value"] <= v["limit"] for v in numbers.values())
    return correct, numbers, counts


def control_plans(problems: list, records: list,
                  dtype=np.float32) -> list:
    """The control: the same records with every checked plan replaced by the
    reference's own answer computed in ``dtype`` — the optimal fractions
    rounded to it and their makespan replayed in it."""
    out = []
    for rec in records:
        rec = dict(rec)
        if rec.get("plan") is not None:
            sent = problems[rec["problem"]]
            gamma = reference.solve(sent)[1].astype(dtype)
            rec["plan"] = dict(rec["plan"], gamma=gamma.astype(float).tolist(),
                               makespan=reference.replay(sent, gamma, dtype))
        out.append(rec)
    return out
