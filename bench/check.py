"""The comparison that decides ``correct``: every answer due in the window
against the plain reference (:mod:`reference`), exactly.

Each number is a count of requests and its limit is 0: the configuration
states bit-exact answers, so one differing request is a fault.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference

NUMBERS = ("unanswered", "plan_differs", "labels_differ", "cost_differs",
           "pick_differs", "rounds_differ")


def request_faults(result, ref_plan, ref_answer) -> List[str]:
    """Which numbers one answered request adds to."""
    info = result.info
    faults = []
    if (tuple(info.get("bucket", ())) != (ref_plan.R, ref_plan.W)
            or info.get("lambda_bound") != ref_plan.lam
            or info.get("threshold") != ref_plan.threshold
            or info.get("high_degree") != ref_plan.high_degree):
        faults.append("plan_differs")
    labels = np.asarray(result.labels)
    if labels.shape != (ref_plan.n,) or not np.array_equal(
            labels, ref_answer["labels"]):
        faults.append("labels_differ")
    if int(result.cost) != ref_answer["cost"]:
        faults.append("cost_differs")
    if int(info.get("picked_sample", 0)) != ref_answer["picked"]:
        faults.append("pick_differs")
    if int(info.get("depth", -1)) != ref_answer["rounds"]:
        faults.append("rounds_differ")
    return faults


def reference_answers(jax, records, pool, eps: float, k: int,
                      plans: Dict[int, reference.Plan] = None,
                      bound=reference.degeneracy):
    """Reference plans (one per pool graph used) and answers (one per
    record), ranks drawn in one call per vertex count."""
    plans = {} if plans is None else plans
    for r in records:
        if r.graph not in plans:
            n, edges = pool[r.graph]
            plans[r.graph] = reference.plan(n, edges, eps, bound=bound)
    answers = {}
    by_n: Dict[int, list] = {}
    for r in records:
        by_n.setdefault(pool[r.graph][0], []).append(r)
    ranks = reference.sample_ranks(
        jax, {n: [r.key for r in group] for n, group in by_n.items()}, k)
    for n, group in by_n.items():
        for r, ranks_k in zip(group, ranks[n]):
            answers[r.uid] = reference.solve(plans[r.graph], ranks_k)
    return plans, answers


def compare(records, plans, answers) -> Tuple[Dict[str, int], int]:
    """Each number's count, and how many requests failed any of them."""
    counts = dict.fromkeys(NUMBERS, 0)
    failed = 0
    for r in records:
        faults = ["unanswered"] if r.result is None else request_faults(
            r.result, plans[r.graph], answers[r.uid])
        for fault in faults:
            counts[fault] += 1
        failed += bool(faults)
    return counts, failed
