"""The control of ``correct``: the reference with one stated guarantee
broken, put in the program's place, must come out not correct.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--requests N]

The configuration states the exact degeneracy as the arboricity bound of
the Theorem 26 cap. The control takes the doubling peel's bound instead —
the cheaper bound a faster admission would be tempted to use — and answers
every request a run of the cell would compare: with an open loop, every
request due in ``--seconds``; otherwise the first ``--requests``. Each of
the check's numbers is printed per seed; the benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.path[:0] = [str(HERE.parent / "src"), str(HERE.parent)]

from bench import check  # noqa: E402
from bench import reference  # noqa: E402
from bench import traffic as traffic_gen  # noqa: E402


def as_result(plan: reference.Plan, answer: dict, k: int):
    """An answer in the shape the engine returns."""
    info = {"bucket": (plan.R, plan.W), "depth": answer["rounds"],
            "threshold": plan.threshold, "high_degree": plan.high_degree,
            "lambda_bound": plan.lam}
    if k > 1:
        info.update(num_samples=k, picked_sample=answer["picked"])
    return SimpleNamespace(labels=answer["labels"], cost=answer["cost"],
                           info=info)


def numbers(jax, config: dict, traffic: dict, seed: int, seconds: float,
            requests: int = None) -> dict:
    pool = traffic_gen.make_pool(config, seed)
    budget = traffic_gen.request_budget(traffic, seconds)
    count = budget if traffic["arrivals"] == "poisson" else requests
    order = traffic_gen.graph_order(len(pool), count, seed)
    keys = traffic_gen.request_keys(count, seed)
    records = [SimpleNamespace(uid=i, graph=int(order[i]), key=keys[i],
                               result=None) for i in range(count)]
    eps, k = config["engine"].get("eps", 2.0), config["engine"]["num_samples"]
    plans, answers = check.reference_answers(jax, records, pool, eps, k)
    bad_plans, bad_answers = check.reference_answers(
        jax, records, pool, eps, k,
        bound=reference.doubling_degeneracy_bound)
    for r in records:
        r.result = as_result(bad_plans[r.graph], bad_answers[r.uid], k)
    return check.compare(records, plans, answers)[0]


def main(argv=None) -> int:
    import jax

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int)
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(args.workload)
    if traffic["arrivals"] != "poisson" and not args.requests:
        ap.error(f"{args.workload} is no open loop: give --requests")
    for seed in args.seeds:
        got = numbers(jax, config, traffic, seed, args.seconds, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "numbers": got,
                          "not_correct": any(v > 0 for v in got.values())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
