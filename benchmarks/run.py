"""Benchmark harness: one function per paper claim.

Prints ``name,us_per_call,derived`` CSV. The ``derived`` column carries the
quantity each theorem bounds (approximation ratio, round count, component
size / log n, ...) — see benchmarks/paper_claims.py docstrings.

    PYTHONPATH=src python -m benchmarks.run [--only substring]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.util import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run benchmarks whose name contains this substring")
    args = ap.parse_args()

    from . import paper_claims

    benches = list(paper_claims.ALL)
    print("name,us_per_call,derived")
    failed = 0
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived:.4f}", flush=True)
        except Exception as e:  # pragma: no cover
            failed += 1
            print(f"{bench.__name__},ERROR,{type(e).__name__}",
                  file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
