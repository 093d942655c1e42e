"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration and its traffic
mix are found by name through ``BENCHMARK.json``. The run needs a TPU: with
none, or fewer chips than the cell asks for, it exits 2 and prints no
result. Progress and the set-up split go to standard error, ending with
each number the correctness check compared, beside its limit; the last
line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
REQUIRED_PLATFORM = "tpu"


def use_checkout_imports() -> None:
    """Import the benchmark as the package ``bench`` and the program from
    ``src``; this script's own directory leaves the path, so that no
    module of the benchmark shadows another of the same name."""
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != HERE]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def find(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = find(bench["workloads"], workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)

    # The persistent compile cache lives in the checkout, at a fixed path.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    use_checkout_imports()
    try:
        import jax

        from repro.util import enable_compile_cache
    except ImportError as err:
        print(f"run.py: cannot import the clustering package: {err}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        print(f"run.py: needs a {REQUIRED_PLATFORM} device; JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from bench import harness

    try:
        out = harness.run(cell, config, traffic, bench, args.seed,
                          args.seconds, bool(args.trace), T0)
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in out["checks"].items():
        print(f"[check] {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
