"""Run one benchmark cell once and print its result as the last line of
standard output:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--control 1]

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1``
runs the same window under the profiler and reports its per-layer
metrics. ``--control 1`` puts the control's answers in the program's
place in the comparison (see ``bench/check.py``): such a run has to
print ``correct`` false. The run needs the cell's chips: on a machine
without a TPU, or with fewer chips, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not here",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import check, harness

    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS,
                                  control=bool(args.control))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        limit = f" (limit {c['limit']})" if "limit" in c else ""
        print(f"check {name} = {c['value']}{limit}", file=sys.stderr)
    print(f"check correct = {str(result['correct']).lower()} "
          f"(limits: {', '.join(check.LIMITS)})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
