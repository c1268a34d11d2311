"""FedCGD round-engine benchmark: one run of one cell.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, deployments, limits and per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/harness/spec.py``).
The run makes its data and weights from ``--seed``, builds the program's
``MultiCellTrainer``, runs the checked and warm-up rounds, measures
rounds for ``--seconds``, and with ``--trace 1`` reads the program's
phase spans and a profiler trace of a few more rounds.  Then it follows
the checked rounds with the plain reference and prints, as the last line
of stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), with the
compared numbers and their limits last under ``check``.  It exits
non-zero and prints no result where JAX finds no TPU, or fewer chips
than the cell asks for."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from harness import cell, spec
    try:
        result = cell.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except (cell.NoChip, spec.SpecError) as e:
        print(f"bench: {e}; no result", file=sys.stderr, flush=True)
        return 2
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
