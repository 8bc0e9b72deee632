"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints its result object as the last line of standard output, and the
numbers that decided ``correct``, each beside its limit, as the last lines of
standard error. Without a TPU, with fewer chips than the cell asks for, or with a ``device_kind`` that
``benchmarks/peaks.json`` lacks, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.time()  # set-up is counted from here, before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks.harness import RefusedError, run_cell  # noqa: PLC0415

    try:
        result = run_cell(
            root, args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t0=_T0,
        )
    except RefusedError as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared: {name} {c['value']!r}, limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
