"""Run one benchmark cell and print its result as the last line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  The run exits non-zero, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
The numbers that decide ``correct`` are printed beside their limits as
the last lines of standard error and under ``checks`` in the result.
``--control 1`` puts the fp8 control in the program's place for the
check (its first choices are compared instead of the served tokens), so
that such a run reads ``correct`` false; it serves to set limits, and
the benchmark's own runs leave it off.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import bench  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    log = lambda *a: print(*a, flush=True)  # noqa: E731
    out = bench.run(args.workload, args.seed, args.seconds,
                    bool(args.trace), control=bool(args.control),
                    t_start=T_START, log=log)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
