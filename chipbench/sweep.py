"""Find a cell's knee: the highest arrival rate whose backlog does not grow.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --start <req/s> [--steps 8]

One process on the cell's chips: set-up once, then a pre-roll and a window
at each rate, from ``--start`` up by a factor of 1.25.  A rate holds when the
queue at the window's close is no longer than at its midpoint.  The knee
is the last rate that holds before the first that does not; the sweep
stops after two rates in a row fail.  Prints one row per rate and, last,
a JSON line with the table, the knee and ``above``, five quarters of the
lowest rate that did not hold.  A cell's rate is then fixed in
``chipbench/cells/<cell>.json``: four fifths of the knee below it, and
``above`` above it, so that the rate lies at least a quarter over
capacity wherever in its step the knee falls.
"""

import time

T_START = time.perf_counter()
FACTOR = 1.25  # between consecutive rates

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import bench, e2e  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    log = lambda *a: print(*a, flush=True)  # noqa: E731
    cell = bench.load_cell(args.workload)
    su = bench.prepare(cell, args.seed, False, t_start=T_START, log=log)
    rows, knee, fails, failed = [], None, 0, False
    for k in range(args.steps):
        rate = args.start * FACTOR ** k
        engine, win, due = bench.window(su, rate, args.seconds, args.seed,
                                        log=log)
        recs = bench.records(engine, due)
        m = e2e.end_to_end(recs, win.t_open, win.t_close)
        row = {"rate": rate, "due": len(e2e.due_in(recs, win.t_open,
                                                   win.t_close)),
               "queue_mid": win.queue_mid, "queue_end": win.queue_end,
               "holds": win.queue_end <= win.queue_mid, **m}
        rows.append(row)
        log("sweep " + json.dumps(row))
        failed |= not row["holds"]
        if not failed:
            knee = rate
        fails = 0 if row["holds"] else fails + 1
        if fails == 2:
            break
    fail = [r["rate"] for r in rows if not r["holds"]]
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "above": FACTOR * fail[0] if fail else None,
                      "rows": rows}))


if __name__ == "__main__":
    main()
