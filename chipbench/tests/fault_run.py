"""One run of a cell with a fault planted in the timed path, at the
cell's own size on its chips; prints the result line, whose ``correct``
should read false.

    python3 chipbench/tests/fault_run.py --workload <cell> --seed <n> \\
        --seconds <s> --fault graft|token
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1]))

import faults  # noqa: E402
from chipbench import bench  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=("graft", "token"), required=True)
    args = ap.parse_args()
    log = lambda *a: print(*a, flush=True)  # noqa: E731
    cell = bench.load_cell(args.workload)
    plant = None
    if args.fault == "token":
        plant = faults.token_altered(cell.conf["vocab_size"])

    def on_executor(ex):
        if args.fault == "graft":
            mod, name, bad = faults.graft_unchanged()
            setattr(mod, name, bad)
        else:
            plant(ex)

    out = bench.run(args.workload, args.seed, args.seconds, False,
                    cell=cell, on_executor=on_executor, t_start=T_START,
                    log=log)
    out["fault"] = args.fault
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
