"""Run one cell on a list of seeds, each run a process of its own started
as the benchmark's command is, and summarise the runs: every metric's
median and spread (the interquartile range by ``statistics.quantiles``
over the median), and the numbers the check compared.

    python3 chipbench/series.py --workload <cell> --seeds 5101-5106 \\
        --seconds 51 [--trace 1] [--control 1] [--fault graft|token] \\
        [--out DIR]

``--fault`` runs ``chipbench/tests/fault_run.py`` with that fault planted
instead of ``chipbench/run.py``.  With ``--out`` each run's standard
output and error are kept there as ``<cell>.<seed>.<kind>.out``/``.err``.
Prints one line per run and, last, a JSON summary.
"""

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def seeds(text: str) -> list:
    """``5101-5106,5110`` -> [5101, ..., 5106, 5110]."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def spread(xs: list) -> float:
    """Interquartile range over the median; nan where there is none (fewer
    than two runs, or a median of 0, as of a counter that reads nothing)."""
    if len(xs) < 2:
        return float("nan")
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default="0")
    ap.add_argument("--fault", choices=("graft", "token"))
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    if args.out:
        args.out.mkdir(parents=True, exist_ok=True)
    runs = []
    for seed in args.seeds:
        cmd = [sys.executable, "chipbench/run.py", "--workload",
               args.workload, "--seed", str(seed), "--seconds", args.seconds,
               "--trace", args.trace, "--control", args.control]
        kind = f"t{args.trace}"
        if args.control != "0":
            kind = "control"
        if args.fault:
            cmd = [sys.executable, "chipbench/tests/fault_run.py",
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", args.seconds, "--fault", args.fault]
            kind = f"fault-{args.fault}"
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        if args.out:
            stem = f"{args.workload}.{seed}.{kind}"
            (args.out / f"{stem}.out").write_text(p.stdout)
            (args.out / f"{stem}.err").write_text(p.stderr)
        lines = p.stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1])
        except (IndexError, json.JSONDecodeError):
            res = None
        row = {"seed": seed, "rc": p.returncode}
        if res is None:
            row["stderr_tail"] = p.stderr[-1500:]
        else:
            row["correct"] = res["correct"]
            row["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
            row["checks"] = {k: v["value"] for k, v in res["checks"].items()}
            row["memory_peak_bytes"] = res["device"]["memory_peak_bytes"]
            for k in ("busy_s", "window_s"):
                if k in res["device"]:
                    row[k] = res["device"][k]
            row["log"] = [ln for ln in lines[:-1]
                          if "after start" in ln or ln.startswith(
                              ("set-up", "pre-roll", "compiles inside",
                               "check:", "drain", "queue"))]
        runs.append(row)
        print(json.dumps(row), flush=True)
    names = sorted({k for r in runs for k in r.get("metrics", {})})
    summary = {}
    for name in names:
        xs = [r["metrics"][name] for r in runs if name in r.get("metrics", {})]
        summary[name] = {"median": statistics.median(xs),
                         "min": min(xs), "max": max(xs),
                         "spread": spread(xs), "n": len(xs)}
    print(json.dumps({"workload": args.workload, "kind": kind,
                      "correct": [r.get("correct") for r in runs],
                      "summary": summary}))


if __name__ == "__main__":
    main()
