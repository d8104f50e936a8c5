#!/usr/bin/env python3
"""Measure a cell's spread: sets of runs of ``run.py``, each a process of
its own, one after the other, then the spread of every metric.

    python3 bench/sets.py --workload <cell> --seeds 9001,...,9006 \\
        --sets 2 --seconds 51 [--traced 9101,9102,9103] --out <dir>

Every set runs the same seeds in the same order; ``--traced`` seeds then
run once each with ``--trace 1``. Each run's standard output and error go
to ``<dir>/<set>_<seed>.out`` and ``.err``. The last line printed is a JSON
summary: per set and metric the median and the spread, the distance
between the first and third quartile (``statistics.quantiles(n=4)``) over
the median, and whether every run was correct. A run that exits with
another code than 0 stops the rest.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

RUN = str(Path(__file__).resolve().parent / "run.py")


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one(workload, seed, seconds, trace, out: Path, tag: str) -> dict:
    cmd = [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(int(trace))]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=1500)
    (out / f"{tag}_{seed}.out").write_text(p.stdout)
    (out / f"{tag}_{seed}.err").write_text(p.stderr)
    if p.returncode != 0:
        sys.exit(f"{tag} seed {seed}: exit {p.returncode}; stopped\n"
                 + p.stderr[-2000:])
    res = json.loads(p.stdout.strip().splitlines()[-1])
    print(json.dumps({"set": tag, "seed": seed, "rc": p.returncode,
                      "correct": res["correct"],
                      "metrics": {k: v["value"] for k, v in
                                  res["metrics"].items()},
                      "check": res.get("check")}), flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    summary = {"correct": True, "sets": []}
    for i in range(args.sets):
        runs = [one(args.workload, s, args.seconds, False, out, f"s{i + 1}")
                for s in seeds]
        names = sorted({k for r in runs for k in r["metrics"]})
        stats = {}
        for k in names:
            vals = [r["metrics"][k]["value"] for r in runs
                    if k in r["metrics"]]
            stats[k] = {"median": statistics.median(vals),
                        "spread": spread(vals) if len(vals) > 1 else None,
                        "values": vals}
        summary["sets"].append(stats)
        summary["correct"] &= all(r["correct"] for r in runs)
    for s in [int(s) for s in args.traced.split(",") if s]:
        summary["correct"] &= one(args.workload, s, args.seconds, True, out,
                                  "t")["correct"]
    print(json.dumps(summary), flush=True)
    return 0 if summary["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
