#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, program and control on the
same prompts and tokens, in one process.

    python3 bench/control.py --workload <cell> --seconds 25 \\
        --seeds 6001,6002,...,6012

Each seed runs the cell as ``run.py`` does (a short window, the same load,
the same comparison) and compares its served requests twice: with the
float32 reference (the program's reading) and with the control, the same
reference computed in int8 wherever the program rounds to bf16
(``reference.py``, ``int8=True``), reading at each position the gap of the
token the control puts first. One JSON line per seed, then a last line with
the lower reading (the largest program reading), the upper one (the
smallest control reading) and their ratio; a limit goes between them.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as RUN  # sets up sys.path for the program and the benchmark

import jax  # noqa: E402

import peaks as PK  # noqa: E402
import spec as SPEC  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dump", default=None,
                    help="append each seed's per-position gaps, program and "
                         "control, to this JSON-lines file")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    RUN.compile_cache()
    cell = SPEC.load_cell(args.workload)
    pk = PK.peaks(devices[0].device_kind)
    program, control = {}, {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        out = RUN.run_cell(cell, seed, args.seconds, False,
                           device=devices[0], n_devices=len(devices),
                           peaks=pk, control=True)
        ctl = out.get("control") or {}
        print(json.dumps({"seed": seed, "attempted": out["attempted"],
                          "failed": out["failed"], "check": out["check"],
                          "control": ctl, "metrics": out["metrics"]}),
              flush=True)
        for name, c in out["check"].items():
            if c["value"] is not None and ctl.get(name) is not None:
                program.setdefault(name, []).append(c["value"])
                control.setdefault(name, []).append(ctl[name])
        gaps = out.get("gaps")
        if args.dump and gaps:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"seed": seed, **{
                    k: v.tolist() for k, v in gaps.items()}}) + "\n")
    print(json.dumps({name: {"lower": max(program[name]),
                             "upper": min(control[name]),
                             "ratio": min(control[name]) / max(program[name]),
                             "seeds": len(program[name])}
                      for name in program}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
