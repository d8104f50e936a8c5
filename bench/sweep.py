#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate the engine sustains.

    python3 bench/sweep.py --workload <cell> --rates 3,4,5,6 --seconds 30

One process: the cell's engine is built and warmed once; each rate then
gets a ramp and a window of its own, and the engine is drained between
rates. Per rate it prints the time-to-first-token and queue-wait tails,
tokens/s, and whether the queue kept growing: the wait of requests due in
the window's second half against the first's. A rate is sustained while
the second half waits no longer than the first and the p95 queue wait stays
under ``--max-wait`` seconds. The cell's traffic file then takes 0.8 x the
knee as its rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as RUN  # sets up sys.path for the program and the benchmark

import jax  # noqa: E402
import numpy as np  # noqa: E402

import model as MODEL  # noqa: E402
import serve as SV  # noqa: E402
import spec as SPEC  # noqa: E402
import traffic as TR  # noqa: E402
import weights as WT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-wait", type=float, default=1.0)
    args = ap.parse_args(argv)
    from repro.serving import Engine

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    RUN.compile_cache()
    cell = SPEC.load_cell(args.workload)
    conf = cell.config
    w = MODEL.widths(conf)
    params = WT.make_params(w, conf["torch_dtype"],
                            TR.seed32(args.seed, TR.WEIGHTS))
    eng = Engine(RUN.engine_config(cell.traffic),
                 cfg=MODEL.program_config(conf), params=params)
    del params
    SV.warm(eng, cell.traffic)
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic, rate_per_s=rate, loop="open")
        reqs = TR.generate(traffic, args.seed, w["V"],
                           traffic["ramp_seconds"] + args.seconds)
        t = time.perf_counter()
        ld = SV.drive(eng, reqs, traffic, args.seconds)
        due = [x for x in ld.tracks if ld.w0 <= x.due < ld.w1]
        mid = (ld.w0 + ld.w1) / 2
        wait = [x.t_first_call - x.due for x in due]
        half = [np.median([x.t_first_call - x.due for x in due
                           if (x.due >= mid) == h] or [0.0])
                for h in (False, True)]
        out = sum(b.tokens_out + sum(b.admitted) for b in ld.blocks
                  if ld.w0 < b.t_ret <= ld.w1) / args.seconds
        row = {"rate": rate, "requests": len(due),
               "ttft_p95_ms": 1e3 * float(np.percentile(
                   [x.t_first - x.due for x in due], 95)),
               "queue_wait_p95_ms": 1e3 * float(np.percentile(wait, 95)),
               "wait_median_first_half_ms": 1e3 * half[0],
               "wait_median_second_half_ms": 1e3 * half[1],
               "tok_per_s": out,
               "sustained": bool(half[1] <= max(half[0], 0.05) * 1.5
                                 and np.percentile(wait, 95)
                                 < args.max_wait)}
        print(json.dumps(row), flush=True)
        while not eng.idle:
            eng.step_block(now=time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
