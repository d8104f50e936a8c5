#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``): imports, the seeded weights drawn on the
device in one jitted call, the engine, and every admission and decode
program the cell's traffic reaches, run once through the engine's public
entry. The window then offers the cell's traffic for ``--seconds`` after a
ramp; with ``--trace 1`` the profiler records its last part. After the
window: peak device memory is read, the program's state is freed, and a
sample of served requests is compared with the float32 reference. The last
line of standard output is one JSON object; the numbers compared, each with
its limit, are the last lines of standard error and the last key of the
line. Exits 2, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import correct as CK  # noqa: E402
import model as MODEL  # noqa: E402
import peaks as PK  # noqa: E402
import serve as SV  # noqa: E402
import spec as SPEC  # noqa: E402
import traffic as TR  # noqa: E402
import weights as WT  # noqa: E402
import window as W  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileCount:
    """Backend compiles (and compile-cache loads) while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == COMPILE_EVENT:
            self.n += 1


def compile_cache() -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``.jax_cache/`` in the checkout (a fixed path: it is part of
    the cache's key). Every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def engine_config(traffic: dict):
    from repro.serving import EngineConfig
    e = traffic["engine"]
    return EngineConfig(reduced=False, n_slots=int(e["n_slots"]),
                        s_max=int(e["s_max"]),
                        prefill_buckets=tuple(e["prefill_buckets"]),
                        decode_block=int(e["decode_block"]), clock="wall")


def run_cell(cell: SPEC.Cell, seed: int, seconds: float, trace: bool, *,
             device, n_devices: int, peaks: dict, engine_hook=None,
             keep_trace: str | None = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``engine_hook`` wraps the engine (tests plant faults through it);
    ``control`` adds, under ``"control"``, the numbers the int8 control
    (``reference.py``) reads on the same prompts and tokens, and under
    ``"gaps"`` both sides' gap at every served position."""
    from repro.serving import Engine

    counter = CompileCount()
    conf, traffic = cell.config, cell.traffic
    widths = MODEL.widths(conf)
    cfg = MODEL.program_config(conf)
    wseed = TR.seed32(seed, TR.WEIGHTS)
    spec = WT.spec_of(widths, conf["torch_dtype"])
    params = WT.make_params(widths, conf["torch_dtype"], wseed)
    eng = Engine(engine_config(traffic), cfg=cfg, params=params)
    del params
    if engine_hook is not None:
        eng = engine_hook(eng)
    warm_calls = SV.warm(eng, traffic)
    jax.block_until_ready(eng.cache)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ({warm_calls} warm-up calls)")

    reqs = TR.generate(traffic, seed, widths["V"],
                       float(traffic["ramp_seconds"]) + seconds)
    tdir = None
    if trace:
        tdir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
    counter.on = True
    load = SV.drive(eng, reqs, traffic, seconds, trace_dir=tdir)
    counter.on = False
    late = np.asarray(load.lateness) * 1e3
    log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
        f"p95 {np.percentile(late, 95):.3f} max {late.max():.3f} "
        f"over {late.size} submissions")
    stats = device.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    reduced = None
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce(tdir)
        log(f"trace: {sum(b.traced for b in load.blocks)} calls traced, "
            f"{reduced.lost_calls()} with no device op")
        if keep_trace is None:
            shutil.rmtree(tdir, ignore_errors=True)

    # correctness: free the program's state, then the reference
    check_cfg = traffic["check"]
    picked = CK.sample(load.tracks, int(check_cfg["requests"]), seed)
    del eng
    gc.collect()
    t = time.perf_counter()
    ref_params = WT.make_params(widths, conf["torch_dtype"], wseed)
    found = (CK.gap_summary(ref_params, spec, picked, check_cfg)
             if picked else {"max_gap": None, "gaps": None})
    ctl = (CK.gap_summary(ref_params, spec, picked, check_cfg,
                          control=True) if control and picked else None)
    del ref_params
    found["reference_s"] = time.perf_counter() - t
    gaps = found.pop("gaps")
    log(f"reference: {json.dumps(found)}")

    due = W.due_in_window(load)
    failed = sum(1 for tr in due if tr.t_first is None
                 or (tr.done and tr.status != "ok"))
    verdicts = CK.verdict(found, cell.limits)
    correct = bool(due) and failed == 0 and all(ok for *_, ok in verdicts)

    run = W.Run(load=load, widths=widths, peaks=peaks, chips=cell.chips,
                setup_s=setup_s, compiles_in_window=counter.n, trace=reduced)
    metrics = {}
    for m in cell.metrics(trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    if ctl is not None:
        out["gaps"] = {"program": gaps, "int8_reference": ctl.pop("gaps")}
        out["control"] = ctl
    out["check"] = {name: {"value": v, "limit": lim}
                    for name, v, lim, _ in verdicts}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the trace here and keep it")
    args = ap.parse_args(argv)

    cell = SPEC.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU (JAX sees {devices[0].platform}); nothing run")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    pk = PK.peaks(devices[0].device_kind)
    log(f"compile cache: {compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   device=devices[0], n_devices=len(devices), peaks=pk,
                   keep_trace=args.keep_trace)
    for name, c in out["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
