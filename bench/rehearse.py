#!/usr/bin/env python3
"""Compile each cell's admission and decode programs for a described TPU
v5e, from shapes alone, and print what each needs beside the weights and
the KV cache. Runs on the CPU; no chip, nothing executes.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell>] [--largest]

Admission compiles one program per (prompt pad length, power-of-two group
size up to ``n_slots``); decode one fused ``decode_block``-step program.
Peak for a program = weights + KV cache in + KV cache out (the engine does
not donate it) + the program's temporaries.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import model as MODEL  # noqa: E402
import spec as SPEC  # noqa: E402
import weights as WT  # noqa: E402

GB = 1e9


def serve_cfg(cfg, n_slots):
    """The engine's dispatch override (``Engine._serve_dispatch``)."""
    import dataclasses
    moe = dataclasses.replace(cfg.moe, dispatch="gather",
                              gather_max_tokens=max(cfg.moe.gather_max_tokens,
                                                    n_slots))
    return cfg.replace(moe=moe)


def rehearse(cell: SPEC.Cell, largest_only: bool, one_chip) -> dict:
    from repro.kernels import ops
    from repro.launch import steps as ST
    from repro.models import model as MD

    ops._on_tpu = lambda: True           # compile the Pallas kernels
    e = cell.traffic["engine"]
    n, s_max = int(e["n_slots"]), int(e["s_max"])
    conf = cell.config
    w = MODEL.widths(conf)
    cfg = serve_cfg(MODEL.program_config(conf), n)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda: WT.make_params(
        w, conf["torch_dtype"], 0)))
    cache = sds(jax.eval_shape(lambda: MD.init_slot_cache(cfg, n, s_max)))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                           for a in jax.tree.leaves(t))
    p_b, c_b = nbytes(params), nbytes(cache)
    shapes = ST.admit_pad_shapes(e["prefill_buckets"], s_max)
    lo = int(cell.traffic["prompt"]["min"])
    hi = int(cell.traffic["prompt"]["max"])
    pads = sorted({next(b for b in shapes if m <= b)
                   for m in range(lo, hi + 1)})
    groups = sorted({min(1 << i, 1 << (n - 1).bit_length())
                     for i in range(n.bit_length() + 1)})
    if largest_only:
        groups = groups[-1:]
    out = {"cell": cell.name, "params_gb": p_b / GB, "kv_gb": c_b / GB,
           "programs": []}

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)

    def record(name, compiled):
        ma = compiled.memory_analysis()
        temp = ma.temp_size_in_bytes
        peak = p_b + 2 * c_b + temp
        row = {"program": name, "temp_gb": temp / GB, "peak_gb": peak / GB}
        out["programs"].append(row)
        print(json.dumps(row), flush=True)

    admit = jax.jit(ST.make_slot_admit(cfg))
    for pad in pads:
        for g in groups:
            record(f"admit pad {pad} x group {g}", admit.lower(
                params, cache, i32(g, pad), i32(g), i32(g)).compile())
    K = int(e["decode_block"])
    multi = jax.jit(ST.make_slot_decode_multi(cfg, K, 0.0))
    b = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=one_chip)
    record(f"decode {K} steps x {n} slots", multi.lower(
        params, cache, i32(n), b, i32(n), i32(n), keys, b).compile())
    out["peak_gb"] = max(r["peak_gb"] for r in out["programs"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--largest", action="store_true",
                    help="only the largest group size of each pad length")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = SPEC.load_json(SPEC.ROOT / "BENCHMARK.json")
    names = args.workload or [c["name"] for c in bench["workloads"]]
    for name in names:
        r = rehearse(SPEC.load_cell(name), args.largest, one_chip)
        print(json.dumps({k: v for k, v in r.items() if k != "programs"}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
