#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

On one chip: the program's decode gather kernel and prefill grouped kernel
at small shapes, inside the harness's own ``bench.*`` host spans, with a
host-side sleep between them so the trace holds an idle gap.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    E, d, f, T, k = 8, 256, 256, 16, 2
    ks = jax.random.split(jax.random.key(0), 6)
    wg = jax.random.normal(ks[0], (E, d, f), jnp.bfloat16)
    wu = jax.random.normal(ks[1], (E, d, f), jnp.bfloat16)
    wd = jax.random.normal(ks[2], (E, f, d), jnp.bfloat16)
    x = jax.random.normal(ks[3], (T, d), jnp.bfloat16)
    idx = jax.random.randint(ks[4], (T, k), 0, E, jnp.int32)
    w = jnp.full((T, k), 0.5, jnp.float32)
    gs = jnp.full((E,), 256 // E, jnp.int32)
    xg = jax.random.normal(ks[5], (256, d), jnp.bfloat16)
    dec = jax.jit(lambda *a: ops.gather_swiglu(*a))
    pre = jax.jit(lambda *a: ops.grouped_swiglu(*a))
    dec(x, wg, wu, wd, idx, w).block_until_ready()
    pre(xg, wg, wu, wd, gs).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step_block"):
            dec(x, wg, wu, wd, idx, w).block_until_ready()
            pre(xg, wg, wu, wd, gs).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, sys.argv[1])
    print(f"recorded {Path(sys.argv[1]).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
