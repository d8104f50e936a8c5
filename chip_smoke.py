#!/usr/bin/env python3
"""Chip smoke: the MergeMoE main path on one TPU chip at qwen3-moe-30b-a3b
widths.

    python chip_smoke.py                # one chip: the default run
    python chip_smoke.py --four-chips   # 2x2 mesh paths vs their reference

One process, no subprocesses. The model is ``qwen3-moe-30b-a3b`` at its
published widths (d_model 2048, 32 q / 4 kv heads of 128, 128 experts of
width 768, top-8, vocab 151936) with seeded random bf16 weights, its depth
cut from 48 layers to 4 (every layer is the same MoE layer). The default
run:

1. checks each Pallas kernel the serving path dispatches against its
   ``kernels/ref.py`` oracle on seeded full-width inputs;
2. serves 8 seeded requests through ``Engine`` with the full model and
   asserts every request finished ``length``/``eos`` with no shedding,
   quarantine, retrace or implicit transfer; prints how many greedy tokens
   agree with ``FixedBatchServer`` (not gated);
3. compresses with MergeMoE (128 -> 64 experts on the paper's suffix
   layers), calibration on the chip and fp64 solves on the host;
4. serves the same requests with the compressed model, same assertions.

``--four-chips`` runs only the multi-chip paths and what they are compared
with, at 2 layers: the expert-parallel ``Engine(mesh="data=2,model=2")`` against the
single-device Engine (no ``mesh=``) on the same params and requests
(greedy tokens must agree), and ``compress_with_plan`` on the same mesh
against the run without one (table digests must agree).

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``. The script exits
nonzero, without that line, when JAX finds no TPU or any check fails.
Wall times printed are set-up figures (compilation included), not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import compress as CMP  # noqa: E402
from repro.core import plan as PLAN  # noqa: E402
from repro.core import quant as Q  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as MD  # noqa: E402
from repro.serving import Engine, EngineConfig  # noqa: E402

ARCH = "qwen3-moe-30b-a3b"
MESH_SPEC = "data=2,model=2"
LAYERS = 4
#: the four-chip comparison holds the sharded model, a whole copy on chip 0
#: and, while compressing on the mesh, weights replicated on every chip
FOUR_CHIP_LAYERS = 2
#: kernel vs oracle: max |kernel - oracle| / max |oracle|. bf16 operands
#: round to 2^-8 relative; a few such roundings stay well under this.
KERNEL_TOL = 2e-2
OK_FINISH = ("length", "eos")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


@dataclasses.dataclass(frozen=True)
class Shape:
    """Traffic and sizes of one smoke run."""
    n_requests: int = 8
    prompt_len: int = 128
    max_new_tokens: int = 32
    n_slots: int = 8
    s_max: int = 256
    merged_experts: int = 64
    calib_batch: int = 8
    calib_max_tokens: int = 1024


def model_config(layers: int):
    """qwen3-moe-30b-a3b at published widths, depth cut to ``layers``."""
    full = configs.get(ARCH)
    return full.replace(n_layers=layers), full.n_layers


def make_prompts(cfg, shape: Shape, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=shape.prompt_len,
                         dtype=np.int32) for _ in range(shape.n_requests)]


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------

def _kernel_inputs(cfg, shape: Shape, seed: int) -> dict:
    d, E = cfg.d_model, cfg.moe.n_experts
    f, k = cfg.moe.d_ff_expert, cfg.moe.top_k
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.param_dtype
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)

    def normal(key, shp, scale):
        return (jax.random.normal(key, shp, jnp.float32) * scale).astype(dt)

    T = shape.n_slots
    wg = normal(ks[0], (E, d, f), d ** -0.5)
    wu = normal(ks[1], (E, d, f), d ** -0.5)
    wd = normal(ks[2], (E, f, d), f ** -0.5)
    x = normal(ks[3], (T, d), 1.0)
    idx = jax.random.randint(ks[4], (T, k), 0, E, jnp.int32)
    w = jax.nn.softmax(jax.random.normal(ks[5], (T, k), jnp.float32), -1)
    # one prompt's token rows sorted by expert, as the ragged path feeds the
    # grouped kernel at prefill
    Tg = shape.prompt_len
    gs = jnp.bincount(jax.random.randint(ks[6], (Tg,), 0, E), length=E)
    xg = normal(ks[7], (Tg, d), 1.0)
    # paged decode attention over a shuffled block pool
    bs = 16
    mb = shape.s_max // bs
    nb = shape.n_slots * mb
    kp = normal(ks[8], (nb, bs, nkv, hd), 1.0)
    vp = normal(ks[9], (nb, bs, nkv, hd), 1.0)
    q = normal(ks[3], (shape.n_slots, nq, hd), 1.0)
    tab = jax.random.permutation(ks[4], nb).reshape(shape.n_slots, mb)
    lens = jax.random.randint(ks[5], (shape.n_slots,), 1, shape.s_max + 1)
    qt = Q.quantize_expert_tables(wg, wu, wd)
    (kq, ksc), (vq, vsc) = Q.quantize_kv(kp), Q.quantize_kv(vp)
    return {
        "gather_swiglu": (x, wg, wu, wd, idx, w),
        "gather_swiglu_q": (x, qt, idx, w),
        "grouped_swiglu": (xg, wg, wu, wd, gs),
        "grouped_swiglu_q": (xg, qt, gs),
        "paged_attention": (q, kp, vp, tab, lens),
        "paged_attention_q": (q, kq, vq, ksc, vsc, tab, lens),
    }


def kernels_phase(cfg, shape: Shape, seed: int) -> dict:
    """Each Pallas kernel the serving path dispatches (bf16 and int8
    variants) against its oracle. ``interpret=True`` makes ``ops`` run the
    Pallas kernel off the TPU too (interpreted); on the TPU it runs
    compiled either way."""
    errs = {}
    for name, args in _kernel_inputs(cfg, shape, seed).items():
        got = getattr(ops, name)(*args, interpret=True)
        with jax.default_matmul_precision("highest"):
            want = getattr(ref, name)(*args)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != "
              f"oracle {want.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                     1e-30))
        check(err <= KERNEL_TOL, f"{name}: error {err} > {KERNEL_TOL}")
        errs[name] = err
    return {"phase": "kernels", "tol": KERNEL_TOL, "max_rel_err": errs}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_phase(cfg, params, prompts, shape: Shape, *, name: str,
                mesh: str | None = None):
    """Serve ``prompts`` through ``Engine`` and hold it to a clean run.
    Returns (engine, phase record with the generated tokens)."""
    ec = EngineConfig(reduced=False, n_slots=shape.n_slots,
                      s_max=shape.s_max, prefill_buckets=(shape.prompt_len,),
                      trace_guard="strict", numeric_sentinel="strict",
                      mesh=mesh)
    t0 = time.perf_counter()
    eng = Engine(ec, cfg=cfg, params=params)
    for p in prompts:
        eng.submit(p, max_new_tokens=shape.max_new_tokens)
    done = sorted(eng.run(), key=lambda r: r.uid)
    wall = time.perf_counter() - t0
    check(len(done) == len(prompts),
          f"{name}: {len(done)}/{len(prompts)} requests finished")
    reasons = [r.finish_reason for r in done]
    check(all(fr in OK_FINISH for fr in reasons),
          f"{name}: finish reasons {reasons}")
    counters = {c: int(eng.counters[c]) for c in
                ("shed", "quarantined", "retraces", "implicit_transfers")}
    check(not any(counters.values()), f"{name}: counters {counters}")
    tokens = [[int(t) for t in r.out_tokens] for r in done]
    return eng, {"phase": name, "requests": len(done),
                 "finish_reasons": sorted(set(reasons)), **counters,
                 "tokens_out": int(eng.counters["tokens_out"]),
                 "wall_s_incl_compile": wall, "tokens": tokens}


def agreement(tokens, ref_tokens) -> dict:
    """Greedy tokens compared position by position. Greedy decoding
    cascades from its first differing token, so the leading run per request
    says where the two first part."""
    agree = sum(int(a == b) for row, rrow in zip(tokens, ref_tokens)
                for a, b in zip(row, rrow))
    leading = [next((i for i, (a, b) in enumerate(zip(row, rrow)) if a != b),
                    len(row)) for row, rrow in zip(tokens, ref_tokens)]
    return {"agree": agree, "of": sum(len(row) for row in tokens),
            "leading_agree": leading}


def fixed_batch_agreement(cfg, params, prompts, shape: Shape,
                          tokens) -> dict:
    """Greedy tokens of ``FixedBatchServer`` on the same prompts against
    the engine's (printed, not gated)."""
    from repro.launch.serve import FixedBatchServer, ServeConfig
    srv = FixedBatchServer(ServeConfig(
        reduced=False, batch_size=len(prompts), prompt_len=shape.prompt_len,
        max_new_tokens=shape.max_new_tokens), cfg=cfg, params=params)
    ref_toks = srv.generate(np.stack(prompts)).tolist()
    return {"phase": "fixed_batch_agreement", **agreement(tokens, ref_toks)}


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def calib_batches(cfg, shape: Shape, seed: int):
    n = -(-shape.calib_max_tokens // shape.prompt_len)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (n, shape.prompt_len), 0, cfg.vocab_size)
    return [{"tokens": toks[i:i + shape.calib_batch]}
            for i in range(0, n, shape.calib_batch)]


def compress_phase(cfg, params, shape: Shape, seed: int):
    """MergeMoE N -> ``merged_experts`` on the default suffix layers."""
    ccfg, cparams, info = CMP.compress_model(
        cfg, params, method="mergemoe", merged_experts=shape.merged_experts,
        batches=calib_batches(cfg, shape, seed),
        max_tokens=shape.calib_max_tokens, seed=seed)
    check(info["calib_tokens"] == shape.calib_max_tokens,
          f"calibration kept {info['calib_tokens']} tokens")
    check(all(m == shape.merged_experts for m in info["merged_per_layer"]),
          f"merged_per_layer {info['merged_per_layer']}")
    resid = [r for layer in info["resid"] for r in layer]
    check(bool(np.isfinite(resid).all()), "non-finite merge residuals")
    return ccfg, cparams, {
        "phase": "compress", "method": info["method"],
        "experts": [info["n_experts"], info["merged_experts"]],
        "layers_merged": info["layers_merged"],
        "calib_tokens": info["calib_tokens"],
        "compression_ratio": info["compression_ratio"],
        "max_resid": float(max(resid)),
        "setup_calibrate_s": info["t_calibrate_s"],
        "setup_solve_s": info["t_merge_s"]}


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------

def run_one_chip(cfg, shape: Shape, seed: int) -> None:
    emit(kernels_phase(cfg, shape, seed))
    params = MD.init(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(cfg, shape, seed)
    eng, full = serve_phase(cfg, params, prompts, shape, name="serve_full")
    serve_cfg = eng.cfg
    del eng
    emit({k: v for k, v in full.items() if k != "tokens"})
    emit(fixed_batch_agreement(serve_cfg, params, prompts, shape,
                               full["tokens"]))
    ccfg, cparams, rec = compress_phase(cfg, params, shape, seed)
    emit(rec)
    del params
    eng, comp = serve_phase(ccfg, cparams, prompts, shape,
                            name="serve_compressed")
    del eng
    emit({**{k: v for k, v in comp.items() if k != "tokens"},
          "vs_full": agreement(comp["tokens"], full["tokens"])})


def run_four_chips(cfg, shape: Shape, seed: int) -> None:
    from repro.ckpt.checkpoint import tree_digest
    from repro.launch.mesh import make_compression_mesh

    prompts = make_prompts(cfg, shape, seed)
    # the EP engine builds its seeded params sharded over the mesh; the
    # single-device Engine (no mesh=) serves a copy of the same arrays on
    # chip 0
    eng, ep = serve_phase(cfg, None, prompts, shape, name="serve_mesh",
                          mesh=MESH_SPEC)
    sharded = eng.params
    del eng
    one = jax.device_put(sharded, jax.devices()[0])
    eng, base = serve_phase(cfg, one, prompts, shape, name="serve_one_chip")
    del eng
    vs = agreement(ep.pop("tokens"), base.pop("tokens"))
    emit(base)
    emit({**ep, "mesh": MESH_SPEC, "vs_one_chip": vs})
    # both comparisons run before either gates, so one call reports both
    failures = [] if vs["agree"] == vs["of"] else [
        "mesh engine's greedy tokens differ from the single-device Engine's"]

    plan = PLAN.uniform(cfg, method="mergemoe",
                        merged_experts=shape.merged_experts)

    def compress(params, mesh):
        _, cparams, info = CMP.compress_with_plan(
            cfg, params, plan, batches=calib_batches(cfg, shape, seed),
            max_tokens=shape.calib_max_tokens, seed=seed, mesh=mesh)
        moe = cparams["stack_c"]["moe"]
        return {"tables": tree_digest({k: moe[k] for k in ("wg", "wu", "wd")}),
                "remap": tree_digest(moe["remap"]),
                "setup_calibrate_s": info["t_calibrate_s"],
                "setup_solve_s": info["t_merge_s"]}

    runs = {"one_chip": compress(one, None)}
    del one     # the mesh run replicates the weights on every chip
    runs["mesh"] = compress(sharded, make_compression_mesh(MESH_SPEC))
    same = all(runs["mesh"][k] == runs["one_chip"][k]
               for k in ("tables", "remap"))
    emit({"phase": "compress_mesh", "mesh": MESH_SPEC,
          "digests_equal_one_chip": same, "runs": runs})
    if not same:
        failures.append("mesh compression's tables differ from the "
                        "single-device run's")
    check(not failures, "; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh paths and their reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()

    cfg, full_layers = model_config(
        FOUR_CHIP_LAYERS if args.four_chips else LAYERS)
    emit({"phase": "config", "arch": ARCH, "layers": cfg.n_layers,
          "layers_published": full_layers, "d_model": cfg.d_model,
          "heads": [cfg.n_heads, cfg.n_kv_heads, cfg.hd],
          "experts": cfg.moe.n_experts, "top_k": cfg.moe.top_k,
          "d_ff_expert": cfg.moe.d_ff_expert, "vocab": cfg.vocab_size,
          "dtype": jnp.dtype(cfg.param_dtype).name, "seed": args.seed,
          "compile_cache": cache_dir})
    shape = Shape()
    if args.four_chips:
        run_four_chips(cfg, shape, args.seed)
    else:
        run_one_chip(cfg, shape, args.seed)
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
