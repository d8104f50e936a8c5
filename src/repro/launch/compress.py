"""MergeMoE compression driver: train-or-load -> calibrate -> plan -> merge
-> eval -> (optionally) persist a loadable artifact.

    # legacy uniform surface
    PYTHONPATH=src python -m repro.launch.compress --arch qwen3-moe-30b-a3b \
        --method mergemoe --merged-experts 4 --eval-batches 4

    # declarative plan from disk
    PYTHONPATH=src python -m repro.launch.compress --plan plan.json

    # budget-driven: allocate per-layer M from calibration stats
    PYTHONPATH=src python -m repro.launch.compress --target-ratio 1.4 \
        --save-dir /tmp/qwen3_c      # artifact for Engine.from_checkpoint

Reports the paper's headline quantities: bytes before/after, per-method
held-out loss, merge wall-time (Fig. 3 analogue), plus the executed per-layer
plan and eval wall-time.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs
from repro.core import calibration as CAL
from repro.core import compress as CMP
from repro.core import plan as PLAN
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD

# ONE jitted loss fn for every evaluation in this process: the config rides
# as a static argument, so calling it with the base and the compressed model
# reuses the same callable (each distinct cfg traces once, instead of the old
# eval_loss re-jitting from scratch on every call).
_EVAL_LOSS = jax.jit(lambda cfg, p, b: MD.loss(cfg, p, b)[0],
                     static_argnums=0)


def eval_loss(cfg, params, batches) -> float:
    losses = [float(_EVAL_LOSS(cfg, params, b)) for b in batches]
    return float(np.mean(losses))


def make_batches(cfg, n, batch=4, seq=64, seed=0):
    out = []
    for i in range(n):
        key = jax.random.PRNGKey(seed + i)
        out.append({"tokens": jax.random.randint(
            key, (batch, seq), 0, cfg.vocab_size)})
    return out


def build_plan(cfg, *, plan_path=None, target_ratio=None, method="mergemoe",
               merged_experts=4, split=None, stream=None,
               weight_dtype="bf16"):
    """Resolve the CLI's three plan sources, most declarative first.
    ``weight_dtype`` applies to the built plan (a plan file keeps its own)."""
    if plan_path:
        return PLAN.CompressionPlan.load(plan_path).validate(cfg)
    if target_ratio:
        stats = stream.stats() if stream is not None else None
        return PLAN.for_target_ratio(cfg, target_ratio=target_ratio,
                                     stats=stats, method=method, split=split,
                                     weight_dtype=weight_dtype)
    return PLAN.uniform(cfg, method=method, merged_experts=merged_experts,
                        split=split, weight_dtype=weight_dtype)


def run(arch: str, method: str = "mergemoe", merged_experts: int = 4,
        split=None, calib_batches: int = 2, eval_batches: int = 4,
        params=None, cfg=None, seed: int = 0, plan=None, plan_path=None,
        target_ratio=None, max_calib_tokens=None, save_dir=None,
        mesh_spec=None, weight_dtype: str = "bf16"):
    cfg = cfg if cfg is not None else configs.get(arch).reduced()
    if params is None:
        params = MD.init(cfg, jax.random.PRNGKey(seed))
    calib = make_batches(cfg, calib_batches, seed=seed + 100)
    evalb = make_batches(cfg, eval_batches, seed=seed + 200)

    # mesh-parallel compression: DP capture over "data", solve shards over
    # "model" — bit-for-bit equal to the single-device run (DESIGN.md §6)
    mesh = None
    if mesh_spec is not None:
        from repro.launch import mesh as MESH
        mesh = MESH.make_compression_mesh(mesh_spec)

    t0 = time.perf_counter()
    base_loss = eval_loss(cfg, params, evalb)
    t_eval_base = time.perf_counter() - t0

    # calibrate ONCE: the same stream feeds the budget planner's stats and
    # the per-layer merges
    stream = CAL.CalibrationStream(cfg, params,
                                   max_tokens_per_layer=max_calib_tokens,
                                   seed=seed, mesh=mesh).consume(calib)
    if plan is None:
        plan = build_plan(cfg, plan_path=plan_path, target_ratio=target_ratio,
                          method=method, merged_experts=merged_experts,
                          split=split, stream=stream,
                          weight_dtype=weight_dtype)

    t0 = time.perf_counter()
    new_cfg, new_params, info = CMP.compress_with_plan(
        cfg, params, plan, stream=stream, mesh=mesh)
    t_total = time.perf_counter() - t0

    t0 = time.perf_counter()
    comp_loss = eval_loss(new_cfg, new_params, evalb)
    t_eval_comp = time.perf_counter() - t0

    if save_dir:
        from repro.ckpt import checkpoint as CKPT
        CKPT.save_compressed(save_dir, new_cfg, new_params,
                             plan=plan.with_mesh(mesh), report=info)

    report = {
        "arch": arch, "method": info["method"],
        "plan": info["plan"],
        "mesh": info["mesh"],
        "weight_dtype": info["weight_dtype"],
        "n_experts": info["n_experts"],
        "merged_experts": info["merged_experts"],
        "merged_per_layer": info["merged_per_layer"],
        "layers_merged": info["layers_merged"],
        "calib_tokens": info["calib_tokens"],
        "bytes_original": info["bytes_original"],
        "bytes_compressed": info["bytes_compressed"],
        "compression_ratio": round(info["compression_ratio"], 4),
        "t_merge_s": round(info["t_merge_s"], 3),
        "t_total_s": round(t_total, 3),
        "t_eval_base_s": round(t_eval_base, 3),
        "t_eval_compressed_s": round(t_eval_comp, 3),
        "t_eval_s": round(t_eval_base + t_eval_comp, 3),
        "loss_full": round(base_loss, 4),
        "loss_compressed": round(comp_loss, 4),
        "loss_delta": round(comp_loss - base_loss, 4),
    }
    if save_dir:
        report["artifact"] = str(save_dir)
    return new_cfg, new_params, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="execute a CompressionPlan from disk "
                         "(overrides --method/--merged-experts/--split)")
    ap.add_argument("--target-ratio", type=float, default=None,
                    help="budget-driven planning: allocate per-layer M from "
                         "calibration stats to hit this compression ratio")
    ap.add_argument("--method", default="mergemoe",
                    choices=PLAN.available_methods())
    ap.add_argument("--weight-dtype", default="bf16",
                    choices=PLAN.WEIGHT_DTYPES,
                    help="storage dtype for the merged expert tables: int8 "
                         "halves decode HBM traffic on top of merging "
                         "(DESIGN.md §8); ignored when --plan is given "
                         "(the plan file carries its own)")
    ap.add_argument("--merged-experts", type=int, default=4)
    ap.add_argument("--split", type=int, default=None)
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--max-calib-tokens", type=int, default=None,
                    help="calibration reservoir cap per layer (bounds host "
                         "memory; default keeps every token)")
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--save-dir", default=None,
                    help="persist the compressed artifact "
                         "(Engine.from_checkpoint loads it)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="device mesh for the compression pipeline, e.g. "
                         "'data=4' (DP capture) or 'data=2,model=2' (DP "
                         "capture + sharded solves); bit-for-bit equal to "
                         "the single-device run (DESIGN.md §6)")
    args = ap.parse_args()
    enable_compile_cache()
    _, _, report = run(args.arch, args.method, args.merged_experts,
                       split=args.split, calib_batches=args.calib_batches,
                       eval_batches=args.eval_batches, plan_path=args.plan,
                       target_ratio=args.target_ratio,
                       max_calib_tokens=args.max_calib_tokens,
                       save_dir=args.save_dir, mesh_spec=args.mesh,
                       weight_dtype=args.weight_dtype)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
