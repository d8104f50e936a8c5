"""End-to-end training driver.

CPU-runnable (reduced configs) and production-shaped: sharded step, data
pipeline with checkpointable cursor, atomic keep-N checkpoints with async
save, automatic resume-from-latest, straggler monitoring, optional int8
error-feedback gradient compression, gradient accumulation.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
        --reduced --steps 100 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import checkpoint as CKPT
from repro.data.pipeline import make_pipeline, DataState
from repro.distributed import StragglerMonitor, ef_compressed
from repro.launch import sharding as SH
from repro.launch import steps as ST
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as MD
from repro.models.numerics import set_activation_mesh
from repro.optim import make_optimizer, default_optimizer_for


@dataclasses.dataclass
class TrainConfig:
    arch: str = "qwen3-moe-30b-a3b"
    reduced: bool = True
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    lr: float = 3e-4
    optimizer: str = ""                # "" -> size-based default
    microbatches: int = 1
    ckpt_dir: str = ""
    ckpt_every: int = 50
    ckpt_keep: int = 3
    async_ckpt: bool = True
    grad_compression: bool = False
    seed: int = 0
    data_dir: str = ""
    log_every: int = 10
    mesh_shape: str = ""               # e.g. "2,4"; "" -> (n_devices, 1)


def build(tc: TrainConfig):
    cfg = configs.get(tc.arch)
    if tc.reduced:
        cfg = cfg.reduced()
    if tc.mesh_shape:
        shape = tuple(int(x) for x in tc.mesh_shape.split(","))
        mesh = make_host_mesh(shape)
    else:
        mesh = make_host_mesh()
    set_activation_mesh(mesh)

    opt_name = tc.optimizer or default_optimizer_for(cfg.param_count())
    opt = make_optimizer(opt_name, lr=tc.lr)
    if tc.grad_compression:
        opt = ef_compressed(opt)

    params = MD.init(cfg, jax.random.PRNGKey(tc.seed))
    opt_state = opt.init(params)

    p_sh = SH.named(SH.params_pspecs(params, mesh), mesh)
    o_sh = SH.named(SH.opt_pspecs(opt_state, mesh), mesh)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)

    step_fn = jax.jit(
        ST.make_train_step(cfg, opt, microbatches=tc.microbatches),
        in_shardings=(p_sh, o_sh, None, None),
        out_shardings=(p_sh, o_sh, None, None),
        donate_argnums=(0, 1))
    return cfg, mesh, params, opt_state, step_fn, (p_sh, o_sh)


def train(tc: TrainConfig):
    cfg, mesh, params, opt_state, step_fn, (p_sh, o_sh) = build(tc)
    data = make_pipeline(cfg, tc.seq_len, tc.global_batch,
                         data_dir=tc.data_dir or None, seed=tc.seed)
    start_step = 0
    mgr = None
    if tc.ckpt_dir:
        mgr = CKPT.CheckpointManager(tc.ckpt_dir, keep=tc.ckpt_keep,
                                     async_save=tc.async_ckpt)
        latest = mgr.latest_step()
        if latest is not None:
            (params, opt_state), extras = mgr.restore(
                latest, shardings=(p_sh, o_sh))
            data.restore(DataState.from_json(extras["data_state"]))
            start_step = latest
            print(f"[train] resumed from step {latest}")

    mon = StragglerMonitor()
    history = []
    for step in range(start_step, tc.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        mon.start_step()
        params, opt_state, loss, metrics = step_fn(
            params, opt_state, batch, jnp.asarray(step, jnp.int32))
        loss = float(loss)
        rep = mon.end_step()
        history.append(loss)
        if rep.should_restart:
            print(f"[train] straggler policy fired at step {step} "
                  f"(x{rep.ratio:.1f} median) — checkpoint + abort for relaunch")
            if mgr:
                mgr.save(step + 1, (params, opt_state),
                         {"data_state": data.state().to_json()})
                mgr.wait()
            return {"aborted_for_relaunch": True, "step": step,
                    "losses": history}
        if step % tc.log_every == 0 or step == tc.steps - 1:
            print(f"[train] step={step} loss={loss:.4f} "
                  f"ce={float(metrics['ce']):.4f} dt={rep.duration_s*1e3:.0f}ms",
                  flush=True)
        if mgr and (step + 1) % tc.ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state),
                     {"data_state": data.state().to_json()})
    if mgr:
        mgr.save(tc.steps, (params, opt_state),
                 {"data_state": data.state().to_json()})
        mgr.wait()
    return {"losses": history, "final_loss": history[-1] if history else None,
            "params": params, "cfg": cfg}


def main():
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            ap.add_argument(name, action="store_true" if not f.default
                            else "store_false", default=f.default)
        else:
            ap.add_argument(name, type=type(f.default), default=f.default)
    args = ap.parse_args()
    enable_compile_cache()
    tc = TrainConfig(**{f.name: getattr(args, f.name)
                        for f in dataclasses.fields(TrainConfig)})
    out = train(tc)
    print(json.dumps({k: v for k, v in out.items()
                      if k in ("final_loss", "aborted_for_relaunch", "step")}))


if __name__ == "__main__":
    main()
