"""Expert-parallel sharded serving: the engine-level differential harness
(DESIGN.md §13) plus the crash-recovery snapshot drill (§12).

Evidence layers:

1. DIFFERENTIAL (subprocess, ``--dist`` lane): the same request trace runs
   through every engine mode — plain / fused-block / speculative decode,
   dense and paged KV, greedy and sampled — single-device and shard_map'd
   over a forced 4-device (data=2, model=2) mesh. Token streams must be
   IDENTICAL: EP all-to-all dispatch + sharded KV is bitwise-transparent
   under the fp32 combine wire.
2. INT8 COMBINE WIRE (in-process, ``--dist`` lane): the opt-in
   ``combine_wire_dtype='int8'`` return path is tolerance-gated — top-1
   agreement with the fp32-wire logits plus a relative-error bound.
3. FAIL-FAST VALIDATION (in-process, ``--dist`` lane): non-divisible
   expert tables and slot counts raise at construction, never mid-decode.
4. CRASH DRILL (subprocess, default lane): a periodic-snapshot engine is
   killed hard mid-trace; restoring from the snapshot directory finishes
   the trace token-for-token identical to an uninterrupted run.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

needs_devices = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs a forced 4-device host platform (scripts/test.sh --dist)")


def _child_env(devices=None):
    # JAX_PLATFORMS=cpu: without it, a container with libtpu installed
    # spends minutes retrying GCP metadata probes before falling back
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _run_ep_child(mesh=None, devices=None, modes=None):
    cmd = [sys.executable, "tests/_ep_child.py"]
    if mesh:
        cmd += ["--mesh", mesh]
    if modes:
        cmd += ["--modes", modes]
    r = subprocess.run(cmd, capture_output=True, text=True,
                       env=_child_env(devices), cwd=str(REPO), timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout)


# ---------------------------------------------------------------------------
# 1. differential: forced-mesh engine == single-device, token for token
# ---------------------------------------------------------------------------

@pytest.mark.distributed
def test_ep_engine_token_identical_to_single_device():
    """Every decode mode of the engine — step loop, fused block (greedy AND
    sampled), speculative, dense and paged KV — produces token-for-token
    identical streams on a forced (data=2, model=2) mesh vs one device."""
    single = _run_ep_child()
    assert single["devices"] == 1
    meshed = _run_ep_child(mesh="data=2,model=2", devices=4)
    assert meshed["devices"] == 4
    modes = [k for k in single if k not in ("devices", "mesh")]
    assert len(modes) == 6
    for mode in modes:
        strip = lambda rec: {k: v for k, v in rec.items() if k != "perf"}
        assert strip(meshed[mode]) == strip(single[mode]), \
            f"{mode}: EP-sharded engine diverged from single device"
        # not vacuous: every request served ok and produced tokens
        assert single[mode]["tokens_out"] > 0
        assert all(s == "ok" for s in single[mode]["statuses"].values())
        assert single[mode]["quarantined"] == 0


# ---------------------------------------------------------------------------
# in-process multi-device cases (scripts/test.sh --dist lane)
# ---------------------------------------------------------------------------

def _mesh_and_model():
    import dataclasses
    from repro import configs
    from repro.models import model as MD
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    # the serving dispatch the Engine would apply (EP engages only on the
    # gather/ragged paths; the dense einsum dispatch replicates)
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, dispatch="gather", gather_max_tokens=64))
    params = MD.init(cfg, jax.random.PRNGKey(0))
    return mesh, cfg, params


@pytest.mark.distributed
@needs_devices
def test_int8_combine_wire_top1_agreement():
    """The int8 combine wire (``compressed_psum`` of the pair-output
    table) is tolerance-gated: decode logits stay close to the fp32-wire
    logits and the greedy token agrees on (almost) every slot."""
    from repro.launch import steps as ST
    from repro.models import model as MD
    from repro.models.numerics import set_activation_mesh

    mesh, cfg, params = _mesh_and_model()
    set_activation_mesh(None)
    n_slots, s_max = 4, 32
    cache = MD.init_slot_cache(cfg, n_slots, s_max)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(n_slots, 16))
    lengths = np.full((n_slots,), 16, np.int32)
    admit = jax.jit(ST.make_slot_admit_mesh(cfg, mesh, params, cache))
    _, _, cache = admit(params, cache, tokens, lengths,
                        np.arange(n_slots, dtype=np.int32))

    tok = np.asarray(rng.integers(0, cfg.vocab_size, size=(n_slots,)),
                     np.int32)
    act = np.ones((n_slots,), bool)
    poison = np.zeros((n_slots,), bool)
    out = {}
    for wire in ("fp32", "int8"):
        dec = jax.jit(ST.make_slot_decode_mesh(cfg, mesh, params, cache,
                                               combine_wire_dtype=wire))
        logits, aux, _ = dec(params, cache, tok, act, poison)
        out[wire] = (np.asarray(logits, np.float32), np.asarray(aux))
    l32, a32 = out["fp32"]
    l8, a8 = out["int8"]
    assert not np.array_equal(l8, l32)          # the int8 wire really ran
    rel = np.abs(l8 - l32).max() / (np.abs(l32).max() + 1e-9)
    assert rel < 0.05, f"int8 combine wire rel err {rel:.4f} >= 5%"
    top1 = float((a8[:, 0] == a32[:, 0]).mean())
    assert top1 >= 0.75, f"top-1 agreement {top1:.2f} < 0.75"


@pytest.mark.distributed
@needs_devices
def test_ep_validation_fails_fast():
    """Non-divisible expert tables (E % ep != 0) and slot counts
    (n_slots % dp != 0) raise at Engine construction."""
    import dataclasses
    from repro import configs
    from repro.models import model as MD
    from repro.serving.engine import Engine, EngineConfig

    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    bad = cfg.replace(moe=dataclasses.replace(cfg.moe, n_experts=6))
    bad_params = MD.init(bad, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not divisible by the EP degree"):
        Engine(EngineConfig(n_slots=4, s_max=32, prefill_buckets=(16,),
                            mesh="data=1,model=4"),
               cfg=bad, params=bad_params)
    with pytest.raises(ValueError, match="n_slots"):
        Engine(EngineConfig(n_slots=6, s_max=32, prefill_buckets=(16,),
                            mesh="data=4,model=1"), cfg=cfg)
    with pytest.raises(ValueError, match="n_blocks"):
        Engine(EngineConfig(n_slots=4, s_max=32, prefill_buckets=(16,),
                            kv_layout="paged", kv_block=8, kv_blocks=18,
                            mesh="data=4,model=1"), cfg=cfg)


@pytest.mark.distributed
@needs_devices
def test_mesh_engine_strict_guard_across_decode_blocks():
    """Per-slot decode inputs land sharded over "data" before the guarded
    call: under the strict trace guard a mesh engine decodes several fused
    blocks without the device-to-device reshard the guard refuses."""
    from repro import configs
    from repro.serving.engine import Engine, EngineConfig

    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    eng = Engine(EngineConfig(n_slots=4, s_max=32, prefill_buckets=(16,),
                              decode_block=4, trace_guard="strict",
                              mesh="data=2,model=2"), cfg=cfg)
    rng = np.random.default_rng(5)
    for _ in range(4):
        eng.submit(rng.integers(0, cfg.vocab_size, size=16),
                   max_new_tokens=10)
    done = eng.run()
    assert [r.finish_reason for r in done] == ["length"] * 4
    assert eng.counters["implicit_transfers"] == 0


@pytest.mark.distributed
@needs_devices
def test_sharded_allocator_partitions_block_ranges():
    """With n_shards > 1 every slot's blocks stay inside its shard's block
    range, prefix chains never cross shards, and deferral is per-shard
    (one full shard defers its slot even while the other has room)."""
    from repro.serving.paging import PagedAllocator

    # slots 0,1 -> shard 0 (blocks 0..5); slots 2,3 -> shard 1 (blocks 6..11)
    a = PagedAllocator(n_slots=4, n_blocks=12, block_size=4, s_max=16,
                       n_shards=2)
    p = np.arange(12, dtype=np.int32)
    assert a.admit(0, p, 16) == 0               # 4 blocks; shard 0 has 2 left
    a.register_prefix(0, p)
    # the registered chain is invisible from the other shard's slots
    assert a.lookup_prefix(p, shard=1) == (0, ())
    # ... but same-shard slot 1 adopts it: 2 shared + 2 new = shard 0 full
    assert a.admit(1, p, 16) == 8
    assert a.stats["prefix_hits"] == 1
    for slot in (0, 1):
        assert all(a.shard_of_block(b) == 0 for b in a._owned[slot])
    a.release(1)                                # shard 0 back to 2 free blocks
    # per-shard capacity: slot 1 needs 4 blocks, shard 0 has 2, and registry
    # eviction can't help (the chain's blocks are still owned by slot 0) ->
    # DEFER, even though shard 1 could satisfy the same request right now
    q = np.arange(50, 62, dtype=np.int32)
    assert a.admit(1, q, 16) is None
    assert a.stats["deferrals"] == 1
    assert a.admit(3, q, 16) == 0               # same request, shard 1: fine
    assert all(a.shard_of_block(b) == 1 for b in a._owned[3])
    a.check_invariants()
    got = a.state_dict()
    b = PagedAllocator(n_slots=4, n_blocks=12, block_size=4, s_max=16,
                       n_shards=2)
    b.load_state(got)
    b.check_invariants()
    assert b.state_dict() == got


# ---------------------------------------------------------------------------
# 4. crash-recovery drill (default lane)
# ---------------------------------------------------------------------------

def test_crash_recovery_drill_token_identical(tmp_path):
    """Kill a periodic-snapshot engine hard mid-trace (os._exit), restore
    from the snapshot directory, finish the trace: the union of pre-crash
    and post-restore token streams equals an uninterrupted run's,
    token-for-token (DESIGN.md §12)."""
    from repro import configs
    from repro.serving.engine import Engine, EngineConfig
    sys.path.insert(0, str(REPO / "tests"))
    from _ep_child import build_trace

    snap_dir = tmp_path / "snaps"
    r = subprocess.run(
        [sys.executable, "tests/_snapshot_drill_child.py",
         "--snapshot-dir", str(snap_dir), "--kill-after-steps", "12"],
        capture_output=True, text=True, env=_child_env(), cwd=str(REPO),
        timeout=900)
    assert r.returncode == 17, \
        f"drill child should die with exit 17, got {r.returncode}: " \
        f"{r.stdout + r.stderr}"
    pre_crash = [json.loads(line) for line in r.stdout.splitlines() if line]

    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    ec = EngineConfig(n_slots=4, s_max=64, prefill_buckets=(16, 32), seed=0,
                      decode_block=4, kv_layout="paged", kv_block=8)
    trace = build_trace(cfg)

    # the uninterrupted reference run (same seeded default params)
    ref_eng = Engine(ec, cfg=cfg)
    for t in trace:
        ref_eng.submit(t["prompt"], t["max_new_tokens"],
                       arrival_time=t["arrival_time"])
    ref = {r_.uid: [int(t) for t in r_.out_tokens] for r_ in ref_eng.run()}

    # restore from the last committed periodic snapshot and finish
    eng = Engine.restore(str(snap_dir), cfg=cfg)
    assert eng.steps > 0 and eng.steps <= 12    # resumed mid-trace
    done = eng.run()
    post = {r_.uid: [int(t) for t in r_.out_tokens] for r_ in done}

    for rec in pre_crash:
        assert rec["tokens"] == ref[rec["uid"]], \
            f"uid {rec['uid']}: pre-crash stream diverged"
    for uid, toks in post.items():
        assert toks == ref[uid], f"uid {uid}: post-restore stream diverged"
    assert set(post) | {rec["uid"] for rec in pre_crash} == set(ref), \
        "some requests were lost across the crash"
    assert len(post) > 0                        # the restore really resumed


def test_mesh_engine_builds_seeded_params_sharded():
    """A mesh engine given no params builds them through the init program's
    out-shardings (so no device first holds the whole model), and a
    mesh-free engine handed the same arrays serves the same tokens."""
    from jax.sharding import NamedSharding
    from repro import configs
    from repro.serving.engine import Engine, EngineConfig

    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    ec = dict(n_slots=2, s_max=32, prefill_buckets=(16,))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=16) for _ in range(2)]

    def serve(eng):
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        return [list(r.out_tokens) for r in sorted(eng.run(),
                                                   key=lambda r: r.uid)]

    mesh_eng = Engine(EngineConfig(**ec, mesh="data=1,model=1"), cfg=cfg)
    tables = mesh_eng.params["stack"]["moe"]["wg"]
    assert isinstance(tables.sharding, NamedSharding)
    assert tables.sharding.spec[1] == "model"       # [L, E, d, f]: E on model
    got = serve(mesh_eng)
    ref = serve(Engine(EngineConfig(**ec), cfg=cfg, params=mesh_eng.params))
    assert got == ref
