"""MoE dispatch invariants (property tests) + multi-device collective
compression (subprocess with 8 simulated devices)."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs
from repro.models import moe as MoE
from repro.models import model as MD


def _cfg(E=8, k=2, cf=2.0, G=64):
    cfg = configs.get("qwen3-moe-30b-a3b").reduced()
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, n_experts=E, top_k=k, capacity_factor=cf, group_size=G))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), E=st.sampled_from([4, 8]),
       k=st.sampled_from([1, 2]))
def test_topk_iterative_matches_lax(seed, E, k):
    probs = jax.random.uniform(jax.random.PRNGKey(seed), (6, 7, E))
    w_ref, i_ref = jax.lax.top_k(probs, k)
    w, i = MoE._topk_iterative(probs, k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_dispatch_combine_weights_sum_to_topk_weights():
    """Every undropped token's combine weights equal its top-k routing
    weights; dropped tokens contribute zero (never NaN)."""
    cfg = _cfg(cf=8.0)   # big capacity: nothing dropped
    m = cfg.moe
    G, E = 32, m.n_experts
    key = jax.random.PRNGKey(0)
    w = jax.nn.softmax(jax.random.normal(key, (G, m.top_k)), axis=-1)
    idx = jax.random.randint(key, (G, m.top_k), 0, E)
    C = MoE._capacity(m, G, E)
    combine, dispatch = MoE._dispatch_tensors(cfg, w, idx, E, C)
    per_token = np.asarray(jnp.sum(combine, axis=(1, 2)))
    np.testing.assert_allclose(per_token, np.asarray(jnp.sum(w, -1)),
                               atol=1e-5)
    assert bool(jnp.all(jnp.sum(dispatch, axis=(1, 2)) <= m.top_k))


def test_capacity_drops_are_deterministic_prefix():
    """With capacity 4, only the first 4 tokens routed to an expert keep
    their slots (GShard prefix semantics)."""
    cfg = _cfg(E=2, k=1, cf=0.25, G=32)   # tiny capacity
    m = cfg.moe
    G = 32
    w = jnp.ones((G, 1))
    idx = jnp.zeros((G, 1), jnp.int32)    # everyone wants expert 0
    C = MoE._capacity(m, G, 2)
    combine, _ = MoE._dispatch_tensors(cfg, w, idx, 2, C)
    kept = np.asarray(jnp.sum(combine, axis=(1, 2)))
    assert kept[:C].sum() == C and kept[C:].sum() == 0


def test_remap_duplicates_sum_weights():
    """After compression, two selected originals mapping to the same merged
    expert contribute additively (matrix A acting on routing weights)."""
    cfg = _cfg(E=4, k=2, cf=8.0)
    params = MoE.moe_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.bfloat16)
    # all originals -> one real expert
    p1 = dict(params, remap=jnp.zeros(4, jnp.int32))
    y1 = MoE.moe_apply(cfg, p1, x).y
    # reference: that expert applied with weight 1 (softmax weights sum to 1)
    from repro.kernels import ref
    xe = x.reshape(-1, cfg.d_model)
    e0 = ref.swiglu_mlp(xe, p1["wg"][0], p1["wu"][0], p1["wd"][0])
    np.testing.assert_allclose(
        np.asarray(y1.reshape(-1, cfg.d_model), np.float32),
        np.asarray(e0, np.float32), atol=2.0, rtol=0.02)  # bf16 precision


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), bad=st.integers(0, 7))
def test_route_fail_closed_drops_corrupted_remap_targets(seed, bad):
    """route() masking must FAIL CLOSED: an original expert whose remap
    lands at or beyond ``live`` (possible only through corruption — valid
    remaps stay below live by construction) can never win top-k, so tokens
    are never dispatched into zero-filled pad rows. The no-op direction
    (valid remap => mask changes nothing) is covered by
    test_plan.py::test_router_logit_mask_is_noop_for_valid_remap; this is
    the DROP direction."""
    cfg = _cfg(E=8, k=2)
    key = jax.random.PRNGKey(seed)
    p = MoE.moe_init(cfg, key, n_real=4)            # M=4 physical rows
    live = 3
    remap = np.array(jax.random.randint(key, (8,), 0, live), np.int32)
    remap[bad] = live                               # corrupted: pad row
    p = dict(p, remap=jnp.asarray(remap),
             live=jnp.asarray(live, jnp.int32))
    # router biased hard toward the corrupted expert so unmasked routing
    # WOULD pick it for every token — the mask must divert all of them
    router = np.zeros((cfg.d_model, 8), np.float32)
    router[:, bad] = 10.0
    p["router"] = jnp.asarray(router)
    x = jax.random.normal(key, (2, 9, cfg.d_model), jnp.float32)

    w, idx, probs = MoE.route(cfg, p, x)
    chosen_remap = np.asarray(jnp.take(p["remap"], idx))
    assert (chosen_remap < live).all(), \
        "masked routing dispatched a token to a pad row"
    assert not np.isin(np.asarray(idx), bad).any()
    assert np.asarray(probs)[..., bad].max() == 0.0   # -inf before softmax
    # weights stay a valid renormalized top-k distribution
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)

    # non-vacuous: without the live mask the corrupted expert DOES win top-k
    # for the tokens whose projection onto its router column is positive
    stripped = {k_: v for k_, v in p.items() if k_ != "live"}
    _, idx_unmasked, _ = MoE.route(cfg, stripped, x)
    assert np.isin(np.asarray(idx_unmasked), bad).any(), \
        "test setup failed to make the corrupted expert attractive"

    # and the full forward stays finite with the corrupted remap in place
    params = MD.init(cfg.compressed(4, 1), jax.random.PRNGKey(0))
    moe_c = dict(params["stack_c"]["moe"])
    lr = np.array(moe_c["remap"])
    lr[:, bad] = 4                                  # >= live on every layer
    moe_c["remap"] = jnp.asarray(lr)
    params["stack_c"] = dict(params["stack_c"], moe=moe_c)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}
    logits, _, _ = MD.forward(cfg.compressed(4, 1), params, batch)
    assert np.isfinite(np.asarray(logits)).all()


def _apply_with(cfg, p, x, dispatch, **kw):
    c = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatch=dispatch))
    return MoE.moe_apply(c, p, x, **kw).y


def test_gather_ragged_dense_parity_uniform():
    """Decode-sized token counts: gather == ragged bitwise (identical
    per-row arithmetic + fp32 combine), both == dense within bf16 tolerance
    (dense combines through the capacity einsum)."""
    cfg = _cfg(E=8, k=2, cf=8.0)     # capacity headroom: dense drops nothing
    p = MoE.moe_init(cfg, jax.random.PRNGKey(0))
    # decode shape: [n_slots, 1, d] — one token per slot sequence
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model),
                          jnp.bfloat16)
    assert 4 <= cfg.moe.gather_max_tokens
    y_g = _apply_with(cfg, p, x, "gather", need_aux=False)
    y_r = _apply_with(cfg, p, x, "ragged", need_aux=False)
    y_d = _apply_with(cfg, p, x, "dense", need_aux=False)
    np.testing.assert_array_equal(np.asarray(y_g, np.float32),
                                  np.asarray(y_r, np.float32))
    # dense combines through bf16 dispatch/combine einsums -> looser tol
    np.testing.assert_allclose(np.asarray(y_g, np.float32),
                               np.asarray(y_d, np.float32),
                               atol=0.1, rtol=0.05)


def test_gather_ragged_dense_parity_hetero_live_masked():
    """Heterogeneous compressed layer: padded tables (live < n_real), valid
    remap onto the live rows — all three dispatches agree and never touch
    the zero pad rows."""
    cfg = _cfg(E=8, k=2, cf=8.0)
    key = jax.random.PRNGKey(3)
    p = MoE.moe_init(cfg, key, n_real=4)
    live = 3
    p = dict(p,
             remap=jax.random.randint(key, (8,), 0, live).astype(jnp.int32),
             live=jnp.asarray(live, jnp.int32))
    # poison the pad row: if any dispatch touched it, outputs would diverge
    p["wd"] = p["wd"].at[live:].set(1e4)
    x = jax.random.normal(key, (6, 1, cfg.d_model), jnp.bfloat16)
    y_g = _apply_with(cfg, p, x, "gather", need_aux=False)
    y_r = _apply_with(cfg, p, x, "ragged", need_aux=False)
    y_d = _apply_with(cfg, p, x, "dense", need_aux=False)
    assert np.isfinite(np.asarray(y_g, np.float32)).all()
    assert np.abs(np.asarray(y_g, np.float32)).max() < 1e3
    np.testing.assert_array_equal(np.asarray(y_g, np.float32),
                                  np.asarray(y_r, np.float32))
    # dense combines through bf16 dispatch/combine einsums -> looser tol
    np.testing.assert_allclose(np.asarray(y_g, np.float32),
                               np.asarray(y_d, np.float32),
                               atol=0.1, rtol=0.05)


def test_gather_fail_closed_corrupted_remap():
    """The corrupted-remap fail-closed contract (DESIGN.md §5) through the
    gather path: a remap entry pointing at a pad row is masked in routing,
    so the gather kernel never loads that row and the output stays finite
    even with the router biased hard toward the corrupted expert."""
    cfg = _cfg(E=8, k=2)
    key = jax.random.PRNGKey(5)
    p = MoE.moe_init(cfg, key, n_real=4)
    live, bad = 3, 6
    remap = np.array(jax.random.randint(key, (8,), 0, live), np.int32)
    remap[bad] = live                               # corrupted: pad row
    router = np.zeros((cfg.d_model, 8), np.float32)
    router[:, bad] = 10.0
    p = dict(p, remap=jnp.asarray(remap),
             live=jnp.asarray(live, jnp.int32), router=jnp.asarray(router))
    p["wd"] = p["wd"].at[live:].set(1e4)            # poisoned pad row
    x = jax.random.normal(key, (6, 1, cfg.d_model), jnp.bfloat16)
    for need_aux in (False, True):
        y = _apply_with(cfg, p, x, "gather", need_aux=need_aux)
        assert np.isfinite(np.asarray(y, np.float32)).all()
        assert np.abs(np.asarray(y, np.float32)).max() < 1e3


def test_gather_falls_back_to_ragged_outside_decode_shape(monkeypatch):
    """dispatch='gather' is a trace-time switch on static shapes: only
    decode-shaped calls (S == 1, T <= gather_max_tokens) take the gather
    kernel; prefill-shaped calls (S > 1) and over-ceiling decode batches
    run the sort-based grouped path and never invoke it."""
    import repro.kernels.ops as kops
    calls = []
    real = kops.gather_swiglu
    monkeypatch.setattr(kops, "gather_swiglu",
                        lambda *a, **k: (calls.append(a[0].shape), real(*a, **k))[1])
    cfg = _cfg(E=8, k=2)
    p = MoE.moe_init(cfg, jax.random.PRNGKey(0))
    decode = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model),
                               jnp.bfloat16)
    prefill = jax.random.normal(jax.random.PRNGKey(1),
                                (1, cfg.moe.gather_max_tokens, cfg.d_model),
                                jnp.bfloat16)
    wide = jax.random.normal(jax.random.PRNGKey(1),
                             (cfg.moe.gather_max_tokens + 1, 1, cfg.d_model),
                             jnp.bfloat16)
    _apply_with(cfg, p, decode, "gather", need_aux=False)
    assert calls == [(4, cfg.d_model)]
    for x in (prefill, wide):                       # gather never re-invoked
        y = _apply_with(cfg, p, x, "gather", need_aux=False)
        assert calls == [(4, cfg.d_model)]
        np.testing.assert_array_equal(
            np.asarray(y, np.float32),
            np.asarray(_apply_with(cfg, p, x, "ragged", need_aux=False),
                       np.float32))


def test_need_aux_false_matches_training_routing():
    """route_infer (top-k on logits + subset softmax) must reproduce
    route()'s renormalized weights and selection; aux comes back as a
    constant zero."""
    cfg = _cfg(E=8, k=2)
    p = MoE.moe_init(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model),
                          jnp.bfloat16)
    w_t, i_t, probs = MoE.route(cfg, p, x)
    w_i, i_i = MoE.route_infer(cfg, p, x)
    np.testing.assert_array_equal(np.asarray(i_t), np.asarray(i_i))
    np.testing.assert_allclose(np.asarray(w_t), np.asarray(w_i),
                               atol=1e-6, rtol=1e-6)
    out_t = MoE.moe_apply(cfg.replace(moe=dataclasses.replace(
        cfg.moe, dispatch="ragged")), p, x)
    out_i = MoE.moe_apply(cfg.replace(moe=dataclasses.replace(
        cfg.moe, dispatch="ragged")), p, x, need_aux=False)
    assert float(out_t.aux_loss) > 0.0
    assert float(out_i.aux_loss) == 0.0
    np.testing.assert_allclose(np.asarray(out_t.y, np.float32),
                               np.asarray(out_i.y, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_compressed_psum_multidevice():
    """int8-over-the-wire psum inside shard_map on 8 simulated devices."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compressed_psum
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 7.0

        def body(xs):
            return compressed_psum(xs[0], "data", jax.random.PRNGKey(0))[None]

        f = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                          out_specs=P("data", None), check_vma=False)
        out = f(x)
        exact = jnp.sum(x, axis=0)
        err = float(jnp.max(jnp.abs(out[0] - exact)) / jnp.max(jnp.abs(exact)))
        assert err < 0.05, err
        print("OK", err)
    """)
    # JAX_PLATFORMS=cpu: without it, a container with libtpu installed spends
    # ~8 min retrying GCP metadata probes before falling back to CPU.
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                       "HOME": "/root",
                                       "JAX_PLATFORMS": "cpu"}, cwd="/root/repo",
                       timeout=300)
    assert "OK" in r.stdout, r.stdout + r.stderr
