"""Per-kernel interpret-mode validation against the pure-jnp oracles in
repro.kernels.ref — shape/dtype sweeps + hypothesis property tests. The
int8 sweeps assert BITWISE equality with the jnp dequant oracles
(DESIGN.md §8): the kernels keep the dequantized weights at fp32 with a
single output-side downcast, so there is no rounding XLA can cancel or
contract out from under the comparison."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import quant as Q
from repro.kernels import ref
from repro.kernels import swiglu as K_swiglu
from repro.kernels import flash_attention as K_fa
from repro.kernels import grouped_mlp as K_gm
from repro.kernels import decode_moe as K_dm

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


def _randn(shape, dtype, scale=0.5):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# fused SwiGLU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,d,f,bt,bf", [
    (32, 16, 32, 8, 8),
    (64, 32, 48, 16, 16),
    (128, 64, 64, 128, 64),   # single block each way
    (48, 24, 96, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_shapes(T, d, f, bt, bf, dtype):
    x = _randn((T, d), dtype)
    wg, wu = _randn((d, f), dtype, 0.2), _randn((d, f), dtype, 0.2)
    wd = _randn((f, d), dtype, 0.2)
    y = K_swiglu.swiglu_mlp(x, wg, wu, wd, block_t=bt, block_f=bf,
                            interpret=True)
    yr = ref.swiglu_mlp(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


@settings(max_examples=10, deadline=None)
@given(T=st.sampled_from([16, 40, 64]), d=st.sampled_from([8, 24]),
       f=st.sampled_from([16, 48]), bt=st.sampled_from([8, 16]))
def test_swiglu_property(T, d, f, bt):
    x = _randn((T, d), jnp.float32)
    wg, wu = _randn((d, f), jnp.float32, 0.2), _randn((d, f), jnp.float32, 0.2)
    wd = _randn((f, d), jnp.float32, 0.2)
    y = K_swiglu.swiglu_mlp(x, wg, wu, wd, block_t=bt, block_f=16,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        ref.swiglu_mlp(x, wg, wu, wd)), atol=1e-4, rtol=1e-4)


def test_swiglu_zero_weights_give_zero():
    x = _randn((16, 8), jnp.float32)
    z = jnp.zeros((8, 16), jnp.float32)
    zd = jnp.zeros((16, 8), jnp.float32)
    y = K_swiglu.swiglu_mlp(x, z, z, zd, block_t=8, block_f=8, interpret=True)
    assert float(jnp.abs(y).max()) == 0.0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,hd,bq,bk", [
    (1, 1, 32, 8, 8, 8),
    (2, 3, 64, 16, 16, 16),
    (1, 2, 128, 32, 64, 32),
    (2, 1, 96, 16, 32, 16),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, S, hd, bq, bk, causal, dtype):
    q, k, v = (_randn((B, H, S, hd), dtype) for _ in range(3))
    o = K_fa.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                             interpret=True)
    orf = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), **_tol(dtype))


def test_flash_cross_attention_rect():
    """Sq != Skv (non-causal cross attention)."""
    q = _randn((1, 2, 32, 16), jnp.float32)
    k = _randn((1, 2, 64, 16), jnp.float32)
    v = _randn((1, 2, 64, 16), jnp.float32)
    o = K_fa.flash_attention(q, k, v, causal=False, block_q=16, block_k=16,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref.flash_attention(q, k, v, causal=False)),
        atol=1e-5, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(S=st.sampled_from([16, 48, 80]), hd=st.sampled_from([8, 16]),
       causal=st.booleans())
def test_flash_property(S, hd, causal):
    q, k, v = (_randn((1, 2, S, hd), jnp.float32) for _ in range(3))
    o = K_fa.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref.flash_attention(q, k, v, causal=causal)),
        atol=1e-4, rtol=1e-4)


def test_flash_softmax_invariance():
    """Attention output is invariant to adding a constant to all logits —
    equivalently to scaling q by 0: output becomes mean of v rows (causal
    prefix mean). Checks the online-softmax normalizer."""
    B, H, S, hd = 1, 1, 32, 8
    q = jnp.zeros((B, H, S, hd), jnp.float32)
    k = _randn((B, H, S, hd), jnp.float32)
    v = _randn((B, H, S, hd), jnp.float32)
    o = K_fa.flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                             interpret=True)
    expect = jnp.cumsum(v[0, 0], axis=0) / jnp.arange(1, S + 1)[:, None]
    np.testing.assert_allclose(np.asarray(o[0, 0]), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# grouped (MoE) SwiGLU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [
    [10, 0, 37, 17],        # empty group
    [64],                   # single expert
    [1, 1, 1, 1, 60],       # tiny + dominant groups
    [16, 16, 16, 16],       # block-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_swiglu(sizes, dtype):
    d, f = 24, 32
    E = len(sizes)
    gs = jnp.asarray(sizes, jnp.int32)
    T = int(gs.sum())
    x = _randn((T, d), dtype)
    wg, wu = _randn((E, d, f), dtype, 0.2), _randn((E, d, f), dtype, 0.2)
    wd = _randn((E, f, d), dtype, 0.2)
    y = K_gm.grouped_swiglu(x, wg, wu, wd, gs, block_t=16, block_f=16,
                            interpret=True)
    yr = ref.grouped_swiglu(x, wg, wu, wd, gs)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=40), min_size=2,
                max_size=5).filter(lambda s: sum(s) > 0))
def test_grouped_property(sizes):
    d, f = 16, 16
    E = len(sizes)
    gs = jnp.asarray(sizes, jnp.int32)
    T = int(gs.sum())
    x = _randn((T, d), jnp.float32)
    wg, wu = _randn((E, d, f), jnp.float32, 0.2), _randn((E, d, f), jnp.float32, 0.2)
    wd = _randn((E, f, d), jnp.float32, 0.2)
    y = K_gm.grouped_swiglu(x, wg, wu, wd, gs, block_t=8, block_f=16,
                            interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.grouped_swiglu(x, wg, wu, wd, gs)),
        atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("sizes", [
    [0, 10],                # empty FIRST group (duplicate start at 0)
    [10, 0],                # empty LAST group
    [0, 0, 16],             # consecutive leading empties
    [5, 0, 0, 0],           # consecutive trailing empties
    [3, 0, 0, 3],           # empty run in the middle
    [0, 0, 0, 0, 64],       # all-but-one empty
    [40, 0, 24, 0, 16, 0, 8, 0],   # post-merge pattern: remap emptied every
                                   # absorbed expert's bucket (M = N/2)
    [0, 0, 0, 0],           # fully empty (T == 0)
])
def test_grouped_swiglu_zero_groups_regression(sizes):
    """Zero-sized expert groups — exactly the layout after aggressive
    MergeMoE merging — must neither skip nor misattribute blocks. Guards the
    block->expert mapping against duplicate entries in ``padded_starts``."""
    d, f = 24, 32
    E = len(sizes)
    gs = jnp.asarray(sizes, jnp.int32)
    T = int(gs.sum())
    x = _randn((T, d), jnp.float32)
    wg, wu = _randn((E, d, f), jnp.float32, 0.2), _randn((E, d, f), jnp.float32, 0.2)
    wd = _randn((E, f, d), jnp.float32, 0.2)
    y = K_gm.grouped_swiglu(x, wg, wu, wd, gs, block_t=16, block_f=16,
                            interpret=True)
    assert y.shape == (T, d)
    yr = ref.grouped_swiglu(x, wg, wu, wd, gs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# gather (decode-mode MoE) SwiGLU
# ---------------------------------------------------------------------------

def _gather_inputs(T, d, f, E, k, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)) * 0.5, dtype)
    wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype)
    wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype)
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, dtype)
    idx = jnp.asarray(rng.integers(0, E, (T, k)), jnp.int32)
    w = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((T, k)), jnp.float32), axis=-1)
    return x, wg, wu, wd, idx, w


@pytest.mark.parametrize("T,d,f,E,k", [
    (4, 24, 32, 8, 2),      # decode shape: n_slots tokens
    (1, 16, 16, 4, 1),      # single token, single expert
    (8, 32, 48, 8, 3),      # k > 2
    (3, 16, 32, 2, 2),      # tiny expert table
    (20, 16, 16, 8, 2),     # rows span several token tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_swiglu(T, d, f, E, k, dtype):
    x, wg, wu, wd, idx, w = _gather_inputs(T, d, f, E, k, dtype)
    y = K_dm.gather_swiglu(x, wg, wu, wd, idx, w, interpret=True)
    yr = ref.gather_swiglu(x, wg, wu, wd, idx, w)
    assert y.shape == (T, d) and y.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


def test_gather_swiglu_duplicate_expert_sums_weights():
    """A token whose top-k selects the SAME expert twice must weight that
    expert by the sum — exactly the post-merge remap situation where two
    original experts collapse onto one merged row."""
    T, d, f, E = 2, 16, 16, 4
    x, wg, wu, wd, _, _ = _gather_inputs(T, d, f, E, 2, jnp.float32)
    idx = jnp.asarray([[1, 1], [2, 0]], jnp.int32)
    w = jnp.asarray([[0.3, 0.7], [0.5, 0.5]], jnp.float32)
    y = K_dm.gather_swiglu(x, wg, wu, wd, idx, w, interpret=True)
    one = ref.swiglu_mlp(x[:1], wg[1], wu[1], wd[1])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(one[0]),
                               atol=1e-5, rtol=1e-5)


def test_gather_swiglu_matches_sorted_grouped_composition():
    """gather(x, idx, w) == the ragged pipeline (sort by expert, grouped
    kernel, weighted scatter-add) on the same routing — the moe_apply-level
    dispatch-parity contract at kernel granularity."""
    T, d, f, E, k = 6, 24, 32, 8, 2
    x, wg, wu, wd, idx, w = _gather_inputs(T, d, f, E, k, jnp.float32, seed=3)
    y = K_dm.gather_swiglu(x, wg, wu, wd, idx, w, interpret=True)

    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    tok_of = order // k
    xs = x[tok_of]
    gs = jnp.asarray(np.bincount(flat, minlength=E), jnp.int32)
    ys = K_gm.grouped_swiglu(xs, wg, wu, wd, gs, block_t=8, block_f=16,
                             interpret=True)
    wf = np.asarray(w).reshape(-1)[order]
    out = np.zeros((T, d), np.float32)
    np.add.at(out, tok_of, np.asarray(ys, np.float32) * wf[:, None])
    np.testing.assert_allclose(np.asarray(y), out, atol=1e-5, rtol=1e-5)


def test_gather_swiglu_clips_out_of_bounds_idx():
    """Corrupted expert ids must not read out of bounds (routing fails
    closed upstream; the kernel clips as defense-in-depth, same as the
    oracle)."""
    T, d, f, E, k = 2, 16, 16, 4, 2
    x, wg, wu, wd, _, w = _gather_inputs(T, d, f, E, k, jnp.float32)
    idx = jnp.asarray([[E + 3, 0], [1, -7]], jnp.int32)
    y = K_dm.gather_swiglu(x, wg, wu, wd, idx, w, interpret=True)
    yr = ref.gather_swiglu(x, wg, wu, wd, idx, w)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(T=st.sampled_from([1, 3, 8]), E=st.sampled_from([2, 8]),
       k=st.sampled_from([1, 2, 4]), seed=st.integers(0, 100))
def test_gather_property(T, E, k, seed):
    x, wg, wu, wd, idx, w = _gather_inputs(T, 16, 16, E, k, jnp.float32,
                                           seed=seed)
    y = K_dm.gather_swiglu(x, wg, wu, wd, idx, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.gather_swiglu(x, wg, wu, wd, idx, w)),
        atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# int8 kernels (fused dequant) — bitwise vs the jnp dequant oracles
# ---------------------------------------------------------------------------

def _quant_inputs(T, d, f, E, k, dtype, seed=0, live=None):
    """Random int8-quantized tables (+ optional hetero zero pad rows beyond
    ``live``), routing restricted to live rows."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)) * 0.5, dtype)
    wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype)
    wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.2, dtype)
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.2, dtype)
    if live is not None:
        wg, wu, wd = (w.at[live:].set(0) for w in (wg, wu, wd))
    qt = Q.quantize_expert_tables(wg, wu, wd)
    idx = jnp.asarray(rng.integers(0, live or E, (T, k)), jnp.int32)
    w = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((T, k)), jnp.float32), axis=-1)
    return x, qt, idx, w


@pytest.mark.parametrize("T,d,f,E,k", [
    (4, 24, 32, 8, 2),      # decode shape: n_slots tokens
    (1, 16, 16, 4, 1),      # single token, single expert
    (8, 32, 48, 8, 3),      # k > 2
    (6, 16, 16, 8, 4),      # k == 4
    (3, 16, 32, 2, 2),      # tiny expert table
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_swiglu_q_bitwise(T, d, f, E, k, dtype):
    """Int8 gather kernel == jnp dequant oracle, BIT FOR BIT."""
    x, qt, idx, w = _quant_inputs(T, d, f, E, k, dtype, seed=T + k)
    y = K_dm.gather_swiglu_q(x, qt, idx, w, interpret=True)
    yr = ref.gather_swiglu_q(x, qt, idx, w)
    assert y.shape == (T, d) and y.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_swiglu_q_duplicate_topk_bitwise(dtype):
    """Duplicate top-k experts (the post-merge remap collision case) stay
    bitwise: the same expert's contribution enters the fp32 combine once
    per slot with its own weight."""
    x, qt, _, w = _quant_inputs(4, 16, 16, 4, 2, dtype, seed=5)
    idx = jnp.asarray([[1, 1], [2, 0], [3, 3], [0, 0]], jnp.int32)
    y = K_dm.gather_swiglu_q(x, qt, idx, w, interpret=True)
    yr = ref.gather_swiglu_q(x, qt, idx, w)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))
    # weights summing on one expert == that expert's full output
    deq = qt.dequant(dtype)
    one = ref.gather_swiglu(x[:1], *deq, jnp.asarray([[1]], jnp.int32),
                            jnp.ones((1, 1), jnp.float32))
    np.testing.assert_allclose(np.asarray(y[0], np.float32),
                               np.asarray(one[0], np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_swiglu_q_hetero_live_masked_bitwise(dtype):
    """Hetero live-masked tables: pad rows are zeros with zero scales;
    routing stays below ``live``. Kernel == oracle bitwise, and a poisoned
    OOB id clips identically on both sides."""
    x, qt, idx, w = _quant_inputs(5, 16, 16, 8, 2, dtype, seed=9, live=5)
    y = K_dm.gather_swiglu_q(x, qt, idx, w, interpret=True)
    yr = ref.gather_swiglu_q(x, qt, idx, w)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))
    bad = jnp.asarray([[11, 0], [1, -7], [0, 0], [1, 1], [2, 2]], jnp.int32)
    yb = K_dm.gather_swiglu_q(x, qt, bad, w, interpret=True)
    yrb = ref.gather_swiglu_q(x, qt, bad, w)
    assert np.isfinite(np.asarray(yb, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(yb, np.float32),
                                  np.asarray(yrb, np.float32))


@pytest.mark.parametrize("sizes", [
    [10, 0, 37, 17],        # empty group
    [1, 1, 1, 1, 60],       # tiny + dominant groups
    [40, 0, 24, 0, 16, 0, 8, 0],   # post-merge: absorbed buckets empty
    [0, 0, 0, 0],           # fully empty (T == 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_swiglu_q_bitwise(sizes, dtype):
    """Int8 grouped kernel == jnp dequant oracle bitwise with the f axis
    unblocked (block_f >= f), including zero-sized groups."""
    d, f = 24, 32
    E = len(sizes)
    gs = jnp.asarray(sizes, jnp.int32)
    T = int(gs.sum())
    x, qt, _, _ = _quant_inputs(max(T, 1), d, f, E, 2, dtype, seed=E)
    x = x[:T]
    y = K_gm.grouped_swiglu_q(x, qt, gs, block_t=16, block_f=f,
                              interpret=True)
    yr = ref.grouped_swiglu_q(x, qt, gs)
    assert y.shape == (T, d)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))


def test_grouped_swiglu_q_blocked_f_allclose():
    """Blocking the f axis reassociates the fp32 accumulation across
    f-blocks — allclose, not bitwise (DESIGN.md §8)."""
    d, f = 16, 32
    gs = jnp.asarray([5, 3, 0, 8], jnp.int32)
    x, qt, _, _ = _quant_inputs(16, d, f, 4, 2, jnp.float32, seed=3)
    y = K_gm.grouped_swiglu_q(x, qt, gs, block_t=8, block_f=16,
                              interpret=True)
    yr = ref.grouped_swiglu_q(x, qt, gs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-5, rtol=1e-5)


def test_gather_q_matches_grouped_q_composition():
    """Int8 gather == the int8 ragged pipeline (sort, grouped_q kernel,
    fp32 weighted scatter-add) on the same routing — the §8 extension of
    the dispatch-parity contract at kernel granularity."""
    T, d, f, E, k = 6, 24, 32, 8, 2
    x, qt, idx, w = _quant_inputs(T, d, f, E, k, jnp.float32, seed=13)
    y = K_dm.gather_swiglu_q(x, qt, idx, w, interpret=True)

    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    tok_of = order // k
    xs = x[tok_of]
    gs = jnp.asarray(np.bincount(flat, minlength=E), jnp.int32)
    ys = K_gm.grouped_swiglu_q(xs, qt, gs, block_t=8, block_f=f,
                               interpret=True)
    wf = np.asarray(w).reshape(-1)[order]
    out = np.zeros((T, d), np.float32)
    np.add.at(out, tok_of, np.asarray(ys, np.float32) * wf[:, None])
    np.testing.assert_allclose(np.asarray(y), out, atol=1e-5, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(T=st.sampled_from([1, 3, 8]), E=st.sampled_from([2, 8]),
       k=st.sampled_from([1, 2, 4]), seed=st.integers(0, 100))
def test_gather_q_property_bitwise(T, E, k, seed):
    x, qt, idx, w = _quant_inputs(T, 16, 16, E, k, jnp.float32, seed=seed)
    y = K_dm.gather_swiglu_q(x, qt, idx, w, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.gather_swiglu_q(x, qt, idx, w)))


def test_grouped_matches_single_expert_swiglu():
    """One expert == plain fused SwiGLU."""
    d, f, T = 16, 32, 48
    x = _randn((T, d), jnp.float32)
    wg, wu = _randn((1, d, f), jnp.float32, 0.2), _randn((1, d, f), jnp.float32, 0.2)
    wd = _randn((1, f, d), jnp.float32, 0.2)
    y = K_gm.grouped_swiglu(x, wg, wu, wd, jnp.asarray([T], jnp.int32),
                            block_t=16, block_f=16, interpret=True)
    y2 = ref.swiglu_mlp(x, wg[0], wu[0], wd[0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (DESIGN.md §11)
# ---------------------------------------------------------------------------

from repro.kernels import paged_attention as K_pa  # noqa: E402


def _paged_inputs(B, nq, nkv, hd, nb, bs, mb, seed=0, dtype=jnp.float32):
    """Random pool + a valid per-slot table: each slot owns ceil(lens/bs)
    distinct blocks; remaining table entries are the sentinel ``nb``."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, nq, hd)) * 0.5, dtype)
    kp = jnp.asarray(rng.standard_normal((nb, bs, nkv, hd)) * 0.5, dtype)
    vp = jnp.asarray(rng.standard_normal((nb, bs, nkv, hd)) * 0.5, dtype)
    lens = rng.integers(1, mb * bs + 1, size=B).astype(np.int32)
    tab = np.full((B, mb), nb, np.int32)
    perm = rng.permutation(nb)
    used = 0
    for b in range(B):
        need = -(-int(lens[b]) // bs)
        tab[b, :need] = perm[used:used + need]
        used += need
    assert used <= nb, "test pool too small"
    return q, kp, vp, jnp.asarray(tab), jnp.asarray(lens)


@pytest.mark.parametrize("B,nq,nkv,hd,bs,mb", [
    (2, 4, 4, 16, 4, 3),      # MHA (n_rep = 1)
    (3, 8, 2, 16, 8, 2),      # GQA (n_rep = 4)
    (1, 4, 4, 32, 4, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_oracle(B, nq, nkv, hd, bs, mb, dtype):
    nb = B * mb + 2
    q, kp, vp, tab, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb,
                                         seed=B * 7 + mb, dtype=dtype)
    y = K_pa.paged_attention(q, kp, vp, tab, lens, interpret=True)
    yr = ref.paged_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_q_matches_oracle(dtype):
    B, nq, nkv, hd, bs, mb = 3, 8, 2, 16, 4, 3
    nb = B * mb + 1
    q, kp, vp, tab, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb,
                                         seed=5, dtype=dtype)
    kq, ks = Q.quantize_kv(kp)
    vq, vs = Q.quantize_kv(vp)
    y = K_pa.paged_attention_q(q, kq, vq, ks, vs, tab, lens, interpret=True)
    yr = ref.paged_attention_q(q, kq, vq, ks, vs, tab, lens)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))


def test_paged_attention_sentinel_blocks_contribute_nothing():
    """Unallocated table entries (sentinel == n_blocks, clipped in range by
    the wrapper) and rows past ``lens`` must contribute exactly zero
    probability: poisoning every block the slot does NOT own with huge
    values cannot change the output."""
    B, nq, nkv, hd, bs, mb = 2, 4, 2, 16, 4, 3
    nb = B * mb + 2
    q, kp, vp, tab, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb, seed=11)
    owned = set(np.asarray(tab).reshape(-1).tolist()) - {nb}
    poison = np.asarray(vp).copy()
    for blk in range(nb):
        if blk not in owned:
            poison[blk] = 1e4
    y0 = K_pa.paged_attention(q, kp, vp, tab, lens, interpret=True)
    y1 = K_pa.paged_attention(q, kp, jnp.asarray(poison), tab, lens,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_paged_attention_zero_lens_row_is_finite():
    """lens == 0 (a slot with nothing admitted yet, e.g. the sentinel pad
    row of a partially-filled admission group) must produce finite output —
    the fully-masked-row normalizer guard, not NaNs from 0/0."""
    B, nq, nkv, hd, bs, mb = 2, 4, 2, 16, 4, 2
    nb = B * mb
    q, kp, vp, tab, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb, seed=3)
    lens = jnp.asarray([0, int(lens[1])], jnp.int32)
    y = K_pa.paged_attention(q, kp, vp, tab, lens, interpret=True)
    assert bool(jnp.isfinite(y).all())
    yr = ref.paged_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(yr[1]),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_matches_dense_sdpa_on_contiguous_table():
    """An identity table (slot b owns blocks [b*mb, b*mb+mb)) makes the pool
    a reshaped dense cache: the paged oracle must then agree with the dense
    decode attention the slot engine uses."""
    B, nq, nkv, hd, bs, mb = 2, 4, 2, 16, 4, 3
    nb = B * mb
    q, kp, vp, _, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb, seed=9)
    tab = jnp.arange(nb, dtype=jnp.int32).reshape(B, mb)
    y = K_pa.paged_attention(q, kp, vp, tab, lens, interpret=True)
    kc = kp.reshape(B, mb * bs, nkv, hd)
    vc = vp.reshape(B, mb * bs, nkv, hd)
    yr = ref._paged_sdpa(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(B=st.sampled_from([1, 2, 4]), nkv=st.sampled_from([1, 2]),
       bs=st.sampled_from([4, 8]), seed=st.integers(0, 100))
def test_paged_attention_property(B, nkv, bs, seed):
    nq, hd, mb = nkv * 2, 16, 2
    nb = B * mb + 1
    q, kp, vp, tab, lens = _paged_inputs(B, nq, nkv, hd, nb, bs, mb,
                                         seed=seed)
    y = K_pa.paged_attention(q, kp, vp, tab, lens, interpret=True)
    yr = ref.paged_attention(q, kp, vp, tab, lens)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5, rtol=2e-5)
