"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret-mode
allclose tests in tests/test_kernels.py) and the fallback implementations on
backends without Pallas support.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.models.numerics import ein, ein32, dot as _ndot

F32 = jnp.float32


def swiglu_mlp(x: jax.Array, wg: jax.Array, wu: jax.Array,
               wd: jax.Array) -> jax.Array:
    """Fused SwiGLU MLP oracle. x: [T, d]; wg/wu: [d, f]; wd: [f, d]."""
    g = _ndot(x, wg)
    u = _ndot(x, wu)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return _ndot(h, wd).astype(x.dtype)


def grouped_swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """Grouped (per-expert) SwiGLU oracle.

    x: [T, d] rows sorted by expert; wg/wu: [E, d, f]; wd: [E, f, d];
    group_sizes: [E] int32 with sum == T. Row t is processed by expert
    e(t) = the bucket t falls into.
    """
    T = x.shape[0]
    E = wg.shape[0]
    starts = jnp.cumsum(group_sizes) - group_sizes
    eid = jnp.searchsorted(starts, jnp.arange(T), side="right") - 1
    eid = jnp.clip(eid, 0, E - 1)
    g = ein("td,tdf->tf", x, wg[eid])
    u = ein("td,tdf->tf", x, wu[eid])
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return ein("tf,tfd->td", h, wd[eid]).astype(x.dtype)


def layer_stack(t):
    """A layer stack ``[L, E, ...]``; one layer's ``[E, ...]`` table is the
    L = 1 stack (the gather kernels' table contract, DESIGN.md §7)."""
    return t if t.ndim == 4 else t[None]


def gather_swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                  idx: jax.Array, w: jax.Array, layer=0) -> jax.Array:
    """Decode-mode (gather-dispatch) MoE oracle.

    x: [T, d]; wg/wu: [L, E, d, f]; wd: [L, E, f, d] (or one layer's
    [E, ...] tables, the L = 1 case); idx: [T, k] int32 REAL-expert ids;
    w: [T, k] combine weights; layer: the layer of the stack to read.
    Returns [T, d] with row t equal to
    ``Σ_j w[t, j] · SwiGLU_{idx[t, j]}(x[t])`` — the same per-row arithmetic
    as :func:`grouped_swiglu` on expert-sorted rows, evaluated token-major
    (no sort/bincount/scatter). The combine accumulates in fp32, mirroring
    the ragged path's scatter-add.
    """
    wg, wu, wd = layer_stack(wg), layer_stack(wu), layer_stack(wd)
    T, d = x.shape
    k = idx.shape[-1]
    E = wg.shape[1]
    eid = jnp.clip(idx.reshape(-1), 0, E - 1)        # [T*k] token-major
    xr = jnp.repeat(x, k, axis=0)                    # [T*k, d]
    g = ein("td,tdf->tf", xr, wg[layer, eid])
    u = ein("td,tdf->tf", xr, wu[layer, eid])
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = ein("tf,tfd->td", h, wd[layer, eid]).astype(x.dtype)
    out = jnp.sum(y.reshape(T, k, d).astype(F32)
                  * w.reshape(T, k, 1).astype(F32), axis=1)
    return out.astype(x.dtype)


def _dequant32(qt):
    """fp32 dequantized tables — ``q * scale`` with NO intermediate downcast.

    The int8 paths keep the dequantized weights at fp32 all the way through
    the SwiGLU (one output-side downcast only). Intermediate ``bf16``
    roundings would be unstable validation targets: XLA's excess-precision
    pass cancels f32→bf16→f32 round-trips inside fused computations, so a
    kernel could not reproduce them bit for bit (DESIGN.md §8)."""
    return (qt.wg.astype(F32) * qt.wg_scale,
            qt.wu.astype(F32) * qt.wu_scale,
            qt.wd.astype(F32) * qt.wd_scale)


def grouped_swiglu_q(x: jax.Array, qt, group_sizes: jax.Array) -> jax.Array:
    """Int8 grouped SwiGLU oracle.

    ``qt``: :class:`repro.core.quant.QuantizedExpertTables`. Same grouping
    semantics as :func:`grouped_swiglu`; arithmetic is fp32 end-to-end on
    the dequantized tables with a single downcast at the output — exactly
    the int8 Pallas kernel's dataflow, which matches this oracle bit for
    bit when the f axis is unblocked (tests/test_kernels.py)."""
    wg32, wu32, wd32 = _dequant32(qt)
    T = x.shape[0]
    E = qt.wg.shape[0]
    starts = jnp.cumsum(group_sizes) - group_sizes
    eid = jnp.searchsorted(starts, jnp.arange(T), side="right") - 1
    eid = jnp.clip(eid, 0, E - 1)
    x32 = x.astype(F32)
    g = jnp.einsum("td,tdf->tf", x32, wg32[eid])
    u = jnp.einsum("td,tdf->tf", x32, wu32[eid])
    h = jax.nn.silu(g) * u
    return jnp.einsum("tf,tfd->td", h, wd32[eid]).astype(x.dtype)


def gather_swiglu_q(x: jax.Array, qt, idx: jax.Array,
                    w: jax.Array, layer=0) -> jax.Array:
    """Int8 decode-mode (gather-dispatch) oracle.

    Row semantics of :func:`gather_swiglu` on the fp32-dequantized tables
    (stacked ``[L, E, ...]`` or one layer's, as there): each (token, j)
    contribution is computed at fp32, downcast to ``x.dtype`` (the same
    output rounding :func:`grouped_swiglu_q` applies, so the int8 ragged
    and gather paths stay bitwise-consistent at top_k = 2), then combined
    with fp32 weights."""
    qt = jax.tree.map(layer_stack, qt)
    T, d = x.shape
    k = idx.shape[-1]
    E = qt.wg.shape[1]
    eid = jnp.clip(idx.reshape(-1), 0, E - 1)
    wg32, wu32, wd32 = _dequant32(jax.tree.map(lambda a: a[layer, eid], qt))
    xr = jnp.repeat(x, k, axis=0).astype(F32)
    g = jnp.einsum("td,tdf->tf", xr, wg32)
    u = jnp.einsum("td,tdf->tf", xr, wu32)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tf,tfd->td", h, wd32).astype(x.dtype)
    out = jnp.sum(y.reshape(T, k, d).astype(F32)
                  * w.reshape(T, k, 1).astype(F32), axis=1)
    return out.astype(x.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: float | None = None) -> jax.Array:
    """Attention oracle. q/k/v: [B, H, S, hd] (same H; GQA expansion is done
    by the caller)."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    logits = ein("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S_q, S_k = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q)
        logits = jnp.where(mask[None, None], logits, jnp.finfo(F32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return ein("bhqk,bhkd->bhqd", probs, v)


def _paged_sdpa(q: jax.Array, kc: jax.Array, vc: jax.Array,
                lens: jax.Array) -> jax.Array:
    """Shared paged-decode attention body over a GATHERED contiguous view.

    This is a bitwise mirror of ``layers._sdpa`` on the decode mask
    (``arange(s_max) <= pos`` with ``lens = pos + 1``): same GQA
    ``jnp.repeat`` expansion, same ``ein32`` logits, same fp32 min fill,
    same softmax-then-downcast, same output einsum. Rows past ``lens``
    carry whatever the pool holds (zeros, stale blocks, clipped sentinels)
    — they get probability exactly 0, and adding exact fp zeros to the
    reductions is the identity, which is why paged bf16 decode is bitwise
    equal to the dense slot cache (DESIGN.md §11)."""
    B, S, nkv, hd = kc.shape
    n_rep = q.shape[1] // nkv
    if n_rep > 1:
        kc = jnp.repeat(kc, n_rep, axis=2)
        vc = jnp.repeat(vc, n_rep, axis=2)
    logits = ein32("bqhd,bkhd->bhqk", q[:, None], kc) / math.sqrt(hd)
    mask = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
    logits = jnp.where(mask, logits, jnp.finfo(F32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(vc.dtype)
    out = ein("bhqk,bkhd->bqhd", probs, vc).astype(vc.dtype)
    return out[:, 0]


def _gather_pool(pool: jax.Array, tab: jax.Array) -> jax.Array:
    """[n_blocks, bs, ...] pool + [B, mb] table -> [B, mb*bs, ...] view.
    Sentinel entries (>= n_blocks) clip to the last real block; their rows
    are masked by ``lens`` downstream."""
    nb, bs = pool.shape[0], pool.shape[1]
    tabc = jnp.clip(tab.astype(jnp.int32), 0, nb - 1)
    g = pool[tabc]                                    # [B, mb, bs, ...]
    return g.reshape((g.shape[0], g.shape[1] * bs) + g.shape[3:])


def paged_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                    tab: jax.Array, lens: jax.Array) -> jax.Array:
    """Paged decode attention oracle.

    q: [B, nq, hd] (the current token's query; its K/V row is already in
    the pool); kp/vp: [n_blocks, bs, nkv, hd]; tab: [B, mb] int32 block
    table (sentinel = n_blocks); lens: [B] int32 valid rows (``pos + 1``).
    Returns [B, nq, hd].
    """
    return _paged_sdpa(q, _gather_pool(kp, tab), _gather_pool(vp, tab), lens)


def paged_attention_q(q: jax.Array, kp: jax.Array, vp: jax.Array,
                      ks: jax.Array, vs: jax.Array, tab: jax.Array,
                      lens: jax.Array) -> jax.Array:
    """Int8-pool paged decode attention oracle.

    kp/vp: int8 [n_blocks, bs, nkv, hd]; ks/vs: fp32 [n_blocks, bs, nkv]
    per-(row, head) scales (``core.quant.quantize_kv``). Dequantizes the
    gathered view through ``quant.dequantize_kv`` — the same helper the
    verify path uses — so decode and verify see one consistent KV
    representation (the spec-decode self-consistency requirement, §11).
    """
    kc = Q.dequantize_kv(_gather_pool(kp, tab), _gather_pool(ks, tab),
                         q.dtype)
    vc = Q.dequantize_kv(_gather_pool(vp, tab), _gather_pool(vs, tab),
                         q.dtype)
    return _paged_sdpa(q, kc, vc, lens)
