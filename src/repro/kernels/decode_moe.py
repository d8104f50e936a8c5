"""Decode-mode (gather-dispatch) MoE SwiGLU Pallas kernel.

The grouped kernel (``grouped_mlp.py``) is built for prefill-sized token
counts: it sorts tokens by expert, pads every expert segment to a token
block, and walks block-aligned groups. At decode the MoE layer sees only
``n_slots`` tokens (a handful), so that path is pure overhead — the argsort,
bincount, segment padding (``T + E·(bt-1)`` rows for T≈4!) and scatter cost
more than the math.

This kernel is the small-T specialization. Tokens are taken in aligned row
tiles of ``tm`` (8 rows at fp32, 16 at bf16: the TPU's sublane tiling, so
no block has an unaligned second-to-last dim). The grid is
``(T/tm, k, tm)``: one tile, one of the top-k slots, one row of the tile.
A scalar-prefetched ``idx`` table lets each step's BlockSpec index maps
gather the three weight tables of exactly the expert that row routed to.
The step picks its row out of the resident tile with a mask (a dynamic
one-row slice is not provably aligned, which Mosaic refuses), runs the
SwiGLU on that row and writes it back under the same mask. No sorting, no
segment padding, no scatter: the only HBM traffic is the k expert row-sets
a token needs. Pad rows repeat the last
real row's experts, so their steps re-use the resident blocks and fetch
nothing. The combine weights sit whole in SMEM; the k contributions
accumulate in an fp32 VMEM scratch, mirroring the ragged path's fp32
scatter-add so the two dispatches agree (tests assert parity).

The tables come stacked over layers, ``[L, E, d, f]`` / ``[L, E, f, d]``,
with a scalar-prefetched ``layer`` index beside ``idx``: the index maps
return ``(layer, idx[...], 0, 0)``, so the kernel fetches its experts
straight out of the whole stack. A decode stack scans its layers with the
tables held whole (``transformer._scan_layers``) because a per-layer slice
of a scanned table is a dynamic slice, which XLA materializes before it can
hand it to a custom call: every decode step would copy each layer's whole
``[E, d, f]`` tables to read a few experts of them. One layer's unstacked
``[E, d, f]`` tables are the L = 1 case (a free reshape, ``layer`` 0).

The three double-buffered expert blocks exceed the default scoped VMEM
limit at real widths (qwen3-moe: 3 x 2 x [2048, 768] bf16 ~ 18.9 MB), so
each call sizes ``vmem_limit_bytes`` from its own blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import layer_stack

F32 = jnp.float32
_MIB = 1 << 20


def _row_tile(dtype) -> int:
    """Token rows per tile: the sublane tiling of ``dtype`` (8 rows of
    32-bit, 16 of 16-bit)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pad_rows(x, idx, w, tm: int, E: int):
    """Pad the token axis up to a multiple of ``tm``. Pad rows have zero
    inputs and weights and repeat the last real row's (clipped) expert ids,
    so their grid steps hit blocks already resident and fetch nothing.

    ``idx`` and ``w`` come back flattened to ``[T * k]``: they live in SMEM,
    where a 2-D ``[T, k]`` array pads its minor dim to 128 words — 4 MiB
    at the expert-parallel prefill's 8192 pairs, over SMEM's 1 MiB."""
    T = x.shape[0]
    pad = -T % tm
    idx = jnp.clip(idx.astype(jnp.int32), 0, E - 1)
    x = jnp.pad(x, ((0, pad), (0, 0)))
    idx = jnp.pad(idx, ((0, pad), (0, 0)), mode="edge")
    w = jnp.pad(w.astype(F32), ((0, pad), (0, 0)))
    return x, idx.reshape(-1), w.reshape(-1)


def _layer(layer, n_layers: int):
    """The layer index as the ``[1]`` int32 scalar-prefetch operand,
    clipped into the stack like the expert ids."""
    return jnp.clip(jnp.asarray(layer, jnp.int32), 0, n_layers - 1)[None]


def _expert_map(tm: int, k: int):
    """Index map of the gathered expert blocks: row ``i*tm + r``'s j-th
    expert of layer ``ly[0]``."""
    def index(i, j, r, ly, ix):
        return (ly[0], ix[(i * tm + r) * k + j], 0, 0)
    return index


def _tile_map(i, j, r, ly, ix):
    """Index map of the token tiles (input rows and output)."""
    return (i, 0)


def _compiler_params(pipelined_bytes: int, temp_bytes: int):
    """Scoped-VMEM limit sized from the blocks: double-buffered pipelined
    blocks plus in-kernel temporaries plus headroom, never below the
    default 16 MiB."""
    need = 2 * pipelined_bytes + temp_bytes + 4 * _MIB
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(16 * _MIB, -(-need // _MIB) * _MIB))


def _row_of(x, r):
    """Row ``r`` of the tile ``x`` as a ``[1, d]`` fp32 value: a masked
    sum over the tile, exact since every other term is zero."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == r, x.astype(F32), 0.0), axis=0,
                   keepdims=True)


def _kernel(ly_ref, idx_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref,
            acc_ref, *, k: int, tm: int, n_tokens: int):
    i, j, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    t = i * tm + r

    @pl.when((j == 0) & (r == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < n_tokens)
    def _row():
        x = _row_of(x_ref[...], r).astype(x_ref.dtype)      # [1, d]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=F32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=F32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        # downcast to the model dtype before the fp32-weighted combine — the
        # exact arithmetic of the ragged path (grouped matmul emits x.dtype
        # rows, the combine scatter-adds them in fp32)
        y = jnp.dot(h, wd_ref[0, 0],
                    preferred_element_type=F32).astype(x.dtype)
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        acc_ref[...] += jnp.where(rows == r,
                                  w_ref[t * k + j] * y.astype(F32), 0.0)

    @pl.when((j == k - 1) & (r == tm - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_swiglu(x, wg, wu, wd, idx, w, layer=0, interpret: bool = False):
    """x: [T, d]; wg/wu: [L, E, d, f]; wd: [L, E, f, d] (or one layer's
    [E, ...] tables, the L = 1 case); idx: [T, k] int32 in REAL expert
    space; w: [T, k] combine weights; layer: int32 scalar, the layer of the
    stack to read. Returns [T, d] where row t is
    ``Σ_j w[t, j] · SwiGLU_{idx[t, j]}(x[t])`` on that layer's experts.

    ``idx`` entries are clipped to [0, E) and ``layer`` to [0, L): routing
    fails closed upstream (``moe.route`` masks remap targets >= live,
    DESIGN.md §5), so the clip is pure out-of-bounds defense for the
    weight-row gather, matching the oracle."""
    wg, wu, wd = layer_stack(wg), layer_stack(wu), layer_stack(wd)
    T, d = x.shape
    n_layers, E, _, f = wg.shape
    k = idx.shape[-1]
    if T == 0:
        return jnp.zeros((0, d), x.dtype)
    tm = _row_tile(x.dtype)
    xp, idx, w = _pad_rows(x, idx, w, tm, E)
    Tp = xp.shape[0]
    expert = _expert_map(tm, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Tp // tm, k, tm),
        in_specs=[
            pl.BlockSpec((tm, d), _tile_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d, f), expert),
            pl.BlockSpec((1, 1, d, f), expert),
            pl.BlockSpec((1, 1, f, d), expert),
        ],
        out_specs=pl.BlockSpec((tm, d), _tile_map),
        scratch_shapes=[pltpu.VMEM((tm, d), F32)],
    )
    isz = x.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, tm=tm, n_tokens=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, d), x.dtype),
        compiler_params=_compiler_params(
            3 * d * f * wg.dtype.itemsize + 2 * tm * d * isz,
            tm * d * 4 + 3 * tm * f * 4),
        interpret=interpret,
    )(_layer(layer, n_layers), idx, xp, w, wg, wu, wd)
    return out[:T]


def _kernel_q(ly_ref, idx_ref, x_ref, qg_ref, qu_ref, qd_ref,
              sg_ref, su_ref, sd_ref, o_ref, *, tm: int, n_tokens: int):
    """Int8 variant of :func:`_kernel`: the three gathered weight blocks are
    int8 plus fp32 per-output-channel scale rows, dequantized in VMEM — one
    byte per weight over HBM instead of two. The dequantized weights stay
    fp32 through the whole SwiGLU and each (token, expert-slot) contribution
    is emitted to its own ``[k, T, d]`` output row at the model dtype; the
    wrapper applies the fp32 combine weights OUTSIDE the kernel with exactly
    the oracle's ops. Rationale: accumulating ``acc += w*y`` in-kernel is an
    FMA-contraction site (XLA:CPU fuses the multiply-add with one fewer
    rounding), which would put the interpret-mode result 1 ulp away from
    any jnp oracle — structurally unfixable, so the combine lives outside
    (DESIGN.md §8). The emitted rows are k·T·d·2 bytes — noise next to the
    k expert row-sets the kernel exists to stream. The grid is
    ``(T/tm, k, tm)`` so one slot's output tile stays resident while its
    rows fill in."""
    i, r = pl.program_id(0), pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i * tm + r < n_tokens)
    def _row():
        x32 = _row_of(x_ref[...], r)                         # [1, d]
        wg = qg_ref[0, 0].astype(F32) * sg_ref[0, 0]
        wu = qu_ref[0, 0].astype(F32) * su_ref[0, 0]
        wd = qd_ref[0, 0].astype(F32) * sd_ref[0, 0]
        g = jnp.dot(x32, wg)
        u = jnp.dot(x32, wu)
        h = jax.nn.silu(g) * u
        y = jnp.dot(h, wd).astype(o_ref.dtype)
        rows = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 0)
        o_ref[0] = jnp.where(rows == r, y, o_ref[0])


def gather_swiglu_q(x, qt, idx, w, layer=0, interpret: bool = False):
    """Int8 decode-mode gather SwiGLU. Same contract as
    :func:`gather_swiglu` with the weight tables replaced by a
    :class:`repro.core.quant.QuantizedExpertTables` (int8 tables + keepdim
    fp32 scales, stacked ``[L, E, ...]`` or one layer's ``[E, ...]``); per
    token the kernel streams k int8 expert row-sets of layer ``layer`` —
    the decode hot loop's dominant HBM term at half the bf16 width. Bitwise
    equal to ``ref.gather_swiglu_q`` in interpret mode. Deliberately
    UNJITTED, same reasoning as ``grouped_swiglu_q`` (production jits at
    the ``ops`` layer)."""
    qt = jax.tree.map(layer_stack, qt)
    T, d = x.shape
    n_layers, E, _, f = qt.wg.shape
    k = idx.shape[-1]
    if T == 0:
        return jnp.zeros((0, d), x.dtype)
    tm = _row_tile(x.dtype)
    xp, idx, _ = _pad_rows(x, idx, w, tm, E)
    Tp = xp.shape[0]
    expert = _expert_map(tm, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Tp // tm, k, tm),
        in_specs=[
            pl.BlockSpec((tm, d), _tile_map),
            pl.BlockSpec((1, 1, d, f), expert),
            pl.BlockSpec((1, 1, d, f), expert),
            pl.BlockSpec((1, 1, f, d), expert),
            pl.BlockSpec((1, 1, 1, f), expert),
            pl.BlockSpec((1, 1, 1, f), expert),
            pl.BlockSpec((1, 1, 1, d), expert),
        ],
        out_specs=pl.BlockSpec((1, tm, d),
                               lambda i, j, r, ly, ix: (j, i, 0)),
        scratch_shapes=[],
    )
    isz = x.dtype.itemsize
    y = pl.pallas_call(
        functools.partial(_kernel_q, tm=tm, n_tokens=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, Tp, d), x.dtype),
        compiler_params=_compiler_params(
            3 * d * f + 4 * (2 * f + d) + 2 * tm * d * isz,
            3 * d * f * 4 + tm * d * 4 + 3 * tm * f * 4),
        interpret=interpret,
    )(_layer(layer, n_layers), idx, xp, qt.wg, qt.wu, qt.wd,
      qt.wg_scale, qt.wu_scale, qt.wd_scale)
    y = jnp.swapaxes(y, 0, 1)[:T]                            # [T, k, d]
    # the oracle's combine, verbatim: fp32 weights over model-dtype rows
    out = jnp.sum(y.astype(F32) * w.reshape(T, k, 1).astype(F32), axis=1)
    return out.astype(x.dtype)
