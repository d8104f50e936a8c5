"""Expert-parallel MoE dispatch: all-to-all pair exchange (DESIGN.md §13).

Runs INSIDE a ``shard_map`` over the mesh axis named by
``cfg.moe.ep_axis``. The expert tables (and ``qexp`` int8 leaves) are
partitioned on that axis — shard ``s`` stores global rows
``[s*E_l, (s+1)*E_l)`` — while tokens arrive replicated across it. The
dataflow per MoE layer:

1. slice my 1/ep of the (padded) token rows — every shard routes the same
   replicated activations, so slicing is free of communication;
2. scatter each (token, j) routed pair into a per-destination send buffer
   ``[ep, C, d]`` (owner = global_id // E_l) and ``lax.all_to_all`` it;
3. run the LOCAL ``gather_swiglu(_q)`` kernel at k=1 over the received
   rows — the per-pair outputs are exactly the per-row terms the
   single-device kernel computes (per-row einsum arithmetic is
   batch-size- and kernel-invariant on this backend; the spec-decode
   bitwise guarantee of §10 is built on the same fact);
4. return the pair outputs via a second all-to-all (fp32-exact wire) or,
   opt-in, an int8 ``compressed_psum`` of the full pair table
   (``combine_wire_dtype='int8'``, tolerance-gated);
5. combine at each token's home slice with the SAME fp32 expression the
   jnp oracles use (``jnp.sum`` over k in gather mode; stable
   expert-sorted scatter-add in ragged mode), then ``all_gather`` the
   token rows back.

Why all-to-all and not all-gather: the a2a payload per token is
``k * d * act_bytes`` each way — independent of E — while all-gathering
activations so every shard can route locally would ship ``ep`` copies of
every token and still leave the combine partial. The a2a exchanges only
the routed pairs, which is also the quantity the interconnect traffic
model meters (``launch/hlo_analysis.decode_traffic_model``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig

F32 = jnp.float32


def moe_apply_ep(cfg: ModelConfig, p: dict, xf: jax.Array, wf: jax.Array,
                 rf: jax.Array, gather_mode: bool) -> jax.Array:
    """EP dispatch for one MoE layer.

    xf: [T, d] tokens (replicated over ``ep_axis``); wf/rf: [T, k] combine
    weights / REAL-expert ids from the replicated router. Returns [T, d]
    replicated — bitwise equal to the single-device ``_moe_gather`` /
    ``_moe_ragged`` result when the wire dtype is fp32.
    """
    from repro.kernels import ops as kops
    from repro.models.moe import n_real_experts, _quant_tables

    m = cfg.moe
    ep, ax = m.ep_degree, m.ep_axis
    T, d = xf.shape
    k = m.top_k
    e_loc = n_real_experts(p)            # LOCAL table rows under shard_map
    me = lax.axis_index(ax)

    # Pad so every shard owns an equal token slice. Pad rows carry x = 0,
    # expert 0, weight 0: they compute SwiGLU(0) = 0 wherever they land and
    # are dropped by the final [:T] slice.
    Tl = -(-T // ep)
    Tp = Tl * ep
    if Tp != T:
        xf = jnp.pad(xf, ((0, Tp - T), (0, 0)))
        wf = jnp.pad(wf, ((0, Tp - T), (0, 0)))
        rf = jnp.pad(rf, ((0, Tp - T), (0, 0)))
    x_my = lax.dynamic_slice_in_dim(xf, me * Tl, Tl, axis=0)
    w_my = lax.dynamic_slice_in_dim(wf, me * Tl, Tl, axis=0)
    r_my = lax.dynamic_slice_in_dim(rf, me * Tl, Tl, axis=0)

    # --- dispatch: pair -> owning shard -----------------------------------
    C = Tl * k                           # per-destination capacity (worst
    rp = r_my.reshape(C)                 # case: every pair one owner)
    owner = rp // e_loc                  # [C] destination shard per pair
    oh = (owner[:, None] == jnp.arange(ep)[None, :]).astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                              owner[:, None], axis=1)[:, 0]
    xpairs = jnp.take(x_my, jnp.arange(C) // k, axis=0)        # [C, d]

    send_x = jnp.zeros((ep, C, d), xf.dtype).at[owner, pos].set(xpairs)
    send_e = jnp.zeros((ep, C), jnp.int32).at[owner, pos].set(rp)
    recv_x = lax.all_to_all(send_x, ax, 0, 0, tiled=True)      # [ep, C, d]
    recv_e = lax.all_to_all(send_e, ax, 0, 0, tiled=True)      # [ep, C]

    # --- local expert compute (k = 1 per received pair) -------------------
    # Unwritten buffer rows hold x = 0 / global id 0; the sharded wrapper
    # zeroes the weight of any id outside [e_base, e_base + e_loc), so both
    # kinds of non-pair rows contribute exactly fp 0.0.
    flat_x = recv_x.reshape(ep * C, d)
    flat_e = recv_e.reshape(ep * C, 1)
    ones = jnp.ones((ep * C, 1), F32)
    e_base = me * e_loc
    qt = _quant_tables(p)
    layer = p.get("layer", 0)
    if qt is not None:
        y = kops.gather_swiglu_q_sharded(flat_x, qt, flat_e, ones, e_base,
                                         layer)
    else:
        y = kops.gather_swiglu_sharded(flat_x, p["wg"], p["wu"], p["wd"],
                                       flat_e, ones, e_base, layer)
    y = y.astype(xf.dtype)               # [ep*C, d] per-pair outputs

    # --- return wire ------------------------------------------------------
    if m.combine_wire_dtype == "int8":
        # Opt-in int8 wire: every shard contributes its computed pairs to a
        # zero-elsewhere [origin, owner, pos] table; compressed_psum ships
        # int8 + one shared scale and sums to the replicated full table
        # (tolerance-gated — stochastic rounding breaks bitwise parity).
        from repro.distributed.compression import compressed_psum
        contrib = lax.dynamic_update_slice(
            jnp.zeros((ep, ep, C, d), F32),
            y.reshape(ep, 1, C, d).astype(F32),
            (jnp.int32(0), me, jnp.int32(0), jnp.int32(0)))
        key = jax.random.PRNGKey(m.combine_wire_seed)
        full = compressed_psum(contrib, ax, key)
        mine = lax.dynamic_slice_in_dim(full, me, 1, axis=0)[0]
        y_pairs = mine[owner, pos].astype(xf.dtype)            # [C, d]
    else:
        # fp32-exact wire: a2a the pair outputs straight back; y_ret[o, p]
        # is my pair p as computed by owner o.
        y_ret = lax.all_to_all(y.reshape(ep, C, d), ax, 0, 0, tiled=True)
        y_pairs = y_ret[owner, pos]                            # [C, d]

    # --- combine (oracle-exact fp32 expressions) --------------------------
    if gather_mode:
        out = jnp.sum(y_pairs.reshape(Tl, k, d).astype(F32)
                      * w_my.reshape(Tl, k, 1).astype(F32), axis=1)
        out = out.astype(xf.dtype)
    else:
        # mirror _moe_ragged's expert-sorted stable scatter-add: restricted
        # to any token slice the per-token add order is (expert asc, j asc)
        # in both, so the fp32 partial sums agree term for term.
        order = jnp.argsort(r_my.reshape(-1))
        tok_of = order // k
        wf_o = w_my.reshape(-1)[order].astype(F32)
        out = jnp.zeros((Tl, d), F32).at[tok_of].add(
            y_pairs[order].astype(F32) * wf_o[:, None])
        out = out.astype(xf.dtype)

    yg = lax.all_gather(out, ax, axis=0, tiled=True)           # [Tp, d]
    return yg[:T]
