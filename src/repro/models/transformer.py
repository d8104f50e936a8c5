"""Layer stacks: decoder-only (dense/MoE/VLM), hybrid (Mamba2 + shared attn),
and encoder-decoder (whisper-style). All homogeneous stacks run under
``jax.lax.scan`` over stacked layer params so HLO size / compile time stay
bounded at 512 simulated devices; ``cfg.remat`` optionally rematerializes each
block.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.numerics import constrain, bf16_cotangent
from repro.models import layers as L
from repro.models import moe as M
from repro.models import mamba as S

F32 = jnp.float32


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _stack_init(init_fn, n: int, key):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# decoder-only block (dense MLP or MoE)
# ---------------------------------------------------------------------------

def block_init(cfg: ModelConfig, key, n_real: int | None = None) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "attn": L.attn_init(cfg, k1),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
    }
    if cfg.moe is not None:
        p["moe"] = M.moe_init(cfg, k2, n_real=n_real)
    else:
        p["mlp"] = L.mlp_init(cfg.d_model, cfg.d_ff, cfg.param_dtype, k2)
    return p


def block_apply(cfg: ModelConfig, p: dict, x, *, inv_freq, positions=None,
                causal=True, capture=False):
    """Returns (y, aux_loss, capture_tuple_or_None).

    Sub-block outputs are constrained to the sequence-parallel layout BEFORE
    the residual add so the row-parallel projections' partial sums lower to
    reduce-scatter (not all-reduce + slice) — Megatron-SP."""
    a = L.attn_apply(cfg, p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                     inv_freq=inv_freq, positions=positions, causal=causal)
    h = x + constrain(a, "DP", "M", None)
    hn = L.rmsnorm(p["ln2"], h, cfg.norm_eps)
    if cfg.moe is not None:
        out = M.moe_apply(cfg, p["moe"], hn, capture=capture)
        cap = (out.expert_inputs, out.usage_counts) if capture else None
        return h + constrain(out.y, "DP", "M", None), out.aux_loss, cap
    return h + constrain(L.mlp_apply(p["mlp"], hn), "DP", "M", None), \
        jnp.zeros((), F32), None


def stack_init(cfg: ModelConfig, key, n_layers: int | None = None,
               n_real: int | None = None) -> dict:
    n = cfg.n_layers if n_layers is None else n_layers
    return _stack_init(lambda k: block_init(cfg, k, n_real=n_real), n, key)


def stack_apply(cfg: ModelConfig, stacked: dict, x, *, inv_freq,
                capture=False):
    """Scan the decoder-only stack. Returns (y, total_aux, captures)."""
    def body(carry, layer_p):
        h, aux = carry
        y, a, cap = block_apply(cfg, layer_p, h, inv_freq=inv_freq,
                                capture=capture)
        y = bf16_cotangent(constrain(y, "DP", "M", None))  # Megatron-SP residual
        return (y, aux + a), cap

    body = _maybe_remat(cfg, body)
    if cfg.scan_layers:
        (y, aux), caps = jax.lax.scan(body, (x, jnp.zeros((), F32)), stacked)
    else:
        caps_list, carry = [], (x, jnp.zeros((), F32))
        for i in range(cfg.n_layers):
            layer_p = jax.tree.map(lambda a: a[i], stacked)
            carry, cap = body(carry, layer_p)
            caps_list.append(cap)
        y, aux = carry
        caps = (jax.tree.map(lambda *xs: jnp.stack(xs), *caps_list)
                if capture and cfg.moe is not None else None)
    return y, aux, caps


def _scan_layers(body, x, stacked: dict, *per_layer):
    """``lax.scan`` of ``body(h, layer_p, *per_layer_slices) -> (h, ys)``
    over the stacked layers, with the MoE expert tables held whole.

    A scanned leaf reaches the body as a per-layer dynamic slice, which XLA
    materializes before a Pallas custom call can read it: every decode step
    would copy each layer's whole ``[E, d, f]`` tables for a gather kernel
    that then reads a few experts of them. So the tables (``wg/wu/wd`` or
    ``qexp``) ride into the loop as invariants, and each layer's ``moe``
    params get them stacked plus the scanned layer index ``layer``
    (``moe.own_tables`` slices them for every path but the gather kernel;
    DESIGN.md §7)."""
    moe = stacked.get("moe", {})
    tables = {k: moe[k] for k in M.TABLE_KEYS if k in moe}
    if tables:
        stacked = dict(stacked, moe={k: v for k, v in moe.items()
                                     if k not in tables})
    n = jax.tree.leaves(stacked)[0].shape[0]

    def step(h, xs):
        layer_p, layer, *rest = xs
        if tables:
            layer_p = dict(layer_p, moe=dict(layer_p["moe"], **tables,
                                             layer=layer))
        return body(h, layer_p, *rest)

    return jax.lax.scan(step, x, (stacked, jnp.arange(n, dtype=jnp.int32))
                        + per_layer)


def _decode_layers(cfg: ModelConfig, stacked: dict, x, attend, *kv):
    """The serving layer body (ln1 -> attention over this layer's KV ->
    residual -> ln2 -> MoE/MLP -> residual) scanned over the stack.

    ``attend(attn_p, hn, *kv) -> (a, *kv)`` reads and writes one layer's
    slice of each ``kv`` array (dense K/V, or paged pools and scales).
    Decode throws the MoE aux loss away every step — ``need_aux=False``
    skips it and the full-probs softmax it retains. Returns
    (y, tuple of new kv arrays)."""
    def body(h, layer_p, *kv):
        hn = L.rmsnorm(layer_p["ln1"], h, cfg.norm_eps)
        a, *kv = attend(layer_p["attn"], hn, *kv)
        h = h + a
        hn = L.rmsnorm(layer_p["ln2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            out = M.moe_apply(cfg, layer_p["moe"], hn, need_aux=False)
            h = h + out.y
        else:
            h = h + L.mlp_apply(layer_p["mlp"], hn)
        return h, tuple(kv)

    return _scan_layers(body, x, stacked, *kv)


def _dense_kv(cfg: ModelConfig, attn_fn, stacked: dict, x, cache_k, cache_v,
              pos, inv_freq):
    def attend(attn_p, hn, ck, cv):
        return attn_fn(cfg, attn_p, hn, ck, cv, pos, inv_freq=inv_freq)
    y, (nk, nv) = _decode_layers(cfg, stacked, x, attend, cache_k, cache_v)
    return y, nk, nv


def stack_decode(cfg: ModelConfig, stacked: dict, x, cache_k, cache_v, pos,
                 *, inv_freq):
    """One-token decode through the scanned stack.

    cache_k/v: [L, B, S_max, nkv, hd]. Returns (y, new_k, new_v)."""
    return _dense_kv(cfg, L.attn_decode, stacked, x, cache_k, cache_v, pos,
                     inv_freq)


def stack_decode_slots(cfg: ModelConfig, stacked: dict, x, cache_k, cache_v,
                       pos, *, inv_freq):
    """One-token decode with per-slot positions (continuous batching).

    cache_k/v: [L, B, S_max, nkv, hd]; pos: [B] int32 per-slot lengths.
    The MoE sub-block goes through ``moe_apply`` unchanged, so under
    ``dispatch='ragged'`` every decode step runs the grouped kernel over the
    B slot tokens. Returns (y, new_k, new_v)."""
    return _dense_kv(cfg, L.attn_decode_slots, stacked, x, cache_k, cache_v,
                     pos, inv_freq)


def stack_verify_slots(cfg: ModelConfig, stacked: dict, x, cache_k, cache_v,
                       pos, *, inv_freq):
    """T-token forward with per-slot positions (speculative verify).

    Same layer body as :func:`stack_decode_slots` but over T positions per
    slot via ``attn_verify_slots``; x: [B, T, d]. With T > 1 the MoE
    sub-block sees B*T tokens, so it always takes the grouped/ragged path —
    the T == 1 gather specialization never applies to a verify forward.
    Returns (y [B, T, d], new_k, new_v)."""
    return _dense_kv(cfg, L.attn_verify_slots, stacked, x, cache_k, cache_v,
                     pos, inv_freq)


def _paged_kv(cfg: ModelConfig, attn_fn, stacked: dict, x, kp, vp, ks, vs,
              tab, pos, inv_freq):
    """The paged decode/verify stacks: the dense slot stacks' layer body
    with the per-layer KV pool (and scales, when int8) threaded through the
    scan. Returns (y, kp, vp, ks, vs), the scales None for bf16 pools."""
    quant = ks is not None

    def attend(attn_p, hn, kp, vp, ks=None, vs=None):
        a, kp, vp, ks, vs = attn_fn(cfg, attn_p, hn, kp, vp, ks, vs, tab,
                                    pos, inv_freq=inv_freq)
        return (a, kp, vp, ks, vs) if quant else (a, kp, vp)

    y, pools = _decode_layers(cfg, stacked, x, attend,
                              *((kp, vp, ks, vs) if quant else (kp, vp)))
    return (y,) + pools + ((None, None) if not quant else ())


def stack_decode_paged(cfg: ModelConfig, stacked: dict, x, kp, vp, ks, vs,
                       tab, pos, *, inv_freq):
    """One-token decode through the scanned stack over paged KV pools.

    kp/vp: [L, n_blocks, bs, nkv, hd]; ks/vs: [L, n_blocks, bs, nkv] fp32
    or None (bf16 pools); tab: [B, mb] int32 (shared by all layers — one
    allocator owns the block ids); pos: [B] int32.
    Returns (y, kp, vp, ks, vs)."""
    return _paged_kv(cfg, L.attn_decode_paged, stacked, x, kp, vp, ks, vs,
                     tab, pos, inv_freq)


def stack_verify_paged(cfg: ModelConfig, stacked: dict, x, kp, vp, ks, vs,
                       tab, pos, *, inv_freq):
    """T-token forward over paged KV pools (speculative verify AND paged
    admission — see ``layers.attn_verify_paged``). x: [B, T, d].
    Returns (y [B, T, d], kp, vp, ks, vs)."""
    return _paged_kv(cfg, L.attn_verify_paged, stacked, x, kp, vp, ks, vs,
                     tab, pos, inv_freq)


def stack_prefill(cfg: ModelConfig, stacked: dict, x, *, inv_freq):
    """Full-sequence forward that also emits per-layer (k, v) decode caches.
    Returns (y, cache_k [L,B,S,nkv,hd], cache_v)."""
    def body(carry, layer_p):
        h = carry
        hn = L.rmsnorm(layer_p["ln1"], h, cfg.norm_eps)
        a, k, v = L.attn_prefill(cfg, layer_p["attn"], hn, inv_freq=inv_freq)
        h = h + a
        hn = L.rmsnorm(layer_p["ln2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            # stack_prefill only feeds serving caches (training runs
            # stack_apply), so the aux loss is never consumed here
            h = h + constrain(M.moe_apply(cfg, layer_p["moe"], hn,
                                          need_aux=False).y,
                              "DP", "M", None)
        else:
            h = h + constrain(L.mlp_apply(layer_p["mlp"], hn),
                              "DP", "M", None)
        return bf16_cotangent(constrain(h, "DP", "M", None)), (k, v)

    body = _maybe_remat(cfg, body)
    y, (ks, vs) = jax.lax.scan(body, x, stacked)
    return y, ks, vs


# ---------------------------------------------------------------------------
# hybrid stack (zamba2): mamba blocks + ONE shared attn+MLP block every k
# ---------------------------------------------------------------------------

def hybrid_init(cfg: ModelConfig, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mamba_ln": _stack_init(
            lambda k: L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            cfg.n_layers, k1),
        "mamba": _stack_init(lambda k: S.mamba_init(cfg, k), cfg.n_layers, k1),
        "shared_ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "shared_attn": L.attn_init(cfg, k2),
        "shared_ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "shared_mlp": L.mlp_init(cfg.d_model, cfg.d_ff, cfg.param_dtype, k3),
    }


def _n_segments(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.hybrid_attn_every


def hybrid_apply(cfg: ModelConfig, p: dict, x, *, inv_freq):
    every = cfg.hybrid_attn_every
    nseg = _n_segments(cfg)

    def mamba_body(h, xs):
        ln, mp = xs
        h = h + S.mamba_apply(cfg, mp, L.rmsnorm(ln, h, cfg.norm_eps))
        return bf16_cotangent(constrain(h, "DP", "M", None)), None

    mamba_body = _maybe_remat(cfg, mamba_body)
    seg_params = jax.tree.map(
        lambda a: a.reshape((nseg, every) + a.shape[1:]), (p["mamba_ln"], p["mamba"]))

    for s_i in range(nseg):
        xs = jax.tree.map(lambda a: a[s_i], seg_params)
        x, _ = jax.lax.scan(mamba_body, x, xs)
        # shared transformer block (weights shared across segments)
        h = x + L.attn_apply(cfg, p["shared_attn"],
                             L.rmsnorm(p["shared_ln1"], x, cfg.norm_eps),
                             inv_freq=inv_freq)
        x = h + L.mlp_apply(p["shared_mlp"],
                            L.rmsnorm(p["shared_ln2"], h, cfg.norm_eps))
    return x


def hybrid_prefill(cfg: ModelConfig, p: dict, x, *, inv_freq):
    """Full-sequence forward emitting the decode cache (per-layer SSM states +
    per-segment shared-attn KV)."""
    every = cfg.hybrid_attn_every
    nseg = _n_segments(cfg)

    def mamba_body(h, xs):
        ln, mp = xs
        out, st = S.mamba_apply(cfg, mp, L.rmsnorm(ln, h, cfg.norm_eps),
                                return_state=True)
        return constrain(h + out, "DP", "M", None), st

    seg_params = jax.tree.map(
        lambda a: a.reshape((nseg, every) + a.shape[1:]),
        (p["mamba_ln"], p["mamba"]))

    ssm_states, ks, vs = [], [], []
    for s_i in range(nseg):
        xs = jax.tree.map(lambda a: a[s_i], seg_params)
        x, sts = jax.lax.scan(mamba_body, x, xs)
        ssm_states.append(sts)
        hn = L.rmsnorm(p["shared_ln1"], x, cfg.norm_eps)
        a, k, v = L.attn_prefill(cfg, p["shared_attn"], hn, inv_freq=inv_freq)
        x = x + a
        x = x + L.mlp_apply(p["shared_mlp"],
                            L.rmsnorm(p["shared_ln2"], x, cfg.norm_eps))
        ks.append(k)
        vs.append(v)
    cache = {
        "ssm": jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *ssm_states),
        "k": jnp.stack(ks),
        "v": jnp.stack(vs),
    }
    return x, cache


def hybrid_decode(cfg: ModelConfig, p: dict, x, cache, pos, *, inv_freq):
    """cache: {"ssm": SSMState stacked [L,...], "k"/"v": [nseg, B, S, nkv, hd]}"""
    every = cfg.hybrid_attn_every
    nseg = _n_segments(cfg)
    new_ssm, new_k, new_v = [], [], []
    for s_i in range(nseg):
        for j in range(every):
            li = s_i * every + j
            ln = jax.tree.map(lambda a: a[li], p["mamba_ln"])
            mp = jax.tree.map(lambda a: a[li], p["mamba"])
            st = jax.tree.map(lambda a: a[li], cache["ssm"])
            out, st = S.mamba_decode(cfg, mp, L.rmsnorm(ln, x, cfg.norm_eps), st)
            x = x + out
            new_ssm.append(st)
        hn = L.rmsnorm(p["shared_ln1"], x, cfg.norm_eps)
        a, ck, cv = L.attn_decode(cfg, p["shared_attn"], hn,
                                  cache["k"][s_i], cache["v"][s_i], pos,
                                  inv_freq=inv_freq)
        x = x + a
        x = x + L.mlp_apply(p["shared_mlp"],
                            L.rmsnorm(p["shared_ln2"], x, cfg.norm_eps))
        new_k.append(ck)
        new_v.append(cv)
    new_cache = {
        "ssm": jax.tree.map(lambda *xs: jnp.stack(xs), *new_ssm),
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
    }
    return x, new_cache


# ---------------------------------------------------------------------------
# encoder-decoder (whisper-style)
# ---------------------------------------------------------------------------

def encdec_init(cfg: ModelConfig, key) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)

    def enc_block(k):
        ka, kb = jax.random.split(k)
        return {
            "ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "attn": L.attn_init(cfg, ka),
            "ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "mlp": L.mlp_init(cfg.d_model, cfg.d_ff, cfg.param_dtype, kb),
        }

    def dec_block(k):
        ka, kb, kc = jax.random.split(k, 3)
        return {
            "ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "self_attn": L.attn_init(cfg, ka),
            "ln_x": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "cross_attn": L.attn_init(cfg, kb),
            "ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "mlp": L.mlp_init(cfg.d_model, cfg.d_ff, cfg.param_dtype, kc),
        }

    return {
        "enc": _stack_init(enc_block, cfg.n_layers, k1),
        "enc_ln": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "dec": _stack_init(dec_block, cfg.n_layers, k2),
    }


def _sinusoid(S: int, d: int) -> jax.Array:
    pos = jnp.arange(S, dtype=F32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=F32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(cfg: ModelConfig, p: dict, frames: jax.Array) -> jax.Array:
    """frames: [B, n_audio_ctx, d] precomputed frame embeddings (conv stub)."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def body(h, layer_p):
        a = L.attn_apply(cfg, layer_p["attn"],
                         L.rmsnorm(layer_p["ln1"], h, cfg.norm_eps),
                         inv_freq=None, causal=False)
        h = h + a
        h = h + L.mlp_apply(layer_p["mlp"],
                            L.rmsnorm(layer_p["ln2"], h, cfg.norm_eps))
        return bf16_cotangent(constrain(h, "DP", "M", None)), None

    body = _maybe_remat(cfg, body)
    x, _ = jax.lax.scan(body, x, p["enc"])
    return L.rmsnorm(p["enc_ln"], x, cfg.norm_eps)


def decode_stack_apply(cfg: ModelConfig, p: dict, x, enc_out, *, inv_freq):
    def body(h, layer_p):
        a = L.attn_apply(cfg, layer_p["self_attn"],
                         L.rmsnorm(layer_p["ln1"], h, cfg.norm_eps),
                         inv_freq=inv_freq, causal=True)
        h = h + a
        c = L.attn_apply(cfg, layer_p["cross_attn"],
                         L.rmsnorm(layer_p["ln_x"], h, cfg.norm_eps),
                         inv_freq=None, kv=enc_out)
        h = h + c
        h = h + L.mlp_apply(layer_p["mlp"],
                            L.rmsnorm(layer_p["ln2"], h, cfg.norm_eps))
        return bf16_cotangent(constrain(h, "DP", "M", None)), None

    body = _maybe_remat(cfg, body)
    y, _ = jax.lax.scan(body, x, p["dec"])
    return y


def decode_stack_step(cfg: ModelConfig, p: dict, x, enc_out, cache_k, cache_v,
                      pos, *, inv_freq):
    def body(h, xs):
        layer_p, ck, cv = xs
        hn = L.rmsnorm(layer_p["ln1"], h, cfg.norm_eps)
        a, ck, cv = L.attn_decode(cfg, layer_p["self_attn"], hn, ck, cv, pos,
                                  inv_freq=inv_freq)
        h = h + a
        c = L.attn_apply(cfg, layer_p["cross_attn"],
                         L.rmsnorm(layer_p["ln_x"], h, cfg.norm_eps),
                         inv_freq=None, kv=enc_out)
        h = h + c
        h = h + L.mlp_apply(layer_p["mlp"],
                            L.rmsnorm(layer_p["ln2"], h, cfg.norm_eps))
        return h, (ck, cv)

    y, (nk, nv) = jax.lax.scan(body, x, (p["dec"], cache_k, cache_v))
    return y, nk, nv
