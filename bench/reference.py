"""Plain float32 reference of the served model, in ``jax.numpy``.

It follows the configuration file (``model.widths``) and reads the weights
the benchmark drew (``weights.py``) by their names; it imports nothing of
the program. Every matmul runs at ``Precision.HIGHEST``. Layers run one at
a time over chunks of sequences, so the reference fits beside the weights
once the program's state is freed.

Layer: x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x)) with causal
GQA softmax attention; x += Σ_j softmax(top-k router logits)_j ·
SwiGLU_{remap[e_j]}(n2(x)). Then the final RMSNorm and the LM head.

``int8=True`` is the control: the same reference computed one precision
below the configuration's bf16, wherever the served program rounds to bf16:
every linear layer's input and weight (per row of the input, per output
channel of the weight), the residual stream after each add, q, k and v per
row and head, the attention probabilities per query, and each expert's
output are rounded to symmetric int8 (then multiplied exactly).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 256


def _ein(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HI, preferred_element_type=F32)


def _int8(x, axis):
    """Round to symmetric int8 along ``axis`` and back."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _act(x, int8):
    """An activation stored at the control's precision (per row)."""
    return _int8(x, -1) if int8 else x


def _lin(spec, x, w, int8):
    """A linear layer: ``x``'s last axis against ``w``'s axis -2."""
    w = w.astype(F32)
    if int8:
        x, w = _int8(x, -1), _int8(w, -2)
    return _ein(spec, x, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, :, None].astype(F32) * inv              # [B, S, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(spec, int8, x, stack, i):
    w = dict(spec)
    p = jax.tree.map(lambda a: a[i], stack)
    B, S, d = x.shape
    nq, nkv, hd = w["nq"], w["nkv"], w["hd"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    a = p["attn"]
    x = _act(x, int8)
    h = _rms(x, p["ln1"]["scale"], w["eps"])
    q = _lin("bsd,dh->bsh", h, a["wq"], int8).reshape(B, S, nq, hd)
    k = _lin("bsd,dh->bsh", h, a["wk"], int8).reshape(B, S, nkv, hd)
    v = _lin("bsd,dh->bsh", h, a["wv"], int8).reshape(B, S, nkv, hd)
    q, k = _rope(q, pos, w["theta"]), _rope(k, pos, w["theta"])
    q, k, v = _act(q, int8), _act(k, int8), _act(v, int8)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = _ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = _ein("bhqk,bkhd->bqhd", _act(jax.nn.softmax(s, -1), int8), v)
    x = _act(x + _lin("bsh,hd->bsd", o.reshape(B, S, nq * hd), a["wo"],
                      int8), int8)

    m = p["moe"]
    h = _rms(x, p["ln2"]["scale"], w["eps"])
    top, idx = jax.lax.top_k(_lin("bsd,de->bse", h, m["router"], int8),
                             w["k"])
    # each (token, expert) pair, sorted by expert, through its expert
    ex = m["remap"][idx].reshape(-1)
    order = jnp.argsort(ex)
    rows = order // w["k"]
    sizes = jnp.bincount(ex, length=m["wg"].shape[0]).astype(jnp.int32)

    def grouped(a, t):
        t = t.astype(F32)
        if int8:
            a, t = _int8(a, -1), _int8(t, -2)
        return jax.lax.ragged_dot(a, t, sizes, precision=HI,
                                  preferred_element_type=F32)

    xs = h.reshape(B * S, d)[rows]
    e = grouped(jax.nn.silu(grouped(xs, m["wg"])) * grouped(xs, m["wu"]),
                m["wd"])
    wts = jax.nn.softmax(top, -1).reshape(-1)[order]
    y = jnp.zeros((B * S, d), F32).at[rows].add(_act(e, int8) * wts[:, None])
    y = y.reshape(B, S, d)
    return _act(x + y, int8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(spec, int8, x, final_scale, head, targets):
    """Per position: the best logit minus the target token's, and the
    best token."""
    w = dict(spec)
    B, S, d = x.shape
    h = _rms(x, final_scale, w["eps"])

    def chunk(args):
        hc, tc = args
        lg = _lin("bsd,dv->bsv", hc, head, int8)
        tl = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0]
        return jnp.stack([jnp.max(lg, -1) - tl,
                          jnp.argmax(lg, -1).astype(F32)])

    hb = math.gcd(HEAD_BLOCK, S)
    n = S // hb
    hs = jnp.moveaxis(h.reshape(B, n, hb, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, hb), 1, 0)
    out = jax.lax.map(chunk, (hs, ts))               # [n, 2, B, hb]
    return jnp.moveaxis(out, 0, 2).reshape(2, B, S)


@jax.jit
def _embed(tok, tokens):
    return tok[tokens].astype(F32)


def _forward(params, spec, int8, tokens, targets, chunk):
    w = dict(spec)
    xs = [_embed(params["embed"]["tok"], jnp.asarray(tokens[i:i + chunk]))
          for i in range(0, len(tokens), chunk)]
    for l in range(w["L"]):
        key, i = (("stack", l) if l < w["split"]
                  else ("stack_c", l - w["split"]))
        xs = [_layer(spec, int8, x, params[key], jnp.int32(i)) for x in xs]
    out = [_head(spec, int8, x, params["final_ln"]["scale"],
                 params["embed"]["head"],
                 jnp.asarray(targets[c * chunk:(c + 1) * chunk]))
           for c, x in enumerate(xs)]
    return np.concatenate([np.asarray(o) for o in out], axis=1)


def served_gaps(params: dict, spec: tuple, tokens: np.ndarray,
                targets: np.ndarray, chunk: int,
                control: bool = False) -> np.ndarray:
    """Gaps [N, S] over ``tokens`` [N, S] (N a multiple of ``chunk``): at
    every position, how far the reference logit of ``targets`` lies below
    the reference's best. With ``control``, the targets are the tokens the
    int8 control puts first at each position instead."""
    if control:
        targets = _forward(params, spec, True, tokens, targets,
                           chunk)[1].astype(np.int32)
    return _forward(params, spec, False, tokens, targets, chunk)[0]
