"""Seeded weights for the served model, made by the benchmark on the device.

One jitted call draws every leaf from the seed, in the dtype it is served
in, laid out as the program's parameter tree. Layer ``l``'s leaves depend on
``fold_in(key, l)`` alone, so the reference draws the same values by calling
the same compiled program again. The program only receives the arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _layer(key, w: dict, n_real: int, dt) -> dict:
    d, nq, nkv, hd = w["d"], w["nq"], w["nkv"], w["hd"]
    E, f = w["E"], w["f"]
    ks = jax.random.split(key, 12)
    remap = (jnp.arange(E, dtype=jnp.int32) if n_real == E else
             (jax.random.permutation(ks[11], E) % n_real).astype(jnp.int32))
    return {
        "ln1": {"scale": (1.0 + 0.1 * jax.random.normal(ks[0], (d,), F32)
                          ).astype(dt)},
        "attn": {"wq": _normal(ks[1], (d, nq * hd), d ** -0.5, dt),
                 "wk": _normal(ks[2], (d, nkv * hd), d ** -0.5, dt),
                 "wv": _normal(ks[3], (d, nkv * hd), d ** -0.5, dt),
                 "wo": _normal(ks[4], (nq * hd, d), (nq * hd) ** -0.5, dt)},
        "ln2": {"scale": (1.0 + 0.1 * jax.random.normal(ks[5], (d,), F32)
                          ).astype(dt)},
        "moe": {"router": _normal(ks[6], (d, E), d ** -0.5, F32),
                "wg": _normal(ks[7], (n_real, d, f), d ** -0.5, dt),
                "wu": _normal(ks[8], (n_real, d, f), d ** -0.5, dt),
                "wd": _normal(ks[9], (n_real, f, d), f ** -0.5, dt),
                "remap": remap,
                "live": jnp.asarray(n_real, jnp.int32)},
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, seed) -> dict:
    w = dict(spec)
    dt = jnp.dtype(w["dtype"])
    key = jax.random.key(seed)
    k_emb, k_head, k_ln, k_layers = jax.random.split(key, 4)
    layers = [_layer(jax.random.fold_in(k_layers, l), w, w["live"][l], dt)
              for l in range(w["L"])]

    def stack(lo, hi):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers[lo:hi])

    params = {"embed": {"tok": _normal(k_emb, (w["V"], w["d"]), 1.0, dt),
                        "head": _normal(k_head, (w["d"], w["V"]),
                                        w["d"] ** -0.5, dt)},
              "final_ln": {"scale": (1.0 + 0.1 * jax.random.normal(
                  k_ln, (w["d"],), F32)).astype(dt)}}
    if w["split"] > 0:
        params["stack"] = stack(0, w["split"])
    if w["split"] < w["L"]:
        params["stack_c"] = stack(w["split"], w["L"])
    return params


def spec_of(widths: dict, dtype: str) -> tuple:
    """Hashable form of the sizes ``make_params`` is compiled for."""
    return tuple(sorted({**widths, "live": tuple(widths["live"]),
                         "dtype": dtype}.items()))


def make_params(widths: dict, dtype: str, seed: int) -> dict:
    """The whole parameter tree for ``widths`` (``model.widths``) from a
    32-bit ``seed``, on the default device."""
    return _make(spec_of(widths, dtype), jnp.uint32(seed))


def layer_slice(params: dict, widths: dict, l: int) -> dict:
    """Layer ``l``'s leaves out of the stacked tree."""
    key, i = (("stack", l) if l < widths["split"]
              else ("stack_c", l - widths["split"]))
    return jax.tree.map(lambda a: a[i], params[key])
