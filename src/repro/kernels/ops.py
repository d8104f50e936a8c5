"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy (ONE place, the :func:`pallas_dispatch` decorator): on TPU
backends the Pallas implementations run natively; on CPU (this container)
they run through the jnp oracle by default, while tests exercise the kernel
bodies via ``interpret=True``. The decorated function body IS the oracle
call, and the Pallas implementation is resolved lazily from the named
kernel module under the same public name — so adding a kernel variant is
one decorated two-liner, not a fifth copy of the policy.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import inspect
from typing import Any, Callable, Dict, Optional

import jax

from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class KernelInfo:
    """Registry entry for one dispatched kernel: where its Pallas impl
    lives and the contract the static checker
    (``repro.analysis.kernel_contracts``) validates against every config."""
    name: str
    module: str                      # module under repro.kernels
    fn: Callable                     # the public dispatch wrapper
    extra_static: tuple
    contract: Optional[Dict[str, Any]]


#: every ``pallas_dispatch``-decorated kernel, by public name. The analysis
#: layer iterates this — registration IS the opt-in to contract checking.
KERNEL_REGISTRY: Dict[str, KernelInfo] = {}


def pallas_dispatch(kernel_module: str, extra_static: tuple = (),
                    contract: Optional[Dict[str, Any]] = None):
    """Decorator factory implementing the interpret/TPU dispatch policy.

    ``kernel_module``: module under ``repro.kernels`` holding the Pallas
    implementation, looked up lazily (Pallas imports stay off the default
    CPU path) under the decorated function's name. ``extra_static``: names
    of oracle parameters to treat as jit-static alongside ``interpret``;
    they may be passed positionally OR by keyword — a thin unjitted shim
    rebinds positionals against the oracle's signature so jit always sees
    them as static kwargs (the pre-decorator wrappers accepted positional
    ``causal``; silently tracing it would turn ``if causal:`` into a
    TracerBoolConversionError). The decorated body is the jnp-oracle
    fallback. ``contract``: shape/dtype contract metadata consumed by the
    static kernel checker — ``kind`` names the shape family the configs
    induce, ``quantized`` marks int8-table kernels.
    """
    def deco(oracle_fn):
        name = oracle_fn.__name__
        param_names = tuple(inspect.signature(oracle_fn).parameters)

        @functools.partial(jax.jit,
                           static_argnames=("interpret",) + extra_static)
        def jitted(*args, interpret: bool = False, **kw):
            if _on_tpu() or interpret:
                mod = importlib.import_module(f"repro.kernels.{kernel_module}")
                return getattr(mod, name)(*args, interpret=not _on_tpu(),
                                          **kw)
            return oracle_fn(*args, **kw)

        def _register(public):
            KERNEL_REGISTRY[name] = KernelInfo(
                name=name, module=kernel_module, fn=public,
                extra_static=extra_static, contract=contract)
            return public

        if not extra_static:
            jitted.__name__ = name
            jitted.__doc__ = oracle_fn.__doc__
            return _register(jitted)

        def wrapper(*args, **kw):
            # keywordize everything from the first positionally-passed
            # static param onward (positional slots cannot be skipped)
            cut = next((i for i, p in enumerate(param_names[:len(args)])
                        if p in extra_static), len(args))
            for i in range(cut, len(args)):
                kw[param_names[i]] = args[i]
            return jitted(*args[:cut], **kw)

        wrapper.__name__ = name
        wrapper.__doc__ = oracle_fn.__doc__
        return _register(wrapper)
    return deco


@pallas_dispatch("swiglu", contract={"kind": "swiglu", "quantized": False})
def swiglu_mlp(x, wg, wu, wd):
    return ref.swiglu_mlp(x, wg, wu, wd)


@pallas_dispatch("grouped_mlp", contract={"kind": "grouped",
                                          "quantized": False})
def grouped_swiglu(x, wg, wu, wd, group_sizes):
    return ref.grouped_swiglu(x, wg, wu, wd, group_sizes)


@pallas_dispatch("decode_moe", contract={"kind": "gather",
                                         "quantized": False})
def gather_swiglu(x, wg, wu, wd, idx, w, layer=0):
    """Decode-mode gather SwiGLU over layer ``layer`` of stacked
    ``[L, E, ...]`` expert tables, read in place (DESIGN.md §7)."""
    return ref.gather_swiglu(x, wg, wu, wd, idx, w, layer)


@pallas_dispatch("grouped_mlp", contract={"kind": "grouped_q",
                                          "quantized": True})
def grouped_swiglu_q(x, qt, group_sizes):
    """Int8 grouped SwiGLU over a ``QuantizedExpertTables`` (DESIGN.md §8)."""
    return ref.grouped_swiglu_q(x, qt, group_sizes)


@pallas_dispatch("decode_moe", contract={"kind": "gather_q",
                                         "quantized": True})
def gather_swiglu_q(x, qt, idx, w, layer=0):
    """Int8 decode-mode gather SwiGLU over a ``QuantizedExpertTables``."""
    return ref.gather_swiglu_q(x, qt, idx, w, layer)


# ---------------------------------------------------------------------------
# expert-parallel (sharded-table) views of the gather kernels
# ---------------------------------------------------------------------------

def localize_expert_ids(idx, w, e_base, e_local: int):
    """Map GLOBAL real-expert ids onto this shard's LOCAL table rows.

    ``idx``: [T, k] int32 global ids; ``e_base``: traced scalar — the first
    global row this shard stores (``axis_index * e_local`` under shard_map);
    ``e_local``: static local row count. Rows owned elsewhere clip into
    range with their combine weight zeroed, so the kernels compute a
    contribution of exactly fp 0.0 for them — the combine stays bitwise
    whatever the foreign rows gather (DESIGN.md §13).
    """
    import jax.numpy as jnp
    lid = idx - e_base
    mine = (lid >= 0) & (lid < e_local)
    return jnp.clip(lid, 0, e_local - 1), jnp.where(mine, w, 0.0)


def gather_swiglu_sharded(x, wg, wu, wd, idx, w, e_base, layer=0):
    """:func:`gather_swiglu` over one EP shard's expert-table slice
    (``[L, E_local, ...]`` or one layer's ``[E_local, ...]``).

    Same per-row arithmetic; ``idx`` stays in GLOBAL expert space and is
    offset by ``e_base`` (this shard's first row) before the gather."""
    lid, w = localize_expert_ids(idx, w, e_base, wg.shape[-3])
    return gather_swiglu(x, wg, wu, wd, lid, w, layer)


def gather_swiglu_q_sharded(x, qt, idx, w, e_base, layer=0):
    """Int8 variant of :func:`gather_swiglu_sharded` (qexp table slice)."""
    lid, w = localize_expert_ids(idx, w, e_base, qt.wg.shape[-3])
    return gather_swiglu_q(x, qt, lid, w, layer)


@pallas_dispatch("flash_attention", extra_static=("causal",),
                 contract={"kind": "flash", "quantized": False})
def flash_attention(q, k, v, causal: bool = True):
    return ref.flash_attention(q, k, v, causal=causal)


@pallas_dispatch("paged_attention", contract={"kind": "paged",
                                              "quantized": False})
def paged_attention(q, kp, vp, tab, lens):
    """Paged decode attention over a block pool (DESIGN.md §11)."""
    return ref.paged_attention(q, kp, vp, tab, lens)


@pallas_dispatch("paged_attention", contract={"kind": "paged_q",
                                              "quantized": True,
                                              "int8_operands": 2,
                                              "f32_min_operands": 2})
def paged_attention_q(q, kp, vp, ks, vs, tab, lens):
    """Int8-pool paged decode attention with per-(row, head) fp32 scales."""
    return ref.paged_attention_q(q, kp, vp, ks, vs, tab, lens)
