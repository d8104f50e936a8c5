"""The serving path's Pallas kernels compile for TPU v5e at
qwen3-moe-30b-a3b widths (d=2048, f=768, E=128, top-8).

The TPU compiler runs here for a described ``v5e:2x2`` topology; nothing
executes. This catches what interpret mode accepts and the chip refuses
(unaligned blocks, scoped-VMEM overruns). The topology is described inside
a module fixture, never at import: only one process at a time may load
the TPU library, and every pytest worker imports every test file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.quant import QuantizedExpertTables
from repro.kernels import decode_moe as DM
from repro.kernels import grouped_mlp as GM
from repro.kernels import ops
from repro.kernels import paged_attention as PA

CFG = configs.get("qwen3-moe-30b-a3b")
D, F = CFG.d_model, CFG.moe.d_ff_expert
E, K = CFG.moe.n_experts, CFG.moe.top_k
NQ, NKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.hd
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache; keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@dataclasses.dataclass(frozen=True)
class Case:
    fn: object
    args: tuple          # (shape, dtype) leaves; QT marks int8 tables

    def shapes(self, sharding):
        def sds(a):
            if a == "QT":
                return _qt(sharding)
            shape, dt = a
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        return tuple(sds(a) for a in self.args)


def _qt(sharding, n_experts=E):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return QuantizedExpertTables(
        wg=s((n_experts, D, F), I8), wg_scale=s((n_experts, 1, F), F32),
        wu=s((n_experts, D, F), I8), wu_scale=s((n_experts, 1, F), F32),
        wd=s((n_experts, F, D), I8), wd_scale=s((n_experts, 1, D), F32))


T_DECODE, T_PREFILL, B, BS, MB = 8, 256, 8, 16, 16
NB = B * MB
TABLES = (((E, D, F), BF16), ((E, D, F), BF16), ((E, F, D), BF16))
ROUTE = (((T_DECODE, K), I32), ((T_DECODE, K), F32))
POOL = ((NB, BS, NKV, HD), BF16)
POOL_Q = ((NB, BS, NKV, HD), I8)
SCALES = ((NB, BS, NKV), F32)
PAGE = (((B, MB), I32), ((B,), I32))

CASES = {
    "gather_swiglu": Case(
        lambda *a: DM.gather_swiglu(*a),
        (((T_DECODE, D), BF16),) + TABLES + ROUTE),
    "gather_swiglu_q": Case(
        lambda *a: DM.gather_swiglu_q(*a),
        (((T_DECODE, D), BF16), "QT") + ROUTE),
    "grouped_swiglu": Case(
        lambda *a: GM.grouped_swiglu(*a),
        (((T_PREFILL, D), BF16),) + TABLES + (((E,), I32),)),
    "grouped_swiglu_q": Case(
        lambda *a: GM.grouped_swiglu_q(*a),
        (((T_PREFILL, D), BF16), "QT", ((E,), I32))),
    "paged_attention": Case(
        lambda *a: PA.paged_attention(*a),
        (((B, NQ, HD), BF16), POOL, POOL) + PAGE),
    "paged_attention_q": Case(
        lambda *a: PA.paged_attention_q(*a),
        (((B, NQ, HD), BF16), POOL_Q, POOL_Q, SCALES, SCALES) + PAGE),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    case = CASES[name]
    compiled = jax.jit(case.fn).lower(*case.shapes(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True])
def test_sharded_gather_compiles_for_v5e(one_chip, quantized):
    """The expert-parallel view: half the expert table (model=2), global
    ids localized by ``ops.localize_expert_ids`` as the ``_sharded``
    wrappers do, then the same kernel."""
    e_loc = E // 2

    def step(x, tables, idx, w, e_base):
        lid, wl = ops.localize_expert_ids(idx, w, e_base, e_loc)
        if quantized:
            return DM.gather_swiglu_q(x, tables, lid, wl)
        return DM.gather_swiglu(x, *tables, lid, wl)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tables = (_qt(one_chip, e_loc) if quantized else
              tuple(s((e_loc,) + shp[1:], dt) for shp, dt in TABLES))
    compiled = jax.jit(step).lower(
        s((T_DECODE, D), BF16), tables, s((T_DECODE, K), I32),
        s((T_DECODE, K), F32), s((), I32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
