"""The serving path's Pallas kernels compile for TPU v5e at
qwen3-moe-30b-a3b widths (d=2048, f=768, E=128, top-8).

The TPU compiler runs here for a described ``v5e:2x2`` topology; nothing
executes. This catches what interpret mode accepts and the chip refuses
(unaligned blocks, scoped-VMEM overruns). The topology is described inside
a module fixture, never at import: only one process at a time may load
the TPU library, and every pytest worker imports every test file.
"""
import dataclasses
import re

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
    args: tuple          # (shape, dtype) leaves; QT / QT2 mark int8
                         # tables of one layer / a 2-layer stack

    def shapes(self, sharding):
        def sds(a):
            if a in ("QT", "QT2"):
                return _qt(sharding, stack=(2,) if a == "QT2" else ())
            shape, dt = a
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        return tuple(sds(a) for a in self.args)


def _qt(sharding, n_experts=E, stack=()):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(stack + (n_experts,) + shape, dt,
                                    sharding=sharding)
    return QuantizedExpertTables(
        wg=s((D, F), I8), wg_scale=s((1, F), F32),
        wu=s((D, F), I8), wu_scale=s((1, F), F32),
        wd=s((F, D), I8), wd_scale=s((1, D), F32))


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
    # a decode stack's [L, E, ...] tables and the layer to read
    "gather_swiglu_stacked": Case(
        lambda *a: DM.gather_swiglu(*a),
        (((T_DECODE, D), BF16),)
        + tuple(((2,) + shp, dt) for shp, dt in TABLES) + ROUTE
        + (((), I32),)),
    "gather_swiglu_q_stacked": Case(
        lambda *a: DM.gather_swiglu_q(*a),
        (((T_DECODE, D), BF16), "QT2") + ROUTE + (((), I32),)),
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


#: one HLO instruction: ``%name = <result type> <opcode>(``
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%[\w.-]+\s*=\s*(.*?)\s([a-z][\w-]*)\(")


def _decode_program_text(sharding, cfg, n_slots=32, s_max=128, k_steps=2):
    """Optimized HLO of the engine's fused decode program for ``cfg``,
    compiled from shapes for the described chip (Pallas kernels on)."""
    from repro.launch import steps as ST
    from repro.models import model as MD

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = sds(jax.eval_shape(lambda: MD.init(cfg, jax.random.key(0))))
    cache = sds(jax.eval_shape(lambda: MD.init_slot_cache(cfg, n_slots,
                                                          s_max)))
    vec = lambda dt: jax.ShapeDtypeStruct((n_slots,), dt,  # noqa: E731
                                          sharding=sharding)
    keys = jax.ShapeDtypeStruct((n_slots, 2), jnp.uint32, sharding=sharding)
    multi = jax.jit(ST.make_slot_decode_multi(cfg, k_steps, 0.0))
    return multi.lower(params, cache, vec(I32), vec(jnp.bool_), vec(I32),
                       vec(I32), keys, vec(jnp.bool_)).compile().as_text()


@pytest.mark.parametrize("merged", [0, E // 2], ids=["one-stack",
                                                     "merged-two-stack"])
def test_decode_program_copies_no_expert_tables(one_chip, merged,
                                                monkeypatch):
    """The decode gather kernel reads each layer's experts out of the
    stacked [L, E, d, f] tables in place: no instruction of the compiled
    v5e decode program but a parameter has a per-layer table's shape
    (a scanned slice of the stack would be materialized for the kernel,
    one whole [E, d, f] copy per table, layer and step)."""
    from repro.core import plan as PLAN

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n_slots = 32
    layers = 4 if merged else 2                  # two layers per stack
    cfg = CFG.replace(n_layers=layers, moe=dataclasses.replace(
        CFG.moe, dispatch="gather", gather_max_tokens=n_slots))
    widths = [E]
    if merged:
        cfg = PLAN.uniform(cfg, merged_experts=merged,
                           split=layers // 2).apply_to(cfg)
        widths.append(merged)
    text = _decode_program_text(one_chip, cfg, n_slots=n_slots)
    assert "gather_swiglu" in text
    shapes = {f"bf16[{e},{D},{F}]" for e in widths} | \
        {f"bf16[{e},{F},{D}]" for e in widths}
    copies = []
    for ln in text.splitlines():
        m = _INSTR.match(ln)                     # result type, opcode
        if m and m[2] != "parameter" and any(s in m[1] for s in shapes):
            copies.append(ln.strip()[:160])
    assert not copies, copies[:4]
    # and the stacks themselves only pass through the loops, uncopied
    stacks = {f"bf16[{layers // len(widths)},{e}," for e in widths}
    moved = {m[2] for m in map(_INSTR.match, text.splitlines())
             if m and any(m[1].startswith(s) for s in stacks)}
    assert moved <= {"parameter", "get-tuple-element"}, moved
