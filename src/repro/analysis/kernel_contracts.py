"""Static Pallas kernel contract checker (DESIGN.md §9).

For every ``pallas_dispatch``-registered kernel, validate — against the
shapes induced by **every** entry in ``repro/configs/`` — the structural
invariants the kernels rely on, WITHOUT executing a single kernel:

* **BlockSpec divisibility**: every operand dimension is divisible by its
  block dimension (Pallas pads silently in interpret mode; on TPU a
  non-dividing block is a launch failure or worse, garbage reads).
* **Grid coverage**: the output index map, enumerated over the full grid,
  writes every output block (a grid that under-covers returns
  uninitialized HBM).
* **Index-map bounds**: every (grid point, spec) pair lands fully
  in-bounds, *including* scalar-prefetch tables evaluated at their extreme
  legal values 0 and E-1 — the §7 contract that OOB-clipped expert ids and
  dropped admission-pad rows keep every gather in-bounds by construction.
  (E, per table, is the block count of the operand dims the table selects:
  dim 0 for expert/slot ids of one table, dims 0 and 1 for the layer index
  and expert ids of a stacked ``[L, E, ...]`` one.)
* **VMEM footprint**: the single-buffered sum of all VMEM-resident blocks
  plus scratch against a per-kernel budget (default 16 MiB, the per-core
  VMEM size). Known exceedances at full-size configs are *waived* with a
  one-line reason in :data:`VMEM_WAIVERS` — the kernels' default
  ``block_t``/``block_f`` target test-scale shapes, and a real TPU launch
  at those configs must pass smaller blocks; the waiver records exactly
  where that cliff is instead of letting the check rot.
* **§8 dtype contract**: quantized kernels take int8 tables + fp32 scale
  rows in; all scratch accumulators are fp32; the kernel body downcasts to
  the output dtype EXACTLY once (checked on the kernel's AST — the
  bitwise kernel==oracle story dies the moment a second rounding appears).

Mechanism: ``pl.pallas_call`` is monkeypatched to a recorder while the
kernel-module implementation (unwrapped from ``jax.jit`` via
``__wrapped__`` so no jit cache is touched) is traced with
``jax.eval_shape``. The recorder captures grid/specs/operand avals and
returns abstract zeros, so nothing ever executes.
"""
from __future__ import annotations

import ast
import contextlib
import dataclasses
import functools
import importlib
import inspect
import itertools
import textwrap
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ContractFinding", "ContractReport", "check_kernel_contracts",
           "VMEM_WAIVERS"]

VMEM_BUDGET_BYTES = 16 * 1024 * 1024      # per-core VMEM (pallas guide)

# (kernel, arch) -> one-line reason. These are REAL exceedances of the
# 16 MiB budget at the kernels' default block sizes; a TPU launch at these
# configs must pass smaller block_t/block_f (the gather kernel additionally
# needs an f-blocked variant for kimi-scale experts — ROADMAP int4 work).
VMEM_WAIVERS: Dict[Tuple[str, str], str] = {
    ("swiglu_mlp", "yi_34b"):
        "d=7168 rows at default bf=512 blocks: ~28 MiB; TPU launch shrinks "
        "block_t/block_f",
    ("swiglu_mlp", "qwen1_5_110b"):
        "d=8192/f=49152 at default blocks: ~32 MiB; TPU launch shrinks "
        "block_t/block_f",
    ("swiglu_mlp", "phi3_medium_14b"):
        "d=5120/f=17920 at default blocks: ~20 MiB; TPU launch shrinks "
        "block_t/block_f",
    ("grouped_swiglu", "kimi_k2_1t_a32b"):
        "d=7168 expert blocks at default bf=512: ~28 MiB; TPU launch "
        "shrinks block_t/block_f",
    ("grouped_swiglu_q", "kimi_k2_1t_a32b"):
        "int8 halves weight blocks but d=7168 x/acc rows still ~18 MiB; "
        "TPU launch shrinks block_t",
    ("gather_swiglu", "kimi_k2_1t_a32b"):
        "gather streams UNBLOCKED [d=7168, f=2048] expert tables (~84 MiB); "
        "needs the f-blocked gather variant before kimi decode on TPU",
    ("gather_swiglu_q", "kimi_k2_1t_a32b"):
        "int8 gather still streams unblocked expert tables (~42 MiB); "
        "needs the f-blocked gather variant before kimi decode on TPU",
}


@dataclasses.dataclass(frozen=True)
class ContractFinding:
    kernel: str
    arch: str
    check: str          # divisibility | coverage | bounds | vmem | dtype
    msg: str

    def format(self) -> str:
        return f"{self.kernel} @ {self.arch}: [{self.check}] {self.msg}"


@dataclasses.dataclass
class ContractReport:
    findings: List[ContractFinding]
    waived: List[ContractFinding]
    checked: List[Tuple[str, str]]          # (kernel, arch) pairs validated

    @property
    def ok(self) -> bool:
        return not self.findings


# ---------------------------------------------------------------------------
# pallas_call capture
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Capture:
    kernel_fn: Any
    grid: Tuple[int, ...]
    in_specs: Sequence[Any]
    out_spec: Any
    out_shape: Any
    scratch: Sequence[Any]
    num_prefetch: int
    operands: Tuple[jax.ShapeDtypeStruct, ...]


@contextlib.contextmanager
def _capture_pallas(records: List[_Capture]):
    """Monkeypatch ``pl.pallas_call`` to record its configuration and
    return abstract zeros. Kernel modules import ``pallas as pl`` and call
    ``pl.pallas_call`` at call time, so patching the module attribute
    covers them all."""
    orig = pl.pallas_call

    def fake(kernel, *, out_shape, grid=None, grid_spec=None, in_specs=None,
             out_specs=None, scratch_shapes=None, interpret=False, **kw):
        if grid_spec is not None:
            g = getattr(grid_spec, "grid", None)
            ins = getattr(grid_spec, "in_specs", None)
            outs = getattr(grid_spec, "out_specs", None)
            scratch = getattr(grid_spec, "scratch_shapes", None) or ()
            npf = getattr(grid_spec, "num_scalar_prefetch", 0)
        else:
            g, ins, outs = grid, in_specs, out_specs
            scratch = scratch_shapes or ()
            npf = 0
        if isinstance(g, int):
            g = (g,)
        out_spec = outs[0] if isinstance(outs, (list, tuple)) else outs

        def runner(*operands):
            records.append(_Capture(
                kernel_fn=kernel, grid=tuple(int(d) for d in g),
                in_specs=tuple(ins), out_spec=out_spec, out_shape=out_shape,
                scratch=tuple(scratch), num_prefetch=int(npf),
                operands=tuple(jax.ShapeDtypeStruct(tuple(o.shape), o.dtype)
                               for o in operands)))
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape)
        return runner

    pl.pallas_call = fake
    try:
        yield
    finally:
        pl.pallas_call = orig


# ---------------------------------------------------------------------------
# per-capture checks
# ---------------------------------------------------------------------------

def _is_smem(spec) -> bool:
    return "smem" in str(getattr(spec, "memory_space", "")).lower()


def _block_shape(spec, op_shape) -> Tuple[int, ...]:
    bs = tuple(getattr(spec, "block_shape", None) or op_shape)
    return tuple(op_shape[i] if b is None else int(b)
                 for i, b in enumerate(bs))


def _grid_points(grid: Tuple[int, ...], cap: int = 500_000):
    total = int(np.prod(grid)) if grid else 0
    if total > cap:
        return None
    return itertools.product(*(range(g) for g in grid))


def _table_fills(cap: _Capture) -> List[List[np.ndarray]]:
    """Synthetic scalar-prefetch tables at extreme legal values.

    Tables in this tree hold block indices (a layer index, expert or slot
    ids) for the operands their index maps gather; the §5/§7 clip contract
    bounds each to [0, n-1], with n its dims' block count. n differs per
    operand, so each table's high fill is the MINIMUM block count over the
    operand dims it addresses (those whose block index moves when that
    table alone does; all operands' dim 0 when none does) — the tightest
    legal extreme any spec could be asked to honor."""
    tables = cap.operands[:cap.num_prefetch]
    if not tables:
        return [[]]
    zeros = [np.zeros(t.shape, np.dtype(t.dtype)) for t in tables]
    at0 = (0,) * len(cap.grid)

    def blocks(imap, fills):
        idx = imap(*at0, *fills)
        return tuple(int(v) for v in
                     (idx if isinstance(idx, tuple) else (idx,)))

    specs = [(op, spec, _block_shape(spec, op.shape))
             for op, spec in zip(cap.operands[cap.num_prefetch:],
                                 cap.in_specs)]
    counts = [op.shape[0] // bs[0] for op, _, bs in specs
              if bs and bs[0] and op.shape]
    highs = []
    for n, t in enumerate(tables):
        ones = list(zeros)
        ones[n] = np.ones(t.shape, np.dtype(t.dtype))
        addressed = []
        for op, spec, bs in specs:
            imap = getattr(spec, "index_map", None)
            if imap is None or not bs:
                continue
            lo, hi = blocks(imap, zeros), blocks(imap, ones)
            addressed += [op.shape[d] // bs[d] for d in range(len(lo))
                          if lo[d] != hi[d]]
        highs.append(max(min(addressed or counts or [1]) - 1, 0))
    return [zeros, [np.full(t.shape, h, np.dtype(t.dtype))
                    for t, h in zip(tables, highs)]]


def _check_capture(cap: _Capture, kernel: str, arch: str,
                   contract: Dict[str, Any]) -> Iterable[ContractFinding]:
    quantized = contract.get("quantized", False)
    ops_for_specs = cap.operands[cap.num_prefetch:]
    if len(ops_for_specs) != len(cap.in_specs):
        yield ContractFinding(kernel, arch, "divisibility",
                              f"{len(ops_for_specs)} operands vs "
                              f"{len(cap.in_specs)} in_specs")
        return
    out_sds = jax.tree.leaves(cap.out_shape)[0]
    pairs = list(zip(ops_for_specs, cap.in_specs)) + [(out_sds, cap.out_spec)]

    # ---- divisibility
    for i, (op, spec) in enumerate(pairs):
        bs = _block_shape(spec, op.shape)
        if len(bs) != len(op.shape):
            yield ContractFinding(
                kernel, arch, "divisibility",
                f"operand {i}: block rank {len(bs)} vs shape {op.shape}")
            continue
        for d, (o, b) in enumerate(zip(op.shape, bs)):
            if b <= 0 or o % b:
                yield ContractFinding(
                    kernel, arch, "divisibility",
                    f"operand {i} dim {d}: {o} not divisible by block {b}")

    pts = _grid_points(cap.grid)
    if pts is None:
        yield ContractFinding(kernel, arch, "coverage",
                              f"grid {cap.grid} too large to enumerate")
        return
    pts = list(pts)
    fills = _table_fills(cap)

    # ---- index-map bounds (all specs, both table extremes)
    for i, (op, spec) in enumerate(pairs):
        imap = getattr(spec, "index_map", None)
        if imap is None:
            continue
        bs = _block_shape(spec, op.shape)
        nblocks = [max(o // b, 1) for o, b in zip(op.shape, bs)]
        bad = None
        for tables in fills:
            for pt in pts:
                idx = imap(*pt, *tables)
                idx = idx if isinstance(idx, tuple) else (idx,)
                for d, v in enumerate(idx):
                    v = int(v)
                    if v < 0 or v >= nblocks[d]:
                        bad = (pt, d, v, nblocks[d])
                        break
                if bad:
                    break
            if bad:
                break
        if bad:
            pt, d, v, nb = bad
            yield ContractFinding(
                kernel, arch, "bounds",
                f"operand {i} index map at grid {pt}: block index {v} on "
                f"dim {d} outside [0, {nb})")

    # ---- output grid coverage
    out_spec = cap.out_spec
    imap = getattr(out_spec, "index_map", None)
    if imap is not None:
        bs = _block_shape(out_spec, out_sds.shape)
        required = set(itertools.product(
            *(range(max(o // b, 1)) for o, b in zip(out_sds.shape, bs))))
        got = set()
        for pt in pts:
            idx = imap(*pt, *fills[0])
            got.add(tuple(int(v) for v in
                          (idx if isinstance(idx, tuple) else (idx,))))
        missing = required - got
        if missing:
            yield ContractFinding(
                kernel, arch, "coverage",
                f"{len(missing)}/{len(required)} output blocks never "
                f"written (e.g. {sorted(missing)[0]})")

    # ---- VMEM footprint (single-buffered blocks + scratch)
    vmem = 0
    for op, spec in pairs:
        if _is_smem(spec):
            continue
        bs = _block_shape(spec, op.shape)
        vmem += int(np.prod(bs)) * np.dtype(op.dtype).itemsize
    for s in cap.scratch:
        shape = tuple(getattr(s, "shape", ()))
        dt = getattr(s, "dtype", np.float32)
        if "smem" not in type(s).__name__.lower():
            vmem += int(np.prod(shape) if shape else 1) * \
                np.dtype(dt).itemsize
    if vmem > VMEM_BUDGET_BYTES:
        yield ContractFinding(
            kernel, arch, "vmem",
            f"estimated VMEM {vmem / 2**20:.1f} MiB exceeds "
            f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget")

    # ---- §8 dtype contract
    x = ops_for_specs[0]
    if np.dtype(out_sds.dtype) != np.dtype(x.dtype):
        yield ContractFinding(
            kernel, arch, "dtype",
            f"output dtype {out_sds.dtype} != input dtype {x.dtype} "
            f"(the one downcast must land AT the model dtype)")
    for s in cap.scratch:
        dt = getattr(s, "dtype", None)
        if dt is not None and np.dtype(dt) != np.float32:
            yield ContractFinding(
                kernel, arch, "dtype",
                f"scratch accumulator dtype {dt} is not float32")
    if quantized:
        # operand-count expectations live in the contract metadata so kernel
        # families with different quantized layouts (3 expert tables vs 2 KV
        # pools) share one check; defaults are the expert-table family's.
        want_i8 = int(contract.get("int8_operands", 3))
        want_f32 = int(contract.get("f32_min_operands", 3))
        n_i8 = sum(np.dtype(o.dtype) == np.int8 for o in ops_for_specs)
        n_f32 = sum(np.dtype(o.dtype) == np.float32 for o in ops_for_specs)
        if n_i8 != want_i8 or n_f32 < want_f32:
            yield ContractFinding(
                kernel, arch, "dtype",
                f"quantized kernel expects {want_i8} int8 tables + "
                f">={want_f32} fp32 scale rows, saw {n_i8} int8 / "
                f"{n_f32} fp32 operands")
    yield from _check_kernel_body(cap, kernel, arch, quantized)


def _check_kernel_body(cap: _Capture, kernel: str, arch: str,
                       quantized: bool) -> Iterable[ContractFinding]:
    """AST checks on the kernel body: exactly one `.astype(o_ref.dtype)`
    downcast; fp32-internal arithmetic (preferred_element_type=F32 on every
    dot, or operands pre-cast to F32 in the quantized kernels)."""
    fn = cap.kernel_fn
    while isinstance(fn, functools.partial):
        fn = fn.func
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return
    tree = ast.parse(src)
    downcasts = 0
    dots = 0
    dots_f32 = 0
    casts_f32 = 0
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "astype":
            arg = node.args[0] if node.args else None
            if (isinstance(arg, ast.Attribute) and arg.attr == "dtype"
                    and isinstance(arg.value, ast.Name)
                    and arg.value.id == "o_ref"):
                downcasts += 1
            elif isinstance(arg, ast.Name) and arg.id in ("F32", "f32"):
                casts_f32 += 1
        if isinstance(f, ast.Attribute) and f.attr == "dot":
            dots += 1
            if any(kw.arg == "preferred_element_type"
                   for kw in node.keywords):
                dots_f32 += 1
    if downcasts != 1:
        yield ContractFinding(
            kernel, arch, "dtype",
            f"kernel body `{getattr(fn, '__name__', '?')}` has {downcasts} "
            f"`.astype(o_ref.dtype)` downcasts; the §8 contract requires "
            f"exactly one")
    if dots and dots_f32 < dots and not casts_f32:
        yield ContractFinding(
            kernel, arch, "dtype",
            f"kernel body `{getattr(fn, '__name__', '?')}`: {dots - dots_f32}"
            f"/{dots} jnp.dot calls neither request "
            f"preferred_element_type=F32 nor operate on pre-cast fp32 "
            f"operands")


# ---------------------------------------------------------------------------
# config -> induced shapes
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _qexp(E: int, d: int, f: int, stack: tuple = ()):
    from repro.core.quant import QuantizedExpertTables
    i8, f32 = jnp.int8, jnp.float32
    s = lambda *shape: stack + (E,) + shape  # noqa: E731
    return QuantizedExpertTables(
        wg=_sds(s(d, f), i8), wg_scale=_sds(s(1, f), f32),
        wu=_sds(s(d, f), i8), wu_scale=_sds(s(1, f), f32),
        wd=_sds(s(f, d), i8), wd_scale=_sds(s(1, d), f32))


def _induced_cases(kind: str, cfg) -> List[Tuple[str, tuple]]:
    """(case label, eval_shape args) pairs a config induces for a kernel
    kind; empty when the config has no such layer."""
    dt = cfg.param_dtype
    d = cfg.d_model
    if kind == "swiglu":
        if not cfg.d_ff:
            return []
        f = cfg.d_ff
        return [("T128", (_sds((128, d), dt), _sds((d, f), dt),
                          _sds((d, f), dt), _sds((f, d), dt)))]
    if kind in ("grouped", "grouped_q"):
        if cfg.moe is None:
            return []
        E, f = cfg.moe.n_experts, cfg.moe.d_ff_expert
        gs = _sds((E,), jnp.int32)
        cases = []
        for T in (16, 64):
            x = _sds((T, d), dt)
            if kind == "grouped":
                w = dt
                cases.append((f"T{T}", (x, _sds((E, d, f), w),
                                        _sds((E, d, f), w),
                                        _sds((E, f, d), w), gs)))
            else:
                cases.append((f"T{T}", (x, _qexp(E, d, f), gs)))
        return cases
    if kind in ("gather", "gather_q"):
        if cfg.moe is None:
            return []
        E, f, k = cfg.moe.n_experts, cfg.moe.d_ff_expert, cfg.moe.top_k
        cases = []
        for T in (1, 4):
            x = _sds((T, d), dt)
            idx = _sds((T, k), jnp.int32)
            w = _sds((T, k), jnp.float32)
            layer = _sds((), jnp.int32)
            if kind == "gather":               # a decode stack's 2 layers
                cases.append((f"T{T}", (x, _sds((2, E, d, f), dt),
                                        _sds((2, E, d, f), dt),
                                        _sds((2, E, f, d), dt), idx, w,
                                        layer)))
            else:
                cases.append((f"T{T}", (x, _qexp(E, d, f, stack=(2,)), idx,
                                        w, layer)))
        return cases
    if kind == "flash":
        if cfg.is_attention_free:
            return []
        H, hd, S = cfg.n_heads, cfg.hd, 256
        qkv = [_sds((1, H, S, hd), dt)] * 3
        return [("S256", tuple(qkv))]
    if kind in ("paged", "paged_q"):
        if cfg.is_attention_free:
            return []
        nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        B, bs, mb, nb = 4, 16, 8, 32         # pool shape is arch-independent
        q = _sds((B, nq, hd), dt)
        tab = _sds((B, mb), jnp.int32)
        lens = _sds((B,), jnp.int32)
        if kind == "paged":
            kv = _sds((nb, bs, nkv, hd), dt)
            return [("B4", (q, kv, kv, tab, lens))]
        kv = _sds((nb, bs, nkv, hd), jnp.int8)
        sc = _sds((nb, bs, nkv), jnp.float32)
        return [("B4", (q, kv, kv, sc, sc, tab, lens))]
    raise ValueError(f"unknown kernel kind {kind!r}")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def check_kernel_contracts(arch_ids: Optional[Sequence[str]] = None
                           ) -> ContractReport:
    """Validate every registered kernel against every config (or the given
    arch ids). Pure abstract evaluation — no kernel executes."""
    from repro import configs
    from repro.kernels import ops as kops

    findings: List[ContractFinding] = []
    waived: List[ContractFinding] = []
    checked: List[Tuple[str, str]] = []
    archs = list(arch_ids) if arch_ids is not None else list(configs.ARCH_IDS)

    for name, info in sorted(kops.KERNEL_REGISTRY.items()):
        contract = info.contract
        if contract is None:
            continue
        mod = importlib.import_module(f"repro.kernels.{info.module}")
        impl = getattr(mod, name)
        impl = getattr(impl, "__wrapped__", impl)   # bypass jit + its cache
        for arch in archs:
            cfg = configs.get(arch)
            cases = _induced_cases(contract["kind"], cfg)
            if not cases:
                continue
            for label, args in cases:
                records: List[_Capture] = []
                # a fresh wrapper per trace: eval_shape caches on function
                # identity, and a cache hit would skip tracing entirely —
                # the recorder would see nothing on a second checker run
                with _capture_pallas(records):
                    if contract["kind"] == "flash":
                        for causal in (True, False):
                            jax.eval_shape(
                                lambda *a, _c=causal: impl(*a, causal=_c),
                                *args)
                    else:
                        jax.eval_shape(lambda *a: impl(*a), *args)
                if not records:
                    findings.append(ContractFinding(
                        name, arch, "coverage",
                        f"no pallas_call reached tracing `{name}` "
                        f"({label}) — dispatch policy regression?"))
                    continue
                for cap in records:
                    for f in _check_capture(cap, name, arch, contract):
                        reason = VMEM_WAIVERS.get((name, arch))
                        if f.check == "vmem" and reason:
                            waived.append(dataclasses.replace(
                                f, msg=f"{f.msg} — waived: {reason}"))
                        else:
                            findings.append(f)
            checked.append((name, arch))
    # dedupe (multiple cases / captures can repeat a finding verbatim)
    findings = sorted(set(findings),
                      key=lambda f: (f.kernel, f.arch, f.check, f.msg))
    waived = sorted(set(waived),
                    key=lambda f: (f.kernel, f.arch, f.check, f.msg))
    return ContractReport(findings, waived, checked)
