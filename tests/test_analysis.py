"""Static-analysis layer (DESIGN.md §9): the AST linter fires each rule on
a seeded fixture and stays at zero findings on the repo tree; the kernel
contract checker validates every registered kernel against every config
without executing one, and rejects crafted contract violations; the trace
guard counts retraces and implicit transfers (and raises in strict mode).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (TraceGuard, TraceGuardError,
                            check_kernel_contracts, run_lint)
from repro.analysis.kernel_contracts import (_Capture, _check_capture,
                                             VMEM_WAIVERS)
from repro.analysis.lint import Analyzer, load_modules

# one seeded violation per rule, plus a suppressed one (the CLI fixture the
# acceptance criteria name)
FIXTURE_BAD = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pdb
    from jax.experimental import pallas as pl


    def hot(x):
        y = jnp.sum(x)
        v = y.item()                      # RA001
        f = float(y)                      # RA002
        if y > 0:                         # RA003
            y = y + 1
        z = np.square(y)                  # RA004
        jax.debug.print("y={}", y)        # RA005
        return y + f + v + z


    step = jax.jit(hot)


    @jax.jit
    def branchy(x, flag):
        if flag:                          # RA006
            return x + 1
        return x


    def rogue(x):
        return pl.pallas_call(lambda r, o: None, out_shape=None)(x)  # RA007


    def ok_suppressed(x):
        y = jnp.sum(x)
        return float(y)  # lint: ignore[RA002] host metric readout


    ok = jax.jit(ok_suppressed)
""")
FIXTURE_IMPORT = "from repro.kernels import grouped_mlp  # RA008\n"

ALL_RULES = {f"RA00{i}" for i in range(1, 9)}


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("lintfix")
    pkg = root / "repro"
    pkg.mkdir()
    (pkg / "bad.py").write_text(FIXTURE_BAD)
    (pkg / "bad_import.py").write_text(FIXTURE_IMPORT)
    return str(root)


# ---------------------------------------------------------------------------
# linter
# ---------------------------------------------------------------------------

def test_every_rule_fires_on_fixture(fixture_root):
    report = run_lint(root=fixture_root)
    assert {f.rule for f in report.findings} == ALL_RULES
    # the one suppression is recorded, with its reason, not silently eaten
    assert [f.rule for f in report.suppressed] == ["RA002"]
    assert report.suppressed[0].reason == "host metric readout"
    assert not report.ok


def test_findings_carry_location_and_format(fixture_root):
    report = run_lint(root=fixture_root)
    f = next(f for f in report.findings if f.rule == "RA001")
    assert f.path.endswith("bad.py") and f.line > 0
    assert f"{f.path}:{f.line}" in f.format() and "RA001" in f.format()


def test_rule_allowlist(fixture_root):
    report = run_lint(root=fixture_root, rules=["RA007"])
    assert {f.rule for f in report.findings} == {"RA007"}


def test_repo_tree_is_clean():
    """The zero-findings baseline the CI lint lane enforces."""
    report = run_lint()
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings)


def test_reachability_covers_hot_paths():
    """The linter only means something if the jit call graph actually
    reaches the model/kernel/serving code — pin the load-bearing entries
    so a resolution regression cannot silently lint nothing."""
    a = Analyzer(load_modules())
    must_reach = [
        ("repro.models.moe", "moe_apply"),
        ("repro.models.moe", "route"),
        ("repro.models.model", "decode_step_slots"),
        ("repro.models.transformer", "stack_apply"),
        ("repro.kernels.grouped_mlp", "_kernel"),
        ("repro.launch.steps",
         "make_slot_decode_multi.slot_decode_multi.step"),
        ("repro.serving.engine", "Engine.bench_decode.block"),
        # speculative decoding (DESIGN.md §10): the draft->verify->accept
        # round and its model-side verify forward
        ("repro.launch.steps", "sample_tokens"),
        ("repro.serving.spec", "build_slot_decode_spec.slot_decode_spec"),
        ("repro.serving.spec", "build_slot_admit_spec.slot_admit_spec"),
        ("repro.serving.spec", "accept_drafts"),
        ("repro.models.model", "verify_step_slots"),
        ("repro.models.transformer", "stack_verify_slots"),
        ("repro.models.layers", "attn_verify_slots"),
        ("repro.serving.engine", "Engine.bench_spec_decode.round_"),
    ]
    for entry in must_reach:
        assert entry in a.reachable, entry


def test_cli_exit_codes(fixture_root):
    env_src = {"PYTHONPATH": "src"}
    import os
    env = dict(os.environ, **env_src)
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--no-contracts",
         "--root", fixture_root],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert bad.returncode == 1
    assert "RA001" in bad.stdout and "suppressed" in bad.stdout
    good = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--no-contracts"],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert good.returncode == 0, good.stdout + good.stderr


def test_taint_does_not_flag_static_config_math(tmp_path):
    """moe._capacity-style int() on closed-over config must NOT be
    flagged: parameters and shape attributes are trace-static."""
    pkg = tmp_path / "repro"
    pkg.mkdir()
    (pkg / "ok.py").write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp


        def helper(x, cf):
            cap = int(x.shape[0] * cf)      # static: shape * config float
            if x.ndim == 2:                 # static: ndim
                cap += 1
            if x is None:                   # static: identity
                return None
            return jnp.zeros((cap,))


        fn = jax.jit(helper)
    """))
    report = run_lint(root=str(tmp_path))
    assert report.findings == [], [f.format() for f in report.findings]


# ---------------------------------------------------------------------------
# kernel contracts
# ---------------------------------------------------------------------------

def test_contracts_pass_on_every_registered_kernel():
    report = check_kernel_contracts()
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings)
    kernels = {k for k, _ in report.checked}
    assert kernels == {"swiglu_mlp", "grouped_swiglu", "grouped_swiglu_q",
                       "gather_swiglu", "gather_swiglu_q", "flash_attention",
                       "paged_attention", "paged_attention_q"}
    # MoE kernels validated against both MoE archs, dense/flash more widely
    moe_archs = {a for k, a in report.checked if k == "gather_swiglu"}
    assert moe_archs == {"kimi_k2_1t_a32b", "qwen3_moe_30b_a3b"}
    # every waiver in the table actually fired (stale waivers rot)
    fired = {(f.kernel, f.arch) for f in report.waived}
    assert fired == set(VMEM_WAIVERS)


def test_contracts_never_execute_a_kernel(monkeypatch):
    """Abstract-eval only: booby-trap every MoE kernel body so any
    invocation crashes, then check a config end to end. functools.wraps
    keeps the real body visible to the AST dtype check (inspect.getsource
    unwraps) while a call — traced or concrete — raises."""
    import functools

    def trap(real):
        @functools.wraps(real)
        def boom(*a, **k):
            raise AssertionError("kernel executed")
        return boom

    import repro.kernels.grouped_mlp as gm
    import repro.kernels.decode_moe as dm
    for mod, name in ((gm, "_kernel"), (gm, "_kernel_q"),
                      (dm, "_kernel"), (dm, "_kernel_q")):
        monkeypatch.setattr(mod, name, trap(getattr(mod, name)))
    report = check_kernel_contracts(arch_ids=["qwen3_moe_30b_a3b"])
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.checked


def test_contracts_rerun_in_same_process_stays_clean():
    """eval_shape caches on function identity; a cache hit would skip
    tracing and the recorder would capture nothing — regression guard for
    back-to-back checker runs (CI lint lane + tests in one process)."""
    for _ in range(2):
        report = check_kernel_contracts(arch_ids=["qwen3_moe_30b_a3b"])
        assert report.findings == []
        assert report.checked, "second run captured nothing (cache hit)"


def _capture(**kw):
    d, f = 64, 128
    base = dict(
        kernel_fn=None,
        grid=(2, 2),
        in_specs=(_spec((32, d), lambda i, j: (i, 0)),),
        out_spec=_spec((32, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((64, d), jnp.bfloat16),
        scratch=(),
        num_prefetch=0,
        operands=(jax.ShapeDtypeStruct((64, d), jnp.bfloat16),),
    )
    base.update(kw)
    return _Capture(**base)


def _spec(block, imap, memory_space=None):
    class S:
        block_shape = block
        index_map = staticmethod(imap)
    if memory_space is not None:
        S.memory_space = memory_space
    return S()


def _findings(cap, quantized=False):
    return list(_check_capture(cap, "k", "a", {"quantized": quantized}))


def test_contract_checker_rejects_bad_divisibility():
    cap = _capture(in_specs=(_spec((48, 64), lambda i, j: (i, 0)),))
    assert any(f.check == "divisibility" for f in _findings(cap))


def test_contract_checker_rejects_oob_index_map():
    # grid (2,2) but index map reaches block row i+1 -> row 2 of 2 blocks
    cap = _capture(in_specs=(_spec((32, 64), lambda i, j: (i + 1, 0)),))
    assert any(f.check == "bounds" for f in _findings(cap))


def test_contract_checker_rejects_undercovered_output():
    cap = _capture(out_spec=_spec((32, 64), lambda i, j: (0, 0)))
    assert any(f.check == "coverage" for f in _findings(cap))


def test_contract_checker_rejects_vmem_blowout():
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)    # 64 MiB
    cap = _capture(
        in_specs=(_spec((4096, 4096), lambda i, j: (0, 0)),),
        operands=(big,))
    assert any(f.check == "vmem" for f in _findings(cap))


def test_contract_checker_rejects_dtype_breaches():
    # output dtype drifts from input dtype
    cap = _capture(out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert any(f.check == "dtype" for f in _findings(cap))
    # quantized contract: needs 3 int8 tables
    cap = _capture()
    assert any("int8" in f.msg for f in _findings(cap, quantized=True))


def test_contract_checker_oob_clip_tables():
    """§7 contract: scalar-prefetch tables at their extreme legal value
    E-1 stay in bounds; a spec that offsets the table value breaks."""
    E, d = 4, 64
    table = jax.ShapeDtypeStruct((2,), jnp.int32)
    w = jax.ShapeDtypeStruct((E, d, d), jnp.bfloat16)
    ok = _capture(
        grid=(2,), num_prefetch=1,
        in_specs=(_spec((1, d, d), lambda i, ix: (ix[i], 0, 0)),),
        operands=(table, w),
        out_spec=_spec((32, d), lambda i, ix: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((64, d), jnp.bfloat16))
    assert not any(f.check == "bounds" for f in _findings(ok))
    bad = _capture(
        grid=(2,), num_prefetch=1,
        in_specs=(_spec((1, d, d), lambda i, ix: (ix[i] + 1, 0, 0)),),
        operands=(table, w),
        out_spec=_spec((32, d), lambda i, ix: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((64, d), jnp.bfloat16))
    assert any(f.check == "bounds" for f in _findings(bad))


def test_contract_checker_table_extreme_ignores_unaddressed_operands():
    """The table's extreme comes from the operands it addresses: a one-block
    token tile beside an E-row expert table must not shrink the fill to 0
    and hide an off-by-one expert index (the tiled gather kernel's shape)."""
    E, d = 4, 64
    table = jax.ShapeDtypeStruct((2,), jnp.int32)
    x = jax.ShapeDtypeStruct((16, d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((E, d, d), jnp.bfloat16)

    def cap(shift):
        return _capture(
            grid=(2,), num_prefetch=1,
            in_specs=(_spec((16, d), lambda i, ix: (0, 0)),
                      _spec((1, d, d), lambda i, ix: (ix[i] + shift, 0, 0))),
            operands=(table, x, w),
            out_spec=_spec((16, d), lambda i, ix: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((16, d), jnp.bfloat16))
    assert not any(f.check == "bounds" for f in _findings(cap(0)))
    assert any(f.check == "bounds" for f in _findings(cap(1)))


# ---------------------------------------------------------------------------
# trace guard
# ---------------------------------------------------------------------------

def test_trace_guard_counts_traces_not_dispatches():
    g = TraceGuard("count")
    fn = g.wrap_jit("f", lambda x: x + 1, expected_traces=1)
    x = jnp.arange(4)
    for _ in range(5):
        fn(x)
    assert g.traces["f"] == 1 and g.counters["retraces"] == 0


def test_trace_guard_flags_retrace():
    g = TraceGuard("count")
    fn = g.wrap_jit("f", lambda x: x + 1, expected_traces=1)
    fn(jnp.arange(4))
    fn(jnp.arange(8))                       # new shape -> retrace
    assert g.traces["f"] == 2
    assert g.counters["retraces"] == 1


def test_trace_guard_strict_raises_on_retrace():
    g = TraceGuard("strict")
    fn = g.wrap_jit("f", lambda x: x * 2, expected_traces=1)
    fn(jnp.arange(4))
    with pytest.raises(TraceGuardError, match="traced 2 times"):
        fn(jnp.arange(8))


def test_trace_guard_flags_implicit_transfer():
    g = TraceGuard("count")
    jitted = g.wrap_jit("f", lambda x: x + 1, expected_traces=1)
    g.run("f", jitted, jnp.arange(4))       # warmup: unguarded
    # np argument -> implicit host-to-device transfer under the armed guard;
    # count mode records it and re-executes unguarded (same result)
    out = g.run("f", jitted, np.arange(4))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4) + 1)
    assert g.counters["implicit_transfers"] == 1


def test_trace_guard_strict_raises_on_transfer():
    g = TraceGuard("strict")
    jitted = g.wrap_jit("f", lambda x: x + 1, expected_traces=2)
    g.run("f", jitted, jnp.arange(4))
    with pytest.raises(TraceGuardError, match="implicit"):
        g.run("f", jitted, np.arange(4))


@pytest.mark.parametrize("err", [
    RuntimeError("device lost during transfer"),
    jax.errors.JaxRuntimeError("INTERNAL: DMA transfer to HBM failed"),
])
def test_trace_guard_reraises_device_errors_naming_transfer(err):
    """Only the transfer guard's own refusal is counted and re-run; a
    device error whose text mentions a transfer propagates, run once."""
    g = TraceGuard("count")
    jitted = g.wrap_jit("f", lambda x: x + 1, expected_traces=1)
    g.run("f", jitted, jnp.arange(4))
    calls = []

    def failing(x):
        calls.append(x)
        raise err

    with pytest.raises(type(err), match="transfer"):
        g.run("f", failing, jnp.arange(4))
    assert len(calls) == 1 and g.counters["implicit_transfers"] == 0


def test_trace_guard_off_mode_is_plain_jit():
    g = TraceGuard("off")
    jitted = g.wrap_jit("f", lambda x: x + 1, expected_traces=1)
    g.run("f", jitted, jnp.arange(4))
    out = g.run("f", jitted, np.arange(4))  # never guarded
    np.testing.assert_array_equal(np.asarray(out), np.arange(4) + 1)
    assert g.counters["implicit_transfers"] == 0


def test_trace_guard_shares_engine_counters():
    shared = {"device_calls": 7}
    g = TraceGuard("count", counters=shared)
    assert shared["retraces"] == 0 and shared["implicit_transfers"] == 0
    assert shared["device_calls"] == 7      # untouched


def test_trace_guard_rejects_unknown_mode():
    with pytest.raises(ValueError, match="unknown trace-guard mode"):
        TraceGuard("loose")
