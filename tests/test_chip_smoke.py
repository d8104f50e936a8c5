"""``chip_smoke.py``'s phases end to end at ``reduced()`` size on the CPU
(Pallas kernels interpreted), its refusal to run without a TPU, and the
compile-cache placement the entry points share."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.errors import NumericHealthError
from repro.launch import compile_cache as CC
from repro.models import model as MD

REPO = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               REPO / "chip_smoke.py")
CS = sys.modules.setdefault("chip_smoke",
                           importlib.util.module_from_spec(_spec))
_spec.loader.exec_module(CS)

SHAPE = CS.Shape(n_requests=4, prompt_len=16, max_new_tokens=10, n_slots=4,
                 s_max=32, merged_experts=4, calib_batch=4,
                 calib_max_tokens=64)


@pytest.fixture(scope="module")
def cfg():
    return configs.get(CS.ARCH).reduced()


def test_kernels_phase_matches_oracles(cfg):
    rec = CS.kernels_phase(cfg, SHAPE, seed=0)
    assert set(rec["max_rel_err"]) == {
        "gather_swiglu", "gather_swiglu_q", "grouped_swiglu",
        "grouped_swiglu_q", "paged_attention", "paged_attention_q"}
    assert all(e <= CS.KERNEL_TOL for e in rec["max_rel_err"].values())


def test_serve_compress_serve_phases(cfg):
    params = MD.init(cfg, jax.random.PRNGKey(0))
    prompts = CS.make_prompts(cfg, SHAPE, seed=0)
    eng, full = CS.serve_phase(cfg, params, prompts, SHAPE,
                               name="serve_full")
    assert full["requests"] == SHAPE.n_requests
    assert full["tokens_out"] == SHAPE.n_requests * SHAPE.max_new_tokens
    assert full["shed"] == full["retraces"] == 0
    agree = CS.fixed_batch_agreement(eng.cfg, params, prompts, SHAPE,
                                     full["tokens"])
    assert agree["of"] == SHAPE.n_requests * SHAPE.max_new_tokens

    ccfg, cparams, rec = CS.compress_phase(cfg, params, SHAPE, seed=0)
    assert rec["experts"] == [cfg.moe.n_experts, SHAPE.merged_experts]
    assert rec["calib_tokens"] == SHAPE.calib_max_tokens
    assert ccfg.moe_merged == SHAPE.merged_experts
    _, comp = CS.serve_phase(ccfg, cparams, prompts, SHAPE,
                             name="serve_compressed")
    assert comp["requests"] == SHAPE.n_requests
    assert all(len(t) == SHAPE.max_new_tokens for t in comp["tokens"])


def test_serve_phase_fails_on_quarantine(cfg):
    """A poisoned run must fail the phase (the strict numeric sentinel
    raises), not end in a clean record."""
    params = MD.init(cfg, jax.random.PRNGKey(0))
    bad = dict(params, final_ln=jax.tree.map(
        lambda a: a * np.float32("nan"), params["final_ln"]))
    with pytest.raises(NumericHealthError, match="non-finite"):
        CS.serve_phase(cfg, bad, CS.make_prompts(cfg, SHAPE, 0), SHAPE,
                       name="serve_full")


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert CS.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU" in out.err


def test_compile_cache_obeys_env_var(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    (JAX's own reading of the variable; nothing overrides it)."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n")
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing written to the cache dir"


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = CC.enable_compile_cache()
        assert got == CC.enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
