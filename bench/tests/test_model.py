"""The benchmark's weights fit the program's parameter tree, and its
float32 reference agrees with the program's own float32 forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model as MODEL
import reference as REF
import spec as SPEC
import weights as WT

CONFIGS = SPEC.BENCH_DIR / "configs"
DATA = SPEC.BENCH_DIR / "tests" / "data"


@pytest.mark.parametrize("path", [CONFIGS / "qwen3-moe-30b-a3b.json",
                                  CONFIGS / "qwen3-moe-30b-a3b-merged64.json",
                                  DATA / "tiny-merged.json"])
def test_weights_match_the_program_tree(path):
    from repro.models import model as MD
    conf = SPEC.load_json(path)
    cfg = MODEL.program_config(conf)
    w = MODEL.widths(conf)
    ours = jax.eval_shape(lambda: WT.make_params(w, conf["torch_dtype"], 0))
    theirs = jax.eval_shape(lambda: MD.init(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_merged_remap_is_balanced():
    conf = SPEC.load_json(DATA / "tiny-merged.json")
    w = MODEL.widths(conf)
    p = WT.make_params(w, conf["torch_dtype"], 5)
    remap = np.asarray(p["stack_c"]["moe"]["remap"])
    for row in remap:
        assert np.bincount(row, minlength=4).tolist() == [2, 2, 2, 2]
    assert not all((row == np.arange(8) % 4).all() for row in remap)


@pytest.mark.parametrize("name", ["tiny", "tiny-merged"])
def test_reference_agrees_with_program_fp32_forward(name):
    from repro.models import model as MD
    import dataclasses
    conf = SPEC.load_json(DATA / f"{name}.json")
    w = MODEL.widths(conf)
    cfg = MODEL.program_config(conf)
    cfg32 = cfg.replace(dtype="float32", moe=dataclasses.replace(
        cfg.moe, dispatch="ragged"))
    params = WT.make_params(w, conf["torch_dtype"], 9)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, params)
    tokens = np.random.default_rng(0).integers(0, w["V"], (2, 32),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = MD.forward(cfg32, p32, {"tokens": jnp.asarray(tokens)})
    best = np.asarray(jnp.argmax(logits, -1), np.int32)
    gaps = REF.served_gaps(params, WT.spec_of(w, conf["torch_dtype"]),
                           tokens, best, 2)
    assert gaps.max() < 1e-4
    # and a token the program would not pick reads a clear gap
    worst = np.asarray(jnp.argmin(logits, -1), np.int32)
    assert REF.served_gaps(params, WT.spec_of(w, conf["torch_dtype"]),
                           tokens, worst, 2).min() > 1.0
