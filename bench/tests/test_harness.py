"""A whole run on the CPU at a small size, with the chip check skipped:
sound, and with a served token altered where it is produced; and the
int8 control against the program on fixed requests. The limits here are
this small model's own, not a cell's."""
import json
import subprocess
import shutil
import sys

import jax
import pytest

import numpy as np

import correct as CK
import model as MODEL
import run as RUN
import serve as SV
import spec as SPEC
import traffic as TR
import weights as WT

DATA = SPEC.BENCH_DIR / "tests" / "data"
PK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# sound runs of the tiny model (top-2 of 8 experts, so one routing flip
# moves a logit far) read a mean gap of 0 to 0.019 on the CPU
TINY_LIMIT = 0.05


def _cell(config="tiny"):
    bench = SPEC.load_json(SPEC.ROOT / "BENCHMARK.json")
    return SPEC.Cell(
        name="tiny", chips=1, config=SPEC.load_json(DATA / f"{config}.json"),
        traffic=SPEC.load_json(DATA / "tiny-traffic.json"),
        limits={"compare": {"mean_gap": {"limit": TINY_LIMIT}}},
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def _run(cell, seed, trace=False, **kw):
    return RUN.run_cell(cell, seed, 1.5, trace, device=jax.devices()[0],
                        n_devices=1, peaks=PK, **kw)


@pytest.mark.parametrize("config", ["tiny", "tiny-merged"])
def test_sound_run_is_correct(config):
    out = _run(_cell(config), 2)
    assert out["correct"], out["check"]
    assert out["attempted"] > 10 and out["failed"] == 0
    bench = SPEC.load_json(SPEC.ROOT / "BENCHMARK.json")
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert list(out)[-1] == "check"
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics():
    out = _run(_cell(), 3, trace=True)
    assert out["correct"]
    # the CPU trace has no TPU plane: device readers find nothing
    assert {"compiles_in_window", "step_mfu.chat"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" in out["device"] and "window_s" in out["device"]


class _AlterTokens:
    """The engine, with the newest token of every request that got tokens in
    a call replaced by another where it is produced."""

    def __init__(self, eng):
        self._eng = eng

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def submit(self, *a, **kw):
        r = self._eng.submit(*a, **kw)
        self._seen = getattr(self, "_seen", {})
        self._seen[id(r)] = (r, 0)
        return r

    def step_block(self, now=None):
        done = self._eng.step_block(now=now)
        V = self._eng.cfg.vocab_size
        for key, (r, n) in list(self._seen.items()):
            if len(r.out_tokens) > n:
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % V
                self._seen[key] = (r, len(r.out_tokens))
        return done


def test_altered_token_is_not_correct():
    out = _run(_cell(), 2, engine_hook=_AlterTokens)
    assert not out["correct"]
    assert out["check"]["mean_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("seed", [6, 8])
def test_int8_control_fails_where_the_program_passes(seed):
    """Eight requests served at once on the step clock (no timing in it),
    top-8 of 32 experts: the program's mean gap reads under 1e-4 on these
    seeds, the int8 reference's 8.5e-4 and 1.7e-3 (CPU)."""
    from repro.serving import Engine, EngineConfig
    conf = SPEC.load_json(DATA / "tiny-top8.json")
    w = MODEL.widths(conf)
    params = WT.make_params(w, conf["torch_dtype"], seed)
    eng = Engine(EngineConfig(reduced=False, n_slots=4, s_max=64,
                              prefill_buckets=(16,), decode_block=4),
                 cfg=MODEL.program_config(conf), params=params)
    g = np.random.default_rng(seed)
    tracks = []
    for i in range(8):
        r = TR.Req(i, g.integers(0, w["V"], 16, dtype=np.int32), 24)
        tracks.append(SV.Track(req=r, due=0.0, t_submit=0.0, n_prompt=16,
                               handle=eng.submit(r.prompt, 24)))
    while not eng.idle:
        eng.step_block()
    for t in tracks:
        t.done, t.status = True, t.handle.status
        t.n_out = len(t.handle.out_tokens)
    check = {"requests": 8, "chunk": 2, "pad": 48}
    spec = WT.spec_of(w, conf["torch_dtype"])
    limits = {"compare": {"mean_gap": {"limit": 2e-4}}}
    program = CK.gap_summary(params, spec, tracks, check)
    control = CK.gap_summary(params, spec, tracks, check, control=True)
    assert all(ok for *_, ok in CK.verdict(program, limits))
    assert not any(ok for *_, ok in CK.verdict(control, limits))


def test_refuses_without_a_chip(capsys):
    rc = RUN.main(["--workload", "qwen3-moe-30b-a3b.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(SPEC.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(SPEC.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-moe-30b-a3b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
