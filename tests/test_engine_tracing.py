"""The engine's host spans (``engine.*``) and its request stamps.

Spans: one set per fused call, nested in ``engine.step_block``, carrying
their counts as arguments, never one per slot or token. Stamps: under the
wall clock every stamp reads the caller's clock (the ``now`` a call is
given plus the seconds since it started); under the step clock they are
what they always were.
"""
import glob
import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import model as MD
from repro.serving import Engine, EngineConfig

ARCH = "qwen3-moe-30b-a3b"
K = 4                               # decode steps per fused call
CHILDREN = ("engine.admit", "engine.admit_group", "engine.decode_inputs",
            "engine.decode_block", "engine.commit")


@pytest.fixture(scope="module")
def model():
    cfg = configs.get(ARCH).reduced()
    return cfg, MD.init(cfg, jax.random.PRNGKey(0))


def _engine(model, clock="wall", n_slots=4):
    cfg, params = model
    return Engine(EngineConfig(arch=ARCH, n_slots=n_slots, s_max=48,
                               prefill_buckets=(8, 16), decode_block=K,
                               clock=clock), cfg=cfg, params=params)


def _prompt(model, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, model[0].vocab_size, size=n, dtype=np.int32)


def _spans(trace_dir):
    """(name, start_ns, end_ns, args) of every ``engine.*`` host span."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    a = int(ev.start_ns)
                    out.append((ev.name, a, a + int(ev.duration_ns),
                                {k: int(v) for k, v in ev.stats}))
    return sorted(out, key=lambda s: s[1])


def _traced(trace_dir, fn):
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _spans(trace_dir)


def _calls(spans):
    """(``engine.step_block`` span, the spans inside it), in call order."""
    return [(c, [s for s in spans if s[0] != "engine.step_block"
                 and c[1] <= s[1] and s[2] <= c[2]])
            for c in spans if c[0] == "engine.step_block"]


def _warm(eng, model, lengths):
    for i, n in enumerate(lengths):
        eng.submit(_prompt(model, n, seed=100 + i), max_new_tokens=2)
    while not eng.idle:
        eng.step_block(now=0.0)


def test_step_block_spans_nest_with_their_arguments(model, tmp_path):
    eng = _engine(model)
    _warm(eng, model, [5, 7, 12])               # compile outside the trace
    lengths = [5, 7, 12]                        # groups: pad 8 x 2, pad 16
    for i, n in enumerate(lengths):
        eng.submit(_prompt(model, n, seed=i), max_new_tokens=6)
    spans = _traced(tmp_path, lambda: eng.step_block(now=0.0))
    calls = _calls(spans)
    assert len(calls) == 1
    (call, children), = calls
    assert call[3] == {"active": 0, "pending": 3}
    # every engine span of the call lies inside its step_block span
    assert len(children) == len(spans) - 1
    names = [s[0] for s in children]
    assert sorted(set(names)) == sorted(CHILDREN)
    admit, = [s for s in children if s[0] == "engine.admit"]
    assert admit[3] == {"admitted": 3}
    groups = sorted((s[3] for s in children
                     if s[0] == "engine.admit_group"), key=lambda a: a["pad"])
    assert groups == [
        {"pad": 8, "rows": 2, "rows_padded": 2, "real_tokens": 5 + 7},
        {"pad": 16, "rows": 1, "rows_padded": 1, "real_tokens": 12}]
    dec, = [s for s in children if s[0] == "engine.decode_block"]
    assert dec[3] == {"rows": 3, "steps": K}
    commit, = [s for s in children if s[0] == "engine.commit"]
    assert commit[3] == {"tokens": 3 * K, "evicted": 0}
    # the phases follow one another and cover the call: what is left is
    # its entry and exit
    ends = [(a, b) for _, a, b, _ in children]
    assert all(b0 <= a1 for (_, b0), (a1, _) in zip(ends, ends[1:]))
    assert sum(b - a for a, b in ends) >= 0.9 * (call[2] - call[1])


def test_rows_padded_is_a_power_of_two(model, tmp_path):
    eng = _engine(model)
    _warm(eng, model, [5, 6, 7])
    for i in range(3):
        eng.submit(_prompt(model, 6, seed=i), max_new_tokens=2)
    spans = _traced(tmp_path, lambda: eng.step_block(now=0.0))
    group, = [s[3] for s in spans if s[0] == "engine.admit_group"]
    assert group == {"pad": 8, "rows": 3, "rows_padded": 4,
                     "real_tokens": 18}
    commit, = [s[3] for s in spans if s[0] == "engine.commit"]
    # each request: its first token at admission, one decoded, then done
    assert commit == {"tokens": 3, "evicted": 3}


def test_span_count_per_call_does_not_grow_with_slots_or_tokens(
        model, tmp_path):
    eng = _engine(model)
    _warm(eng, model, [6])
    _warm(eng, model, [6, 6, 6])
    eng.submit(_prompt(model, 6), max_new_tokens=4 * K + 1)

    def one_then_three():
        eng.step_block(now=0.0)                  # 1 admitted, 1 slot
        for i in range(3):
            eng.submit(_prompt(model, 6, seed=i + 1),
                       max_new_tokens=4 * K + 1)
        eng.step_block(now=1.0)                  # 3 admitted, 4 slots
        eng.step_block(now=2.0)                  # decode only, 4 slots

    calls = _calls(_traced(tmp_path, one_then_three))
    counts = [len(children) for _, children in calls]
    rows = [c[3]["active"] for c, _ in calls]
    assert rows == [0, 1, 4]
    # admit, admit_group, decode_inputs, decode_block, commit; no group in
    # the decode-only call
    assert counts == [5, 5, 4]
    tokens = [next(s[3]["tokens"] for s in ch if s[0] == "engine.commit")
              for _, ch in calls]
    assert tokens == [K, 4 * K, 4 * K]


def test_wall_stamps_read_the_callers_clock(model):
    """``arrival <= t_admitted <= t_first_token <= t_finished``, each
    inside the caller's reading of the call that made it."""
    eng = _engine(model)
    _warm(eng, model, [5, 5, 12])
    t_base, c0 = 5000.0, time.perf_counter()

    def clock():                     # the caller's clock, not the engine's
        return t_base + time.perf_counter() - c0

    def call():
        t_in = clock()
        eng.step_block(now=t_in)
        return t_in, clock()

    short = eng.submit(_prompt(model, 5), max_new_tokens=K,
                       arrival_time=t_base - 0.5)      # done in call 1
    one = eng.submit(_prompt(model, 12, seed=1), max_new_tokens=1,
                     arrival_time=t_base - 0.1)        # done at admission
    long_ = eng.submit(_prompt(model, 5, seed=2), max_new_tokens=K + 3,
                       arrival_time=t_base)            # done in call 2
    late = eng.submit(_prompt(model, 5, seed=3), max_new_tokens=2,
                      arrival_time=t_base - 1.0, deadline=t_base - 0.9)
    c1 = call()
    c2 = call()
    assert c1[0] < c1[1] < c2[0] < c2[1]
    for r in (short, one, long_):
        assert r.arrival_time <= r.t_admitted <= r.t_first_token \
            <= r.t_finished
        assert c1[0] <= r.t_admitted <= r.t_first_token <= c1[1]
    assert one.t_finished == one.t_first_token
    assert c1[0] <= short.t_finished <= c1[1]
    assert c2[0] <= long_.t_finished <= c2[1]
    assert late.status == "shed" and c1[0] <= late.t_finished <= c1[1]


def test_step_clock_stamps_are_the_call_start_and_inner_step(model):
    eng = _engine(model, clock="steps")
    a = eng.submit(_prompt(model, 5), max_new_tokens=K + 2)
    b = eng.submit(_prompt(model, 5, seed=1), max_new_tokens=1,
                   arrival_time=4.0)
    c = eng.submit(_prompt(model, 5, seed=2), max_new_tokens=2,
                   arrival_time=1.0, deadline=2.0)
    eng.step_block()                 # now 0: admits a, decodes K tokens
    assert (a.t_admitted, a.t_first_token, a.t_finished) == (0.0, 0.0, None)
    eng.step_block()                 # now K: sheds c, admits b, finishes a
    assert (a.t_finished, a.finish_reason) == (float(K), "length")
    assert (b.t_admitted, b.t_first_token, b.t_finished) == (
        float(K), float(K), float(K))
    assert (c.status, c.t_finished) == ("shed", float(K))
