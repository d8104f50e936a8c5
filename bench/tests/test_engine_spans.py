"""The readers of the engine's request stamps, and ``engine_spans`` on
hand-made spans, on a trace recorded here and on the recorded TPU trace
(which holds no engine span)."""
import importlib.util
import types

import jax
import numpy as np
import pytest

import engine_spans as ES
import serve as SV
import spec as SPEC
import trace_reduce as TRD
import window as W
from traffic import Req

MS = 1_000_000
SMALL = SPEC.BENCH_DIR / "tests" / "data" / "small_tpu.xplane.pb"


def _reader(name):
    path = SPEC.BENCH_DIR / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def _track(due, admitted, first_token, first):
    """A request due at ``due``, stamped by the engine, delivered at
    ``first`` on the harness's clock."""
    t = SV.Track(req=Req(0, np.zeros(4, np.int32), 8), due=due, t_submit=due,
                 n_prompt=4, t_first=first)
    t.handle = types.SimpleNamespace(arrival_time=due, t_admitted=admitted,
                                     t_first_token=first_token)
    return t


def _run(tracks):
    load = SV.Load(tracks=tracks, blocks=[], w0=1.0, w1=3.0)
    return W.Run(load=load, widths={}, peaks={}, chips=1, setup_s=1.0,
                 compiles_in_window=0)


def test_stamp_readers_on_requests_due_in_the_window():
    tracks = [_track(1.0, 1.2, 1.25, 1.6),
              _track(2.0, 2.1, 2.2, 2.4),
              _track(0.5, 0.9, 1.0, 1.1),       # due before the window
              _track(3.0, 3.1, 3.2, 3.3)]       # due after it
    run = _run(tracks)
    assert _reader("admit_wait_p95_ms")(run) == pytest.approx(
        100.0 + 0.95 * 100.0)
    assert _reader("prefill_ms_p95")(run) == pytest.approx(
        50.0 + 0.95 * 50.0)
    assert _reader("first_token_hold_p50_ms")(run) == pytest.approx(
        (350.0 + 200.0) / 2)


@pytest.mark.parametrize("absent", ["handle", "stamps", "requests"])
def test_stamp_readers_read_nothing_without_their_data(absent):
    t = _track(1.0, 1.2, 1.25, 1.6)
    if absent == "handle":
        t.handle = None
    elif absent == "stamps":
        t.handle.t_admitted = t.handle.t_first_token = None
    run = _run([] if absent == "requests" else [t])
    for name in ("admit_wait_p95_ms", "prefill_ms_p95",
                 "first_token_hold_p50_ms"):
        assert _reader(name)(run) is None


def _call(t, admit_groups=(), steps=8, rows=4):
    """The spans of one ``step_block`` call starting at ``t`` ms: 1 ms of
    admission, 2 ms a group, 1 ms of inputs, 8 ms of decode, 1 ms of
    commit, 0.5 ms of entry and exit left uncovered."""
    sp = [ES.Span("engine.admit", (t + 0.25) * MS, (t + 1.25) * MS,
                  {"admitted": sum(g["rows"] for g in admit_groups)})]
    at = t + 1.25
    for g in admit_groups:
        sp.append(ES.Span("engine.admit_group", at * MS, (at + 2) * MS, g))
        at += 2
    sp += [ES.Span("engine.decode_inputs", at * MS, (at + 1) * MS, {}),
           ES.Span("engine.decode_block", (at + 1) * MS, (at + 9) * MS,
                   {"steps": steps, "rows": rows}),
           ES.Span("engine.commit", (at + 9) * MS, (at + 10) * MS,
                   {"tokens": steps * rows, "evicted": 0})]
    call = ES.Span("engine.step_block", t * MS, (at + 10.25) * MS,
                   {"active": rows, "pending": 0})
    return [call] + sp


def test_decode_step_pad_share_and_coverage():
    groups = [{"pad": 128, "rows": 3, "rows_padded": 4, "real_tokens": 300},
              {"pad": 512, "rows": 1, "rows_padded": 1, "real_tokens": 400}]
    spans = _call(0, groups) + _call(20, steps=4) + _call(40, steps=0)
    # 8 ms over 8 steps, 8 ms over 4; a call in which no slot emitted
    # has no step to divide by
    assert ES.decode_step_ms_p50(spans) == pytest.approx(1.5)
    assert ES.admit_pad_share(spans) == pytest.approx(
        100 * (1 - 700 / (4 * 128 + 512)))
    assert ES.uncovered_share(spans) == pytest.approx(1.5 / (15.5 + 2 * 11.5))
    assert ES.decode_step_ms_p50([]) is None
    assert ES.admit_pad_share(_call(0)) is None
    assert ES.uncovered_share([]) is None


def test_a_gap_inside_commit_is_named_commit_and_the_window_stays():
    spans = _call(0)    # admit 0.25-1.25, decode block 2.25-10.25, commit
    #                     10.25-11.25, the call 0-11.5 ms
    ops = [TRD.Op(0, "fusion", "jit_decode", "", a * MS // 100,
                  (b - a) * MS // 100)
           for a, b in [(0, 50), (150, 1050), (1075, 1100), (1170, 1180)]]
    red = TRD.Reduced(t0=0, t1=12 * MS, ops=ops,
                      spans=[("bench.step_block", 0, 1160 * MS // 100),
                             ("bench.collect", 1160 * MS // 100, 12 * MS)],
                      n_devices=1)
    gaps = dict(ES.idle_gaps(red, spans))
    assert gaps == {"engine.admit": pytest.approx(1.0e-3),      # 0.5-1.5
                    "engine.commit": pytest.approx(0.25e-3),    # 10.5-10.75
                    "engine.step_block": pytest.approx(0.7e-3),  # 11.0-11.7
                    "bench.collect": pytest.approx(0.2e-3)}     # 11.8-12
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    # engine spans name gaps and nothing else: the window, busy time and
    # lost calls are the reduction's own
    assert dict(red.breakdown()["idle_gaps"]) == {
        "bench.step_block": pytest.approx(1.95e-3),
        "bench.collect": pytest.approx(0.2e-3)}
    assert (red.t0, red.t1, red.lost_calls()) == (0, 12 * MS, 0)


def test_a_trace_without_engine_spans_reads_nothing():
    spans = ES.read(str(SMALL))
    assert spans == []
    assert ES.decode_step_ms_p50(spans) is None
    assert ES.admit_pad_share(spans) is None
    red = TRD.reduce(str(SMALL))
    assert ES.idle_gaps(red, spans) == red.breakdown()["idle_gaps"]


def test_read_keeps_span_arguments(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step_block"):
        with jax.profiler.TraceAnnotation("engine.step_block", active=3,
                                          pending=1):
            with jax.profiler.TraceAnnotation("engine.decode_block",
                                              rows=3) as span:
                span.set_metadata(steps=5)
    jax.profiler.stop_trace()
    spans = ES.read(str(tmp_path))
    assert [(s.name, s.args) for s in spans] == [
        ("engine.step_block", {"active": 3, "pending": 1}),
        ("engine.decode_block", {"rows": 3, "steps": 5})]
    assert spans[0].start <= spans[1].start <= spans[1].end <= spans[0].end
    red = TRD.reduce(str(tmp_path))
    assert [name for name, _, _ in red.spans] == ["bench.step_block"]
