"""Metric readers on hand-made runs: the inter-token latency samples, and
trace readers that withhold what a trace that lost events would misread."""
import importlib.util

import numpy as np
import pytest

import serve as SV
import spec as SPEC
import trace_reduce as TRD
import window as W
from traffic import Req


def _reader(name):
    path = SPEC.BENCH_DIR / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def _track(due, deliveries):
    t = SV.Track(req=Req(0, np.zeros(4, np.int32), 8), due=due, t_submit=due,
                 n_prompt=4)
    t.deliveries = list(deliveries)
    t.t_first = deliveries[0][0] if deliveries else None
    return t


def _run(tracks, blocks=(), trace=None, widths=None):
    load = SV.Load(tracks=tracks, blocks=list(blocks), w0=1.0, w1=3.0)
    return W.Run(load=load, widths=widths or {}, peaks={}, chips=1,
                 setup_s=1.0, compiles_in_window=0, trace=trace)


def test_token_gaps_weigh_every_token_and_skip_the_first_call():
    # first call at 1.2 s delivers 3 tokens (the first and two more), then
    # 4 tokens 0.4 s later, 2 tokens 0.2 s after that, and one after the
    # window closes
    tr = _track(1.0, [(1.2, 3), (1.6, 4), (1.8, 2), (3.5, 1)])
    early = _track(0.0, [(0.5, 1), (1.1, 8)])   # 0.6 s over 8, in window
    g = W.token_gaps(_run([tr, early]))
    assert sorted(g.tolist()) == pytest.approx(
        [0.075] * 8 + [0.1] * 6)
    assert _reader("itl_p95_ms")(_run([tr, early])) == pytest.approx(100.0)
    assert _reader("itl_p95_ms")(_run([])) is None


def test_time_to_first_token_counts_requests_due_in_the_window():
    tracks = [_track(1.0, [(1.5, 1)]), _track(2.0, [(2.1, 1)]),
              _track(0.5, [(2.0, 1)]), _track(3.0, [(3.2, 1)])]
    assert sorted(W.first_token_s(_run(tracks))) == pytest.approx([0.1, 0.5])
    assert _reader("ttft_p50_ms")(_run(tracks)) == pytest.approx(300.0)
    tracks[0].t_first_call, tracks[1].t_first_call = 1.3, 2.05
    assert _reader("queue_wait_p95_ms")(_run(tracks)) == pytest.approx(
        50.0 + 0.95 * 250.0)


def _lossy_trace(lost: bool):
    ms = 1_000_000
    ops = [TRD.Op(0, "gather_swiglu", "jit_decode", "", 1 * ms, 4 * ms),
           TRD.Op(0, "gather_swiglu", "jit_decode", "", 12 * ms, 4 * ms)]
    if lost:
        ops.pop()
    spans = [("bench.step_block", 0, 6 * ms),
             ("bench.step_block", 11 * ms, 17 * ms)]
    return TRD.Reduced(t0=0, t1=17 * ms, ops=ops, spans=spans, n_devices=1)


@pytest.mark.parametrize("lost", [False, True])
def test_trace_readers_withhold_a_lossy_trace(lost):
    widths = {"L": 1, "live": [128], "d": 2048, "f": 768, "k": 8,
              "dtype_bytes": 2}
    blocks = [SV.Block(t_call=1.0, t_ret=1.1, n_active_before=2, admitted=[],
                       buckets=[], decode_tokens=2, decode_steps=1,
                       traced=True) for _ in range(2)]
    run = _run([], blocks, _lossy_trace(lost), widths)
    run.peaks.update(bf16_flops=197e12, hbm_bytes_per_s=819e9)
    idle = _reader("device_idle_share.chat")(run)
    roof = _reader("moe_decode_roofline")(run)
    if lost:
        assert idle is None and roof is None
    else:
        assert idle == pytest.approx(100 * 9 / 17)
        assert 0 < roof < 100
