"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``record_trace.py`` (the decode gather and prefill grouped kernels inside
``bench.step_block`` spans, a ``bench.wait`` sleep between), and on
hand-made events."""
import pytest

import trace_reduce as TR
import spec as SPEC

SMALL = SPEC.BENCH_DIR / "tests" / "data" / "small_tpu.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return TR.reduce(str(SMALL))


def test_window_and_busy(small):
    assert small.n_devices == 1
    assert 0 < small.busy_s < small.window_s
    spans = {name for name, _, _ in small.spans}
    assert spans == {"bench.step_block", "bench.wait"}


def test_kernels_by_name(small):
    # three calls of each kernel ran; the device clock reads about 1 ms
    # behind the host's, so the first call falls before the first span
    dec = small.kernel_ops("gather_swiglu")
    pre = small.kernel_ops("grouped_swiglu")
    assert len(dec) == 2 and len(pre) == 2
    assert all(o.module.startswith("jit_") for o in dec + pre)


def test_breakdown_names_kernels_and_waits(small):
    b = small.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert any(n.endswith("/gather_swiglu") for n in names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = dict(b["idle_gaps"])
    assert idle.get("bench.wait", 0) > 0
    total_idle = sum(v for _, v in b["idle_gaps"])
    assert total_idle == pytest.approx(small.window_s - small.busy_s,
                                       rel=1e-6)


def test_containers_are_dropped():
    evs = [(0, 100, "%while.1 = (...) while()"),
           (10, 20, "%fusion.2 = f32[] fusion()"),
           (40, 30, "%gather_swiglu.3 = bf16[8,256] custom-call()"),
           (200, 5, "%copy.4 = f32[] copy()")]
    leaves = TR._leaves(evs)
    assert [e[2].split(" ")[0] for e in leaves] == [
        "%fusion.2", "%gather_swiglu.3", "%copy.4"]


def test_busy_is_the_union():
    ops = [TR.Op(0, "a", "m", "", 0, 10), TR.Op(0, "b", "m", "", 5, 10),
           TR.Op(0, "c", "m", "", 30, 10)]
    r = TR.Reduced(t0=0, t1=100, ops=ops,
                   spans=[("bench.step_block", 0, 100)], n_devices=1)
    assert r.busy_s == pytest.approx(25e-9)
    assert r.window_s == pytest.approx(100e-9)


def test_the_recorded_trace_lost_no_call(small):
    assert small.lost_calls() == 0 and small.complete


def test_a_call_with_no_device_op_is_lost():
    ms = 1_000_000
    ops = [TR.Op(0, "gather_swiglu", "m", "", 1 * ms, 5 * ms),
           TR.Op(0, "gather_swiglu", "m", "", 30 * ms, 5 * ms)]
    spans = [("bench.step_block", 0, 10 * ms),
             ("bench.wait", 10 * ms, 15 * ms),
             ("bench.step_block", 15 * ms, 25 * ms),   # its ops were lost
             ("bench.step_block", 29 * ms, 40 * ms)]
    r = TR.Reduced(t0=0, t1=40 * ms, ops=ops, spans=spans, n_devices=1)
    assert r.lost_calls() == 1 and not r.complete
    r.spans.pop(2)
    assert r.lost_calls() == 0 and r.complete
