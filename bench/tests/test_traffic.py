"""The generator: seeded, and the same work for every seed."""
import numpy as np

import spec as SPEC
import traffic as TR

BIG = 2 ** 31 + 12345


def _mix(name):
    return SPEC.load_json(SPEC.BENCH_DIR / "traffic" / f"{name}.json")


def test_same_seed_same_requests():
    t = _mix("chat")
    a = TR.generate(t, BIG, 151936, 48.0)
    b = TR.generate(t, BIG, 151936, 48.0)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_seeds_share_sizes_and_gaps_in_another_order():
    t = _mix("chat")
    a = TR.generate(t, 1, 151936, 48.0)
    b = TR.generate(t, 2, 151936, 48.0)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga = np.sort(np.diff([0.0] + [r.due for r in a]))
    gb = np.sort(np.diff([0.0] + [r.due for r in b]))
    np.testing.assert_allclose(ga, gb)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]


def test_open_loop_covers_its_horizon_at_its_rate():
    t = _mix("chat")
    reqs = TR.generate(t, 3, 151936, 48.0)
    assert len(reqs) == int(np.ceil(t["rate_per_s"] * 48.0)) + 1
    assert abs(reqs[-1].due - 48.0) < 0.2 * 48.0
    p = t["prompt"]
    assert all(p["min"] <= r.prompt.size <= p["max"] for r in reqs)
    o = t["output"]
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    med = np.median([r.prompt.size for r in reqs])
    assert abs(med - p["median"]) <= 0.1 * p["median"]


def test_closed_loop_pool_and_uniform_outputs():
    t = _mix("prefill-batch")
    reqs = TR.generate(t, 4, 151936, 10.0)
    assert len(reqs) == t["pool"]
    assert all(r.due == 0.0 for r in reqs)
    outs = np.bincount([r.max_new for r in reqs], minlength=17)[1:]
    assert outs.min() >= len(reqs) // 16 - 1


def test_derived_seeds_differ_by_purpose():
    assert TR.seed32(BIG, TR.WEIGHTS) != TR.seed32(BIG, TR.TRAFFIC)
    assert 0 <= TR.seed32(BIG, TR.WEIGHTS) < 2 ** 32
