"""Operations and bytes per kernel, checked by hand at qwen3 widths."""

import pytest

import counts as C
import model as MODEL
import spec as SPEC

PK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def w():
    return MODEL.widths(SPEC.load_json(
        SPEC.BENCH_DIR / "configs" / "qwen3-moe-30b-a3b.json"))


def test_expert_table_bytes(w):
    # gate + up + down, 2048 x 768 each, bf16
    assert C.expert_bytes(w) == 3 * 2048 * 768 * 2 == 9_437_184


def test_distinct_experts_at_32_slots_top8():
    # 256 draws: E (1 - (1 - 1/E)^256)
    distinct = C.expected_distinct_experts
    assert distinct(128, 256) == pytest.approx(110.81, abs=0.01)
    assert distinct(64, 256) == pytest.approx(62.87, abs=0.01)
    assert C.expected_distinct_experts(128, 0) == 0.0


def test_decode_least_time_is_the_distinct_tables(w):
    flops, nbytes = C.moe_call(w, 128, 32)
    assert flops == pytest.approx(2 * 3 * 2048 * 768 * 256)
    tables = C.expected_distinct_experts(128, 256) * 9_437_184
    assert nbytes == pytest.approx(tables + 2 * 32 * 2048 * 2)
    # memory-bound: about 1.28 ms for one layer at 819 GB/s
    assert C.least_time(flops, nbytes, PK) == pytest.approx(
        nbytes / 819e9)
    assert 1.25e-3 < C.least_time(flops, nbytes, PK) < 1.30e-3


def test_prefill_group_is_compute_bound(w):
    flops, nbytes = C.moe_call(w, 128, 16 * 768)
    assert flops / PK["bf16_flops"] > nbytes / PK["hbm_bytes_per_s"]


def test_merged_widths_live_experts():
    conf = SPEC.load_json(SPEC.BENCH_DIR / "configs"
                          / "qwen3-moe-30b-a3b-merged64.json")
    w = MODEL.widths(conf)
    assert (w["split"], w["live"]) == (3, [128] * 3 + [64] * 3)


def test_flops_per_token(w):
    d, hd = 2048, 128
    attn = 2 * (d * 32 * hd + 2 * d * 4 * hd + 32 * hd * d)
    per = attn + 2 * d * 128 + 2 * 3 * d * 768 * 8
    assert C.linear_flops_per_token(w) == per
    # one prompt of 4: 4 tokens through 6 layers, causal scores, one head
    assert C.prefill_flops(w, 4) == pytest.approx(
        6 * (4 * per + 2 * 32 * hd * 4 * 5) + 2 * d * 151936)
    assert C.decode_flops(w, 2, 10) == pytest.approx(
        6 * (2 * per + 4 * 32 * hd * 10) + 2 * 2 * d * 151936)
