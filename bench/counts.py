"""Operations and bytes the algorithm needs, from shapes.

Counted from the model's sizes (``model.widths``), never from what a kernel
happens to stream, so the counts stay valid when a kernel is replaced or
taken off the path. ``expected_distinct_experts`` and the per-expert bytes
are copied from the program (``launch/hlo_analysis.py``,
``core/plan.expert_bytes``).
"""
from __future__ import annotations

ACT_BYTES = 2          # bf16 activations
WEIGHT_BYTES = 2       # bf16 weights


def expected_distinct_experts(n_experts: int, draws: float) -> float:
    """Expected distinct experts hit by ``draws`` uniform routing draws over
    ``n_experts``: ``E·(1 − (1 − 1/E)^draws)``."""
    if n_experts <= 0 or draws <= 0:
        return 0.0
    return n_experts * (1.0 - (1.0 - 1.0 / n_experts) ** draws)


def expert_bytes(w: dict) -> int:
    """One expert's gate, up and down tables."""
    return 3 * w["d"] * w["f"] * WEIGHT_BYTES


def moe_call(w: dict, live: int, tokens: float) -> tuple:
    """(FLOPs, bytes) one MoE expert computation over ``tokens`` rows needs
    in a layer of ``live`` experts: the distinct experts' tables once, the
    rows in and the combined rows out."""
    pairs = tokens * w["k"]
    flops = 2.0 * 3 * w["d"] * w["f"] * pairs
    nbytes = (expected_distinct_experts(live, pairs) * expert_bytes(w)
              + 2 * tokens * w["d"] * ACT_BYTES)
    return flops, nbytes


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])


def linear_flops_per_token(w: dict) -> float:
    """One token through one layer's projections, router and its k experts
    (attention scores excluded)."""
    d, nq, nkv, hd = w["d"], w["nq"], w["nkv"], w["hd"]
    attn = 2.0 * (d * nq * hd + 2 * d * nkv * hd + nq * hd * d)
    return attn + 2.0 * d * w["E"] + 2.0 * 3 * d * w["f"] * w["k"]


def head_flops(w: dict) -> float:
    return 2.0 * w["d"] * w["V"]


def prefill_flops(w: dict, n_prompt: int) -> float:
    """A prompt's forward through every layer, causal attention, and the
    head at its last position (the first generated token)."""
    scores = 2.0 * w["nq"] * w["hd"] * n_prompt * (n_prompt + 1)
    return w["L"] * (n_prompt * linear_flops_per_token(w) + scores) \
        + head_flops(w)


def decode_flops(w: dict, tokens: int, ctx: int) -> float:
    """``tokens`` decoded tokens attending ``ctx`` keys in all."""
    return w["L"] * (tokens * linear_flops_per_token(w)
                     + 4.0 * w["nq"] * w["hd"] * ctx) + tokens * head_flops(w)

