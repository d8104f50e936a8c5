"""The comparison that decides ``correct``, made once the window has closed.

A sample of the requests the run finished, drawn from the seed and holding
the one that generated most, is replayed through the float32 reference
(``reference.py``): each prompt followed by its served tokens. At every
served position, the gap is how far the served token's reference logit lies
below the reference's best. The mean gap over the sample's served positions
is compared with the cell's limit (``bench/limits/<cell>.json``). Greedy
decoding in exact arithmetic gives 0; rounding flips only near-ties, by
little.
"""
from __future__ import annotations

from typing import List

import numpy as np

import reference as R
import traffic as TR


def sample(tracks, n: int, seed: int) -> list:
    """``n`` finished requests: the longest and ``n - 1`` others at random."""
    done = [t for t in tracks if t.done and t.status == "ok" and t.n_out]
    if not done:
        return []
    done.sort(key=lambda t: (-t.n_out, t.req.idx))
    rest = done[1:]
    pick = TR.rng(seed, TR.SAMPLE).permutation(len(rest))[:max(0, n - 1)]
    return [done[0]] + [rest[i] for i in sorted(pick)]


def batch(picked, pad: int, chunk: int):
    """Token and target rows for ``picked``, padded to ``pad`` positions and
    to a multiple of ``chunk`` rows; ``mask`` marks served positions."""
    rows = -(-len(picked) // chunk) * chunk
    tokens = np.zeros((rows, pad), np.int32)
    targets = np.zeros((rows, pad), np.int32)
    mask = np.zeros((rows, pad), bool)
    for i, t in enumerate(picked):
        out = np.asarray(t.handle.out_tokens, np.int32)
        seq = np.concatenate([t.req.prompt, out[:-1]])
        if seq.size > pad:
            raise ValueError(f"sequence of {seq.size} > check pad {pad}")
        P = t.n_prompt
        tokens[i, :seq.size] = seq
        targets[i, P - 1:P - 1 + out.size] = out
        mask[i, P - 1:P - 1 + out.size] = True
    return tokens, targets, mask


def gap_summary(params, spec: tuple, picked, check: dict,
                control: bool = False) -> dict:
    """The gaps at the sample's served positions, summed up: ``mean_gap``
    (the number compared), ``max_gap`` and ``flip_share`` (printed only),
    and under ``"gaps"`` the gap at every served position. With ``control``
    the gaps are the int8 control's (``reference.served_gaps``)."""
    tokens, targets, mask = batch(picked, int(check["pad"]),
                                  int(check["chunk"]))
    gaps = R.served_gaps(params, spec, tokens, targets, int(check["chunk"]),
                         control=control)
    served = gaps[mask]
    if not served.size:
        return {"max_gap": None, "gaps": served}
    return {"max_gap": float(served.max()),
            "mean_gap": float(served.mean()),
            "flip_share": float((served > 0).mean()),
            "served_tokens": int(mask.sum()),
            "requests": len(picked), "gaps": served}


def verdict(found: dict, limits: dict) -> List[tuple]:
    """(name, value, limit, ok) for each number compared."""
    out = []
    for name, lim in limits["compare"].items():
        v = found.get(name)
        limit = lim["limit"]
        ok = v is not None and limit is not None and v <= limit
        out.append((name, v, limit, ok))
    return out
