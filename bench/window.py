"""What the readers of ``bench/metrics/`` share: the run's context and the
requests and calls that fall in the measured window."""
from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional

import numpy as np

from serve import Block, Load, Track


@dataclasses.dataclass
class Run:
    load: Load
    widths: dict            # model.widths of the configuration
    peaks: dict             # peaks.peaks(device_kind)
    chips: int
    setup_s: float
    compiles_in_window: int
    trace: Optional[object] = None   # trace_reduce.Reduced (--trace 1)


def quantile_ms(values, q: float) -> Optional[float]:
    return 1e3 * float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def token_gaps(run: Run) -> np.ndarray:
    """The inter-token latency of every token delivered in the window after
    its request's first call, one entry per token: the time since the
    request's previous delivery over the tokens the call delivered. Tokens
    that come with the first one belong to the time to first token."""
    ld = run.load
    gaps, counts = [], []
    for t in ld.tracks:
        d = t.deliveries
        for (ta, _), (tb, k) in zip(d, d[1:]):
            if ld.w0 < tb <= ld.w1:
                gaps.append((tb - ta) / k)
                counts.append(k)
    return np.repeat(np.asarray(gaps, float), np.asarray(counts, int))


def first_token_s(run: Run) -> List[float]:
    """Due time to first token delivered, for every request due in the
    window that got one (one that did not fails the run)."""
    return [t.t_first - t.due for t in due_in_window(run.load)
            if t.t_first is not None]


def queue_wait_s(run: Run) -> List[float]:
    """Due time to the start of the call that delivered the first token,
    for every request due in the window that got one."""
    return [t.t_first_call - t.due for t in due_in_window(run.load)
            if t.t_first_call is not None]


def due_in_window(load: Load) -> List[Track]:
    return [t for t in load.tracks if load.w0 <= t.due < load.w1]


def blocks_in_window(run: Run) -> List[Block]:
    ld = run.load
    return [b for b in ld.blocks if ld.w0 < b.t_ret <= ld.w1]


def window_s(run: Run) -> float:
    return run.load.w1 - run.load.w0


def traced_blocks(run: Run) -> List[Block]:
    return [b for b in run.load.blocks if b.traced]


def kernel_share(run: Run, kernel: str, need_s: float,
                 calls: int) -> Optional[float]:
    """``need_s``, the least time of the work the traced calls gave the
    kernel, over the device time of its ops in the trace, in %. None where
    the trace lost device events or holds another number of the kernel's
    ops than the ``calls`` the traced blocks made."""
    t = run.trace
    if t is None or not calls:
        return None
    ops = t.kernel_ops(kernel)
    if len(ops) != calls or not t.complete:
        print(f"{kernel}: {len(ops)} ops traced of {calls}, "
              f"{t.lost_calls()} calls with no device op: withheld",
              file=sys.stderr)
        return None
    return 100.0 * need_s / (sum(o.dur for o in ops) * 1e-9)
