"""The load generator and the loop that drives the engine's public entry.

The window drives ``Engine.submit`` and ``Engine.step_block(now=...)`` on
the harness's own clock. A token counts as delivered when the
``step_block`` call that produced it returns; every time is taken here, on
the host clock, from when a request was due. ``Request.t_first_token`` is
not read: the engine stamps it with the block's start, before the prefill.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from traffic import Req


@dataclasses.dataclass
class Track:
    """One request as seen from outside the engine."""
    req: Req
    due: float
    t_submit: float
    n_prompt: int
    handle: object = None          # the engine's Request
    t_first: Optional[float] = None
    t_first_call: Optional[float] = None   # start of the delivering call
    t_last: Optional[float] = None
    n_out: int = 0
    done: bool = False
    status: str = ""
    # (return time of the delivering call, tokens it delivered), in order
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class Block:
    """One ``step_block`` call."""
    t_call: float
    t_ret: float
    n_active_before: int
    admitted: List[int]            # prompt lengths admitted by this call
    buckets: List[int]             # their admission pad lengths
    decode_tokens: int = 0         # tokens from the fused decode block
    decode_steps: int = 0          # decode steps that ran (max per slot)
    decode_ctx: int = 0            # keys attended, summed over decode tokens
    tokens_out: int = 0            # all tokens delivered, first ones too
    traced: bool = False


@dataclasses.dataclass
class Load:
    """Everything the window produced, on one clock (seconds from t0)."""
    tracks: List[Track]
    blocks: List[Block]
    w0: float                      # window opens (end of the ramp)
    w1: float                      # window closes
    trace_span: Optional[tuple] = None
    lateness: List[float] = dataclasses.field(default_factory=list)


def _span(name):
    return jax.profiler.TraceAnnotation(name)


def _start_trace(trace_dir: str) -> None:
    """Device ops and host spans; no Python function tracing, no HLO."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def drive(eng, reqs: List[Req], traffic: dict, seconds: float,
          trace_dir: Optional[str] = None,
          clock: Callable[[], float] = time.perf_counter) -> Load:
    """Offer ``reqs`` to ``eng`` for a ramp, then a window of ``seconds``;
    then keep stepping, with no new arrivals, until every request due in
    the window has its first token or ``drain_seconds`` have passed.

    With ``trace_dir`` the profiler records the last ``trace_seconds`` of
    the window, starting and stopping between two calls."""
    ramp = float(traffic["ramp_seconds"])
    w0, w1 = ramp, ramp + float(seconds)
    trace_from = w1 - min(float(traffic["trace_seconds"]), float(seconds))
    open_loop = traffic["loop"] == "open"
    queue = list(reqs)
    nxt = 0
    live: List[Track] = []
    tracks: List[Track] = []
    blocks: List[Block] = []
    lateness: List[float] = []
    refill: List[float] = []      # closed loop: due times of next sends
    tracing = False
    span = None
    t0 = clock()

    def now():
        return clock() - t0

    def submit(r: Req, due: float):
        with _span("bench.submit"):
            t = now()
            h = eng.submit(r.prompt, max_new_tokens=r.max_new,
                           arrival_time=due)
        tr = Track(req=r, due=due, t_submit=t, n_prompt=int(r.prompt.size),
                   handle=h)
        live.append(tr)
        tracks.append(tr)
        lateness.append(t - due)

    if not open_loop:
        for _ in range(int(traffic["concurrency"])):
            submit(queue[nxt], 0.0)
            nxt += 1

    def step():
        nonlocal live
        b = Block(t_call=now(), t_ret=0.0, n_active_before=eng.n_active,
                  admitted=[], buckets=[], traced=tracing)
        with _span("bench.step_block"):
            eng.step_block(now=b.t_call)
        b.t_ret = now()
        with _span("bench.collect"):
            still = []
            for tr in live:
                h = tr.handle
                n = len(h.out_tokens)
                if n > tr.n_out:
                    dec = n - tr.n_out
                    # token i (1-based) is decoded from the token at
                    # position n_prompt + i - 2, attending n_prompt + i - 1
                    b.decode_ctx += sum(tr.n_prompt + i - 1 for i in
                                        range(max(tr.n_out, 1) + 1, n + 1))
                    if tr.n_out == 0:
                        tr.t_first, tr.t_first_call = b.t_ret, b.t_call
                        b.admitted.append(tr.n_prompt)
                        b.buckets.append(eng.bucket_for(tr.n_prompt))
                        dec -= 1
                    b.decode_tokens += dec
                    b.decode_steps = max(b.decode_steps, dec)
                    b.tokens_out += n - tr.n_out
                    tr.deliveries.append((b.t_ret, n - tr.n_out))
                    tr.n_out, tr.t_last = n, b.t_ret
                if h.finish_reason is not None:
                    tr.done, tr.status = True, h.status
                    if not open_loop and b.t_ret < w1:
                        # a closed-loop client sends its next request now
                        refill.append(b.t_ret)
                else:
                    still.append(tr)
            live = still
        blocks.append(b)
        return b

    while True:
        t = now()
        if t >= w1:
            break
        if open_loop:
            while nxt < len(queue) and queue[nxt].due <= t:
                submit(queue[nxt], queue[nxt].due)
                nxt += 1
        else:
            for due in refill:
                submit(queue[nxt % len(queue)], due)
                nxt += 1
            refill.clear()
        if not live:
            if not open_loop or nxt >= len(queue):
                break
            with _span("bench.wait"):
                time.sleep(max(0.0, min(queue[nxt].due, w1) - now()))
            continue
        if trace_dir is not None and not tracing and t >= trace_from:
            _start_trace(trace_dir)
            tracing, span = True, [now(), None]
        step()
    if tracing:
        span[1] = now()
        jax.profiler.stop_trace()
    # drain: every request due in the window gets its first token
    grace = w1 + float(traffic["drain_seconds"])
    while now() < grace and any(
            tr.t_first is None and w0 <= tr.due < w1 for tr in live):
        step()
    return Load(tracks=tracks, blocks=blocks, w0=w0, w1=w1,
                trace_span=tuple(span) if span else None, lateness=lateness)


def warm(eng, traffic: dict) -> int:
    """Run every admission and decode program the mix can reach, through the
    engine's public entry, so none compiles in the window. Returns the
    number of calls made.

    Admission pads to (prompt pad length, power-of-two group); the engine
    then slices the group's rows out of the padded result, one small
    program per group size, so one pad length runs every group size."""
    lo, hi = int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
    n = eng.ec.n_slots
    pads = sorted({eng.bucket_for(m) for m in range(lo, hi + 1)})
    sizes_all = list(range(1, n + 1))
    sizes_pow2 = sorted({min(1 << i, n) for i in range(n.bit_length() + 1)})
    calls = 0
    for j, pad in enumerate(pads):
        for size in (sizes_all if j == 0 else sizes_pow2):
            for _ in range(size):
                eng.submit(np.ones((pad,), np.int32), max_new_tokens=1)
            done = eng.step_block(now=0.0)
            calls += 1
            if len(done) != size or not eng.idle:
                raise RuntimeError(f"warm-up admission of {size} x {pad} "
                                   f"finished {len(done)}")
    eng.submit(np.ones((lo,), np.int32),
               max_new_tokens=2 * eng.ec.decode_block + 1)
    while not eng.idle:
        eng.step_block(now=0.0)
        calls += 1
    return calls
