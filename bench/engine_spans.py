"""The serving engine's own host spans (``engine.*``) in a profiler trace,
with their arguments, and what they show inside a ``step_block`` call that
the harness's ``bench.*`` spans cannot.

The engine emits, per call, ``engine.step_block`` holding ``engine.admit``,
one ``engine.admit_group`` per prefill group (``pad``, ``rows``,
``rows_padded``, ``real_tokens``), ``engine.decode_inputs``,
``engine.decode_block`` (``steps``, ``rows``) and ``engine.commit``. Read
here from a trace the harness kept (``bench/run.py --keep-trace <dir>``):

- ``decode_step_ms_p50``: median over ``engine.decode_block`` spans of the
  span's duration over the decode steps in which a slot emitted;
- ``admit_pad_share``: 100 x (1 - real prompt tokens / padded tokens) over
  the ``engine.admit_group`` spans, the share of prefill rows that is pad;
- ``uncovered_share``: the share of ``engine.step_block`` time under none
  of its phase spans;
- ``idle_gaps``: the trace's idle gaps named by the innermost span of
  either kind, ``bench.*`` or ``engine.*``; the window, the busy time and
  the lost calls stay those of ``trace_reduce``.

    python3 bench/engine_spans.py <dir or .xplane.pb>
"""
from __future__ import annotations

import dataclasses
import json
import re
import sys
from typing import Dict, List, Optional

import numpy as np

import trace_reduce as TRD

ENGINE_SPAN = re.compile(r"^engine\.")
CALL = "engine.step_block"


@dataclasses.dataclass
class Span:
    name: str
    start: int                  # ns, on the trace's clock
    end: int
    args: Dict[str, int]


def read(path: str) -> List[Span]:
    """Every ``engine.*`` host span of the trace, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(TRD.find(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ENGINE_SPAN.match(ev.name):
                    a = int(ev.start_ns)
                    out.append(Span(ev.name, a, a + int(ev.duration_ns),
                                    {k: int(v) for k, v in ev.stats}))
    return sorted(out, key=lambda s: s.start)


def decode_step_ms_p50(spans: List[Span]) -> Optional[float]:
    per = [(s.end - s.start) * 1e-6 / s.args["steps"] for s in spans
           if s.name == "engine.decode_block" and s.args.get("steps")]
    return float(np.median(per)) if per else None


def admit_pad_share(spans: List[Span]) -> Optional[float]:
    groups = [s.args for s in spans if s.name == "engine.admit_group"]
    padded = sum(a["rows_padded"] * a["pad"] for a in groups)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["real_tokens"] for a in groups) / padded)


def uncovered_share(spans: List[Span]) -> Optional[float]:
    """Share of the calls' time under none of their phase spans (the
    phases follow one another, so their durations add)."""
    calls = [s for s in spans if s.name == CALL]
    total = sum(c.end - c.start for c in calls)
    if not total:
        return None
    covered = sum(s.end - s.start for s in spans if s.name != CALL
                  and any(c.start <= s.start and s.end <= c.end
                          for c in calls))
    return 1.0 - covered / total


def idle_gaps(red: TRD.Reduced, spans: List[Span], top: int = 10) -> list:
    """``red``'s idle gaps, each named by the innermost span of either
    kind that covers its middle."""
    both = sorted(red.spans + [(s.name, s.start, s.end) for s in spans],
                  key=lambda s: s[1])
    return dataclasses.replace(red, spans=both).breakdown(top)["idle_gaps"]


def summary(path: str) -> dict:
    red, spans = TRD.reduce(path), read(path)
    return {"calls": sum(s.name == CALL for s in spans),
            "decode_step_ms_p50": decode_step_ms_p50(spans),
            "admit_pad_share": admit_pad_share(spans),
            "uncovered_share": uncovered_share(spans),
            "idle_s": red.window_s - red.busy_s,
            "idle_gaps": idle_gaps(red, spans)}


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    print(json.dumps(summary(argv[0]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
