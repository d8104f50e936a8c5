"""Retrace + implicit-transfer guard for jitted serving entry points.

DESIGN.md §7's dispatches-per-token win rests on two runtime properties the
type system cannot see: each jitted entry point compiles a BOUNDED number
of specializations (decode: exactly one; admission: one per
(bucket, pow2-group) pair), and the steady-state decode block moves NO data
between host and device except the one ``[K, B]`` token readback the engine
counts in ``host_syncs``. Both regress silently — a stray ``float(...)`` on
config, a numpy arg that slips past ``jnp.asarray``, a shape that varies
per call — and only show up as a 10x wall-clock cliff on real hardware.

:class:`TraceGuard` makes both properties observable and enforceable:

* ``wrap_jit(name, fn, expected_traces)`` jits ``fn`` with a shim that
  counts Python-body executions — i.e. actual traces, not dispatches.
  Traces beyond ``expected_traces`` increment ``counters["retraces"]``
  (mode ``"count"``) or raise :class:`TraceGuardError` (mode ``"strict"``).
* ``run(name, fn, *args)`` executes one call. Once ``name`` has traced at
  least once (warmup done — compilation itself legitimately transfers
  constants), the call runs under ``jax.transfer_guard("disallow")``: any
  implicit device<->host transfer increments
  ``counters["implicit_transfers"]`` (count mode re-executes unguarded —
  jitted calls are pure, so the retry is side-effect-free) or raises
  (strict mode).

Mode ``"off"`` degrades to plain ``jax.jit`` with zero overhead besides
the trace counter.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

import jax

__all__ = ["TraceGuard", "TraceGuardError"]


# what ``jax.transfer_guard("disallow")`` raises: a JaxRuntimeError whose
# message names the refused direction. Other runtime errors (a device fault
# whose text happens to mention a transfer) are not the guard's to absorb.
_GUARD_REFUSAL = re.compile(
    r"Disallowed (host-to-device|device-to-host|device-to-device) transfer")


def _is_guard_refusal(e: Exception) -> bool:
    return (isinstance(e, jax.errors.JaxRuntimeError)
            and _GUARD_REFUSAL.search(str(e)) is not None)


class TraceGuardError(RuntimeError):
    """A guarded invariant (no retraces / no implicit transfers) broke in
    strict mode."""


class TraceGuard:
    """Per-engine registry of guarded jitted callables.

    ``counters`` may be a shared dict (the Engine passes its own
    ``counters``); the guard only touches the ``"retraces"`` and
    ``"implicit_transfers"`` keys, creating them if absent.
    """

    def __init__(self, mode: str = "count",
                 counters: Optional[Dict[str, int]] = None):
        if mode not in ("off", "count", "strict"):
            raise ValueError(f"unknown trace-guard mode {mode!r}")
        self.mode = mode
        self.counters: Dict[str, int] = (
            counters if counters is not None else {})
        self.counters.setdefault("retraces", 0)
        self.counters.setdefault("implicit_transfers", 0)
        self.traces: Dict[str, int] = {}
        self.expected: Dict[str, int] = {}

    # ------------------------------------------------------------- wrapping
    def wrap_jit(self, name: str, fn: Callable, expected_traces: int = 1,
                 **jit_kwargs: Any) -> Callable:
        """``jax.jit(fn)`` with trace counting under ``name``.

        The counting shim runs inside the traced body, so it executes once
        per compilation, never per dispatch — steady-state overhead is
        zero. ``expected_traces`` is the specialization budget; traces
        beyond it are retraces."""
        self.traces.setdefault(name, 0)
        self.expected[name] = int(expected_traces)

        def counted(*args, **kw):
            self.traces[name] += 1
            if self.traces[name] > self.expected[name]:
                self.counters["retraces"] += 1
                if self.mode == "strict":
                    raise TraceGuardError(
                        f"`{name}` traced {self.traces[name]} times "
                        f"(budget {self.expected[name]}): an argument's "
                        f"shape/dtype or a closed-over static changed "
                        f"after warmup")
            return fn(*args, **kw)

        counted.__name__ = getattr(fn, "__name__", name)
        return jax.jit(counted, **jit_kwargs)

    # ------------------------------------------------------------- running
    def warmed(self, name: str) -> bool:
        """True once ``name`` has compiled at least once (transfer guard
        arms only past this point — compilation itself device_puts
        constants, which is legitimate)."""
        return self.traces.get(name, 0) >= 1

    def run(self, name: str, fn: Callable, *args: Any) -> Any:
        """Execute ``fn(*args)``; steady-state calls run under
        ``jax.transfer_guard("disallow")``."""
        if self.mode == "off" or not self.warmed(name):
            return fn(*args)
        try:
            with jax.transfer_guard("disallow"):
                return fn(*args)
        except jax.errors.JaxRuntimeError as e:
            if not _is_guard_refusal(e):
                raise
            self.counters["implicit_transfers"] += 1
            if self.mode == "strict":
                raise TraceGuardError(
                    f"implicit device<->host transfer inside guarded "
                    f"`{name}`: {e}") from e
            return fn(*args)
