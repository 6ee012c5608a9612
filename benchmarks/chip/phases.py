"""What the per-layer metrics of the program's own tracing read.

The program (``repro.telemetry``) names each round's device phases by
``jax.named_scope`` scopes, which the window program's instructions carry
in their ``op_name``, and records its host spans and compile counters in
memory.  A program without that module has none of them, and every
reader then returns ``None``.
"""
from __future__ import annotations

from devtrace import SPAN


def telemetry():
    """The program's ``repro.telemetry`` module, or ``None``."""
    try:
        from repro import telemetry as tel
    except ImportError:
        return None
    return tel


def window_rounds(trace) -> int:
    """Rounds dispatched inside the traced window."""
    lo, hi = trace.window
    return sum(1 for name, s, _ in trace.spans
               if name == SPAN + "round.dispatch" and lo <= s < hi)


def per_round_s(run, scopes):
    """Device seconds a round of the window spends in ops under any of
    ``scopes``; ``scopes`` ``None`` means in ops under none of the phase
    scopes.  ``None`` where the program names no phases."""
    tel = telemetry()
    hlo = run.trace.hlo
    n = window_rounds(run.trace)
    if tel is None or hlo is None or not n or not any(
            p in op for op in hlo.op_name.values() for p in tel.PHASES):
        return None
    if scopes is not None:
        return run.trace.attributed_s(op_names=scopes) / n
    leaves = sum(s for _, s in run.trace.top_ops(10 ** 6))
    return (leaves - run.trace.attributed_s(op_names=tel.PHASES)) / n


def newest_span(name: str):
    """The program's newest host span called ``name``, or ``None``."""
    tel = telemetry()
    found = [s for s in tel.spans() if s.name == name] if tel else []
    return found[-1] if found else None
