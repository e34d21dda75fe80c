"""The program's ``spgemm.dispatch`` spans in a ``--trace 1`` run's window:
one per call into the executor, each with its counts as arguments
(:mod:`bench.spans`). :func:`mean_arg` reads what one call carries on
average: an argument summed over the window's spans over their number.

The counts are structural (``kernel_calls`` and ``triples`` are fixed
when the plan is built), so their means read the same in every run of a
cell; they say which schedule ran, not how fast.
"""
from __future__ import annotations

from bench import spans, trace

__all__ = ["DISPATCH", "mean_arg"]

DISPATCH = "spgemm.dispatch"


def _window_dispatches(ctx: dict) -> list:
    """The arguments of the ``spgemm.dispatch`` spans that start in the
    window, read from the run's trace once and kept in ``ctx``."""
    if "dispatches" not in ctx:
        from bench.harness import TRACE_DIR

        events = spans.load(trace.newest_xplane(TRACE_DIR))
        w0, w1 = trace._window(events)
        ctx["dispatches"] = [args for name, start, _, args in events["spans"]
                             if name == DISPATCH and w0 <= start < w1]
    return ctx["dispatches"]


def mean_arg(ctx: dict, arg: str) -> float | None:
    """Argument ``arg`` of the window's ``spgemm.dispatch`` spans, summed
    and over their number; ``None`` without a trace, or where the spans
    do not carry ``arg``."""
    if ctx.get("trace") is None:
        return None
    found = [a[arg] for a in _window_dispatches(ctx)
             if isinstance(a.get(arg), (int, float))]
    if not found:
        return None
    return sum(found) / len(found)
