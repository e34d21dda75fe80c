"""Pallas calls per product: the ``kernel_calls`` argument of the program's
``spgemm.dispatch`` spans in the traced window over their number
(:mod:`bench.dispatches`). One call takes a schedule of at most the
triples that one call's SMEM holds; a longer schedule runs as several.
"""
from bench import dispatches

UNIT = "1"


def read(ctx):
    return dispatches.mean_arg(ctx, "kernel_calls")
