"""Device microseconds of the Pallas kernel per block triple: ``kernel_ms``
(the kernel's device time per product) times 1000 over the triples one
product runs, the ``triples`` argument of the program's ``spgemm.dispatch``
spans in the traced window over their number (:mod:`bench.dispatches`).
It puts a schedule split into several calls and one whole call on one
scale.
"""
from bench import dispatches, load_module

UNIT = "us"


def read(ctx):
    triples = dispatches.mean_arg(ctx, "triples")
    kernel_ms = load_module("metrics", "kernel_ms").read(ctx)
    if triples is None or kernel_ms is None:
        return None
    return kernel_ms * 1e3 / triples
