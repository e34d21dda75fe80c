"""Device nanoseconds of the output assembly per C value: ``assemble_ms``
(the ops under the ``spgemm.assemble`` scope per product) times 1e6 over
the values one product assembles, the ``assemble_values`` argument of the
program's ``spgemm.dispatch`` spans in the traced window over their number
(:mod:`bench.dispatches`). It puts configurations of different sizes on
one scale.
"""
from bench import dispatches, load_module

UNIT = "ns"


def read(ctx):
    values = dispatches.mean_arg(ctx, "assemble_values")
    assemble_ms = load_module("metrics", "assemble_ms").read(ctx)
    if not values or assemble_ms is None:
        return None
    return assemble_ms * 1e6 / values
