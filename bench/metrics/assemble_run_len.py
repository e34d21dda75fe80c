"""C values per run of the output assembly: the ``assemble_values``
argument of the program's ``spgemm.dispatch`` spans in the traced window
over their ``assemble_runs`` (:mod:`bench.dispatches`). Both are fixed
when the plan is built, so it is structural: the mean length of the runs
the run-wise assembly kernel places, and 1 where the assembly gathers one
value at a time. It says which assembly ran, not how fast.
"""
from bench import dispatches

UNIT = "1"


def read(ctx):
    values = dispatches.mean_arg(ctx, "assemble_values")
    runs = dispatches.mean_arg(ctx, "assemble_runs")
    if values is None or not runs:
        return None
    return values / runs
