"""Device milliseconds of the Pallas SpGEMM kernel per product: the summed
durations of its events in the traced window over the products completed
in it.

The kernel is found by name. On a TPU v5 lite under jax 0.9.0 the trace's
``XLA Ops`` line names each op by its HLO text, and the Pallas call is the
op whose text holds ``KERNEL_MARK`` (``%kernel_core.1 = f32[<panels>,512,128]
... custom-call(...), custom_call_target="tpu_custom_call"`` on the stream
path, ``%numeric_core.1 = ...`` on the single path): read by hand from
this benchmark's traces (PERF.md). The SpGEMM kernel is the only Pallas
call on these paths.
"""
UNIT = "ms"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["completed"]:
        return None
    total = sum(s for name, s in tr["op_s"].items() if KERNEL_MARK in name)
    if total <= 0:
        return None
    return total * 1e3 / ctx["completed"]
