"""Milliseconds per SpGEMM: the whole window over the products whose C
reached the host as a CSR in it (mean time per product in a closed loop,
inverse throughput in a stream)."""
UNIT = "ms"


def read(ctx):
    if not ctx["completed"]:
        return None
    return ctx["window_s"] * 1e3 / ctx["completed"]
