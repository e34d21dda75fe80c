"""``plan.execute(a_vals, b_vals)``: one caller, closed loop.

Each product starts when the previous one's CSR is in hand. Latency runs
from the call to its return, which is when C's values are on the host.
"""
import time
import traceback


def warm(plan, values, traffic):
    for _ in range(2):
        c = plan.execute(*values)
    return c


def run(plan, ring, seconds, traffic, sink, span):
    latencies, errors, n = [], [], 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t_end = t_start
    while t_end < deadline:
        slot = n % len(ring)
        t = time.perf_counter()
        try:
            with span("bench.execute"):
                c = plan.execute(*ring[slot])
        except Exception:  # a failed product is counted; the window goes on
            errors.append(traceback.format_exc())
            c = None
        t_end = time.perf_counter()
        if c is not None:
            sink(len(latencies), slot, c)
            latencies.append(t_end - t)
        n += 1
    return {"window_s": t_end - t_start, "latencies_s": latencies,
            "attempted": n, "completed": len(latencies), "errors": errors[:1]}
