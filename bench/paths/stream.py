"""``plan.execute_stream(values, depth=traffic["depth"])``: one caller.

The stream pulls the next value set whenever it has room for it in its
``depth``-deep pipeline and yields the CSRs in order. Latency runs from the
moment a value set is handed to the stream to the moment its CSR is yielded.
The source stops handing out sets at the deadline; the window closes when
the last set handed out has come back.
"""
import time
import traceback


def warm(plan, values, traffic):
    depth = int(traffic["depth"])
    for c in plan.execute_stream([values] * (depth + 1), depth=depth):
        pass
    return c


def run(plan, ring, seconds, traffic, sink, span):
    handed = []  # (time handed over, ring slot), in order
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def source():
        n = 0
        while time.perf_counter() < deadline:
            with span("bench.stream_next"):
                slot = n % len(ring)
                handed.append((time.perf_counter(), slot))
            yield ring[slot]
            n += 1

    latencies, errors = [], []
    results = plan.execute_stream(source(), depth=int(traffic["depth"]))
    t_end = t_start
    while True:
        try:
            with span("bench.wait_for_c"):
                c = next(results)
        except StopIteration:
            break
        except Exception:  # the stream ends at a failed step; it is counted
            errors.append(traceback.format_exc())
            break
        t_end = time.perf_counter()
        t_in, slot = handed[len(latencies)]
        sink(len(latencies), slot, c)
        latencies.append(t_end - t_in)
    results.close()
    return {"window_s": t_end - t_start, "latencies_s": latencies,
            "attempted": len(handed), "completed": len(latencies), "errors": errors}
