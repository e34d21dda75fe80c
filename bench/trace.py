"""Profiler trace of a run's window, and its reduction to numbers.

``--trace 1`` runs the window under ``jax.profiler``. :func:`load` reads the
``.xplane.pb`` it leaves into a small JSON-able dict:

* ``devices``: for each device plane, the events of its ``XLA Ops`` line
  as ``[name, start_ns, duration_ns]``;
* ``host``: the benchmark's own spans (names starting ``bench.``), which
  the paths open around their calls into the program, on the same clock.

:func:`reduce` turns that dict into the device's busy time (the union of
its op intervals inside the ``bench.window`` span), time per op name, and
the idle gaps attributed to the innermost benchmark span the host was in.
Both are plain functions so the reduction is checked on a recorded trace
(``bench/testdata``) without a chip.

``python3 bench/trace.py <file.xplane.pb>`` prints every plane and line of
a trace with its busiest event names, for reading a trace by hand.
"""
from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict

__all__ = ["start", "stop", "newest_xplane", "load", "reduce", "union", "excerpt"]

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls are not traced: host spans suffice
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, device_prefix: str = DEVICE_PLANE_PREFIX) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        else:
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def union(intervals):
    """Merged ``[start, end]`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(events: dict):
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(spans)}")
    return spans[0]


def _clipped(ops, w0, w1):
    for name, s, d in ops:
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 > s0:
            yield name, s0, e0


def _spans_at(spans, times):
    """Innermost benchmark span covering each of the sorted ``times``
    (one sweep: spans are sorted by start and nest only a few deep)."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] + sp[2] > t]
        inner = min(active, key=lambda sp: sp[2], default=None)
        out.append(inner[0] if inner else "(no bench span)")
    return out


def reduce(events: dict) -> dict:
    """Busy and idle time of the window, averaged over the device planes,
    with the busiest op names and the idle time by host span (first device)."""
    w0, w1 = _window(events)
    window_ns = w1 - w0
    planes = sorted(events["devices"])
    if not planes:
        raise ValueError("the trace has no device plane")
    busy, op_ns = [], defaultdict(int)
    for p in planes:
        clipped = list(_clipped(events["devices"][p], w0, w1))
        for name, s, e in clipped:
            op_ns[name] += e - s
        merged = union([s, e] for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        if p == planes[0]:
            first = merged
    spans = [h for h in events["host"] if h[0] != WINDOW_SPAN]
    idle_ns = defaultdict(int)
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    for (s, e), name in zip(gaps, _spans_at(spans, [(s + e) // 2 for s, e in gaps])):
        idle_ns[name] += e - s
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    idle_top = sorted(idle_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
        "device_ops": [[k, v * 1e-9] for k, v in top],
        "idle_gaps": [[k, v * 1e-9] for k, v in idle_top],
    }


def excerpt(events: dict, span: str, count: int) -> dict:
    """The first ``count`` ``span`` spans of a trace as a trace of its own:
    a ``bench.window`` from the first one's start to the last one's end,
    and the device ops and host spans that overlap it (``bench/testdata``
    holds such excerpts of chip traces)."""
    spans = [h for h in events["host"] if h[0] == span][:count]
    w0, w1 = spans[0][1], spans[-1][1] + spans[-1][2]
    keep = [[n, s, d] for n, s, d in events["host"]
            if n != WINDOW_SPAN and s < w1 and s + d > w0]
    return {
        "devices": {p: [[n, s, d] for n, s, d in ops if s < w1 and s + d > w0]
                    for p, ops in events["devices"].items()},
        "host": [[WINDOW_SPAN, w0, w1 - w0]] + keep,
    }


def _summary(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            tot, cnt = defaultdict(int), defaultdict(int)
            for e in line.events:
                tot[e.name] += int(e.duration_ns)
                cnt[e.name] += 1
            print(f"  line {line.name!r}: {sum(cnt.values())} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns * 1e-6:12.3f} ms  x{cnt[name]:<6d} {name}")


if __name__ == "__main__":
    _summary(sys.argv[1])
