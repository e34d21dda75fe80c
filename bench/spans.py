"""The program's own instrumentation in a ``--trace 1`` run's profiler trace.

:mod:`bench.trace` reads the benchmark's spans (``bench.*``) and the device
ops. The program under test adds its own (``repro.spgemm``): host spans
named ``spgemm.*`` (``execute``, ``submit``, ``rebind``, ``dispatch``,
``collect``, ``wait``, ``d2h``), each with its counts as arguments, and the
named scopes ``spgemm.bind``, ``spgemm.kernel`` and ``spgemm.assemble``,
which reach each device op's HLO ``op_name`` metadata. :func:`load` reads
a trace into :mod:`bench.trace`'s dict plus two keys:

* ``spans``: the program's host spans as ``[name, start_ns, duration_ns,
  {argument: value}]``;
* ``scopes``: for each device plane, the outermost ``spgemm.*`` scope of
  each of its ops (``None`` where an op has none), in the order of
  ``devices``.

On a TPU v5 lite under jax 0.9.0 an op's event carries no stat with its
``op_name`` (only ``device_offset_ps``, ``device_duration_ps`` and ``Time
Scale Multiplier``; read by hand, PERF.md), so the scope is looked up in
the HLO of the op's module, which the trace keeps (:func:`hlo_modules`).

:func:`reduce` adds to :func:`bench.trace.reduce` the device seconds in
the window by scope (``scope_s``), the host seconds by program span
(``span_s``), the span arguments summed over the window (``span_args``),
and ``idle_gaps`` over the spans of both prefixes: each instant of
device-idle time goes to the innermost span the host was in. A trace
without the program's instrumentation reduces to empty ``scope_s``,
``span_s`` and ``span_args``, so the readers of ``bench/metrics`` read
nothing from it.

``python3 bench/spans.py <file.xplane.pb>`` prints, for every plane and
line of a trace, its busiest events with their stats, and the scoped
instructions of each module; then, for a ``--trace 1`` run's trace, the
reduction (device time by scope, host time and arguments by program span,
device-idle time by span) and :func:`waits`, the per-product account of
the waits for results.
"""
from __future__ import annotations

import bisect
import os
import re
import sys
from collections import defaultdict

if __package__ in (None, ""):  # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace  # noqa: E402

__all__ = ["PREFIX", "hlo_modules", "op_scopes", "load", "idle_by_span", "reduce", "excerpt",
           "of", "per_product_ms", "waits", "report"]

PREFIX = "spgemm."
SCOPE = re.compile(r"(?:^|/)(spgemm\.[A-Za-z_]+)(?=/|$)")
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(")
INSTRUCTION = re.compile(r"^\s+(ROOT )?%([^\s=]+) = ")
CALLS = re.compile(r"(?:calls|to_apply)=%([^\s,)]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message, read at the wire
    level: varints as ints, length-delimited fields as memoryviews; fixed
    width fields are skipped."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, value


def hlo_modules(path: str) -> dict:
    """``{module name: HLO text}`` of the programs in a trace: the "Hlo
    Proto" the profiler keeps for each module on its ``/host:metadata``
    plane, which :class:`jax.profiler.ProfileData` does not reach. Read
    with the field numbers of tsl's ``xplane.proto`` (``XSpace.planes`` 1;
    ``XPlane.name`` 2, ``.event_metadata`` 4, a map entry's value 2;
    ``XEventMetadata.name`` 2, ``.stats`` 5; ``XStat.bytes_value`` 6) and
    xla's ``hlo.proto`` (``HloProto.hlo_module`` 1)."""
    from jaxlib import _jax

    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for num, plane in _fields(data):
        fields = list(_fields(plane)) if num == 1 else ()
        if not any(n == 2 and bytes(v) == METADATA_PLANE.encode() for n, v in fields):
            continue
        for n, entry in fields:
            if n != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next(bytes(v).decode() for k, v in meta if k == 2)
            for k, stat in meta:
                proto = dict(_fields(stat)).get(6) if k == 5 else None
                if proto is not None:
                    module = dict(_fields(proto))[1]
                    out[name] = _jax.HloModule.from_serialized_hlo_module_proto(
                        bytes(module)).to_string()
    return out


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: outermost spgemm.* scope}`` of one module's
    HLO text, from each instruction's ``op_name`` metadata; an instruction
    without one (a fusion, a call) takes the scope of the root of the
    computation it calls."""
    own, calls, root, comp = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op = OP_NAME.search(line)
        scope = SCOPE.search(op.group(1)) if op else None
        own[name] = scope.group(1) if scope else None
        call = CALLS.search(line)
        if call:
            calls[name] = call.group(1)
        if m.group(1):
            root[comp] = name

    def resolve(name, depth=0):
        if own.get(name) or name not in calls or depth > 8:
            return own.get(name)
        return resolve(root.get(calls[name]), depth + 1)

    return {name: resolve(name) for name in own}


def _args(stats) -> dict:
    return {k: v for k, v in stats if not k.startswith("_")}


def load(path: str, device_prefix: str = trace.DEVICE_PLANE_PREFIX) -> dict:
    """:func:`bench.trace.load` of the trace, with the program's ``spans``
    and the ops' ``scopes``: each op's module is the ``XLA Modules`` event
    it falls in, and its scope is looked up in that module's HLO
    (:func:`hlo_modules`)."""
    from jax.profiler import ProfileData

    events = trace.load(path, device_prefix)
    modules = {name: op_scopes(text) for name, text in hlo_modules(path).items()}
    runs, found = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            runs[plane.name] = sorted((int(e.start_ns), e.name) for line in plane.lines
                                      if line.name == MODULES_LINE for e in line.events)
            continue
        found.extend([e.name, int(e.start_ns), int(e.duration_ns), _args(e.stats)]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(PREFIX))
    events["scopes"] = {p: _scopes(ops, runs.get(p, []), modules)
                        for p, ops in events["devices"].items()}
    events["spans"] = sorted(found, key=lambda e: e[1])
    return events


def _scopes(ops, runs, modules):
    starts = [s for s, _ in runs]
    out = []
    for name, start, _ in ops:
        i = bisect.bisect_right(starts, start) - 1
        module = modules.get(runs[i][1], {}) if i >= 0 else {}
        inst = re.match(r"%([^\s=]+) = ", name)
        out.append(module.get(inst.group(1)) if inst else None)
    return out


def idle_by_span(events: dict) -> dict:
    """Device-idle nanoseconds of the window (first device plane) by the
    innermost span, of either prefix, the host was in at each instant:
    a gap that spans several host steps is split among them
    (:func:`bench.trace.reduce` names a whole gap by its midpoint)."""
    w0, w1 = trace._window(events)
    plane = sorted(events["devices"])[0]
    busy = trace.union([s, e] for _, s, e in trace._clipped(events["devices"][plane], w0, w1))
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    spans = [(n, s, s + d) for n, s, d in events["host"] if n != trace.WINDOW_SPAN]
    spans += [(n, s, s + d) for n, s, d, _ in events["spans"]]
    out = defaultdict(int)
    for g0, g1 in zip(edges[::2], edges[1::2]):
        over = [sp for sp in spans if sp[1] < g1 and sp[2] > g0]
        cuts = sorted({g0, g1} | {t for _, s, e in over for t in (s, e) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            inner = min((sp for sp in over if sp[1] <= a and sp[2] >= b),
                        key=lambda sp: sp[2] - sp[1], default=None)
            out[inner[0] if inner else "(no bench span)"] += b - a
    return dict(out)


def reduce(events: dict) -> dict:
    """:func:`bench.trace.reduce` of the benchmark's spans and the device
    ops, with the program's scopes, spans and span arguments in the
    window, and ``idle_gaps`` split by :func:`idle_by_span`."""
    out = trace.reduce(events)
    w0, w1 = trace._window(events)
    planes = sorted(events["devices"])
    scope_ns = defaultdict(int)
    for p in planes:
        for (name, s, d), scope in zip(events["devices"][p], events["scopes"][p]):
            lo, hi = max(s, w0), min(s + d, w1)
            if scope is not None and hi > lo:
                scope_ns[scope] += hi - lo
    span_ns, args = defaultdict(int), defaultdict(lambda: defaultdict(int))
    for name, s, d, a in events["spans"]:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo:
            span_ns[name] += hi - lo
        if w0 <= s < w1:
            for k, v in a.items():
                if isinstance(v, (int, float)) and k != "step":
                    args[name][k] += v
    idle = idle_by_span(events)
    out.update(
        scope_s={k: v * 1e-9 / len(planes) for k, v in scope_ns.items()},
        span_s={k: v * 1e-9 for k, v in span_ns.items()},
        span_args={k: dict(v) for k, v in args.items()},
        idle_gaps=[[k, v * 1e-9] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])],
    )
    return out


def excerpt(events: dict, span: str, count: int) -> dict:
    """:func:`bench.trace.excerpt` that keeps the program's scopes and the
    spans that overlap the excerpt's window (``bench/testdata``)."""
    out = trace.excerpt(events, span, count)
    w0, w1 = out["host"][0][1], out["host"][0][1] + out["host"][0][2]
    out["scopes"] = {
        p: [sc for (n, s, d), sc in zip(ops, events["scopes"][p]) if s < w1 and s + d > w0]
        for p, ops in events["devices"].items()}
    out["spans"] = [sp for sp in events["spans"] if sp[1] < w1 and sp[1] + sp[2] > w0]
    return out


def of(ctx: dict) -> dict | None:
    """The program's reduced instrumentation for a metric reader's
    ``ctx``: ``None`` without a trace; else :func:`reduce` of the run's
    ``.xplane.pb`` (under ``bench/.traces``), kept in ``ctx`` so the
    trace is read once per run."""
    if ctx.get("trace") is None:
        return None
    if "program" not in ctx:
        from bench.harness import TRACE_DIR

        ctx["program"] = reduce(load(trace.newest_xplane(TRACE_DIR)))
    return ctx["program"]


def per_product_ms(ctx: dict, kind: str, name: str) -> float | None:
    """Milliseconds per completed product of device time under scope
    ``name`` (``kind="scope_s"``) or host time in span ``name``
    (``kind="span_s"``); ``None`` where the trace holds none."""
    red = of(ctx)
    if red is None or not ctx["completed"] or red[kind].get(name, 0) <= 0:
        return None
    return red[kind][name] * 1e3 / ctx["completed"]


def waits(events: dict) -> list:
    """The stall question, one row per ``spgemm.wait`` span that ends in
    the window (first device plane): ``[step, end_ms, wait_ms, idle_ms,
    lag_ms, turn_ms, busy_ms]``, being the wait's end from the window's
    start, its length, the device-idle time inside it, the time from the
    end of the device's last ``spgemm.assemble`` op before it to its end
    (``None`` without one), the time since the previous wait ended (the
    product's turn) and the device-busy time in that turn. A long wait
    with a long lag is the host waking late; with a short lag, the device
    finishing late."""
    w0, w1 = trace._window(events)
    plane = sorted(events["devices"])[0]
    ops = events["devices"][plane]
    busy = trace.union([s, e] for _, s, e in trace._clipped(ops, w0, w1))
    done = sorted(s + d for (_, s, d), scope in zip(ops, events["scopes"][plane])
                  if scope == "spgemm.assemble")

    def busy_in(a, b):
        return sum(max(0, min(e, b) - max(s, a)) for s, e in busy)

    rows, last = [], w0
    for name, s, d, a in events["spans"]:
        end = s + d
        if name != "spgemm.wait" or not w0 < end <= w1:
            continue
        s = max(s, w0)
        i = bisect.bisect_right(done, end) - 1
        rows.append([a.get("step"), (end - w0) * 1e-6, (end - s) * 1e-6,
                     (end - s - busy_in(s, end)) * 1e-6,
                     (end - done[i]) * 1e-6 if i >= 0 else None,
                     (end - last) * 1e-6, busy_in(last, end) * 1e-6])
        last = end
    return rows


def _listing(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            tot, cnt, stats = defaultdict(int), defaultdict(int), {}
            for e in line.events:
                tot[e.name] += int(e.duration_ns)
                cnt[e.name] += 1
                if e.name not in stats:
                    stats[e.name] = list(e.stats)
            print(f"  line {line.name!r}: {sum(cnt.values())} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns * 1e-6:12.3f} ms  x{cnt[name]:<6d} {name[:160]}")
                for k, v in stats[name]:
                    print(f"        {k} = {str(v)[:200]}")
    for name, text in hlo_modules(path).items():
        scoped = {k: v for k, v in op_scopes(text).items() if v}
        print(f"module {name}: {len(scoped)} scoped instructions {sorted(set(scoped.values()))}")


def _summary(path: str) -> None:
    """The trace by hand (every plane and line, the busiest events with
    their stats, each module's scopes), then its :func:`reduce` and
    :func:`waits` (a ``--trace 1`` run's trace)."""
    _listing(path)
    report(load(path))


def report(events: dict) -> None:
    """Print :func:`reduce` of a loaded trace, with the count of each
    program span, and :func:`waits`."""
    red = reduce(events)
    w0, w1 = trace._window(events)
    count = defaultdict(int)
    for name, s, _, _ in events["spans"]:
        count[name] += w0 <= s < w1
    print(f"window {red['window_s'] * 1e3:.3f} ms, device busy {red['busy_s'] * 1e3:.3f} ms")
    print("device ms by scope:")
    for name, sec in sorted(red["scope_s"].items()):
        print(f"  {sec * 1e3:12.3f}  {name}")
    print("host ms by program span (count: spans that start in the window):")
    for name, sec in sorted(red["span_s"].items()):
        print(f"  {sec * 1e3:12.3f}  x{count[name]:<4d} {name}")
    print("span arguments summed over the window:")
    for name, args in sorted(red["span_args"].items()):
        print(f"  {name}: {args}")
    print("device-idle ms by the innermost span the host was in:")
    for name, sec in red["idle_gaps"]:
        print(f"  {sec * 1e3:12.3f}  {name}")
    print("waits (ms): step, end, wait, device idle in wait, lag after the last"
          " assembly, turn, device busy in turn")
    for step, *ms in waits(events):
        print(f"  {step!s:>6}" + "".join(f" {x:10.3f}" if x is not None else "       n/a"
                                         for x in ms))


if __name__ == "__main__":
    _summary(sys.argv[1])
