"""One run of one benchmark cell: set-up, warm-up, window, check, metrics.

Everything cell-specific is found by name (see ``BENCHMARK.json``):

* ``bench/configs/<config>.json`` — the deployment: matrix and its sizes,
  operation, plan settings, and the limit of the value check;
* ``bench/matrices/<matrix>.py`` — ``pattern(cfg)``, the matrix's
  coordinates from the configuration's sizes;
* ``bench/traffic/<mix>.json`` — the mix's parameters, among them
  ``path``, the entry point that drives it;
* ``bench/paths/<path>.py`` — ``warm(plan, values, traffic)``, which
  returns its last result, and
  ``run(plan, ring, seconds, traffic, sink, span) -> dict``;
* ``bench/metrics/<metric>.py`` — ``read(ctx)`` returning the metric's
  value, or ``None`` where the run has nothing to read.

So a later change adds a configuration, a mix, an entry point or a metric
as a new file, and edits none.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time

import numpy as np
from repro.sparse.formats import COO  # the system under test
from repro.spgemm import PlanCache, spgemm_plan

from bench import load_module, patterns, trace, traffic
from bench.reference import Reference

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PLAN_DIR = os.path.join(BENCH, ".plans")
TRACE_DIR = os.path.join(BENCH, ".traces")

__all__ = ["Cell", "load_module", "resolve", "run_cell", "emit"]


def _json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str, spec: dict, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        self.name, self.chips, self.root = name, int(w["chips"]), root
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        self.config = _json(os.path.join(root, conf["file"]))
        self.traffic = _json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
        self.path = load_module("paths", self.traffic["path"], root)
        self.end_to_end = [m["name"] for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m["name"] for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]


def resolve(name: str, root: str = ROOT) -> Cell:
    return Cell(name, _json(os.path.join(root, "BENCHMARK.json")), root)


class Sampler:
    """Reservoir of ``k`` results drawn from the seed: every result of the
    window has the same chance to be compared.

    Each result's values are copied into one of ``k + 1`` buffers of the
    sampler's own, as a caller that reads C would, and the result itself is
    let go. So every product costs the caller one copy, whichever results
    the seed keeps, and the program's host buffers are freed and reused
    alike in every run: were the kept results held instead, the seed would
    decide which products allocate fresh host memory, and the time per
    product would follow the seed. :meth:`prime` allocates the buffers
    before the window."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng((int(seed), 1))
        self.kept = []  # (index, ring slot, (indptr, indices, values))
        self._free = []  # buffers that hold no kept result

    def prime(self, c) -> None:
        """Allocate and touch the ``k + 1`` buffers, shaped as the values of
        ``c`` (a warm-up result), so the window allocates none of them."""
        data = np.asarray(c.data)
        self._free = [data.copy() for _ in range(self.k + 1)]

    def __call__(self, index: int, slot: int, c) -> None:
        data = np.asarray(c.data)
        buf = self._free.pop() if self._free else None
        if buf is None or buf.shape != data.shape or buf.dtype != data.dtype:
            buf = np.empty_like(data)
        np.copyto(buf, data)
        entry = (index, slot, (c.indptr, c.indices, buf))
        if len(self.kept) < self.k:
            self.kept.append(entry)
            return
        j = int(self.rng.integers(0, index + 1))
        if j < self.k:
            self._free.append(self.kept[j][2][2])
            self.kept[j] = entry
        else:
            self._free.append(buf)


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and event in self.EVENTS:
            self.count += 1


def make_plan(cfg: dict, ops: patterns.Operands, values):
    """The cell's plan through the public entry point, from the plan store
    under ``bench/.plans`` (built and saved there on a cold checkout)."""
    if cfg["dtype"] != "float32":
        raise ValueError(f"the traffic makes float32 values, not {cfg['dtype']}")
    a = COO(ops.a.row, ops.a.col, values[0], ops.a.shape)
    b = COO(ops.b.row, ops.b.col, values[1], ops.b.shape)
    plan = spgemm_plan(
        a, b, tile=int(cfg["tile"]), group=int(cfg["group"]),
        backend=cfg["backend"], output=cfg["output"],
        cache=PlanCache(disk_dir=cfg.get("plan_dir", PLAN_DIR)))
    # execute() takes values in the plan's canonical order: it must be ours.
    for mine, its in ((ops.a, plan.a_pattern), (ops.b, plan.b_pattern)):
        if not (np.array_equal(mine.row, its.row) and np.array_equal(mine.col, its.col)):
            raise AssertionError("the plan reordered the operand pattern")
    return plan


def _device_peak_bytes(device):
    """Peak device memory: ``peak_bytes_in_use``, the arrays, plus
    ``peak_bytes_reserved``, where the TPU runtime keeps the temporaries of
    compiled programs and which ``peak_bytes_in_use`` leaves out (``None``
    where the backend keeps no such count, as the CPU's does not)."""
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return None
    return int(stats["peak_bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0))


def check(ref: Reference, kept, ring, memos=None) -> dict:
    """Worst readings over the sampled results; ``memos`` keeps one
    reference per value set (by ring slot)."""
    worst = {"pattern_mismatch": 0, "value_err": 0.0}
    memos = {} if memos is None else memos
    for _, slot, (indptr, indices, data) in sorted(kept, key=lambda k: k[1]):
        r = ref.readings(indptr, indices, data, ring[slot][0], memos.setdefault(slot, {}))
        for k in worst:
            worst[k] = max(worst[k], r[k])
    return worst


def limits(cfg: dict) -> dict:
    return {"failed": 0, "pattern_mismatch": 0,
            "value_err": float(cfg["check"]["value_err_limit"])}


def verdict(readings: dict, lim: dict, compared: int) -> bool:
    return compared > 0 and all(readings[k] <= lim[k] for k in lim)


def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
             t0: float, overrides: dict | None = None, plan_hook=None) -> dict:
    """One run; returns the result line as a dict (``checks`` last).

    ``overrides`` replaces configuration keys (tests run on a small
    ``grid`` with ``backend="pallas_interpret"``); ``plan_hook(plan)`` may
    wrap the planned entry points (the fault tests break them there)."""
    import jax

    cfg = dict(cell.config, **(overrides or {}))
    tr = cell.traffic
    marks = [("start", t0)]
    device = jax.devices()[0]
    marks.append(("devices", time.perf_counter()))
    ops = patterns.operands(cfg, cell.root)
    ring, warm = traffic.value_ring(seed, int(tr["ring"]), ops.a.nnz, ops.b_from_a)
    marks.append(("inputs", time.perf_counter()))
    plan = make_plan(cfg, ops, warm)
    marks.append(("plan", time.perf_counter()))
    plan_s = marks[-1][1] - marks[-2][1]
    if plan_hook is not None:
        plan_hook(plan)
    last = cell.path.warm(plan, warm, tr)
    sampler = Sampler(int(tr["check_samples"]), seed)
    sampler.prime(last)
    del last
    marks.append(("warm", time.perf_counter()))
    print("setup: " + " ".join(f"{name}={t - marks[i][1]:.3f}s"
                               for i, (name, t) in enumerate(marks[1:])),
          file=sys.stderr, flush=True)
    counter = CompileCounter()
    span = contextlib.nullcontext
    if trace_on:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        span = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t0

    counter.active = True
    if trace_on:
        trace.start(TRACE_DIR)
    try:
        with span(trace.WINDOW_SPAN):
            res = cell.path.run(plan, ring, seconds, tr, sampler, span)
    finally:
        if trace_on:
            trace.stop()
        counter.active = False
    peak = _device_peak_bytes(device)
    for err in res.get("errors", []):
        print(err, file=sys.stderr, flush=True)
    plan.release()
    del plan

    ref = Reference(ops)
    readings = check(ref, sampler.kept, ring)
    readings["failed"] = int(res["attempted"] - res["completed"])
    lim = limits(cfg)
    correct = verdict(readings, lim, len(sampler.kept))

    ctx = {
        "cfg": cfg, "traffic": tr, "ops": ops, "nnz_c": ref.nnz if ref.indptr is not None else None,
        "setup_s": setup_s, "plan_s": plan_s, "memory_peak_bytes": peak,
        "window_s": res["window_s"], "latencies_s": res["latencies_s"],
        "completed": res["completed"], "device_kind": device.device_kind,
        "trace": None,
    }
    out_device = {"platform": device.platform, "kind": device.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(res["attempted"]),
              "failed": readings["failed"]}
    if trace_on:
        red = trace.reduce(trace.load(trace.newest_xplane(TRACE_DIR)))
        ctx["trace"] = red
        out_device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        names = cell.per_layer
    else:
        names = cell.end_to_end
    metrics = {}
    for name in names:
        mod = load_module("metrics", name, cell.root)
        value = mod.read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": mod.UNIT}
    result["metrics"] = metrics
    result["device"] = out_device
    if trace_on:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["window_compiles"] = counter.count
    result["checks"] = {k: {"value": readings[k], "limit": lim[k]} for k in lim}
    result["checks"]["compared"] = {"value": len(sampler.kept), "limit": 1}
    return result


def _num(x):
    return "inf" if isinstance(x, float) and math.isinf(x) else x


def emit(result: dict, out=None, err=None) -> None:
    """The checks as the last lines on stderr, the result as the last line
    on stdout (an infinite reading is written as the string ``"inf"``)."""
    out, err = out or sys.stdout, err or sys.stderr
    for name, c in result["checks"].items():
        rel = ">=" if name == "compared" else "<="
        print(f"check {name} {_num(c['value'])} (limit {rel} {c['limit']})",
              file=err, flush=True)
    line = dict(result)
    line["checks"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                      for k, v in result["checks"].items()}
    print(json.dumps(line), file=out, flush=True)
