"""Smoke run of the SpGEMM main path on a TPU, at a published Table 4 size.

    python3 chip_smoke.py              # one chip: every serving path
    python3 chip_smoke.py --chips 4    # 4-shard plan vs the single-device plan

The workload is C = A @ A^T for the 2cubes_sphere pattern at its published
size (``suite_matrix("2cubes_sphere", scale=1.0, seed=0)``: 101,000 x
101,000 at Table 4's density), tile 128, group 4, element-exact
(``output="compact"``)
output, with the compiled Pallas kernel (``backend="pallas"``). On one chip
it runs, in this one process and through the public entry points:

* ``plan``     — ``spgemm_plan``;
* ``execute``  — ``plan.execute()``, checked against scipy's ``A @ A^T``;
* ``fresh``    — three ``execute(a_vals, b_vals)`` with seeded fresh values,
  each checked against scipy;
* ``batch``    — ``execute_batch`` of 4 value sets, bitwise equal to
  single executes;
* ``stream``   — ``execute_stream`` at depth 2 over 8 steps, bitwise equal
  to sequential executes;
* ``gateway``  — an ``SpGEMMGateway`` serving 8 submits from 2 threads,
  every ticket ``Outcome.OK`` and bitwise equal to ``execute``.

The scipy check compares sparse matrices (never densified): the compact
pattern must equal the structural product pattern exactly, and values must
satisfy ``max|C - R| <= 1e-4 * max|R|`` (f32 on both sides).

``--chips 4`` runs only the sharded path: a 4-shard plan on
``make_shard_mesh(4)`` through ``execute``, ``execute_batch(4)`` and
``execute_stream(depth 2)``, each compared bitwise with the single-device
plan on device 0, and checks that every shard's constants and outputs sit
on their own chip.

Each phase prints one line with its wall seconds (set-up information: it
includes compilation, and is not a speed metric), the plan's host bytes and
the device's ``peak_bytes_in_use`` where the backend reports it. The last
line is the JSON verdict. Without a TPU the script exits non-zero and prints
no verdict. ``run_smoke`` / ``run_sharded`` are importable so the phases
can be driven at a small scale in interpret mode on CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro.data.pipeline import SpGEMMValueStream  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_shard_mesh  # noqa: E402
from repro.sparse.formats import COO  # noqa: E402
from repro.sparse.random import suite_matrix  # noqa: E402
from repro.spgemm import (  # noqa: E402
    Outcome,
    PlanCache,
    SpGEMMGateway,
    resolve_backend,
    spgemm_plan,
)

MATRIX = "2cubes_sphere"
TILE = 128
GROUP = 4
REL_TOL = 1e-4  # f32 bound: max|C - R| <= REL_TOL * max|R|
BATCH = 4
STREAM_STEPS = 8
STREAM_DEPTH = 2
GATEWAY_THREADS = 2
GATEWAY_SUBMITS = 8
# A gateway micro-batch runs as one bsz-1 chunk per request at this size,
# and a pipeline dispatches every chunk of its depth x max_batch requests
# at once, each with ~1.7 GB of staged blocks and panels: on a v5e,
# max_batch 4 peaked at 14.3 GB of the chip's 16 and max_batch 2 at 11.0.
GATEWAY_MAX_BATCH = 2
TOKEN = f"{MATRIX}/AAt"


def _phase_line(name: str, t0: float, plan=None, **extra) -> None:
    """One set-up line per phase: wall seconds, host bytes, device peak."""
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    fields = [f"wall_s={time.perf_counter() - t0:.3f}"]
    if plan is not None:
        fields.append(f"plan_host_bytes={plan.host_nbytes()}")
    fields += [f"process_max_rss_bytes={rss}", f"peak_bytes_in_use={peak}"]
    fields += [f"{k}={v}" for k, v in extra.items()]
    print(f"phase {name}: " + " ".join(fields), flush=True)


def operands(scale: float, seed: int = 0):
    """A (canonical COO) and A^T for the smoke matrix."""
    a = suite_matrix(MATRIX, scale=scale, seed=seed).to_coo().sum_duplicates()
    return a, COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))


class Reference:
    """scipy's C = A @ B on the plan's patterns, compared sparse-to-sparse."""

    def __init__(self, plan):
        self._a, self._b = plan.a_pattern, plan.b_pattern
        ones = self._scipy_product(
            np.ones(self._a.nnz, np.float32), np.ones(self._b.nnz, np.float32)
        )
        ones.sort_indices()
        self.indptr, self.indices = ones.indptr, ones.indices

    def _scipy_product(self, a_vals, b_vals):
        a, b = self._a, self._b
        sa = sp.csr_matrix((a_vals, (a.row, a.col)), shape=a.shape)
        sb = sp.csr_matrix((b_vals, (b.row, b.col)), shape=b.shape)
        return (sa @ sb).tocsr()

    def check(self, c, a_vals, b_vals) -> float:
        """Exact pattern + f32 value bound; returns max|C - R| / max|R|."""
        if not (np.array_equal(c.indptr, self.indptr)
                and np.array_equal(c.indices, self.indices)):
            raise AssertionError("C's compact pattern differs from scipy's")
        if not np.isfinite(c.data).all():
            raise AssertionError("C has non-finite values")
        r = self._scipy_product(a_vals, b_vals)
        scale = float(np.abs(r.data).max()) if r.nnz else 0.0
        d = c.to_scipy() - r
        err = float(np.abs(d.data).max()) if d.nnz else 0.0
        rel = err / scale if scale else err
        if rel > REL_TOL:
            raise AssertionError(
                f"max|C - R| = {err:.3e} is {rel:.3e} of max|R| = {scale:.3e}"
                f" (bound {REL_TOL:g})"
            )
        return rel


def _same(x, y) -> bool:
    return (np.array_equal(x.indptr, y.indptr)
            and np.array_equal(x.indices, y.indices)
            and np.array_equal(x.data, y.data))


def _assert_bitwise(label: str, got, want) -> None:
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if not _same(g, w)]
    if len(got) != len(want) or bad:
        raise AssertionError(
            f"{label}: {len(bad)} of {len(want)} results differ bitwise "
            f"(first at {bad[:1]}, got {len(got)} results)"
        )


def _check_kernel_path(plan, backend: str) -> None:
    if resolve_backend(backend) != backend or plan.backend != backend:
        raise AssertionError(f"plan backend {plan.backend!r}, wanted {backend!r}")
    if plan._executor is None:
        raise AssertionError("plan has no executor (empty product)")
    if plan._executor._interpret != (backend == "pallas_interpret"):
        raise AssertionError(
            f"executor interpret={plan._executor._interpret} on {backend!r}")
    if backend == "pallas" and jax.devices()[0].platform != "tpu":
        raise AssertionError("backend='pallas' without a TPU")


def run_smoke(scale: float = 1.0, backend: str = "pallas", seed: int = 0) -> dict:
    """Every one-chip phase; raises on the first failure."""
    t0 = time.perf_counter()
    a, b = operands(scale, seed)
    plan = spgemm_plan(a, b, tile=TILE, group=GROUP, backend=backend,
                       cache=PlanCache(), output="compact")
    _check_kernel_path(plan, backend)
    rep = plan.report
    _phase_line("plan", t0, plan, shape=rep.shape, nnz_a=rep.nnz_a,
                nnzb_a=rep.nnzb_a, nnzb_b=rep.nnzb_b,
                triples=rep.num_triples, panels=rep.n_panels,
                nnz_c=plan.compact.nnz, backend=plan.backend,
                interpret=plan._executor._interpret)

    t0 = time.perf_counter()
    ref = Reference(plan)
    c = plan.execute()
    rel = ref.check(c, plan.a_pattern.val, plan.b_pattern.val)
    _phase_line("execute", t0, plan, rel_err=f"{rel:.3e}")

    stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=seed + 1)
    single = {}  # step -> CSR of plan.execute(*stream.values_at(step))

    def execute_step(s):
        if s not in single:
            single[s] = plan.execute(*stream.values_at(s))
        return single[s]

    t0 = time.perf_counter()
    rels = []
    for s in range(3):
        rels.append(ref.check(execute_step(s), *stream.values_at(s)))
    _phase_line("fresh", t0, plan, steps=3,
                rel_err_max=f"{max(rels):.3e}")

    t0 = time.perf_counter()
    av, bv = stream.values_batch_at(0, batch=BATCH)
    batched = plan.execute_batch(av, bv)
    _assert_bitwise("execute_batch", batched,
                    [execute_step(s) for s in range(BATCH)])
    _phase_line("batch", t0, plan, batch=BATCH,
                batch_chunk=plan._executor.batch_chunk(), bitwise=True)

    t0 = time.perf_counter()
    streamed = list(plan.execute_stream(
        stream.value_iter(steps=STREAM_STEPS), depth=STREAM_DEPTH))
    _assert_bitwise("execute_stream", streamed,
                    [execute_step(s) for s in range(STREAM_STEPS)])
    _phase_line("stream", t0, plan, steps=STREAM_STEPS, depth=STREAM_DEPTH,
                bitwise=True)

    t0 = time.perf_counter()
    tickets = {}
    per_thread = GATEWAY_SUBMITS // GATEWAY_THREADS
    with SpGEMMGateway(max_pipelines=1, depth=STREAM_DEPTH,
                       max_batch=GATEWAY_MAX_BATCH) as gw:
        gw.register_plan(TOKEN, plan)

        def client(k):
            for j in range(per_thread):
                s = k * per_thread + j
                tickets[s] = gw.submit(TOKEN, *stream.values_at(s))

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(GATEWAY_THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        results = {s: t.wait(timeout=900) for s, t in tickets.items()}
        fill = gw.stats()["patterns"][TOKEN]["batch_fill"]
    failed = {s: (r.outcome.value, r.error) for s, r in results.items()
              if r.outcome is not Outcome.OK}
    if len(results) != GATEWAY_SUBMITS or failed:
        raise AssertionError(f"gateway: {len(results)} tickets, not OK: {failed}")
    order = sorted(results)
    _assert_bitwise("gateway", [results[s].value for s in order],
                    [execute_step(s) for s in order])
    _phase_line("gateway", t0, plan, submits=GATEWAY_SUBMITS,
                threads=GATEWAY_THREADS, batch_fill=f"{fill:.2f}",
                bitwise=True)
    return {"report": rep.as_dict(), "nnz_c": plan.compact.nnz}


def _shard_devices(arr) -> list:
    """Device id of each leading-axis shard of ``arr``, in shard order."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    return [int(s.device.id) for s in shards]


def run_sharded(n_shards: int, scale: float = 1.0, backend: str = "pallas",
                seed: int = 0) -> dict:
    """The 4-chip phase: sharded plan vs the single-device plan, bitwise."""
    t0 = time.perf_counter()
    a, b = operands(scale, seed)
    kw = dict(tile=TILE, group=GROUP, backend=backend, output="compact")
    single = spgemm_plan(a, b, cache=PlanCache(), **kw)
    mesh = make_shard_mesh(n_shards)
    plan = spgemm_plan(a, b, cache=PlanCache(), mesh=mesh, **kw)
    _check_kernel_path(single, backend)
    _check_kernel_path(plan, backend)
    _phase_line("sharded_plan", t0, plan, n_shards=n_shards,
                shard_triples=plan.shard_stats()["triples"],
                imbalance=f"{plan.shard_stats()['imbalance']:.3f}")

    # Placement: every stacked per-shard constant and every per-shard
    # output segment lives on its own mesh device.
    t0 = time.perf_counter()
    stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=seed + 1)
    ex = plan._executor
    want = [int(d.id) for d in mesh.devices.ravel()]
    constants = {"sched": ex._sched[0][0], "gather": ex._gather,
                 "a_scatter": ex._a_scatter}
    staged = ex.pipe_stage(*stream.values_at(0), mode="values")
    packed = ex.pipe_kernel(staged, mode="single")
    placed = {k: _shard_devices(v) for k, v in constants.items()}
    placed["output"] = _shard_devices(packed)
    print(f"shard devices: mesh={want} " + " ".join(
        f"{k}={v}" for k, v in placed.items()), flush=True)
    wrong = {k: v for k, v in placed.items() if v != want}
    if len(set(want)) != n_shards or wrong:
        raise AssertionError(f"shards not one per device: {wrong}")
    c0 = single.execute(*stream.values_at(0))
    _assert_bitwise("sharded pipe protocol",
                    [plan._wrap_packed(ex.pipe_collect(packed, mode="single"))],
                    [c0])
    _phase_line("sharded_placement", t0, plan, bitwise=True)

    single_out = {0: c0}

    def single_step(s):
        if s not in single_out:
            single_out[s] = single.execute(*stream.values_at(s))
        return single_out[s]

    t0 = time.perf_counter()
    _assert_bitwise("sharded execute",
                    [plan.execute(*stream.values_at(s)) for s in range(2)],
                    [single_step(s) for s in range(2)])
    _phase_line("sharded_execute", t0, plan, steps=2, bitwise=True)

    t0 = time.perf_counter()
    av, bv = stream.values_batch_at(0, batch=BATCH)
    _assert_bitwise("sharded execute_batch", plan.execute_batch(av, bv),
                    [single_step(s) for s in range(BATCH)])
    _phase_line("sharded_batch", t0, plan, batch=BATCH,
                batch_chunk=ex.batch_chunk(), bitwise=True)

    t0 = time.perf_counter()
    steps = BATCH
    _assert_bitwise(
        "sharded execute_stream",
        list(plan.execute_stream(stream.value_iter(steps=steps),
                                 depth=STREAM_DEPTH)),
        [single_step(s) for s in range(steps)])
    _phase_line("sharded_stream", t0, plan, steps=steps, depth=STREAM_DEPTH,
                bitwise=True)
    return {"shard_devices": placed, "mesh": want}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-shard phase and its comparison")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but only "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.chips > 1:
        run_sharded(args.chips)
    else:
        run_smoke()
    print(f"jax {jax.__version__} device_kind {dev.device_kind!r} "
          f"devices {len(jax.devices())}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
