"""Static-analysis CLI: verify paper-matrix plans without executing them.

    PYTHONPATH=src python -m repro.analysis.check --paper-matrices [--shards N]

For each paper matrix (``repro.configs.paper_matrices``) the driver
builds — in an isolated :class:`~repro.spgemm.cache.PlanCache` — an
element plan, a block plan, an optionally sharded plan, and a
disk-rehydrated plan, and runs :func:`repro.analysis.verify.verify_plan`
plus the kernel-spec lint on each. ``--lock-lint`` additionally runs a
scripted gateway/pipeline workload under the lock-order instrumentation
(:mod:`repro.analysis.locks`) and fails on acquisition-graph cycles.
``--store DIR`` (or ``REPRO_SPGEMM_PLAN_DIR``) audits the on-disk
:class:`~repro.spgemm.persist.PlanStore` — orphaned ``tokens.index.json``
aliases are reported and pruned.

Exit status is nonzero if any verification, lint, or audit fails, so CI
can gate on it directly (the ``spgemm-verify`` job).

``--shards N`` with more shards than visible CPU devices re-executes
itself with ``--xla_force_host_platform_device_count`` when jax has not
been imported yet — the same forced-host-device convention as the
sharded test jobs. On an accelerator host it never re-executes.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

__all__ = ["main"]


def _ensure_devices(n: int) -> None:
    """Force ``n`` visible host devices when the platform is the CPU.

    jax reads ``XLA_FLAGS`` at backend initialization (lazily, at the
    first device query), so setting the env var here normally suffices
    even though ``repro`` imports jax at module load. The flag sizes the
    CPU platform only: on an accelerator host the devices are the chips,
    and this process keeps them (a re-exec would have to reach chips this
    process already holds), so a shortfall is reported by the sharded
    check instead. If the CPU backend is somehow already initialized with
    fewer devices, re-exec once with the flag exported (the flag's
    presence in the inherited env stops a second re-exec)."""
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )
    import jax

    if jax.default_backend() != "cpu":
        return
    if len(jax.devices()) < n:
        os.execv(sys.executable,
                 [sys.executable, "-m", "repro.analysis.check",
                  *sys.argv[1:]])


def _operands(name: str, scale: float):
    from repro.sparse.formats import COO
    from repro.sparse.random import suite_matrix

    a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
    b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
    return a, b


def _verify_one(plan, label: str, failures: list) -> None:
    from repro.analysis.kernel_lint import lint_plan_kernel_specs
    from repro.analysis.verify import verify_plan

    rep = verify_plan(plan)
    lint = lint_plan_kernel_specs(plan)
    bad = [f for f in lint if f.severity == "error"]
    ok = rep.ok and not bad
    print(f"  {label:<28} "
          f"{'ok' if ok else 'FAILED':<7} "
          f"({len(rep.checks_run)} checks, {rep.elapsed_s * 1e3:6.1f} ms, "
          f"t={plan.report.num_triples}, nnz_c={plan.assembly.nnz})")
    for f in rep.findings + lint:
        print(f"    {f}")
    if not ok:
        failures.append(f"{label}: verification failed")


def _check_matrix(name: str, scale: float, shards: int, backend: str,
                  failures: list) -> None:
    import jax

    from repro.spgemm import PlanCache, spgemm_plan
    from repro.sparse.convert import bcsr_from_coo, bcsv_from_coo

    print(f"\n== {name} (scale={scale}) " + "=" * max(1, 40 - len(name)))
    a, b = _operands(name, scale)
    tile, group = 16, 2
    with tempfile.TemporaryDirectory() as tmp:
        cache = PlanCache(disk_dir=tmp)
        plan = spgemm_plan(a, b, tile=tile, group=group, backend=backend,
                           cache=cache, validate="deep")
        _verify_one(plan, "element", failures)
        a_bcsv, _ = bcsv_from_coo(a, (tile, tile), group)
        b_bcsr, _ = bcsr_from_coo(b, (tile, tile))
        bplan = spgemm_plan(a_bcsv, b_bcsr, backend=backend, cache=cache,
                            validate="deep")
        _verify_one(bplan, "block", failures)
        if shards > 1:
            from repro.launch.mesh import make_shard_mesh

            if len(jax.devices()) < shards:
                failures.append(
                    f"{name}: {shards} shards requested but only "
                    f"{len(jax.devices())} devices visible"
                )
            else:
                splan = spgemm_plan(
                    a, b, tile=tile, group=group, backend=backend,
                    cache=cache, mesh=make_shard_mesh(shards),
                    validate="deep",
                )
                _verify_one(splan, f"sharded x{shards}", failures)
        # Warm-restart path: a fresh cache over the same store directory
        # must rehydrate from disk (no symbolic rebuild) and still verify.
        cache2 = PlanCache(disk_dir=tmp)
        rplan = spgemm_plan(a, b, tile=tile, group=group, backend=backend,
                            cache=cache2, validate="deep")
        if rplan.report.load_hits < 1:
            failures.append(f"{name}: rehydrated plan did not load from disk")
        _verify_one(rplan, "rehydrated", failures)


def _lock_lint(failures: list) -> None:
    """Scripted serving workload under lock instrumentation.

    Multi-pattern by design: with a single registered pattern the
    dispatcher only ever interleaves one pipeline's locks with the
    gateway's, so the cross-pattern edges (dispatcher draining pattern
    p0 while the collector retires pattern p1, both touching the shared
    queue/stats locks) never enter the acquisition graph. Three patterns
    submitted concurrently from separate threads — at ``max_pipelines=2``
    so at least one pair *must* contend for a pipeline slot — exercise
    exactly those edges before ``mon.check()`` looks for cycles.
    """
    import threading

    import numpy as np

    from repro.analysis.locks import LockOrderError, instrument_spgemm_locks

    print("\n== lock-order lint " + "=" * 40)
    with instrument_spgemm_locks() as mon:
        # Import inside the instrumented scope is not needed (locks are
        # created at *object* construction) — build the stack fresh here.
        from repro.spgemm.gateway import SpGEMMGateway

        specs = [
            ("lint/p0", _operands("poisson3Da", 0.01)),
            ("lint/p1", _operands("2cubes_sphere", 0.002)),
            ("lint/p2", _operands("scircuit", 0.002)),
        ]
        gw = SpGEMMGateway(max_pipelines=2, depth=2, max_batch=4)
        plans = {
            name: gw.register(name, a, b, tile=16, group=2, backend="jnp")
            for name, (a, b) in specs
        }
        tickets: list = []
        tickets_lock = threading.Lock()

        def drive(name: str, seed: int) -> None:
            wa, wb = plans[name].value_shapes()
            rng = np.random.default_rng(seed)
            for _ in range(4):
                t = gw.submit(
                    name,
                    rng.standard_normal(wa).astype(np.float32),
                    rng.standard_normal(wb).astype(np.float32),
                )
                with tickets_lock:
                    tickets.append(t)

        threads = [
            threading.Thread(target=drive, args=(name, i))
            for i, (name, _) in enumerate(specs)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for t in tickets:
            t.wait(timeout=120)
        gw.close()
    edges = mon.edges()
    n_edges = sum(len(v) for v in edges.values())
    print(f"  {len(mon.sites())} lock sites, {n_edges} ordered edges")
    for src in sorted(edges):
        print(f"    {src} -> {', '.join(sorted(edges[src]))}")
    try:
        warnings = mon.check()
    except LockOrderError as e:
        failures.append(f"lock-order cycle: {e}")
        print(f"  FAILED: {e}")
        return
    for w in warnings:
        print(f"    {w}")
    print("  acyclic: ok")


def _audit_store(root: str, failures: list) -> None:
    from repro.spgemm.persist import PlanStore

    print(f"\n== store audit: {root} " + "=" * 20)
    store = PlanStore(root)
    report = store.audit()
    print(f"  {report['files']} artifact file(s), {report['aliases']} "
          f"alias(es), {len(report['orphaned'])} orphaned "
          f"(pruned={report['pruned']})")
    for tok in report["orphaned"]:
        print(f"    orphaned alias: {tok}")
    # Orphans are pruned, not fatal — a second audit must come back clean.
    if store.audit()["orphaned"]:
        failures.append("store audit: orphaned aliases survived pruning")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--paper-matrices", action="store_true",
                    help="verify plans for every paper matrix")
    ap.add_argument("--matrices", default=None,
                    help="comma-separated matrix subset (default: all)")
    ap.add_argument("--scale", type=float, default=0.01,
                    help="suite_matrix scale (default 0.01: CI-sized)")
    ap.add_argument("--shards", type=int, default=0,
                    help="additionally verify a sharded plan at N shards")
    ap.add_argument("--backend", default="jnp",
                    help="plan backend to build with (default jnp)")
    ap.add_argument("--lock-lint", action="store_true",
                    help="run the gateway/pipeline lock-order lint")
    ap.add_argument("--store", default=None,
                    help="audit this PlanStore directory (default: "
                         "$REPRO_SPGEMM_PLAN_DIR when set)")
    args = ap.parse_args(argv)
    _ensure_devices(args.shards)

    t0 = time.perf_counter()
    failures: list = []
    ran = False
    if args.paper_matrices or args.matrices:
        ran = True
        from repro.analysis.kernel_lint import lint_kernel_module
        from repro.configs.paper_matrices import SUITE

        print("== kernel module lint " + "=" * 38)
        mod_findings = lint_kernel_module()
        for f in mod_findings:
            print(f"  {f}")
            if f.severity == "error":
                failures.append(f"kernel lint: {f.message}")
        if not mod_findings:
            print("  ok (semantics + fp32 accumulation)")
        names = (args.matrices.split(",") if args.matrices
                 else list(SUITE))
        for name in names:
            _check_matrix(name.strip(), args.scale, args.shards,
                          args.backend, failures)
    if args.lock_lint:
        ran = True
        _lock_lint(failures)
    store_dir = args.store or os.environ.get("REPRO_SPGEMM_PLAN_DIR")
    if store_dir and os.path.isdir(store_dir):
        ran = True
        _audit_store(store_dir, failures)
    if not ran:
        ap.error("nothing to do: pass --paper-matrices, --matrices, "
                 "--lock-lint, and/or --store")
    dt = time.perf_counter() - t0
    if failures:
        print(f"\nFAILED ({len(failures)} problem(s), {dt:.1f}s):")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"\nall static checks passed ({dt:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
