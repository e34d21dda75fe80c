"""Plan/execute SpGEMM API (the paper's "pre-process once" claim as code).

Typical use::

    from repro.spgemm import spgemm_plan
    from repro.launch.mesh import make_shard_mesh

    plan = spgemm_plan(a, b, tile=64, group=4, backend="auto")
    c0 = plan.execute()                     # staged values
    c1 = plan.execute(a_vals2, b_vals2)     # fresh values, zero symbolic work
    cs = plan.execute_batch(a_batch, b_batch)  # [batch, nnz] values, one
                                               # vmapped device call

    with plan.pipeline(depth=2) as pipe:    # async serving (submit/collect)
        for c in pipe.stream(values.value_iter(steps=1000)):
            consume(c)

    sharded = spgemm_plan(a, b, tile=64, group=4,
                          mesh=make_shard_mesh(4))  # ShardedSpGEMMPlan
    c2 = sharded.execute(a_vals2, b_vals2)  # same semantics, 4 devices

The numeric phase is device-resident (``repro.spgemm.executor``): value
rebind, the scheduled kernel, and output assembly run against the symbolic
phase's precomputed CSR structure — fused under one ``jax.jit`` for
synchronous executes, and split in two for the async path (H2D +
rebind -> kernel and assembly -> collect) behind one interface.

**Kernel dispatch** is decided once per plan by its resolved backend and
honored on *every* numeric path — single execute, ``execute_batch``, the
pipeline's jits, and the per-shard programs inside ``shard_map``::

    backend            x  path          -> scheduled kernel
    -------------------------------------------------------------------
    pallas             execute/pipeline    spgemm_scheduled_impl
                                           (scalar-prefetch Pallas grid)
    pallas             execute_batch /     spgemm_scheduled_batch_impl
                       batched pipeline    (batch-folded grid (bsz, t))
    pallas             sharded (any)       same two, one Pallas program
                                           per shard inside shard_map
    pallas_interpret   all of the above    identical grids, interpret=True
    jnp                all paths           ref.spgemm_scheduled_ref
                                           (segment scatter-add reference)
    auto                                   pallas on TPU, jnp elsewhere

The batch fold iterates the triple dimension innermost, so every element
runs its full schedule in single-grid order: batched, pipelined, and
sharded results are **bitwise-equal** to looped single executes on every
backend (tests/test_pallas_dispatch.py pins this, including a guard that
pallas plans never silently fall back to the jnp reference).

**Output formats & chaining**: a plan's output format is fixed at build
time by ``spgemm_plan(..., output=)``:

* ``output="block"`` (default, bitwise-unchanged): C is the structural
  *block* CSR — every element of every structurally nonzero ``bm x bn``
  block is stored, padding zeros included. Cheapest to assemble and the
  right shape for block-granular consumers.
* ``output="compact"``: C is the element-exact CSR of the structural
  product pattern — per-row counts, prefix-summed ``indptr``, and a
  compacted gather map (:func:`repro.core.schedule.build_compact_map`, a
  strict subset of the block assembly's gather) drop the block-padding
  zeros on **every** dispatch path (execute / batch / pipeline /
  sharded). Same kernels, same bits at every stored coordinate; only
  the output gather changes. Compact plans get their own cache keys
  (``+ ("compact",)``) and persist the compact map beside the block
  map (``casm.*`` arrays), so block artifacts stay byte-identical.

Because C's pattern is value-independent, one plan's structural output
(:meth:`SpGEMMPlan.output_pattern`) can seed the *next* plan without any
host round trip or COO conversion — the graph-workload chaining layer::

    p1 = spgemm_plan(a, b, tile=16, group=2, output="compact")
    chain = p1.then(c)                 # SpGEMMChain; or chain_plans([...])
    out = chain.execute()              # A @ B @ C, intermediates stay
                                       # device-resident (packed values
                                       # feed the next stage's fused jit)
    p2 = plan_from_structural_pattern( # the explicit form: skip COO
        p1.output_pattern(), c)        # conversion + canonicalizing sort

``execute_chain`` results are bitwise-equal to independent per-stage
executes with host round trips between them; chained plans carry their
own ``"chain"``-digest cache keys and persist/rehydrate like any other
plan (``CacheStats.chain_lookups`` counts the composition path). See
``examples/spgemm_chain.py`` (A²-based triangle counting) and
``benchmarks/bench_chain.py``.

**Batch chunking**: ``execute_batch`` fuses many value sets into one
device call only while a set's working bytes stay under a per-backend
budget, and sizes chunks to a per-backend cache target
(``executor.batch_chunk``). Both knobs resolve with precedence
``REPRO_SPGEMM_CHUNK_BYTES`` env var > ``chunk_bytes=`` constructor
argument (the tier a plan's applied ``TunedConfig`` feeds) > the
measured per-backend ``executor._CHUNK_POLICY`` row (calibrated with
``benchmarks.bench_chunk_knee`` /
:func:`repro.core.tuning.measure_chunk_knee`; re-run on new hosts).

**Autotuning** (``repro.spgemm.autotune``): per-pattern config search
over ``(tile, group)`` x ``chunk_bytes`` x pipeline depth, run once and
amortized like the symbolic phase itself. Stage 1 ranks the candidate
grid with the roofline model over each schedule's exact FLOP/traffic
counts (:func:`repro.core.perfmodel.spgemm_schedule_traffic` +
:func:`repro.core.perfmodel.roofline_seconds`) and keeps the top K plus
the requested default; stage 2 measures the survivors with short
interleaved min-of-N ``execute_batch`` probes on synthetic values (the
``measure_chunk_knee`` machinery), then probes pipeline depth on the
winner only. The result — a
:class:`~repro.spgemm.autotune.TunedConfig` with measured values/s for
winner and default, the model's rank of the winner, and the
model-vs-measured ranking agreement — is applied to the plan and
persisted beside the plan artifacts (a versioned ``PlanStore`` sidecar
record *and* inside ``persist_artifacts`` meta), so a warm-restarted
process rehydrates schedule **and** tuned config with **zero** probe
executions (``repro.spgemm.autotune.probe_run_count`` stays flat).
Numerics never change: chunk/depth are bitwise-invariant, and a tuned
(tile, group) plan is bitwise-equal to an untuned plan built directly
at that tile/group. Cookbook::

    from repro.spgemm import spgemm_plan
    from repro.spgemm.autotune import probe_run_count

    plan = spgemm_plan(a, b, tile=64, group=4, autotune=True)
    cfg = plan.tuned_config           # TunedConfig(tile, group,
                                      #   chunk_bytes, pipeline_depth, ...)
    cfg.speedup                       # measured winner/default ratio
    plan.report.config_source         # "tuned" | "persisted" |
                                      # "env-override" | "default"
    # warm restart, same REPRO_SPGEMM_PLAN_DIR: zero probes
    plan = spgemm_plan(a, b, tile=64, group=4, autotune=True)
    assert plan.report.config_source == "persisted"
    assert probe_run_count() == 0

The full exec-config precedence chain, highest first:

1. ``REPRO_SPGEMM_CHUNK_BYTES`` env var — the operator override, always
   wins (``report.config_source == "env-override"``);
2. explicit ``chunk_bytes=`` executor constructor argument / an applied
   ``TunedConfig`` (``plan.apply_tuned_config``, what ``autotune=True``
   and persisted-artifact rehydration do);
3. the measured per-backend ``executor._CHUNK_POLICY`` table row
   (``report.config_source == "default"``).

**Async serving** (``repro.spgemm.pipeline``): ``plan.pipeline(depth)``
returns an :class:`~repro.spgemm.pipeline.SpGEMMPipeline` —
``submit(a_vals, b_vals)`` dispatches a step and returns a ticket
immediately; ``collect(ticket)`` (or ``ticket.result()``) is the only
blocking call. With ``depth`` steps in flight, step s+1's value staging
(H2D + rebind, its own device program) overlaps step s's kernel — the
paper's double-buffered operand fetch at ``depth=2``, each in-flight step
owning its own staged packed A/B buffers on device (per shard on sharded
plans). ``plan.execute_async`` is the one-shot form,
``plan.execute_stream(value_iter, depth=)`` the ordered streaming form
(feed it :meth:`repro.data.pipeline.SpGEMMValueStream.value_iter`).
Pipelined results are **bitwise-equal** to sequential ``execute`` calls on
element, block, batched, and sharded plans. While tickets are in flight
the plan refuses buffer teardown — ``release_values``/``release`` and
explicit cache eviction raise, and LRU eviction skips the plan — so
staged device buffers can never be torn down under a running step.

**Sharded plans** (the mesh-aware path): passing ``mesh=`` partitions the
symbolic panel schedule across the devices of one mesh axis —

* *partitioning policy*: shard boundaries are block-row **group**
  boundaries chosen to balance **triple count** (the numeric work unit,
  not panel count) via :func:`repro.core.schedule.partition_spgemm_schedule`;
  every shard is a contiguous slice of the parent schedule, so shards may
  be ragged or empty and C stays a concatenation of contiguous row ranges;
* *data placement*: packed A blocks / A values are **row-sharded** (each
  shard's contiguous slot/value slice lives on its own device), packed B
  blocks / B values are **replicated** — the paper's shared B-buffer
  scheme lifted to the mesh — and C's packed values come back row-sharded,
  assembled on host with one concatenation along the precomputed indptr
  boundaries;
* *execution*: one ``jax.jit(shard_map(...))`` call per execute, each
  shard running its own padded triple schedule against its own
  :class:`~repro.core.schedule.AssemblyMap` slice with the backend's
  kernel (a per-shard Pallas program on pallas backends — see the
  dispatch matrix above — the scatter-add reference on jnp); the async
  path splits the same computation into per-stage ``shard_map`` programs.

Plans are cached in a **two-tier** cache keyed on ``(pattern hash, tile,
group, backend, mesh key)`` — the mesh key pins the shard axis, shard
count, and device ids, and is ``None`` on the unchanged single-device
path:

* the **memory tier** is a process-wide LRU of live plan objects (count +
  byte budgets, ``PlanCache.stats()`` observability). Serving callers can
  attach a ``pattern_token`` (``spgemm_plan(..., pattern_token="layer3")``)
  — a caller-chosen fast key that resolves warm lookups *without*
  ``to_coo`` canonicalization or the pattern digest (most of the warm
  path's host cost); the token is validated against the digest whenever
  both are present and echoed in ``report.pattern_token``;
* the **disk tier** (opt-in: ``PlanCache(disk_dir=...)``, or point
  ``REPRO_SPGEMM_PLAN_DIR`` at a directory for the process-default cache)
  persists the value-independent symbolic artifacts — triple schedule,
  scatter indices, assembly map, shard bounds — through
  ``repro.spgemm.persist.PlanStore``, so a **warm-restarted** process
  rehydrates its plans (``report.schedule_builds == 0``,
  ``report.load_hits >= 1``) with results bitwise-equal to a cold build.
  Files carry a format-version header, the full cache key, and a payload
  digest; anything stale or corrupt degrades to a silent fresh build.

**Multi-tenant serving gateway** (``repro.spgemm.gateway``): the front
end above per-plan pipelines for many tenants hammering many patterns
concurrently. :class:`~repro.spgemm.gateway.SpGEMMGateway` resolves each
registered pattern through the cache (``pattern_token`` fast key),
micro-batches same-pattern requests arriving within a bounded window
into single ``execute_batch``-semantics pipeline submissions (results
stay bitwise-equal to per-request ``plan.execute``), schedules fairly
across patterns by deficit round-robin over pending **value bytes** on a
bounded pool of live pipelines (pool eviction never tears down a
pipeline with in-flight tickets), and sheds overload as explicit typed
outcomes (:class:`~repro.spgemm.gateway.Outcome`: queue-full, in-flight
byte budget, plan-cache byte pressure, closed) instead of raising from
the executor. Per-pattern queue depth, batch-fill, p50/p99 latency,
throughput, and shed counts are recorded in a
:class:`~repro.runtime.heartbeat.MetricsRegistry` and snapshotted by
``gateway.stats()``::

    gw = SpGEMMGateway(max_pipelines=4, depth=2, max_batch=8,
                       max_inflight_bytes=64 << 20)
    gw.register("tenant0/layer3", a, b, tile=16, group=2)
    ticket = gw.submit("tenant0/layer3", a_vals, b_vals)
    res = ticket.wait()        # typed GatewayResult (never raises on shed)
    gw.close()                 # drains admitted work by default

**Validation & static analysis** (``repro.analysis``): every invariant
the numeric phase relies on — schedule well-formedness, write-only
dummy-pad-panel discipline, assembly coverage (each structural C nnz
gathered exactly once), write-write race freedom of the batch-folded and
stacked-shard Pallas grids, shard-partition exactness — can be checked
statically, on the host, without executing a single kernel::

    from repro.analysis import verify_plan

    report = verify_plan(plan)        # VerifyReport; report.ok / findings
    report.raise_if_failed()          # PlanVerificationError with detail

    plan = spgemm_plan(a, b, tile=16, group=2, validate="deep")

``validate="deep"`` runs the verifier on whatever this call returns —
fresh build, memory hit, or disk rehydrate. Rehydrates are verified
*inside* the loader, so a corrupted-but-digest-valid artifact (the one
corruption class the store's payload digest cannot catch: a consistent
rewrite that re-signs the digest) counts as a ``load_failure`` and falls
back to a clean symbolic rebuild instead of executing. The same checks
back the kernel lint (``repro.analysis.kernel_lint`` — the proof
obligation behind the batch grid's ``("parallel", "arbitrary")``
dimension semantics), the serving stack's lock-order lint
(``repro.analysis.locks``), and the CI gate
``python -m repro.analysis.check --paper-matrices --shards 8``.

``repro.kernels.ops.spgemm`` is a thin compatibility shim over this
package.
"""
from repro.spgemm.autotune import TunedConfig, autotune_plan, probe_run_count
from repro.spgemm.cache import (
    CacheStats,
    PlanCache,
    default_cache,
    pattern_digest,
)
from repro.spgemm.persist import PLAN_DIR_ENV, PlanStore
from repro.spgemm.executor import ShardedSpGEMMExecutor, SpGEMMExecutor
from repro.spgemm.gateway import (
    GatewayResult,
    GatewayShed,
    GatewayTicket,
    Outcome,
    SpGEMMGateway,
)
from repro.spgemm.pipeline import (
    PipelineFullError,
    SpGEMMPipeline,
    SpGEMMTicket,
)
from repro.spgemm.plan import (
    PlanReport,
    ShardedSpGEMMPlan,
    SpGEMMChain,
    SpGEMMPlan,
    StructuralPattern,
    chain_plans,
    execute_chain,
    plan_from_structural_pattern,
    resolve_backend,
    schedule_build_count,
    spgemm_plan,
)

__all__ = [
    "CacheStats",
    "GatewayResult",
    "GatewayShed",
    "GatewayTicket",
    "Outcome",
    "PLAN_DIR_ENV",
    "PipelineFullError",
    "PlanCache",
    "PlanReport",
    "PlanStore",
    "ShardedSpGEMMExecutor",
    "ShardedSpGEMMPlan",
    "SpGEMMChain",
    "SpGEMMExecutor",
    "SpGEMMGateway",
    "SpGEMMPipeline",
    "SpGEMMPlan",
    "SpGEMMTicket",
    "StructuralPattern",
    "TunedConfig",
    "autotune_plan",
    "chain_plans",
    "default_cache",
    "execute_chain",
    "pattern_digest",
    "plan_from_structural_pattern",
    "probe_run_count",
    "resolve_backend",
    "schedule_build_count",
    "spgemm_plan",
]
