"""Plan/execute SpGEMM: symbolic phase once, device-resident numeric phase.

FSpGEMM's host-side claim (Sec. 4.3) is that CSV pre-processing "only needs
to be performed once". This module is that claim as an API, in the
descriptor/setup-execute shape of cuSPARSE-style two-phase SpGEMM and the
symbolic/numeric split of Nagasaka et al. — with the numeric phase a pure
streaming pipeline, as on the paper's FPGA:

* :func:`spgemm_plan` runs every amortizable step once — sparse-native
  format conversion (COO -> BCSV/BCSR with value-scatter indices), the
  symbolic block-Gustavson phase (C structure + static triple schedule +
  the :class:`~repro.core.schedule.AssemblyMap` output-scatter structure),
  schedule padding, and device staging — and returns a :class:`SpGEMMPlan`.
* The numeric phase is the *functional core* of
  :class:`~repro.spgemm.executor.SpGEMMExecutor`: value rebind, the
  scheduled kernel, and output assembly fused under one ``jax.jit``;
  C's CSR pattern is precomputed, so assembly is a single static device
  gather — no host ``nonzero`` scan, no per-panel Python loop.
* :meth:`SpGEMMPlan.execute` is a thin stateful wrapper over that core: it
  keeps the lock / host-value staging / copy-on-stage semantics (no-arg
  ``execute()`` reuses staged values; plans are shared cache objects) and
  wraps the packed C values in the precomputed CSR structure.
* :meth:`SpGEMMPlan.execute_batch` vmaps the functional core over a leading
  value-batch axis — the serving workload, fed by the batch mode of
  :class:`repro.data.pipeline.SpGEMMValueStream`.
* Plans are cached process-wide (``repro.spgemm.cache``) keyed on
  ``(pattern hash, tile, group, backend)``, with optional byte-budget
  eviction — the serving path where one sparsity pattern meets millions of
  fresh value sets pays the symbolic phase exactly once.

Output convention: C's CSR pattern is *structural* (every element of every
structurally nonzero C block, trimmed to the true shape), so values that
compute to exact zero are stored explicitly — the pattern is
value-independent, which is what makes assembly jittable and batchable.

Host spans: the served numeric path opens ``jax.profiler.TraceAnnotation``
spans, so a profiler trace shows each product's host work on the same
clock as its device ops. ``spgemm.execute`` and ``spgemm.execute_batch``
(``spgemm.submit`` in the pipeline) hold ``spgemm.rebind`` (the host
scatter of one operand's values into its block array, element plans),
``spgemm.dispatch`` (H2D plus the jit enqueue) and ``spgemm.collect``,
whose children are ``spgemm.wait`` (the device finishing) and
``spgemm.d2h`` (the copy to host); the rest of ``spgemm.collect`` is the
CSR wrap. A sharded plan's ``execute`` and ``execute_batch`` call an
executor that blocks and brings C to the host itself: ``spgemm.run``
covers that call in place of ``spgemm.dispatch``, ``spgemm.wait`` and
``spgemm.d2h``. Every span carries ``step`` (the product's ``report.executes``,
or its pipeline index) and its counts as arguments; with no profiler
running a span records nothing and its arguments are never formatted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.core.schedule import (
    AssemblyMap,
    ScheduleShard,
    SpGEMMSchedule,
    assembly_from_arrays,
    assembly_to_arrays,
    build_assembly_map,
    build_compact_map,
    build_spgemm_schedule,
    partition_spgemm_schedule,
    schedule_from_arrays,
    schedule_to_arrays,
    shards_from_bounds,
    shards_to_bounds,
    structural_product_pattern,
)
from repro.sparse.convert import bcsr_from_coo, bcsv_from_coo, to_coo
from repro.sparse.formats import BCSR, BCSV, COO, CSR
from repro.spgemm.cache import PlanCache, default_cache, pattern_digest
from repro.spgemm.executor import (
    CHUNK_BYTES_ENV,
    ShardedSpGEMMExecutor,
    SpGEMMExecutor,
)
from repro.spgemm.pipeline import SpGEMMPipeline, SpGEMMTicket, _Prepared

__all__ = [
    "PlanReport",
    "ShardedSpGEMMPlan",
    "SpGEMMChain",
    "SpGEMMPlan",
    "StructuralPattern",
    "chain_plans",
    "execute_chain",
    "plan_from_structural_pattern",
    "spgemm_plan",
    "resolve_backend",
    "schedule_build_count",
]

# Global count of symbolic-phase runs (schedule constructions). Tests and
# the acceptance criteria assert this stays flat across cached re-executes.
_SCHEDULE_BUILDS = 0


def schedule_build_count() -> int:
    return _SCHEDULE_BUILDS


def resolve_backend(backend: str = "auto") -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("pallas", "pallas_interpret", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


_REPORT_FIELDS = (
    "pattern_key", "pattern_token", "tile", "group", "backend", "shape",
    "nnz_a", "nnz_b", "nnzb_a", "nnzb_b", "nnzb_c", "num_triples",
    "n_panels", "b_fetches", "block_omar", "schedule_builds", "cache_hits",
    "executes", "loads", "load_hits", "config_source", "tuned",
    "kernel_calls",
)


class PlanReport:
    """Structured statistics of one plan: what was built, what it costs,
    and how often it has been reused.

    ``pattern_key``, ``nnz_a``, and ``nnz_b`` may be supplied as zero-arg
    callables: they resolve (and memoize) on first access, so plan paths
    whose report nobody reads — the uncached ``ops.spgemm(..., schedule=)``
    shim — never pay the pattern digest or the ``count_nonzero`` scans.
    """

    def __init__(
        self,
        pattern_key: Union[str, Callable[[], str]],
        tile: Tuple[int, int, int],
        group: int,
        backend: str,
        shape: Tuple[int, int],  # output C shape
        nnz_a: Union[int, Callable[[], int]],
        nnz_b: Union[int, Callable[[], int]],
        nnzb_a: int,
        nnzb_b: int,
        nnzb_c: int,
        num_triples: int,
        n_panels: int,
        b_fetches: int,
        block_omar: float,
        schedule_builds: int = 1,  # symbolic-phase runs for this plan (0
        # when a pre-built schedule was supplied or the plan was loaded
        # from the disk tier, else 1)
        cache_hits: int = 0,  # times this plan was served from a PlanCache
        executes: int = 0,  # numeric-phase runs (value sets, for batches)
        loads: int = 0,  # disk-tier deserializations that built this plan
        # object (1 on a warm restart, 0 on a cold build)
        load_hits: int = 0,  # plan-cache lookups this plan satisfied from
        # the disk tier (the warm-restart acceptance counter)
        pattern_token: Optional[str] = None,  # caller-supplied fast cache
        # key (spgemm_plan(..., pattern_token=)); echoed so serving
        # callers can audit which token a plan answers to
        config_source: str = "default",  # where the active exec config
        # came from: "default" (policy table), "tuned" (probed this
        # process), "persisted" (tuned record loaded from disk), or
        # "env-override" (REPRO_SPGEMM_CHUNK_BYTES wins regardless)
        tuned: Optional[dict] = None,  # TunedConfig.to_meta() snapshot of
        # the applied tuned config (None when untuned)
        kernel_calls: int = 0,  # kernel calls one product runs: the
        # schedule's slices that one call's SMEM holds (0: no executor)
    ):
        self._pattern_key = pattern_key
        self._nnz_a = nnz_a
        self._nnz_b = nnz_b
        self.tile = tuple(tile)
        self.group = group
        self.backend = backend
        self.shape = tuple(shape)
        self.nnzb_a = nnzb_a
        self.nnzb_b = nnzb_b
        self.nnzb_c = nnzb_c
        self.num_triples = num_triples
        self.n_panels = n_panels
        self.b_fetches = b_fetches
        self.block_omar = block_omar
        self.schedule_builds = schedule_builds
        self.cache_hits = cache_hits
        self.executes = executes
        self.loads = loads
        self.load_hits = load_hits
        self.pattern_token = pattern_token
        self.config_source = config_source
        self.tuned = tuned
        self.kernel_calls = kernel_calls

    @property
    def pattern_key(self) -> str:
        if callable(self._pattern_key):
            self._pattern_key = self._pattern_key()
        return self._pattern_key

    @property
    def nnz_a(self) -> int:
        if callable(self._nnz_a):
            self._nnz_a = self._nnz_a()
        return self._nnz_a

    @property
    def nnz_b(self) -> int:
        if callable(self._nnz_b):
            self._nnz_b = self._nnz_b()
        return self._nnz_b

    def as_dict(self) -> dict:
        return {f: getattr(self, f) for f in _REPORT_FIELDS}

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        lazies = ", ".join(
            f for f, v in (("pattern_key", self._pattern_key),
                           ("nnz_a", self._nnz_a), ("nnz_b", self._nnz_b))
            if callable(v)
        )
        return (f"PlanReport(shape={self.shape}, triples={self.num_triples},"
                f" executes={self.executes}"
                + (f", unresolved=[{lazies}]" if lazies else "") + ")")


class SpGEMMPlan:
    """A fully pre-processed SpGEMM: symbolic phase done, numeric phase
    repeatable — single-shot or batched — with fresh values.

    Build through :func:`spgemm_plan` (cached) or
    :meth:`SpGEMMPlan.from_blocks` (explicit). ``execute`` / ``__call__``
    accept new value sets bound to the *same* sparsity pattern:

    * element plans (built from COO/CSR/dense inputs): ``a_vals`` is a
      ``[nnz_a]`` vector aligned with ``plan.a_pattern`` (canonical
      row-major deduplicated order), likewise ``b_vals``;
    * block plans (built from BCSV/BCSR inputs): ``a_vals`` is a packed
      ``[nnzb_a, bm, bk]`` block array, likewise ``b_vals``.

    Passing ``None`` reuses the values staged at build / last execute.
    ``execute_batch`` takes the same per-set shapes with a leading batch
    axis and runs the whole batch in one vmapped device call.

    Results returned by one plan share the precomputed CSR ``indptr`` /
    ``indices`` arrays (treat them as read-only).
    """

    def __init__(
        self,
        *,
        schedule: SpGEMMSchedule,
        a_blocks: np.ndarray,
        b_blocks: np.ndarray,
        backend: str,
        out_shape: Tuple[int, int],
        report: PlanReport,
        a_scatter: Optional[np.ndarray] = None,
        b_scatter: Optional[np.ndarray] = None,
        a_pattern: Optional[COO] = None,
        b_pattern: Optional[COO] = None,
        assembly: Optional[AssemblyMap] = None,
        output: str = "block",
        compact: Optional[AssemblyMap] = None,
    ):
        if output not in ("block", "compact"):
            raise ValueError(
                f"output must be 'block' or 'compact', got {output!r}"
            )
        self.schedule = schedule
        self.backend = backend
        self.report = report
        self.a_pattern = a_pattern
        self.b_pattern = b_pattern
        self._a_scatter = a_scatter
        self._b_scatter = b_scatter
        self._a_blocks: Optional[np.ndarray] = a_blocks
        self._b_blocks: Optional[np.ndarray] = b_blocks
        # Packed-array geometry survives release_values(): rebinds validate
        # against (and reallocate to) these.
        self._a_shape = tuple(a_blocks.shape)
        self._b_shape = tuple(b_blocks.shape)
        self._a_dtype = a_blocks.dtype
        self._b_dtype = b_blocks.dtype
        # Block slots a device bind fills per value set (element plans).
        self._bind_slots = int(np.prod(self._a_shape)) + int(
            np.prod(self._b_shape))
        self._m, self._n = out_shape
        self._group = schedule.group
        self._bm = int(a_blocks.shape[1]) if a_blocks.ndim == 3 else 0
        self._bn = int(b_blocks.shape[2]) if b_blocks.ndim == 3 else 0
        # Symbolic output structure: C's CSR pattern + the panels->CSR
        # gather map. Computed here (plan build) unless rehydrated from
        # persisted artifacts, consumed on device by the executor — the
        # numeric phase never scans values for structure.
        self.assembly: AssemblyMap = (
            assembly if assembly is not None
            else build_assembly_map(schedule, (self._bm, self._bn), out_shape)
        )
        # Output mode + the element-exact compact map (tentpole). The plan
        # always keeps the block-structural map above (its coverage /
        # race-freedom proofs anchor the verifier); ``output="compact"``
        # additionally precomputes the nnz-exact subset map the executor
        # gathers through instead — explicit zero *block fill* never
        # reaches C. Block plans have no element patterns, so their
        # "element-exact" pattern is the block fill itself: compact
        # degenerates to the block map (documented; the savings come from
        # element plans, where the pattern is real).
        self.output = output
        self.compact: Optional[AssemblyMap] = compact
        if output == "compact" and self.compact is None:
            if a_pattern is not None and b_pattern is not None:
                rows, cols = structural_product_pattern(
                    a_pattern.row, a_pattern.col,
                    b_pattern.row, b_pattern.col,
                    a_pattern.shape, b_pattern.shape,
                )
                self.compact = build_compact_map(self.assembly, rows, cols)
            else:
                self.compact = self.assembly
        # Device-resident numeric executor: schedule + scatter + gather
        # staged to device once; runs the fused rebind/kernel/assembly jit.
        # ``_make_executor`` is the subclass seam — ShardedSpGEMMPlan
        # replaces it with the mesh-partitioned executor.
        self._executor = (
            self._make_executor()
            if schedule.num_triples and self.assembly.nnz
            else None
        )
        if self._executor is not None:
            report.kernel_calls = self._executor.kernel_calls
        # Device block values are staged lazily (first execute) so building
        # a plan never pays H2D for values that are immediately rebound.
        self._a_dev = None
        self._b_dev = None
        # Guards value rebinds + report counters: plans are shared objects
        # (PlanCache returns the same instance to every pattern-equal
        # caller), so concurrent executes must each see a consistent
        # (values, device array) pair.
        self._lock = threading.Lock()
        # Pipeline accounting: steps submitted but not yet collected (or
        # discarded). While nonzero, buffer teardown (release_values /
        # release / cache eviction) refuses — an in-flight step's device
        # work still reads staged constants.
        self._inflight = 0
        self._released = False
        # (weakref-to-cache, key) set by PlanCache on insert; release()
        # evicts through it so a dead plan never stays resident.
        self._cache_ref = None
        # TunedConfig applied by the autotuner (None = policy defaults).
        # Changes only the executor chunk budget and default pipeline
        # depth — never numerics.
        self.tuned_config = None
        # A persisted TunedConfig whose tile/group no longer matches this
        # plan (artifact drift). Recorded instead of raising — the plan
        # runs on policy defaults and the verifier surfaces a finding.
        self._stale_tuned = None
        # Device copy of B's element values, staged lazily by chained
        # executes (stage s >= 2 reuses the plan's own B values against the
        # previous stage's device-resident C values).
        self._b_vals_dev = None

    def _active(self) -> AssemblyMap:
        """The output map results are wrapped in (and the executor gathers
        through): the compact map under ``output="compact"``, else the
        block-structural map."""
        return self.compact if self.output == "compact" else self.assembly

    def _make_executor(self):
        """Build the numeric executor (called once, at plan build)."""
        return SpGEMMExecutor(
            schedule=self.schedule,
            assembly=self._active(),
            backend=self.backend,
            a_scatter=self._a_scatter,
            b_scatter=self._b_scatter,
            a_shape=self._a_shape,
            b_shape=self._b_shape,
        )

    def apply_tuned_config(self, cfg) -> None:
        """Apply an autotuner :class:`~repro.spgemm.autotune.TunedConfig`:
        set the executor's chunk budget and make ``cfg.pipeline_depth``
        the default for :meth:`pipeline` / :meth:`execute_stream`.

        Numerics are untouched — chunk/depth are bitwise-invariant knobs,
        and a config tuned at a different (tile, group) is applied to the
        plan *built at that tile/group* by the autotuner, never here.
        Report provenance: ``config_source`` becomes ``cfg.source``
        (``"tuned"``/``"persisted"``) unless ``REPRO_SPGEMM_CHUNK_BYTES``
        is set, which always wins and keeps ``"env-override"``.

        A config whose (tile, group) does not match this plan is *stale* —
        a persisted sidecar that drifted from the artifact it rode with.
        Drift is not an execution error (the plan is correct on policy
        defaults), so it is recorded instead of raised: the config is
        ignored, ``report.config_source`` becomes ``"stale-tuned"``, and
        :func:`repro.analysis.verify.verify_plan` surfaces a
        ``tuned.stale-config`` finding.
        """
        if tuple(cfg.tile) != tuple(self.report.tile) or (
            int(cfg.group) != int(self.report.group)
        ):
            with self._lock:
                self._stale_tuned = cfg
                self.tuned_config = None
                self.report.tuned = None
                if not os.environ.get(CHUNK_BYTES_ENV):
                    self.report.config_source = "stale-tuned"
            return
        with self._lock:
            self.tuned_config = cfg
            self.report.tuned = cfg.to_meta()
            if os.environ.get(CHUNK_BYTES_ENV):
                self.report.config_source = "env-override"
            else:
                self.report.config_source = (
                    "persisted" if cfg.source == "persisted" else "tuned"
                )
            if self._executor is not None:
                self._executor.set_chunk_bytes(cfg.chunk_bytes)

    def _default_depth(self) -> int:
        cfg = self.tuned_config
        return int(cfg.pipeline_depth) if cfg is not None else 2

    def _stage_a(self, blocks: np.ndarray):
        """Host packed A blocks -> device layout for ``executor.run``.

        copy=True: on CPU backends jnp.asarray may alias the numpy scratch
        buffer, and a later rebind would mutate an earlier caller's staged
        values mid-flight.
        """
        return jnp.array(blocks, copy=True)

    def _stage_b(self, blocks: np.ndarray):
        return jnp.array(blocks, copy=True)

    # -- construction -----------------------------------------------------

    @classmethod
    def from_blocks(
        cls,
        a: BCSV,
        b: BCSR,
        *,
        backend: str = "auto",
        schedule: Optional[SpGEMMSchedule] = None,
        pattern_key: str = "",
        mesh: Optional[Mesh] = None,
        mesh_axis: Optional[str] = None,
        output: str = "block",
    ) -> "SpGEMMPlan":
        """Plan from pre-converted block formats (the ops.spgemm shim path).

        When ``schedule`` is supplied the symbolic phase is skipped entirely
        (and not counted as a build). Report identity/population fields
        (pattern digest, element nnz counts) are lazy — computed only if
        the report is actually read. The thunks pin no operand-sized
        memory: the digest closes over the (small) index arrays only, and
        the nnz counts read the plan's *currently staged* blocks (so they
        raise if resolved after ``release_values``).
        """
        global _SCHEDULE_BUILDS
        backend = resolve_backend(backend)
        built = 0
        if schedule is None:
            schedule = build_spgemm_schedule(a, b)
            _SCHEDULE_BUILDS += 1
            built = 1
        if not pattern_key:
            idx = (a.brow, a.bcol, a.group_ptr, b.indptr, b.indices)
            meta = ("blocks", a.shape, b.shape, a.block_shape,
                    b.block_shape, a.group, str(a.blocks.dtype),
                    str(b.blocks.dtype))

            def pattern_key(idx=idx, meta=meta):
                return pattern_digest(*idx, meta=meta)
        report = _make_report(
            pattern_key,
            (a.block_shape[0], a.block_shape[1], b.block_shape[1]),
            a.group, backend, (a.shape[0], b.shape[1]),
            0, 0,  # placeholders; bound to staged blocks below
            a.nnzb, b.nnzb, schedule,
        )
        report.schedule_builds = built
        plan_cls, extra = _resolve_plan_cls(mesh, mesh_axis)
        plan = plan_cls(
            schedule=schedule,
            a_blocks=a.blocks,
            b_blocks=b.blocks,
            backend=backend,
            out_shape=(a.shape[0], b.shape[1]),
            report=report,
            output=output,
            **extra,
        )
        report._nnz_a = _staged_nnz(plan, "_a_blocks", "nnz_a")
        report._nnz_b = _staged_nnz(plan, "_b_blocks", "nnz_b")
        return plan

    # -- persistence (the disk tier's codec endpoints) --------------------

    def persist_artifacts(self) -> Tuple[dict, dict]:
        """The plan's value-independent symbolic artifacts as
        ``(arrays, meta)`` — the payload the disk tier
        (:class:`repro.spgemm.persist.PlanStore`) writes once per cache
        key.

        ``arrays`` holds only what the symbolic phase computed: the triple
        schedule, the assembly map, and the value-scatter indices (element
        plans; :class:`ShardedSpGEMMPlan` adds its shard bounds). ``meta``
        holds the padding/geometry scalars (packed block-array shapes and
        dtypes, true output shape, tile/group, backend). Values are
        deliberately excluded — a warm restart brings its own.
        """
        arrays = {}
        arrays.update(schedule_to_arrays(self.schedule))
        arrays.update(assembly_to_arrays(self.assembly))
        if self.output == "compact":
            # The compact map rides the same AssemblyMap codec under its
            # own prefix; block artifacts keep their pre-compaction byte
            # layout exactly.
            arrays.update(assembly_to_arrays(self.compact, prefix="casm."))
        if self._a_scatter is not None:
            arrays["a_scatter"] = self._a_scatter
        if self._b_scatter is not None:
            arrays["b_scatter"] = self._b_scatter
        element = self._a_scatter is not None and self._b_scatter is not None
        meta = {
            "kind": "element" if element else "block",
            "output": self.output,
            "backend": self.backend,
            "out_shape": [self._m, self._n],
            "a_shape": list(self._a_shape),
            "b_shape": list(self._b_shape),
            "a_dtype": str(self._a_dtype),
            "b_dtype": str(self._b_dtype),
            "tile": list(self.report.tile),
            "group": self.report.group,
        }
        if self.tuned_config is not None:
            # The tuned exec config rides inside the plan artifact too (in
            # addition to the cache's sidecar record), so a copied/shared
            # artifact file rehydrates fully tuned on its own.
            meta["tuned_config"] = self.tuned_config.to_meta()
        return arrays, meta

    @classmethod
    def from_artifacts(
        cls,
        arrays: dict,
        meta: dict,
        *,
        backend: str,
        pattern_key: Union[str, Callable[[], str]] = "",
        a_vals=None,
        b_vals=None,
        a_blocks: Optional[np.ndarray] = None,
        b_blocks: Optional[np.ndarray] = None,
        a_pattern: Optional[COO] = None,
        b_pattern: Optional[COO] = None,
        mesh: Optional[Mesh] = None,
        mesh_axis: Optional[str] = None,
        output: str = "block",
    ) -> "SpGEMMPlan":
        """Rehydrate a plan from persisted artifacts + this call's values.

        The inverse of :meth:`persist_artifacts`: the symbolic phase is
        **not** re-run (``report.schedule_builds == 0``); the packed block
        arrays are rebuilt by scattering the caller's ``a_vals``/``b_vals``
        through the persisted scatter indices (element plans) or taken
        directly from ``a_blocks``/``b_blocks`` (block plans). Any
        inconsistency between artifacts and inputs raises — the cache
        treats that as an unusable entry and falls back to a cold build.
        """
        backend = resolve_backend(backend)
        kind = meta.get("kind")
        if kind not in ("element", "block"):
            raise ValueError(f"unknown persisted plan kind {kind!r}")
        if meta.get("backend") != backend:
            raise ValueError(
                f"persisted backend {meta.get('backend')!r} != {backend!r}"
            )
        if meta.get("output", "block") != output:
            raise ValueError(
                f"persisted output {meta.get('output', 'block')!r} != "
                f"{output!r}"
            )
        schedule = schedule_from_arrays(arrays)
        assembly = assembly_from_arrays(arrays)
        compact = (
            assembly_from_arrays(arrays, prefix="casm.")
            if output == "compact" else None
        )
        a_shape = tuple(int(x) for x in meta["a_shape"])
        b_shape = tuple(int(x) for x in meta["b_shape"])
        a_dtype = np.dtype(meta["a_dtype"])
        b_dtype = np.dtype(meta["b_dtype"])
        out_shape = tuple(int(x) for x in meta["out_shape"])
        tile = tuple(int(x) for x in meta["tile"])
        group = int(meta["group"])
        a_scatter = arrays.get("a_scatter")
        b_scatter = arrays.get("b_scatter")

        def rebuild(vals, scatter, shape, dtype, name):
            if scatter is None:
                raise ValueError(f"{name}: persisted scatter missing")
            vals = np.asarray(vals)
            scatter = np.asarray(scatter)
            if vals.shape != (int(scatter.shape[0]),):
                raise ValueError(
                    f"{name}: {vals.shape} values vs persisted scatter "
                    f"of {int(scatter.shape[0])}"
                )
            blocks = np.zeros(shape, dtype)
            blocks.reshape(-1)[scatter] = vals.astype(dtype, copy=False)
            return blocks

        if kind == "element":
            if a_vals is None or b_vals is None:
                raise ValueError("element plan needs a_vals/b_vals")
            a_blocks = rebuild(a_vals, a_scatter, a_shape, a_dtype, "a_vals")
            b_blocks = rebuild(b_vals, b_scatter, b_shape, b_dtype, "b_vals")
            nnz_a = int(np.asarray(a_scatter).shape[0])
            nnz_b = int(np.asarray(b_scatter).shape[0])
        else:
            if a_blocks is None or b_blocks is None:
                raise ValueError("block plan needs a_blocks/b_blocks")
            a_blocks = np.asarray(a_blocks)
            b_blocks = np.asarray(b_blocks)
            if tuple(a_blocks.shape) != a_shape or a_blocks.dtype != a_dtype:
                raise ValueError(
                    f"a_blocks {a_blocks.shape}/{a_blocks.dtype} vs "
                    f"persisted {a_shape}/{a_dtype}"
                )
            if tuple(b_blocks.shape) != b_shape or b_blocks.dtype != b_dtype:
                raise ValueError(
                    f"b_blocks {b_blocks.shape}/{b_blocks.dtype} vs "
                    f"persisted {b_shape}/{b_dtype}"
                )
            nnz_a = nnz_b = 0  # bound to staged blocks below (lazy)
        report = _make_report(
            pattern_key, tile, group, backend, out_shape,
            nnz_a, nnz_b, a_shape[0] if a_blocks.ndim == 3 else 0,
            b_shape[0] if b_blocks.ndim == 3 else 0, schedule,
        )
        report.schedule_builds = 0
        report.loads = 1
        report.load_hits = 1
        plan_cls, extra = _resolve_plan_cls(mesh, mesh_axis)
        if mesh is not None and "shard_bounds" in arrays:
            extra["shards"] = shards_from_bounds(
                schedule, arrays["shard_bounds"]
            )
        plan = plan_cls(
            schedule=schedule,
            a_blocks=a_blocks,
            b_blocks=b_blocks,
            backend=backend,
            out_shape=out_shape,
            report=report,
            a_scatter=None if a_scatter is None else np.asarray(a_scatter),
            b_scatter=None if b_scatter is None else np.asarray(b_scatter),
            a_pattern=a_pattern,
            b_pattern=b_pattern,
            assembly=assembly,
            output=output,
            compact=compact,
            **extra,
        )
        if kind == "block":
            report._nnz_a = _staged_nnz(plan, "_a_blocks", "nnz_a")
            report._nnz_b = _staged_nnz(plan, "_b_blocks", "nnz_b")
        tuned_meta = meta.get("tuned_config")
        if tuned_meta is not None:
            # Import here: autotune imports this module at its top level.
            from repro.spgemm.autotune import TunedConfig

            plan.apply_tuned_config(
                TunedConfig.from_meta(dict(tuned_meta), source="persisted")
            )
        return plan

    # -- numeric phase ----------------------------------------------------

    def _rebind(
        self,
        vals,
        blocks: Optional[np.ndarray],
        scatter: Optional[np.ndarray],
        nnz: int,
        name: str,
        shape: Tuple[int, ...],
        dtype,
    ) -> np.ndarray:
        vals = np.asarray(vals)
        if scatter is not None:
            if vals.shape != (nnz,):
                raise ValueError(
                    f"{name}: expected [{nnz}] values in canonical pattern "
                    f"order, got shape {vals.shape}"
                )
            if blocks is None:  # scratch was released; reallocate
                blocks = np.zeros(shape, dtype)
            # Positions outside `scatter` are structurally zero and never
            # written, so in-place rebinding is sound.
            blocks.reshape(-1)[scatter] = vals.astype(blocks.dtype, copy=False)
            return blocks
        if vals.shape != shape:
            raise ValueError(
                f"{name}: expected packed blocks of shape {shape}, "
                f"got {vals.shape}"
            )
        return vals

    def value_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Per-set operand shapes the numeric phase accepts:
        ``(want_a, want_b)`` — ``[nnz]`` vectors for element plans, packed
        block arrays for block plans. ``execute_batch``/``submit`` take the
        same shapes with a shared leading batch axis. This is the
        validation contract serving front ends (the gateway) check
        requests against before queueing them."""
        if self._a_scatter is not None and self._b_scatter is not None:
            return (self.report.nnz_a,), (self.report.nnz_b,)
        return self._a_shape, self._b_shape

    def value_nbytes(self) -> int:
        """Bytes of one request's operand values (a_vals + b_vals at the
        plan's packed dtypes) — the admission-control unit the gateway's
        in-flight byte budget counts."""
        want_a, want_b = self.value_shapes()
        return (
            int(np.prod(want_a)) * self._a_dtype.itemsize
            + int(np.prod(want_b)) * self._b_dtype.itemsize
        )

    def _empty_csr(self) -> CSR:
        return CSR(
            np.zeros(self._m + 1, np.int64), np.zeros(0, np.int32),
            np.zeros(0, np.float32), (self._m, self._n),
        )

    def _wrap_packed(self, packed: np.ndarray) -> CSR:
        """Packed C values (active-map order) -> CSR on the precomputed
        structure. indptr/indices are shared across this plan's results."""
        asm = self._active()
        return CSR(asm.indptr, asm.indices, packed, (self._m, self._n))

    def output_pattern(self) -> "StructuralPattern":
        """C's value-independent output structure — the seed for the next
        plan in a chain (:func:`plan_from_structural_pattern`). Under
        ``output="compact"`` this is the element-exact pattern; under the
        default block output it is the block-structural pattern (explicit
        zero fill included)."""
        asm = self._active()
        return StructuralPattern(asm.indptr, asm.indices, (self._m, self._n))

    def device_indptr(self):
        """Device-resident CSR ``indptr`` of the active output map (the
        device half of the compaction bookkeeping; see
        :meth:`repro.spgemm.executor.SpGEMMExecutor.device_indptr`).
        Together with a ``_run_packed`` result this is a complete CSR
        replica of C that never leaves the device."""
        if self._executor is None:
            return jnp.asarray(self._active().indptr.astype(np.int32))
        return self._executor.device_indptr()

    def then(self, b, **kwargs) -> "SpGEMMChain":
        """Compose this plan with a next operand: plan ``C @ b`` directly
        from this plan's structural output pattern (no COO conversion of
        C) and return the two-stage :class:`SpGEMMChain`. ``kwargs``
        forward to :func:`plan_from_structural_pattern`; tile/group/
        backend/output default to this plan's own config. Chain further
        with :meth:`SpGEMMChain.then`."""
        return SpGEMMChain([self, self._plan_next(b, **kwargs)])

    def _plan_next(self, b, **kwargs) -> "SpGEMMPlan":
        kwargs.setdefault("tile", self.report.tile)
        kwargs.setdefault("group", self.report.group)
        kwargs.setdefault("backend", self.backend)
        kwargs.setdefault("output", self.output)
        kwargs.setdefault("dtype", self._a_dtype)
        return plan_from_structural_pattern(
            self.output_pattern(), b, **kwargs
        )

    def execute(self, a_vals=None, b_vals=None) -> CSR:
        """Numeric phase only: C = A @ B for fresh values on the planned
        pattern. Zero schedule-construction work; the whole phase (kernel +
        output assembly) runs inside the executor's jit."""
        with TraceAnnotation("spgemm.execute") as span:
            packed, step = self._run_packed(a_vals, b_vals)
            span.set_metadata(step=step)
            if packed is None:
                return self._empty_csr()
            with TraceAnnotation("spgemm.collect", step=step):
                return self._wrap_packed(self._fetch(packed, step))

    def _fetch(self, packed, step: int, mode: Optional[str] = None):
        """Packed C values to host: ``spgemm.wait`` until the device has
        them, then ``spgemm.d2h`` for the copy — through the executor's
        ``pipe_collect`` for a pipeline ``mode``, which also joins a
        sharded plan's per-shard segments. The copy is queued before the
        wait, where a lone ``np.asarray`` queues it too (jax's
        ``ArrayImpl._value`` starts the copy, then blocks on it), so it
        still follows the device's last op without a round trip through
        this thread; ``spgemm.d2h`` is the part of it still to run. A
        sharded plan's ``run`` results are on the host already."""
        if not isinstance(packed, jax.Array):
            return packed
        packed.copy_to_host_async()
        with TraceAnnotation("spgemm.wait", step=step):
            jax.block_until_ready(packed)
        with TraceAnnotation("spgemm.d2h", step=step,
                             d2h_bytes=packed.nbytes):
            if mode is None:
                return np.asarray(packed)
            return self._executor.pipe_collect(packed, mode=mode)

    # The span over a call into the executor's run, run_values or
    # run_batch, which return before the device is done: H2D plus the jit
    # enqueue.
    _RUN_SPAN = "spgemm.dispatch"

    def _dispatch_span(self, step: int, *sent, sets: int = 1,
                       bind_sets: int = 0, name: str = "spgemm.dispatch"):
        """``name`` around one call into the executor for ``sets`` value
        sets: the H2D of the host arrays ``sent`` plus the jit enqueue.
        Where the device binds ``bind_sets`` value sets, ``sent`` is their
        values, and the span counts the values the bind scatters and the
        block slots of the zeroed arrays it scatters them into (their
        ratio is the block fill). ``kernel_calls`` is the Pallas calls the
        call dispatches (the schedule's slices), ``triples`` the block
        triples it runs, ``assemble_values`` the C values it assembles and
        ``assemble_runs`` the runs they are placed in (one a value where
        the assembly gathers them one at a time)."""
        return TraceAnnotation(
            name, step=step,
            h2d_bytes=sum(x.nbytes for x in sent),
            bind_values=sum(x.size for x in sent) if bind_sets else 0,
            bind_slots=bind_sets * self._bind_slots,
            kernel_calls=self._executor.kernel_calls,
            triples=sets * self._executor.triples,
            assemble_runs=sets * self._executor.assemble_runs,
            assemble_values=sets * self._executor.assemble_values,
        )

    def _run_packed(self, a_vals=None, b_vals=None):
        """``execute``'s device core: dispatch the numeric phase and return
        ``(packed C values, step)`` *without* materializing the values on
        host (``None`` for an empty plan); ``step`` is the product's
        ``report.executes``. Single-device plans return a device array —
        the handoff ``execute_chain`` keeps resident between stages;
        sharded plans return host arrays (their executor concatenates
        per-shard segments on host by design)."""
        with contextlib.ExitStack() as spans:
            with self._lock:
                self._check_released()
                # The count the increment below makes: this product's
                # number.
                step = self.report.executes + 1
                # report.nnz_* is read only on the scatter (element-plan)
                # path: block plans keep their lazy count_nonzero report
                # fields unresolved through executes.
                if a_vals is not None:
                    with _rebind_span(step, "a", self._a_scatter,
                                      self._a_shape):
                        self._a_blocks = self._rebind(
                            a_vals, self._a_blocks, self._a_scatter,
                            self.report.nnz_a if self._a_scatter is not None
                            else 0,
                            "a_vals", self._a_shape, self._a_dtype,
                        )
                    self._a_dev = None
                if b_vals is not None:
                    with _rebind_span(step, "b", self._b_scatter,
                                      self._b_shape):
                        self._b_blocks = self._rebind(
                            b_vals, self._b_blocks, self._b_scatter,
                            self.report.nnz_b if self._b_scatter is not None
                            else 0,
                            "b_vals", self._b_shape, self._b_dtype,
                        )
                    self._b_dev = None
                if self._a_blocks is None or self._b_blocks is None:
                    raise ValueError(
                        "plan values were released (release_values); pass "
                        "a_vals/b_vals to execute"
                    )
                # Element plans called with both value vectors take the
                # fully fused device path (rebind + kernel + assembly in one
                # jit): only [nnz] vectors cross to device, not full packed
                # blocks. The host rebind above still ran, so no-arg
                # execute() stays current; device block staging is left to
                # the next such call.
                fused_values = (
                    a_vals is not None and b_vals is not None
                    and self._a_scatter is not None
                    and self._b_scatter is not None
                )
                if fused_values:
                    a_send = np.asarray(a_vals, dtype=self._a_dtype)
                    b_send = np.asarray(b_vals, dtype=self._b_dtype)
                    sent = (a_send, b_send)
                else:  # the blocks not on the device yet go down below
                    sent = tuple(host for host, dev in (
                        (self._a_blocks, self._a_dev),
                        (self._b_blocks, self._b_dev)) if dev is None)
                if self._executor is not None:
                    # Held past the lock, to the end of the executor's call.
                    spans.enter_context(self._dispatch_span(
                        step, *sent, bind_sets=int(fused_values),
                        name=self._RUN_SPAN))
                if not fused_values:
                    if self._a_dev is None:
                        self._a_dev = self._stage_a(self._a_blocks)
                    if self._b_dev is None:
                        self._b_dev = self._stage_b(self._b_blocks)
                    # Snapshot under the lock so a concurrent rebind on
                    # this shared plan cannot mix one caller's A with
                    # another's B.
                    a_dev, b_dev = self._a_dev, self._b_dev
                self.report.executes += 1
            if self._executor is None:
                return None, step
            if fused_values:
                return self._executor.run_values(a_send, b_send), step
            return self._executor.run(a_dev, b_dev), step

    def _run_packed_chained(self, c_packed):
        """Stage ``s >= 2`` of :func:`execute_chain`: the previous stage's
        packed C values (active-map order == canonical row-major element
        order) are this plan's A values, consumed directly on device
        through the fused rebind/kernel/assembly jit — no host transfer.
        B values are the plan's own staged element values, shipped to
        device once and reused across chain executes."""
        if self._a_scatter is None or self._b_scatter is None:
            raise ValueError(
                "chained stages need element plans (built from COO/CSR "
                "inputs or plan_from_structural_pattern)"
            )
        with self._lock:
            self._check_released()
            if self._b_vals_dev is None:
                if self.b_pattern is None:
                    raise ValueError(
                        "chained stage has no B values: the plan was built "
                        "without a B pattern (release_values?); rebuild via "
                        "plan_from_structural_pattern with B in hand"
                    )
                self._b_vals_dev = jnp.asarray(
                    np.asarray(self.b_pattern.val, dtype=self._b_dtype)
                )
            b_dev = self._b_vals_dev
            self.report.executes += 1
        if c_packed is None:  # previous stage was empty: A values all zero
            c_packed = jnp.zeros((self.report.nnz_a,), self._a_dtype)
        if c_packed.shape != (self.report.nnz_a,):
            raise ValueError(
                f"chained values: expected [{self.report.nnz_a}] from the "
                f"previous stage, got shape {tuple(c_packed.shape)}"
            )
        if self._executor is None:
            return None
        return self._executor.run_values(
            c_packed.astype(self._a_dtype), b_dev
        )

    __call__ = execute

    def execute_batch(self, a_vals, b_vals) -> list:
        """Batched numeric phase: one vmapped device call over a leading
        value-batch axis (the serving workload).

        ``a_vals`` is ``[batch, nnz_a]`` for element plans or
        ``[batch, nnzb_a, bm, bk]`` packed blocks for block plans
        (``b_vals`` likewise). Returns a list of ``batch`` CSR results that
        share this plan's precomputed ``indptr``/``indices``.

        Stateless with respect to the plan's staged values: it never touches
        the buffers no-arg ``execute()`` reuses, so it is safe to interleave
        with single executes and works after ``release_values()``. The
        batch honors the plan's backend: pallas plans run the batch-folded
        Pallas grid, jnp plans the offset-folded scatter-add reference —
        both bitwise-equal to looping ``execute`` per element.
        """
        with TraceAnnotation("spgemm.execute_batch") as span:
            a_vals = np.asarray(a_vals)
            b_vals = np.asarray(b_vals)
            rebind = (self._a_scatter is not None
                      and self._b_scatter is not None)
            want_a, want_b = self.value_shapes()
            if a_vals.ndim != len(want_a) + 1 or a_vals.shape[1:] != want_a:
                raise ValueError(
                    f"a_vals: expected "
                    f"[batch, {', '.join(map(str, want_a))}], "
                    f"got shape {a_vals.shape}"
                )
            if (b_vals.shape[1:] != want_b
                    or b_vals.shape[0] != a_vals.shape[0]):
                raise ValueError(
                    f"b_vals: expected [{a_vals.shape[0]}, "
                    f"{', '.join(map(str, want_b))}], got shape {b_vals.shape}"
                )
            batch = int(a_vals.shape[0])
            with self._lock:
                self._check_released()
                step = self.report.executes + 1  # the batch's first value set
                self.report.executes += batch
            span.set_metadata(step=step, batch=batch)
            if batch == 0:
                return []
            if self._executor is None:
                return [self._empty_csr() for _ in range(batch)]
            # Match execute()'s rebind semantics: values are cast to the
            # plan's packed dtype.
            a_vals = a_vals.astype(self._a_dtype, copy=False)
            b_vals = b_vals.astype(self._b_dtype, copy=False)
            # Oversized batches are split so the device accumulator working
            # set stays cache-resident (see SpGEMMExecutor.batch_chunk); each
            # chunk is still one fused device call.
            chunk = min(batch, self._executor.batch_chunk())
            out = []
            for lo in range(0, batch, chunk):
                hi = min(lo + chunk, batch)
                # Host slices go down as-is: the executor owns device layout
                # (plain jnp.asarray unsharded; per-shard slicing + mesh
                # placement on sharded plans).
                a, b = a_vals[lo:hi], b_vals[lo:hi]
                with self._dispatch_span(
                        step, a, b, sets=hi - lo,
                        bind_sets=hi - lo if rebind else 0,
                        name=self._RUN_SPAN):
                    packed = self._executor.run_batch(a, b, rebind=rebind)
                with TraceAnnotation("spgemm.collect", step=step):
                    packed = self._fetch(packed, step)
                    out.extend(self._wrap_packed(packed[i])
                               for i in range(hi - lo))
            return out

    # -- async serving (the executor's pipeline surface) -------------------

    def pipeline(self, depth: Optional[int] = None) -> SpGEMMPipeline:
        """A bounded-depth submit/collect pipeline over this plan.

        ``depth=None`` takes the plan's tuned pipeline depth when an
        autotuner config is applied, else 2 — the paper's double buffer:
        one step staging (H2D + rebind) while one computes. See
        :class:`repro.spgemm.pipeline.SpGEMMPipeline`."""
        return SpGEMMPipeline(
            self, depth=self._default_depth() if depth is None else depth
        )

    def execute_async(self, a_vals=None, b_vals=None) -> SpGEMMTicket:
        """Dispatch one numeric phase without blocking; redeem the
        returned ticket with ``.result()``.

        Same operand shapes as ``execute`` (a leading batch axis makes
        the ticket redeem to ``execute_batch``'s list-of-CSR output).
        Each call is its own depth-1 pipeline — in-flight count is
        caller-managed; use :meth:`pipeline` for bounded-depth serving.
        """
        return SpGEMMPipeline(self, depth=1).submit(a_vals, b_vals)

    def execute_stream(self, value_iter, *, depth: Optional[int] = None):
        """Stream value sets through a ``depth``-deep pipeline, yielding
        one CSR per item in order (``depth=None``: the tuned depth if an
        autotuner config is applied, else 2).

        ``value_iter`` yields ``(a_vals, b_vals)`` tuples or ``{"a_vals",
        "b_vals"}`` dicts — e.g.
        :meth:`repro.data.pipeline.SpGEMMValueStream.value_iter`. Results
        are bitwise-equal to calling ``execute`` per item; step ``s+1``'s
        staging overlaps step ``s``'s kernel throughout."""
        return self.pipeline(depth).stream(value_iter)

    @property
    def in_flight(self) -> int:
        """Pipeline steps submitted against this plan and not yet
        collected (or discarded). Buffer teardown refuses while > 0."""
        with self._lock:
            return self._inflight

    def _check_released(self) -> None:
        """Call under ``self._lock``."""
        if self._released:
            raise RuntimeError(
                "plan was released (release()); build or fetch a new plan"
            )

    def _check_no_inflight(self, what: str) -> None:
        """Call under ``self._lock``."""
        if self._inflight:
            raise RuntimeError(
                f"cannot {what}: {self._inflight} in-flight pipeline "
                f"step(s) still read this plan's staged buffers; collect "
                f"the tickets or close the pipeline first"
            )

    def _pipe_check(self, a_vals, b_vals) -> _Prepared:
        """Validate one submission and prepare its operands (host work +
        plan-state snapshot only; no device compute is dispatched).

        Stateless w.r.t. the plan's staged values — explicit operands
        never touch the buffers no-arg ``execute()`` reuses — except that
        the no-arg form stages (and caches) the plan's own values exactly
        like ``execute()`` does."""
        if (a_vals is None) != (b_vals is None):
            raise ValueError(
                "submit takes both a_vals and b_vals, or neither "
                "(to reuse the plan's staged values)"
            )
        if a_vals is None:
            with self._lock:
                self._check_released()
                if self._a_blocks is None or self._b_blocks is None:
                    raise ValueError(
                        "plan values were released (release_values); pass "
                        "a_vals/b_vals to submit"
                    )
                sent = ()
                if self._executor is not None:
                    sent = tuple(host for host, dev in (
                        (self._a_blocks, self._a_dev),
                        (self._b_blocks, self._b_dev)) if dev is None)
                    if self._a_dev is None:
                        self._a_dev = self._stage_a(self._a_blocks)
                    if self._b_dev is None:
                        self._b_dev = self._stage_b(self._b_blocks)
                return _Prepared("blocks", self._a_dev, self._b_dev,
                                 None, 1, sent)
        with self._lock:
            self._check_released()
        a_vals = np.asarray(a_vals)
        b_vals = np.asarray(b_vals)
        rebind = self._a_scatter is not None and self._b_scatter is not None
        want_a, want_b = self.value_shapes()
        single = a_vals.shape == want_a and b_vals.shape == want_b
        batched = (
            a_vals.ndim == len(want_a) + 1 and a_vals.shape[1:] == want_a
            and b_vals.shape[:1] == a_vals.shape[:1]
            and b_vals.shape[1:] == want_b
        )
        if not (single or batched):
            raise ValueError(
                f"submit: expected a_vals {want_a} / b_vals {want_b} "
                f"(optionally with a shared leading batch axis), got "
                f"{a_vals.shape} / {b_vals.shape}"
            )
        a_vals = a_vals.astype(self._a_dtype, copy=False)
        b_vals = b_vals.astype(self._b_dtype, copy=False)
        if single:
            if rebind:
                return _Prepared("values", a_vals, b_vals, None, 1)
            # Packed-block operands: stage now (copy-on-stage, the
            # executor's device layout) so the caller may reuse buffers.
            return _Prepared(
                "blocks", self._stage_a(a_vals), self._stage_b(b_vals),
                None, 1, (a_vals, b_vals),
            )
        mode = "batch_values" if rebind else "batch_blocks"
        batch = int(a_vals.shape[0])
        return _Prepared(mode, a_vals, b_vals, batch, batch)

    def _pipe_begin(self, n_execs: int) -> None:
        with self._lock:
            self._check_released()
            self.report.executes += n_execs
            self._inflight += 1

    def _pipe_end(self) -> None:
        with self._lock:
            self._inflight -= 1

    def _pipe_dispatch(self, prep: _Prepared, step: int):
        """Dispatch one prepared step's device work (stage -> kernel and
        assembly) without blocking; returns the packed device result (a
        list of per-chunk results for batch submissions). ``step`` is the
        pipeline index its spans carry."""
        if self._executor is None or (prep.batch == 0):
            return None
        ex = self._executor
        if prep.batch is None:
            if prep.mode == "blocks":  # staged by _pipe_check
                with self._dispatch_span(step, *prep.sent):
                    return ex.pipe_kernel((prep.a, prep.b), mode="single")
            with self._dispatch_span(step, prep.a, prep.b, bind_sets=1):
                staged = ex.pipe_stage(prep.a, prep.b, mode=prep.mode)
                return ex.pipe_kernel(staged, mode="single")
        # Batch submissions chunk exactly like execute_batch, so the
        # device accumulator working set stays cache-resident; each chunk
        # is dispatched back-to-back (still zero host blocking).
        chunk = min(prep.batch, ex.batch_chunk())
        out = []
        for lo in range(0, prep.batch, chunk):
            hi = min(lo + chunk, prep.batch)
            a, b = prep.a[lo:hi], prep.b[lo:hi]
            bind = hi - lo if prep.mode == "batch_values" else 0
            with self._dispatch_span(step, a, b, sets=hi - lo,
                                     bind_sets=bind):
                staged = ex.pipe_stage(a, b, mode=prep.mode)
                out.append(ex.pipe_kernel(staged, mode="batch"))
        return out

    def _pipe_collect(self, prep: _Prepared, packed, step: int):
        """Materialize one dispatched step on host (the blocking D2H) and
        wrap it in the plan's precomputed CSR structure, inside the
        step's ``spgemm.collect`` span."""
        with TraceAnnotation("spgemm.collect", step=step):
            if prep.batch is None:
                if self._executor is None:
                    return self._empty_csr()
                return self._wrap_packed(
                    self._fetch(packed, step, mode="single"))
            if self._executor is None:
                return [self._empty_csr() for _ in range(prep.batch)]
            out = []
            for chunk_packed in (packed or ()):
                arr = self._fetch(chunk_packed, step, mode="batch")
                out.extend(self._wrap_packed(arr[i])
                           for i in range(arr.shape[0]))
            return out

    # -- teardown ----------------------------------------------------------

    def release_device_values(self) -> None:
        """Drop only the staged device copies of the packed block values.

        The next execute restages from the host arrays on demand. Refuses
        while pipeline steps are in flight (they read these buffers).
        """
        with self._lock:
            self._check_no_inflight("release device values")
            self._a_dev = None
            self._b_dev = None
            self._b_vals_dev = None

    def release_values(self) -> None:
        """Drop host AND device copies of the packed block values.

        Cached plans outlive individual calls; one-shot callers (the
        ``ops.spgemm`` shim) release values after executing so a warm
        cache pins only the pattern state (schedule, scatter indices,
        assembly map) — not operand-sized value arrays. After release,
        ``execute`` requires explicit ``a_vals``/``b_vals``
        (``execute_batch`` is unaffected — it never reads staged values).
        Refuses while pipeline steps are in flight.
        """
        with self._lock:
            self._check_no_inflight("release values")
            self._a_dev = None
            self._b_dev = None
            self._b_vals_dev = None
            self._a_blocks = None
            self._b_blocks = None

    def release(self) -> None:
        """Full teardown: values (host + device) AND the executor's
        device-resident constants. The plan is dead afterwards — any
        execute/submit raises — and it evicts itself from the cache that
        holds it, so the next ``spgemm_plan`` for this pattern builds (or
        disk-loads) a fresh plan instead of hitting the dead one. Refuses
        while pipeline steps are in flight; serving operators drain or
        ``close()`` pipelines first.
        """
        with self._lock:
            self._check_no_inflight("release plan")
            self._released = True
            self._a_dev = None
            self._b_dev = None
            self._b_vals_dev = None
            self._a_blocks = None
            self._b_blocks = None
            self._executor = None
            ref = self._cache_ref
        # Self-evict outside the plan lock (eviction re-checks in_flight,
        # which takes it). in_flight is 0 and submits now refuse, so the
        # guarded evict cannot race back to RuntimeError.
        if ref is not None:
            cache = ref[0]()
            if cache is not None:
                cache.evict(ref[1], only=self)

    def host_nbytes(self) -> int:
        """Approximate bytes of host arrays this plan retains — the sizing
        basis for :class:`~repro.spgemm.cache.PlanCache` byte budgets."""
        sch = self.schedule
        arrays = [
            sch.a_slot, sch.b_slot, sch.panel, sch.sub_row, sch.start,
            sch.panel_group, sch.panel_bcol, sch.c_brow, sch.c_bcol,
        ]
        with self._lock:
            arrays += [self._a_blocks, self._b_blocks]
        arrays += [self._a_scatter, self._b_scatter]
        for pat in (self.a_pattern, self.b_pattern):
            if pat is not None:
                arrays += [pat.row, pat.col, pat.val]
        compact = self.compact.nbytes() if self.compact is not None else 0
        return self.assembly.nbytes() + compact + sum(
            a.nbytes for a in arrays if a is not None
        )


class ShardedSpGEMMPlan(SpGEMMPlan):
    """A mesh-aware :class:`SpGEMMPlan`: the panel schedule is partitioned
    across the devices of one mesh axis and the numeric phase runs as a
    single ``shard_map`` call.

    Construction (via ``spgemm_plan(..., mesh=...)``) partitions the
    symbolic schedule at block-row-group boundaries balanced by **triple
    count** (:func:`~repro.core.schedule.partition_spgemm_schedule`), builds
    each shard's own :class:`~repro.core.schedule.AssemblyMap` slice, and
    stages each shard's packed A blocks / schedule / gather map on its own
    device (B replicated). ``execute`` / ``execute_batch`` keep the exact
    single-device semantics — same lock / staged-value / copy-on-stage
    behavior, same structural CSR output sharing the plan-wide
    ``indptr``/``indices`` — because C's per-shard segments are contiguous
    row ranges: the final CSR data is one concatenation along the
    precomputed indptr boundaries.
    """

    def __init__(
        self,
        *,
        mesh: Mesh,
        mesh_axis: Optional[str] = None,
        shards: Optional[List[ScheduleShard]] = None,
        **kw,
    ):
        if mesh_axis is None:
            mesh_axis = mesh.axis_names[0]
        if mesh_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no axis {mesh_axis!r}: {mesh.axis_names}"
            )
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.n_shards = int(mesh.shape[mesh_axis])
        # ``shards`` is the persistence seam: a rehydrated plan passes the
        # deserialized partition here so _make_executor skips the
        # partitioner along with the rest of the symbolic phase.
        self._preloaded_shards = shards
        self._shards: List[ScheduleShard] = []
        self._shard_assemblies: List[AssemblyMap] = []
        self._shard_compacts: List[AssemblyMap] = []
        super().__init__(**kw)

    def _make_executor(self):
        if self._preloaded_shards is not None:
            if len(self._preloaded_shards) != self.n_shards:
                raise ValueError(
                    f"{len(self._preloaded_shards)} persisted shards for "
                    f"a {self.n_shards}-device mesh axis"
                )
            self._shards = self._preloaded_shards
        else:
            self._shards = partition_spgemm_schedule(
                self.schedule, self.n_shards
            )
        bm, bn, g = self._bm, self._bn, self._group
        for sh in self._shards:
            row_lo = min(sh.group_lo * g * bm, self._m)
            row_hi = min(sh.group_hi * g * bm, self._m)
            self._shard_assemblies.append(build_assembly_map(
                sh.schedule, (bm, bn), (row_hi - row_lo, self._n)
            ))
        if sum(a.nnz for a in self._shard_assemblies) != self.assembly.nnz:
            raise AssertionError(
                "shard assembly slices do not cover the plan assembly"
            )
        # Compact output: each shard gathers through its own slice of the
        # element-exact pattern (subset of its block map, rows rebased to
        # the shard). Shard row ranges are contiguous, so the plan-wide
        # compact rows split into per-shard runs by searchsorted; the
        # executor's pad-trim/concat bookkeeping then counts compact nnz.
        active_assemblies = self._shard_assemblies
        if self.output == "compact":
            rows_c = np.repeat(
                np.arange(self._m, dtype=np.int64),
                np.diff(self.compact.indptr),
            )
            self._shard_compacts = []
            for sh, asm in zip(self._shards, self._shard_assemblies):
                row_lo = min(sh.group_lo * g * bm, self._m)
                row_hi = min(sh.group_hi * g * bm, self._m)
                lo, hi = np.searchsorted(rows_c, [row_lo, row_hi])
                self._shard_compacts.append(build_compact_map(
                    asm, rows_c[lo:hi] - row_lo,
                    self.compact.indices[lo:hi],
                ))
            if sum(a.nnz for a in self._shard_compacts) != self.compact.nnz:
                raise AssertionError(
                    "shard compact slices do not cover the compact map"
                )
            active_assemblies = self._shard_compacts
        a_val_bounds = None
        if self._a_scatter is not None:
            # Element values are canonical row-major, and shards own
            # contiguous row ranges: each shard's A values are one slice.
            a_val_bounds = np.concatenate([
                np.searchsorted(
                    self.a_pattern.row,
                    [sh.group_lo * g * bm for sh in self._shards],
                ),
                [self.a_pattern.nnz],
            ]).astype(np.int64)
        return ShardedSpGEMMExecutor(
            shards=self._shards,
            assemblies=active_assemblies,
            mesh=self.mesh,
            axis=self.mesh_axis,
            backend=self.backend,
            a_scatter=self._a_scatter,
            b_scatter=self._b_scatter,
            a_shape=self._a_shape,
            b_shape=self._b_shape,
            a_val_bounds=a_val_bounds,
        )

    # Its executor's run, run_values and run_batch block: H2D, the
    # shard_map program, its wait, and the D2H with the host concat.
    _RUN_SPAN = "spgemm.run"

    def _stage_a(self, blocks: np.ndarray):
        if self._executor is None:  # empty plan: nothing to lay out
            return jnp.array(blocks, copy=True)
        return self._executor.stage_a(blocks)

    def _stage_b(self, blocks: np.ndarray):
        if self._executor is None:
            return jnp.array(blocks, copy=True)
        return self._executor.stage_b(blocks)

    def shard_stats(self) -> dict:
        """Per-shard load profile: triple/panel/nnz counts plus the
        max/mean triple-count imbalance the partitioner achieved."""
        triples = [sh.num_triples for sh in self._shards]
        mean = sum(triples) / max(len(triples), 1)
        return {
            "n_shards": self.n_shards,
            "mesh_axis": self.mesh_axis,
            "triples": triples,
            "panels": [sh.n_panels for sh in self._shards],
            "nnz_c": [a.nnz for a in self._shard_assemblies],
            "imbalance": (max(triples) / mean) if mean else 0.0,
        }

    def host_nbytes(self) -> int:
        return super().host_nbytes() + sum(
            a.nbytes()
            for a in self._shard_assemblies + self._shard_compacts
        )

    def persist_artifacts(self) -> Tuple[dict, dict]:
        """Adds the shard partition to the base artifacts: the group-bound
        vector alone reconstructs every :class:`ScheduleShard` slice
        bitwise (see :func:`repro.core.schedule.shards_from_bounds`), so
        per-shard executors rebuild from deserialized constants without
        re-running the partitioner. Empty plans (no executor, no shards)
        persist without bounds and re-partition trivially on load."""
        arrays, meta = super().persist_artifacts()
        if self._shards:
            arrays["shard_bounds"] = shards_to_bounds(self._shards)
        meta["n_shards"] = self.n_shards
        meta["mesh_axis"] = self.mesh_axis
        return arrays, meta


def _resolve_plan_cls(mesh: Optional[Mesh], mesh_axis: Optional[str]):
    """(plan class, extra ctor kwargs) for an optional mesh."""
    if mesh is None:
        return SpGEMMPlan, {}
    return ShardedSpGEMMPlan, {"mesh": mesh, "mesh_axis": mesh_axis}


def _mesh_key(mesh: Optional[Mesh], mesh_axis: Optional[str]):
    """Cache-key component for the mesh/shard axis: plans stage per-shard
    constants on concrete devices, so the key pins axis name, shard count,
    and device identity. ``None`` for single-device plans keeps every
    pre-mesh cache key shape unchanged."""
    if mesh is None:
        return None
    axis = mesh_axis if mesh_axis is not None else mesh.axis_names[0]
    return (axis, int(mesh.shape[axis]),
            tuple(int(d.id) for d in np.ravel(mesh.devices)))


def _coo_is_canonical(coo: COO) -> bool:
    """True when the COO is in canonical order: strictly increasing
    row-major (row, col) keys — sorted, deduplicated. O(nnz) vectorized,
    far cheaper than the sort ``sum_duplicates`` pays."""
    key = coo.row.astype(np.int64) * int(coo.shape[1]) + coo.col
    return bool(np.all(np.diff(key) > 0))


def _canonical_coo(coo: COO) -> COO:
    """The COO in canonical order, paying the sort only when needed."""
    return coo if _coo_is_canonical(coo) else coo.sum_duplicates()


def _value_dtype(x):
    """The value dtype of any plan input, or ``None`` if unreadable."""
    if x is None:
        return None
    v = getattr(x, "val", None)  # COO/CSR/CSC/CSV
    if v is not None:
        return np.asarray(v).dtype
    blocks = getattr(x, "blocks", None)  # BCSV/BCSR
    if blocks is not None:
        return np.asarray(blocks).dtype
    if isinstance(x, np.ndarray):
        return x.dtype
    return None


def _rebind_span(step: int, operand: str, scatter, shape):
    """``spgemm.rebind``: the host scatter of one operand's values into
    its block array, on element plans (``scatter`` set); nothing on block
    plans, whose packed values are taken as they are."""
    if scatter is None:
        return contextlib.nullcontext()
    return TraceAnnotation(
        "spgemm.rebind", step=step, operand=operand,
        values=int(scatter.shape[0]), slots=int(np.prod(shape)),
    )


def _staged_nnz(plan: "SpGEMMPlan", attr: str, field: str):
    """Lazy element-count resolver reading the plan's staged blocks —
    holds no reference to operand arrays beyond what the plan itself
    stages, so unread reports cannot pin memory past release_values()."""
    def resolve() -> int:
        blocks = getattr(plan, attr)
        if blocks is None:
            raise ValueError(
                f"{field}: plan values were released before the lazy "
                f"report field was read"
            )
        return int(np.count_nonzero(blocks))

    return resolve


def _make_report(
    pattern_key, tile, group, backend, shape, nnz_a, nnz_b, nnzb_a, nnzb_b,
    schedule: SpGEMMSchedule,
) -> PlanReport:
    return PlanReport(
        pattern_key=pattern_key,
        tile=tuple(tile),
        group=group,
        backend=backend,
        shape=shape,
        nnz_a=nnz_a,
        nnz_b=nnz_b,
        nnzb_a=nnzb_a,
        nnzb_b=nnzb_b,
        nnzb_c=schedule.nnzb_c,
        num_triples=schedule.num_triples,
        n_panels=schedule.n_panels,
        b_fetches=schedule.b_fetches(),
        block_omar=schedule.block_omar(),
        # An operator env override beats everything (resolve_chunk_bytes);
        # the report says so up front rather than claiming "default".
        config_source=(
            "env-override" if os.environ.get(CHUNK_BYTES_ENV) else "default"
        ),
    )


def _block_pattern_key(a: BCSV, b: BCSR) -> str:
    return pattern_digest(
        a.brow, a.bcol, a.group_ptr, b.indptr, b.indices,
        meta=("blocks", a.shape, b.shape, a.block_shape, b.block_shape,
              a.group, str(a.blocks.dtype), str(b.blocks.dtype)),
    )


def _normalize_tile(tile: Union[int, Tuple[int, ...]]) -> Tuple[int, int, int]:
    if isinstance(tile, int):
        return (tile, tile, tile)
    tile = tuple(int(t) for t in tile)
    if len(tile) == 2:
        return (tile[0], tile[1], tile[1])
    if len(tile) != 3:
        raise ValueError(f"tile must be int, (bm, bk) or (bm, bk, bn); got {tile}")
    return tile


def _deep_verify(plan) -> None:
    """``validate="deep"``: run the full static verifier on ``plan``.

    Raises :class:`repro.analysis.verify.PlanVerificationError` (an
    ``AssertionError``) when any invariant fails. Called *inside* the
    disk-rehydrate loaders, the raise is swallowed by the cache's loader
    fallback (``load_failures``) and the plan is rebuilt symbolically —
    a corrupted-but-digest-valid artifact fails verification, never
    executes. Called on a fresh build or memory hit, the raise
    propagates to the caller."""
    from repro.analysis.verify import verify_plan

    verify_plan(plan).raise_if_failed()


def _loaded_block_plan(arrays, meta, a, b, *, backend, pattern_key,
                       mesh, mesh_axis, validate=None, output="block"):
    """Block-path disk rehydrate (+ optional deep verification)."""
    plan = SpGEMMPlan.from_artifacts(
        arrays, meta, backend=backend, pattern_key=pattern_key,
        a_blocks=a.blocks, b_blocks=b.blocks,
        mesh=mesh, mesh_axis=mesh_axis, output=output,
    )
    if validate == "deep":
        _deep_verify(plan)
    return plan


def _token_disk_loader(a, b, backend, mesh, mesh_axis, validate=None,
                       output="block"):
    """The loader :meth:`PlanCache.token_disk_get` rehydrates through.

    The whole point of the disk alias is to skip the pattern digest, so
    the loader validates this call's operands against the *persisted*
    meta instead: value dtypes must match exactly (``from_artifacts``
    would silently cast), input types must match the persisted plan kind,
    and ``from_artifacts`` itself re-checks element counts / block
    geometry. Any mismatch raises -> ``load_failures`` -> the caller
    falls back to the digest path, which settles conflicts explicitly.
    """

    def load(key: Tuple, arrays: dict, meta: dict) -> SpGEMMPlan:
        kind = meta.get("kind")
        if kind == "element" and isinstance(a, COO) and isinstance(b, COO):
            if (str(np.asarray(a.val).dtype) != meta["a_dtype"]
                    or str(np.asarray(b.val).dtype) != meta["b_dtype"]):
                raise ValueError("value dtype differs from persisted plan")
            a_c, b_c = _canonical_coo(a), _canonical_coo(b)
            plan = SpGEMMPlan.from_artifacts(
                arrays, meta, backend=backend, pattern_key=key[0],
                a_vals=a_c.val, b_vals=b_c.val,
                a_pattern=a_c, b_pattern=b_c,
                mesh=mesh, mesh_axis=mesh_axis, output=output,
            )
            if validate == "deep":
                _deep_verify(plan)
            return plan
        if kind == "block" and isinstance(a, BCSV) and isinstance(b, BCSR):
            if (str(a.blocks.dtype) != meta["a_dtype"]
                    or str(b.blocks.dtype) != meta["b_dtype"]):
                raise ValueError("block dtype differs from persisted plan")
            plan = SpGEMMPlan.from_artifacts(
                arrays, meta, backend=backend, pattern_key=key[0],
                a_blocks=a.blocks, b_blocks=b.blocks,
                mesh=mesh, mesh_axis=mesh_axis, output=output,
            )
            if validate == "deep":
                _deep_verify(plan)
            return plan
        raise ValueError(
            f"input types {type(a).__name__}/{type(b).__name__} do not "
            f"match persisted plan kind {kind!r}"
        )

    return load


PlanInput = Union[np.ndarray, COO, CSR, BCSV, BCSR]


def spgemm_plan(
    a,
    b,
    *,
    tile: Union[int, Tuple[int, ...]] = 64,
    group: int = 4,
    backend: str = "auto",
    cache: Optional[PlanCache] = None,
    mesh: Optional[Mesh] = None,
    mesh_axis: Optional[str] = None,
    pattern_token: Optional[str] = None,
    autotune: Union[bool, dict, None] = None,
    validate: Optional[str] = None,
    output: str = "block",
) -> SpGEMMPlan:
    """Build — or fetch from the plan cache — an :class:`SpGEMMPlan`.

    ``a``/``b`` may be dense arrays, any element-level sparse format
    (COO/CSR/CSC/CSV), or pre-converted BCSV/BCSR blocks (in which case
    ``tile``/``group`` are taken from the formats themselves). All symbolic
    work happens here, once per distinct
    ``(pattern, tile, group, backend, mesh shard axis)``.

    Pass ``mesh`` (e.g. from :func:`repro.launch.mesh.make_shard_mesh`) to
    get a :class:`ShardedSpGEMMPlan` whose panel schedule is partitioned
    over ``mesh_axis`` (default: the mesh's first axis); ``mesh=None`` is
    the unchanged single-device path. Pass ``cache=PlanCache(...)`` to
    isolate from the process-level cache.

    ``pattern_token`` is the serving warm path's fast key: a caller's
    name for the sparsity pattern (e.g. a model/layer id). On a cache hit
    the token resolves the plan directly — no ``to_coo``
    canonicalization, no pattern digest, which is most of the warm path's
    host cost on large patterns. The token is the caller's *claim* of
    pattern equality: it is validated against the digest whenever both
    are present (the first build, and any later digest-path lookup —
    binding one token to two different patterns/configs raises), and
    echoed in ``report.pattern_token``. On a token hit, values are
    rebound only when ``a``/``b`` are :class:`COO` inputs (canonical
    row-major order is verified in O(nnz) and restored by a sort only
    when an input needs it; an element-count mismatch raises); other
    input types are returned with whatever values the plan has staged —
    serving callers pass fresh values to ``execute``/``submit`` anyway.
    A value-dtype mismatch never hits the token: it falls through to the
    digest path, which raises the token conflict instead of silently
    casting. ``a=None, b=None`` with a token is a pure lookup (raises
    ``KeyError`` on a miss).

    With the disk tier enabled, a token miss with operands in hand also
    consults the store's persisted token-alias index before falling back
    to the digest path: a restarted worker's first ``spgemm_plan`` call
    resolves token -> full key -> disk artifacts without ever paying the
    COO pattern digest (``stats.token_disk_hits``).

    ``autotune=True`` (or a dict of
    :func:`repro.spgemm.autotune.autotune_plan` keyword overrides, e.g.
    ``{"repeats": 5}``) runs the per-pattern config search — or loads
    its persisted result with zero probes — and returns the winning plan
    with its :class:`~repro.spgemm.autotune.TunedConfig` applied.

    ``validate="deep"`` opts this call into full static verification
    (:func:`repro.analysis.verify.verify_plan`): the returned plan —
    fresh build, cache hit, or disk rehydrate — has every schedule,
    assembly, race-freedom, and shard-partition invariant checked, and a
    failure raises :class:`~repro.analysis.verify.PlanVerificationError`.
    Disk rehydrates are verified *inside* the loader, so a
    corrupted-but-digest-valid artifact counts as a ``load_failure`` and
    falls back to a clean symbolic rebuild instead of executing.

    ``output="compact"`` selects the element-exact (nnz-compacted) output
    path: the plan additionally precomputes the compact gather map and
    results store only C's true structural nonzeros — no explicit zero
    block fill. The default ``output="block"`` is bitwise-unchanged from
    the pre-compaction behavior (same keys, same artifacts, same CSR).
    Compact plans live under their own cache keys (the base key suffixed
    ``"compact"``), so both modes of one pattern can be resident at once.
    """
    global _SCHEDULE_BUILDS
    if validate not in (None, "deep"):
        raise ValueError(
            f"validate must be None or 'deep', got {validate!r}"
        )
    if output not in ("block", "compact"):
        raise ValueError(
            f"output must be 'block' or 'compact', got {output!r}"
        )
    if autotune and output != "block":
        raise ValueError(
            "autotune composes with output='block' only: tune the block "
            "plan, then request output='compact' separately (tuned knobs "
            "are output-independent)"
        )
    if autotune:
        from repro.spgemm.autotune import autotune_plan

        spec = dict(autotune) if isinstance(autotune, dict) else {}
        plan = autotune_plan(
            a, b, tile=tile, group=group, backend=backend, cache=cache,
            mesh=mesh, mesh_axis=mesh_axis, pattern_token=pattern_token,
            **spec,
        )
        # The tuned plan is verified post-hoc (the search itself builds
        # candidates through this function without `validate`).
        if validate == "deep":
            _deep_verify(plan)
        return plan
    backend = resolve_backend(backend)
    if cache is None:
        cache = default_cache()
    shard_key = _mesh_key(mesh, mesh_axis)
    # Compact plans get their own keys by suffix; block keys (and thus
    # every pre-compaction persisted artifact) are byte-identical.
    out_key = ("compact",) if output == "compact" else ()

    token_key = None
    if pattern_token is not None:
        token_key = ("token", str(pattern_token), _normalize_tile(tile),
                     int(group), backend, shard_key) + out_key
        plan = cache.token_get(token_key)
        # Value dtype is part of the full (digest) key but not the token
        # key — a dtype mismatch must not be served (and silently cast) by
        # the token hit. Fall through to the digest path instead, where
        # token_bind raises the conflict explicitly.
        if plan is not None:
            dt_a, dt_b = _value_dtype(a), _value_dtype(b)
            if ((dt_a is not None and dt_a != plan._a_dtype)
                    or (dt_b is not None and dt_b != plan._b_dtype)):
                plan = None
        if plan is None and a is not None and b is not None:
            # Warm restart: the in-memory token map is empty but the
            # store's alias index may resolve the token straight to a
            # disk load — no canonicalization or digest unless needed.
            plan, fresh = cache.token_disk_get(
                token_key,
                _token_disk_loader(a, b, backend, mesh, mesh_axis,
                                   validate=validate, output=output),
            )
            if fresh:
                # Values were bound by the loader; nothing to rebind.
                plan.report.pattern_token = str(pattern_token)
                return plan
            if plan is not None:
                dt_a, dt_b = _value_dtype(a), _value_dtype(b)
                if ((dt_a is not None and dt_a != plan._a_dtype)
                        or (dt_b is not None and dt_b != plan._b_dtype)):
                    plan = None
        if plan is not None:
            element = (plan._a_scatter is not None
                       and plan._b_scatter is not None)
            with plan._lock:
                plan.report.cache_hits += 1
                if a is None and b is None:
                    pass  # pure lookup: staged values stay as they are
                elif (element
                        and isinstance(a, COO) and isinstance(b, COO)):
                    # Scatter indices assume canonical row-major order;
                    # verify it (O(nnz)) and pay the canonicalizing sort
                    # only for inputs that need it. An element-count
                    # mismatch means the token named a different pattern
                    # — refuse rather than stage garbage.
                    a_c, b_c = _canonical_coo(a), _canonical_coo(b)
                    if (a_c.nnz != plan.report.nnz_a
                            or b_c.nnz != plan.report.nnz_b):
                        raise ValueError(
                            f"pattern_token {pattern_token!r}: input nnz "
                            f"({a_c.nnz}, {b_c.nnz}) does not match the "
                            f"token's plan ({plan.report.nnz_a}, "
                            f"{plan.report.nnz_b}); the token must name "
                            f"this exact sparsity pattern"
                        )
                    plan._a_blocks = plan._rebind(
                        a_c.val, plan._a_blocks, plan._a_scatter,
                        plan.report.nnz_a, "a_vals", plan._a_shape,
                        plan._a_dtype,
                    )
                    plan._a_dev = None
                    plan._b_blocks = plan._rebind(
                        b_c.val, plan._b_blocks, plan._b_scatter,
                        plan.report.nnz_b, "b_vals", plan._b_shape,
                        plan._b_dtype,
                    )
                    plan._b_dev = None
                elif (not element
                        and isinstance(a, BCSV) and isinstance(b, BCSR)):
                    # Block plans: mirror the digest hit path's rebind of
                    # this call's packed blocks (geometry-checked — a
                    # shape mismatch means the token lied).
                    if (tuple(a.blocks.shape) != plan._a_shape
                            or tuple(b.blocks.shape) != plan._b_shape):
                        raise ValueError(
                            f"pattern_token {pattern_token!r}: packed "
                            f"block shapes {a.blocks.shape}/"
                            f"{b.blocks.shape} do not match the token's "
                            f"plan {plan._a_shape}/{plan._b_shape}"
                        )
                    plan._a_blocks = a.blocks
                    plan._b_blocks = b.blocks
                    plan._a_dev = None
                    plan._b_dev = None
                else:
                    # Any other input type would silently keep the
                    # previous caller's staged values — refuse instead
                    # (the digest path, which converts anything, is one
                    # dropped kwarg away).
                    raise ValueError(
                        f"pattern_token {pattern_token!r}: the token fast "
                        f"path rebinds values only for COO (element "
                        f"plans) or BCSV/BCSR (block plans) inputs, or "
                        f"a=b=None for a pure lookup; got "
                        f"{type(a).__name__}/{type(b).__name__} — drop "
                        f"pattern_token to take the full conversion path"
                    )
            if validate == "deep":
                _deep_verify(plan)
            return plan
        if a is None or b is None:
            raise KeyError(
                f"pattern_token {pattern_token!r} is not resident in the "
                f"plan cache and no operands were given to build from"
            )

    def bind_token(plan: SpGEMMPlan, key: Tuple) -> None:
        if token_key is None:
            return
        cache.token_bind(token_key, key)
        plan.report.pattern_token = str(pattern_token)

    if isinstance(a, BCSV) and isinstance(b, BCSR):
        if a.block_shape[1] != b.block_shape[0]:
            raise ValueError(
                f"block inner dims mismatch: {a.block_shape} vs {b.block_shape}"
            )
        tile3 = (a.block_shape[0], a.block_shape[1], b.block_shape[1])
        key = (_block_pattern_key(a, b), tile3, a.group, backend,
               shard_key) + out_key
        plan, hit = cache.get_or_build(
            key, lambda: SpGEMMPlan.from_blocks(
                a, b, backend=backend, pattern_key=key[0],
                mesh=mesh, mesh_axis=mesh_axis, output=output),
            # Disk tier (warm restart): rehydrate the persisted symbolic
            # artifacts with this call's packed blocks as the values.
            loader=lambda arrays, meta: _loaded_block_plan(
                arrays, meta, a, b, backend=backend, pattern_key=key[0],
                mesh=mesh, mesh_axis=mesh_axis, validate=validate,
                output=output),
        )
        bind_token(plan, key)
        if hit:
            with plan._lock:
                plan.report.cache_hits += 1
                # Pattern-equal but possibly fresh values: rebind this
                # call's packed blocks so execute() without args is current
                # (device staging is lazy — execute pays H2D once).
                plan._a_blocks = a.blocks
                plan._b_blocks = b.blocks
                plan._a_dev = None
                plan._b_dev = None
        if validate == "deep":
            _deep_verify(plan)
        return plan

    bm, bk, bn = _normalize_tile(tile)
    # sum_duplicates already emits canonical row-major order.
    a_coo = to_coo(a).sum_duplicates()
    b_coo = to_coo(b).sum_duplicates()
    if a_coo.shape[1] != b_coo.shape[0]:
        raise ValueError(f"inner dims mismatch: {a_coo.shape} x {b_coo.shape}")
    # Value dtype is part of the key: a float64 request must not be served
    # (and silently downcast) by a float32-built plan.
    pattern = pattern_digest(
        a_coo.row, a_coo.col, b_coo.row, b_coo.col,
        meta=("coo", a_coo.shape, b_coo.shape,
              str(a_coo.val.dtype), str(b_coo.val.dtype)),
    )
    key = (pattern, (bm, bk, bn), group, backend, shard_key) + out_key

    def build() -> SpGEMMPlan:
        global _SCHEDULE_BUILDS
        a_bcsv, a_scatter = bcsv_from_coo(a_coo, (bm, bk), group)
        b_bcsr, b_scatter = bcsr_from_coo(b_coo, (bk, bn))
        schedule = build_spgemm_schedule(a_bcsv, b_bcsr)
        _SCHEDULE_BUILDS += 1
        report = _make_report(
            pattern, (bm, bk, bn), group, backend,
            (a_coo.shape[0], b_coo.shape[1]),
            a_coo.nnz, b_coo.nnz, a_bcsv.nnzb, b_bcsr.nnzb, schedule,
        )
        plan_cls, extra = _resolve_plan_cls(mesh, mesh_axis)
        return plan_cls(
            schedule=schedule,
            a_blocks=a_bcsv.blocks,
            b_blocks=b_bcsr.blocks,
            backend=backend,
            out_shape=(a_coo.shape[0], b_coo.shape[1]),
            report=report,
            a_scatter=a_scatter,
            b_scatter=b_scatter,
            a_pattern=a_coo,
            b_pattern=b_coo,
            output=output,
            **extra,
        )

    def load(arrays: dict, meta: dict) -> SpGEMMPlan:
        # Disk tier (warm restart): the symbolic artifacts come from the
        # store, the values from this call's (already canonicalized) COOs.
        plan = SpGEMMPlan.from_artifacts(
            arrays, meta, backend=backend, pattern_key=pattern,
            a_vals=a_coo.val, b_vals=b_coo.val,
            a_pattern=a_coo, b_pattern=b_coo,
            mesh=mesh, mesh_axis=mesh_axis, output=output,
        )
        if validate == "deep":
            _deep_verify(plan)
        return plan

    plan, hit = cache.get_or_build(key, build, loader=load)
    bind_token(plan, key)
    if hit:
        with plan._lock:
            plan.report.cache_hits += 1
            # A cache hit may carry stale values from the previous caller;
            # the pattern matches by construction, so rebind this call's
            # values (device staging is lazy — execute pays H2D once).
            plan._a_blocks = plan._rebind(
                a_coo.val, plan._a_blocks, plan._a_scatter,
                plan.report.nnz_a, "a_vals", plan._a_shape, plan._a_dtype,
            )
            plan._a_dev = None
            plan._b_blocks = plan._rebind(
                b_coo.val, plan._b_blocks, plan._b_scatter,
                plan.report.nnz_b, "b_vals", plan._b_shape, plan._b_dtype,
            )
            plan._b_dev = None
    if validate == "deep":
        _deep_verify(plan)
    return plan


# ---------------------------------------------------------------------------
# Structural plan composition (the chaining layer)
#
# C's pattern is value-independent, so one plan's output *structure* fully
# determines the next plan's A-side input structure — no values, no COO
# conversion, no canonicalizing sort. These are the pieces that turn
# one-shot SpGEMM into device-resident chains (A @ B @ C, A^k): a plan's
# ``output_pattern()`` feeds ``plan_from_structural_pattern``, and
# ``execute_chain`` hands each stage's packed device values straight to the
# next stage's fused rebind/kernel/assembly jit.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StructuralPattern:
    """A CSR-shaped structural sparsity pattern, detached from any values.

    This is a plan's value-independent output structure
    (:meth:`SpGEMMPlan.output_pattern`) in the exact arrays the plan's
    results share — and the seed :func:`plan_from_structural_pattern`
    builds the next chained plan from. The pattern order (row-major,
    strictly ascending ``(row, col)``) is canonical COO order, which is
    what lets a previous stage's packed values bind positionally as the
    next stage's A values.
    """

    indptr: np.ndarray  # [m + 1] CSR row pointers
    indices: np.ndarray  # [nnz] int32 CSR column ids
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def rows(self) -> np.ndarray:
        """The expanded per-element row ids (canonical order)."""
        return np.repeat(
            np.arange(self.shape[0], dtype=np.int64), np.diff(self.indptr)
        )

    def to_coo(self, val=None, dtype=np.float32) -> COO:
        """The pattern as canonical COO; ``val=None`` fills placeholder
        zeros (chained plans bind real values per execute)."""
        if val is None:
            val = np.zeros(self.nnz, dtype)
        return COO(self.rows(), self.indices, val, self.shape)


def _check_chain_link(p: SpGEMMPlan, q: SpGEMMPlan, stage: int) -> None:
    """Stage ``stage + 1``'s A pattern must be stage ``stage``'s output
    pattern, elementwise — the positional-binding contract of
    :func:`execute_chain`."""
    if q._a_scatter is None or q._b_scatter is None:
        raise ValueError(
            f"chain stage {stage + 1} is not an element plan; chained "
            f"stages are built by plan_from_structural_pattern"
        )
    asm = p._active()
    pat = q.a_pattern
    if pat is None or tuple(pat.shape) != (p._m, p._n):
        got = None if pat is None else tuple(pat.shape)
        raise ValueError(
            f"chain stage {stage + 1}: A shape {got} != stage {stage} "
            f"output shape {(p._m, p._n)}"
        )
    if q.report.nnz_a != asm.nnz or not (
        np.array_equal(pat.col, asm.indices)
        and np.array_equal(
            np.bincount(pat.row, minlength=p._m), np.diff(asm.indptr)
        )
    ):
        raise ValueError(
            f"chain stage {stage + 1}: A pattern does not match stage "
            f"{stage}'s output pattern; build it from that plan's "
            f"output_pattern() (plan.then / plan_from_structural_pattern)"
        )


class SpGEMMChain:
    """An ordered composition of plans: ``A @ B1 @ B2 @ ...`` where stage
    ``s + 1``'s A pattern *is* stage ``s``'s structural output pattern
    (validated at construction). :meth:`execute` runs the whole chain with
    every intermediate staying device-resident — the only D2H transfer is
    the final result (single-device plans; sharded stages concatenate
    per-shard segments on host by design)."""

    def __init__(self, plans: Sequence[SpGEMMPlan]):
        plans = list(plans)
        if not plans:
            raise ValueError("a chain needs at least one plan")
        for s, (p, q) in enumerate(zip(plans, plans[1:])):
            _check_chain_link(p, q, s)
        self.plans = plans

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.plans[0]._m, self.plans[-1]._n)

    def then(self, b, **kwargs) -> "SpGEMMChain":
        """Extend the chain by one more operand (see
        :meth:`SpGEMMPlan.then`)."""
        return SpGEMMChain(
            self.plans + [self.plans[-1]._plan_next(b, **kwargs)]
        )

    def output_pattern(self) -> StructuralPattern:
        return self.plans[-1].output_pattern()

    def device_indptr(self):
        return self.plans[-1].device_indptr()

    def execute(self, a_vals=None, b_vals=None) -> CSR:
        """Run the chain; ``a_vals``/``b_vals`` are stage 1's operands
        (same contract as :meth:`SpGEMMPlan.execute`), later stages use
        their own staged B values."""
        return execute_chain(self.plans, a_vals=a_vals, b_vals=b_vals)

    __call__ = execute


def chain_plans(plans: Sequence[SpGEMMPlan]) -> SpGEMMChain:
    """Validate and wrap an ordered plan list as a :class:`SpGEMMChain`
    (each plan's A pattern must be its predecessor's output pattern)."""
    return SpGEMMChain(plans)


def execute_chain(plans, a_vals=None, b_vals=None) -> CSR:
    """Run ``A @ B1 @ B2 @ ...`` through a validated plan chain with
    intermediates device-resident.

    Stage 1 dispatches exactly like ``plans[0].execute`` but keeps its
    packed C values on device; every later stage consumes the previous
    packed values directly as its A values (active-map order is canonical
    element order, so the binding is positional) against its own staged B
    values — no intermediate CSR wrap, no host transfer, no re-staging.
    The final stage's values are materialized once and wrapped in its
    precomputed CSR structure. Bitwise-equal to executing each stage
    independently with a host round trip between them (same jits, same
    operand bits).

    ``plans`` is a :class:`SpGEMMChain` or a plan sequence (validated
    here when raw); ``a_vals``/``b_vals`` optionally rebind stage 1's
    operands.
    """
    if isinstance(plans, SpGEMMChain):
        plans = plans.plans
    else:
        plans = list(plans)
        if not plans:
            raise ValueError("a chain needs at least one plan")
        for s, (p, q) in enumerate(zip(plans, plans[1:])):
            _check_chain_link(p, q, s)
    packed, _ = plans[0]._run_packed(a_vals, b_vals)
    for stage in plans[1:]:
        packed = stage._run_packed_chained(packed)
    last = plans[-1]
    if packed is None:
        return last._empty_csr()
    return last._wrap_packed(np.asarray(packed))


def plan_from_structural_pattern(
    c_pattern: StructuralPattern,
    b,
    *,
    tile: Union[int, Tuple[int, ...]] = 64,
    group: int = 4,
    backend: str = "auto",
    cache: Optional[PlanCache] = None,
    mesh: Optional[Mesh] = None,
    mesh_axis: Optional[str] = None,
    output: str = "block",
    validate: Optional[str] = None,
    dtype=np.float32,
) -> SpGEMMPlan:
    """Plan ``C @ b`` directly from a prior plan's structural output
    pattern — the chaining fast path.

    Where :func:`spgemm_plan` would convert C to COO and pay
    ``sum_duplicates``'s canonicalizing sort plus a digest over expanded
    row/col arrays, this builds the A-side COO *positionally* from the
    CSR pattern (already canonical by construction) and fingerprints the
    CSR arrays themselves. A values are zero placeholders — chained
    executes bind the previous stage's packed device values per run;
    ``dtype`` fixes the value dtype those stages flow at (it is part of
    the cache key, like every plan's value dtype).

    Chained plans get their own cache keys (a ``"chain"``-tagged digest)
    and the same two-tier :class:`~repro.spgemm.cache.PlanCache`
    persistence as any other plan — a warm restart rehydrates the whole
    chain from disk without re-running any symbolic phase.
    """
    backend = resolve_backend(backend)
    if validate not in (None, "deep"):
        raise ValueError(
            f"validate must be None or 'deep', got {validate!r}"
        )
    if output not in ("block", "compact"):
        raise ValueError(
            f"output must be 'block' or 'compact', got {output!r}"
        )
    if cache is None:
        cache = default_cache()
    bm, bk, bn = _normalize_tile(tile)
    b_coo = _canonical_coo(to_coo(b))
    if c_pattern.shape[1] != b_coo.shape[0]:
        raise ValueError(
            f"inner dims mismatch: {c_pattern.shape} x {b_coo.shape}"
        )
    a_coo = c_pattern.to_coo(dtype=dtype)
    shard_key = _mesh_key(mesh, mesh_axis)
    out_key = ("compact",) if output == "compact" else ()
    pattern = pattern_digest(
        c_pattern.indptr, c_pattern.indices, b_coo.row, b_coo.col,
        meta=("chain", c_pattern.shape, b_coo.shape,
              str(np.dtype(dtype)), str(b_coo.val.dtype)),
    )
    key = (pattern, (bm, bk, bn), group, backend, shard_key) + out_key
    with cache._lock:
        cache.stats.chain_lookups += 1

    def build() -> SpGEMMPlan:
        global _SCHEDULE_BUILDS
        a_bcsv, a_scatter = bcsv_from_coo(a_coo, (bm, bk), group)
        b_bcsr, b_scatter = bcsr_from_coo(b_coo, (bk, bn))
        schedule = build_spgemm_schedule(a_bcsv, b_bcsr)
        _SCHEDULE_BUILDS += 1
        report = _make_report(
            pattern, (bm, bk, bn), group, backend,
            (c_pattern.shape[0], b_coo.shape[1]),
            a_coo.nnz, b_coo.nnz, a_bcsv.nnzb, b_bcsr.nnzb, schedule,
        )
        plan_cls, extra = _resolve_plan_cls(mesh, mesh_axis)
        return plan_cls(
            schedule=schedule,
            a_blocks=a_bcsv.blocks,
            b_blocks=b_bcsr.blocks,
            backend=backend,
            out_shape=(c_pattern.shape[0], b_coo.shape[1]),
            report=report,
            a_scatter=a_scatter,
            b_scatter=b_scatter,
            a_pattern=a_coo,
            b_pattern=b_coo,
            output=output,
            **extra,
        )

    def load(arrays: dict, meta: dict) -> SpGEMMPlan:
        plan = SpGEMMPlan.from_artifacts(
            arrays, meta, backend=backend, pattern_key=pattern,
            a_vals=a_coo.val, b_vals=b_coo.val,
            a_pattern=a_coo, b_pattern=b_coo,
            mesh=mesh, mesh_axis=mesh_axis, output=output,
        )
        if validate == "deep":
            _deep_verify(plan)
        return plan

    plan, hit = cache.get_or_build(key, build, loader=load)
    if hit:
        with plan._lock:
            plan.report.cache_hits += 1
            # Pattern-equal hit serving a possibly different B operand:
            # rebind this call's B values (blocks + the chained-stage
            # device copy) so both standalone and chained executes see
            # them. A-side placeholders are untouched — chain runs bind A
            # per execute, on device.
            plan._b_blocks = plan._rebind(
                b_coo.val, plan._b_blocks, plan._b_scatter,
                plan.report.nnz_b, "b_vals", plan._b_shape, plan._b_dtype,
            )
            plan._b_dev = None
            plan._b_vals_dev = None
            plan.b_pattern = b_coo
    if validate == "deep":
        _deep_verify(plan)
    return plan
