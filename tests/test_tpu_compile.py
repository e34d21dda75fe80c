"""Compile the SpGEMM Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, which refuses what interpret mode cannot see
(tiling misalignment, VMEM/SMEM overruns, programs that do not fit HBM).
Shapes are the smoke workload's: 2cubes_sphere C = A @ A^T at its
published size, tile 128, group 4; and the benchmark's: PETSc ex56 at
``-ne 32`` squared, whose schedule one call's SMEM cannot hold, and
hpcg40-A2, whose schedule one call holds.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so the worker that
runs this file loads it and every other worker collects the same tests.
The persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.perfmodel import (
    SCHEDULE_TRIPLES_PER_CALL,
    assembly_items_per_call,
)
from repro.kernels.assemble_runs import BLOCK_ROWS, assemble_runs_impl
from repro.kernels.gustavson_spgemm import (
    spgemm_scheduled_batch_impl,
    spgemm_scheduled_impl,
)
from repro.spgemm.executor import (
    numeric_core,
    numeric_core_values,
    shard_program,
)

# 2cubes_sphere A @ A^T, scale=1.0, tile 128, group 4 (plan report).
T, NNZB, N_PANELS, TILE, GROUP = 39_841, 5_601, 3_189, 128, 4
NNZ_A, NNZ_C = 1_830_000, 25_100_000
# Per-shard sizes of its 4-shard plan, about a quarter of each total with
# headroom for the partitioner's imbalance.
T_MAX, P_MAX, A_MAX, C_MAX, E_MAX = 12_000, 1_000, 1_700, 7_500_000, 560_000
HBM_BYTES = 16e9
F32, I32 = jnp.float32, jnp.int32
# ex56-ne32-A2 (bench/configs): 107,811^2, its plan at tile 128, group 4.
EX56 = dict(t=96_945, nnzb=8_997, n_panels=9_692, nnz=8_214_057,
            nnz_c=36_177_111)
# hpcg40-A2 (bench/configs): 64,000^2, the same plan settings.
HPCG40 = dict(t=23_386, nnzb=3_410, n_panels=4_108, nnz=1_643_032,
              nnz_c=7_301_384)
# Their run tables (build_assembly_runs of each C pattern at tile 128,
# group 4): runs a block, padded, and items (grid steps).
EX56.update(width=3_072, items=14_270)
HPCG40.update(width=7_168, items=5_637)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies

        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("shard",))


@pytest.fixture(scope="module", autouse=False)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    """The kernel is in the program, and the program fits one chip."""
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES


def _slices(sharding, *lengths):
    """The schedule as the executor stages it: five int32 arrays a call."""
    return tuple((_sds((n,), I32, sharding),) * 5 for n in lengths)


def test_single_kernel_compiles(one_chip, no_compile_cache):
    fn = functools.partial(spgemm_scheduled_impl, n_panels=N_PANELS,
                           group=GROUP, interpret=False)
    blocks = _sds((NNZB, TILE, TILE), F32, one_chip)
    _check(jax.jit(fn).lower(blocks, blocks, _slices(one_chip, T)).compile())


def test_batch_kernel_compiles(one_chip, no_compile_cache):
    bsz = 4
    fn = functools.partial(spgemm_scheduled_batch_impl, bsz=bsz,
                           n_panels=N_PANELS, group=GROUP, interpret=False)
    blocks = _sds((bsz * NNZB, TILE, TILE), F32, one_chip)
    _check(jax.jit(fn).lower(blocks, blocks, _slices(one_chip, T)).compile())


def _fused(sharding, shapes, *lengths):
    """``numeric_core_values`` (the rebind + kernel + compact-assembly jit
    ``execute(a, b)`` runs) lowered for ``shapes``, its schedule in calls
    of ``lengths`` triples; the bind's scatter maps are ``[nnz]``, as the
    plan passes them."""
    nnz, shape = shapes["nnz"], (shapes["nnzb"], TILE, TILE)
    return numeric_core_values.lower(
        _sds((nnz,), F32, sharding), _sds((nnz,), F32, sharding),
        _sds((nnz,), I32, sharding), _sds((nnz,), I32, sharding),
        _slices(sharding, *lengths), _sds((shapes["nnz_c"],), I32, sharding),
        a_shape=shape, b_shape=shape, n_panels=shapes["n_panels"],
        group=GROUP, backend="pallas", interpret=False,
    )


def test_fused_execute_program_compiles(one_chip, no_compile_cache):
    _check(_fused(one_chip, dict(t=T, nnzb=NNZB, n_panels=N_PANELS,
                                 nnz=NNZ_A, nnz_c=NNZ_C), T).compile())


# ex56's schedule in the calls the executor cuts it into at the budget
# (schedule_cuts of the plan's schedule: [0, 49144, 96945]).
EX56_CALLS = (49_144, 47_801)


def test_split_fused_program_compiles_and_fits(one_chip, no_compile_cache):
    """ex56-ne32-A2's single path: the schedule in two calls, each within
    one call's SMEM, and the whole program within 16 GB."""
    compiled = _fused(one_chip, EX56, *EX56_CALLS).compile()
    _check(compiled)
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_split_stream_kernel_compiles_and_fits(one_chip, no_compile_cache):
    """ex56-ne32-A2's stream path: the kernel and the assembly over the
    bound blocks (``numeric_core``), two calls into one panel array."""
    shape = (EX56["nnzb"], TILE, TILE)
    compiled = numeric_core.lower(
        _sds(shape, F32, one_chip), _sds(shape, F32, one_chip),
        _slices(one_chip, *EX56_CALLS), _sds((EX56["nnz_c"],), I32, one_chip),
        n_panels=EX56["n_panels"], group=GROUP, backend="pallas",
        interpret=False,
    ).compile()
    _check(compiled)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # The second call writes into the first call's buffer in place.
    assert "output_to_operand_aliasing" in text


def test_whole_ex56_schedule_in_one_call_is_refused(one_chip,
                                                    no_compile_cache):
    """Unsplit, ex56-ne32-A2's 96,945 triples do not fit one call's SMEM."""
    with pytest.raises(Exception, match="Ran out of memory in memory space smem"):
        _fused(one_chip, EX56, EX56["t"]).compile()


def test_hpcg40_program_holds_one_kernel_call(one_chip, no_compile_cache):
    """hpcg40-A2's schedule is within the budget: one call, as before."""
    assert HPCG40["t"] <= SCHEDULE_TRIPLES_PER_CALL
    assert max(EX56_CALLS) <= SCHEDULE_TRIPLES_PER_CALL < EX56["t"]
    text = _fused(one_chip, HPCG40, HPCG40["t"]).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def _shard_args(kind, mesh):
    sep, rep = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
    sched = ((_sds((4, T_MAX), I32, sep),) * 5,)
    if kind == "kernel":
        return (_sds((4, A_MAX, TILE, TILE), F32, sep),
                _sds((NNZB, TILE, TILE), F32, rep), sched,
                _sds((4, C_MAX), I32, sep))
    return (_sds((4, E_MAX), F32, sep), _sds((NNZ_A,), F32, rep),
            _sds((4, E_MAX), I32, sep), _sds((NNZ_A,), I32, rep), sched,
            _sds((4, C_MAX), I32, sep))


@pytest.mark.parametrize("kind", ["kernel", "run_values"])
def test_per_shard_program_compiles(kind, mesh4, no_compile_cache):
    """Each device of a 4-chip mesh runs its own Pallas grid in the
    sharded plan's ``shard_map`` programs."""
    fn = shard_program(
        kind, mesh=mesh4, axis="shard", backend="pallas", interpret=False,
        group=GROUP, a_max=A_MAX, p_max=P_MAX,
        a_shape=(NNZB, TILE, TILE), b_shape=(NNZB, TILE, TILE),
    )
    _check(fn.lower(*_shard_args(kind, mesh4)).compile())


def _scopes(compiled):
    """The stage scopes named in the compiled program's op_name metadata."""
    text = compiled.as_text()
    return {s for s in ("spgemm.bind", "spgemm.kernel", "spgemm.assemble")
            if f"/{s}/" in text}


@pytest.mark.parametrize("program", ["fused", "sharded"])
def test_compiled_programs_keep_the_stage_scopes(program, one_chip, mesh4,
                                                 no_compile_cache):
    """The chip's compiler keeps the executor's named scopes in the HLO
    ``op_name`` metadata, where the profiler reads each device op's stage
    (``bench/spans.py``). Small shapes: only the metadata is checked."""
    t, nnzb, nnz, nnz_c = 64, 8, 1_000, 4_000
    shape = (nnzb, TILE, TILE)
    if program == "fused":
        compiled = numeric_core_values.lower(
            _sds((nnz,), F32, one_chip), _sds((nnz,), F32, one_chip),
            _sds((nnz,), I32, one_chip), _sds((nnz,), I32, one_chip),
            _slices(one_chip, t), _sds((nnz_c,), I32, one_chip),
            a_shape=shape, b_shape=shape, n_panels=4, group=GROUP,
            backend="pallas", interpret=False,
        ).compile()
    else:
        sep, rep = NamedSharding(mesh4, P("shard")), NamedSharding(mesh4, P())
        fn = shard_program(
            "run_values", mesh=mesh4, axis="shard", backend="pallas",
            interpret=False, group=GROUP, a_max=nnzb, p_max=4,
            a_shape=shape, b_shape=shape,
        )
        compiled = fn.lower(
            _sds((4, nnz), F32, sep), _sds((nnz,), F32, rep),
            _sds((4, nnz), I32, sep), _sds((nnz,), I32, rep),
            ((_sds((4, t), I32, sep),) * 5,), _sds((4, nnz_c), I32, sep),
        ).compile()
    assert _scopes(compiled) == {"spgemm.bind", "spgemm.kernel",
                                 "spgemm.assemble"}


def _run_assembly(sharding, shapes, width, *items):
    """The run-wise assembly of ``shapes``' panels, its items in calls of
    ``items`` each."""
    blocks = -(-shapes["nnz_c"] // (BLOCK_ROWS * 128))
    fn = functools.partial(assemble_runs_impl, bsz=1, nnz=shapes["nnz_c"],
                           interpret=False)
    return jax.jit(fn).lower(
        _sds((shapes["n_panels"], GROUP * TILE, TILE), F32, sharding),
        _sds((blocks * 2 * width,), I32, sharding),
        tuple((_sds((n,), I32, sharding),) * 3 for n in items))


@pytest.mark.parametrize("shapes", [HPCG40, EX56], ids=["hpcg40", "ex56"])
def test_run_assembly_compiles(shapes, one_chip, no_compile_cache):
    """The run-wise assembly at the benchmark's shapes: one call."""
    _check(_run_assembly(one_chip, shapes, shapes["width"],
                         shapes["items"]).compile())


def test_run_assembly_smem_budget(one_chip, no_compile_cache):
    """At the widest run table (a block of one-value runs) a call of the
    budget's items fits SMEM, and one of 6,000 more does not."""
    width = BLOCK_ROWS * 128
    budget = assembly_items_per_call(width)
    _check(_run_assembly(one_chip, EX56, width, budget).compile())
    with pytest.raises(Exception, match="Ran out of memory in memory space smem"):
        _run_assembly(one_chip, EX56, width, budget + 6_000).compile()


def test_stream_program_with_run_assembly_fits(one_chip, no_compile_cache):
    """ex56-ne32-A2's stream program as it runs now: two kernel calls into
    one panel array, then the run-wise assembly's call, within 16 GB."""
    shape = (EX56["nnzb"], TILE, TILE)
    blocks = -(-EX56["nnz_c"] // (BLOCK_ROWS * 128))
    compiled = numeric_core.lower(
        _sds(shape, F32, one_chip), _sds(shape, F32, one_chip),
        _slices(one_chip, *EX56_CALLS),
        (_sds((blocks * 2 * EX56["width"],), I32, one_chip),
         ((_sds((EX56["items"],), I32, one_chip),) * 3,)),
        n_panels=EX56["n_panels"], group=GROUP, backend="pallas",
        interpret=False,
        run_nnz=EX56["nnz_c"],
    ).compile()
    _check(compiled)
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3
