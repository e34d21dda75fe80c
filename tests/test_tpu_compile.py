"""Compile the SpGEMM Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, which refuses what interpret mode cannot see
(tiling misalignment, VMEM/SMEM overruns, programs that do not fit HBM).
Shapes are the smoke workload's: 2cubes_sphere C = A @ A^T at its
published size, tile 128, group 4.

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

from repro.kernels.gustavson_spgemm import (
    spgemm_scheduled_batch_impl,
    spgemm_scheduled_impl,
)
from repro.spgemm.executor import numeric_core_values, shard_program

# 2cubes_sphere A @ A^T, scale=1.0, tile 128, group 4 (plan report).
T, NNZB, N_PANELS, TILE, GROUP = 39_841, 5_601, 3_189, 128, 4
NNZ_A, NNZ_C = 1_830_000, 25_100_000
# Per-shard sizes of its 4-shard plan, about a quarter of each total with
# headroom for the partitioner's imbalance.
T_MAX, P_MAX, A_MAX, C_MAX, E_MAX = 12_000, 1_000, 1_700, 7_500_000, 560_000
HBM_BYTES = 16e9
F32, I32 = jnp.float32, jnp.int32


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


def test_single_kernel_compiles(one_chip, no_compile_cache):
    fn = functools.partial(spgemm_scheduled_impl, n_panels=N_PANELS,
                           group=GROUP, interpret=False)
    sched = [_sds((T,), I32, one_chip)] * 5
    blocks = _sds((NNZB, TILE, TILE), F32, one_chip)
    _check(jax.jit(fn).lower(blocks, blocks, *sched).compile())


def test_batch_kernel_compiles(one_chip, no_compile_cache):
    bsz = 4
    fn = functools.partial(spgemm_scheduled_batch_impl, bsz=bsz,
                           n_panels=N_PANELS, group=GROUP, interpret=False)
    sched = [_sds((T,), I32, one_chip)] * 5
    blocks = _sds((bsz * NNZB, TILE, TILE), F32, one_chip)
    _check(jax.jit(fn).lower(blocks, blocks, *sched).compile())


def test_fused_execute_program_compiles(one_chip, no_compile_cache):
    """The rebind + kernel + compact-assembly jit ``execute(a, b)`` runs;
    the bind's scatter maps are ``[nnz]``, as the plan passes them."""
    shape = (NNZB, TILE, TILE)
    _check(numeric_core_values.lower(
        _sds((NNZ_A,), F32, one_chip), _sds((NNZ_A,), F32, one_chip),
        _sds((NNZ_A,), I32, one_chip), _sds((NNZ_A,), I32, one_chip),
        [_sds((T,), I32, one_chip)] * 5, _sds((NNZ_C,), I32, one_chip),
        a_shape=shape, b_shape=shape, n_panels=N_PANELS, group=GROUP,
        backend="pallas", interpret=False,
    ).compile())


def _shard_args(kind, mesh):
    sep, rep = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
    sched = [_sds((4, T_MAX), I32, sep)] * 5
    if kind == "kernel":
        return (_sds((4, A_MAX, TILE, TILE), F32, sep),
                _sds((NNZB, TILE, TILE), F32, rep), *sched)
    return (_sds((4, E_MAX), F32, sep), _sds((NNZ_A,), F32, rep),
            _sds((4, E_MAX), I32, sep), _sds((NNZ_A,), I32, rep), *sched,
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
            [_sds((t,), I32, one_chip)] * 5, _sds((nnz_c,), I32, one_chip),
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
            *[_sds((4, t), I32, sep)] * 5, _sds((4, nnz_c), I32, sep),
        ).compile()
    assert _scopes(compiled) == {"spgemm.bind", "spgemm.kernel",
                                 "spgemm.assemble"}
