"""Grouped (expert-parallel) matmul Pallas kernel for MoE dispatch.

DESIGN.md Sec. 3: sorting tokens by expert *is* the paper's CSV vector-major
pre-processing — the expert axis is the "vector" axis, and the per-expert
weight tile plays the role of the buffered B row shared by all tokens of the
group (Sec. 4.1 buffering scheme). The host (ops.py) sorts token indices by
expert and pads each group to a tile multiple so every token tile belongs to
exactly one expert; ``tile_expert`` is the scalar-prefetched schedule.

Grid = (token_tiles, f_tiles, d_tiles); the expert weight block
W[tile_expert[i], k-block, j-block] is revisited across consecutive token
tiles of the same expert (VMEM reuse = OMAR at expert granularity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["moe_gmm"]


def _kernel(tile_expert_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tm", "bd", "bf", "out_dtype", "interpret")
)
def moe_gmm(
    x: jax.Array,  # [T, D] tokens sorted by expert, T % tm == 0
    w: jax.Array,  # [E, D, F] expert weights
    tile_expert: jax.Array,  # [T // tm] int32 expert of each token tile
    *,
    tm: int = 128,
    bd: int = 128,
    bf: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    t, d = x.shape
    e, d2, f = w.shape
    assert d == d2 and t % tm == 0 and d % bd == 0 and f % bf == 0
    grid = (t // tm, f // bf, d // bd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, bd), lambda i, j, k, te: (i, k)),
            pl.BlockSpec((1, bd, bf), lambda i, j, k, te: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((tm, bf), lambda i, j, k, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, bf), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, f), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
    )(tile_expert, x, w)
