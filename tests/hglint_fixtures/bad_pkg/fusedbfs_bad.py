"""Seeded HG501 + HG503 hazards shaped like a fused pull-BFS hop
kernel (one ``pallas_call`` a hop): the scalar-prefetched chunk plan
overflowing SMEM, and DMA row scratch + double-buffered visited windows
overflowing VMEM — window math that such a kernel has to guard at
runtime, and that Mosaic refuses on hardware only."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hop_kernel(blk_off, chunk_rows, idx, visited, vis_blk, out_ref,
                rows, sems):
    out_ref[...] = vis_blk[...]


def fused_hop_smem_overflow(visited):
    # HG503: the fused chunk plan — (1 << 17,) chunk_rows + (1 << 20,)
    # idx int32 — is 4.5 MB of scalar prefetch against the 1 MB SMEM;
    # Mosaic allocation dies on hardware only
    blk_off = jnp.zeros((257,), jnp.int32)
    chunk_rows = jnp.zeros((1 << 17,), jnp.int32)
    idx = jnp.zeros((1 << 20,), jnp.int32)
    return pl.pallas_call(
        functools.partial(_hop_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(256,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((8, 128), lambda i, s0, s1, s2: (i, 0)),
            ],
            out_specs=pl.BlockSpec((8, 128), lambda i, s0, s1, s2: (i, 0)),
            scratch_shapes=[pltpu.VMEM((64, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA((8,))],
        ),
        out_shape=jax.ShapeDtypeStruct((2048, 128), jnp.uint32),
    )(blk_off, chunk_rows, idx, visited, visited[:2048])


def fused_hop_vmem_overflow(visited):
    # HG501: a 16K-lane visited row blows the window model — the
    # double-buffered (8, 16384) uint32 in/out blocks plus the
    # (64, 16384) DMA row scratch total ~6 MiB... widened further by a
    # (2048, 16384) scratch that alone is 128 MiB
    blk_off = jnp.zeros((257,), jnp.int32)
    return pl.pallas_call(
        functools.partial(_hop_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(256,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((8, 16384), lambda i, s0: (i, 0)),
            ],
            out_specs=pl.BlockSpec((8, 16384), lambda i, s0: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2048, 16384), jnp.uint32),
                            pltpu.SemaphoreType.DMA((8,))],
        ),
        out_shape=jax.ShapeDtypeStruct((2048, 16384), jnp.uint32),
    )(blk_off, visited, visited[:2048])
