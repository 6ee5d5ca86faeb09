"""Clean twin of fusedbfs_bad — a fused hop geometry that fits
(B=8 rows × 128 lanes, D*W=64-row DMA scratch, chunk plan inside
half the SMEM budget). Zero findings allowed."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hop_kernel(blk_off, chunk_rows, idx, visited, vis_blk, out_ref,
                rows, sems):
    out_ref[...] = vis_blk[...]


def fused_hop_in_budget(visited):
    # chunk plan: 16K chunks × (8 idx + 1 row) int32 = 578 KB of the
    # 1 MB SMEM; windows: 2×2×(8,128) u32 tiles + (64,128) scratch =
    # 48 KB of the 16 MiB VMEM — the committed real-kernel geometry
    blk_off = jnp.zeros((257,), jnp.int32)
    chunk_rows = jnp.zeros((1 << 14,), jnp.int32)
    idx = jnp.zeros((1 << 17,), jnp.int32)
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
