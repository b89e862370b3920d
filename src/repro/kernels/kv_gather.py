"""Pallas TPU KV chunk gather — ObjectCache server-side aggregation, on chip.

The paper's storage server assembles one layer-major payload from the layer-l
slices of N matched chunks (Table A3).  Once payloads land in the device's
paged chunk arena, attention wants them *contiguous*.  This kernel is that
last hop of the aggregation pipeline, adapted to the TPU memory hierarchy:
a scalar-prefetched index vector drives the BlockSpec index_map, so each grid
step DMAs one [G, W] chunk tile HBM -> VMEM -> its slot in the contiguous
layer buffer.  No gather materialises twice, and the index arithmetic happens
in SMEM before the DMA engine needs it (the TPU analogue of the paper's
"deliver in the order the GPU consumes").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, pool_ref, out_ref):
    # The index indirection is entirely inside the BlockSpec index_map; the
    # body is a straight VMEM copy.
    out_ref[...] = pool_ref[...]


def kv_gather(pool, indices, *, interpret: bool = False) -> jnp.ndarray:
    """pool: [P, G, W] paged chunk arena; indices: [N] -> [N, G, W].

    W is the collapsed 2*n_kv*head_dim payload width of one token row
    (KV_L2TD layout keeps it contiguous already — Eq. 1's S over G rows)."""
    P, G, W = pool.shape
    N = indices.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, G, W), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, W), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, G, W), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(indices, pool)
