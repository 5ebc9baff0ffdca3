"""EmbeddingBag Pallas kernel (FBGEMM-TBE pattern, TPU-adapted).

The table stays in HBM (memory_space=ANY); bag indices arrive via scalar
prefetch (PrefetchScalarGridSpec) so row DMAs can be issued from the scalar
core. Grid: (bag_blocks, dim_blocks); each program accumulates its bag
block's L rows into a VMEM tile with a fori_loop of dynamic row loads.

This is the hot path of every recsys arch in the pool: a gather +
segment-sum whose arithmetic intensity is ~0 — the kernel's job is purely
to keep the row DMAs streaming.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, table_ref, out_ref, *, bag_len: int,
            block_b: int):
    b0 = pl.program_id(0) * block_b

    def body(i, acc):
        bag, slot = i // bag_len, i % bag_len
        row = idx_ref[b0 + bag, slot]
        valid = row >= 0
        safe = jnp.maximum(row, 0)
        vec = table_ref[pl.ds(safe, 1), :][0]
        w = jnp.where(valid, w_ref[b0 + bag, slot], 0.0)
        return acc.at[bag].add(vec * w)

    acc = jnp.zeros_like(out_ref)
    acc = jax.lax.fori_loop(0, block_b * bag_len, body, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def embedding_bag_pallas(table: jax.Array, indices: jax.Array,
                         weights: jax.Array, *, block_b: int = 8,
                         interpret: bool = False) -> jax.Array:
    B, Lb = indices.shape
    R, d = table.shape
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, bag_len=Lb, block_b=block_b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,           # indices, weights
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # table in HBM
            out_specs=pl.BlockSpec((block_b, d), lambda i, idx, w: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, d), table.dtype),
        interpret=interpret,
    )(indices, weights, table)
