"""Index-fused gradient-ranking Pallas kernel (indices in, keys out),
wide-block edition.

The pre-gathered ``neighbor_rank`` kernel needs a (Q, B, D) fp32 neighbor
block staged through HBM before it runs. This variant takes the resident
corpus plus the (Q, B) neighbor-id table and performs the row gather
*inside* the kernel — and instead of the original one-(q, b)-pair-per-step
BlockSpec gather, each grid step now DMAs a tile of ``bt`` neighbor rows
of the store's kernel payload (``CorpusStore.kernel_rows``: float32 rows
padded to 128 lanes) into a double-buffered (2, bt, Dp) VMEM scratch
(``kernels/dma.py``). The
(q, neighbor-tile) grid is linearized to 1-D so the double-buffer schedule
is uniform: step ``t`` covers lane ``t // tiles_per_q``'s neighbors
``[bt·(t % tiles_per_q), ...)``, and step ``t+1``'s row copies (which may
cross a lane boundary — the flat id vector doesn't care) are issued before
step ``t``'s compute, hiding the gather behind the (bt, D) rank math.
``bt`` comes from the autotune cache; B is padded up to a multiple.

Per tile: cosine (or projection) of x' − x against ∂f/∂x, a (bt, 1) raw
score column out. The angle, the row-wise best θ and the α·θ band are
O(Q·B) with no D dimension — ops.py takes them on the kernel output
(``neighbor_rank.ref.keys_from_raw``, shared with the ref; Mosaic lowers
no ``acos``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma import RowGather, gather_scratch


def _rank_tile(x, g, rows, *, rank_by: str):
    """x/g: (1, D); rows: (bt, D) -> (bt, 1) raw scores."""
    eps = 1e-12
    diff = rows - x
    dot = jnp.sum(diff * g, axis=1, keepdims=True)
    gnorm = jnp.sqrt(jnp.sum(g * g, axis=1, keepdims=True)) + eps
    if rank_by == "angle":
        dnorm = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True)) + eps
        return jnp.clip(dot / (dnorm * gnorm), -1.0, 1.0)
    return dot / gnorm


def _kernel(idx_ref, x_ref, g_ref, data_ref, raw_ref, vmem, sem, *,
            rank_by: str, bt: int, dim: int):
    rows = RowGather(idx_ref, data_ref, vmem, sem, bt).rows(
        pl.program_id(0), dim)                            # (bt, D)
    raw_ref[...] = _rank_tile(x_ref[...], g_ref[...], rows, rank_by=rank_by)


@functools.partial(jax.jit, static_argnames=("dim", "rank_by", "interpret",
                                             "bt"))
def neighbor_rank_fused_pallas(x, grad, rows, idx, *, dim: int,
                               rank_by: str = "angle",
                               interpret: bool = False,
                               bt: int = 8) -> jax.Array:
    """x/grad: (Q, D) f32; rows: (N, Dp) f32 kernel payload
    (``CorpusStore.kernel_rows``) whose first ``dim`` lanes are the corpus;
    idx: (Q, B) int32 row ids (must be pre-clamped >= 0); bt: neighbor rows
    per grid step (autotuned; B is padded up to a multiple). Returns raw
    scores (Q, B) f32 — ops.py turns them into keys and the α·θ band."""
    Q, B = idx.shape
    bt = max(1, int(bt))
    bp = -(-B // bt) * bt
    tiles_per_q = bp // bt
    idx_flat = jnp.pad(idx, ((0, 0), (0, bp - B))).reshape(Q * bp)
    # x/grad travel as (Q, 1, D): a (1, D) block of a (Q, D) array is off
    # the (8, 128) tiling, but it is the whole of each (1, D) slab
    lane = lambda t, idx_ref: (t // tiles_per_q, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q * tiles_per_q,),
        in_specs=[
            pl.BlockSpec((None, 1, dim), lane),           # x
            pl.BlockSpec((None, 1, dim), lane),           # grad
            pl.BlockSpec(memory_space=pl.ANY),            # corpus
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda t, idx_ref: (t, 0)),
        scratch_shapes=gather_scratch(bt, rows.shape[1]),
    )
    raw = pl.pallas_call(
        functools.partial(_kernel, rank_by=rank_by, bt=bt, dim=dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q * bp, 1), jnp.float32),
        interpret=interpret,
        name="neighbor_rank_fused",
    )(idx_flat, x.astype(jnp.float32)[:, None, :],
      grad.astype(jnp.float32)[:, None, :], rows)
    return raw.reshape(Q, bp)[:, :B]
