"""MLP-measure scoring Pallas kernels (pre-gathered + index-fused).

The generic MLP measure f(x, q) = sigmoid(MLP([x, q])) is the 'heavier f'
regime the paper motivates — and the measure the serving demo and most
tests run — yet until this kernel it only had the vmap fallback. Same
shape as ``deepfm_score``: one VMEM-resident fusion per row block (concat,
L small matmuls back-to-back on the MXU, one (BN, 1) sigmoid column out —
the last matmul's own layout; a rank-1 block would have to be a multiple
of 128), with the layer count static per compile (MLP depth is a config
constant).

The index-fused variant walks candidate *tiles* with a scalar-prefetch
grid: each step DMAs ``bt`` corpus rows (autotuned — kernels/autotune.py)
into a double-buffered VMEM tile so the next tile's gather overlaps this
tile's matmuls; the flattened (M, Dx) candidate block never exists in HBM.
It gathers from the store's kernel payload (``CorpusStore.kernel_rows``:
dequantized float32 rows padded to 128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma import RowGather, gather_scratch
from repro.kernels.mxu import dot_f32


def _forward(h, wb_refs, n_layers: int):
    """h: (BN, Dx+Dq) concat block; wb_refs: [w0, b0, ..., wL-1, bL-1]."""
    for i in range(n_layers):
        w = wb_refs[2 * i][...]
        b = wb_refs[2 * i + 1][...]
        h = dot_f32(h, w) + b[None, :]
        if i < n_layers - 1:
            h = jnp.maximum(h, 0.0)
    return jax.nn.sigmoid(h[:, :1])


def _kernel(*refs, n_layers: int):
    cand_ref, query_ref = refs[0], refs[1]
    wb_refs, out_ref = refs[2:-1], refs[-1]
    cand = cand_ref[...]                        # (BN, Dx)
    query = jnp.broadcast_to(query_ref[...],
                             (cand.shape[0], query_ref.shape[-1]))
    h = jnp.concatenate([cand, query], axis=-1)
    out_ref[...] = _forward(h, wb_refs, n_layers)


@functools.partial(jax.jit, static_argnames=("n_layers", "block_n",
                                             "q_shared", "interpret"))
def mlp_score_pallas(cand: jax.Array, query: jax.Array, *wb,
                     n_layers: int, block_n: int = 256,
                     q_shared: bool = False,
                     interpret: bool = False) -> jax.Array:
    """cand: (N, Dx) with N % block_n == 0 (ops.py pads); query: (N, Dq)
    rows or (1, Dq) shared; wb: w0, b0, ..., wL-1, bL-1. Returns (N,) f32."""
    N, _ = cand.shape
    grid = (N // block_n,)
    row_spec = pl.BlockSpec((block_n, cand.shape[1]), lambda i: (i, 0))
    q_spec = pl.BlockSpec((1, query.shape[1]), lambda i: (0, 0)) \
        if q_shared else pl.BlockSpec((block_n, query.shape[1]),
                                      lambda i: (i, 0))
    full = lambda *s: pl.BlockSpec(s, lambda i: tuple(0 for _ in s))
    return pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers),
        grid=grid,
        in_specs=[row_spec, q_spec] + [full(*a.shape) for a in wb],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=interpret,
        name="mlp_score",
    )(cand, query, *wb)[:, 0]


def _kernel_fused(idx_ref, *refs, n_layers: int, bt: int, d_x: int,
                  q_shared: bool, masked: bool):
    """Wide-block fused scorer: ``bt`` candidate rows per grid step, DMAed
    into a double-buffered VMEM tile (``kernels/dma.py``) so the next
    tile's gather overlaps this tile's matmuls. ``masked``: an adaptive
    (bt, 1) prefix-mask tile rides along — masked rows score -inf, and an
    all-masked tile skips the matmuls entirely (the DMA schedule still
    runs; step t prefetches step t+1's rows)."""
    refs = list(refs)
    data_ref = refs.pop(0)
    mask_ref = refs.pop(0) if masked else None
    q_ref = refs[0]
    wb_refs = refs[1: 1 + 2 * n_layers]
    out_ref, vmem, sem = refs[1 + 2 * n_layers:]
    rows = RowGather(idx_ref, data_ref, vmem, sem, bt).rows(
        pl.program_id(0), d_x)                            # (bt, Dx)

    def _scores():
        q = q_ref[...]
        if q_shared:
            q = jnp.broadcast_to(q, (bt, q.shape[-1]))
        h = jnp.concatenate([rows, q], axis=-1)
        return _forward(h, wb_refs, n_layers)

    if not masked:
        out_ref[...] = _scores()
    else:
        m = mask_ref[...] != 0
        any_live = jnp.any(m)

        @pl.when(any_live)
        def _():
            out_ref[...] = jnp.where(m, _scores(), -jnp.inf)

        @pl.when(~any_live)
        def _():
            out_ref[...] = jnp.full((bt, 1), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_layers", "d_x", "q_shared",
                                             "interpret", "bt"))
def mlp_score_fused_pallas(rows, idx, query, *wb, n_layers: int, d_x: int,
                           q_shared: bool = False,
                           interpret: bool = False,
                           bt: int = 8, mask=None) -> jax.Array:
    """rows: (N, Dp) f32 kernel payload (``CorpusStore.kernel_rows``) whose
    first ``d_x`` lanes are the corpus; idx: (M,) int32 (pre-clamped >= 0);
    query: (M, Dq) rows or (1, Dq) shared; bt: candidate rows per grid step
    (autotuned; M is padded up to a multiple); mask: optional (M,) bool —
    masked rows score -inf and all-masked ``bt`` tiles skip their matmuls.
    Returns (M,) f32."""
    M = idx.shape[0]
    masked = mask is not None
    bt = max(1, int(bt))
    mp = -(-M // bt) * bt
    idx = jnp.pad(idx, (0, mp - M))
    if not q_shared:
        query = jnp.pad(query, ((0, mp - M), (0, 0)))
    full = lambda *s: pl.BlockSpec(s, lambda t, idx_ref: tuple(0 for _ in s))
    col = pl.BlockSpec((bt, 1), lambda t, idx_ref: (t, 0))
    q_spec = full(1, query.shape[1]) if q_shared \
        else pl.BlockSpec((bt, query.shape[1]), lambda t, idx_ref: (t, 0))
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    args = [rows]
    if masked:
        # int32 0/1 tiles (bool HBM tensors don't lay out portably on TPU)
        in_specs.append(col)
        args.append(jnp.pad(mask.astype(jnp.int32), (0, mp - M))[:, None])
    in_specs += [q_spec]
    in_specs += [full(*a.shape) for a in wb]
    args += [query, *wb]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bt,),
        in_specs=in_specs,
        out_specs=col,
        scratch_shapes=gather_scratch(bt, rows.shape[1]),
    )
    out = pl.pallas_call(
        functools.partial(_kernel_fused, n_layers=n_layers, bt=bt, d_x=d_x,
                          q_shared=q_shared, masked=masked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        interpret=interpret,
        name="mlp_score_fused",
    )(idx, *args)
    return out[:M, 0]
