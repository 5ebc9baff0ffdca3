"""Index-fused analytic DeepFM grad kernel (frontier ids in, grads out),
wide-block edition.

The pre-gathered ``deepfm_grad`` kernel consumes a (Q, D) fp32 frontier
block the engine staged through HBM (gather + dequant as a separate pass).
This variant takes the resident corpus and the (Q,) frontier-id vector and
gathers in-kernel: each grid step DMAs ``bt`` frontier rows of the store's
kernel payload (``CorpusStore.kernel_rows``: dequantized float32 rows
padded to 128 lanes) into a double-buffered (2, bt, Dp) VMEM tile (``kernels/dma.py``) so the next
tile's gather overlaps this tile's forward+backward, and every matmul runs
at (bt, ·) instead of as a GEMV. ``bt`` comes from the autotune cache.
Because the rows are already resident in VMEM — and the rank stage needs
the same rows for its diffs — the kernel also writes the frontier tile
out, turning the engine's separate gather-dequant pass into
a single (Q, D) store.

Per tile: forward FM dot + two MLP matmuls with pre-activations kept live,
then the analytic backward (sigmoid derivative, transposed matmuls, relu
masks, FM closing term). Same math as ``deepfm_grad``. Values leave as a
(bt, 1) column (a rank-1 block would have to be a multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma import RowGather, gather_scratch
from repro.kernels.mxu import dot_f32


def _grad_tile(rows, q, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref, b2_ref,
               w0t_ref, w1t_ref, w2t_ref, *, fm_dim: int, deep_dim: int):
    """rows/q: (bt, D) f32 -> (vals (bt, 1), grads (bt, D))."""
    fm = jnp.sum(rows[:, :fm_dim] * q[:, :fm_dim], axis=1, keepdims=True)
    deep_in = jnp.concatenate(
        [q[:, fm_dim: fm_dim + deep_dim], rows[:, fm_dim: fm_dim + deep_dim]],
        axis=1)                                           # (bt, 2*deep)
    z0 = dot_f32(deep_in, w0_ref[...]) + b0_ref[...][None, :]
    h0 = jnp.maximum(z0, 0.0)
    z1 = dot_f32(h0, w1_ref[...]) + b1_ref[...][None, :]
    h1 = jnp.maximum(z1, 0.0)
    logit = dot_f32(h1, w2_ref[...])
    val = jax.nn.sigmoid(logit + b2_ref[...][0] + fm)     # (bt, 1)
    g_logit = val * (1.0 - val)
    g1 = jnp.where(z1 > 0, g_logit * w2t_ref[...], 0.0)   # (bt, H2)
    g0 = dot_f32(g1, w1t_ref[...])
    g0 = jnp.where(z0 > 0, g0, 0.0)
    g_in = dot_f32(g0, w0t_ref[...])    # (bt, 2*deep)
    grads = jnp.concatenate(
        [g_logit * q[:, :fm_dim], g_in[:, deep_dim:]], axis=1)
    return val, grads


def _kernel(idx_ref, data_ref, q_ref, w0, b0, w1, b1, w2, b2, w0t, w1t, w2t,
            val_ref, grad_ref, x_ref, vmem, sem, *, fm_dim: int,
            deep_dim: int, bt: int):
    rows = RowGather(idx_ref, data_ref, vmem, sem, bt).rows(
        pl.program_id(0), fm_dim + deep_dim)              # (bt, D)
    val, grads = _grad_tile(rows, q_ref[...], w0, b0, w1, b1, w2, b2,
                            w0t, w1t, w2t, fm_dim=fm_dim, deep_dim=deep_dim)
    val_ref[...] = val
    grad_ref[...] = grads
    x_ref[...] = rows


@functools.partial(jax.jit, static_argnames=("fm_dim", "deep_dim",
                                             "interpret", "bt"))
def deepfm_grad_fused_pallas(rows, idx, query, w0, b0, w1, b1, w2, b2, *,
                             fm_dim: int = 8, deep_dim: int = 32,
                             interpret: bool = False, bt: int = 8):
    """rows: (N, Dp) f32 kernel payload (``CorpusStore.kernel_rows``) whose
    first D = fm_dim + deep_dim lanes are the corpus; idx: (Q,) int32
    frontier ids (pre-clamped >= 0); query: (Q, D) per-lane user rows; bt:
    lanes per grid step (autotuned; Q is padded up to a multiple). Returns
    (vals (Q,), grads (Q, D), x (Q, D) frontier rows)."""
    Q = idx.shape[0]
    D = fm_dim + deep_dim
    bt = max(1, int(bt))
    qp = -(-Q // bt) * bt
    idx = jnp.pad(idx, (0, qp - Q))
    query = jnp.pad(query, ((0, qp - Q), (0, 0)))
    w2t = w2[:, 0][None, :]
    full = lambda *s: pl.BlockSpec(s, lambda t, idx_ref: tuple(0 for _ in s))
    tile = lambda w: pl.BlockSpec((bt, w), lambda t, idx_ref: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qp // bt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            tile(query.shape[1]),
            full(*w0.shape), full(*b0.shape),
            full(*w1.shape), full(*b1.shape),
            full(*w2.shape), full(*b2.shape),
            full(*w0.T.shape), full(*w1.T.shape), full(*w2t.shape),
        ],
        out_specs=(tile(1), tile(D), tile(D)),
        scratch_shapes=gather_scratch(bt, rows.shape[1]),
    )
    vals, grads, x = pl.pallas_call(
        functools.partial(_kernel, fm_dim=fm_dim, deep_dim=deep_dim, bt=bt),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((qp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((qp, D), jnp.float32),
                   jax.ShapeDtypeStruct((qp, D), jnp.float32)),
        interpret=interpret,
        name="deepfm_grad_fused",
    )(idx, rows, query, w0, b0, w1, b1, w2, b2, w0.T, w1.T, w2t)
    return vals[:Q, 0], grads[:Q], x[:Q]
