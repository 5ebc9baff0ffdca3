"""Analytic MLP-measure forward+backward Pallas kernels (pre-gathered +
index-fused) — the GUITAR grad stage for the generic MLP measure.

One VMEM pass per row block: forward concat + L matmuls keeping every
pre-activation resident, then the hand-derived backward (sigmoid
derivative, transposed matmuls with relu masks off the resident
pre-activations), writing a (BN, 1) value column and the df/dx gradient
rows.
ops.py passes the transposed weights pre-materialized so the backward
matmuls are plain MXU contractions. The fused variant gathers ``bt``
frontier rows per grid step (autotuned — kernels/autotune.py) of the
store's kernel payload (``CorpusStore.kernel_rows``) into a double-buffered
VMEM tile and also writes the rows out for the rank stage — the engine
never gathers the (Q, Dx) frontier block itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma import RowGather, gather_scratch
from repro.kernels.mxu import dot_f32


def _value_and_grad(h, wb_refs, wt_refs, n_layers: int, d_x: int):
    """h: (BN, Dx+Dq) concat block. Returns (val (BN, 1), gx (BN, d_x))."""
    zs = []
    for i in range(n_layers):
        w = wb_refs[2 * i][...]
        b = wb_refs[2 * i + 1][...]
        z = dot_f32(h, w) + b[None, :]
        zs.append(z)
        h = jnp.maximum(z, 0.0) if i < n_layers - 1 else z
    val = jax.nn.sigmoid(h[:, :1])                        # (BN, 1)
    g = val * (1.0 - val)
    for i in range(n_layers - 1, -1, -1):
        wt = wt_refs[i][...]
        if wt.shape[0] == 1:                              # last layer: a row
            g = g * wt                                    # (BN, H) via VPU
        else:
            g = dot_f32(g, wt)
        if i > 0:
            g = jnp.where(zs[i - 1] > 0, g, 0.0)
    return val, g[:, :d_x]


def _kernel(*refs, n_layers: int, d_x: int):
    cand_ref, query_ref = refs[0], refs[1]
    wb_refs = refs[2: 2 + 2 * n_layers]
    wt_refs = refs[2 + 2 * n_layers: 2 + 3 * n_layers]
    val_ref, grad_ref = refs[-2], refs[-1]
    cand = cand_ref[...]                                  # (BN, Dx)
    query = jnp.broadcast_to(query_ref[...],
                             (cand.shape[0], query_ref.shape[-1]))
    h = jnp.concatenate([cand, query], axis=-1)
    val, gx = _value_and_grad(h, wb_refs, wt_refs, n_layers, d_x)
    val_ref[...] = val
    grad_ref[...] = gx


def _wt_rows(Ws):
    """Transposed weights for the backward; the last layer's (H, 1) column
    becomes a (1, H) row so the kernel broadcasts it on the VPU."""
    return [Ws[i].T if i < len(Ws) - 1 else Ws[i][:, 0][None, :]
            for i in range(len(Ws))]


@functools.partial(jax.jit, static_argnames=("n_layers", "block_n",
                                             "q_shared", "interpret"))
def mlp_grad_pallas(cand: jax.Array, query: jax.Array, *wbt,
                    n_layers: int, block_n: int = 128,
                    q_shared: bool = False, interpret: bool = False):
    """cand: (N, Dx) with N % block_n == 0 (ops.py pads); query: (N, Dq)
    rows or (1, Dq) shared; wbt: w0, b0, ..., then the transposed weights
    (ops.py appends ``_wt_rows``). Returns (vals (N,), grads (N, Dx))."""
    N, d_x = cand.shape
    grid = (N // block_n,)
    row_spec = pl.BlockSpec((block_n, d_x), lambda i: (i, 0))
    q_spec = pl.BlockSpec((1, query.shape[1]), lambda i: (0, 0)) \
        if q_shared else pl.BlockSpec((block_n, query.shape[1]),
                                      lambda i: (i, 0))
    full = lambda *s: pl.BlockSpec(s, lambda i: tuple(0 for _ in s))
    vals, grads = pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers, d_x=d_x),
        grid=grid,
        in_specs=[row_spec, q_spec] + [full(*a.shape) for a in wbt],
        out_specs=(pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_n, d_x), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, d_x), jnp.float32)),
        interpret=interpret,
        name="mlp_grad",
    )(cand, query, *wbt)
    return vals[:, 0], grads


def _kernel_fused(idx_ref, *refs, n_layers: int, d_x: int, bt: int):
    """Wide-block fused grad: ``bt`` frontier rows per grid step, DMAed
    into a double-buffered VMEM tile (``kernels/dma.py``) so the next
    tile's gather overlaps this tile's forward+backward."""
    data_ref, q_ref = refs[0], refs[1]
    wb_refs = refs[2: 2 + 2 * n_layers]
    wt_refs = refs[2 + 2 * n_layers: 2 + 3 * n_layers]
    val_ref, grad_ref, x_ref, vmem, sem = refs[2 + 3 * n_layers:]
    rows = RowGather(idx_ref, data_ref, vmem, sem, bt).rows(
        pl.program_id(0), d_x)                            # (bt, Dx)
    h = jnp.concatenate([rows, q_ref[...]], axis=-1)
    val, gx = _value_and_grad(h, wb_refs, wt_refs, n_layers, d_x)
    val_ref[...] = val
    grad_ref[...] = gx
    x_ref[...] = rows


@functools.partial(jax.jit, static_argnames=("n_layers", "d_x", "interpret",
                                             "bt"))
def mlp_grad_fused_pallas(rows, idx, query, *wbt, n_layers: int, d_x: int,
                          interpret: bool = False, bt: int = 8):
    """rows: (N, Dp) f32 kernel payload (``CorpusStore.kernel_rows``) whose
    first ``d_x`` lanes are the corpus; idx: (Q,) int32 frontier ids
    (pre-clamped >= 0); query: (Q, Dq) per-lane rows; bt: lanes per grid
    step (autotuned; Q is padded up to a multiple). Returns (vals (Q,),
    grads (Q, Dx), x (Q, Dx))."""
    Q = idx.shape[0]
    bt = max(1, int(bt))
    qp = -(-Q // bt) * bt
    idx = jnp.pad(idx, (0, qp - Q))
    query = jnp.pad(query, ((0, qp - Q), (0, 0)))
    full = lambda *s: pl.BlockSpec(s, lambda t, idx_ref: tuple(0 for _ in s))
    tile = lambda w: pl.BlockSpec((bt, w), lambda t, idx_ref: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qp // bt,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), tile(query.shape[1])]
        + [full(*a.shape) for a in wbt],
        out_specs=(tile(1), tile(d_x), tile(d_x)),
        scratch_shapes=gather_scratch(bt, rows.shape[1]),
    )
    vals, grads, x = pl.pallas_call(
        functools.partial(_kernel_fused, n_layers=n_layers, d_x=d_x, bt=bt),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((qp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((qp, d_x), jnp.float32),
                   jax.ShapeDtypeStruct((qp, d_x), jnp.float32)),
        interpret=interpret,
        name="mlp_grad_fused",
    )(idx, rows, query, *wbt)
    return vals[:Q, 0], grads[:Q], x[:Q]
