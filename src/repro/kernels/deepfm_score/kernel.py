"""Fused DeepFM candidate-scoring Pallas kernel.

The GUITAR search inner loop evaluates f(x, q) over a (candidates,) batch per
step. On TPU this wants to be ONE VMEM-resident fusion: load a tile of
candidate/query rows, compute the FM dot on the VPU, run the two small MLP
matmuls back-to-back on the MXU without spilling the 64-wide hidden
activations to HBM, and write a (BLOCK_N, 1) score column back — the last
matmul's own layout (a rank-1 block would have to be a multiple of 128).

Tiling: grid over row blocks (BLOCK_N rows). Feature dims are padded to
lane-friendly sizes by ops.py (deep-in = 64, hidden = 64 — the MXU pads to
128 lanes internally; acceptable at these measure sizes, and the win is the
fusion, not the matmul shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mxu import dot_f32


def _kernel(cand_ref, query_ref, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref,
            b2_ref, out_ref, *, fm_dim: int, deep_dim: int):
    cand = cand_ref[...]                       # (BN, D)
    query = query_ref[...]                     # (BN, D) or (1, D) shared
    fm = jnp.sum(cand[:, :fm_dim] * query[:, :fm_dim], axis=-1,
                 keepdims=True)                # (BN, 1)
    q_deep = jnp.broadcast_to(query[:, fm_dim: fm_dim + deep_dim],
                              (cand.shape[0], deep_dim))  # VMEM-only bcast
    deep_in = jnp.concatenate(
        [q_deep, cand[:, fm_dim: fm_dim + deep_dim]],
        axis=-1)                               # (BN, 2*deep_dim)
    h = jnp.maximum(
        dot_f32(deep_in, w0_ref[...])
        + b0_ref[...][None, :], 0.0)
    h = jnp.maximum(
        dot_f32(h, w1_ref[...])
        + b1_ref[...][None, :], 0.0)
    logit = dot_f32(h, w2_ref[...])
    logit = logit + b2_ref[...][0] + fm
    out_ref[...] = jax.nn.sigmoid(logit)


@functools.partial(jax.jit, static_argnames=("fm_dim", "deep_dim", "block_n",
                                             "q_shared", "interpret"))
def deepfm_score_pallas(cand: jax.Array, query: jax.Array, w0, b0, w1, b1,
                        w2, b2, *, fm_dim: int = 8, deep_dim: int = 32,
                        block_n: int = 256, q_shared: bool = False,
                        interpret: bool = False) -> jax.Array:
    """cand: (N, D) with N % block_n == 0 (ops.py pads); query: (N, D) rows,
    or (1, D) when ``q_shared`` — the kernel broadcasts the single row over
    each block in VMEM, so no (N, D) query copy is ever materialized."""
    N, D = cand.shape
    H = w0.shape[1]
    grid = (N // block_n,)
    row_spec = pl.BlockSpec((block_n, D), lambda i: (i, 0))
    q_spec = pl.BlockSpec((1, D), lambda i: (0, 0)) if q_shared else row_spec
    full = lambda *s: pl.BlockSpec(s, lambda i: tuple(0 for _ in s))
    return pl.pallas_call(
        functools.partial(_kernel, fm_dim=fm_dim, deep_dim=deep_dim),
        grid=grid,
        in_specs=[
            row_spec, q_spec,
            full(*w0.shape), full(*b0.shape),
            full(*w1.shape), full(*b1.shape),
            full(*w2.shape), full(*b2.shape),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=interpret,
        name="deepfm_score",
    )(cand, query, w0, b0, w1, b1, w2, b2)[:, 0]
