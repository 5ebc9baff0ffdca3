"""Analytic DeepFM forward+backward Pallas kernel (the GUITAR grad stage).

The cost model charges gradients double (Table 2: Total = #NN + 2·#Grad),
yet until this kernel the grad stage was the one hot stage still running as
a generic ``vmap(jax.value_and_grad)``. This kernel computes f(x, q) AND
df/dx in one VMEM pass over a row block: forward FM dot + two MLP matmuls
(keeping the pre-activations resident), then the hand-derived backward —
sigmoid derivative on the score lane, two transposed matmuls back down the
MLP with relu masks off the resident activations, and the FM term's
``g_logit · q_fm`` closing the gradient row. Nothing but (vals, grads)
leaves VMEM; autodiff would stage the activations to HBM and replay the
forward structure from a transposed graph.

Tiling mirrors ``deepfm_score``: grid over row blocks, weights whole in
VMEM (measure MLPs are tiny), transposed weights passed pre-materialized by
ops.py so the backward matmuls are plain MXU contractions. Values leave as
a (BN, 1) column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mxu import dot_f32


def _kernel(cand_ref, query_ref, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref,
            b2_ref, w0t_ref, w1t_ref, w2t_ref, val_ref, grad_ref, *,
            fm_dim: int, deep_dim: int):
    x = cand_ref[...]                          # (BN, D)
    q = query_ref[...]                         # (BN, D) or (1, D) shared
    BN = x.shape[0]
    fm = jnp.sum(x[:, :fm_dim] * q[:, :fm_dim], axis=-1,
                 keepdims=True)                                   # (BN, 1)
    q_deep = jnp.broadcast_to(q[:, fm_dim: fm_dim + deep_dim],
                              (BN, deep_dim))
    deep_in = jnp.concatenate(
        [q_deep, x[:, fm_dim: fm_dim + deep_dim]], axis=-1)       # (BN, 2dd)
    z0 = dot_f32(deep_in, w0_ref[...]) + b0_ref[...][None, :]
    h0 = jnp.maximum(z0, 0.0)
    z1 = dot_f32(h0, w1_ref[...]) + b1_ref[...][None, :]
    h1 = jnp.maximum(z1, 0.0)
    logit = dot_f32(h1, w2_ref[...])
    val = jax.nn.sigmoid(logit + b2_ref[...][0] + fm)             # (BN, 1)
    # backward — activations still resident in VMEM
    g_logit = val * (1.0 - val)                                   # (BN, 1)
    g1 = g_logit * w2t_ref[...]                                   # (BN, H2)
    g1 = jnp.where(z1 > 0, g1, 0.0)
    g0 = dot_f32(g1, w1t_ref[...])
    g0 = jnp.where(z0 > 0, g0, 0.0)
    g_in = dot_f32(g0, w0t_ref[...])            # (BN, 2dd)
    q_fm = jnp.broadcast_to(q[:, :fm_dim], (BN, fm_dim))
    val_ref[...] = val
    grad_ref[...] = jnp.concatenate(
        [g_logit * q_fm, g_in[:, deep_dim:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("fm_dim", "deep_dim", "block_n",
                                             "q_shared", "interpret"))
def deepfm_grad_pallas(cand: jax.Array, query: jax.Array, w0, b0, w1, b1,
                       w2, b2, *, fm_dim: int = 8, deep_dim: int = 32,
                       block_n: int = 128, q_shared: bool = False,
                       interpret: bool = False):
    """cand: (N, D) with N % block_n == 0 (ops.py pads); query: (N, D) rows
    or (1, D) shared. Returns (vals (N,) f32, grads (N, D) f32)."""
    N, D = cand.shape
    grid = (N // block_n,)
    w2t = w2[:, 0][None, :]                    # (1, H2) row for the VPU bcast
    row_spec = pl.BlockSpec((block_n, D), lambda i: (i, 0))
    q_spec = pl.BlockSpec((1, D), lambda i: (0, 0)) if q_shared else row_spec
    full = lambda *s: pl.BlockSpec(s, lambda i: tuple(0 for _ in s))
    vals, grads = pl.pallas_call(
        functools.partial(_kernel, fm_dim=fm_dim, deep_dim=deep_dim),
        grid=grid,
        in_specs=[
            row_spec, q_spec,
            full(*w0.shape), full(*b0.shape),
            full(*w1.shape), full(*b1.shape),
            full(*w2.shape), full(*b2.shape),
            full(*w0.T.shape), full(*w1.T.shape), full(*w2t.shape),
        ],
        out_specs=(pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_n, D), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, D), jnp.float32)),
        interpret=interpret,
        name="deepfm_grad",
    )(cand, query, w0, b0, w1, b1, w2, b2, w0.T, w1.T, w2t)
    return vals[:, 0], grads
