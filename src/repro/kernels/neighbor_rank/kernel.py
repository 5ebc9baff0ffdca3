"""Fused gradient-ranking Pallas kernel.

Per GUITAR expansion: diffs = x' - x and their cosine (or projection)
against ∂f/∂x, in one VMEM pass over a (BLOCK_Q, B, D) tile. Memory-bound
fusion: the naive XLA lowering materializes diffs/norms/dots as separate
HBM tensors; here each neighbor tile is touched once. The kernel writes the
raw (Q, B) scores; the angle, the best angle θ and the α·θ band are taken
on that output by ``ref.keys_from_raw`` (Mosaic lowers no ``acos``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, g_ref, nv_ref, raw_ref, *, rank_by: str):
    eps = 1e-12
    x = x_ref[...]                    # (BQ, D)
    g = g_ref[...]                    # (BQ, D)
    nv = nv_ref[...]                  # (BQ, B, D)
    diffs = nv - x[:, None, :]
    dot = jnp.sum(diffs * g[:, None, :], axis=-1)               # (BQ, B)
    gnorm = jnp.sqrt(jnp.sum(g * g, axis=-1, keepdims=True)) + eps
    if rank_by == "angle":
        dnorm = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1)) + eps
        raw_ref[...] = jnp.clip(dot / (dnorm * gnorm), -1.0, 1.0)
    else:
        raw_ref[...] = dot / gnorm


@functools.partial(jax.jit, static_argnames=("rank_by", "block_q",
                                             "interpret"))
def neighbor_rank_pallas(x, grad, nvecs, *, rank_by: str = "angle",
                         block_q: int = 8, interpret: bool = False):
    """x/grad: (Q, D) f32; nvecs: (Q, B, D) f32 with Q % block_q == 0.
    Returns the raw (Q, B) f32 scores (see ``ref.keys_from_raw``)."""
    Q, B, D = nvecs.shape
    grid = (Q // block_q,)
    return pl.pallas_call(
        functools.partial(_kernel, rank_by=rank_by),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda i: (i, 0)),
            pl.BlockSpec((block_q, D), lambda i: (i, 0)),
            pl.BlockSpec((block_q, B, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, B), jnp.float32),
        interpret=interpret,
        name="neighbor_rank",
    )(x, grad, nvecs)
