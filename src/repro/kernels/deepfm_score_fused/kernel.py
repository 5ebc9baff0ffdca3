"""Index-fused DeepFM scoring Pallas kernel (indices in, scores out),
wide-block edition.

The pre-gathered ``deepfm_score`` kernel consumes a flattened (M, D) fp32
candidate block that the engine had to stage through HBM. This variant
takes the resident corpus and the (M,) candidate-id vector and gathers
*inside* the kernel — but instead of the original one-row-per-grid-step
BlockSpec gather, each grid step now DMAs ``bt`` candidate rows into a
double-buffered (2, bt, Dp) VMEM tile (``kernels/dma.py``): step ``t+1``'s
row copies are issued before step ``t`` computes, so the gather hides
behind the tile's MLP, and the per-step compute is a real (bt, 2·deep)
matmul instead of a GEMV. ``bt`` comes from the autotune cache
(``kernels/autotune.py``); on TPU it must be a multiple of 8, the
sublane tile of the (bt, 1) score column.

The kernel gathers from the store's kernel payload (``CorpusStore.
kernel_rows``: dequantized float32 rows padded to 128 lanes), so bf16 and
int8 residency reach it already rounded and it holds no dequant code.
Scores leave as a (bt, 1) column per step: the MLP's last matmul yields
that layout, and a rank-1 block would have to be a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma import RowGather, gather_scratch
from repro.kernels.mxu import dot_f32


def _score_tile(rows, q, w0_ref, b0_ref, w1_ref, b1_ref, w2_ref, b2_ref, *,
                fm_dim: int, deep_dim: int):
    """rows/q: (bt, D) f32 tiles -> (bt, 1) scores."""
    fm = jnp.sum(rows[:, :fm_dim] * q[:, :fm_dim], axis=1, keepdims=True)
    deep_in = jnp.concatenate(
        [q[:, fm_dim: fm_dim + deep_dim], rows[:, fm_dim: fm_dim + deep_dim]],
        axis=1)                                           # (bt, 2*deep)
    h = jnp.maximum(
        dot_f32(deep_in, w0_ref[...])
        + b0_ref[...][None, :], 0.0)
    h = jnp.maximum(
        dot_f32(h, w1_ref[...])
        + b1_ref[...][None, :], 0.0)
    logit = dot_f32(h, w2_ref[...])
    return jax.nn.sigmoid(logit + b2_ref[...][0] + fm)


def _kernel(idx_ref, *refs, fm_dim: int, deep_dim: int, bt: int,
            q_shared: bool, masked: bool):
    refs = list(refs)
    data_ref = refs.pop(0)
    mask_ref = refs.pop(0) if masked else None
    q_ref, w0, b0, w1, b1, w2, b2, out_ref, vmem, sem = refs
    # the double-buffer schedule runs UNCONDITIONALLY — step t issues step
    # t+1's row copies, so skipping it inside a masked tile would starve
    # the next tile's gather
    rows = RowGather(idx_ref, data_ref, vmem, sem, bt).rows(
        pl.program_id(0), fm_dim + deep_dim)              # (bt, D)

    def _scores():
        q = q_ref[...]
        if q_shared:
            q = jnp.broadcast_to(q, (bt, q.shape[-1]))
        return _score_tile(rows, q, w0, b0, w1, b1, w2, b2,
                           fm_dim=fm_dim, deep_dim=deep_dim)

    if not masked:
        out_ref[...] = _scores()
    else:
        # adaptive prefix mask: per-lane dynamic |C| arrives as a (bt, 1)
        # mask tile on the same grid as the tail padding; a fully-masked
        # tile skips the FM + MLP entirely (the DMA already ran — the
        # pipeline stays sound) and writes the -inf sentinel the engine's
        # insert stage treats as absent
        m = mask_ref[...] != 0
        any_live = jnp.any(m)

        @pl.when(any_live)
        def _():
            out_ref[...] = jnp.where(m, _scores(), -jnp.inf)

        @pl.when(~any_live)
        def _():
            out_ref[...] = jnp.full((bt, 1), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("fm_dim", "deep_dim",
                                             "q_shared", "interpret", "bt"))
def deepfm_score_fused_pallas(rows, idx, query, w0, b0, w1, b1, w2, b2, *,
                              fm_dim: int = 8, deep_dim: int = 32,
                              q_shared: bool = False,
                              interpret: bool = False,
                              bt: int = 8, mask=None) -> jax.Array:
    """rows: (N, Dp) f32 kernel payload (``CorpusStore.kernel_rows``) whose
    first fm_dim + deep_dim lanes are the corpus; idx: (M,) int32
    (pre-clamped >= 0); query: (M, D) rows, or (1, D) shared across
    candidates when ``q_shared`` (the kernel broadcasts — no (M, D) query
    copy is ever built); bt: candidate rows per grid step (autotuned; M is
    padded up to a multiple); mask: optional (M,) bool — masked rows score
    -inf and all-masked ``bt`` tiles skip their compute (the mask pads with
    False onto the same grid tail as ``idx``)."""
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
    in_specs += [
        q_spec,
        full(*w0.shape), full(*b0.shape),
        full(*w1.shape), full(*b1.shape),
        full(*w2.shape), full(*b2.shape),
    ]
    args += [query, w0, b0, w1, b1, w2, b2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bt,),
        in_specs=in_specs,
        out_specs=col,
        scratch_shapes=gather_scratch(bt, rows.shape[1]),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, fm_dim=fm_dim, deep_dim=deep_dim, bt=bt,
                          q_shared=q_shared, masked=masked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        interpret=interpret,
        name="deepfm_score_fused",
    )(idx, *args)
    return out[:M, 0]
