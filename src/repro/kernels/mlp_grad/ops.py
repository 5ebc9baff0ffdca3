"""Public wrappers for the analytic MLP grad kernels: padding, interpret
switch, param flattening + weight transposes, and the bit-matching jnp
fallbacks (refs live in kernels/mlp_score/ref.py with the score oracles)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.corpus import CorpusStore
from repro.kernels.mlp_grad.kernel import (_wt_rows, mlp_grad_fused_pallas,
                                           mlp_grad_pallas)
from repro.kernels.mlp_score.ops import _flat, _wb
from repro.kernels.mlp_score.ref import (mlp_grad_fused_ref,
                                         mlp_value_and_grad_ref)


def mlp_value_and_grad(cand: jax.Array, query: jax.Array, mlp_params: dict,
                       block_n: int = 128, use_pallas: bool = True,
                       interpret: bool | None = None):
    """cand: (N, Dx); query: (N, Dq) or a single (Dq,) vector; mlp_params:
    {'w': [...], 'b': [...]} (any depth). Returns (vals (N,) f32,
    grads (N, Dx) f32) with grads = df/d cand (paper Eq. 2).

    The jnp fallback is fp32 bit-identical to
    ``jax.vmap(jax.value_and_grad(score_fn))`` — see mlp_score/ref.py."""
    Ws, bs = _wb(mlp_params)
    if not use_pallas:
        if query.ndim == 1:
            query = jnp.broadcast_to(query[None, :],
                                     (cand.shape[0], query.shape[0]))
        return mlp_value_and_grad_ref(cand, query, Ws, bs)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N = cand.shape[0]
    block_n = min(block_n, max(8, N))
    pad = (-N) % block_n
    if pad:
        cand = jnp.pad(cand, ((0, pad), (0, 0)))
    q_shared = query.ndim == 1
    if q_shared:
        q_arg = query[None, :]
    elif pad:
        q_arg = jnp.pad(query, ((0, pad), (0, 0)))
    else:
        q_arg = query
    vals, grads = mlp_grad_pallas(
        cand.astype(jnp.float32), q_arg.astype(jnp.float32),
        *_flat(Ws, bs), *_wt_rows(Ws), n_layers=len(Ws), block_n=block_n,
        q_shared=q_shared, interpret=interpret)
    return vals[:N], grads[:N]


def mlp_grad_fused(store: CorpusStore, idx: jax.Array, query: jax.Array,
                   mlp_params: dict, use_pallas: bool = True,
                   interpret: bool | None = None,
                   tile: str | None = None):
    """store: resident corpus; idx: (Q,) int32 frontier ids (clamped here);
    query: (Q, Dq) per-lane rows; tile: optional override spec for the
    autotuned rows-per-grid-step (e.g. ``":16"``). Returns (vals (Q,),
    grads (Q, Dx), x (Q, Dx) dequantized frontier rows — feeds the rank
    stage, no second gather)."""
    from repro.kernels import autotune

    idx = jnp.maximum(idx, 0).astype(jnp.int32)
    Ws, bs = _wb(mlp_params)
    if not use_pallas:
        return mlp_grad_fused_ref(store, idx, query, Ws, bs)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = autotune.resolve(
        "mlp_grad_fused", q=int(idx.shape[0]), m=0, d=int(store.dim),
        dtype=store.dtype, override=autotune.parse_tile(tile))
    return mlp_grad_fused_pallas(
        store.kernel_rows(), idx, query.astype(jnp.float32),
        *_flat(Ws, bs), *_wt_rows(Ws), n_layers=len(Ws), d_x=store.dim,
        interpret=interpret, bt=cfg.bt)
