"""Public wrappers for the MLP-measure scoring kernels: padding, interpret
switch, param flattening, and the bit-matching jnp fallbacks."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.corpus import CorpusStore
from repro.kernels.mlp_score.kernel import (mlp_score_fused_pallas,
                                            mlp_score_pallas)
from repro.kernels.mlp_score.ref import mlp_score_fused_ref, mlp_score_ref


def _wb(mlp_params: dict):
    Ws = [jnp.asarray(w, jnp.float32) for w in mlp_params["w"]]
    bs = [jnp.asarray(b, jnp.float32) for b in mlp_params["b"]]
    return Ws, bs


def _flat(Ws, bs):
    out = []
    for w, b in zip(Ws, bs):
        out += [w, b]
    return out


def mlp_score(cand: jax.Array, query: jax.Array, mlp_params: dict,
              block_n: int = 256, use_pallas: bool = True,
              interpret: bool | None = None) -> jax.Array:
    """cand: (N, Dx); query: (N, Dq) or a single (Dq,) vector; mlp_params:
    {'w': [w0, ...], 'b': [b0, ...]} (any depth). Returns (N,) f32.

    The jnp fallback is fp32 bit-identical to the engine's generic
    ``vmap(score_fn)`` stage — see ref.py."""
    Ws, bs = _wb(mlp_params)
    if not use_pallas:
        if query.ndim == 1:
            query = jnp.broadcast_to(query[None, :],
                                     (cand.shape[0], query.shape[0]))
        return mlp_score_ref(cand, query, Ws, bs)
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
    out = mlp_score_pallas(cand.astype(jnp.float32),
                           q_arg.astype(jnp.float32), *_flat(Ws, bs),
                           n_layers=len(Ws), block_n=block_n,
                           q_shared=q_shared, interpret=interpret)
    return out[:N]


def mlp_score_fused(store: CorpusStore, idx: jax.Array, query: jax.Array,
                    mlp_params: dict, use_pallas: bool = True,
                    interpret: bool | None = None,
                    tile: str | None = None,
                    mask: jax.Array | None = None) -> jax.Array:
    """store: resident corpus; idx: (M,) int32 candidate ids (may contain -1
    padding — clamped here; mask scores at the call site); query: (M, Dq)
    rows or a single (Dq,) vector; tile: optional override spec for the
    autotuned rows-per-grid-step (e.g. ``":16"``); mask: optional (M,) bool
    — the adaptive engine's per-lane prefix mask: masked rows return -inf,
    and the Pallas grid skips the matmuls for tiles whose ``bt`` rows are
    ALL masked. Returns (M,) f32."""
    from repro.kernels import autotune

    idx = jnp.maximum(idx, 0).astype(jnp.int32)
    Ws, bs = _wb(mlp_params)
    if not use_pallas:
        out = mlp_score_fused_ref(store, idx, query, Ws, bs)
        # jnp ref is dense — masked rows are computed then overwritten
        # (XLA:CPU has no tile-skip to win; the adaptive speedup on this
        # path comes from fewer loop iterations)
        return out if mask is None else jnp.where(mask, out, -jnp.inf)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = autotune.resolve(
        "mlp_score_fused", q=0, m=int(idx.shape[0]), d=int(store.dim),
        dtype=store.dtype, override=autotune.parse_tile(tile))
    q_shared = query.ndim == 1
    q_arg = query[None, :] if q_shared else query
    return mlp_score_fused_pallas(
        store.kernel_rows(), idx, q_arg.astype(jnp.float32),
        *_flat(Ws, bs), n_layers=len(Ws), d_x=store.dim, q_shared=q_shared,
        interpret=interpret, bt=cfg.bt, mask=mask)
