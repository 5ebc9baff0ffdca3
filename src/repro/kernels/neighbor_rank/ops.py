"""Public wrapper: pad the query-batch dim, pick interpret mode, fallback."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.neighbor_rank.kernel import neighbor_rank_pallas
from repro.kernels.neighbor_rank.ref import keys_from_raw, neighbor_rank_ref


def neighbor_rank(x, grad, nvecs, valid, alpha: float = 1.01,
                  rank_by: str = "angle", block_q: int = 8,
                  use_pallas: bool = True, interpret: bool | None = None):
    """Batched Eq. 3/4 ranking. Returns (key (Q,B) f32, in_range (Q,B) bool)."""
    if not use_pallas:
        return neighbor_rank_ref(x, grad, nvecs, valid, alpha, rank_by)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Q = x.shape[0]
    pad = (-Q) % block_q
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        grad = jnp.pad(grad, ((0, pad), (0, 0)))
        nvecs = jnp.pad(nvecs, ((0, pad), (0, 0), (0, 0)))
    raw = neighbor_rank_pallas(
        x.astype(jnp.float32), grad.astype(jnp.float32),
        nvecs.astype(jnp.float32), rank_by=rank_by, block_q=block_q,
        interpret=interpret)
    return keys_from_raw(raw[:Q], valid, alpha, rank_by)
