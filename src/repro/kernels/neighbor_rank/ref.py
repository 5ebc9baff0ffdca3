"""Pure-jnp oracle for the fused gradient-ranking kernel (paper Eq. 3/4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def keys_from_raw(raw: jax.Array, valid: jax.Array, alpha: float,
                  rank_by: str):
    """Shared Eq. 3/4 keys and α·θ band from raw per-neighbor scores.

    raw: (Q, B) f32 — the clipped cosine of x' − x against ∂f/∂x for
    ``angle``, the projection onto ∂f/∂x for ``projection``; larger is
    better in both. The Pallas kernels emit ``raw`` and stop there: Mosaic
    has no ``acos``, so the angle (and with it the band test) is taken
    here, on the (Q, B) output, by XLA. Returns (key (Q, B) f32 — smaller
    is better, +inf for invalid; in_range (Q, B) bool)."""
    eps = 1e-12
    if rank_by == "angle":
        key = jnp.where(valid, jnp.arccos(raw), jnp.inf)
        theta = jnp.min(key, axis=1, keepdims=True)
        in_range = valid & (key <= alpha * theta + eps)
    else:
        pk = jnp.where(valid, raw, -jnp.inf)
        theta = jnp.max(pk, axis=1, keepdims=True)
        bound = jnp.where(theta >= 0, theta / alpha, theta * alpha)
        in_range = valid & (pk >= bound - eps)
        key = jnp.where(valid, -raw, jnp.inf)
    return key.astype(jnp.float32), in_range


def neighbor_rank_ref(x, grad, nvecs, valid, alpha: float = 1.01,
                      rank_by: str = "angle"):
    """x: (Q, D) frontier; grad: (Q, D) = ∂f/∂x; nvecs: (Q, B, D) neighbor
    vectors; valid: (Q, B) bool.

    Returns (key (Q, B) f32 — smaller is better, +inf for invalid;
             in_range (Q, B) bool — the adaptive α·θ mask)."""
    eps = 1e-12
    diffs = nvecs - x[:, None, :]
    dot = jnp.einsum("qbd,qd->qb", diffs, grad)
    gnorm = jnp.linalg.norm(grad, axis=-1, keepdims=True) + eps
    if rank_by == "angle":
        dnorm = jnp.linalg.norm(diffs, axis=-1) + eps
        raw = jnp.clip(dot / (dnorm * gnorm), -1.0, 1.0)
    else:
        raw = dot / gnorm
    return keys_from_raw(raw, valid, alpha, rank_by)
