"""Public wrapper for index-fused neighbor ranking: backend pick, id
clamping, and the shared keys and α·θ band on the raw kernel scores."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.corpus import CorpusStore
from repro.kernels import autotune
from repro.kernels.neighbor_rank.ref import keys_from_raw
from repro.kernels.neighbor_rank_fused.kernel import neighbor_rank_fused_pallas
from repro.kernels.neighbor_rank_fused.ref import neighbor_rank_fused_ref


def neighbor_rank_fused(x, grad, store: CorpusStore, idx, valid,
                        alpha: float = 1.01, rank_by: str = "angle",
                        use_pallas: bool = True,
                        interpret: bool | None = None,
                        tile: str | None = None):
    """Batched Eq. 3/4 ranking straight off the resident corpus.

    x/grad: (Q, D); store: CorpusStore; idx: (Q, B) int32 neighbor ids
    (may contain -1 padding — clamped here, masked by ``valid``); valid:
    (Q, B) bool; tile: optional override spec for the autotuned
    rows-per-grid-step (e.g. ``":16"``). Returns (key (Q, B) f32,
    in_range (Q, B) bool)."""
    if not use_pallas:
        return neighbor_rank_fused_ref(x, grad, store, jnp.maximum(idx, 0),
                                       valid, alpha=alpha, rank_by=rank_by)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = autotune.resolve(
        "neighbor_rank_fused", q=int(idx.shape[0]), m=int(idx.shape[1]),
        d=int(store.dim), dtype=store.dtype,
        override=autotune.parse_tile(tile))
    raw = neighbor_rank_fused_pallas(
        x, grad, store.kernel_rows(), jnp.maximum(idx, 0).astype(jnp.int32),
        dim=store.dim, rank_by=rank_by, interpret=interpret, bt=cfg.bt)
    return keys_from_raw(raw, valid, alpha, rank_by)
