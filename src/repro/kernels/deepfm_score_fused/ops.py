"""Public wrapper for the index-fused DeepFM scorer: backend pick, id
clamping, param casting, tile resolution, and the shared-query (1, D)
fast path."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.corpus import CorpusStore
from repro.kernels import autotune
from repro.kernels.deepfm_score.ops import _check_depth
from repro.kernels.deepfm_score_fused.kernel import deepfm_score_fused_pallas
from repro.kernels.deepfm_score_fused.ref import deepfm_score_fused_ref


def deepfm_score_fused(store: CorpusStore, idx: jax.Array, query: jax.Array,
                       mlp_params: dict, fm_dim: int = 8,
                       use_pallas: bool = True,
                       interpret: bool | None = None,
                       tile: str | None = None,
                       mask: jax.Array | None = None) -> jax.Array:
    """store: resident corpus; idx: (M,) int32 candidate row ids (may contain
    -1 padding — clamped here; mask the scores at the call site); query:
    (M, D) user rows or a single (D,) vector shared by every candidate;
    mlp_params: {'w': [w0, w1, w2], 'b': [b0, b1, b2]}; tile: optional
    override spec for the autotuned rows-per-grid-step (e.g. ``":16"``);
    mask: optional (M,) bool — the adaptive engine's per-lane prefix mask:
    masked rows return -inf, and the Pallas grid skips the MLP for tiles
    whose ``bt`` rows are ALL masked (the same tail-masking path that pads
    M up to a multiple of ``bt``). Returns (M,) f32."""
    idx = jnp.maximum(idx, 0).astype(jnp.int32)
    w = [jnp.asarray(a, jnp.float32) for a in mlp_params["w"]]
    b = [jnp.asarray(a, jnp.float32) for a in mlp_params["b"]]
    _check_depth(w)
    if not use_pallas:
        out = deepfm_score_fused_ref(store, idx, query, w[0], b[0], w[1],
                                     b[1], w[2], b[2], fm_dim)
        # jnp ref is dense — masked rows are computed then overwritten
        # (XLA:CPU has no tile-skip to win; the adaptive speedup on this
        # path comes from fewer loop iterations)
        return out if mask is None else jnp.where(mask, out, -jnp.inf)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = autotune.resolve(
        "deepfm_score_fused", q=0, m=int(idx.shape[0]), d=int(store.dim),
        dtype=store.dtype, override=autotune.parse_tile(tile))
    q_shared = query.ndim == 1
    q_arg = query[None, :] if q_shared else query
    return deepfm_score_fused_pallas(
        store.kernel_rows(), idx, q_arg.astype(jnp.float32),
        w[0], b[0], w[1], b[1], w[2], b[2],
        fm_dim=fm_dim, deep_dim=store.dim - fm_dim, q_shared=q_shared,
        interpret=interpret, bt=cfg.bt, mask=mask)
