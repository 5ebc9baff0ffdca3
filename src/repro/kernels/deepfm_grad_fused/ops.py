"""Public wrapper for the index-fused DeepFM grad kernel: backend pick, id
clamping, and param casting."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.corpus import CorpusStore
from repro.kernels import autotune
from repro.kernels.deepfm_grad.ops import check_deepfm_mlp_depth
from repro.kernels.deepfm_grad_fused.kernel import deepfm_grad_fused_pallas
from repro.kernels.deepfm_grad_fused.ref import deepfm_grad_fused_ref


def deepfm_grad_fused(store: CorpusStore, idx: jax.Array, query: jax.Array,
                      mlp_params: dict, fm_dim: int = 8,
                      use_pallas: bool = True,
                      interpret: bool | None = None,
                      tile: str | None = None):
    """store: resident corpus; idx: (Q,) int32 frontier ids (may contain -1
    padding — clamped here; inactive lanes are masked downstream by the
    engine); query: (Q, D) per-lane user rows; mlp_params: {'w': [w0, w1,
    w2], 'b': [b0, b1, b2]}; tile: optional override spec for the autotuned
    rows-per-grid-step (e.g. ``":16"``). Returns (vals (Q,), grads (Q, D),
    x (Q, D)) where ``x`` is the dequantized frontier row block (feeds the
    rank stage — no second gather)."""
    idx = jnp.maximum(idx, 0).astype(jnp.int32)
    w = [jnp.asarray(a, jnp.float32) for a in mlp_params["w"]]
    b = [jnp.asarray(a, jnp.float32) for a in mlp_params["b"]]
    check_deepfm_mlp_depth(w)
    if not use_pallas:
        return deepfm_grad_fused_ref(store, idx, query, w[0], b[0], w[1],
                                     b[1], w[2], b[2], fm_dim)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = autotune.resolve(
        "deepfm_grad_fused", q=int(idx.shape[0]), m=0, d=int(store.dim),
        dtype=store.dtype, override=autotune.parse_tile(tile))
    return deepfm_grad_fused_pallas(
        store.kernel_rows(), idx, query.astype(jnp.float32),
        w[0], b[0], w[1], b[1], w[2], b[2],
        fm_dim=fm_dim, deep_dim=store.dim - fm_dim, interpret=interpret,
        bt=cfg.bt)
