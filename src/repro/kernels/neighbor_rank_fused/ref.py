"""Pure-jnp oracle for the index-fused gradient-ranking kernel.

Takes ``(store, idx)`` instead of pre-gathered neighbor vectors: the
gather-dequant runs *inside* the stage, so under jit it fuses into the
ranking math and the (Q, B, D) fp32 neighbor block never exists as an
engine-level intermediate. float32 residency defers to
``neighbor_rank_ref`` on the gathered rows — bit-exact with the
pre-gathered stage by construction (tests pin this); bf16/int8 residency
dequantizes on gather (bf16 via the integer widen-shift-bitcast pipeline —
see core/corpus.py — which on XLA:CPU is ~2.3x faster than the fp32
gather it replaces).
"""
from __future__ import annotations

from repro.core.corpus import CorpusStore
from repro.kernels.neighbor_rank.ref import neighbor_rank_ref


def neighbor_rank_fused_ref(x, grad, store: CorpusStore, idx, valid,
                            alpha: float = 1.01, rank_by: str = "angle"):
    """x: (Q, D) frontier; grad: (Q, D); store: resident corpus; idx: (Q, B)
    int32 row ids (clamped >= 0 by the caller); valid: (Q, B) bool.

    Returns (key (Q, B) f32, in_range (Q, B) bool) — same contract as
    ``neighbor_rank_ref`` on pre-gathered vectors."""
    return neighbor_rank_ref(x, grad, store.take(idx), valid,
                             alpha=alpha, rank_by=rank_by)
