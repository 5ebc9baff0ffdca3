"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as a subpackage: kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper with interpret/fallback switches), ref.py
(pure-jnp oracle used by the allclose test sweeps).

  deepfm_score   fused candidate-batch DeepFM measure evaluation (the GUITAR
                 search inner loop — FM dot + 2-layer MLP in one VMEM pass)
  neighbor_rank  fused gradient ranking: diffs, norms, cosine / projection
                 per frontier (the angle and the adaptive α·θ mask of
                 Eq. 3/4 are taken on its output — Mosaic has no acos)
  deepfm_score_fused / neighbor_rank_fused
                 index-fused variants: (corpus, idx) in, scores out — the
                 row gather runs inside the kernel via scalar-prefetch
                 indexing over the store's lane-padded f32 kernel payload
                 (core/corpus.py), so the pre-gathered (Q·C, D) /
                 (Q, B, D) blocks never hit HBM
  deepfm_grad / deepfm_grad_fused
                 analytic forward+backward for the GUITAR grad stage (the
                 cost the paper charges double) — fp32 refs bit-match
                 vmap(jax.value_and_grad); the fused variant gathers the
                 frontier row by scalar-prefetch index and hands the
                 dequantized row to the rank stage
  mlp_score / mlp_grad
                 the generic MLP measure promoted to first-class kernel
                 status (score + analytic grad, pre-gathered AND fused
                 entry points, any MLP depth) — resolved via the
                 core/bundles.py measure-kernel registry
  embedding_bag  FBGEMM-TBE-style gather + segment-sum bag lookup (recsys)
  decode_attn    flash-decode GQA attention over a KV cache (LM serving)
  flash_attn     causal flash-attention forward (FA-2 schedule) — the §Perf
                 cell-A lever for the LM train/prefill memory term
"""
