"""The one matmul the search-path kernels use."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dot_f32(a, b):
    """float32 matmul at full float32 precision. Mosaic's default contract
    precision for f32 operands is lower: on a TPU v5e it left DeepFM
    scores up to 7e-5 off a float32 evaluation, enough to reorder nearly
    tied neighbours and send the graph walk elsewhere."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
