"""Pieces shared by the plain references: the matmul at a stated
precision, ReLU MLP layers, the permutation of a ReLU chain's hidden
units, and the forward FLOPs of a layer stack. Imports nothing of the
program under test."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(a, b, precision: str):
    """float32 matmul at ``precision``: 'float32' is full float32
    (``HIGHEST``); 'bfloat16' rounds both operands to bfloat16 and
    accumulates in float32, one MXU pass, the control's precision."""
    if precision == "float32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def init_mlp(key, dims, bias_scale: float = 0.1) -> dict:
    """{'w': [...], 'b': [...]} with W ~ N(0, 1/fan_in), b ~ N(0,
    bias_scale^2): the layout and weight scale of the program's MLPs,
    with biases that are not zero so a stage that drops them shows."""
    ws, bs = [], []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        kw, kb = jax.random.split(k)
        ws.append(jax.random.normal(kw, (dims[i], dims[i + 1]), jnp.float32)
                  / math.sqrt(dims[i]))
        bs.append(bias_scale * jax.random.normal(kb, (dims[i + 1],),
                                                 jnp.float32))
    return {"w": ws, "b": bs}


def apply_layers(params: dict, h, precision: str, first: int = 0):
    """ReLU MLP from layer ``first`` on; the last layer is linear."""
    n = len(params["w"])
    for i in range(first, n):
        h = matmul(h, params["w"][i], precision) + params["b"][i]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def permute_chain(params: dict, key, m: dict) -> dict:
    """The weights of a ReLU chain ``{"w": [...], "b": [...]}`` with the
    units of every hidden layer permuted by ``key``: the same function.
    The permutation of a family whose reference defines no ``permute``;
    weights with any other key are refused, since their units are not a
    chain's."""
    if set(params) != {"w", "b"}:
        raise ValueError(
            f"weights with keys {sorted(params)} are no ReLU chain "
            f"{{'w', 'b'}}: the family's reference must define "
            f"permute(params, key, m)")
    ws, bs = list(params["w"]), list(params["b"])
    keys = jax.random.split(key, len(ws) - 1)
    for i in range(len(ws) - 1):
        perm = jax.random.permutation(keys[i], ws[i].shape[1])
        ws[i], bs[i] = ws[i][:, perm], bs[i][perm]
        ws[i + 1] = ws[i + 1][perm, :]
    return {"w": ws, "b": bs}


def layer_flops(dims) -> int:
    """Multiply-add FLOPs of one forward pass through a dense stack."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
