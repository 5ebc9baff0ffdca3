"""Plain float32 reference of the MLP matching measure of the SL2G line
of work (Tan et al., WSDM 2020):

    f(x, q) = sigmoid(MLP([x, q]))

with ReLU hidden layers and one linear output. Imports nothing of the
program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references.mlp_common import (apply_layers, init_mlp, layer_flops,
                                    matmul)


def dims(m: dict):
    return [m["item_dim"] + m["query_dim"], *m["hidden"], 1]


def item_dim(m: dict) -> int:
    return m["item_dim"]


def query_dim(m: dict) -> int:
    return m["query_dim"]


def init(key, m: dict) -> dict:
    return init_mlp(key, dims(m))


def forward_flops(m: dict) -> int:
    return layer_flops(dims(m))


def pair_scores(params, x, q, m: dict, precision: str = "float32"):
    h = jnp.concatenate([x, q], axis=-1)
    return jax.nn.sigmoid(apply_layers(params, h, precision)[:, 0])


def block_scores(params, xb, qb, m: dict, precision: str = "float32"):
    """qb (Qb, Dq), xb (Nb, D) -> (Qb, Nb); the first layer's matmul over
    [x, q] is split into its item and user halves."""
    d = m["item_dim"]
    w0, b0 = params["w"][0], params["b"][0]
    hx = matmul(xb, w0[:d], precision)
    hq = matmul(qb, w0[d:], precision)
    h = jax.nn.relu(hq[:, None, :] + hx[None, :, :] + b0)
    return jax.nn.sigmoid(apply_layers(params, h, precision, first=1)[..., 0])
