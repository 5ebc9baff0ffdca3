"""Plain float32 reference of the DeepFM matching measure (GUITAR,
arXiv:2312.16828, Sec. 4):

    f(x, q) = sigmoid(<x_fm, q_fm> + MLP([q_deep, x_deep]))

x is an item row and q a user row, both [fm | deep]; the MLP has ReLU
hidden layers and one linear output. Imports nothing of the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references.mlp_common import (apply_layers, init_mlp, layer_flops,
                                    matmul)


def dims(m: dict):
    return [2 * m["deep_dim"], *m["hidden"], 1]


def item_dim(m: dict) -> int:
    return m["fm_dim"] + m["deep_dim"]


def query_dim(m: dict) -> int:
    return m["fm_dim"] + m["deep_dim"]


def init(key, m: dict) -> dict:
    return init_mlp(key, dims(m))


def forward_flops(m: dict) -> int:
    """FLOPs of f on one (item, user) pair: the FM dot and the MLP."""
    return 2 * m["fm_dim"] + layer_flops(dims(m))


def pair_scores(params, x, q, m: dict, precision: str = "float32"):
    """f on matching rows: x (P, D), q (P, D) -> (P,)."""
    fd = m["fm_dim"]
    fm = jnp.sum(x[:, :fd] * q[:, :fd], axis=-1)
    h = jnp.concatenate([q[:, fd:], x[:, fd:]], axis=-1)
    return jax.nn.sigmoid(fm + apply_layers(params, h, precision)[:, 0])


def block_scores(params, xb, qb, m: dict, precision: str = "float32"):
    """f on every pair of a user block and an item block: qb (Qb, D),
    xb (Nb, D) -> (Qb, Nb). The first layer's matmul over [q_deep,
    x_deep] is split into its user and item halves."""
    fd, dd = m["fm_dim"], m["deep_dim"]
    w0, b0 = params["w"][0], params["b"][0]
    fm = matmul(qb[:, :fd], xb[:, :fd].T, precision)
    hq = matmul(qb[:, fd:], w0[:dd], precision)              # (Qb, H)
    hx = matmul(xb[:, fd:], w0[dd:], precision)              # (Nb, H)
    h = jax.nn.relu(hq[:, None, :] + hx[None, :, :] + b0)
    out = apply_layers(params, h, precision, first=1)[..., 0]
    return jax.nn.sigmoid(fm + out)
