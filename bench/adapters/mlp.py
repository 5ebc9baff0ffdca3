"""The program's MLP measure, built on the benchmark's weights."""
from __future__ import annotations

import dataclasses


def program_measure(params: dict, m: dict):
    import jax
    from repro.core.measures import mlp_measure
    measure = mlp_measure(jax.random.PRNGKey(0), m["item_dim"],
                          m["query_dim"], hidden=tuple(m["hidden"]))
    return dataclasses.replace(measure, params=params)
