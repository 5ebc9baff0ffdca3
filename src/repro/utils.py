"""Small shared utilities: PRNG handling, tree helpers, timing, the
persistent compilation cache."""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np


REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is used
    as is — JAX reads it itself and nothing is set here. Otherwise the
    cache lives at ``<repo>/.jax_cache``: a fixed path, because the path
    is part of what a later process must find again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def key_iter(seed: int) -> Iterator[jax.Array]:
    """Infinite stream of fresh PRNG keys."""
    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def tree_size(tree: Any) -> int:
    """Total number of parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_cast(tree: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def is_axes_leaf(x: Any) -> bool:
    """A logical-axes annotation: tuple of str/None (possibly empty)."""
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def assert_tree_match(params: Any, axes: Any) -> None:
    """Assert a params tree and its logical-axes tree line up: same structure
    (axes tuples are leaves) and per-leaf rank agreement."""
    ta = jax.tree_util.tree_structure(params)
    tb = jax.tree_util.tree_structure(axes, is_leaf=is_axes_leaf)
    if ta != tb:
        raise ValueError(f"pytree structure mismatch:\n{ta}\nvs\n{tb}")
    pl = jax.tree_util.tree_leaves(params)
    al = jax.tree_util.tree_leaves(axes, is_leaf=is_axes_leaf)
    for p, a in zip(pl, al):
        if hasattr(p, "ndim") and len(a) != p.ndim:
            raise ValueError(f"axes rank mismatch: param shape {p.shape} vs axes {a}")


def timeit(fn: Callable[[], Any], iters: int = 10, warmup: int = 2) -> float:
    """Median wall-clock microseconds per call (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(n: int, m: int) -> int:
    """Smallest multiple of m >= n (sharding-divisibility padding)."""
    return ((n + m - 1) // m) * m


def pad_to(x: np.ndarray, size: int, axis: int = 0, fill=0) -> np.ndarray:
    """Pad `x` along `axis` up to `size` with `fill`."""
    if x.shape[axis] >= size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return np.pad(x, widths, constant_values=fill)
