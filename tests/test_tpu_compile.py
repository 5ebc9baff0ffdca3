"""Deviceless compiles of the search path's Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib, so a described (not attached) v5e chip
refuses what a real one would: block shapes off the (8, 128) tiling, row
DMAs narrower than a lane tile, primitives Mosaic cannot lower. Interpret
mode, which every other kernel test uses, sees none of these. Each case
compiles at the paper's widths (D=40 DeepFM, fm 8 / deep 32, hidden
(64, 64)) in both a single-block and a multi-block shape, and checks that
the kernel survived into the program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (CorpusStore, EngineOptions, SearchConfig,
                        build_engine, make_family_measure)
from repro.kernels.deepfm_grad import deepfm_value_and_grad
from repro.kernels.deepfm_grad_fused import deepfm_grad_fused
from repro.kernels.deepfm_score import deepfm_score
from repro.kernels.deepfm_score_fused import deepfm_score_fused
from repro.kernels.mlp_grad import mlp_grad_fused, mlp_value_and_grad
from repro.kernels.mlp_score import mlp_score, mlp_score_fused
from repro.kernels.neighbor_rank import neighbor_rank
from repro.kernels.neighbor_rank_fused import neighbor_rank_fused

D = 40            # paper item/user width (configs/guitar_deepfm.py)
FM = 8
B = 48            # graph degree: M=24 pruned, symmetrized to 2M
N = 100_000       # corpus rows of the chip smoke


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a deviceless compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    """shape, dtype -> ShapeDtypeStruct on one described v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    return make


def _like(tree, spec):
    return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), tree)


@pytest.fixture(scope="module")
def deepfm():
    return make_family_measure("deepfm", jax.random.PRNGKey(0), D)


@pytest.fixture(scope="module")
def mlp():
    return make_family_measure("mlp", jax.random.PRNGKey(0), D)


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kernels(fn, *args) -> set:
    """Names of the Pallas kernels in the lowered program."""
    return set(re.findall(r'kernel_name = "([a-z_]+)"',
                          jax.jit(fn).lower(*args).as_text()))


PALLAS = dict(use_pallas=True, interpret=False)


@pytest.mark.parametrize("rank_by", ["angle", "projection"])
@pytest.mark.parametrize("q", [32, 256])
def test_neighbor_rank_compiles(spec, rank_by, q):
    def f(x, g, nv, valid):
        return neighbor_rank(x, g, nv, valid, rank_by=rank_by, **PALLAS)
    _compile(f, spec((q, D)), spec((q, D)), spec((q, B, D)),
             spec((q, B), jnp.bool_))


# M = Q·C at budget C=8: 256 is one 256-row block, 1024 and 4096 are many
@pytest.mark.parametrize("m", [256, 1024, 4096])
def test_deepfm_score_compiles(spec, deepfm, m):
    def f(params, cand, q):
        return deepfm_score(cand, q, params["mlp"], fm_dim=FM, **PALLAS)
    _compile(f, _like(deepfm.params, spec), spec((m, D)), spec((m, D)))


@pytest.mark.parametrize("q", [32, 256])
def test_deepfm_grad_compiles(spec, deepfm, q):
    def f(params, x, qs):
        return deepfm_value_and_grad(x, qs, params["mlp"], fm_dim=FM,
                                     **PALLAS)
    _compile(f, _like(deepfm.params, spec), spec((q, D)), spec((q, D)))


@pytest.mark.parametrize("m", [256, 1024, 4096])
def test_mlp_score_compiles(spec, mlp, m):
    def f(params, cand, q):
        return mlp_score(cand, q, params, **PALLAS)
    _compile(f, _like(mlp.params, spec), spec((m, D)), spec((m, D)))


@pytest.mark.parametrize("q", [32, 256])
def test_mlp_grad_compiles(spec, mlp, q):
    def f(params, x, qs):
        return mlp_value_and_grad(x, qs, params, **PALLAS)
    _compile(f, _like(mlp.params, spec), spec((q, D)), spec((q, D)))


def _kernel_store(spec, dtype: str) -> CorpusStore:
    """A store already in the kernel layout (as engines hold it)."""
    return CorpusStore(spec((N, 128)), None, dtype, kernel_dim=D)


@pytest.mark.parametrize("bt", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_kernels_compile(spec, deepfm, mlp, dtype, bt):
    q, m = 32, 256
    store = _kernel_store(spec, dtype)
    tile = f":{bt}"

    def rank(store, x, g, idx, valid):
        return neighbor_rank_fused(x, g, store, idx, valid, tile=tile,
                                   **PALLAS)

    def deepfm_both(params, store, fid, idx, qs, qs_flat, mask):
        grad = deepfm_grad_fused(store, fid, qs, params["mlp"], fm_dim=FM,
                                 tile=tile, **PALLAS)
        score = deepfm_score_fused(store, idx, qs_flat, params["mlp"],
                                   fm_dim=FM, tile=tile, mask=mask, **PALLAS)
        return grad, score

    def mlp_both(params, store, fid, idx, qs, qs_flat):
        grad = mlp_grad_fused(store, fid, qs, params, tile=tile, **PALLAS)
        score = mlp_score_fused(store, idx, qs_flat, params, tile=tile,
                                **PALLAS)
        return grad, score

    _compile(rank, store, spec((q, D)), spec((q, D)),
             spec((q, B), jnp.int32), spec((q, B), jnp.bool_))
    for fn, measure, extra in ((deepfm_both, deepfm, [spec((m,), jnp.bool_)]),
                               (mlp_both, mlp, [])):
        text = _compile(fn, _like(measure.params, spec), store,
                        spec((q,), jnp.int32), spec((m,), jnp.int32),
                        spec((q, D)), spec((m, D)), *extra)
        assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("fused", [False, True])
def test_engine_search_compiles(spec, deepfm, fused):
    """The whole jitted search of the chip smoke: N=100k, Q=32, D=40. The
    CPU backend routes 'auto' to the jnp refs, so the test names Pallas."""
    cfg = SearchConfig(k=10, ef=64, budget=8)
    opts = EngineOptions(rank_impl="pallas", measure_impl="pallas",
                         grad_impl="pallas", interpret=False, fused=fused)
    eng = build_engine(deepfm, cfg, opts)
    q = 32
    args = (_like(deepfm.params, spec), spec((N, D)),
            spec((N, B), jnp.int32), spec((q, D)), spec((q,), jnp.int32))
    _compile(eng.search, *args)
    suffix = "_fused" if fused else ""
    # every stage of the step runs as its kernel, none as a jnp ref
    assert _kernels(eng.search, *args) == {
        f"deepfm_score{suffix}", f"deepfm_grad{suffix}",
        f"neighbor_rank{suffix}"}
