"""What a configuration owns: its family's permutation of the weights and
its query rows, each with a default that leaves the declared cells as
they were.

- ``make_weights`` of both declared cells is bit-identical to the chain
  permutation the harness had before a family could own one (kept here as
  the oracle), and keeps ``pair_scores``;
- weights with keys other than a chain's, from a family with no
  ``permute``, are refused;
- without ``queries`` the query rows are the index's users, and the index
  key is the corpus's and the graph's whatever ``queries`` says;
- a history row holds item rows of its user's clicks, drawn as the
  program's ``make_interactions`` draws them;
- the check rescores ``SAMPLE`` answers, runs the exhaustive top-k over
  the same answers only where the cell reports recall, and repeats the
  search over ``SEARCH_SAMPLE``;
- a test-only family with a non-chain parameter, its own ``permute`` and
  history queries runs end to end at a CPU size and is correct.
"""
import json
import os
import types

import jax
import numpy as np
import pytest

import data
import harness
from conftest import ROOT

CELLS = ["deepfm-twitch.batch", "mlp-twitch.batch"]
SEEDS = [0, 7, 2**33 + 5]
# the index key both declared configurations had before queries were a
# configuration's own (their corpus and graph)
TWITCH_KEY = "371548b522d1f2b4"

HIST = "histgate-tiny"
HIST_CELL = HIST + ".batch"

# A measure whose weights are no ReLU chain: a gate on each hidden unit,
# sigmoid(relu([x, q] W0 + b0) * gate W1 + b1), over a history query.
HIST_REF = '''"""Test-only family: a gated one-layer MLP over [item, history]."""
import jax
import jax.numpy as jnp

from references.mlp_common import init_mlp, layer_flops, matmul


def dims(m):
    return [m["item_dim"] + m["query_dim"], m["hidden"], 1]


def item_dim(m):
    return m["item_dim"]


def query_dim(m):
    return m["query_dim"]


def init(key, m):
    k_mlp, k_gate = jax.random.split(key)
    p = init_mlp(k_mlp, dims(m))
    p["gate"] = 1.0 + 0.5 * jax.random.normal(k_gate, (m["hidden"],),
                                              jnp.float32)
    return p


def permute(params, key, m):
    perm = jax.random.permutation(key, m["hidden"])
    (w0, w1), (b0, b1) = params["w"], params["b"]
    return {"w": [w0[:, perm], w1[perm, :]], "b": [b0[perm], b1],
            "gate": params["gate"][perm]}


def forward_flops(m):
    return layer_flops(dims(m))


def _head(params, h, precision):
    h = jax.nn.relu(h) * params["gate"]
    return jax.nn.sigmoid(matmul(h, params["w"][1], precision)
                          + params["b"][1])[..., 0]


def pair_scores(params, x, q, m, precision="float32"):
    h = matmul(jnp.concatenate([x, q], axis=-1), params["w"][0], precision)
    return _head(params, h + params["b"][0], precision)


def block_scores(params, xb, qb, m, precision="float32"):
    d, w0 = m["item_dim"], params["w"][0]
    h = (matmul(qb, w0[d:], precision)[:, None, :]
         + matmul(xb, w0[:d], precision)[None, :, :] + params["b"][0])
    return _head(params, h, precision)
'''

HIST_ADAPTER = '''"""The program's generic measure on the test-only family's weights."""


def _score(p, x, q):
    import jax
    import jax.numpy as jnp
    h = jnp.concatenate([x, q], axis=-1) @ p["w"][0] + p["b"][0]
    h = jax.nn.relu(h) * p["gate"]
    return jax.nn.sigmoid(h @ p["w"][1] + p["b"][1])[..., 0]


def program_measure(params, m):
    from repro.core.measures import Measure
    return Measure("histgate", _score, params)
'''


def _parent_make_weights(cell, seed):
    """The chain permutation as the harness built it before a family
    could define its own: the oracle of the default path."""
    import jax
    m = cell.config["measure"]

    @jax.jit
    def build(base_key, perm_key):
        p = cell.ref.init(base_key, m)
        ws, bs = list(p["w"]), list(p["b"])
        keys = jax.random.split(perm_key, len(ws) - 1)
        for i in range(len(ws) - 1):
            perm = jax.random.permutation(keys[i], ws[i].shape[1])
            ws[i], bs[i] = ws[i][:, perm], bs[i][perm]
            ws[i + 1] = ws[i + 1][perm, :]
        return {"w": ws, "b": bs}

    return build(jax.random.PRNGKey(m["weight_seed"]),
                 harness.seed_key(seed))


def _bits(tree):
    return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


@pytest.fixture(scope="module")
def hist_root(tiny_root):
    """tiny_root with the test-only family and its history cell added,
    on the TINY Twitch corpus and graph (the same index)."""
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "configs", "deepfm-twitch.json")) as f:
        base = json.load(f)
    cfg = dict(base, name=HIST,
               measure={"family": "histgate", "item_dim": 40,
                        "query_dim": 3 * 40, "hidden": 32, "weight_seed": 0},
               queries={"kind": "history", "length": 3},
               kernels={"rank": "neighbor_rank"})
    files = {"references/histgate.py": HIST_REF,
             "adapters/histgate.py": HIST_ADAPTER,
             f"configs/{HIST}.json": json.dumps(cfg)}
    for rel, body in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(body)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    if HIST not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append(dict(spec["configs"][0], name=HIST,
                                    file=f"bench/configs/{HIST}.json"))
        spec["workloads"].append({"name": HIST_CELL, "config": HIST,
                                  "traffic": "batch", "chips": 1,
                                  "why": "test-only history family"})
        for m in spec["end_to_end"]:
            if m["name"] in ("qps", "recall_at_10"):
                m["workloads"].append(HIST_CELL)
        with open(path, "w") as f:
            json.dump(spec, f)
    return tiny_root


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", CELLS)
def test_default_weights_bit_identical_to_chain_oracle(cell, seed):
    c = harness.find_cell(cell, ROOT)
    got, want = harness.make_weights(c, seed), _parent_make_weights(c, seed)
    assert set(got) == {"w", "b"}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(_bits(got), _bits(want)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("cell", CELLS + [HIST_CELL])
def test_permute_keeps_pair_scores(hist_root, cell):
    c = harness.find_cell(cell, hist_root)
    m = c.config["measure"]
    base = c.ref.init(jax.random.PRNGKey(m["weight_seed"]), m)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(256, c.ref.item_dim(m))).astype(np.float32)
    q = rng.normal(size=(256, c.ref.query_dim(m))).astype(np.float32)
    want = np.asarray(c.ref.pair_scores(base, x, q, m))
    for seed in SEEDS:
        w = harness.make_weights(c, seed)
        assert set(w) == set(base)
        assert not np.array_equal(np.asarray(w["w"][0]),
                                  np.asarray(base["w"][0]))
        got = np.asarray(c.ref.pair_scores(w, x, q, m))
        assert np.max(np.abs(got - want)) <= 1e-6


def test_extra_keys_without_permute_are_refused(hist_root):
    full = harness.find_cell(HIST_CELL, hist_root)
    cell = types.SimpleNamespace(config=full.config,
                                 ref=types.SimpleNamespace(init=full.ref.init))
    with pytest.raises(ValueError, match="permute"):
        harness.make_weights(cell, 1)


def test_index_key_is_corpus_and_graph_only():
    for name in ("deepfm-twitch", "mlp-twitch"):
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            cfg = json.load(f)
        assert harness.index_key(cfg) == TWITCH_KEY
        hist = dict(cfg, queries={"kind": "history", "length": 20})
        assert harness.index_key(hist) == TWITCH_KEY


def test_default_users_and_history_rows(hist_root):
    envs = {}
    for cell in ("deepfm-twitch.batch", HIST_CELL):
        env = harness.Env(harness.find_cell(cell, hist_root), 5, 1.0, False,
                          0.0)
        env.setup()
        envs[cell] = env
    c = envs["deepfm-twitch.batch"].cell.config["corpus"]
    users, items = data.cluster_corpus(c["users"], c["items"], c["clusters"],
                                       c["dim"], c["data_seed"])
    env = envs["deepfm-twitch.batch"]
    assert env.users.dtype == np.float32 and env.query_dim == c["dim"]
    assert np.array_equal(env.users.view(np.uint32), users.view(np.uint32))
    hist = envs[HIST_CELL]
    assert hist.users.shape == (c["users"], 3 * c["dim"])
    assert hist.query_dim == 3 * c["dim"]
    assert hist.index_path == env.index_path
    assert np.array_equal(hist.users, data.history_queries(
        items, c["users"], c["clusters"], c["data_seed"], 3))


def test_history_rows_are_the_users_clicks():
    """Every history row is an item row, and as often from the user's own
    cluster as the clicks of the program's make_interactions are."""
    from repro.data.synthetic import make_interactions
    n_users, n_items, n_clusters, dim, length = 700, 3000, 16, 40, 5
    _, items = data.cluster_corpus(n_users, n_items, n_clusters, dim, 9)
    u_cl, i_cl = data.cluster_ids(n_users, n_items, n_clusters, dim, 9)
    rows = data.history_queries(items, n_users, n_clusters, 9, length)
    assert rows.shape == (n_users, length * dim) and rows.dtype == np.float32
    where = {items[j].tobytes(): j for j in range(n_items)}
    picked = np.array([[where[r.tobytes()] for r in row.reshape(length, dim)]
                       for row in rows])
    own = i_cl[picked] == u_cl[:, None]
    inter = make_interactions(n_users, n_items, 400000, n_clusters, dim, 9)
    assert np.array_equal(inter["item_init"], items)
    clicked = inter["labels"] == 1
    want = np.mean(u_cl[inter["user_ids"][clicked]]
                   == i_cl[inter["item_ids"][clicked]])
    # 3,500 draws: one standard error is 0.0075
    assert abs(own.mean() - want) < 0.03, (own.mean(), want)
    # off the user's cluster the picks spread over the other clusters
    off = np.bincount(i_cl[picked][~own], minlength=n_clusters)
    assert (off > 0).sum() == n_clusters
    # another data seed draws other histories
    _, items2 = data.cluster_corpus(n_users, n_items, n_clusters, dim, 10)
    assert not np.array_equal(
        rows, data.history_queries(items2, n_users, n_clusters, 10, length))


def test_history_queries_refuse_unknown_keys():
    for q in ({"kind": "history", "length": 3, "seed": 1},
              {"kind": "session", "length": 3}):
        with pytest.raises(ValueError, match="unknown queries"):
            harness.query_rows({"queries": q}, np.zeros((4, 2)), "")


def test_cluster_ids_are_cluster_corpus_draws():
    """The clusters line up with cluster_corpus's rows: users and items
    of one cluster share its centre, so their means agree."""
    n, k, dim = 20000, 16, 40
    users, items = data.cluster_corpus(n, n, k, dim, 2)
    u_cl, i_cl = data.cluster_ids(n, n, k, dim, 2)
    mu = np.stack([users[u_cl == c].mean(0) for c in range(k)])
    mi = np.stack([items[i_cl == c].mean(0) for c in range(k)])
    dist = np.linalg.norm(mu[:, None] - mi[None], axis=-1)
    assert (dist.argmin(axis=1) == np.arange(k)).all()
    assert dist.diagonal().max() < 0.5


@pytest.mark.parametrize("recall", [True, False])
def test_check_sample_sizes(recall):
    """score_gap rescores SAMPLE answers, the exhaustive top-k covers the
    same answers where the cell reports recall and runs nowhere else,
    and the reference search repeats SEARCH_SAMPLE."""
    n, k = harness.SAMPLE + 500, 10
    rng = np.random.default_rng(0)
    ids = np.tile(np.arange(k, dtype=np.int32), (n, 1))
    done = {"ids": ids, "scores": np.zeros((n, k), np.float32),
            "user": rng.integers(0, 50, n), "n_iters": rng.integers(1, 9, n)}
    seen = {}

    class Ref:
        def pair_scores(self, params, ids, qs):
            seen["rescored"] = len(ids)
            return np.zeros(ids.shape, np.float32)

        def topk(self, params, qs, k):
            seen["top_k"] = len(qs)
            return np.tile(np.arange(k), (len(qs), 1))

        def search(self, params, qs, search):
            seen["searched"] = len(qs)
            return np.tile(np.arange(k), (len(qs), 1))

    limits = {"score_gap": 0, "search_miss": 0, "bad_rows": 0, "missing": 0}
    env = types.SimpleNamespace(
        cell=types.SimpleNamespace(
            config={"limits": limits, "search": {}},
            end_to_end=[{"name": "recall_at_10"}] if recall else []),
        seed=3, users=np.zeros((50, 4), np.float32), n_items=100,
        weights=None, k=k)
    cmp = harness.compare(env, {"completed": done, "missing": 0}, Ref())
    want = {"rescored": harness.SAMPLE, "searched": harness.SEARCH_SAMPLE}
    if recall:
        want["top_k"] = harness.SAMPLE
    assert seen == want
    assert cmp["recall"] == (1.0 if recall else None)


def test_history_family_runs_correct(hist_root):
    res, lines = harness.run_cell(HIST_CELL, 2**32 + 17, 1.0, False, 0.0,
                                  root=hist_root, require_tpu=False)
    text = "\n".join(lines)
    assert res["correct"], text
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["search_miss"]["value"] == 0, text
    assert list(res)[-1] == "checks"
