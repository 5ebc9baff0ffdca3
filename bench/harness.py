"""The benchmark harness: finds a cell's files by name, sets the cell up,
hands it to its traffic's driver, checks what the timed path returned
against the plain reference, and assembles the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, search settings, limits
    bench/references/<family>.py    the measure's plain reference
    bench/references/search.py      the search's plain reference
    bench/adapters/<family>.py      the program's measure on those weights
    bench/traffic/<traffic>.json    a traffic mix: its driver and parameters
    bench/drivers/<driver>.py       one general generator per kind of loop
    bench/metrics/<metric>.py       one reader per per-layer metric

The deployment's index (items, users, the L2 graph) comes from the
configuration's own data seed and is built once per checkout into
``bench/.cache/index/<key>``; every ``--seed`` reuses it. ``--seed`` draws
the users a run queries, the order of its arrivals, and the measure's
weights: the configuration's fixed weights with their hidden units
permuted, which leaves the function, and so the work of a search, the
same on every seed.

Two pieces are the configuration's own, each with a default:

- ``permute(params, key, m) -> params`` in the family's reference: a
  permutation of its hidden units that keeps the function. Without it
  the weights are a ReLU chain ``{"w": [...], "b": [...]}`` whose hidden
  layers' outputs are permuted (``references/mlp_common.py:
  permute_chain``), and weights with other keys are refused.
- ``"queries": {"kind": "history", "length": L}`` in the configuration
  file: each user's query row is the item rows of its last L clicks
  under the corpus's click model, oldest first
  (``data.history_queries``). Without it the query rows are the index's
  users. Either way the index, and its key, are the corpus's and the
  graph's alone.

Only a cell that reports ``recall_at_10`` runs the exhaustive top-k
behind it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Optional

import numpy as np

import data

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# seconds of a run's window that a --trace 1 run records
TRACE_SECONDS = 2.0
# most answers the reference rescores per run (a seeded sample beyond it)
SAMPLE = 16384
# most answers the reference search repeats per run
SEARCH_SAMPLE = 1024
# items a step of the exhaustive top-k scores
BLOCK = 4096
# queries a step of the exhaustive top-k scores
Q_BLOCK = 128
GENERATOR = "bench/data.py:cluster_corpus v1"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# programs JAX compiled or read from its cache so far (start_jax counts)
COMPILES = [0]


def _count_compile(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES[0] += 1


def start_jax(tag: str = "bench"):
    """Import JAX with its persistent compilation cache at the fixed
    ``bench/.cache/jax`` of this checkout (the program takes its cache
    directory from ``JAX_COMPILATION_CACHE_DIR``), every program cached
    however fast it compiled, so only a checkout's first run compiles.
    Returns JAX's devices, or None, after saying why, where they are no
    TPU: there is no CPU fallback."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".cache",
                                                          "jax")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[{tag}] no TPU: JAX found {len(devs)} {devs[0].platform} "
              f"device(s); the benchmark has no CPU fallback",
              file=sys.stderr)
        return None
    from repro.utils import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    print(f"[{tag}] {devs[0].device_kind} x{len(devs)}, jax "
          f"{jax.__version__}, compile cache {cache}", file=sys.stderr)
    return devs


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path under a module name made from it."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A JAX key from any non-negative seed, 64 bits and over included."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    ref: Any
    adapter: Any
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict         # per-layer metric name -> reader module
    root: str

    @property
    def bench(self) -> str:
        return os.path.join(self.root, "bench")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = os.path.join(root, "bench")
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench, "traffic",
                                     w["traffic"] + ".json"))
    family = config["measure"]["family"]
    per_layer = [m for m in spec["per_layer"] if _applies(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        driver=load_module(os.path.join(bench, "drivers",
                                        traffic["driver"] + ".py")),
        ref=load_module(os.path.join(bench, "references", family + ".py")),
        adapter=load_module(os.path.join(bench, "adapters",
                                         family + ".py")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
        readers={m["name"]: load_module(os.path.join(
            bench, "metrics", m["name"] + ".py")) for m in per_layer},
        root=root)


# ---------------------------------------------------------------------------
# set-up: the deployment's index, the weights, the engine
# ---------------------------------------------------------------------------

def index_key(config: dict) -> str:
    """Cache key of a configuration's index: the content of what builds
    it (corpus and graph), so configurations that share a corpus and a
    graph share one build."""
    blob = json.dumps({"corpus": config["corpus"], "graph": config["graph"],
                       "generator": GENERATOR}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _save_npy(path: str, arr: np.ndarray) -> None:
    tmp = path + ".tmp.npy"
    np.save(tmp, arr)
    os.replace(tmp, path)


def ensure_index(cell: Cell) -> str:
    """The index directory of the cell's configuration, built on a miss
    (generate items and users, ``build_l2_graph``, ``save_index``)."""
    from repro.graph import build_l2_graph, save_index
    path = os.path.join(cell.bench, ".cache", "index",
                        index_key(cell.config))
    done = [os.path.join(path, f) for f in
            ("meta.json", "items.npy", "users.npy")]
    if all(os.path.exists(p) for p in done):
        return path
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    c, g = cell.config["corpus"], cell.config["graph"]
    t0 = time.perf_counter()
    users, items = data.cluster_corpus(
        c["users"], c["items"], c["clusters"], c["dim"], c["data_seed"])
    log(f"cold set-up: generated {c['items']} items and {c['users']} users "
        f"in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    graph = build_l2_graph(items, m=g["m"], k_construction=g["k_construction"],
                           seed=g["build_seed"])
    log(f"cold set-up: L2 graph built in {time.perf_counter() - t0:.3f} s "
        f"(max degree {graph.max_degree})")
    _save_npy(os.path.join(path, "users.npy"), users)
    _save_npy(os.path.join(path, "items.npy"), items)
    save_index(path, graph)
    return path


def make_weights(cell: Cell, seed: int):
    """The measure's weights, on the device in one jitted call: the
    configuration's weights (``weight_seed``) with their hidden units
    permuted by ``seed``, by the family's ``permute`` or, where it has
    none, the ReLU chain's. A permutation of hidden units leaves the
    function unchanged, so every seed does the same work."""
    import jax
    from references.mlp_common import permute_chain
    m = cell.config["measure"]
    permute = getattr(cell.ref, "permute", permute_chain)

    @jax.jit
    def build(base_key, perm_key):
        return permute(cell.ref.init(base_key, m), perm_key, m)

    return build(jax.random.PRNGKey(m["weight_seed"]), seed_key(seed))


def query_rows(config: dict, items: np.ndarray,
               index_path: str) -> np.ndarray:
    """The configuration's query row of every user: the index's users,
    or with ``"queries": {"kind": "history", "length": L}`` the item rows
    of each user's last L clicks."""
    q = config.get("queries")
    if q is None:
        return np.load(os.path.join(index_path, "users.npy"))
    if q.get("kind") != "history" or set(q) != {"kind", "length"}:
        raise ValueError(f"unknown queries {q!r}; known: "
                         f"{{'kind': 'history', 'length': L}}")
    c = config["corpus"]
    return data.history_queries(items, c["users"], c["clusters"],
                                c["data_seed"], int(q["length"]))


class Env:
    """What a driver gets: the set-up system, the seeded streams, the
    window's clock and the profiler."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t_start = trace, t_start
        self.traffic = cell.traffic
        self.t_window: Optional[float] = None
        self.window_compiles: Optional[int] = None
        self.trace_seconds = TRACE_SECONDS
        self.trace_dir = os.path.join(cell.bench, ".cache", "trace",
                                      cell.name)
        self._annotation = None

    def rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream])

    def open_window(self) -> float:
        """Set-up ends here; returns the window's start on the host
        clock. What set-up left alive is moved out of the garbage
        collector's sight first, as a server does once it has loaded, so
        a full collection in the window scans only the window's own
        objects."""
        gc.collect()
        gc.freeze()
        self.compiles_at_open = COMPILES[0]
        self.t_window = time.perf_counter()
        log(f"set-up: window opens {self.t_window - self.t_start:.3f} s "
            f"after process start (engine ready at "
            f"{self.t_ready - self.t_start:.3f} s, then warm-up)")
        return self.t_window

    def last_answer(self) -> None:
        """The window's last answer is in: logs the programs compiled
        since the window opened, which should be none. What the driver
        does after this, such as reading back the compiled search, is
        not the window's. Every driver calls it: ``run_cell`` refuses
        one that does not, so this line is in every run's log."""
        n = self.window_compiles = COMPILES[0] - self.compiles_at_open
        log(f"programs compiled or read from the cache from the window's "
            f"start to its last answer: {n}")

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench/window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def span(self, name: str):
        """A host annotation on the trace clock (free when not traced)."""
        if self._annotation is None:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    def setup(self) -> None:
        """Load the index and put it on the device, then ``set_seed``."""
        import jax.numpy as jnp
        from repro.graph import load_index
        t0 = time.perf_counter()
        path = ensure_index(self.cell)
        index = load_index(path)
        self.index_path = path
        self.users = query_rows(self.cell.config, index.base, path)
        self.query_dim = int(self.users.shape[1])
        self.n_items = int(index.base.shape[0])
        self.degree = int(index.neighbors.shape[1])
        self.entry = int(index.entry)
        self.neighbors = jnp.asarray(index.neighbors)
        self.base = jnp.asarray(index.base)
        del index
        log(f"set-up: index loaded and placed in "
            f"{time.perf_counter() - t0:.3f} s (N={self.n_items}, "
            f"B={self.degree}), {t0 - self.t_start:.3f} s after process "
            f"start")
        self.set_seed(self.seed)

    def set_seed(self, seed: int) -> None:
        """The seed's weights, the program's measure on them, the engine
        and its prepared store."""
        from repro.core import EngineOptions, SearchConfig, build_engine
        cfg = self.cell.config
        self.seed = seed
        self.weights = make_weights(self.cell, seed)
        self.measure = self.cell.adapter.program_measure(
            self.weights, cfg["measure"])
        self.params = self.measure.params
        search = SearchConfig(**cfg["search"])
        self.k = search.k
        self.engine = build_engine(self.measure, search,
                                   EngineOptions(**cfg["engine_options"]))
        self.store = self.engine.prepare_store(self.base)
        self.t_ready = time.perf_counter()

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        for name in ("engine", "store", "neighbors", "base", "measure",
                     "params", "runtime"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def bad_rows(ids: np.ndarray, scores: np.ndarray, n_items: int) -> int:
    """Answers that are malformed on their face: an id outside the
    corpus, an id twice in one answer, a score that is not finite, or
    scores out of descending order."""
    bad = (ids < 0) | (ids >= n_items)
    srt = np.sort(ids, axis=1)
    dup = np.zeros_like(bad)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    unsorted = np.zeros_like(bad)
    unsorted[:, 1:] = scores[:, 1:] > scores[:, :-1]
    row = (bad | dup | unsorted | ~np.isfinite(scores)).any(axis=1)
    return int(row.sum())


def sample_rows(out: dict, seed: int, n: int = SAMPLE) -> np.ndarray:
    """A seeded sample of the completed answers, the longest search
    (most expansions) always in it."""
    total = len(out["n_iters"])
    if total == 0:
        return np.zeros((0,), np.int64)
    rng = np.random.default_rng([seed, 99])
    idx = rng.choice(total, min(n, total), replace=False)
    longest = int(np.argmax(out["n_iters"]))
    if longest not in idx:
        idx[0] = longest
    return np.sort(idx)


class Reference:
    """The plain reference over the deployment's index as stored, jitted
    once per process: exhaustive top-k, per-pair scores at a stated
    precision, and the graph search of ``references/search.py``."""

    def __init__(self, cell: Cell, index_path: str):
        import jax
        import jax.numpy as jnp
        items = np.load(os.path.join(index_path, "items.npy"))
        self.cell = cell
        self.m = cell.config["measure"]
        self.n = items.shape[0]
        block = self.block = BLOCK
        arrays = np.load(os.path.join(index_path, "arrays.npz"))
        self.neighbors = jnp.asarray(arrays["neighbors"])
        self.entry = int(load_json(os.path.join(index_path,
                                                "meta.json"))["entry"])
        self._searches = {}
        nblk = -(-self.n // block)
        pad = np.zeros((nblk * block - self.n, items.shape[1]), np.float32)
        self.items = jnp.asarray(items)
        self.blocks = jnp.asarray(np.concatenate([items, pad])).reshape(
            nblk, block, items.shape[1])
        ref, m, n = cell.ref, self.m, self.n

        def topk(params, blocks, qb, k, precision):
            def body(carry, xs):
                best_s, best_i = carry
                xb, base = xs
                s = ref.block_scores(params, xb, qb, m, precision)
                ids = base + jnp.arange(block, dtype=jnp.int32)
                s = jnp.where(ids[None, :] < n, s, -jnp.inf)
                cs = jnp.concatenate([best_s, s], axis=1)
                ci = jnp.concatenate(
                    [best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
                top, pos = jax.lax.top_k(cs, k)
                return (top, jnp.take_along_axis(ci, pos, axis=1)), None
            init = (jnp.full((qb.shape[0], k), -jnp.inf, jnp.float32),
                    jnp.full((qb.shape[0], k), -1, jnp.int32))
            bases = jnp.arange(blocks.shape[0], dtype=jnp.int32) * block
            (s, i), _ = jax.lax.scan(body, init, (blocks, bases))
            return s, i

        def pairs(params, items, ids, qs, precision):
            x = items[ids.reshape(-1)]
            q = jnp.repeat(qs, ids.shape[1], axis=0)
            return ref.pair_scores(params, x, q, m, precision).reshape(
                ids.shape)

        self._topk = jax.jit(topk, static_argnames=("k", "precision"))
        self._pairs = jax.jit(pairs, static_argnames=("precision",))

    def topk(self, params, queries: np.ndarray, k: int,
             precision: str = "float32") -> np.ndarray:
        import jax.numpy as jnp
        out = []
        for s in range(0, len(queries), Q_BLOCK):
            qb = queries[s:s + Q_BLOCK]
            pad = Q_BLOCK - len(qb)
            qj = jnp.asarray(np.pad(qb, ((0, pad), (0, 0))))
            _, i = self._topk(params, self.blocks, qj, k=k,
                              precision=precision)
            out.append(np.asarray(i)[:len(qb)])
        return np.concatenate(out) if out else np.zeros((0, k), np.int32)

    def pair_scores(self, params, ids: np.ndarray, queries: np.ndarray,
                    precision: str = "float32") -> np.ndarray:
        import jax.numpy as jnp
        safe = np.clip(ids, 0, self.n - 1).astype(np.int32)
        return np.asarray(self._pairs(params, self.items,
                                      jnp.asarray(safe),
                                      jnp.asarray(queries),
                                      precision=precision))

    def search(self, params, queries: np.ndarray, search: dict,
               lanes: int = 256) -> np.ndarray:
        """The reference search's answer ids, ``lanes`` queries at a
        time, over the neighbour lists and entry point as stored."""
        import jax
        import jax.numpy as jnp
        from references.search import make_search
        key = json.dumps(search, sort_keys=True)
        if key not in self._searches:
            self._searches[key] = jax.jit(make_search(
                self.cell.ref, self.m, search))
        run = self._searches[key]
        out = []
        for s in range(0, len(queries), lanes):
            qb = queries[s:s + lanes]
            qj = jnp.asarray(np.pad(qb, ((0, lanes - len(qb)), (0, 0))))
            ids, _ = run(params, self.items, self.neighbors, self.entry, qj)
            out.append(np.asarray(ids)[:len(qb)])
        return (np.concatenate(out) if out
                else np.zeros((0, search["k"]), np.int32))


def search_miss(ids: np.ndarray, ref_ids: np.ndarray) -> float:
    """Mean over answers of the share of the reference search's valid ids
    that the answer does not hold."""
    miss = []
    for a, r in zip(ids, ref_ids):
        r = set(r[r >= 0].tolist())
        miss.append(len(r - set(a.tolist())) / max(len(r), 1))
    return float(np.mean(miss)) if miss else math.inf


def reports_recall(cell: Cell, k: int) -> bool:
    """Whether ``recall_at_<k>`` is among the cell's end-to-end metrics:
    only then does the check run the exhaustive top-k."""
    return any(m["name"] == f"recall_at_{k}" for m in cell.end_to_end)


def compare(env: Env, out: dict, ref: Reference) -> dict:
    """The numbers compared, each with its limit, and recall@k.

    score_gap    widest |returned score - float32 reference score| over
                 the sampled answers' valid ids
    search_miss  mean share of the reference search's top-k ids missing
                 from the answer, over a smaller seeded sample
    bad_rows     completed answers malformed on their face (all of them)
    missing      requests due in the window that never completed

    recall@k is read over score_gap's sampled answers, and only in a cell
    that reports it (else recall is None and no exhaustive top-k runs)."""
    lim = env.cell.config["limits"]
    done = out["completed"]
    idx = sample_rows(done, env.seed)
    ids, scores = done["ids"][idx], done["scores"][idx]
    qs = env.users[done["user"][idx]]
    valid = (ids >= 0) & (ids < env.n_items)
    if len(idx) and valid.any():
        exact = ref.pair_scores(env.weights, ids, qs)
        gap = float(np.max(np.abs(scores - exact)[valid]))
    else:
        gap = math.inf
    recall = None
    if reports_recall(env.cell, env.k):
        true = ref.topk(env.weights, qs, env.k)
        hits = [len(set(a.tolist()) & set(b.tolist()))
                for a, b in zip(ids, true)]
        recall = float(np.mean(hits) / env.k) if hits else 0.0
    few = sample_rows(done, env.seed, SEARCH_SAMPLE)
    ref_ids = ref.search(env.weights, env.users[done["user"][few]],
                         env.cell.config["search"])
    checks = {
        "score_gap": {"value": gap, "limit": lim["score_gap"]},
        "search_miss": {"value": search_miss(done["ids"][few], ref_ids),
                        "limit": lim["search_miss"]},
        "bad_rows": {"value": bad_rows(done["ids"], done["scores"],
                                       env.n_items),
                     "limit": lim["bad_rows"]},
        "missing": {"value": int(out["missing"]), "limit": lim["missing"]},
    }
    return {"checks": checks, "recall": recall, "sample": len(idx), "ids": ids,
            "queries": qs, "valid": valid}


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader gets. ``trace`` is the trace reduction
    (None when nothing was traced); ``out`` the driver's outcome."""
    cell: Cell
    out: dict
    trace: Optional[dict]
    peak: dict
    degree: int

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def ref(self):
        return self.cell.ref


def load_peak(kind: str, root: str = ROOT) -> dict:
    peaks = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(peaks)}")
    return peaks[kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info():
    import jax
    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = ROOT,
             require_tpu: bool = True) -> tuple:
    """Run one cell once. Returns (result dict, lines for stderr)."""
    cell = find_cell(workload, root)
    devs, device = device_info()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell.chips):
        raise RuntimeError(
            f"cell {workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{device['count']} {device['platform']} device(s)")
    peak = load_peak(device["kind"], root) if require_tpu else None
    env = Env(cell, seed, seconds, trace, t_start)
    env.setup()
    out = cell.driver.run(env)
    if env.window_compiles is None:
        raise RuntimeError(f"driver {cell.traffic['driver']!r} returned "
                           f"without calling env.last_answer()")
    setup_s = env.t_window - t_start
    device["memory_peak_bytes"] = memory_peak(devs)
    red = None
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(env.trace_dir), devices=cell.chips)
        shutil.rmtree(env.trace_dir, ignore_errors=True)
        if red["overlap_s"] > 0:
            log(f"trace: ops inside a loop overlap by {red['overlap_s']} s; "
                f"the loop's own time reads that much low")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    degree = env.degree
    env.release()
    t0 = time.perf_counter()
    ref = Reference(cell, env.index_path)
    cmp = compare(env, out, ref)
    if cmp["recall"] is None:
        scan = f"no exhaustive top-k (recall@{env.k} not reported)"
    else:
        scan = (f"the same searched exhaustively, "
                f"recall@{env.k} {cmp['recall']:.4f}")
    log(f"reference: {cmp['sample']} answers rescored, {scan}, in "
        f"{time.perf_counter() - t0:.3f} s")
    checks = cmp["checks"]
    # a number whose limit is null is read and printed, not compared
    correct = all(c["value"] <= c["limit"] for c in checks.values()
                  if c["limit"] is not None)
    values = dict(out["e2e"], setup_s=setup_s)
    if cmp["recall"] is not None:
        values[f"recall_at_{env.k}"] = cmp["recall"]
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = MetricContext(cell, out, red, peak, degree)
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["missing"]), "metrics": metrics,
              "device": device}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    lines = [f"check {k}: {c['value']} limit {c['limit']}"
             for k, c in checks.items()]
    return result, lines
