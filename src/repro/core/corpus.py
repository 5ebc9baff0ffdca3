"""Quantized corpus residency for the expansion engine (DESIGN.md §8).

The expansion step is bandwidth-bound: every iteration gathers (Q, B, D)
neighbor rows for ranking and (Q·C, D) candidate rows for the measure — at
fp32 that traffic dominates search cost long before the MXU saturates (the
paper's whole premise is that measure evaluation is the bottleneck; at scale
the *bytes behind it* are). ``CorpusStore`` holds the corpus resident in
``float32``, ``bfloat16``, or per-row-scaled ``int8`` (the SPANN/DiskANN
trick for billion-scale residency) and centralizes the dequantize-on-gather
contract used by the index-fused kernels and the engine's ref fallbacks.

The store is a registered pytree, so it crosses ``jit`` / ``shard_map``
boundaries as an ordinary argument; the dtype tag is static aux data, so
engines specialize per residency format.

**Residency is a policy, not a constructor argument** (DESIGN.md §11):
``ResidencyPolicy`` selects between

- ``whole`` — the corpus lives device-resident in one ``(N, D)`` payload
  (everything above; today's behavior, bit-identical, the default); and
- ``paged`` — the payload stays on disk (``np.load(mmap_mode="r")``-backed
  page-aligned files, io v3) or host memory, carved into fixed-size row
  pages faulted on demand into an LRU page cache with a byte budget.
  ``PagedCorpusStore.take`` is page-fault-aware: inside jitted searches the
  gather runs as a ``jax.pure_callback`` into the host pager, returning the
  exact same dequantized float32 rows as the whole-resident ``take`` — so a
  paged search is bit-identical to a whole-resident one while its resident
  footprint stays bounded by ``cache_bytes`` instead of growing with N.

Both stores optionally carry a **tombstone bitmap** (packed uint32 words,
one bit per corpus row): streaming deletes (``graph/mutate.py``) mark rows
dead without rewriting the index, and the engine's pool insert scores
tombstoned candidates ``-inf`` — exactly the padded-row convention of the
sharded merge — so they can never surface in results.

Quantization layout (int8): ``q8[i] = round(x[i] / scale[i])`` with
``scale[i] = max|x[i]| / 127`` per row — reconstruction error is bounded by
``scale/2 = max|x_i| / 254`` per element (pinned by tests). Row scales keep
the format local: a single hot row with a large dynamic range cannot degrade
the whole corpus.

bfloat16 payloads are held as their **uint16 bit patterns**: XLA:CPU's
native bf16 gather scalarizes (measured *slower* than the fp32 gather it
was meant to halve), while a u16 gather + integer widen + shift + bitcast
is a pure-SIMD pipeline ~2.3x faster than the fp32 gather.

**Kernel layout** (DESIGN.md §8): the Pallas index-fused kernels gather
one row per DMA, and Mosaic slices an HBM array row by row only when a row
is whole (1, 128) tiles of a 32-bit type. ``kernel_rows()`` is that
payload: the dequantized float32 rows padded to a multiple of 128 lanes.
An engine whose fused stages run as Pallas kernels holds its store in this
layout (``with_kernel_layout``); the dtype tag stays, so bf16/int8
residency keeps its rounding, but every row costs ``Dp * 4`` bytes
whatever the dtype (512 B at D=40 — what XLA's (8, 128) tiling gives an
f32[N, 40] array anyway).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs.trace import NULL_TRACER

CORPUS_DTYPES = ("float32", "bfloat16", "int8")
LANES = 128                  # TPU vector lanes: the kernel layout's row unit
RESIDENCY_KINDS = ("whole", "paged")

_EPS = 1e-8


@dataclasses.dataclass(frozen=True)
class ResidencyPolicy:
    """How the corpus payload is held during search.

    kind:        'whole' (device-resident (N, D) payload, the default) |
                 'paged' (fixed-size row pages faulted on demand through an
                 LRU cache bounded by ``cache_bytes``)
    page_rows:   rows per page (paged only) — io v3 writes page-aligned
                 files so a page slice never straddles a read
    cache_bytes: LRU byte budget for resident page copies (paged only)

    Failure policy (paged only — DESIGN.md §12): a physical page read that
    raises ``OSError`` is retried up to ``max_retries`` times with
    exponential backoff (``retry_backoff_s * 2**attempt``); if every retry
    fails the pager *degrades* — it reads the whole payload once and serves
    all further gathers from memory (``stats.fallback == 'whole'``), unless
    that would exceed ``fallback_bytes`` (None = always allowed), in which
    case ``CorpusUnavailableError`` surfaces and the shard above this store
    is the fault domain that fails.
    """
    kind: str = "whole"
    page_rows: int = 4096
    cache_bytes: int = 64 << 20
    max_retries: int = 3
    retry_backoff_s: float = 0.001
    fallback_bytes: Optional[int] = None

    def __post_init__(self):
        if self.kind not in RESIDENCY_KINDS:
            raise ValueError(f"residency kind must be one of "
                             f"{RESIDENCY_KINDS}, got {self.kind!r}")
        if self.kind == "paged" and self.page_rows < 1:
            raise ValueError(f"page_rows must be >= 1, got {self.page_rows}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {self.max_retries}")


WHOLE = ResidencyPolicy()


def pack_bitmap(flags: np.ndarray) -> np.ndarray:
    """(N,) bool -> packed (ceil(N/32),) uint32 words (bit i of word i//32),
    the same layout as the engine's per-lane visited bitmap."""
    flags = np.asarray(flags, bool)
    n = flags.shape[0]
    pad = (-n) % 32
    if pad:
        flags = np.concatenate([flags, np.zeros(pad, bool)])
    bits = flags.reshape(-1, 32).astype(np.uint32)
    return (bits << np.arange(32, dtype=np.uint32)[None, :]).sum(
        axis=1, dtype=np.uint32)


def unpack_bitmap(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of ``pack_bitmap``: (W,) uint32 -> (n,) bool."""
    words = np.asarray(words, np.uint32)
    bits = (words[:, None] >> np.arange(32, dtype=np.uint32)[None, :]) & 1
    return bits.reshape(-1)[:n].astype(bool)


def bit_test_global(words: jax.Array, ids: jax.Array) -> jax.Array:
    """Packed global bitmap test: words (W,) uint32, ids (...,) int -> bool.
    Negative ids test bit 0 of word 0 (callers mask them separately)."""
    safe = jnp.maximum(ids, 0)
    w = jnp.take(words, safe >> 5, axis=0, mode="clip")
    return ((w >> (safe & 31).astype(jnp.uint32)) & 1).astype(jnp.bool_)


def quantize_rows_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization over the last axis.

    x: (..., D) float -> (q8 (..., D) int8, scales (..., 1) float32)."""
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scales = jnp.maximum(amax, _EPS) / 127.0
    q8 = jnp.clip(jnp.round(x / scales), -127, 127).astype(jnp.int8)
    return q8, scales.astype(jnp.float32)


def dequantize_rows_int8(q8: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of ``quantize_rows_int8`` (up to rounding error)."""
    return q8.astype(jnp.float32) * scales


def bf16_bits_to_f32(bits: jax.Array) -> jax.Array:
    """uint16 bf16 bit patterns -> float32 (widen, shift, bitcast — exact,
    and all-integer so it vectorizes on every backend)."""
    return lax.bitcast_convert_type(bits.astype(jnp.uint32) << 16,
                                    jnp.float32)


def f32_to_bf16_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint16 bf16 bit patterns (round via the bf16 cast)."""
    return lax.bitcast_convert_type(jnp.asarray(x).astype(jnp.bfloat16),
                                    jnp.uint16)


class CorpusStore:
    """Dtype-tagged resident corpus: (N, D) payload + optional row scales.

    ``data`` is float32, uint16 bf16 bit patterns, or int8; ``scales`` is
    (N, 1) float32 for int8 (None otherwise). ``take(ids)`` gathers +
    dequantizes to float32 rows for any integer ids shape — the reference
    gather used everywhere the Pallas index-fused kernels don't run.
    ``tombstones`` is an optional packed (ceil(N/32),) uint32 bitmap of
    deleted rows (streaming deletes — the engine scores them -inf).

    ``kernel_dim`` set means the kernel layout: ``data`` is the (N, Dp)
    float32 ``kernel_rows()`` payload, already dequantized, whose first
    ``kernel_dim`` lanes are the corpus (see the module docstring).
    """

    is_paged = False

    def __init__(self, data: jax.Array, scales: Optional[jax.Array],
                 dtype: str, tombstones: Optional[jax.Array] = None,
                 kernel_dim: Optional[int] = None):
        if dtype not in CORPUS_DTYPES:
            raise ValueError(f"corpus_dtype must be one of {CORPUS_DTYPES}, "
                             f"got {dtype!r}")
        self.data = data
        self.scales = scales
        self.dtype = dtype
        self.tombstones = tombstones
        self.kernel_dim = kernel_dim

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def dim(self) -> int:
        if self.kernel_dim is not None:
            return self.kernel_dim
        return self.data.shape[-1]

    def take(self, ids: jax.Array, in_bounds: bool = False) -> jax.Array:
        """Gather rows by id (any ids shape) -> (..., D) float32.

        ``in_bounds=True`` promises every id is already in [0, N): the
        gather then uses clip mode, dropping XLA's out-of-bounds select
        (bit-identical for valid ids, measurably cheaper on CPU). The
        engine's tile plan uses it — its ids are clamped upstream."""
        mode = "clip" if in_bounds else None
        rows = jnp.take(self.data, ids, axis=0, mode=mode)
        if self.kernel_dim is not None:
            return rows[..., :self.kernel_dim]
        if self.dtype == "bfloat16":
            return bf16_bits_to_f32(rows)
        if self.dtype == "int8":
            return rows.astype(jnp.float32) * jnp.take(self.scales, ids,
                                                       axis=0, mode=mode)
        return rows.astype(jnp.float32)

    def dequantize(self) -> jax.Array:
        """The full (N, D) float32 corpus (materializes!)."""
        if self.kernel_dim is not None:
            return self.data[:, :self.kernel_dim]
        if self.dtype == "bfloat16":
            return bf16_bits_to_f32(self.data)
        if self.dtype == "int8":
            return dequantize_rows_int8(self.data, self.scales)
        return self.data.astype(jnp.float32)

    def kernel_rows(self) -> jax.Array:
        """The (N, Dp) float32 payload the Pallas index-fused kernels DMA
        from: dequantized rows padded to ``Dp``, a multiple of 128 lanes.
        Free in the kernel layout; otherwise built here, one (N, Dp) pass
        per call — engines hold their store in the kernel layout so that
        this never runs inside a search."""
        if self.kernel_dim is not None:
            return self.data
        rows = self.dequantize()
        return jnp.pad(rows, ((0, 0), (0, -self.dim % LANES)))

    def with_kernel_layout(self) -> "CorpusStore":
        """This store in the kernel layout (a no-op if it already is)."""
        if self.kernel_dim is not None:
            return self
        return CorpusStore(self.kernel_rows(), None, self.dtype,
                           self.tombstones, kernel_dim=self.dim)

    def nbytes(self) -> int:
        """Resident payload bytes (data + scales)."""
        total = self.data.size * self.data.dtype.itemsize
        if self.scales is not None:
            total += self.scales.size * self.scales.dtype.itemsize
        return int(total)

    def with_tombstones(self, flags: Optional[np.ndarray]) -> "CorpusStore":
        """A view of this store with the given (N,) bool delete flags
        packed into the tombstone bitmap (None clears it)."""
        words = None if flags is None else jnp.asarray(pack_bitmap(flags))
        return CorpusStore(self.data, self.scales, self.dtype, words,
                           self.kernel_dim)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        layout = "" if self.kernel_dim is None else ", kernel layout"
        return (f"CorpusStore(n={self.data.shape[0]}, dim={self.dim}, "
                f"dtype={self.dtype}{layout})")


def _store_flatten(s: CorpusStore):
    return (s.data, s.scales, s.tombstones), (s.dtype, s.kernel_dim)


def _store_unflatten(aux, children):
    data, scales, tombstones = children
    return CorpusStore(data, scales, aux[0], tombstones, aux[1])


jax.tree_util.register_pytree_node(CorpusStore, _store_flatten,
                                   _store_unflatten)


# ---------------------------------------------------------------------------
# paged residency
# ---------------------------------------------------------------------------

class CorpusUnavailableError(RuntimeError):
    """The pager exhausted its retry budget AND could not degrade to whole
    residency — the corpus behind this store is effectively offline. The
    sharded runtime treats this as a shard failure (breaker strike)."""


@dataclasses.dataclass
class PageCacheStats:
    """Host-side pager accounting (benchmarks/residency.py reports these;
    the serving health line reports the failure counters)."""
    hits: int = 0
    faults: int = 0
    evictions: int = 0
    resident_bytes: int = 0
    peak_resident_bytes: int = 0
    retries: int = 0         # physical reads re-attempted after OSError
    io_errors: int = 0       # OSErrors observed (pre-retry, pre-fallback)
    fallback: str = ""       # "" = paged; "whole" = degraded to resident

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.faults
        return self.hits / total if total else 0.0


class _PageCache:
    """Host pager: fixed ``page_rows`` row pages over a payload array (an
    ``np.memmap`` from io v3's page-aligned files, or a host ndarray),
    faulted on demand into an LRU dict bounded by ``cache_bytes``. Pages
    needed by the in-flight gather are pinned — the budget evicts cold
    pages, never the working set — so a single gather larger than the
    budget still completes (peak_resident_bytes records the overshoot)."""

    def __init__(self, data: np.ndarray, scales: Optional[np.ndarray],
                 dtype: str, policy: ResidencyPolicy):
        if dtype not in CORPUS_DTYPES:
            raise ValueError(f"corpus_dtype must be one of {CORPUS_DTYPES}, "
                             f"got {dtype!r}")
        self.data = data
        self.scales = scales
        self.dtype = dtype
        self.policy = policy
        self.n, self.dim = data.shape
        self.page_rows = int(policy.page_rows)
        self.n_pages = -(-self.n // self.page_rows)
        self._pages: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        self.stats = PageCacheStats()
        # fault-injection surface: called as read_hook(pid, attempt) before
        # every physical read (pid == -1 for the whole-payload fallback read);
        # an OSError it raises is indistinguishable from a real I/O failure
        self.read_hook: Optional[Callable[[int, int], None]] = None
        self._whole: Optional[np.ndarray] = None
        self._whole_scales: Optional[np.ndarray] = None
        # telemetry (DESIGN.md §13): page_fault / fallback spans, emitted
        # with site="pager" and no rid (a fault serves whichever lanes
        # share the tick); NullTracer default = one attribute lookup on
        # the hit path. NOTE gathers run inside jax.pure_callback — the
        # tracer's deque append is thread-safe under the GIL.
        self.tracer = NULL_TRACER

    def _read_block(self, lo: int, hi: int, pid: int) -> tuple:
        """One physical read with bounded exponential-backoff retries —
        the first rung of the degradation ladder (DESIGN.md §12)."""
        last: Optional[OSError] = None
        for attempt in range(self.policy.max_retries + 1):
            if attempt:
                self.stats.retries += 1
                if self.policy.retry_backoff_s > 0:
                    time.sleep(self.policy.retry_backoff_s
                               * (1 << (attempt - 1)))
            try:
                if self.read_hook is not None:
                    self.read_hook(pid, attempt)
                payload = np.array(self.data[lo:hi])    # copy off the mmap
                scales = None if self.scales is None \
                    else np.array(self.scales[lo:hi])
                return payload, scales
            except OSError as err:
                self.stats.io_errors += 1
                last = err
        raise last

    def _fallback_whole(self, cause: OSError) -> None:
        """Retry budget exhausted on a page: degrade paged → whole (one bulk
        read, then every gather is memory-resident) or, if the payload
        exceeds ``fallback_bytes``, give up with CorpusUnavailableError."""
        nbytes = self.data.size * self.data.dtype.itemsize
        if self.scales is not None:
            nbytes += self.scales.size * self.scales.dtype.itemsize
        limit = self.policy.fallback_bytes
        if limit is not None and nbytes > limit:
            raise CorpusUnavailableError(
                f"page read failed after {self.policy.max_retries} retries "
                f"and the whole payload ({nbytes}B) exceeds "
                f"fallback_bytes={limit}") from cause
        tr = self.tracer
        t0 = time.perf_counter() if tr.enabled else 0.0
        try:
            self._whole, self._whole_scales = self._read_block(0, self.n, -1)
        except OSError as err:
            if tr.enabled:
                tr.emit("fallback", t0, time.perf_counter(), site="pager",
                        rows=self.n, failed=True)
            raise CorpusUnavailableError(
                f"page read failed after {self.policy.max_retries} retries "
                f"and the whole-payload fallback read failed too") from err
        if tr.enabled:
            tr.emit("fallback", t0, time.perf_counter(), site="pager",
                    rows=self.n)
        self.stats.fallback = "whole"
        self._pages.clear()                 # page copies are redundant now
        self.stats.resident_bytes = nbytes
        self.stats.peak_resident_bytes = max(self.stats.peak_resident_bytes,
                                             nbytes)

    def _fault(self, pid: int) -> None:
        s, e = pid * self.page_rows, min((pid + 1) * self.page_rows, self.n)
        tr = self.tracer
        t0 = time.perf_counter() if tr.enabled else 0.0
        errs0 = self.stats.io_errors
        try:
            payload, scales = self._read_block(s, e, pid)
        except OSError as err:
            if tr.enabled:
                tr.emit("page_fault", t0, time.perf_counter(), site="pager",
                        pid=int(pid), failed=True,
                        io_errors=self.stats.io_errors - errs0)
            self._fallback_whole(err)
            return
        if tr.enabled:
            kw = {"pid": int(pid), "rows": int(e - s)}
            n_err = self.stats.io_errors - errs0
            if n_err:            # retry-absorbed errors, visible in traces
                kw["io_errors"] = n_err
            tr.emit("page_fault", t0, time.perf_counter(), site="pager",
                    **kw)
        nbytes = payload.nbytes + (0 if scales is None else scales.nbytes)
        self._pages[pid] = (payload, scales, nbytes)
        self.stats.faults += 1
        self.stats.resident_bytes += nbytes
        self.stats.peak_resident_bytes = max(self.stats.peak_resident_bytes,
                                             self.stats.resident_bytes)

    def _evict_cold(self, pinned: set) -> None:
        while self.stats.resident_bytes > self.policy.cache_bytes:
            victim = next((p for p in self._pages if p not in pinned), None)
            if victim is None:
                break                               # working set > budget
            _, _, nbytes = self._pages.pop(victim)
            self.stats.evictions += 1
            self.stats.resident_bytes -= nbytes

    def _dequant(self, rows: np.ndarray,
                 scales: Optional[np.ndarray]) -> np.ndarray:
        # numpy twins of CorpusStore.take's dequant pipelines — elementwise
        # IEEE fp32 ops, so paged rows are bit-identical to whole-resident
        if self.dtype == "bfloat16":
            return (rows.astype(np.uint32) << 16).view(np.float32)
        if self.dtype == "int8":
            return rows.astype(np.float32) * scales
        return rows.astype(np.float32)

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """ids (any shape) -> (..., D) float32 dequantized rows; out-of-range
        ids clamp (the whole store's ``mode="clip"`` contract)."""
        shape = ids.shape
        flat = np.clip(np.asarray(ids, np.int64).reshape(-1), 0, self.n - 1)
        if self._whole is None:
            pids = flat // self.page_rows
            need = np.unique(pids)
            for pid in need:
                pid = int(pid)
                if self._whole is not None:
                    break                   # degraded mid-loop; serve below
                if pid in self._pages:
                    self._pages.move_to_end(pid)
                    self.stats.hits += 1
                else:
                    self._fault(pid)
        if self._whole is not None:
            # degraded to whole residency: pure in-memory gather, same
            # dequant pipeline, so results stay bit-identical
            srows = None if self._whole_scales is None \
                else self._whole_scales[flat]
            return self._dequant(self._whole[flat],
                                 srows).reshape(shape + (self.dim,))
        self._evict_cold(pinned=set(int(p) for p in need))
        out = np.empty((flat.size, self.dim), np.float32)
        for pid in need:
            m = pids == pid
            payload, scales, _ = self._pages[int(pid)]
            local = flat[m] - int(pid) * self.page_rows
            srows = None if scales is None else scales[local]
            out[m] = self._dequant(payload[local], srows)
        return out.reshape(shape + (self.dim,))

    def materialize(self) -> np.ndarray:
        """The full (N, D) float32 corpus straight off the backing files —
        bypasses (and never populates) the page cache."""
        return self._dequant(np.array(self.data),
                             None if self.scales is None
                             else np.array(self.scales))


class PagedCorpusStore:
    """Residency-policy twin of ``CorpusStore``: same ``take`` contract,
    but the payload lives behind a host ``_PageCache`` and gathers run as
    ``jax.pure_callback``s — usable inside jitted searches (the engine's
    tile plan issues ONE combined gather per step through this path).

    Registered as a pytree whose only array child is the tombstone bitmap;
    the pager itself rides as static aux data (hashed by identity), so each
    store instance compiles once and every call reuses the trace."""

    is_paged = True

    def __init__(self, cache: _PageCache,
                 tombstones: Optional[jax.Array] = None):
        self.cache = cache
        self.tombstones = tombstones

    @property
    def dtype(self) -> str:
        return self.cache.dtype

    @property
    def policy(self) -> ResidencyPolicy:
        return self.cache.policy

    @property
    def n(self) -> int:
        return self.cache.n

    @property
    def dim(self) -> int:
        return self.cache.dim

    @property
    def stats(self) -> PageCacheStats:
        return self.stats_snapshot()

    def stats_snapshot(self) -> PageCacheStats:
        return dataclasses.replace(self.cache.stats)

    def set_read_hook(self,
                      hook: Optional[Callable[[int, int], None]]) -> None:
        """Install a fault-injection read hook (see ``_PageCache.read_hook``;
        typically ``FaultPlan.pager_hook()``). None uninstalls."""
        self.cache.read_hook = hook

    def set_tracer(self, tracer) -> None:
        """Route pager spans (page_fault / fallback, site="pager") into an
        ``obs.Tracer``; pass ``NULL_TRACER`` to disable again."""
        self.cache.tracer = tracer

    def bind_registry(self, registry, shard: str = "0"):
        """Adapter into an ``obs.Registry``: pager counters/gauges are
        copied out of ``stats_snapshot()`` at exposition time — nothing
        is added to the fault path."""
        labels = {"shard": str(shard)}
        c_hits = registry.counter("repro_pager_hits_total",
                                  "page-cache hits", labelnames=("shard",))
        c_faults = registry.counter("repro_pager_faults_total",
                                    "page faults (physical page reads)",
                                    labelnames=("shard",))
        c_evic = registry.counter("repro_pager_evictions_total",
                                  "LRU page evictions",
                                  labelnames=("shard",))
        c_retry = registry.counter("repro_pager_retries_total",
                                   "physical reads re-attempted after "
                                   "OSError", labelnames=("shard",))
        c_ioerr = registry.counter("repro_pager_io_errors_total",
                                   "OSErrors observed by the pager",
                                   labelnames=("shard",))
        g_res = registry.gauge("repro_pager_resident_bytes",
                               "current page-cache footprint",
                               labelnames=("shard",))
        g_peak = registry.gauge("repro_pager_peak_resident_bytes",
                                "page-cache footprint high-water mark",
                                labelnames=("shard",))
        g_fall = registry.gauge("repro_pager_degraded",
                                "1 when degraded to whole residency",
                                labelnames=("shard",))

        def _collect():
            st = self.stats_snapshot()
            c_hits.labels(**labels).set_to(st.hits)
            c_faults.labels(**labels).set_to(st.faults)
            c_evic.labels(**labels).set_to(st.evictions)
            c_retry.labels(**labels).set_to(st.retries)
            c_ioerr.labels(**labels).set_to(st.io_errors)
            g_res.labels(**labels).set(st.resident_bytes)
            g_peak.labels(**labels).set(st.peak_resident_bytes)
            g_fall.labels(**labels).set(1.0 if st.fallback else 0.0)

        registry.register_collect(_collect)
        return registry

    def take(self, ids: jax.Array, in_bounds: bool = False) -> jax.Array:
        """Page-fault-aware gather: same (..., D) float32 rows as the
        whole-resident ``take`` (the pager's dequant pipelines are numpy
        twins of the jnp ones), faulting only the touched pages. The
        ``in_bounds`` promise is already the pager's behavior (clamp)."""
        ids = jnp.asarray(ids)
        out = jax.ShapeDtypeStruct(ids.shape + (self.dim,), jnp.float32)
        return jax.pure_callback(self.cache.gather, out, ids)

    def dequantize(self) -> jax.Array:
        """The full (N, D) float32 corpus (materializes — debugging / ground
        truth only; reads the backing store, never populates the cache)."""
        return jnp.asarray(self.cache.materialize())

    def nbytes(self) -> int:
        """RESIDENT bytes — the LRU cache's current footprint, not the
        backing payload (that's the whole point of paging)."""
        return int(self.cache.stats.resident_bytes)

    def with_tombstones(self,
                        flags: Optional[np.ndarray]) -> "PagedCorpusStore":
        words = None if flags is None else jnp.asarray(pack_bitmap(flags))
        return PagedCorpusStore(self.cache, words)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"PagedCorpusStore(n={self.n}, dim={self.dim}, "
                f"dtype={self.dtype}, page_rows={self.cache.page_rows}, "
                f"cache_bytes={self.policy.cache_bytes})")


def _paged_flatten(s: PagedCorpusStore):
    return (s.tombstones,), s.cache


def _paged_unflatten(cache, children):
    (tombstones,) = children
    return PagedCorpusStore(cache, tombstones)


jax.tree_util.register_pytree_node(PagedCorpusStore, _paged_flatten,
                                   _paged_unflatten)


def make_paged_store(data: np.ndarray, corpus_dtype: str,
                     policy: ResidencyPolicy,
                     scales: Optional[np.ndarray] = None,
                     tombstones: Optional[np.ndarray] = None
                     ) -> PagedCorpusStore:
    """Paged store over a payload already in residency format — typically
    ``np.load(..., mmap_mode="r")`` memmaps of io v3's page-aligned files
    (``scales`` required for int8). ``tombstones``: (N,) bool delete flags."""
    if corpus_dtype == "int8" and scales is None:
        raise ValueError("int8 paged residency requires per-row scales")
    cache = _PageCache(data, scales, corpus_dtype, policy)
    words = None if tombstones is None \
        else jnp.asarray(pack_bitmap(tombstones))
    return PagedCorpusStore(cache, words)


AnyCorpusStore = Union[CorpusStore, PagedCorpusStore]


def make_corpus_store(base: jax.Array, corpus_dtype: str = "float32",
                      residency: Optional[ResidencyPolicy] = None,
                      tombstones: Optional[np.ndarray] = None
                      ) -> AnyCorpusStore:
    """Quantize/cast an (N, D) float corpus into residency format under the
    given policy (None = whole, today's behavior). The paged path quantizes
    host-side and serves pages off the host array — file-backed pages (the
    bounded-RAM story) come from io v3 via ``load_corpus_store``."""
    if residency is not None and residency.kind == "paged":
        base_np = np.asarray(base, np.float32)
        if corpus_dtype == "float32":
            data, scales = base_np, None
        elif corpus_dtype == "bfloat16":
            data, scales = np.asarray(f32_to_bf16_bits(base_np)), None
        elif corpus_dtype == "int8":
            q8, sc = quantize_rows_int8(base_np)
            data, scales = np.asarray(q8), np.asarray(sc)
        else:
            raise ValueError(f"corpus_dtype must be one of {CORPUS_DTYPES}, "
                             f"got {corpus_dtype!r}")
        return make_paged_store(data, corpus_dtype, residency, scales,
                                tombstones)
    base = jnp.asarray(base)
    if corpus_dtype == "float32":
        data = base.astype(jnp.float32)
        scales = None
    elif corpus_dtype == "bfloat16":
        data = f32_to_bf16_bits(base)
        scales = None
    elif corpus_dtype == "int8":
        data, scales = quantize_rows_int8(base)
    else:
        raise ValueError(f"corpus_dtype must be one of {CORPUS_DTYPES}, "
                         f"got {corpus_dtype!r}")
    words = None if tombstones is None \
        else jnp.asarray(pack_bitmap(tombstones))
    return CorpusStore(data, scales, corpus_dtype, words)


def as_corpus_store(base: Union[jax.Array, AnyCorpusStore],
                    corpus_dtype: str = "float32") -> AnyCorpusStore:
    """Coerce an array or an existing store to residency format. A store
    already in the requested dtype passes through untouched (the serving
    path quantizes once, up front). A paged store never re-quantizes — a
    dtype mismatch there is a configuration error, not a conversion."""
    if isinstance(base, PagedCorpusStore):
        if base.dtype != corpus_dtype:
            raise ValueError(
                f"paged store holds {base.dtype!r} pages but the engine "
                f"wants {corpus_dtype!r}; rebuild the paged store in the "
                f"serving dtype (re-quantizing on the fly would materialize "
                f"the corpus and defeat paging)")
        return base
    if isinstance(base, CorpusStore):
        if base.dtype != corpus_dtype:
            store = make_corpus_store(base.dequantize(), corpus_dtype)
            store.tombstones = base.tombstones  # deletes survive requantize
            return store
        return base
    return make_corpus_store(base, corpus_dtype)
