"""Double-buffered multi-row DMA gather for the wide-block fused kernels.

The fused kernels keep the corpus in ``pl.ANY`` (HBM) and gather ``bt``
rows per grid step with explicit per-row async copies into a (2, bt, Dp)
VMEM scratch tile:

    slot 0             slot 1
    [tile t compute]   [tile t+1 DMA in flight]

Step ``t`` issues tile ``t+1``'s copies *before* waiting on its own rows,
so the next gather overlaps the current tile's (bt, ·) matmuls. Grids are
linearized to 1-D by the callers so the tile index is just ``program_id``.

The source is the store's kernel payload (``CorpusStore.kernel_rows``):
float32 rows padded to a multiple of 128 lanes. Mosaic slices an HBM array
one row at a time only when the row is a whole number of (1, 128) 32-bit
tiles — a 40-wide row, or any 16- or 8-bit row, is refused.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class RowGather:
    """Per-row async copies of ``idx``-selected rows of ``src`` into one
    slot of a double-buffered VMEM scratch.

    idx_ref:     scalar-prefetch ref holding the flat (n_tiles * bt,) row
                 ids (callers pad/linearize)
    src_ref:     (N, Dp) float32 kernel payload in ANY memory
    scratch_ref: (2, bt, Dp) VMEM scratch
    sem_ref:     (2, bt) DMA semaphores
    """

    def __init__(self, idx_ref, src_ref, scratch_ref, sem_ref, bt: int):
        self.idx_ref = idx_ref
        self.src_ref = src_ref
        self.scratch_ref = scratch_ref
        self.sem_ref = sem_ref
        self.bt = bt

    def _dma(self, slot, tile, j):
        return pltpu.make_async_copy(
            self.src_ref.at[self.idx_ref[tile * self.bt + j]],
            self.scratch_ref.at[slot, j],
            self.sem_ref.at[slot, j])

    def start(self, slot, tile):
        for j in range(self.bt):
            self._dma(slot, tile, j).start()

    def wait(self, slot, tile):
        for j in range(self.bt):
            self._dma(slot, tile, j).wait()

    def rows(self, t, dim: int):
        """Run the warm-up / prefetch / wait schedule for grid step ``t``
        and return its (bt, dim) f32 rows (the lane padding dropped)."""
        nt = pl.num_programs(0)

        @pl.when(t == 0)
        def _():
            self.start(0, 0)

        @pl.when(t + 1 < nt)
        def _():
            self.start((t + 1) % 2, t + 1)

        self.wait(t % 2, t)
        return self.scratch_ref[t % 2][:, :dim]


def gather_scratch(bt: int, width: int):
    """The scratch shapes one ``RowGather`` needs: the (2, bt, width) f32
    double buffer and its (2, bt) DMA semaphores."""
    return [pltpu.VMEM((2, bt, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2, bt))]
