"""The page fetch the paged kernels had before one wait a block, kept as
the oracle of ``ops/paged_attention``'s ``_start_pages`` and
``_land_pages``, which both paged kernels call: a test swaps these in
for them, runs the kernel in the Pallas interpreter in ``ON_WAIT`` mode,
and holds the shipped fetch to this one's output bit for bit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# the TPU interpreter that lands a DMA only when a wait on its semaphore
# needs its bytes, into buffers that start as NaN, and prints a semaphore
# left with a count at the kernel's exit
ON_WAIT = pltpu.InterpretParams(dma_execution_mode="on_wait", uninitialized_memory="nan")


def committed_start(streams, tables_ref, sem, layer, row, first, n, slot):
    """A loop of dynamic length starts a DMA a page a stream ..."""
    def one(j, _):
        pid = tables_ref[row, first + j]
        for hbm, buf in streams:
            pltpu.make_async_copy(hbm.at[layer, pid], buf.at[slot, j], sem.at[slot]).start()
        return _
    jax.lax.fori_loop(0, n, one, None)


def committed_land(streams, sem, n, slot, cleared):
    """... another waits for them page by page, a third zeroes the rest."""
    def wait(j, _):
        for hbm, buf in streams:
            pltpu.make_async_copy(hbm.at[0, 0], buf.at[slot, j], sem.at[slot]).wait()
        return _
    jax.lax.fori_loop(0, n, wait, None)

    def clear(j, _):
        cleared[slot, j] = jnp.zeros(cleared.shape[2:], cleared.dtype)
        return _
    jax.lax.fori_loop(n, cleared.shape[1], clear, None)
