"""Paged decode attention over latent rows: every cached position of a row,
through one Mosaic call a layer.

A latent-attention model without a sparse selection (JoyAI-LLM-Flash, the
DeepSeek-V3 layer) reads, in every decode step and layer, EVERY latent row
``[c_kv | k_rope | 0]`` a row has cached (``ops/mla.py``: 576 values
padded to 640). All heads score the same row, so the absorbed form is one
product a block of positions for all heads at once:

    s_h(u) = (q_lat,h · c_kv(u) + q_rope,h · k_rope(u)) · scale      u < seq_len
    o_lat,h = sum_u softmax_u(s_h)(u) · c_kv(u)

:func:`paged_latent_attention` is ``ops/paged_attention``'s decode kernel
in this shape: one program a row, no grid axis over pages. The pool stays
in HBM whole (``[L, N+1, 1, page, W]``, the layer a scalar); the kernel
loops over the blocks of pages the row owns up to ``seq_len``
(``cdiv(seq_len, ppb * page)`` trips, read from the scalar-prefetched
lengths and block tables), fetches each page by a DMA of its own into a
double buffer in VMEM — block *i + 1*, at a row's end the next row's
first block, is in flight while block *i* is computed; the starts are
unrolled over the block's static pages and a full block lands with ONE
wait (:func:`_start_pages`, :func:`_land_pages`) — and keeps an
online softmax in float32 for all heads: ``[H, W] x [W, block]`` to score,
``[H, block] x [block, kv_lora_rank]`` to sum, one float32 product. It
returns o_lat ``[B, H,
kv_lora_rank]`` float32 and writes no pool, so XLA gives the pool no
layout of its own (``ops/paged_attention.py``'s header says why).

On the CPU the entry computes :func:`paged_latent_attention_ref` —
``mla.latent_attention`` over ``mla.row_pages`` masked to ``seq_len`` —
unless a test asks for the Pallas interpreter (``ops/backend.py``). The
kernel's device events are ``%paged_latent_attention.<n>`` custom calls
(the jitted wrapper and the ``pallas_call`` share the name).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops import mla
from gofr_tpu.ops.backend import INTERPRET, REFERENCE, kernel_mode

NEG_INF = -1e30
# positions one compute block and one DMA batch hold (640 KiB a slot at 640
# bf16 lanes). The last block of a row is computed whole and masked, yet on
# the v5e at 64 rows of 32 heads and 188k positions 512 read faster than 256
# and 128: the per-block cost outweighs the tail's
_BLOCK_TOKENS = 512
_LANES = 128  # the running max and sum are kept a lane row wide


def paged_latent_attention_ref(
    q: jnp.ndarray,  # [B, H, W] q_lat | q_rope | 0
    latent_pool: jnp.ndarray,  # [L, N+1, 1, page, W]
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] positions each row sees
    layer: jnp.ndarray,  # scalar int32
    *,
    scale: float,
    kv_lora_rank: int,
) -> jnp.ndarray:
    """A gather of the row's pages and dense latent attention under the
    length's mask: the oracle the kernel is tested against, and the CPU
    path. Returns o_lat [B, H, kv_lora_rank] float32."""
    rows = mla.row_pages(latent_pool, block_tables, layer)  # [B, M*page, W]
    keep = jnp.arange(rows.shape[1])[None, :] < seq_lens[:, None]
    return mla.latent_attention(q[:, None], rows, keep[:, None], scale, kv_lora_rank)[:, 0]


def _start_pages(pool_hbm, tables_ref, buf, sem, layer, row, first, n, slot):
    """Starts the DMAs of the ``n`` pages from entry ``first`` of ``row``'s
    block table into slot ``slot`` of the double buffer, a DMA a page,
    unrolled over the slot's static pages: a page costs its descriptor and
    no loop trip. A full block — every block of a row but its last —
    starts them under no predicate; a partial one puts each under its own."""
    ppb = buf.shape[1]

    def start(j):
        pid = tables_ref[row, first + j]
        pltpu.make_async_copy(pool_hbm.at[layer, pid], buf.at[slot, j], sem.at[slot]).start()

    @pl.when(n == ppb)
    def _whole():
        for j in range(ppb):
            start(j)

    @pl.when(n < ppb)
    def _partial():
        for j in range(ppb):
            pl.when(j < n)(functools.partial(start, j))


def _land_pages(pool_hbm, buf, sem, n, slot):
    """Waits for the ``n`` pages :func:`_start_pages` started into slot
    ``slot`` and zeroes the slot's pages past them. The DMAs count the
    bytes they land on the slot's one semaphore, so a full slot — every
    block of a row but its last — is ONE wait, described as the whole
    slot (its source a run of pages of the pool, for its shape alone); a
    partial block waits page by page."""
    ppb = buf.shape[1]

    @pl.when(n == ppb)
    def _whole():
        pltpu.make_async_copy(pool_hbm.at[0, pl.ds(0, ppb)], buf.at[slot], sem.at[slot]).wait()

    @pl.when(n < ppb)
    def _partial():
        for j in range(ppb):
            @pl.when(j < n)
            def _wait():
                pltpu.make_async_copy(pool_hbm.at[0, 0], buf.at[slot, j], sem.at[slot]).wait()

            # a page past the row's last holds what the slot held before: its
            # scores are masked, but a zero weight times a stale NaN is NaN
            @pl.when(j >= n)
            def _clear():
                buf[slot, j] = jnp.zeros(buf.shape[2:], buf.dtype)


def _kernel(
    seq_lens_ref,  # SMEM [B] (scalar prefetch)
    tables_ref,  # SMEM [B, M] (scalar prefetch)
    layer_ref,  # SMEM [1] (scalar prefetch)
    q_ref,  # VMEM [1, H, W]: this row's queries
    pool_hbm,  # HBM [L, N+1, 1, page, W]: the whole pool, never copied or sliced
    o_ref,  # VMEM [1, H, R] float32
    buf,  # VMEM [2, ppb, 1, page, W]: the double buffer
    sem,  # DMA (2,): one a slot
    slot_ref,  # SMEM [1]: the slot the next row starts in
    m_scr,  # VMEM [H, 128] float32
    l_scr,
    acc_scr,  # VMEM [H, R] float32
    *,
    scale: float,
    ppb: int,
):
    """One program a row. Its pages arrive ``ppb`` at a time by DMAs this
    kernel starts, into the slot of the double buffer that is not being
    computed on; the last block of a row starts the first block of the
    next row. The loop runs ``cdiv(seq_len, ppb * page)`` times: a row
    costs the pages it owns up to its length (a row of length 0 is read as
    one of length 1)."""
    layer = layer_ref[0]
    page, W = pool_hbm.shape[3:]
    H, R = acc_scr.shape
    bk = ppb * page
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def row_pages(row):
        return jnp.clip(pl.cdiv(seq_lens_ref[row], page), 1, tables_ref.shape[1])

    def block_pages(row, blk):
        return jnp.minimum(row_pages(row) - blk * ppb, ppb)

    def start_block(row, blk, slot):
        _start_pages(pool_hbm, tables_ref, buf, sem, layer, row, blk * ppb, block_pages(row, blk), slot)

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        start_block(0, 0, 0)

    seq_len = seq_lens_ref[b]
    nb = pl.cdiv(row_pages(b), ppb)
    slot0 = slot_ref[0]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]  # [H, W]

    def block(i, _):
        slot = (slot0 + i) % 2
        last = i + 1 == nb

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < B))
        def _prefetch():
            row = jnp.where(last, jnp.minimum(b + 1, B - 1), b)
            start_block(row, jnp.where(last, 0, i + 1), 1 - slot)

        _land_pages(pool_hbm, buf, sem, block_pages(b, i), slot)

        rows = buf[slot, :, 0].reshape(bk, W)  # [bk, W]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [H, bk]
        seen = i * bk + jax.lax.broadcasted_iota(jnp.int32, (H, bk), 1) < seq_len
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row that sees nothing (length 0) sums nothing and returns zeros
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = correction * l_scr[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
        # one float32 product: on the v5e it read 1-3 % faster at every block
        # size than the paged kernel's split of the weights into two bf16 terms
        latents = rows[:, :R].astype(jnp.float32)  # [bk, R]
        pv = jax.lax.dot_general(p, latents, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * correction + pv
        m_scr[:, 0:1] = m_new
        return _

    jax.lax.fori_loop(0, nb, block, None)
    slot_ref[0] = (slot0 + nb) % 2
    denom = l_scr[:, 0:1]
    o_ref[0] = acc_scr[...] / jnp.where(denom == 0.0, 1.0, denom)


def _call(q, latent_pool, block_tables, seq_lens, layer, scale, kv_lora_rank, interpret):
    B, H, W = q.shape
    page = latent_pool.shape[3]
    M = block_tables.shape[1]
    # a slot's pages never outnumber the pool's: the whole slot's wait is described by a run of them
    ppb = max(1, min(_BLOCK_TOKENS // page, M, latent_pool.shape[1]))
    kernel = functools.partial(_kernel, scale=scale, ppb=ppb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # seq_lens, block_tables, layer
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, kv_lora_rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb) + latent_pool.shape[2:], latent_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),  # one a slot
            pltpu.SMEM((1,), jnp.int32),  # the slot the next row starts in
            pltpu.VMEM((H, _LANES), jnp.float32),  # m
            pltpu.VMEM((H, _LANES), jnp.float32),  # l
            pltpu.VMEM((H, kv_lora_rank), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, kv_lora_rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # rows in order: each starts the next one's first block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_latent_attention",
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(latent_pool.dtype), latent_pool)


@functools.partial(jax.jit, static_argnames=("scale", "kv_lora_rank", "interpret"))
def paged_latent_attention(
    q: jnp.ndarray,  # [B, H, W] q_lat | q_rope | 0
    latent_pool: jnp.ndarray,  # [L, N+1, 1, page, W], whole
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] positions each row sees (this step's included)
    layer: jnp.ndarray,  # scalar int32 (may be traced)
    *,
    scale: float,
    kv_lora_rank: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Absorbed latent attention of one query a row over every position
    the row holds, ``[0, seq_len)``, read from layer ``layer`` of the
    whole pool through the row's block table. Returns o_lat [B, H,
    kv_lora_rank] float32; contract identical to
    :func:`paged_latent_attention_ref`."""
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return paged_latent_attention_ref(q, latent_pool, block_tables, seq_lens, layer,
                                          scale=scale, kv_lora_rank=kv_lora_rank)
    return _call(q, latent_pool, block_tables, seq_lens, layer, scale, kv_lora_rank, mode == INTERPRET)
