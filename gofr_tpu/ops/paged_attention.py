"""Paged (block-table) decode attention for TPU, and the decode step's
append into the same pools.

The decode-side companion of ops/flash_attention.py: K/V live in a pooled
page table (``[L, N_pages, Hkv, page_size, Dh]``, a layer's slice
``[N_pages, Hkv, page_size, Dh]``) shared by every sequence in the
server, and each sequence addresses its pages through an int32 block
table — the vLLM/ragged-paged-attention layout (SURVEY §5.7 lever (a),
PAPERS.md: ragged paged attention kernel for TPU). This is what lets the
continuous-batching engine admit by *token* budget instead of reserving
max_seq_len rows per slot.

Reading, two implementations with one contract:
- ``paged_decode_attention_ref`` — pure-XLA gather reference: the oracle
  the kernel is tested against, and what the entry computes on the
  CPU unless a test passes ``interpret=True`` (``ops/backend.py``);
- ``paged_decode_attention`` — one Pallas kernel with one program a row
  and no grid axis over pages. The pools stay in HBM; the kernel loops
  over the blocks of pages the row owns
  (``cdiv(seq_len, pages_per_block * page)``
  trips, read from the scalar-prefetched lengths and block tables) and
  fetches each block itself, one DMA a page — a page of the pool is
  contiguous for all KV heads — into a double buffer in VMEM, so that
  block *i + 1* (at a row's end, the next row's first block) is in
  flight while block *i* is computed. The online softmax runs in float32
  over every KV head of the block. A row of length 1 (an empty slot)
  costs one page. ``pages_per_block`` is worked out from the shapes the
  call sees (:func:`_pages_per_block`). The kernel addresses
  ``pool[layer, page id]``: given a ``layer`` the entry takes the WHOLE
  pools, as the decode step passes them; without one the pools are one
  layer's.

Writing: ``paged_kv_append`` (reference ``paged_kv_append_ref``, the
scatter it replaced) puts one decode step's K/V of every row into the
whole pools by a Pallas call aliased over them. Within a decode program
the pools are written by that call alone and read by the kernel above
alone, both by layer index, so XLA has no op on a pool and assigns it no
layout of its own: any XLA write of one token into ``[..., Hkv, page, Dh]``
makes layout assignment swap the KV-head and page axes, and the pool is
then transposed on entry, around every kernel call and on exit (PERF.md
§6, PR 30: more than half of a decode step).

The jitted entries are declared in the kernel contract table
(``gofr_tpu/analysis/kernel_contracts.KERNELS``; note the pool ranks
there) and replayed by the kerneltrace eval_shape matrix; a signature
or rank change must update the table in the same commit. The benchmark
finds the attention kernel's device events by the jitted wrapper's name,
``paged_decode_attention``: no other entry's name may begin with it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.backend import INTERPRET, REFERENCE, kernel_mode

NEG_INF = -1e30


def paged_decode_attention_ref(
    q: jnp.ndarray,  # [B, H, Dh] one query token per sequence
    k_pool: jnp.ndarray,  # [N_pages, Hkv, page, Dh]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] int32 page ids (unused entries: any)
    seq_lens: jnp.ndarray,  # [B] valid token count per sequence
    *,
    scale: float | None = None,
    window: jnp.ndarray | int | None = None,
    layer: jnp.ndarray | int | None = None,  # pools are [L, N_pages, ...]
) -> jnp.ndarray:
    """Gather-based reference: materializes [B, M*page] K/V. Correctness
    oracle + the CPU path. With ``window`` the query (at position
    ``seq_len - 1``) sees the last ``window`` positions only. With
    ``layer`` the pools are whole ([L, N_pages, Hkv, page, Dh]) and that
    layer's pages are read."""
    B, H, Dh = q.shape
    Hkv, page = k_pool.shape[-3], k_pool.shape[-2]
    M = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    owned = block_tables if layer is None else (layer, block_tables)

    # [B, M, Hkv, page, Dh] -> [B, M*page, Hkv, Dh]
    k = k_pool[owned].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, Dh)
    v = v_pool[owned].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, Dh)
    group = H // Hkv
    k = jnp.repeat(k, group, axis=2)  # [B, S, H, Dh]
    v = jnp.repeat(v, group, axis=2)

    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    pos = jnp.arange(M * page)[None, :]  # [1, S]
    seen = pos < seq_lens[:, None]
    if window is not None:
        seen &= pos >= seq_lens[:, None] - window
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# VMEM the K and V page buffers may hold between them, both slots of the
# double buffer counted: a quarter of the 16 MiB a Mosaic kernel gets on a
# v5e, which leaves room for the float32 working set of one head's block
_KV_VMEM_BUDGET = 4 * 1024 * 1024
# A block is also the unit of compute: the last block of a row is computed
# whole and masked, so a longer block wastes work on every short row. 128
# tokens is one MXU tile of K^T (or V) a head.
_BLOCK_TOKENS = 128
_LANES = 128  # the running max and sum are kept a lane row wide


def _pages_per_block(Hkv: int, page: int, Dh: int, itemsize: int, M: int) -> int:
    """Pages one DMA batch and one compute block hold, from the shapes the
    call sees: as many as fit :data:`_KV_VMEM_BUDGET` (two slots of K and
    V), at most :data:`_BLOCK_TOKENS` tokens, never more than the table is
    wide."""
    page_bytes = Hkv * page * Dh * itemsize
    fit = _KV_VMEM_BUDGET // (4 * page_bytes)
    return max(1, min(fit, _BLOCK_TOKENS // page, M))


def _paged_kernel(
    seq_lens_ref,  # SMEM [B] (scalar prefetch)
    tables_ref,  # SMEM [B, M] (scalar prefetch)
    layer_ref,  # SMEM [1] (scalar prefetch): the layer of the pools to read
    *refs,  # windowed: window_ref SMEM [1] (scalar prefetch) first; then as _paged_body
    windowed: bool,
    **static,
):
    window = refs[0][0] if windowed else None
    _paged_body(seq_lens_ref, tables_ref, layer_ref[0], window, *refs[windowed:], **static)


def _paged_body(
    seq_lens_ref,
    tables_ref,
    layer,  # the scalar: pages are read from this layer of the pools
    window,  # None, or the scalar: a row sees its last ``window`` positions
    q_ref,  # VMEM [1, Hkv, group, Dh]: this row's queries
    k_hbm,  # HBM [L, N, Hkv, page, Dh]: the whole pool, never copied or sliced
    v_hbm,
    o_ref,  # VMEM [1, Hkv, group, Dh]
    k_buf,  # VMEM [2, ppb, Hkv, page, Dh]: the double buffer
    v_buf,
    sem,  # DMA (2,): one a slot
    slot_ref,  # SMEM [1]: the slot the next row starts in
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    ppb: int,
):
    """One program a row. The row's pages arrive ``ppb`` at a time by DMAs
    this kernel issues (one a page: a page is contiguous for all KV heads),
    into the slot of a double buffer that is not being computed on; the
    last block of a row starts the first block of the next row. The loop
    runs ``cdiv(seq_len, ppb * page)`` times: a row costs the pages it
    owns. With a ``window`` the loop starts at the block that holds
    position ``seq_len - window`` and that block masks below it: a row
    costs the pages its window covers."""
    streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    Hkv, page, Dh = k_hbm.shape[2:]
    group = q_ref.shape[2]
    bk = ppb * page
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def row_pages(row):
        # a row of length 0 is read as one of length 1 (it costs one page,
        # and every program has a block to wait for); one longer than its
        # table stops where the table does
        return jnp.clip(pl.cdiv(seq_lens_ref[row], page), 1, tables_ref.shape[1])

    def block_pages(row, blk):
        return jnp.minimum(row_pages(row) - blk * ppb, ppb)

    def first_block(row):
        if window is None:
            return 0
        # never past the row's last block (a row of length 0 is read as 1)
        lo = jnp.clip(seq_lens_ref[row] - window, 0, (row_pages(row) - 1) * page)
        return lo // bk

    def start_block(row, blk, slot):
        def one(j, _):
            pid = tables_ref[row, blk * ppb + j]
            for hbm, buf in streams:
                pltpu.make_async_copy(hbm.at[layer, pid], buf.at[slot, j], sem.at[slot]).start()
            return _
        jax.lax.fori_loop(0, block_pages(row, blk), one, None)

    def wait_block(n, slot):
        # every copy of the block before any is read: the streams share the
        # slot's semaphore, so a count of one stream's bytes proves nothing
        # about that stream
        def one(j, _):
            for hbm, buf in streams:
                pltpu.make_async_copy(hbm.at[0, 0], buf.at[slot, j], sem.at[slot]).wait()
            return _
        jax.lax.fori_loop(0, n, one, None)

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        start_block(0, first_block(0), 0)

    seq_len = seq_lens_ref[b]
    nb = pl.cdiv(row_pages(b), ppb)
    b0 = first_block(b)
    slot0 = slot_ref[0]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(i, _):
        slot = (slot0 + i - b0) % 2
        last = i + 1 == nb

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < B))
        def _prefetch():
            row = jnp.where(last, jnp.minimum(b + 1, B - 1), b)
            start_block(row, jnp.where(last, first_block(row), i + 1), 1 - slot)

        n = block_pages(b, i)
        wait_block(n, slot)

        # the pages of the block this row does not own were not fetched and
        # hold whatever the slot held before: K's are masked below, but a
        # zero weight times a stale NaN in V is NaN
        def clear(j, _):
            v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return _
        jax.lax.fori_loop(n, ppb, clear, None)

        k_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (group, bk), 1)
        nn = (((1,), (0,)), ((), ()))  # [group, bk] x [bk, Dh]
        for h in range(Hkv):
            q = q_ref[0, h]  # [group, Dh]
            k = k_buf[slot, :, h].reshape(bk, Dh)
            v = v_buf[slot, :, h].reshape(bk, Dh)
            # products of two bf16 values are exact in the float32 the MXU
            # accumulates in, so bf16 pools go in as they are; anything
            # wider is computed in float32
            mxu = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(mxu), k.astype(mxu), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [group, bk]
            seen = k_pos < seq_len
            if window is not None:
                seen = jnp.logical_and(seen, k_pos >= seq_len - window)
            s = jnp.where(seen, s, NEG_INF)

            m_prev = m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_scr[h, :, 0:1] = correction * l_scr[h, :, 0:1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            if v.dtype == jnp.float32:
                pv = jax.lax.dot_general(p, v, nn, preferred_element_type=jnp.float32)
            else:
                # the weights as two bf16 terms (16 bits of mantissa) against
                # V as it is stored: both products exact, and on the v5e the
                # pair runs faster than one product of either width
                p_hi = p.astype(v.dtype)
                p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
                pv = jax.lax.dot_general(
                    p_hi, v, nn, preferred_element_type=jnp.float32
                ) + jax.lax.dot_general(
                    p_lo, v, nn, preferred_element_type=jnp.float32
                )
            acc_scr[h] = acc_scr[h] * correction + pv
            m_scr[h, :, 0:1] = m_new
        return _

    jax.lax.fori_loop(b0, nb, block, None)
    slot_ref[0] = (slot0 + nb - b0) % 2

    denom = l_scr[:, :, 0:1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _paged_attention_call(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    scale_v: float,
    interpret: bool,
    window: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The pallas_call and its plumbing. ``window=None`` builds the kernel
    without the argument. The kernel sees whole pools and a layer index:
    with ``layer=None`` the pools are one layer's, given a leading axis of
    one here (a bitcast)."""
    B, H, Dh = q.shape
    Hkv, page = k_pool.shape[-3], k_pool.shape[-2]
    M = block_tables.shape[1]
    group = H // Hkv
    ppb = _pages_per_block(Hkv, page, Dh, k_pool.dtype.itemsize, M)

    # [B, Hkv, group, Dh]: a program sees its row's queries by kv head
    q_t = q.reshape(B, Hkv, group, Dh)
    windowed = window is not None
    pools = [k_pool, v_pool]
    if layer is None:
        pools, layer = [pool[None] for pool in pools], 0
    prefetch = [
        seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
    ]
    if windowed:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    kernel = functools.partial(_paged_kernel, windowed=windowed, scale=scale_v, ppb=ppb)
    row_spec = pl.BlockSpec((1, Hkv, group, Dh), lambda b, *_: (b, 0, 0, 0))
    page_buffers = [
        pltpu.VMEM((2, ppb) + pool.shape[2:], pool.dtype) for pool in pools
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # seq_lens, block_tables, layer[, window]
        grid=(B,),
        in_specs=[row_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=row_spec,
        scratch_shapes=page_buffers + [
            pltpu.SemaphoreType.DMA((2,)),  # one a slot
            pltpu.SMEM((1,), jnp.int32),  # the slot the next row starts in
            pltpu.VMEM((Hkv, group, _LANES), jnp.float32),  # m
            pltpu.VMEM((Hkv, group, _LANES), jnp.float32),  # l
            pltpu.VMEM((Hkv, group, Dh), jnp.float32),  # acc
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_t.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows in order: each starts the next one's first block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*prefetch, q_t, *pools)
    return out.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, Dh]
    k_pool: jnp.ndarray,  # [L, N_pages, Hkv, page, Dh] with ``layer``, else one layer's
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    interpret: bool | None = None,
    window: jnp.ndarray | None = None,  # scalar int32 (may be traced)
    layer: jnp.ndarray | None = None,  # scalar int32 (may be traced)
) -> jnp.ndarray:
    """Pallas paged decode attention; contract identical to
    :func:`paged_decode_attention_ref`. Streams only owned pages — with a
    ``window``, only those that hold a row's last ``window`` positions. In the
    [..., N, Hkv, page, Dh] pool layout a page is one contiguous piece for
    all KV heads — one DMA — whose trailing two dims (page, Dh) are whole
    Mosaic tiles. With ``layer`` the pools are whole and stay in HBM as
    they are: the page DMAs address ``[layer, page id]``, so a decode step
    hands every layer the same two buffers and XLA slices nothing."""
    Dh = q.shape[-1]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(Dh)
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, seq_lens, scale=scale_v,
            window=window, layer=layer,
        )
    return _paged_attention_call(
        q, k_pool, v_pool, block_tables, seq_lens, scale_v, mode == INTERPRET,
        window=window, layer=layer,
    )


# ------------------------------------------------------------- the append
# VMEM the append's page buffers may hold (K and V of every row of a
# chunk): at the served shapes (32 rows x 32 KiB, 6 x 128 KiB, 64 x 32 KiB
# a pool) one chunk is the whole batch
_APPEND_VMEM_BUDGET = 4 * 1024 * 1024


def paged_kv_append_ref(
    k_pool: jnp.ndarray,  # [L, N_pages, Hkv, page, Dh]
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, Hkv, Dh] this step's K of every row
    v_new: jnp.ndarray,
    layer: jnp.ndarray | int,
    pages: jnp.ndarray,  # [B] page id a row writes (inactive rows: the trash page)
    offsets: jnp.ndarray,  # [B] slot in that page
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The append as an XLA scatter: the oracle, and the CPU path.
    ``pool.at[layer, pages, :, offsets]`` (advanced indices split by a
    slice) addresses [B, Hkv, Dh]. A ``v_pool`` of None (a model that
    stores one thing a token) stays None."""
    return (
        k_pool.at[layer, pages, :, offsets].set(k_new.astype(k_pool.dtype)),
        None if v_pool is None else v_pool.at[layer, pages, :, offsets].set(v_new.astype(v_pool.dtype)),
    )


def _append_kernel(
    layer_ref,  # SMEM [1] (scalar prefetch)
    pages_ref,  # SMEM [B] (scalar prefetch)
    offsets_ref,  # SMEM [B] (scalar prefetch)
    *refs,  # for each of the n pools (K, V; or one): its new rows, then
    # its HBM input and output (one buffer, aliased), then its VMEM page
    # buffer; last the DMA semaphores (2,): reads, writes
):
    """One program a chunk of ``R`` rows. A DMA cannot write one token's
    row into a page (a slice of 1 along the tiled page axis), so a row's
    append is a read-modify-write of the page it writes: every row's page
    is fetched (all reads in flight at once, one wait), slot ``offset`` is
    replaced in VMEM under an iota mask, and the pages go back the same
    way. Sound because NO TWO LIVE ROWS OWN THE SAME PAGE IN A STEP: the
    allocator hands a page to one sequence, and the prefix cache copies
    shared slabs into owned pages (serving/kv_cache.py) — only the trash
    page is written by several rows, and its content is garbage by
    contract."""
    n = (len(refs) - 1) // 4
    new_refs, ins, outs, bufs = (refs[i * n:(i + 1) * n] for i in range(4))
    sem = refs[-1]
    R = bufs[0].shape[0]  # the two pools may hold pages of two shapes
    c = pl.program_id(0)
    rows = jnp.minimum(R, pages_ref.shape[0] - c * R)
    layer = layer_ref[0]
    streams = tuple(zip(ins, outs, bufs, new_refs))

    def each_row(fn):
        def one(r, _):
            fn(r, c * R + r)  # the row's place in the chunk, and in the batch
            return _
        jax.lax.fori_loop(0, rows, one, None)

    def read(r, row):
        for src, _, buf, _ in streams:
            pltpu.make_async_copy(src.at[layer, pages_ref[row]], buf.at[r], sem.at[0]).start()

    def read_done(r, row):
        for src, _, buf, _ in streams:
            pltpu.make_async_copy(src.at[0, 0], buf.at[r], sem.at[0]).wait()

    def patch(r, row):
        hits = {}  # one mask a page shape: pools of one shape share it, as before there were two
        for _, _, buf, new_ref in streams:
            Hkv, page, Dh = buf.shape[1:]
            if (page, Dh) not in hits:
                hits[page, Dh] = jax.lax.broadcasted_iota(jnp.int32, (page, Dh), 0) == offsets_ref[row]
            for h in range(Hkv):
                new = jnp.broadcast_to(new_ref[r, pl.ds(h, 1), :], (page, Dh))
                buf[r, h] = jnp.where(hits[page, Dh], new, buf[r, h])

    def write(r, row):
        for _, dst, buf, _ in streams:
            pltpu.make_async_copy(buf.at[r], dst.at[layer, pages_ref[row]], sem.at[1]).start()

    def write_done(r, row):
        for _, dst, buf, _ in streams:
            pltpu.make_async_copy(buf.at[r], dst.at[0, 0], sem.at[1]).wait()

    for phase in (read, read_done, patch, write, write_done):
        each_row(phase)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_append(
    k_pool: jnp.ndarray,  # [L, N_pages, Hkv, page, Dh]
    v_pool: jnp.ndarray | None,  # None: the model stores one thing a token
    k_new: jnp.ndarray,  # [B, Hkv, Dh]
    v_new: jnp.ndarray | None,  # the V pool's pages may have a shape of their own
    layer: jnp.ndarray,  # scalar int32 (may be traced)
    pages: jnp.ndarray,  # [B] int32
    offsets: jnp.ndarray,  # [B] int32
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """One decode step's K/V of every row into ``[layer, pages[b], :,
    offsets[b]]`` of the pools, by a Pallas call aliased over both whole
    pools (over the one, with ``v_pool`` None: a latent row a token);
    contract identical to :func:`paged_kv_append_ref`, given that
    rows that share a page (the trash page) leave garbage in it. Inside a
    program that donates the pools this writes in place, and — the reason
    it is a kernel — leaves XLA no op that writes into a pool: an XLA
    scatter or dynamic-update-slice of one token makes layout assignment
    swap the pool's KV-head and page axes, and the pool is then transposed
    around every call of the attention kernel."""
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return paged_kv_append_ref(k_pool, v_pool, k_new, v_new, layer, pages, offsets)
    B = k_new.shape[0]
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)  # their pages [Hkv, page, Dh] may differ in shape
    news = (k_new,) if v_pool is None else (k_new, v_new)
    n = len(pools)
    page_bytes = sum(math.prod(pool.shape[2:]) * pool.dtype.itemsize for pool in pools)
    R = max(1, min(B, _APPEND_VMEM_BUDGET // page_bytes))
    row_specs = [pl.BlockSpec((R, pool.shape[2], pool.shape[4]), lambda c, *_: (c, 0, 0)) for pool in pools]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, pages, offsets
        grid=(pl.cdiv(B, R),),
        in_specs=row_specs + [hbm] * n,
        out_specs=[hbm] * n,
        scratch_shapes=[pltpu.VMEM((R,) + pool.shape[2:], pool.dtype) for pool in pools] + [
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools],
        # operands count the scalar prefetches: 3 scalars, n new rows, pools
        input_output_aliases={3 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=mode == INTERPRET,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pages.astype(jnp.int32),
        offsets.astype(jnp.int32), *(new.astype(pool.dtype) for new, pool in zip(news, pools)), *pools,
    )
    return out[0], (out[1] if n == 2 else None)
