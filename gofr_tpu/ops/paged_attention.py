"""Paged (block-table) decode attention for TPU.

The decode-side companion of ops/flash_attention.py: K/V live in a pooled
page table (``[N_pages, page_size, Hkv, Dh]``) shared by every sequence in
the server, and each sequence addresses its pages through an int32 block
table — the vLLM/ragged-paged-attention layout (SURVEY §5.7 lever (a),
PAPERS.md: ragged paged attention kernel for TPU). This is what lets the
continuous-batching engine admit by *token* budget instead of reserving
max_seq_len rows per slot.

Two implementations with one contract:
- ``paged_decode_attention_ref`` — pure-XLA gather reference: the oracle
  the kernels are tested against, and what both entries compute on the
  CPU unless a test passes ``interpret=True`` (``ops/backend.py``);
- ``paged_decode_attention`` / ``paged_decode_attention_q`` — one Pallas
  kernel (bf16 or int8-with-scales pools) whose grid walks
  (batch, kv_head, page) with the page axis innermost, carrying the
  online-softmax state in VMEM scratch. The page index feeds the K/V
  BlockSpec index maps from scalar-prefetched block tables, so only the
  pages a sequence actually owns are streamed from HBM; pages past the
  sequence length are skipped with ``@pl.when``. int8 pools stream at
  half width and dequantize in VMEM (per-vector absmax scales).

The jitted entries are declared in the kernel contract table
(``gofr_tpu/analysis/kernel_contracts.KERNELS``; note the PER-LAYER
pool ranks there — [N_pages, Hkv, page, Dh], no leading L) and
replayed by the kerneltrace eval_shape matrix; a signature or rank
change must update the table in the same commit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.backend import COMPILED, INTERPRET, REFERENCE, kernel_mode

NEG_INF = -1e30

# int8 arrays tile as (32, 128) on TPU; a smaller page would violate the
# Mosaic block constraints for the quantized pools
INT8_MIN_PAGE = 32


def paged_decode_attention_ref(
    q: jnp.ndarray,  # [B, H, Dh] one query token per sequence
    k_pool: jnp.ndarray,  # [N_pages, Hkv, page, Dh]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] int32 page ids (unused entries: any)
    seq_lens: jnp.ndarray,  # [B] valid token count per sequence
    *,
    scale: float | None = None,
    k_scale: jnp.ndarray | None = None,  # int8 pools: [N, Hkv, page, 1] f32
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather-based reference: materializes [B, M*page] K/V. Correctness
    oracle + the CPU path. int8 pools carry per-vector absmax scales
    and dequantize AFTER the gather — only the owned pages widen, never
    the whole pool."""
    B, H, Dh = q.shape
    Hkv = k_pool.shape[1]
    page = k_pool.shape[2]
    M = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)

    # [B, M, Hkv, page, Dh] -> [B, M*page, Hkv, Dh]
    k = k_pool[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, Dh)
    v = v_pool[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, Dh)
    if k_scale is not None:
        ks = k_scale[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, 1)
        vs = v_scale[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, 1)
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs
    group = H // Hkv
    k = jnp.repeat(k, group, axis=2)  # [B, S, H, Dh]
    v = jnp.repeat(v, group, axis=2)

    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    pos = jnp.arange(M * page)[None, :]  # [1, S]
    s = jnp.where((pos < seq_lens[:, None])[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_kernel(
    seq_lens_ref,  # SMEM [B] (scalar prefetch)
    tables_ref,  # SMEM [B, M] (scalar prefetch)
    q_ref,  # VMEM [1, 1, group, Dh]  ([B, Hkv, group, Dh] layout)
    k_ref,  # VMEM [1, 1, page, Dh]   (page j of this sequence, kv head g)
    v_ref,  # VMEM [1, 1, page, Dh]
    *rest,  # quantized: ks_ref, vs_ref, o_ref, scratches; else o_ref, scratches
    scale: float,
    page: int,
    quantized: bool,
):
    """One kernel for both pool widths: with ``quantized`` the K/V blocks
    arrive int8 plus per-vector scale blocks and dequantize in VMEM."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scratch, l_scratch, acc_scratch = rest
    else:
        o_ref, m_scratch, l_scratch, acc_scratch = rest

    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    seq_len = seq_lens_ref[b]

    @pl.when(j * page < seq_len)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # [group, Dh]
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # [page, Dh]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0, :, :]  # [page, 1] scale broadcasts over Dh
            v = v * vs_ref[0, 0, :, :]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [group, page]
        s = s * scale
        k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < seq_len, s, NEG_INF)

        m_prev = m_scratch[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scratch[:, 0:1] = correction * l_scratch[:, 0:1] + jnp.sum(
            p, axis=-1, keepdims=True
        )
        acc_scratch[:] = acc_scratch[:] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[:, 0:1] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = l_scratch[:, 0:1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0, :, :] = (acc_scratch[:] / denom).astype(o_ref.dtype)


def _paged_attention_call(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    scale_v: float,
    interpret: bool,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Shared pallas_call plumbing for both pool widths."""
    B, H, Dh = q.shape
    Hkv, page = k_pool.shape[1], k_pool.shape[2]
    M = block_tables.shape[1]
    group = H // Hkv
    quantized = k_scale is not None

    # [B, Hkv, group, Dh] so each program sees its kv-head's query group
    q_t = q.reshape(B, Hkv, group, Dh)
    kernel = functools.partial(
        _paged_kernel, scale=scale_v, page=page, quantized=quantized
    )

    def _kv_index(b, g, j, seq_lens, tables):
        # Clamp j to the sequence's last owned page: iterations past
        # seq_len repeat the previous index, and Mosaic's pipeline elides
        # DMAs whose block index didn't change — so a 50-token sequence
        # streams ceil(50/page) pages, not M (the compute for the repeats
        # is skipped by the @pl.when in the kernel body).
        last = jnp.maximum(pl.cdiv(seq_lens[b], page) - 1, 0)
        return (tables[b, jnp.minimum(j, last)], g, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, group, Dh),
            lambda b, g, j, seq_lens, tables: (b, g, 0, 0),
        ),
        # page j of sequence b: the scalar-prefetched block table drives
        # the HBM->VMEM DMA — this is the "paged" part
        pl.BlockSpec((1, 1, page, Dh), _kv_index),
        pl.BlockSpec((1, 1, page, Dh), _kv_index),
    ]
    operands = [q_t, k_pool, v_pool]
    kv_elem = 1 if quantized else k_pool.dtype.itemsize
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, page, 1), _kv_index),
            pl.BlockSpec((1, 1, page, 1), _kv_index),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # seq_lens, block_tables
        grid=(B, Hkv, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, group, Dh),
            lambda b, g, j, seq_lens, tables: (b, g, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, Dh), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_t.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * H * M * page * Dh),
            # K AND V pools (+ both scale arrays when quantized)
            bytes_accessed=int(
                q.size * 2
                + 2 * B * M * page * Hkv * (Dh * kv_elem + (4 if quantized else 0))
            ),
            transcendentals=int(B * H * M * page),
        ),
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32), *operands)
    return out.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, Dh]
    k_pool: jnp.ndarray,  # [N_pages, Hkv, page, Dh]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas paged decode attention; contract identical to
    :func:`paged_decode_attention_ref`. Streams only owned pages. The
    [N, Hkv, page, Dh] pool layout keeps every BlockSpec's trailing two
    dims equal to full array dims (page, Dh) — the Mosaic tiling rule."""
    Dh = q.shape[-1]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(Dh)
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, seq_lens, scale=scale_v
        )
    return _paged_attention_call(
        q, k_pool, v_pool, block_tables, seq_lens, scale_v, mode == INTERPRET
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_q(
    q: jnp.ndarray,  # [B, H, Dh]
    k_pool: jnp.ndarray,  # [N_pages, Hkv, page, Dh] int8
    v_pool: jnp.ndarray,
    k_scale: jnp.ndarray,  # [N_pages, Hkv, page, 1] f32
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas paged decode attention over int8 pools (same kernel,
    dequantizing in VMEM). A compiled call with pages below the int8
    Mosaic tile (:data:`INT8_MIN_PAGE` sublanes) is an error — the
    gather reference it used to drop to inverts the bandwidth win int8
    exists for (ServingEngine validates the page size up front)."""
    Dh = q.shape[-1]
    page = k_pool.shape[2]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(Dh)
    mode = kernel_mode(interpret)
    if mode == COMPILED and page < INT8_MIN_PAGE:
        raise ValueError(
            f"int8 paged attention needs page >= {INT8_MIN_PAGE} to compile "
            f"(the int8 Mosaic tile); got page={page}"
        )
    if mode == REFERENCE:
        return paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, seq_lens,
            scale=scale_v, k_scale=k_scale, v_scale=v_scale,
        )
    return _paged_attention_call(
        q, k_pool, v_pool, block_tables, seq_lens, scale_v, mode == INTERPRET,
        k_scale=k_scale, v_scale=v_scale,
    )
