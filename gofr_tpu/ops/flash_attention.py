"""Pallas flash-attention kernel for TPU (prefill hot path).

Blockwise online-softmax attention (the FlashAttention recurrence) tiled for
the MXU: the grid walks (batch, q_head, q_block, kv_block) with the kv_block
axis innermost, carrying the running max/denominator/accumulator in VMEM
scratch across kv iterations. Causal blocks that are fully masked are skipped
entirely (the `@pl.when` guard), so prefill does ~half the work of the dense
path and never materialises the [Sq, Sk] logits matrix in HBM — that is the
whole point on a bandwidth-bound chip.

GQA is handled in the BlockSpec index maps: q head ``h`` reads kv head
``h * n_kv // n_heads``, so no `jnp.repeat` materialisation of K/V.

Reference parity note (SURVEY §5.7): the reference framework (gofr, pure Go)
has no attention; this kernel is the TPU-native hot-op the north-star serving
path requires. On a TPU the kernel compiles through Mosaic; on the CPU the
call computes ``ops.attention.attention`` unless ``interpret=True`` asks for
the Pallas interpreter (tests do); any other platform is an error
(``ops/backend.py``).

``flash_attention`` is declared in the kernel contract table
(``gofr_tpu/analysis/kernel_contracts.KERNELS``) and replayed by the
kerneltrace eval_shape matrix — signature/static-arg changes must
update the table in the same commit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.backend import INTERPRET, REFERENCE, kernel_mode

NEG_INF = -1e30


def _flash_kernel(
    kv_len_ref,  # SMEM [B] (scalar prefetch) — valid kv length per batch row
    *refs,  # windowed: window_ref SMEM [1] (scalar prefetch) first; then
    # q_ref, k_ref, v_ref, o_ref and the three scratches, as _flash_body has them
    windowed: bool,
    **static,
):
    window = refs[0][0] if windowed else None
    _flash_body(kv_len_ref, window, *refs[windowed:], **static)


def _flash_body(
    kv_len_ref,
    window,  # None, or the scalar: query i sees keys i - window < j <= i
    q_ref,  # VMEM [1, 1, block_q, D]  ([B, H, S, D] layout)
    k_ref,  # VMEM [1, 1, block_k, D]
    v_ref,  # VMEM [1, 1, block_k, D]
    o_ref,  # VMEM [1, 1, block_q, D]
    m_scratch,  # VMEM [block_q, 128] f32 — running row max (col 0 used)
    l_scratch,  # VMEM [block_q, 128] f32 — running denominator
    acc_scratch,  # VMEM [block_q, D] f32 — running weighted sum
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    kv_len = kv_len_ref[b]
    q_start = iq * block_q
    k_start = ik * block_k

    # Skip kv blocks strictly above the causal diagonal and blocks fully past
    # the valid kv length. (Padding rows have kv_len 0 → everything skipped,
    # output stays zero.)
    in_band = k_start < kv_len
    if causal:
        in_band = jnp.logical_and(in_band, k_start <= q_start + block_q - 1)
    if window is not None:  # some key of the block is inside the first query's window
        in_band = jnp.logical_and(in_band, k_start + block_k - 1 > q_start - window)

    @pl.when(in_band)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0, :, :].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        s = s * scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[:, 0:1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)

        p = jnp.exp(s - m_new)  # [bq, bk]
        correction = jnp.exp(m_prev - m_new)  # [bq, 1]

        l_new = correction * l_scratch[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[:, 0:1] = m_new
        l_scratch[:, 0:1] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = l_scratch[:, 0:1]
        denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked q rows → 0
        o_ref[0, 0, :, :] = (acc_scratch[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    kv_len: jnp.ndarray | None = None,  # [B] valid kv length per row
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    window: jnp.ndarray | None = None,  # scalar int32 (may be traced)
) -> jnp.ndarray:
    """Flash attention. Same contract as ops.attention.attention with
    q_offset=0 (prefill): right-padded K/V masked by ``kv_len``; causal over
    absolute positions; with ``window`` (causal only) query i sees keys
    ``i - window < j <= i`` and blocks wholly below the window are skipped.
    ``window=None`` is the kernel without the argument. Returns
    [B, Sq, H, D] in q's dtype."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return attention(q, k, v, causal=causal, kv_len=kv_len, scale=scale, window=window)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq lens ({Sq},{Sk}) must be multiples of blocks ({block_q},{block_k})"
        )

    if kv_len is None:
        kv_len = jnp.full((B,), Sk, jnp.int32)
    kv_len = kv_len.astype(jnp.int32)

    group = H // Hkv

    windowed = window is not None
    prefetch = [kv_len]
    if windowed:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    kernel = functools.partial(
        _flash_kernel,
        windowed=windowed,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
    )

    # [B, H, S, D] layout so the last two block dims are (block, D) —
    # Mosaic requires sublane/lane tile alignment there.
    q_t = q.transpose(0, 2, 1, 3)
    k_t = k.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # kv_len, and the window if there is one
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D),
                lambda b, h, iq, ik, *_: (b, h, iq, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, iq, ik, *_: (b, h // group, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, iq, ik, *_: (b, h // group, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D),
            lambda b, h, iq, ik, *_: (b, h, iq, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )

    flops = 4 * B * H * Sq * Sk * D * (0.5 if causal else 1.0)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_t.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(flops),
            bytes_accessed=int(q.size * 2 + k.size * 2 + v.size * 2),
            transcendentals=int(B * H * Sq * Sk),
        ),
        interpret=mode == INTERPRET,
    )(*prefetch, q_t, k_t, v_t)
    return out.transpose(0, 2, 1, 3)
