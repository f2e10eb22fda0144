"""Latent attention with a learned sparse selection (DeepSeek-V3.2's MLA
under its lightning indexer): what a token caches is ONE latent row
shared by every head — ``[c_kv | k_rope | zero pad]``, the 576 values
padded to a whole number of 128-lane tiles — and ONE indexer key; a
cheap second attention (the indexer) scores the whole context and the
real attention reads only the ``index_topk`` best positions.

    I(t,u)  = sum_j w_j(t) · relu(q_j(t) · k(u))        u <= t     :func:`index_scores`
    S_t     = the min(topk, t+1) positions with the largest I(t,·)  :func:`select_topk`
    s_h(t,u)= (q_lat,h(t) · c_kv(u) + q_rope,h(t) · k_rope(u)) · scale,  u in S_t
    o_lat,h = sum_u softmax_u(s_h)(t,u) · c_kv(u)                   :func:`latent_attention`

``latent_attention`` is the ABSORBED form: the per-head key and value
projections ride on the query and on the output, so keys and values are
the latent rows themselves. :func:`expanded_attention` is the published
reference's own form (per-head keys and values, dense scores under a
mask that keeps ``S_t``); the two give the same numbers.

Decode reads the paged pools (``serving/kv_cache.py``; the first pool
holds the latent rows ``[L, N+1, 1, page, 640]``, the second the indexer
keys ``[L, N+1, 1, page, 128]``): :func:`paged_index_scores` gathers a
row's pages of indexer keys (every position is scored), and
:func:`sparse_decode_attention` reads from the latent pool ONLY the rows
of the selected positions — an XLA gather by token, feeding one dense
product a row. Where the selection is a large part of the slot, the
model reads the row's pages whole instead, through
``ops/latent_attention.paged_latent_attention`` under
:func:`selection_mask` (``models/deepseek_v32.sparse_read``). Both
(:func:`paged_index_scores`, :func:`sparse_decode_attention`) are jitted
under their own names so that a device trace shows them
(``jit(paged_index_scores)``, ``jit(sparse_decode_attention)``); neither writes a pool, and the
gathers read the pool through a bitcast, so XLA gives it no layout of
its own (PERF.md §6, PR 30). The selection is one sort a layer that
carries each position's row in the pool (:func:`pool_rows`), so the read
needs no lookup of pages by position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Width of a cached latent row: latent and rope key, padded to whole
    lane tiles (512 + 64 -> 640)."""
    return -(-(kv_lora_rank + rope_dim) // LANES) * LANES


def index_scores(
    q: jnp.ndarray,  # [..., T, Hi, Di] the indexer's queries
    k: jnp.ndarray,  # [..., S, Di] the indexer's keys, one a position
    w: jnp.ndarray,  # [..., T, Hi] float32 head weights (scales folded in)
) -> jnp.ndarray:
    """I(t,u) = sum_j w_j(t) · relu(q_j(t) · k(u)) -> [..., T, S] float32,
    unmasked."""
    s = jnp.einsum("...thd,...sd->...ths", q, k, preferred_element_type=jnp.float32)
    return jnp.einsum("...ths,...th->...ts", jax.nn.relu(s), w.astype(jnp.float32))


def select_topk(
    scores: jnp.ndarray,  # [..., S]
    seen: jnp.ndarray,  # [..., S] bool
    k: int,
    payload: jnp.ndarray | None = None,  # [..., S] int32: what to list of a position, if not itself
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ``min(k, S)`` best of the positions a query may see: their
    indices [..., K] — or their ``payload``, such as the row each holds in
    a pool — and which entries name a seen position (a query that sees
    fewer than K positions selects them all; the rest of its list is
    marked off). Of equal scores the earlier position comes first, as
    ``top_k`` lists them. A top-k over the context is a sort on the TPU,
    so it is written as one: the sort carries the payload, where a
    ``top_k`` would need a gather of it by the indices afterwards."""
    masked = jnp.where(seen, scores, NEG_INF)
    if payload is None:
        payload = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.int32), scores.shape)
    key, listed = jax.lax.sort((-masked, payload), dimension=-1, is_stable=True, num_keys=1)
    k = min(k, scores.shape[-1])
    return listed[..., :k], key[..., :k] < -NEG_INF / 2


def selection_mask(scores: jnp.ndarray, seen: jnp.ndarray, k: int) -> jnp.ndarray:
    """:func:`select_topk` as a mask over the context [..., S]: the form
    dense scores take (prefill and chunks), and a decode step that reads
    the row's pages whole. A position is kept iff it is
    seen and among the k best; of positions that tie with the k-th best
    the earlier ones are kept, as ``top_k`` lists them (a score is a sum
    of ReLUs, so ties at zero do occur with few heads). Two forms of one
    selection, held to each other by a test, because each path needs its
    own: a gather by token takes a LIST, and a mask made from a list is
    a scatter of [T, K] into [T, S], which the TPU runs element by
    element; made from the k-th best score it is two comparisons."""
    if k >= scores.shape[-1]:
        return seen
    masked = jnp.where(seen, scores, NEG_INF)
    kth = jax.lax.top_k(masked, k)[0][..., -1:]
    above, ties = masked > kth, masked == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def _softmax_pv(s: jnp.ndarray, keep: jnp.ndarray, v: jnp.ndarray, eq: str) -> jnp.ndarray:
    """Masked softmax in float32 and the weighted sum of ``v``; a query
    that keeps nothing (a padded row) gets zeros, not NaN."""
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(keep, p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum(eq, p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def latent_attention(
    q: jnp.ndarray,  # [..., T, H, W] queries in the latent row's layout: q_lat | q_rope | 0
    rows: jnp.ndarray,  # [..., S, W] latent rows: c_kv | k_rope | 0
    keep: jnp.ndarray,  # [..., T, S] bool
    scale: float,
    kv_lora_rank: int,
) -> jnp.ndarray:
    """Absorbed latent attention: every head scores the same rows and sums
    the same latents. Returns o_lat [..., T, H, kv_lora_rank] float32."""
    s = jnp.einsum("...thw,...sw->...ths", q, rows, preferred_element_type=jnp.float32) * scale
    return _softmax_pv(s, keep[..., :, None, :], rows[..., :kv_lora_rank], "...ths,...sc->...thc")


def expanded_attention(
    q_nope: jnp.ndarray,  # [..., T, H, Dn]
    q_rope: jnp.ndarray,  # [..., T, H, Dr]
    k_nope: jnp.ndarray,  # [..., S, H, Dn]
    k_rope: jnp.ndarray,  # [..., S, Dr] one for all heads
    v: jnp.ndarray,  # [..., S, H, Dv]
    keep: jnp.ndarray,  # [..., T, S] bool
    scale: float,
) -> jnp.ndarray:
    """The published form: per-head keys and values, dense scores, a mask
    that is kept on S_t. Returns [..., T, H, Dv] float32."""
    s = jnp.einsum("...thd,...shd->...ths", q_nope, k_nope, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("...thd,...sd->...ths", q_rope, k_rope, preferred_element_type=jnp.float32)
    return _softmax_pv(s * scale, keep[..., :, None, :], v, "...ths,...shd->...thd")


# --------------------------------------------------------- the paged reads
def row_pages(pool: jnp.ndarray, block_tables: jnp.ndarray, layer: jnp.ndarray) -> jnp.ndarray:
    """A layer's pages of every row, [B, M*page, W], from a whole pool
    [L, N+1, 1, page, W]: a gather by page."""
    L, n, _, page, W = pool.shape
    flat = pool.reshape(L * n, page, W)
    B, M = block_tables.shape
    return flat[layer * n + block_tables].reshape(B, M * page, W)


@jax.jit
def paged_index_scores(
    q: jnp.ndarray,  # [B, Hi, Di] this step's indexer queries
    w: jnp.ndarray,  # [B, Hi] float32
    key_pool: jnp.ndarray,  # [L, N+1, 1, page, Di] the indexer's keys
    block_tables: jnp.ndarray,  # [B, M]
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this step's position
    layer: jnp.ndarray,  # scalar int32
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The indexer over a row's whole context: scores [B, M*page] float32
    and which positions the row sees. Reads ``seq_len`` keys a row (whole
    pages: the tail of the last page and the pages past it are masked)."""
    keys = row_pages(key_pool, block_tables, layer)  # [B, S, Di]
    scores = index_scores(q[:, None], keys, w[:, None])[:, 0]
    seen = jnp.arange(keys.shape[1])[None, :] < seq_lens[:, None]
    return scores, seen


def pool_rows(block_tables: jnp.ndarray, n_pages: int, page: int, layer: jnp.ndarray) -> jnp.ndarray:
    """The row each position of every sequence holds in a pool
    ``[L, N+1, 1, page, W]`` read as ``[L*(N+1)*page, W]``: [B, M*page]
    int32, the payload :func:`select_topk` lists for the sparse read."""
    B, M = block_tables.shape
    rows = (layer * n_pages + block_tables)[:, :, None] * page + jnp.arange(page, dtype=jnp.int32)
    return rows.reshape(B, M * page)


@functools.partial(jax.jit, static_argnames=("scale", "kv_lora_rank"))
def sparse_decode_attention(
    q: jnp.ndarray,  # [B, H, W] q_lat | q_rope | 0
    latent_pool: jnp.ndarray,  # [L, N+1, 1, page, W]
    rows: jnp.ndarray,  # [B, K] the selected positions' rows in the pool (pool_rows, select_topk)
    valid: jnp.ndarray,  # [B, K] bool
    *,
    scale: float,
    kv_lora_rank: int,
) -> jnp.ndarray:
    """Decode attention over the selected positions alone: of the latent
    pool, only the K rows a query selected are read (a gather by token of
    ``[L*(N+1)*page, W]``, which is the pool bitcast), and one dense
    product a row scores all heads against them. Entries marked off read
    the pool's first row and are masked. Returns o_lat [B, H,
    kv_lora_rank] float32."""
    flat = latent_pool.reshape(-1, latent_pool.shape[-1])
    gathered = flat[jnp.where(valid, rows, 0)]  # [B, K, W]
    return latent_attention(q[:, None], gathered, valid[:, None], scale, kv_lora_rank)[:, 0]
