"""Mamba-2 ("SSD") ops: the recurrence of a state-space layer whose decay
is one scalar a head and whose B and C are shared by a group of heads.

    S_t[h] = exp(Delta_t[h] A[h]) S_t-1[h] + Delta_t[h] x_t[h] (x) B_t[g(h)]    S[h] is [P, N]
    y_t[h] = S_t[h] C_t[g(h)]                                                     (+ D[h] x_t[h], the caller's)

``g(h) = h // (H / G)``. The state is float32 and laid out ``[..., H, P,
N]``: the state axis last, so that a stored head is whole (8, 128) tiles
(N is 128 in the served model). Two forms of the recurrence, both exact
to it in exact arithmetic:

- :func:`ssd_chunk_scan`, a prompt or a chunk in MATRIX form over
  sub-chunks of ``chunk`` positions (the published ``chunk_size``):
  within a sub-chunk ``y = (L o C B^T)(Delta x)`` plus the carried
  state's term, and the state at its end; a ``lax.scan`` runs over the
  sub-chunks (2 for a chunk of 256), never over positions. A start state
  goes in and the end state comes out, so a prompt prefilled over several
  engine chunks carries its state across every boundary. The decays are
  differences of cumulative sums taken segment by segment (``_segsum``),
  never of two long sums: those cancel.
- :func:`ssd_state_step`, the one position of a decode step, on the
  state pool itself: a Mosaic kernel on the chip that reads and writes
  each LIVE row's ``[H, P, N]`` state once, in place
  (``input_output_aliases``), and touches no other row; the XLA form
  (:func:`ssd_state_step_ref`) is the CPU's path and the kernel's test
  reference.

Positions with ``Delta = 0`` leave the state as it is and add nothing, so
a caller masks padding by zeroing Delta, as ``ops/ssm.py`` does.
:func:`gated_rms_norm` is the layer's output norm: the gate before the
norm, the norm over groups of channels.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.backend import INTERPRET, REFERENCE, kernel_mode

_HI = jax.lax.Precision.HIGHEST
_LANES = 128
# VMEM the state step may use: both slots of a row's state in and out
# (4 x 2 MiB at the served widths) and the small per-row blocks
_STEP_VMEM = 32 * 1024 * 1024


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """a [..., Q] -> [..., Q, Q]: ``sum_{k=j+1..i} a_k`` at (i, j), i >= j,
    and -inf above the diagonal (``exp`` of it is the causal decay L)."""
    Q = a.shape[-1]
    rows = jnp.arange(Q)[:, None]
    cols = jnp.arange(Q)[None, :]
    terms = jnp.where(rows > cols, a[..., :, None], 0.0)  # a_k at (k, j) for k > j
    seg = jnp.cumsum(terms, axis=-2)
    return jnp.where(rows >= cols, seg, -jnp.inf)


def ssd_chunk_scan(
    x: jnp.ndarray,      # [B, T, H, P] float32
    dt: jnp.ndarray,     # [B, T, H] Delta after the softplus (0 at a position that is padding)
    a: jnp.ndarray,      # [H] A = -exp(A_log)
    b: jnp.ndarray,      # [B, T, G, N]
    c: jnp.ndarray,      # [B, T, G, N]
    state: jnp.ndarray,  # [B, H, P, N] float32, the state before position 0
    *,
    chunk: int = 128,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """T positions in matrix form: (y [B, T, H, P] without the D term, the
    state after the last position). T is padded to whole sub-chunks with
    Delta = 0 (exact: nothing moves)."""
    B, T, H, P = x.shape
    G, N = b.shape[-2:]
    r = H // G
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)) for v in (x, dt, b, c))
    n = (T + pad) // Q

    def split(v: jnp.ndarray) -> jnp.ndarray:  # [B, n Q, ...] -> [n, B, Q, ...]
        return jnp.swapaxes(v.reshape((B, n, Q) + v.shape[2:]), 0, 1)

    def sub_chunk(s: jnp.ndarray, xs: tuple) -> tuple:
        xq, dq, bq, cq = xs  # [B, Q, H, P], [B, Q, H], [B, Q, G, N] x2
        da = jnp.swapaxes(dq * a, 1, 2).reshape(B, G, r, Q)  # [B, G, r, Q]
        seg = _segsum(da)  # [B, G, r, Q, Q]
        decay = jnp.exp(seg)  # L: 0 above the diagonal
        from_start = jnp.exp(jnp.cumsum(da, axis=-1))  # [B, G, r, Q]: the start state's decay to i
        to_end = decay[..., Q - 1, :]  # [B, G, r, Q]: position j's decay to the sub-chunk's end
        u = (dq[..., None] * xq).reshape(B, Q, G, r, P)  # Delta x
        cb = jnp.einsum("bign,bjgn->bgij", cq, bq, precision=_HI)  # [B, G, Q, Q]
        y = jnp.einsum("bgij,bgrij,bjgrp->bigrp", cb, decay, u, precision=_HI)
        sg = s.reshape(B, G, r, P, N)
        y = y + jnp.einsum("bign,bgrpn->bigrp", cq, sg, precision=_HI) * jnp.moveaxis(from_start, -1, 1)[..., None]
        new = (from_start[..., -1, None, None] * sg
               + jnp.einsum("bgrj,bjgrp,bjgn->bgrpn", to_end, u, bq, precision=_HI))
        return new.reshape(B, H, P, N), y.reshape(B, Q, H, P)

    state, y = jax.lax.scan(sub_chunk, state.astype(jnp.float32), tuple(split(v) for v in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1).reshape(B, n * Q, H, P)[:, :T], state


def ssd_state_step_ref(
    pool: jnp.ndarray,   # [L, B, H, P, N] float32: every layer's state of every row
    layer: Any,          # scalar int32 (may be traced)
    live: jnp.ndarray,   # [B] bool: rows whose state advances
    u: jnp.ndarray,      # [B, H, P] Delta x
    decay: jnp.ndarray,  # [B, H] exp(Delta A)
    b: jnp.ndarray,      # [B, G, N]
    c: jnp.ndarray,      # [B, G, N]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One position as XLA: (y [B, H, P] without the D term — 0 on a row
    that is not live — and the pool with layer ``layer`` advanced on the
    live rows)."""
    H, G = u.shape[1], b.shape[1]
    s = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False).astype(jnp.float32)  # [B, H, P, N]
    bh, ch = (jnp.repeat(v, H // G, axis=1)[:, :, None, :] for v in (b, c))  # [B, H, 1, N]
    new = decay[..., None, None] * s + u[..., None] * bh
    y = jnp.sum(new * ch, axis=-1)
    new = jnp.where(live[:, None, None, None], new, s).astype(pool.dtype)
    return jnp.where(live[:, None, None], y, 0.0), jax.lax.dynamic_update_index_in_dim(pool, new, layer, 0)


def _step_kernel(layer_ref, rows_ref, n_live_ref, u_ref, decay_ref, b_ref, c_ref, s_in, y_out, s_out):
    """Grid step i: row ``rows[i]``'s state of the layer, advanced by one
    position when i < n_live. The rows are the live ones first and then
    the last live one again, so a step past them keeps the block it had:
    the pipeline neither fetches nor writes back another row."""
    del layer_ref
    i = pl.program_id(0)
    H, P, N = s_in.shape
    G = b_ref.shape[0]
    r = H // G

    @pl.when(i < n_live_ref[0])
    def _advance():
        u = u_ref[...]  # [P, lanes]: Delta x of head h in lane h
        decay = decay_ref[...]  # [1, lanes]
        lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
        y = jnp.zeros(u.shape, jnp.float32)
        for g in range(G):
            brow, crow = b_ref[g:g + 1, :], c_ref[g:g + 1, :]  # [1, N]
            for h in range(g * r, (g + 1) * r):
                # head h's lane by a masked sum (exact: one term is not zero)
                at = lane == h
                col = jnp.sum(jnp.where(at, u, 0.0), axis=1, keepdims=True)  # [P, 1]
                dec = jnp.sum(jnp.where(at[:1], decay, 0.0), axis=1, keepdims=True)  # [1, 1]
                new = dec * s_in[h] + col * brow  # [P, N]
                s_out[h] = new
                y = jnp.where(at, jnp.sum(new * crow, axis=1, keepdims=True), y)
        y_out[...] = y

    @pl.when((i == 0) & (n_live_ref[0] == 0))
    def _none():
        # no row is live: the one block the pipeline holds goes back as it came
        s_out[...] = s_in[...]


@partial(jax.jit, static_argnames=("interpret",))
def ssd_state_step(
    pool: jnp.ndarray,   # [L, B, H, P, N] float32
    layer: Any,          # scalar int32 (may be traced)
    live: jnp.ndarray,   # [B] bool
    u: jnp.ndarray,      # [B, H, P] Delta x
    decay: jnp.ndarray,  # [B, H] exp(Delta A)
    b: jnp.ndarray,      # [B, G, N]
    c: jnp.ndarray,      # [B, G, N]
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`ssd_state_step_ref`'s contract as one Pallas call aliased
    over the whole pool: a grid step a row, the live rows first, each
    live row's state of ``layer`` read once and written once; a row that
    is not live is neither read nor written (but where no row is live:
    row 0's block goes back unchanged). The per-row vectors are handed to
    the kernel with the heads on the lanes, padded to a whole lane tile.
    ``interpret=True`` runs the Pallas interpreter; on the CPU's reference
    path the XLA form runs."""
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        return ssd_state_step_ref(pool, layer, live, u, decay, b, c)
    L, B, H, P, N = pool.shape
    G = b.shape[1]
    lanes = -(-H // _LANES) * _LANES
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)  # live rows first, each once
    rows = jnp.where(jnp.arange(B) < n_live, order, order[jnp.maximum(n_live - 1, 0)])
    u_t = jnp.pad(jnp.swapaxes(u.astype(jnp.float32), 1, 2), ((0, 0), (0, 0), (0, lanes - H)))  # [B, P, lanes]
    dec = jnp.pad(decay.astype(jnp.float32), ((0, 0), (0, lanes - H)))[:, None, :]  # [B, 1, lanes]

    def row(shape):
        return pl.BlockSpec((None,) + shape, lambda i, layer, rows, n: (rows[i],) + (0,) * len(shape))

    state = pl.BlockSpec((None, None, H, P, N), lambda i, layer, rows, n: (layer[0], rows[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the layer, the rows' order, how many are live
        grid=(B,),
        in_specs=[row((P, lanes)), row((1, lanes)), row((G, N)), row((G, N)), state],
        out_specs=[row((P, lanes)), state],
    )
    y_t, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, P, lanes), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the scalar prefetches: 3 scalars, 4 row blocks, the pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_STEP_VMEM),
        cost_estimate=pl.CostEstimate(flops=6 * B * H * P * N, transcendentals=0,
                                      bytes_accessed=2 * B * H * P * N * 4),
        interpret=mode == INTERPRET,
        name="ssd_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, n_live.reshape(1), u_t, dec,
      b.astype(jnp.float32), c.astype(jnp.float32), pool)
    y = jnp.swapaxes(y_t[..., :H], 1, 2)
    return jnp.where(live[:, None, None], y, 0.0), pool


def gated_rms_norm(y: jnp.ndarray, z: jnp.ndarray, w: jnp.ndarray, group: int, eps: float) -> jnp.ndarray:
    """The layer's output norm: ``y * SiLU(z)`` RMS-normed over each group
    of ``group`` channels, times ``w``; float32 [..., C]."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shaped = g.reshape(g.shape[:-1] + (g.shape[-1] // group, group))
    normed = shaped * jax.lax.rsqrt(jnp.mean(shaped * shaped, axis=-1, keepdims=True) + eps)
    return normed.reshape(g.shape) * w.astype(jnp.float32)
