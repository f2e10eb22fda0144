"""State-space (Mamba-1, "S6") ops and the gated memory unit that reads
their output: what a recurrent layer needs beside the matrix products.

    u~_t = SiLU(sum_k c_k * u_{t-K+1+k} + b_c)            causal depthwise conv, kernel K
    [delta | B | C] = u~ W_x ;  Delta = softplus(delta W_dt + b_dt)
    S_t  = exp(Delta_t A) * S_{t-1} + (Delta_t u~_t) (x) B_t     A = -exp(A_log)
    y_t  = S_t C_t + D * u~_t

The state ``S`` is float32 and laid out ``[..., N, Din]`` — the state axis
second to last, the channels last — so that a stored state is whole (8, 128)
tiles (``N`` is 16; the other way round its minor axis would be padded
eightfold). ``A_log`` has the same orientation, ``[N, Din]``. Everything
here is float32: a bf16 ``exp(Delta A)`` or a bf16 state loses the
recurrence over a few thousand steps.

Two forms of the recurrence: ``selective_scan``, a ``lax.scan`` a position
over a chunk or a prompt, and ``selective_step``, the one step a decode step
runs. (An associative scan inside blocks of 16 positions was measured
beside the ``lax.scan`` on a v5e and lost, 1.51 against 1.37 ms for one row
of 256 positions and 158 against 12 ms for 32 rows: PERF.md section 6, PR 35;
``benchmarks/tools/phi4flash_kernels.py`` keeps it for the comparison.)

Positions with ``Delta = 0`` leave the state as it is and add nothing, so a
caller masks padding by zeroing ``Delta`` (``ssm_inputs(..., live=)``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST

def causal_conv(
    u: jnp.ndarray,     # [B, T, Din] the positions to convolve
    tail: jnp.ndarray,  # [B, K-1, Din] the K-1 inputs before them (zeros at a sequence's start)
    w: jnp.ndarray,     # [K, Din] float32, w[K-1] weighs the position itself
    b: jnp.ndarray | None = None,  # [Din]; None: no bias
    silu: bool = True,  # False: the conv as it is (LFM2's gated short convolution)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(SiLU(conv) [B, T, Din] float32 — the conv itself with ``silu``
    False — and the inputs seen [B, K-1+T, Din]: ``conv_tail`` takes the
    next call's ``tail`` out of them)."""
    K, T = w.shape[0], u.shape[1]
    seen = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    acc = None if b is None else b.astype(jnp.float32)
    for k in range(K):
        term = w[k].astype(jnp.float32) * seen[:, k:k + T].astype(jnp.float32)
        acc = term if acc is None else acc + term
    return (jax.nn.silu(acc) if silu else acc), seen


def conv_tail(seen: jnp.ndarray, n: jnp.ndarray, width: int) -> jnp.ndarray:
    """The ``width`` inputs that end at a row's ``n``-th new position:
    ``seen[b, n[b] : n[b] + width]`` (``n = 0`` hands the old tail back)."""
    idx = n[:, None] + jnp.arange(width)[None, :]
    return jnp.take_along_axis(seen, idx[:, :, None], axis=1)


def ssm_inputs(
    u: jnp.ndarray,  # [..., Din] float32, after the conv
    x_proj: jnp.ndarray,  # [Din, R + 2N] float32
    dt_w: jnp.ndarray,    # [R, Din] float32
    dt_b: jnp.ndarray,    # [Din]
    n_state: int,
    live: jnp.ndarray | None = None,  # [...] bool: positions that advance the state
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(Delta [..., Din], B [..., N], C [..., N]), float32. The two small
    products run at ``highest``: Delta is an exponent's factor."""
    R = dt_w.shape[0]
    dbc = jnp.matmul(u, x_proj, precision=_HI)
    delta = jax.nn.softplus(jnp.matmul(dbc[..., :R], dt_w, precision=_HI) + dt_b)
    if live is not None:
        delta = jnp.where(live[..., None], delta, 0.0)
    return delta, dbc[..., R:R + n_state], dbc[..., R + n_state:R + 2 * n_state]


def selective_step(
    u: jnp.ndarray,      # [B, Din] float32
    delta: jnp.ndarray,  # [B, Din]
    a_log: jnp.ndarray,  # [N, Din]
    b: jnp.ndarray,      # [B, N]
    c: jnp.ndarray,      # [B, N]
    d: jnp.ndarray,      # [Din]
    state: jnp.ndarray,  # [B, N, Din] float32 (another type: the state is rounded to it after the step)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One position: (y [B, Din], the new state)."""
    decay = jnp.exp(delta[:, None, :] * -jnp.exp(a_log)[None])
    new = decay * state.astype(jnp.float32) + (delta * u)[:, None, :] * b[:, :, None]
    return jnp.sum(new * c[:, :, None], axis=1) + d * u, new.astype(state.dtype)


def selective_scan(
    u: jnp.ndarray,      # [B, T, Din] float32
    delta: jnp.ndarray,  # [B, T, Din]  (0 at a position that is padding)
    a_log: jnp.ndarray,  # [N, Din]
    b: jnp.ndarray,      # [B, T, N]
    c: jnp.ndarray,      # [B, T, N]
    d: jnp.ndarray,      # [Din]
    state: jnp.ndarray,  # [B, N, Din] float32, the state before position 0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """T positions, a step at a time: (y [B, T, Din], the state after the last)."""
    def step(s: jnp.ndarray, xs: tuple) -> tuple:
        y, s = selective_step(*xs[:2], a_log, *xs[2:], d, s)
        return s, y

    state, y = jax.lax.scan(step, state, tuple(jnp.swapaxes(x, 0, 1) for x in (u, delta, b, c)))
    return jnp.swapaxes(y, 0, 1), state


def gated_memory(m: jnp.ndarray, a: jnp.ndarray, w_gate: Any, w_out: Any, mm: Any) -> jnp.ndarray:
    """The gated memory unit: ``(m * SiLU(a W_g)) W_o`` — ``m`` [..., Din]
    is a state-space layer's output at the same position (before its own
    gate), ``a`` [..., D] this layer's normed input, ``mm`` the model's
    product (plain or int8 weights). Mixes no positions, stores nothing."""
    gate = jax.nn.silu(mm(a, w_gate).astype(jnp.float32))
    return mm((m.astype(jnp.float32) * gate).astype(a.dtype), w_out)
