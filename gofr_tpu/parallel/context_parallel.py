"""Context/sequence parallelism: ring attention and Ulysses all-to-all.

The reference framework has no tensor concept at all (SURVEY §5.7 — its only
"sequence length" is a 32 MB multipart cap, http/request.go:18), so this
module is sourced from the TPU/LLM literature rather than the reference:
long sequences are sharded on the ``sp`` mesh axis and attention runs either

- **ring attention**: each device keeps its Q shard resident and streams KV
  shards around the ``sp`` ring with ``ppermute`` (nearest-neighbor ICI
  hops), accumulating with an online-softmax — peak memory per chip is
  O(S/n) and the KV transfer overlaps with the block matmul, or
- **Ulysses**: two ``all_to_all`` reshardings (seq→heads, heads→seq) so the
  middle runs ordinary full-sequence attention with H/n heads per device —
  preferable when head-count ≥ ring size and seq fits after resharding.

Both are SPMD-per-device functions wrapped in ``jax.shard_map`` over the
framework mesh (parallel/mesh.py axis vocabulary), so XLA compiles the
collectives onto ICI — no NCCL-style runtime calls exist anywhere (SURVEY
§2.9: the runtime's job is mesh ownership, not collectives).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gofr_tpu.ops.attention import NEG_INF, gqa_repeat
from gofr_tpu.parallel.mesh import require_axis



def _block_accumulate(q, k, v, acc, m, l, q_start, k_start, scale):
    """One online-softmax block update.

    q: [B,Sq,H,D]; k/v: [B,Sk,Hkv,D]; acc: [B,Sq,H,D] f32;
    m, l: [B,H,Sq] f32 running max / denominator.
    Positions are global: ``q_start``/``k_start`` are the absolute offsets of
    the local blocks, so the causal mask is exact across ring steps.
    """
    H = q.shape[2]
    k = gqa_repeat(k, H)
    v = gqa_repeat(v, H)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale

    q_pos = q_start + jnp.arange(q.shape[1])  # [Sq]
    k_pos = k_start + jnp.arange(k.shape[1])  # [Sk]
    mask = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
    logits = jnp.where(mask[None, None], logits, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    # exp(NEG_INF - NEG_INF) == 1 for fully-masked blocks: zero those probs
    # explicitly instead of trusting the subtraction.
    p = jnp.exp(logits - m_new[..., None]) * mask[None, None]
    corr = jnp.exp(m - m_new)  # [B,H,Sq]
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return acc_new, m_new, l_new


def ring_attention_sharded(
    q: jnp.ndarray,  # [B, S_loc, H, D] — this device's sequence shard
    k: jnp.ndarray,  # [B, S_loc, Hkv, D]
    v: jnp.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    scale: float | None = None,
) -> jnp.ndarray:
    """Causal ring attention; call inside shard_map with seq sharded on
    ``axis_name``. KV blocks rotate the ring; block ``(i - s) mod n`` is
    resident at device ``i`` on step ``s``."""
    B, S_loc, H, D = q.shape
    n = axis_size
    scale = scale if scale is not None else D ** -0.5
    idx = jax.lax.axis_index(axis_name)
    q_start = idx * S_loc

    acc = jnp.zeros((B, S_loc, H, D), jnp.float32)
    m = jnp.full((B, H, S_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S_loc), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(s, carry):
        k_blk, v_blk, acc, m, l = carry
        src = (idx - s) % n
        acc, m, l = _block_accumulate(
            q, k_blk, v_blk, acc, m, l, q_start, src * S_loc, scale
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, acc, m, l

    # n-1 accumulate+rotate steps, then a final accumulate with no rotation
    # (the last rotated blocks would be discarded — one ICI hop saved/layer)
    k_blk, v_blk, acc, m, l = jax.lax.fori_loop(0, n - 1, body, (k, v, acc, m, l))
    acc, m, l = _block_accumulate(
        q, k_blk, v_blk, acc, m, l, q_start, ((idx - (n - 1)) % n) * S_loc, scale
    )
    out = acc / (l.transpose(0, 2, 1)[..., None] + 1e-30)
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D] global view
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "sp",
    scale: float | None = None,
) -> jnp.ndarray:
    """shard_map wrapper: shards seq on ``axis``, runs the ring."""
    n = require_axis(mesh, axis)
    if q.shape[1] % n != 0:
        raise ValueError(f"seq {q.shape[1]} not divisible by {axis}={n}")
    spec = P(None, axis, None, None)
    fn = functools.partial(
        ring_attention_sharded, axis_name=axis, axis_size=n, scale=scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention_sharded(
    q: jnp.ndarray,  # [B, S_loc, H, D]
    k: jnp.ndarray,  # [B, S_loc, Hkv, D]
    v: jnp.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    scale: float | None = None,
) -> jnp.ndarray:
    """Ulysses: all_to_all seq→heads, full-seq attention on H/n heads,
    all_to_all back. Requires H % n == 0 (KV heads are broadcast up first
    when Hkv doesn't divide)."""
    from gofr_tpu.ops.attention import attention

    import math as _math

    H = q.shape[2]
    n = axis_size
    if H % n != 0:
        raise ValueError(f"heads {H} not divisible by {axis_name}={n}")
    if k.shape[2] % n != 0:
        # repeat KV only to lcm(Hkv, n) — enough for an even head split;
        # the inner attention contracts grouped queries against the
        # unexpanded KV, so the all_to_all moves the minimum KV volume
        target = _math.lcm(k.shape[2], n)
        k = gqa_repeat(k, target)
        v = gqa_repeat(v, target)

    def reshard_in(x):  # [B,S_loc,h,D] -> [B,S,h/n,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def reshard_out(x):  # [B,S,H/n,D] -> [B,S_loc,H,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q2, k2, v2 = reshard_in(q), reshard_in(k), reshard_in(v)
    out = attention(q2, k2, v2, causal=True, scale=scale)
    return reshard_out(out)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "sp",
    scale: float | None = None,
) -> jnp.ndarray:
    n = require_axis(mesh, axis)
    if q.shape[1] % n != 0:
        raise ValueError(f"seq {q.shape[1]} not divisible by {axis}={n}")
    spec = P(None, axis, None, None)
    fn = functools.partial(
        ulysses_attention_sharded, axis_name=axis, axis_size=n, scale=scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Trace-time context so model code can pick up the CP mesh without threading
# it through every call (static at jit trace time, like cfg fields).

_cp_state: list[tuple[Mesh, str, str]] = []


class cp_context:
    """``with cp_context(mesh, axis="sp", impl="ring"): forward(...)`` —
    layers whose config says ``attn_impl="cp"`` use this mesh/axis."""

    def __init__(self, mesh: Mesh, axis: str = "sp", impl: str = "ring") -> None:
        if impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown cp impl {impl!r}")
        self.entry = (mesh, axis, impl)

    def __enter__(self):
        _cp_state.append(self.entry)
        return self

    def __exit__(self, *exc: Any):
        _cp_state.pop()
        return False


def current_cp() -> tuple[Mesh, str, str] | None:
    return _cp_state[-1] if _cp_state else None


def cp_attention(q, k, v, *, scale: float | None = None) -> jnp.ndarray:
    """Dispatch to ring/ulysses per the ambient cp_context (model hook)."""
    state = current_cp()
    if state is None:
        raise RuntimeError("attn_impl='cp' requires an enclosing cp_context(mesh)")
    mesh, axis, impl = state
    fn = ring_attention if impl == "ring" else ulysses_attention
    return fn(q, k, v, mesh, axis=axis, scale=scale)
