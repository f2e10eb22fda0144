"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style).

Absent from the reference by design (SURVEY §2.9 — GoFr's unit of scale is
the stateless replica); this is the TPU-native equivalent: transformer
layers are stage-sharded over ``pp`` (stage s owns layers
[s·L/n, (s+1)·L/n)), microbatches stream through the stages, and activations
hop stage→stage with ``ppermute`` — a nearest-neighbor ICI transfer compiled
by XLA, exactly where the reference would have used a broker or gRPC hop
between services.

Composition with the other axes is by **partial manual mapping**:
``shard_map(..., axis_names={'pp'})`` makes only the pipeline axis manual;
tp/fsdp/dp stay under GSPMD, so the Megatron TP shardings of each stage's
weights keep working inside the pipeline body with zero extra code.

Schedule: single-direction fill-drain (GPipe). T = M + n - 1 ticks; stage 0
feeds microbatch t at tick t, the last stage emits microbatch t-(n-1).
Bubble fraction (n-1)/(M+n-1) — callers pick M ≥ n to amortize.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gofr_tpu.parallel.mesh import require_axis


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x_mb: jnp.ndarray,  # [M, b, ...] microbatched activations
    mesh: Mesh,
    *,
    axis: str = "pp",
) -> jnp.ndarray:
    """Run ``stage_fn(local_stage_params, x) -> x`` through the pp ring.

    ``stage_params`` leaves are stage-stacked on axis 0 (global [L, ...],
    manual-sharded to [L/n, ...] per device). ``x_mb`` is replicated over
    pp (dp/tp shardings of the batch/feature dims remain in GSPMD's hands).
    Output has the same shape as ``x_mb``, valid on every pp rank.
    """
    n = require_axis(mesh, axis)
    if n == 1:
        return jax.lax.map(lambda x: stage_fn(stage_params, x), x_mb)

    M = x_mb.shape[0]
    T = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]
    param_specs = jax.tree.map(lambda _: P(axis), stage_params)

    def body(stage_local: Any, x_mb: jnp.ndarray) -> jnp.ndarray:
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            recv, outs = carry
            mb_in = x_mb[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(stage == 0, mb_in, recv)
            out = stage_fn(stage_local, inp)
            out_idx = t - (n - 1)
            idx = jnp.clip(out_idx, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, idx, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(out_idx >= 0, out, cur), idx, axis=0
            )
            recv = jax.lax.ppermute(out, axis, perm)
            return (recv, outs), None

        # carries become pp-varying after the first ppermute: mark the
        # replicated zeros as varying up front so scan's carry types match
        outs0 = jax.lax.pcast(jnp.zeros_like(x_mb), (axis,), to="varying")
        recv0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (axis,), to="varying")
        (recv, outs), _ = jax.lax.scan(tick, (recv0, outs0), jnp.arange(T))
        # only the last stage accumulated real outputs; broadcast over pp
        mask = (stage == n - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        axis_names={axis},
    )(stage_params, x_mb)


# ----------------------------------------------------------------- llama glue


def pp_forward(
    cfg: Any,
    params: dict,
    tokens: jnp.ndarray,  # [B, S]
    mesh: Mesh,
    *,
    microbatches: int | None = None,
    axis: str = "pp",
) -> jnp.ndarray:
    """Llama forward with the layer stack pipelined over ``axis``.
    Embedding and LM head run outside the pipeline (replicated over pp,
    TP/DP-sharded by GSPMD as usual). Returns logits [B, S, V]."""
    from gofr_tpu.models.llama import _layer, _logits
    from gofr_tpu.ops.rope import rope_table

    if cfg.attn_impl == "cp":
        raise ValueError("attn_impl='cp' cannot nest inside pp_forward")
    n = require_axis(mesh, axis)
    if cfg.n_layers % n != 0:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={n}")
    M = microbatches or max(n, 1)
    B, S = tokens.shape
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches={M}")

    x = params["embedding"][tokens].astype(cfg.dtype)  # [B, S, D]
    sin, cos = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(S), (B // M, S))

    def stage_fn(stage_layers: dict, h: jnp.ndarray) -> jnp.ndarray:
        def layer_body(h, lp):
            h = _layer(cfg, h, lp, sin, cos, positions)
            return h, None

        h, _ = jax.lax.scan(layer_body, h, stage_layers)
        return h

    x_mb = x.reshape(M, B // M, S, -1)
    out = pipeline_apply(stage_fn, params["layers"], x_mb, mesh, axis=axis)
    x = out.reshape(B, S, -1)
    return _logits(cfg, params, x)
