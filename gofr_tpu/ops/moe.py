"""Mixture-of-Experts ops: top-k routing and expert-parallel dispatch.

No counterpart exists in the reference (SURVEY §2.9 lists EP as absent);
design follows the GShard/Mixtral lineage, TPU-first:

- routing and the dispatch/combine one-hots are dense einsums (MXU work,
  static shapes — no dynamic gather/scatter that would defeat XLA),
- expert parallelism is a ``shard_map`` over the ``ep`` mesh axis: tokens
  are grouped per device, ``all_to_all`` carries each group's dispatched
  tokens to the devices owning their experts and back — the two transposes
  ride ICI, exactly the pattern the scaling book prescribes for MoE.

Capacity model: each expert accepts at most C tokens per group
(C = ceil(top_k · tokens/E) · capacity_factor); overflow tokens fall
through with a zero expert contribution (standard GShard drop policy) and
the combine weights are renormalized over the surviving assignments.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gofr_tpu.ops import expert_rows
from gofr_tpu.parallel.mesh import require_axis


def router_topk(
    x: jnp.ndarray,  # [T, D]
    w_router: jnp.ndarray,  # [D, E]
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k gating: returns (expert_idx [T, k], gate_weights [T, k],
    full_probs [T, E]); weights are softmax probs renormalized over the
    selected k; full_probs feed the load-balance aux loss."""
    logits = (x @ w_router).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)
    return top_i, top_p, probs


def switch_aux_stats(
    top_i: jnp.ndarray,  # [T, k]
    probs: jnp.ndarray,  # [T, E]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-expert (f_e, P_e) from the ACTUAL routing decisions: f_e is the
    fraction of tokens whose top-1 choice is e, P_e the mean router prob —
    the two factors of the Switch-transformer load-balance loss."""
    n_experts = probs.shape[-1]
    top1 = top_i[:, 0]
    f = jnp.mean(jax.nn.one_hot(top1, n_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return f, p


def _dispatch_combine(
    top_i: jnp.ndarray,  # [T, k]
    top_p: jnp.ndarray,  # [T, k]
    n_experts: int,
    capacity: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Build GShard dispatch [T, E, C] (one-hot) and combine [T, E, C]
    (gate-weighted) tensors. Position of a token within its expert's buffer
    is its routing order (cumsum over tokens)."""
    T, k = top_i.shape
    onehot = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32)  # [T, k, E]
    # position within each expert buffer, counted over (token, k) in order
    flat = onehot.reshape(T * k, n_experts)
    pos = jnp.cumsum(flat, axis=0) - flat  # [T*k, E] position if routed
    pos = pos.reshape(T, k, n_experts)
    in_cap = (pos < capacity).astype(jnp.float32)
    keep = onehot * in_cap  # [T, k, E]
    pos_idx = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T, k]
    cap_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)  # [T, k, C]
    dispatch = jnp.einsum("tke,tkc->tec", keep, cap_onehot)
    combine = jnp.einsum("tke,tkc,tk->tec", keep, cap_onehot, top_p)
    # renormalize over surviving assignments so dropped tokens don't skew
    surv = jnp.einsum("tec->t", combine)
    combine = combine / (surv[:, None, None] + 1e-9)
    mask_any = (jnp.einsum("tec->t", dispatch) > 0)[:, None, None]
    combine = jnp.where(mask_any, combine, 0.0)
    return dispatch, combine


def expert_ffn(
    h: jnp.ndarray,  # [E, N, D] tokens grouped per expert
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
) -> jnp.ndarray:
    """SwiGLU FFN per expert — batched einsum over the expert axis (MXU)."""
    gate = jnp.einsum("end,edf->enf", h, w_gate)
    up = jnp.einsum("end,edf->enf", h, w_up)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    return jnp.einsum("enf,efd->end", act, w_down)


def capacity_for(tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    return max(1, math.ceil(top_k * tokens / n_experts * factor))


def moe_ffn_reference(
    x: jnp.ndarray,  # [T, D]
    w_router: jnp.ndarray,
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    top_k: int = 2,
    return_stats: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense reference (no capacity drops, no EP): every expert computes
    every token, combined by the top-k gates. O(E·T·D·F) — test/debug only."""
    top_i, top_p, probs = router_topk(x, w_router, top_k)
    all_out = expert_ffn(
        jnp.broadcast_to(x, (w_gate.shape[0], *x.shape)), w_gate, w_up, w_down
    )  # [E, T, D]
    onehot = jax.nn.one_hot(top_i, w_gate.shape[0], dtype=jnp.float32)  # [T,k,E]
    weights = jnp.einsum("tke,tk->te", onehot, top_p)  # [T, E]
    y = jnp.einsum("etd,te->td", all_out.astype(jnp.float32), weights).astype(x.dtype)
    if return_stats:
        f, p = switch_aux_stats(top_i, probs)
        return y, f, p
    return y


def moe_ffn_ep_sharded(
    x: jnp.ndarray,  # [t, D] — this device's token group
    w_router: jnp.ndarray,  # [D, E] replicated
    w_gate: jnp.ndarray,  # [E_loc, D, F] — local expert shard
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    axis_name: str,
    axis_size: int,
    n_experts: int,
    top_k: int,
    capacity: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-device body: route locally, all_to_all tokens to expert owners,
    run local experts, all_to_all back, combine. Also returns the global
    (pmean over the axis) per-expert (f_e, P_e) aux-loss stats."""
    n = axis_size
    e_loc = n_experts // n
    top_i, top_p, probs = router_topk(x, w_router, top_k)
    f_loc, p_loc = switch_aux_stats(top_i, probs)
    f = jax.lax.pmean(f_loc, axis_name)
    p = jax.lax.pmean(p_loc, axis_name)
    dispatch, combine = _dispatch_combine(top_i, top_p, n_experts, capacity)

    # [t, E, C] x [t, D] -> [E, C, D], grouped by owning device
    sent = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    sent = sent.reshape(n, e_loc, capacity, -1)
    # exchange: device g receives, from every peer p, the block destined for
    # g's experts; afterwards axis 0 indexes the source group
    recv = jax.lax.all_to_all(sent, axis_name, split_axis=0, concat_axis=0, tiled=True)
    h = recv.transpose(1, 0, 2, 3).reshape(e_loc, n * capacity, -1)  # [E_loc, N, D]
    out = expert_ffn(h, w_gate, w_up, w_down)  # [E_loc, N, D]
    out = out.reshape(e_loc, n, capacity, -1).transpose(1, 0, 2, 3)  # [n, E_loc, C, D]
    back = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0, tiled=True)
    back = back.reshape(n_experts, capacity, -1)  # [E, C, D] for this group
    y = jnp.einsum("ecd,tec->td", back.astype(jnp.float32), combine).astype(x.dtype)
    return y, f, p


def moe_ffn_ep(
    x: jnp.ndarray,  # [T, D] global tokens
    w_router: jnp.ndarray,
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "ep",
    top_k: int = 2,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
    return_stats: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE FFN: tokens grouped on ``axis``, experts sharded
    on ``axis``, two all_to_all transposes over ICI. With ``return_stats``
    also returns the global per-expert (f_e, P_e) for the aux loss."""
    n = require_axis(mesh, axis)
    T = x.shape[0]
    E = w_gate.shape[0]
    if T % n != 0:
        raise ValueError(f"tokens {T} not divisible by {axis}={n}")
    if E % n != 0:
        raise ValueError(f"experts {E} not divisible by {axis}={n}")
    cap = capacity or capacity_for(T // n, E, top_k, capacity_factor)
    fn = functools.partial(
        moe_ffn_ep_sharded,
        axis_name=axis,
        axis_size=n,
        n_experts=E,
        top_k=top_k,
        capacity=cap,
    )
    espec = P(axis)
    out, f, p = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis), P(), espec, espec, espec),
        out_specs=(P(axis), P(), P()),
        axis_names={axis},
    )(x, w_router, w_gate, w_up, w_down)
    return (out, f, p) if return_stats else out


# ----------------------------------------------------- dropless, for serving
def sigmoid_topk_gates(
    h: jnp.ndarray,  # [T, D]
    w_router: jnp.ndarray,  # [D, E] float32: every published expert
    k: int,
    *,
    bias: jnp.ndarray | None = None,  # [E] added to the scores for the CHOICE only
    n_group: int = 1,  # the experts in this many equal groups ...
    topk_group: int = 1,  # ... of which a row keeps this many
    scale: float = 1.0,  # the routed scaling factor
) -> jnp.ndarray:
    """The sigmoid rule with ``norm_topk_prob``: scores ``sigmoid(W_r h)``
    in float32 (the product at ``highest``: a bf16 pass can swap the 8th
    and 9th expert), the ``k`` largest kept and divided by their sum.
    Returns the gates [T, E] float32, zero off the chosen experts.

    The group-limited form (``noaux_tc``): the choice is made on ``scores +
    bias``; with ``n_group > 1`` a group's score is the sum of its two
    largest corrected scores, the ``topk_group`` best groups stay, and the
    ``k`` experts are chosen among theirs. The gates are the UNcorrected
    scores of the chosen experts over their sum, times ``scale``. The
    neutral values (no bias, one group, scale 1) give the plain
    rule bit for bit, by the plain rule's own operations: a program that
    passes none of them compiles as it did before they existed."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    if bias is None and n_group <= 1:
        top_s, top_i = jax.lax.top_k(scores, k)  # the plain rule: the choice is made on the scores
    else:
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        if n_group > 1:
            T, E = scores.shape
            grouped = choice.reshape(T, n_group, E // n_group)
            group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, n_group]
            _, kept = jax.lax.top_k(group_score, topk_group)
            stays = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.float32), axis=1) > 0
            choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(T, E)
        _, top_i = jax.lax.top_k(choice, k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    if scale != 1.0:
        top_s = top_s * scale
    onehot = jax.nn.one_hot(top_i, scores.shape[-1], dtype=jnp.float32)  # [T, k, E]
    return jnp.einsum("tke,tk->te", onehot, top_s)


# Rows of int8 weights a product needs before the chip's arithmetic, and
# not its memory, bounds it: 2 FLOPs a byte and row against 197 TFLOP/s
# over 819 GB/s = 240 FLOPs a byte, about 120 rows on a v5e; the prefill
# bucket nearest above it.
RIDGE_ROWS = 128

# Places of one expert a step of the grouped product takes. On the chip 16,
# 32 and 64 read within 3 % of each other at 32 rows and at a chunk of 256
# (PERF.md §6, PR 34); 16 reads experts of 17-32 rows twice.
TILE_ROWS = 32


def groups_rows(T: int, n_experts: int, top_k: int | None) -> bool:
    """Whether :func:`held_experts` multiplies each held expert by its own
    rows alone (True) or by every row with the gate as the weight (False),
    for ``T`` rows that each choose ``top_k`` of ``n_experts`` published
    experts — every one of them where ``top_k`` is not told. The loop over
    every row is right where both hold: the rows are under the ridge
    (:data:`RIDGE_ROWS`: each matrix is read once and the products for zero
    gates hide under that read) and a held expert expects two rows or more
    (``T · top_k / n_experts``: every expert is reached, so every matrix
    would be read anyway, and a sort and a gather can only add). Past the
    ridge the products for zero gates are time the chip spends on nothing;
    under two rows an expert, matrices are read that no row chose."""
    return T > RIDGE_ROWS or T * (n_experts if top_k is None else top_k) < 2 * n_experts


def path(T: int, n_experts: int, top_k: int | None, experts: dict) -> str:
    """The branch :func:`held_experts` takes for ``T`` rows over the
    stacks ``experts``, by the tests it makes itself: ``grouped`` where
    :func:`groups_rows`, else the every-row sum as one Mosaic call a layer
    (``kernel``: ``expert_rows.serves`` the stacks) or as the loop of XLA
    products (``loop``: on the CPU, or stacks that are not int8 of whole
    lane tiles). The engine's host mirror."""
    if groups_rows(T, n_experts, top_k):
        return "grouped"
    return "kernel" if expert_rows.serves(experts) else "loop"


def held_experts(
    h: jnp.ndarray,  # [T, D]
    gates: jnp.ndarray,  # [T, E] over every published expert
    experts: dict,  # w_gate/w_up [held, D, F], w_down [held, F, D]
    shared: dict,  # the same three, [n_shared, ...]
    first: Any,  # index of the first held expert among the published ones
    mm: Any = jnp.matmul,  # the product for the weights' storage (llama._mm for int8)
    layer: Any = None,  # the stacks are [L, held, ...] and this (traced) layer's is meant
    *,
    top_k: int | None = None,  # experts a row chooses (None: any number)
    rows: jnp.ndarray | None = None,  # [T] bool: the rows whose output counts (None: all)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One chip's part of a dropless expert layer: the sum over the HELD
    experts ``first .. first + held`` of ``g_e · FFN_e(h)``, plus the mean
    of the shared experts, which every chip computes alike: exact, no
    capacity, no drop. With ``first=0`` and every expert held this is the
    whole layer; the shares of all chips, the shared part counted once,
    add up to it. Returns the float32 sum [T, D], the held experts' gates
    [T, held], and how many held experts' matrices were read (int32
    scalar).

    Two ways to the same sum, chosen from the shapes alone
    (:func:`groups_rows`, which has the rule and its two reasons): every
    held expert over every row with its gate as the weight, zero where the
    row chose another expert (:func:`_over_every_row`: all ``held`` are
    read, whatever the routing; one Mosaic call a tree of int8 stacks on
    the chip, ``ops/expert_rows.py``); or each held expert over the rows
    that chose it, in tiles (:func:`_over_own_rows`), where an expert that
    no counted row chose reads nothing and a row that ``rows`` leaves out
    pulls no expert (its output is nobody's to read). :func:`path` names
    the branch.

    Inside a scan over layers, hand the stacks over whole with ``layer``:
    a matrix is then ONE dynamic slice of its stack with one product to
    read it, which XLA fuses; the scan's own slice of a layer's experts
    has as many readers as experts and is copied out first (268 MB a
    matrix kind at 16 experts of 4096 x 4096)."""
    held = _experts_in(experts, layer)
    g = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)  # [T, held]
    T, E = gates.shape
    if held == 0 or not groups_rows(T, E, top_k):
        return _over_every_row(h, g, experts, shared, mm, layer), g, jnp.int32(held)
    y, read = _over_own_rows(h, g, experts, mm, layer, rows)
    return _add_shared(y, h, shared, mm, layer), g, read


def _ffn(x: jnp.ndarray, w: dict, e: Any, mm: Any, layer: Any) -> jnp.ndarray:
    """Expert ``e``'s SwiGLU over x [n, D], float32."""
    gate = jax.nn.silu(mm(x, _at(w["w_gate"], e, layer)).astype(jnp.float32)).astype(x.dtype)
    return mm(gate * mm(x, _at(w["w_up"], e, layer)), _at(w["w_down"], e, layer)).astype(jnp.float32)


def _add_shared(y: jnp.ndarray, h: jnp.ndarray, shared: dict, mm: Any, layer: Any) -> jnp.ndarray:
    """y plus the mean of the shared experts over every row of h."""
    n_shared = _experts_in(shared, layer)
    for e in range(n_shared):
        y = y + _ffn(h, shared, e, mm, layer) / n_shared
    return y


def _over_every_row(h: jnp.ndarray, g: jnp.ndarray, experts: dict, shared: dict, mm: Any,
                    layer: Any) -> jnp.ndarray:
    """Every held expert over every row, the gate ``g`` [T, held] as the
    weight; then the shared experts' mean, the same sum with 1/n_shared
    over every row. Where ``expert_rows.serves`` the stacks, each tree
    (routed, shared) is one Mosaic call — the stacks whole, the layer a
    scalar the call reads; both trees are stored alike (the families'
    ``quantize_params``) — else :func:`_loop_over_every_row`, the
    reference."""
    if not expert_rows.serves(experts):
        return _loop_over_every_row(h, g, experts, shared, mm, layer)
    y = expert_rows.expert_rows(h, g, experts, layer)
    n_shared = _experts_in(shared, layer)
    if not n_shared:
        return y
    mean = jnp.full((h.shape[0], n_shared), 1.0 / n_shared, jnp.float32)
    return y + expert_rows.expert_rows(h, mean, shared, layer)


def _loop_over_every_row(h: jnp.ndarray, g: jnp.ndarray, experts: dict, shared: dict, mm: Any,
                         layer: Any) -> jnp.ndarray:
    """:func:`_over_every_row` as a loop of XLA products, an expert at a
    time: the CPU's path, the reference of the kernel and of the grouped
    product."""
    y = jnp.zeros(h.shape, jnp.float32)
    for e in range(g.shape[1]):
        y = y + g[:, e:e + 1] * _ffn(h, experts, e, mm, layer)
    return _add_shared(y, h, shared, mm, layer)


def _over_own_rows(h: jnp.ndarray, g: jnp.ndarray, experts: dict, mm: Any, layer: Any,
                   rows: jnp.ndarray | None, tile: int = TILE_ROWS) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The grouped product of the held experts: a counting pass over
    ``g > 0`` gives each row its place among its expert's rows; ONE loop
    then runs over the tiles of ``tile`` places that hold a row, expert
    after expert — an expert with no row has no tile and reads nothing —
    and a tile's rows are taken out of ``h`` and its results added back to
    them, gate as weight, by two products with the tile's 0/1 matrix
    (exact: one term a sum). Returns the sum and how many held experts had
    a tile."""
    weight = (g if rows is None else jnp.where(rows[:, None], g, 0.0)).astype(jnp.float32)  # [T, held]
    chosen = weight > 0
    place = jnp.cumsum(chosen, axis=0, dtype=jnp.int32) - 1  # a row's place among its expert's rows
    tiles = (jnp.sum(chosen, axis=0, dtype=jnp.int32) + tile - 1) // tile  # [held]
    ends = jnp.cumsum(tiles)
    slots = jnp.arange(tile, dtype=jnp.int32)[:, None]
    exact = jax.lax.Precision.HIGHEST

    def step(i, y):
        e = jnp.sum(ends <= i, dtype=jnp.int32)  # the expert tile i belongs to
        w_rows = jax.lax.dynamic_index_in_dim(weight, e, axis=1, keepdims=False)
        place_rows = jax.lax.dynamic_index_in_dim(place, e, axis=1, keepdims=False)
        first_place = (i - ends[e] + tiles[e]) * tile
        take = (w_rows > 0)[None, :] & (place_rows[None, :] - first_place == slots)  # [tile, T]
        x = jnp.matmul(take.astype(h.dtype), h, precision=exact, preferred_element_type=jnp.float32).astype(h.dtype)
        back = jnp.where(take, w_rows[None, :], 0.0).T  # [T, tile]
        return y + jnp.matmul(back, _ffn(x, experts, e, mm, layer), precision=exact)

    y = jax.lax.fori_loop(0, ends[-1], step, jnp.zeros(h.shape, jnp.float32))
    return y, jnp.sum(tiles > 0, dtype=jnp.int32)


def _experts_in(stacks: dict, layer: Any) -> int:
    """How many experts a tree of stacks [n, ...] — or [L, n, ...] with a ``layer`` — holds."""
    return jax.tree.leaves(stacks["w_gate"])[0].shape[0 if layer is None else 1]


def _at(w: Any, e: Any, layer: Any) -> Any:
    """Expert ``e``'s matrix of a stack [held, ...] — or of layer ``layer``
    of a stack [L, held, ...] — plain or ``{"q", "s"}`` int8; ``e`` may be
    traced."""
    if layer is None:
        return jax.tree.map(lambda a: a[e], w)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(
            a.reshape((-1,) + a.shape[2:]), layer * a.shape[1] + e, 0, keepdims=False),
        w,
    )
