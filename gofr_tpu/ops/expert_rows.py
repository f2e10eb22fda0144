"""The every-row expert sum as one Mosaic call a layer.

``ops/moe.held_experts`` multiplies every held expert by every row, the
gate as the weight, where the rows are under the ridge and each held
expert expects two rows or more (``moe.groups_rows``). As a Python loop
of XLA products that costs, an expert: two launched fusions (gate and up,
then down), a trip of the float32 running sum ``[T, D]`` through HBM, and
slices of its three scale vectors. :func:`expert_rows` does the whole sum
in one ``pallas_call``:

- the grid is (held experts, tiles of F): step ``(e, f)`` brings the f-th
  column tile of W_gate[e] and W_up[e] and the f-th row tile of W_down[e]
  into VMEM — the Pallas pipeline double-buffers them, so expert e+1's
  bytes stream while expert e multiplies — and adds
  ``g_e ⊙ (silu(x W_g s_g) ⊙ (x W_u s_u)) W_d s_d`` into the float32 output
  block, which stays in VMEM for the whole call and is written once. Tiling
  F is exact: the down scale is per output channel.
- x, the gates and the layer's scale rows are fetched once a call.
- the stacks go in whole, as stored (``[L, n, D, F]`` with a ``layer``,
  ``[n, D, F]`` without), and the layer is a scalar prefetch that the
  blocks' index maps read: nothing is sliced out of a stack by XLA.

Rounding is ``llama._mm``'s: the int8 weights are converted to the
activations' bf16 in VMEM, the products accumulate in float32, the
per-output-channel scale multiplies the float32 result, and the gate and
up products are rounded to bf16 before SiLU and their product, as
``moe._ffn`` rounds them. The down product is not rounded to bf16 before
the gate weighs it (the loop rounds it once an expert): the sum keeps
float32 from the product on.

Which implementation runs is ``ops/backend.kernel_mode``'s: Mosaic on the
chip; on the CPU the loop in ``ops/moe.py`` is the reference, and a test
asks for the Pallas interpreter with ``interpret=True``. :func:`serves`
says whether a tree of stacks can go through the kernel at all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gofr_tpu.ops.backend import INTERPRET, REFERENCE, kernel_mode

# VMEM the call may use, set as its compiler limit: a quarter of the v5e's
# 128 MiB, twice the default scoped limit
_VMEM_LIMIT = 32 * 1024 * 1024
# of which both slots of the three int8 weight tiles may hold this much;
# the rest is their bf16 copies, x, the output block and the scale rows
_WEIGHT_VMEM = _VMEM_LIMIT // 4
_LANES = 128


def serves(stacks: dict) -> bool:
    """Whether the kernel computes the sum over ``stacks`` on this
    platform: not on the CPU's reference path; only int8 ``{"q", "s"}``
    matrices; D and F whole lane tiles (the weight tiles' DMAs and the
    products need 128-lane columns)."""
    w = stacks.get("w_gate")
    if not isinstance(w, dict) or kernel_mode() == REFERENCE:
        return False
    D, F = w["q"].shape[-2:]
    return w["q"].dtype == jnp.int8 and D % _LANES == 0 and F % _LANES == 0


def f_tile(D: int, F: int, budget: int) -> int:
    """Columns of F a grid step takes: the widest multiple of 128 that
    divides F and keeps both slots of three int8 tiles of ``D x tile``
    within ``budget``; 128 where none does."""
    fit = budget // (2 * 3 * D)
    return max([t for t in range(_LANES, F + 1, _LANES) if F % t == 0 and t <= fit] or [_LANES])


def _kernel(layer_ref, x_ref, g_ref, wg_ref, sg_ref, wu_ref, su_ref, wd_ref, sd_ref, o_ref,
            sg_row, su_row, sd_row, weight):
    """Grid step (e, f): expert e's f-th tile of F, added into ``o_ref``
    with the gate column e as the weight. ``layer_ref`` is read by the
    index maps alone."""
    del layer_ref
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(f == 0)
    def _expert():
        # expert e's scale rows and gate column, once an expert, each by a
        # masked sum (exact: one term is not zero) — Mosaic refuses a load
        # at a traced row of a 128-lane tile
        def pick(a, axis):
            at = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis) == e
            return jnp.sum(jnp.where(at, a, 0.0), axis=axis, keepdims=True)

        sg_row[...] = pick(sg_ref[...], 0)
        su_row[...] = pick(su_ref[...], 0)
        sd_row[...] = pick(sd_ref[...], 0)
        weight[...] = pick(g_ref[...], 1)

    x = x_ref[...]  # [T, D]
    tf = wg_ref.shape[-1]
    cols = pl.ds(pl.multiple_of(f * tf, _LANES), tf)

    def product(w_ref, s):
        # llama._mm: int8 as the activations' type, float32 sums, scaled, rounded
        y = jnp.dot(x, w_ref[...].astype(x.dtype), preferred_element_type=jnp.float32)
        return (y * s).astype(x.dtype)

    gate = product(wg_ref, sg_row[:, cols])  # [T, tf]
    up = product(wu_ref, su_row[:, cols])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    h = (act.astype(jnp.float32) * up.astype(jnp.float32)).astype(x.dtype)
    down = jnp.dot(h, wd_ref[...].astype(x.dtype), preferred_element_type=jnp.float32)  # [T, D]
    o_ref[...] += weight[...] * (down * sd_row[...])


def _call(x: jnp.ndarray, g: jnp.ndarray, stacks: dict, layer: jnp.ndarray, interpret: bool,
          budget: int | None = None) -> jnp.ndarray:
    """The pallas_call over stacks ``[L, n, ...]`` at ``layer``; the
    weight tiles' VMEM is :data:`_WEIGHT_VMEM` unless ``budget`` says
    otherwise (the probe's)."""
    T, D = x.shape
    wg, wu, wd = (stacks[k] for k in ("w_gate", "w_up", "w_down"))
    n, F = wg["q"].shape[1], wg["q"].shape[3]
    tf = f_tile(D, F, _WEIGHT_VMEM if budget is None else budget)

    def whole(shape):
        return pl.BlockSpec(shape, lambda e, f, layer: (0,) * len(shape))

    def scales(width):  # the layer's rows [n, width], fetched once
        return pl.BlockSpec((None, n, width), lambda e, f, layer: (layer[0], 0, 0))

    column_tile = pl.BlockSpec((None, None, D, tf), lambda e, f, layer: (layer[0], e, 0, f))
    row_tile = pl.BlockSpec((None, None, tf, D), lambda e, f, layer: (layer[0], e, f, 0))
    cost = pl.CostEstimate(
        flops=2 * 3 * T * D * F * n,
        transcendentals=T * F * n,
        bytes_accessed=3 * n * D * F + 4 * n * (2 * F + D) + T * D * (x.dtype.itemsize + 4),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the layer
        grid=(n, F // tf),
        in_specs=[whole((T, D)), whole((T, n)), column_tile, scales(F), column_tile, scales(F),
                  row_tile, scales(D)],
        out_specs=whole((T, D)),
        scratch_shapes=[pltpu.VMEM((1, F), jnp.float32), pltpu.VMEM((1, F), jnp.float32),
                        pltpu.VMEM((1, D), jnp.float32), pltpu.VMEM((T, 1), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # both axes add into the one output block
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="expert_rows",
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, g, wg["q"], wg["s"], wu["q"], wu["s"], wd["q"], wd["s"])


def expert_rows(
    x: jnp.ndarray,  # [T, D] the activations (bf16)
    g: jnp.ndarray,  # [T, n] float32: each row's weight of each expert of the stacks
    stacks: dict,  # w_gate / w_up {"q": int8 [(L,) n, D, F], "s": f32 [(L,) n, F]}, w_down [(L,) n, F, D]
    layer: jnp.ndarray | int | None = None,  # the stacks are [L, n, ...] and this (traced) layer's is meant
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Σ_e g[:, e] · FFN_e(x), float32 [T, D], over every expert of the
    stacks (one layer's, or layer ``layer`` of whole stacks) and every row.
    ``interpret=True`` runs the Pallas interpreter (a test's request); on
    the chip Mosaic. The CPU's reference is ``ops/moe``'s loop, which
    :func:`serves` sends callers to."""
    mode = kernel_mode(interpret)
    if mode == REFERENCE:
        raise ValueError("expert_rows has no reference of its own: ops/moe's loop over every row is it")
    if layer is None:  # one layer's stacks as a stack of one (a bitcast)
        stacks, layer = jax.tree.map(lambda a: a[None], stacks), 0
    return _call(x, g.astype(jnp.float32), stacks, layer, mode == INTERPRET)

