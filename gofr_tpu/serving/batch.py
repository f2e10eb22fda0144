"""Jitted fixed-shape device functions for the continuous-batching engine.

All shapes are static (slot count, padded prompt buckets) so everything
compiles once per bucket and never again — the XLA contract. Slots are rows
of the batch; each row's KV lives in pages of a persistent pool, addressed
through its block table (``serving/kv_cache.py``). Requests come and go
between steps by writing pages and masking rows, with the pools donated
end-to-end so the cache never copies.

Device-side state per engine:
- the page pools: k/v [L, N_pages+1, Hkv, page, Dh] (+1: the trash page),
  or the model's own pools by layer kind (``cache_spec``)
- ``DecodeState``: the per-row decode carry (last token, resident length,
  done flag, remaining token budget, stop id, sampling params, PRNG key) —
  donated through every block dispatch and every admission scatter, so the
  host never reads it and nothing aliases it

The decode hot loop is CPU-free (Blink, arXiv:2604.07609): sampling AND
stop-condition evaluation run inside the jitted N-step block
(``decode_block_paged``), which returns ONE packed int32 [B, steps+2] array —
``steps`` token columns (-1 past each row's stop), a done column, and an
n_valid column — so the engine's single host sync happens once per N
tokens instead of once per token.

This file is a shardcheck retrace zone (``make lint``): donated buffers
must be rebound at every call site (``use-after-donation``) and nothing
here may branch on traced values or take unhashable statics
(``retrace-hazard``) — one per-request recompile eats the whole TTFT
budget.

Every jitted entry here is ALSO under device contract: its parameter
tuple, donated/static sets, packed output layout, and carry signatures
are declared in ``gofr_tpu/analysis/kernel_contracts.KERNELS`` and
enforced by kernelcheck + the eval_shape runtime twin
(docs/static-analysis.md "kernelcheck — device-contract analysis").
Changing a signature, a pack column, or a ``DecodeState`` field means
updating the contract table in the same commit — the lint gate and the
tier-1 matrix both fail otherwise, by design.
"""

from __future__ import annotations

import dataclasses
import sys
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.ops.sampling import sample_logits, sampler_path, stop_eval


def model_of(cfg: Any) -> Any:
    """The module that serves ``cfg``: the one its class is defined in
    (``LlamaConfig`` → ``models/llama.py``, ``Cohere2MoeConfig`` →
    ``models/cohere2_moe.py``, ``DeepseekV32Config`` →
    ``models/deepseek_v32.py``, ``Phi4FlashConfig`` →
    ``models/phi4flash.py``, ``Lfm2MoeConfig`` → ``models/lfm2_moe.py``,
    ``NemotronHConfig`` → ``models/nemotron_h.py``).
    Every program here reaches its model
    through this one lookup, at trace time. What a served module holds:
    ``KVCache`` (a bucketed prefill's one-row scratch),
    ``prefill``, ``decode_step_paged`` and ``decode_chunk_paged`` with
    ``llama``'s arguments, ``step_stats_len(cfg)`` — how many int32
    counters its paged step returns after the pools (0: none) —
    ``page_shapes(cfg, page_size)``, what a page of each of the two pools
    holds (``serving/kv_cache.py``; None for the second: one pool, and
    ``v_pool`` is None) — or, for a model that stores several
    kinds of thing, ``cache_spec(cfg, page_size)``: its pools by layer kind
    and a per-slot state, in which case the programs' ``k_pool``,
    ``v_pool`` and ``block_tables`` are the pager's dicts, its prefill
    returns them through ``prefill_slabs`` and, if it sets
    ``CHUNK_TAKES_FINISH``, its chunk program is told which rows finish
    (``lfm2_moe``'s is not told: it returns logits [B, 1, V] at every
    row's last chunk position, which this module's fold reads as it reads
    any) — ``step_stats(cfg)``, the names of counters its step returns
    (after the experts' rows and reads for a config with ``held_experts``:
    the engine sets them on the commit span), and ``unserved(engine_config,
    lora, cfg)``, the sentence that refuses
    an engine the model has no program for."""
    return sys.modules[type(cfg).__module__]


@partial(jax.jit, static_argnums=0)
def prefill_compute(
    cfg: Any,  # a served model's config (model_of)
    params: dict,
    tokens: jnp.ndarray,  # [1, S_bucket] right-padded
    seq_len: jnp.ndarray,  # [1]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run prefill WITHOUT a persistent cache: returns (last_logits [1,V],
    k_slab, v_slab [L, S_bucket, Hkv, Dh]) for writing into a slot's pages (a
    model with a ``cache_spec``: what its ``prefill_slabs`` makes of the
    cache — slabs by pool name and the row's state)."""
    model = model_of(cfg)
    scratch = model.KVCache.create(cfg, 1, max_len=tokens.shape[1])
    last, cache = model.prefill(cfg, params, tokens, scratch, seq_len)
    slabs = getattr(model, "prefill_slabs", None)
    if slabs is not None:
        return (last, *slabs(cache))
    return last, cache.k[:, 0], cache.v[:, 0]


# ------------------------------------------------------- CPU-free hot loop
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DecodeState:
    """The per-row decode carry: everything the device needs to run N
    steps without the host. Donated through every block dispatch and
    every :func:`admit_decode_state` scatter — the host NEVER
    reads these buffers (results come back only through the packed block
    output), so the donation can never alias a host-held reference: the
    aliasing that produced the round-4 on-TPU crash ("Array has been
    deleted with shape=int32[32]") is impossible by construction here.

    ``budget`` is the number of tokens the row may still emit — the engine
    folds ``max_new_tokens`` AND the sequence-length cap into it at
    admission, so the device's stop evaluation covers both. ``stop_tok``
    is the row's EOS id (-1 disables). ``done`` rows are frozen: they stop
    spending budget and emit -1, and their garbage KV writes land where
    they cannot matter — the trash page. Never a live position: rows can
    be frozen while still holding LIVE prompt KV (mid-chunked-prefill
    cursor rows).

    ``adapter`` is the row's LoRA device-table slot (serving/lora.py; 0 =
    base model). It rides the donated carry like ``stop_tok`` so the
    heterogeneous-adapter gather inside the block needs no per-step host
    traffic — the adapter index is admitted once and stays on device."""

    last_token: jnp.ndarray  # [B] int32
    seq_len: jnp.ndarray  # [B] int32 — tokens RESIDENT in KV (incl. prompt)
    done: jnp.ndarray  # [B] bool
    budget: jnp.ndarray  # [B] int32 — tokens the row may still emit
    stop_tok: jnp.ndarray  # [B] int32
    temperature: jnp.ndarray  # [B] f32
    top_k: jnp.ndarray  # [B] int32
    top_p: jnp.ndarray  # [B] f32
    rng: jax.Array
    adapter: jnp.ndarray = None  # [B] int32 — LoRA table slot (0 = base)

    def tree_flatten(self):
        return (
            self.last_token, self.seq_len, self.done, self.budget,
            self.stop_tok, self.temperature, self.top_k, self.top_p, self.rng,
            self.adapter,
        ), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


def make_decode_state(
    last_token: Any, seq_len: Any, done: Any, budget: Any, stop_tok: Any,
    temperature: Any, top_k: Any, top_p: Any, rng: jax.Array,
    adapter: Any = None,
) -> DecodeState:
    """Upload a fresh device-resident DecodeState from host (numpy)
    mirrors — the cold path (engine start, post-failure rebuild). Steady
    state never re-uploads: admissions fold in via the donated scatter
    below, and everything else advances on device. ``adapter`` defaults
    to all-base (slot 0) for callers predating the LoRA plane."""
    import numpy as _np

    if adapter is None:
        adapter = _np.zeros(_np.asarray(last_token).shape[0], _np.int32)
    return DecodeState(
        jnp.asarray(last_token, jnp.int32),
        jnp.asarray(seq_len, jnp.int32),
        jnp.asarray(done, bool),
        jnp.asarray(budget, jnp.int32),
        jnp.asarray(stop_tok, jnp.int32),
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        jnp.asarray(top_p, jnp.float32),
        rng,
        jnp.asarray(adapter, jnp.int32),
    )


@partial(jax.jit, donate_argnums=(0,))
def admit_decode_state(
    state: DecodeState,  # donated: nothing aliases it (see DecodeState)
    slots: jnp.ndarray,  # [K] int32
    tokens: jnp.ndarray,  # [K] int32 — each slot's prefill-sampled token
    lens: jnp.ndarray,  # [K] int32 — resident prompt length
    budgets: jnp.ndarray,  # [K] int32
    stops: jnp.ndarray,  # [K] int32
    temps: jnp.ndarray,  # [K] f32
    topks: jnp.ndarray,  # [K] int32
    topps: jnp.ndarray,  # [K] f32
    adapters: jnp.ndarray,  # [K] int32 — LoRA table slots (0 = base)
) -> DecodeState:
    """Fold freshly-prefilled slots into the device-resident decode state
    in one fused scatter (un-done + new budget + sampling params + the
    per-row adapter index the block kernels gather with)."""
    return DecodeState(
        state.last_token.at[slots].set(tokens),
        state.seq_len.at[slots].set(lens),
        state.done.at[slots].set(False),
        state.budget.at[slots].set(budgets),
        state.stop_tok.at[slots].set(stops),
        state.temperature.at[slots].set(temps),
        state.top_k.at[slots].set(topks),
        state.top_p.at[slots].set(topps),
        state.rng,
        state.adapter.at[slots].set(adapters),
    )


def _pack_block(toks: jnp.ndarray, done: jnp.ndarray,
                active: jnp.ndarray) -> jnp.ndarray:
    """Pack a block's results into ONE int32 [B, steps+2] array — columns
    [0, steps) are the sampled tokens (-1 past each row's stop), column
    ``steps`` the done flag, column ``steps+1`` the per-row valid count —
    so the host pays exactly one device sync per block."""
    n_valid = jnp.sum(toks >= 0, axis=1, dtype=jnp.int32)
    return jnp.concatenate(
        [
            toks.astype(jnp.int32),
            (done & active)[:, None].astype(jnp.int32),
            n_valid[:, None],
        ],
        axis=1,
    )


def _append_stats(packed: jnp.ndarray, stats: jnp.ndarray) -> jnp.ndarray:
    """A model's block counters (``step_stats_len`` of them, summed over
    the block's steps) ride the same host read: laid out, zero-padded, in
    whole rows below the B rows of ``packed``. A model without counters
    leaves ``packed`` as it is. :func:`block_stats` is the host's inverse."""
    n, width = stats.shape[0], packed.shape[1]
    if n == 0:
        return packed
    rows = -(-n // width)
    tail = jnp.zeros(rows * width, jnp.int32).at[:n].set(stats)
    return jnp.concatenate([packed, tail.reshape(rows, width)], axis=0)


def block_stats(packed: Any, n_rows: int, n: int) -> Any:
    """The counters :func:`_append_stats` laid below the ``n_rows`` rows of
    a block's packed result (host side: ``packed`` is the numpy copy)."""
    return packed[n_rows:].reshape(-1)[:n]


def _lora_delta(
    embedding: jnp.ndarray,  # [V, D] — the model's token embedding table
    a_tab: jnp.ndarray,      # [n_adapters, D, r]
    b_tab: jnp.ndarray,      # [n_adapters, r, V]
    tokens: jnp.ndarray,     # [B] — the input tokens whose forward made logits
    adapter: jnp.ndarray,    # [B] int32 — per-row adapter table slot
) -> jnp.ndarray:
    """Grouped low-rank logits delta for a heterogeneous-adapter batch:
    a per-row ADAPTER-INDEX GATHER out of the stacked factor tables, then
    two batched low-rank matmuls — ``emb[t] @ A_i @ B_i`` per row. Slot 0
    is all-zero (base model), so mixed base/adapter batches need no mask.
    Pure device math inside the fused block: no host traffic, no syncs."""
    e = embedding[tokens].astype(jnp.float32)               # [B, D]
    h = jnp.einsum("bd,bdr->br", e, a_tab[adapter])         # [B, r]
    return jnp.einsum("br,brv->bv", h, b_tab[adapter])      # [B, V]


def _lora_logits(params: dict, lora, tokens, adapter, logits):
    """Apply the per-row adapter delta to a sampling site's logits.
    ``lora`` is ``(a_table, b_table)`` or None (base-only engines trace
    the exact pre-LoRA graph — the None path adds zero ops)."""
    if lora is None or adapter is None:
        return logits
    a_tab, b_tab = lora
    return logits + _lora_delta(
        params["embedding"], a_tab, b_tab, tokens, adapter
    )


@jax.jit
def lora_adjust_logits(
    embedding: jnp.ndarray,  # [V, D]
    a_row: jnp.ndarray,      # [D, r] — ONE adapter's factors
    b_row: jnp.ndarray,      # [r, V]
    token: jnp.ndarray,      # scalar int32 — the logits' input token
    logits: jnp.ndarray,     # [1, V]
) -> jnp.ndarray:
    """Single-row adapter delta for the HOST-path first-token sampling
    sites (monolithic prefill, full chunk-prefix-cache hits): the same
    math as :func:`_lora_delta`, applied to one row's last-position
    logits before ``sample_logits``. Pure device op — no sync."""
    e = embedding[token].astype(jnp.float32)
    h = e @ a_row.astype(jnp.float32)
    return logits + (h @ b_row.astype(jnp.float32))[None]


@jax.jit
def sample_first_token(
    logits: jnp.ndarray,       # [1, V] last-position logits (LoRA-adjusted)
    key: jax.Array,            # fold_in(rng_root, request id)
    temperature: jnp.ndarray,  # scalar f32
    top_k: jnp.ndarray,        # scalar i32
    top_p: jnp.ndarray,        # scalar f32
) -> jnp.ndarray:
    """An admission's first token on the HOST-path sampling sites (bucketed
    prefill, whole-prompt chunk-prefix-cache hits): ``sample_logits`` as ONE
    program where the eager call queued some forty, which for a greedy
    request runs the argmax branch alone. Returns ids [1]; no sync."""
    return sample_logits(
        logits, key, temperature=temperature, top_k=top_k, top_p=top_p
    )


def _block_step(st: DecodeState, active, logits, params=None, lora=None):
    """Shared per-step tail of every decode scan body: apply the
    per-row LoRA delta (heterogeneous-adapter batching, serving/lora.py),
    sample with the row's own params, evaluate stop conditions, advance
    the carry. Frozen (done/inactive) rows keep their token and length
    and emit -1."""
    live = active & ~st.done
    # the logits came from forwarding st.last_token — the delta is the
    # same token's low-rank bypass, gathered by the row's adapter slot
    logits = _lora_logits(params, lora, st.last_token, st.adapter, logits)
    rng, key = jax.random.split(st.rng)
    # only the live rows choose the sampler's path: a slot that never held
    # a request carries temperature 1.0, a retired one its last request's
    nxt = sample_logits(
        logits, key, temperature=st.temperature, top_k=st.top_k,
        top_p=st.top_p, rows=live,
    )
    nxt = jnp.where(live, nxt, st.last_token)
    done = st.done | (live & stop_eval(nxt, st.stop_tok, st.budget))
    new_st = DecodeState(
        nxt,
        jnp.where(live, st.seq_len + 1, st.seq_len),
        done,
        jnp.where(live, st.budget - 1, st.budget),
        st.stop_tok, st.temperature, st.top_k, st.top_p, rng,
        st.adapter,
    )
    return new_st, jnp.where(live, nxt, -1)


def _paged_steps(cfg, params, k_pool, v_pool, state, block_tables, active,
                 steps, lora):
    """The N-step decode scan over the bf16 page pool that
    :func:`decode_block_paged` and :func:`ragged_step_paged` share: each
    step is the model's ``decode_step_paged`` and the shared sampling
    tail. Returns (tokens [B, steps], k_pool, v_pool, state, the model's
    counters summed over the steps — length 0 for a model without)."""
    model = model_of(cfg)

    def step(carry, _):
        kp, vp, st, stats = carry
        live = active & ~st.done
        step_len = jnp.where(live, st.seq_len + 1, 1)
        logits, kp, vp, *counted = model.decode_step_paged(
            cfg, params, st.last_token, kp, vp, block_tables, step_len, live
        )
        if counted:  # the model's counters for this step, after the pools
            stats = stats + counted[0]
        st, out = _block_step(st, active, logits, params, lora)
        return (kp, vp, st, stats), out

    stats = jnp.zeros(model.step_stats_len(cfg), jnp.int32)
    (k_pool, v_pool, state, stats), toks = jax.lax.scan(
        step, (k_pool, v_pool, state, stats), None, length=steps
    )
    return jnp.transpose(toks), k_pool, v_pool, state, stats


@partial(jax.jit, static_argnums=(0, 7), donate_argnums=(2, 3, 4))
def decode_block_paged(
    cfg: Any,  # a served model's config (model_of)
    params: dict,
    k_pool: jnp.ndarray,  # [L, N_pages+1, Hkv, page, Dh] donated (+1: trash)
    v_pool: jnp.ndarray,  # donated
    state: DecodeState,  # donated
    block_tables: jnp.ndarray,  # [B, M] — covers the whole block's writes
    active: jnp.ndarray,  # [B] bool
    steps: int,
    lora: tuple | None = None,  # (a_table, b_table) — heterogeneous LoRA
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, DecodeState]:
    """``steps`` fused decode+sample+stop-eval iterations in ONE dispatch
    over the page pools. A row that stops mid-block freezes: no further
    budget spend, its remaining columns are -1, and its appends divert to
    the trash page (llama.decode_step_paged's ``active`` redirect), so a
    mid-block stop never writes a live page. ``lora`` (never donated)
    carries the stacked adapter factor tables; each step gathers per-row
    slots out of the carry's ``adapter`` index — one dispatch serves rows
    with DIFFERENT adapters (serving/lora.py). Returns (packed — see
    :func:`_pack_block`, with the model's counters below it — k_pool,
    v_pool, state); the packed array is the block's ONLY host-read
    value."""

    toks, k_pool, v_pool, state, stats = _paged_steps(
        cfg, params, k_pool, v_pool, state, block_tables, active, steps, lora
    )
    packed = _append_stats(_pack_block(toks, state.done, active), stats)
    return packed, k_pool, v_pool, state


# ------------------------------------------------- unified ragged dispatch
#
# Continuous batching (Ragged Paged Attention, arXiv:2604.15464): one
# dispatch runs a ragged mix of PREFILL CHUNKS (the next <=C prompt tokens
# of each partially-prefilled row, written into the same page pool decode
# reads) and an N-step DECODE BLOCK, returning ONE packed
# array so the host still pays exactly one sync per block. A row whose
# chunk completes its prompt gets its first token sampled ON DEVICE (with
# the same fold_in(root, request_id) key the host path uses) and is folded
# into the donated DecodeState in the same dispatch — admission to decode
# costs no extra host round trip.


def _fold_finished_prefill(
    st: DecodeState,
    logits_c: jnp.ndarray,   # [B, C, V] chunk-forward logits
    chunk: jnp.ndarray,      # [B, C] the chunk's input tokens
    chunk_start: jnp.ndarray,  # [B] resident length before the chunk
    finish: jnp.ndarray,     # [B] bool — this chunk completes the prompt
    new_len: jnp.ndarray,    # [B] resident length after the chunk
    budgets: jnp.ndarray,    # [B] tokens the row may emit AFTER the first
    stops: jnp.ndarray,      # [B] per-row stop id (-1 disables)
    temps: jnp.ndarray,
    topks: jnp.ndarray,
    topps: jnp.ndarray,
    rids: jnp.ndarray,       # [B] request ids (first-token RNG keys)
    rng_root: jax.Array,
    adapters: jnp.ndarray | None = None,  # [B] LoRA table slots
    params: dict | None = None,
    lora: tuple | None = None,
) -> tuple[DecodeState, jnp.ndarray, jnp.ndarray]:
    """Sample first tokens for rows whose prompt just finished prefilling
    and fold them into the decode carry (including each row's LoRA
    adapter slot, so the decode steps gather the right delta). Returns
    (state, first [B] — -1 on non-finishing rows — last_logits [B, V] at
    each row's final chunk position, for the chunk-prefix cache —
    BASE-model logits: the adapter delta applies at sampling sites, so
    cached entries stay adapter-independent while the adapter-id-scoped
    keys keep cross-adapter hits impossible anyway)."""
    C = logits_c.shape[1]
    pos = jnp.clip(new_len - chunk_start - 1, 0, C - 1)
    last_logits = jnp.take_along_axis(
        logits_c, pos[:, None, None], axis=1
    )[:, 0]  # [B, V]
    # the logits sampled from were produced by the chunk's last prompt
    # token — the same token keys the low-rank bypass delta
    last_tok = jnp.take_along_axis(chunk, pos[:, None], axis=1)[:, 0]
    if adapters is None:
        adapters = jnp.zeros_like(rids)
    sample_from = _lora_logits(params, lora, last_tok, adapters, last_logits)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(rng_root, rids)

    def sample_one(lg, key, t, tk, tp):
        return sample_logits(
            lg[None], key, temperature=t, top_k=tk, top_p=tp
        )[0]

    # under vmap the sampler's branches would all run (a cond of a batched
    # predicate is a select), so the choice is made here, over the rows
    # that finish: the argmax alone unless one of them samples
    sampled = jax.lax.cond(
        sampler_path(temps, topks, topps, finish) > 0,
        lambda: jax.vmap(sample_one)(sample_from, keys, temps, topks, topps),
        lambda: jnp.argmax(sample_from.astype(jnp.float32), axis=-1),
    )
    done_f = (sampled == stops) | (budgets <= 0)
    st = DecodeState(
        jnp.where(finish, sampled, st.last_token),
        jnp.where(finish, new_len, st.seq_len),
        jnp.where(finish, done_f, st.done),
        jnp.where(finish, budgets, st.budget),
        jnp.where(finish, stops, st.stop_tok),
        jnp.where(finish, temps, st.temperature),
        jnp.where(finish, topks, st.top_k),
        jnp.where(finish, topps, st.top_p),
        st.rng,
        jnp.where(finish, adapters, st.adapter),
    )
    return st, jnp.where(finish, sampled, -1), last_logits


def _pack_ragged(toks: jnp.ndarray, done: jnp.ndarray, active: jnp.ndarray,
                 first: jnp.ndarray) -> jnp.ndarray:
    """:func:`_pack_block` plus one trailing column: the on-device-sampled
    first token of rows whose prefill finished this dispatch (-1
    elsewhere). Layout [B, steps+3]: tokens | done | n_valid | first."""
    return jnp.concatenate(
        [_pack_block(toks, done, active), first[:, None].astype(jnp.int32)],
        axis=1,
    )


@partial(jax.jit, static_argnums=(0, 20), donate_argnums=(2, 3, 4))
def ragged_step_paged(
    cfg: Any,  # a served model's config (model_of)
    params: dict,
    k_pool: jnp.ndarray,       # donated
    v_pool: jnp.ndarray,       # donated
    state: DecodeState,        # donated
    block_tables: jnp.ndarray,  # [B, M] — covers chunk AND block writes
    chunk: jnp.ndarray,        # [B, C]
    chunk_start: jnp.ndarray,  # [B]
    chunk_active: jnp.ndarray,  # [B] bool — rows prefill-chunking now
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
    finish: jnp.ndarray,
    new_len: jnp.ndarray,
    budgets: jnp.ndarray,
    stops: jnp.ndarray,
    temps: jnp.ndarray,
    topks: jnp.ndarray,
    topps: jnp.ndarray,
    rids: jnp.ndarray,
    rng_root: jax.Array,
    decode_active: jnp.ndarray,
    steps: int,
    adapters: jnp.ndarray | None = None,  # [B] LoRA slots for chunk rows
    lora: tuple | None = None,  # (a_table, b_table) — never donated
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, DecodeState]:
    """Unified ragged dispatch: prefill-chunk forward for the chunk rows,
    first-token fold for finishing rows, then the N-step decode scan —
    one dispatch, one packed host read. Chunk writes route through the
    block tables (inactive rows and beyond-capacity positions divert to
    the trash page), decode appends likewise. Returns (packed
    [B, steps+3] — see :func:`_pack_ragged` — last_logits [B, V], k_pool,
    v_pool, state); ``last_logits`` stays on device unless the engine
    retains it for the chunk-prefix cache."""
    model = model_of(cfg)
    # a model whose upper layers run on a prompt's last position alone is
    # told which rows finish, and returns logits [B, 1, V] at that position
    told = {"finish": finish} if getattr(model, "CHUNK_TAKES_FINISH", False) else {}
    logits_c, k_pool, v_pool = model.decode_chunk_paged.__wrapped__(
        cfg, params, chunk, k_pool, v_pool, block_tables, chunk_start,
        chunk_active, kv_capacity, **told,
    )
    state, first, last_logits = _fold_finished_prefill(
        state, logits_c, chunk, chunk_start, finish, new_len, budgets,
        stops, temps, topks, topps, rids, rng_root, adapters, params, lora,
    )
    toks, k_pool, v_pool, state, stats = _paged_steps(
        cfg, params, k_pool, v_pool, state, block_tables, decode_active,
        steps, lora,
    )
    packed = _append_stats(
        _pack_ragged(toks, state.done, decode_active, first), stats
    )
    return packed, last_logits, k_pool, v_pool, state


# ----------------------------------------------------- speculative decoding
def _accept_and_bonus(
    chunk: jnp.ndarray,  # [B, T] (pos 0 = last committed; 1.. = drafts, -1 pad)
    logits: jnp.ndarray,  # [B, T, V] from a chunk verify forward
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    rng: jax.Array,
) -> tuple[jnp.ndarray, jnp.ndarray, jax.Array]:
    """Greedy draft acceptance + per-row bonus sampling, fused device-side.
    :func:`verify_and_sample_paged` packs (tokens, n_accept) into ONE
    [B, T+1] int32 array so the engine's spec path pays a single host
    sync per chunk (tokens in columns [0, T), n_accept in column T).

    Position i's logits predict the token after chunk token i, so draft
    chunk[:, i+1] is accepted iff argmax(logits[:, i]) equals it AND every
    earlier draft was accepted (cumulative product). -1 padding never
    matches, so per-row draft counts need no separate length input. The
    bonus token samples from logits at the first rejected position with
    the row's own sampling params — rows the engine didn't draft for
    (temperature > 0) therefore take exactly a normal sampled step.
    Returns (tokens [B, T] — accepted drafts then bonus, -1 beyond —
    n_accept [B], rng)."""
    B, T = chunk.shape
    greedy = jnp.argmax(logits, axis=-1)  # [B, T]
    drafts = chunk[:, 1:]  # [B, T-1]
    match = (greedy[:, :-1] == drafts) & (drafts >= 0)
    n_accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    bonus_logits = jnp.take_along_axis(
        logits, n_accept[:, None, None], axis=1
    )[:, 0]  # [B, V]
    rng, key = jax.random.split(rng)
    bonus = sample_logits(
        bonus_logits, key, temperature=temperature, top_k=top_k, top_p=top_p
    )
    idx = jnp.arange(T)[None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), drafts.dtype)], axis=1
    )
    out = jnp.where(
        idx < n_accept[:, None], drafts_pad,
        jnp.where(idx == n_accept[:, None], bonus[:, None], -1),
    )
    return out, n_accept, rng


@partial(jax.jit, static_argnums=0, donate_argnums=(2, 3))
def verify_and_sample_paged(
    cfg: Any,  # a served model's config (model_of)
    params: dict,
    k_pool: jnp.ndarray,  # donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,
    chunk: jnp.ndarray,
    start_len: jnp.ndarray,
    active: jnp.ndarray,
    kv_capacity: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    rng: jax.Array,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jax.Array]:
    """Speculative engine step: chunk-verify forward through the block
    tables + draft acceptance + bonus sampling in ONE dispatch. Returns
    (packed [B, T+1] — tokens | n_accept — k_pool, v_pool, rng)."""
    logits, k_pool, v_pool = model_of(cfg).decode_chunk_paged.__wrapped__(
        cfg, params, chunk, k_pool, v_pool, block_tables, start_len,
        active, kv_capacity,
    )
    out, n_accept, rng = _accept_and_bonus(
        chunk, logits, temperature, top_k, top_p, rng
    )
    packed = jnp.concatenate(
        [out.astype(jnp.int32), n_accept[:, None].astype(jnp.int32)], axis=1
    )
    return packed, k_pool, v_pool, rng


def pad_bucket(length: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket ≥ length (prompt padding, limits recompiles)."""
    for b in buckets:
        if length <= b:
            return b
    return buckets[-1]
