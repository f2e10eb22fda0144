"""Paged KV cache: pooled pages + native block-table accounting.

The engine's one KV layout, a shared page pool ([L, N_pages+1, Hkv, page, Dh] — the +1 is a trash page
for inactive rows' redirected writes): sequences own pages through the
native BlockAllocator (native/runtime/gofr_runtime.cc — the refcounted
allocator with copy-on-write forks), so HBM is committed by tokens
actually resident, not by worst-case slots. SURVEY §5.7 lever (a).

Host side (this class): page accounting, block tables, seq lens.
Device side: scatter prefilled slabs into owned pages (_write_pages); the
decode-step append is ops/paged_attention.paged_kv_append — a Pallas call
aliased over the whole pools, called per layer by the model's
decode_step_paged, which rewrites the page a row writes and so relies on
NO TWO LIVE ROWS OWNING THE SAME PAGE IN A STEP (the allocator gives a
page to one sequence; a prefix-cache hit copies slabs into owned pages,
below; the allocator's copy-on-write fork is used by no serving path, and
one that forks must copy a shared last page before a step writes it; only
the trash page is shared) — and the read path is
paged_decode_attention in the same module, given the whole pools and a
layer index. Inside a decode program XLA neither slices nor writes a pool.

A model that stores more than one kind of thing says so (its module's
``cache_spec``: pools by layer kind, and a per-slot state that is no page)
and the pager builds from the answer: ``k_pool`` and ``v_pool`` are then
dicts by pool name, ``k_pool["state"]`` holds the state arrays
[layers, slots, ...], and ``tables_device()`` gives a table a pool. A pool
whose layers read every position shares the native allocator and the block
table above. A pool whose layers read a row's last ``window`` positions is a
RING: a slot owns ``cdiv(window, page) + 1`` pages for its whole life, block
``j`` of its row lives in page ``j mod ring``, and the table addresses a
ring page only while a query can read it or a dispatched step may write it
— every other entry is the trash page — so a page behind the window is
never read again, and is written over once the row has come round.

shardcheck retrace/donation zone: the pool buffers are donated through
every _write_pages/decode dispatch and MUST be rebound in the same
statement (``use-after-donation``, docs/static-analysis.md) — a stale
``self.k_pool`` read after a donating call is the round-4 on-TPU crash.
``_write_pages`` is declared in the kernel contract table
(``gofr_tpu/analysis/kernel_contracts.KERNELS``) — its pool/slab
signature and donation set are enforced by kernelcheck and replayed by
the kerneltrace eval_shape matrix.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu import chaos
from gofr_tpu.native.runtime import BlockAllocator, OutOfBlocks

__all__ = ["PagedKVCache", "OutOfBlocks"]


@partial(jax.jit, donate_argnums=(0, 1))
def _write_pages(
    k_pool: jnp.ndarray,  # [L, N, Hkv, page, Dh] donated
    v_pool: jnp.ndarray | None,  # its pages may have a shape of their own; None: one pool
    k_slab: jnp.ndarray,  # [L, S_pad, Hkv, Dh] (S_pad = n_pages*page)
    v_slab: jnp.ndarray | None,
    page_ids: jnp.ndarray,  # [n_pages] int32
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    n_pages = page_ids.shape[0]
    return jax.tree.map(lambda pool, slab: pool.at[:, page_ids].set(_paged(slab, n_pages)),
                        (k_pool, v_pool), (k_slab, v_slab))


def _paged(slab: jnp.ndarray, n_pages: int) -> jnp.ndarray:
    """A slab [L, n_pages*page, Hkv, Dh] as pages [L, n_pages, Hkv, page, Dh]."""
    L, S_pad, Hkv, Dh = slab.shape
    return slab.reshape(L, n_pages, S_pad // n_pages, Hkv, Dh).transpose(0, 1, 3, 2, 4)


@partial(jax.jit, donate_argnums=(0, 1))
def _write_slot(
    k_pool: dict,  # by pool name, and "state": donated
    v_pool: dict,  # donated
    k_slab: dict,  # what a prefill returns for one row, padded to whole pages
    v_slab: dict,
    page_ids: dict,  # pool name -> [n_pages] int32 (the trash page where a page is not kept)
    slot: jnp.ndarray,  # scalar int32
) -> tuple[dict, dict]:
    """A prefilled row into a cache of several pools: each pool's pages,
    and the slot's state (whatever its last occupant left is overwritten)."""
    k_out, v_out = dict(k_pool), dict(v_pool)
    for name, ids in page_ids.items():
        k_out[name] = k_pool[name].at[:, ids].set(_paged(k_slab[name], ids.shape[0]))
        v_out[name] = v_pool[name].at[:, ids].set(_paged(v_slab[name], ids.shape[0]))
    k_out["state"] = {
        key: arr.at[:, slot].set(k_slab["state"][key].astype(arr.dtype))
        for key, arr in k_pool["state"].items()
    }
    return k_out, v_out


def _pad_tokens(slab: jnp.ndarray | None, pad: int) -> jnp.ndarray | None:
    return jnp.pad(slab, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad and slab is not None else slab


def _contiguous(pages: jnp.ndarray) -> jnp.ndarray:
    """Gathered pages [L, n, Hkv, page, Dh] as a slab [L, n*page, Hkv, Dh]."""
    L, n, Hkv, page, Dh = pages.shape
    return pages.transpose(0, 1, 3, 2, 4).reshape(L, n * page, Hkv, Dh)


class PagedKVCache:
    """Owns the device page pool + host page accounting for up to
    ``max_slots`` concurrent sequences."""

    def __init__(
        self,
        cfg: Any,  # a served model's config: n_layers, dtype
        *,
        num_pages: int,
        page_size: int = 16,
        max_slots: int = 8,
        max_seq_len: int = 1024,
        dtype: Any = None,
        # what a page of each pool holds, [Hkv, page, Dh] twice: the model's
        # ``page_shapes`` (the engine hands it over); None is K and V of
        # every KV head. A second shape of None: no second pool (v_pool is
        # None, and so is every V slab)
        page_shapes: tuple[tuple, tuple] | None = None,
        # a model that stores several kinds of thing: its ``cache_spec``,
        # (pools by layer kind, per-slot state). None is one pool pair of
        # ``cfg.n_layers`` layers under one table, as above
        spec: tuple[tuple, dict] | None = None,
    ) -> None:
        self.cfg = cfg
        self.page_size = page_size
        self._page_shapes = page_shapes or ((cfg.n_kv_heads, page_size, cfg.head_dim),) * 2
        self._spec = spec
        self.num_pages = num_pages
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.max_pages_per_seq = (max_seq_len + page_size - 1) // page_size
        dtype = dtype or cfg.dtype
        self._pool_dtype = dtype
        # a window pool's ring: pages a slot owns, and where block j of a
        # slot's row lives ([slots, M], constant)
        self._rings: dict[str, tuple[int, int, np.ndarray]] = {}
        for name, _, _, _, window in (spec[0] if spec else ()):
            if window is not None:
                ring = -(-window // page_size) + 1
                ids = (np.arange(max_slots)[:, None] * ring
                       + np.arange(self.max_pages_per_seq)[None, :] % ring).astype(np.int32)
                self._rings[name] = (window, ring, ids)
        self.reset_pools()
        self.allocator = BlockAllocator(num_pages, page_size)
        # host mirrors (authoritative): per-slot block table + length
        self.tables = np.zeros((max_slots, self.max_pages_per_seq), np.int32)
        self.seq_lens = np.zeros(max_slots, np.int32)
        # tokens the slot's owned pages cover (its table's length in tokens)
        self._cover = np.zeros(max_slots, np.int32)
        # blocks behind the window that window_turnover has counted as freed
        self._win_seen = np.zeros(max_slots, np.int32)
        self._slot_seq: list[int | None] = [None] * max_slots

    def reset_pools(self) -> None:
        """(Re)allocate the device page pools. Called at init and by engine
        recovery when a dispatch that failed after donation committed left
        the pools deleted (serving/engine.py:_rebuild_kv) — resident pages
        are unrecoverable either way; fresh zeros restore a servable pool.

        [L, N+1, Hkv, page, Dh]: trailing (page, Dh) are full dims in the
        pallas BlockSpecs (ops/paged_attention.py) — Mosaic tiling rule.
        What a page holds, [Hkv, page, Dh] for each of the two pools, is
        the model's to say (``page_shapes``, handed over at construction:
        K and V of every KV head for ``llama`` and ``cohere2_moe``; one
        latent row and one indexer key a token for ``deepseek_v32``, the
        latent row alone where it has no indexer, and no second pool) —
        one block table and one allocator serve both. The extra LAST page is the
        trash page: inactive rows' decode appends are redirected there
        (the model's decode_step_paged), so the append never writes a
        live page for a row that does not own it. With a ``spec`` each of
        its pools is such a pair (a ring pool of ``slots x ring`` pages)
        and the state arrays, zeros, ride ``k_pool["state"]``."""
        # build both arrays BEFORE assigning either: a mid-rebuild failure
        # (backend still down during recovery) must not leave a half-fresh
        # pool pair that the engine's health probe — it samples k_pool —
        # would report healthy while v_pool is still deleted
        if self._spec is None:
            k_page, v_page = self._page_shapes
            lead = (self.cfg.n_layers, self.num_pages + 1)
            self.k_pool, self.v_pool = (
                jnp.zeros(lead + k_page, self._pool_dtype),
                None if v_page is None else jnp.zeros(lead + v_page, self._pool_dtype),
            )
            return
        pools, state = self._spec
        k_pool: dict = {}
        v_pool: dict = {}
        for name, n_layers, k_page, v_page, _ in pools:
            lead = (n_layers, self.pool_pages(name) + 1)
            k_pool[name] = jnp.zeros(lead + tuple(k_page), self._pool_dtype)
            v_pool[name] = jnp.zeros(lead + tuple(v_page), self._pool_dtype)
        k_pool["state"] = {
            key: jnp.zeros((n_layers, self.max_slots) + tuple(shape), dt)
            for key, (n_layers, shape, dt) in state.items()
        }
        self.k_pool, self.v_pool = k_pool, v_pool

    def pool_pages(self, name: str) -> int:
        """Pages of a spec'd pool, the trash page not counted."""
        return self.max_slots * self._rings[name][1] if name in self._rings else self.num_pages

    def delete_pools(self) -> None:
        """Delete every device buffer of the cache (the engine's
        ``device.loss`` chaos point poisons it for real)."""
        for leaf in jax.tree.leaves((self.k_pool, self.v_pool)):
            leaf.delete()

    def probe(self) -> jnp.ndarray:
        """One array of the cache, for the engine's health probe."""
        return jax.tree.leaves(self.k_pool)[0]

    # ------------------------------------------------------------- accounting
    def alloc_slot(
        self, slot: int, seq_id: int, prompt_len: int,
        reserve_tokens: int | None = None,
    ) -> None:
        """Reserve pages for a prompt (``reserve_tokens`` ≥ prompt_len when
        prefill buckets pad past the prompt). Raises OutOfBlocks (caller
        keeps the request queued) without touching slot state on failure.
        The allocator tracks RESERVED capacity; true length lives in
        ``seq_lens``."""
        if self._slot_seq[slot] is not None:
            raise KeyError(f"slot {slot} busy")
        chaos.maybe_fail("kv.alloc")
        self.allocator.alloc(seq_id, max(prompt_len, reserve_tokens or 0))
        self._slot_seq[slot] = seq_id
        self.tables[slot] = 0
        self._sync_table(slot, seq_id)
        self.seq_lens[slot] = prompt_len
        self._win_seen[slot] = 0

    def _sync_table(self, slot: int, seq_id: int) -> list[int]:
        """The allocator's pages of a sequence into the slot's row of the
        host table (after an alloc or an extend); returns them."""
        table = self.allocator.block_table(seq_id)
        self.tables[slot, : len(table)] = table
        self._cover[slot] = len(table) * self.page_size
        return table

    def extend_slot(self, slot: int) -> None:
        """Account one appended token (decode). Raises OutOfBlocks when the
        pool is exhausted — the engine must retire or spill a sequence."""
        seq_id = self._slot_seq[slot]
        assert seq_id is not None
        new_len = int(self.seq_lens[slot]) + 1
        if new_len > self.allocator.seq_length(seq_id):
            chaos.maybe_fail("kv.alloc")
            self.allocator.extend(seq_id, new_len)
            self._sync_table(slot, seq_id)
        self.seq_lens[slot] = new_len

    def try_reserve_chunk(self, slots: list[int], tokens: int) -> bool:
        """Reserve page COVERAGE for up to ``tokens`` further positions on
        every slot, or none — WITHOUT advancing seq_lens (speculative
        verify writes up to ``tokens`` positions but commits only the
        accepted prefix; lengths advance later via :meth:`advance_slot` —
        the block-stepped decode path uses the per-row twin
        :meth:`try_reserve_slot` the same way). Per-slot targets clamp to
        max_seq_len: a row one token short of the limit reserves exactly
        its last page rather than overflowing the block-table width —
        chunk positions past the clamp divert to the trash page via the
        kv_capacity write guard. Returns False untouched when the pool
        can't cover all slots."""
        targets = []
        needed = 0
        for slot in slots:
            seq_id = self._slot_seq[slot]
            assert seq_id is not None
            target = min(int(self.seq_lens[slot]) + tokens, self.max_seq_len)
            targets.append((slot, seq_id, target))
            # compare against blocks actually OWNED: the reservation may
            # sit mid-page, in which case the remaining page capacity
            # absorbs the chunk with zero new blocks (code-review r4)
            owned = len(self.allocator.block_table(seq_id))
            needed += max(0, self.pages_needed(target) - owned)
        if needed > self.allocator.stats()["free_blocks"]:
            return False
        for slot, seq_id, target in targets:
            if target > self.allocator.seq_length(seq_id):
                self.allocator.extend(seq_id, target)
                self._sync_table(slot, seq_id)
        return True

    def try_reserve_slot(self, slot: int, tokens: int) -> bool:
        """Reserve page COVERAGE for up to ``tokens`` positions past the
        slot's committed length, or nothing — the per-row twin of
        :meth:`try_reserve_chunk`, used by the block-stepped decode loop
        where each row's dispatched-ahead depth differs (the device runs
        ahead of the committed host mirror by the in-flight blocks).
        Clamps to max_seq_len like the chunk variant; lengths advance
        later via :meth:`advance_slot` as blocks are consumed. Returns
        False untouched when the pool cannot cover the target."""
        seq_id = self._slot_seq[slot]
        assert seq_id is not None
        target = min(int(self.seq_lens[slot]) + tokens, self.max_seq_len)
        # the pages the slot owns, as the last _sync_table counted them: no
        # copy of the allocator's table, and no ask for its free pages
        # unless a page is wanted (a row wants one every page_size tokens)
        owned = int(self._cover[slot]) // self.page_size
        needed = max(0, self.pages_needed(target) - owned)
        if needed and needed > self.allocator.stats()["free_blocks"]:
            return False
        if target > self.allocator.seq_length(seq_id):
            try:
                chaos.maybe_fail("kv.alloc")
                self.allocator.extend(seq_id, target)
            except OutOfBlocks:
                # free_blocks raced another consumer (or the chaos point
                # fired): same contract as the capacity check above
                return False
            if needed:  # else the reservation grew inside the pages owned: the table is as it was
                self._sync_table(slot, seq_id)
        return True

    def advance_slot(self, slot: int, n_tokens: int) -> None:
        """Commit ``n_tokens`` accepted positions (speculative decode).
        The caller reserved coverage up front (try_reserve_chunk), so this
        never allocates."""
        self.seq_lens[slot] = int(self.seq_lens[slot]) + n_tokens

    def owned_capacity(self, slot: int) -> int:
        """Tokens covered by the slot's OWNED pages — the write guard for
        chunk verifies (positions past this must spill to the trash page,
        never through the zero-filled table tail into live page 0)."""
        return int(self._cover[slot]) if self._slot_seq[slot] is not None else 0

    def free_slot(self, slot: int) -> None:
        seq_id = self._slot_seq[slot]
        if seq_id is None:
            return
        self.allocator.free(seq_id)
        self._slot_seq[slot] = None
        self.tables[slot] = 0
        self.seq_lens[slot] = 0
        self._cover[slot] = 0

    def pages_needed(self, tokens: int) -> int:
        return (tokens + self.page_size - 1) // self.page_size

    def free_pages(self) -> int:
        """Pages the allocator can still hand out: what an admission needs
        (a ring pool's pages are a slot's own and never run out)."""
        return int(self.allocator.stats()["free_blocks"])

    def stats(self) -> dict[str, Any]:
        """The allocator's counts; with a ``spec`` the sum over the pools
        (``total_blocks``, ``free_blocks``) and each pool beside it
        (``pools``: name -> used, total). A ring page counts as used while
        the table addresses it (:meth:`_ring_spans`)."""
        s = self.allocator.stats()
        s["page_size"] = self.page_size
        if self._spec is None:
            return s
        pools = {}
        for name, *_ in self._spec[0]:
            total = self.pool_pages(name)
            if name in self._rings:
                used = int(self._ring_held(name).sum())
            else:
                used = int(s["total_blocks"] - s["free_blocks"])
            pools[name] = {"used": used, "total": total}
        s["total_blocks"] = sum(p["total"] for p in pools.values())
        s["free_blocks"] = s["total_blocks"] - sum(p["used"] for p in pools.values())
        s["pools"] = pools
        return s

    # ------------------------------------------------------------ window rings
    def _ring_spans(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """(lo, hi) [slots]: the blocks of each slot's row that its table
        addresses in ring pool ``name`` — from the block that holds the
        committed length less the window (the device is never behind the
        committed length, so a query never reads below it) to the last
        block the slot's reservation covers (a dispatched step writes no
        further). An empty slot has hi = lo - 1."""
        window = self._rings[name][0]
        lo = np.maximum(self.seq_lens - window, 0) // self.page_size
        hi = (self._cover - 1) // self.page_size
        return lo, np.maximum(hi, lo - 1)

    def _ring_held(self, name: str) -> np.ndarray:
        """Ring pages of pool ``name`` each slot's table addresses [slots]:
        its span's blocks, which alias once they outnumber the ring."""
        lo, hi = self._ring_spans(name)
        return np.minimum(hi - lo + 1, self._rings[name][1])

    def window_turnover(self, name: str, rows: np.ndarray) -> tuple[int, int]:
        """Over the slots of ``rows`` (a bool mask): ring pages of pool
        ``name`` their tables address now, and the blocks that fell behind
        the window since the last call counted them (pages the row will
        write over)."""
        lo, _ = self._ring_spans(name)
        held = self._ring_held(name)[rows].sum()
        freed = (lo - self._win_seen)[rows].sum()
        self._win_seen[rows] = lo[rows]
        return int(held), int(freed)

    @property
    def ring_pools(self) -> tuple[str, ...]:
        return tuple(self._rings)

    # ------------------------------------------------------------- device ops
    def write_prefill(self, slot: int, k_slab: jnp.ndarray, v_slab: jnp.ndarray) -> None:
        """Scatter a prefilled slab [L, S_bucket, Hkv, Dh] into the slot's
        pages (S_bucket rounded up to whole pages; surplus pages of the
        bucket beyond the owned table are masked by seq_lens at read)."""
        seq_id = self._slot_seq[slot]
        assert seq_id is not None
        if self._spec is not None:
            return self._write_prefill_pools(slot, seq_id, k_slab, v_slab)
        S = k_slab.shape[1]
        n_pages = self.pages_needed(S)
        pad = n_pages * self.page_size - S
        k_slab, v_slab = _pad_tokens(k_slab, pad), _pad_tokens(v_slab, pad)
        owned = self._owned_pages(slot, seq_id, n_pages)
        page_ids = jnp.asarray(owned[:n_pages], jnp.int32)
        self.k_pool, self.v_pool = _write_pages(
            self.k_pool, self.v_pool, k_slab, v_slab, page_ids
        )

    def _owned_pages(self, slot: int, seq_id: int, n_pages: int) -> list[int]:
        """The sequence's pages, grown to ``n_pages`` if a bucket's
        padding spilled past the reservation."""
        owned = self.allocator.block_table(seq_id)
        if n_pages > len(owned):
            self.allocator.extend(seq_id, n_pages * self.page_size)
            owned = self._sync_table(slot, seq_id)
        return owned

    def _write_prefill_pools(self, slot: int, seq_id: int, k_slab: dict, v_slab: dict) -> None:
        """``write_prefill`` into a cache of several pools: slabs by pool
        name [L, S_bucket, Hkv, Dh], and the row's state under
        ``k_slab["state"]``. A ring pool keeps the pages that hold the
        prompt's last ``window`` positions (the prompt's true length is
        ``seq_lens[slot]``); the rest of the bucket goes to the trash page."""
        names = [name for name, *_ in self._spec[0]]
        S = k_slab[names[0]].shape[1]
        n_pages = self.pages_needed(S)
        pad = n_pages * self.page_size - S
        owned = self._owned_pages(slot, seq_id, n_pages)
        ids = {}
        for name in names:
            if name in self._rings:
                window, _, ring_ids = self._rings[name]
                n = int(self.seq_lens[slot])
                j = np.arange(n_pages)
                kept = (j >= max(n - window, 0) // self.page_size) & (j <= (n - 1) // self.page_size)
                ids[name] = np.where(kept, ring_ids[slot, :n_pages], self.pool_pages(name)).astype(np.int32)
            else:
                ids[name] = np.asarray(owned[:n_pages], np.int32)
        k_in = {**{n: _pad_tokens(k_slab[n], pad) for n in names}, "state": k_slab["state"]}
        v_in = {n: _pad_tokens(v_slab[n], pad) for n in names}
        self.k_pool, self.v_pool = _write_slot(
            self.k_pool, self.v_pool, k_in, v_in,
            {n: jnp.asarray(v) for n, v in ids.items()}, jnp.int32(slot),
        )

    def write_span(
        self, slot: int, start: int, k_slab: jnp.ndarray, v_slab: jnp.ndarray
    ) -> None:
        """Scatter a cached chunk-prefix slab [L, C, Hkv, Dh] into the
        pages covering token span [start, start+C) — ``write_prefill``'s
        offset twin for chunked admissions that skip cached chunk
        prefixes. ``start`` must be page-aligned; the caller reserved
        coverage through ``alloc_slot``/``try_reserve_slot`` first. The
        slab is padded to whole pages (pad positions sit beyond
        ``seq_lens`` and are masked at read)."""
        self._one_pool("write_span")
        if start % self.page_size:
            raise ValueError(f"write_span start {start} not page-aligned")
        seq_id = self._slot_seq[slot]
        assert seq_id is not None
        C = k_slab.shape[1]
        p0 = start // self.page_size
        p1 = self.pages_needed(start + C)
        pad = (p1 - p0) * self.page_size - C
        k_slab, v_slab = _pad_tokens(k_slab, pad), _pad_tokens(v_slab, pad)
        owned = self._owned_pages(slot, seq_id, p1)
        page_ids = jnp.asarray(owned[p0:p1], jnp.int32)
        self.k_pool, self.v_pool = _write_pages(
            self.k_pool, self.v_pool, k_slab, v_slab, page_ids
        )

    def read_span(
        self, slot: int, start: int, end: int
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Gather the slot's resident K/V for token span [start, end) out
        of the page pool into contiguous slabs [L, end-start, Hkv, Dh] —
        the chunk-prefix cache's extraction path (serving/engine.py).
        ``start`` must be page-aligned (chunk boundaries are); the gather
        is a pure device read (no sync, nothing donated) and the returned
        slabs are fresh buffers safe to retain across later dispatches."""
        self._one_pool("read_span")
        if start % self.page_size:
            raise ValueError(f"read_span start {start} not page-aligned")
        p0 = start // self.page_size
        p1 = self.pages_needed(end)
        page_ids = self.tables[slot, p0:p1]
        off = start - p0 * self.page_size  # 0 by alignment, kept explicit
        # [L, n, Hkv, page, Dh] gathered; a V pool of None gives a V slab of None
        return jax.tree.map(lambda pool: _contiguous(pool[:, page_ids])[:, off : off + (end - start)],
                            (self.k_pool, self.v_pool))

    def _one_pool(self, what: str) -> None:
        if self._spec is not None:
            raise NotImplementedError(
                f"{what} moves K/V slabs of one pool pair; this cache holds several pools and a "
                "per-slot state (the model's `unserved` refuses what would call it)"
            )

    def tables_device(self) -> Any:
        """The block tables as the programs take them: one [slots, M]
        array, or with a ``spec`` one a pool by name — the allocator's for a
        pool that keeps every position, a ring's (:meth:`_ring_spans`) for a
        window pool, the trash page wherever nothing may be addressed."""
        # .copy(): host→device transfers are async, and the engine's
        # pipelined dispatch mutates self.tables (extend_slot) while the
        # previous step's transfer may still be pending — upload a snapshot
        # the host never touches again
        if self._spec is None:
            return jnp.asarray(self.tables.copy())
        out = {}
        j = np.arange(self.max_pages_per_seq)[None, :]
        for name, *_ in self._spec[0]:
            if name in self._rings:
                lo, hi = self._ring_spans(name)
                live = (j >= lo[:, None]) & (j <= hi[:, None])
                out[name] = jnp.asarray(np.where(live, self._rings[name][2], self.pool_pages(name)).astype(np.int32))
            else:
                out[name] = jnp.asarray(self.tables.copy())
        return out

    def seq_lens_device(self) -> jnp.ndarray:
        return jnp.asarray(self.seq_lens.copy())

    def close(self) -> None:
        self.allocator.close()

    def leak(self) -> None:  # leakcheck: transfer(quarantine)
        """Quarantine-leak the native allocator (engine warm restart under
        a hung thread): the page pools are plain device arrays the GC can
        reclaim once the thread thaws, but the native handle must never be
        destroyed under a thread that may still be inside it."""
        self.allocator.leak()
